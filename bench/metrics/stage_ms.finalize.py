"""Self time of ``serve.finalize`` per verdict step, in ms: what the step's
harvest does outside its block, unpack, head and rows stages.  Where the
score heads adapt, that is their recalibration (the calibration rings and
counts copied to the host, and the quantile); elsewhere the loop around
the child stages."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.finalize", own=True)
