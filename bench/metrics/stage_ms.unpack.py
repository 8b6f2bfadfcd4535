"""Time copying the step's outputs to the host (``serve.unpack``) per
verdict step, in ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.unpack")
