"""Time building one ``Verdict`` per plant (``serve.rows``), summed over
the ready units, per verdict step, in ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.rows")
