"""Time in the jitted step call (``serve.dispatch``, until it returns)
per verdict step, in ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.dispatch")
