"""Time waiting for the step's outputs (``serve.block``,
``block_until_ready``) per verdict step, in ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.block")
