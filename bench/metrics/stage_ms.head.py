"""Time in the heads' host epilogues (``serve.head``,
``host_verdicts``), summed over the ready units, per verdict step, in
ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.head")
