"""Device time of the step's ring write and window gather (ops under
the named scope ``ring_scatter``) per verdict step on the busiest device,
in ms: the union of their intervals in the traced window over the verdict
steps in it."""

from bench import stages
from bench import trace as T


def read(ctx):
    got = stages.of(ctx)
    if got is None:
        return None
    device, _ = T.busiest(ctx.trace, ctx.lo, ctx.hi)
    ns = stages.scope_ns(got["device_scopes"].get(device, []),
                         "ring_scatter", ctx.lo, ctx.hi)
    steps = stages.verdict_steps(ctx)
    if not ns or not steps:
        return None
    return ns / steps / 1e6
