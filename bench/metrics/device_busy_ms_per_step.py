"""Device busy time per verdict step on the busiest device, in ms: the
union of its op intervals in the traced window over the verdict steps in
it."""

from bench import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    steps = sum(1 for s in ctx.trace["spans"] if s[0] == "ingest.verdict")
    _, busy = T.busiest(ctx.trace, ctx.lo, ctx.hi)
    if not steps or not busy:
        return None
    return busy / steps / 1e6
