"""Host part of a verdict step, in ms: the mean traced ``ingest()`` span of
a verdict cycle minus the device busy time per step."""

from bench import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    spans = [e - s for n, s, e in ctx.trace["spans"] if n == "ingest.verdict"]
    _, busy = T.busiest(ctx.trace, ctx.lo, ctx.hi)
    if not spans or not busy:
        return None
    return (sum(spans) - busy) / len(spans) / 1e6
