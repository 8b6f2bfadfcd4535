"""Share of the traced window in which no op ran on the busiest device,
in %."""

from bench import trace as T


def read(ctx):
    if ctx.trace is None or ctx.hi <= ctx.lo:
        return None
    _, busy = T.busiest(ctx.trace, ctx.lo, ctx.hi)
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (ctx.hi - ctx.lo))
