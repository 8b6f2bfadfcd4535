"""Mean time of the normalization of one scan cycle's readings
(``serve.normalize``, once per ``ingest()`` call), in us."""

from bench import stages


def read(ctx):
    got = stages.of(ctx)
    if got is None:
        return None
    name = "serve.normalize"
    n = stages.count(got["stages"], ctx.lo, ctx.hi).get(name)
    if not n:
        return None
    return stages.total_ns(got["stages"], ctx.lo, ctx.hi)[name] / n / 1e3
