"""Mean time of an ``ingest()`` call on a cycle without a verdict, in us,
on the harness's clock."""


def read(ctx):
    s = ctx.window.nonverdict_s
    return sum(s) / len(s) * 1e6 if s else None
