"""Verdicts returned in the window over the window's elapsed seconds."""


def read(ctx):
    return ctx.window.windows / ctx.window.seconds
