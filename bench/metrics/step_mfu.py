"""The whole service's share of the chips' peak, in %: model operations per
verdict times verdicts returned in the traced window, over the window's
seconds, the chips and the peak of the scheme's matrix products."""

from bench import work


def read(ctx):
    if ctx.trace is None or not ctx.window.windows:
        return None
    ops = work.model_ops_per_window(ctx.config) * ctx.window.windows
    peak = work.peak_ops(ctx.peaks, ctx.config["scheme"])
    return 100.0 * ops / (ctx.window.seconds * ctx.chips * peak)
