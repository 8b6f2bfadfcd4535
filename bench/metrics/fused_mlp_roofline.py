"""The single-model fused kernel's share of its roofline, in %: the least
time the chips could take for every verdict step's work in the traced
window (``bench.work.fused_mlp`` over each device's plants), over the
kernel's device time summed over the devices."""

from bench import trace as T
from bench import work


def read(ctx):
    if ctx.trace is None or len(ctx.config["groups"]) != 1:
        return None
    steps = sum(1 for s in ctx.trace["spans"] if s[0] == "ingest.verdict")
    n_dev = len(ctx.trace["devices"])
    kernel_ns = sum(e - s for ops in ctx.trace["devices"].values()
                    for _, s, e, _ in T.kernel_ops(T.clip_ops(
                        ops, ctx.lo, ctx.hi)))
    if not steps or not kernel_ns:
        return None
    ops, nbytes = work.fused_mlp(ctx.config, ctx.plants // n_dev)
    least = work.roofline_s(ops, nbytes, ctx.peaks, ctx.config["scheme"])
    return 100.0 * least * steps * n_dev / (kernel_ns / 1e9)
