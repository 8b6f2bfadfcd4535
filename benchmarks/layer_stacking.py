"""§5.2 / Fig. 4: inference CPU time vs number of stacked 64-neuron dense
layers — ICSML runtime (planned arena execution) vs the XLA baseline (plain
jnp forward, our TFLite stand-in).  The paper's claims: dot-product,
activation and total inference times scale LINEARLY with depth, and the
optimized baseline is a constant factor faster."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, linear_fit, time_fn
from repro.configs.icsml_mlp import BENCH_FEATURES
from repro.core import layers as L, sequential
from repro.launch.compile_cache import enable_compile_cache

DEPTHS = (1, 2, 4, 8, 16, 32)


def build(depth: int):
    layers = [L.Input()] + [
        L.Dense(units=BENCH_FEATURES, activation="relu") for _ in range(depth)
    ]
    m = sequential(layers, (BENCH_FEATURES,))
    return m, m.init_params(jax.random.PRNGKey(0))


def main(quick: bool = False):
    rows = []
    depths = DEPTHS[:4] if quick else DEPTHS
    # batched measurement: a modern CPU is dispatch-bound on a 64-wide MLP,
    # so per-sample cost is measured over a vmapped batch (the PLC regime is
    # compute-bound; batching recovers the compute-scaling signal)
    batch = 512
    xb = jax.random.normal(jax.random.PRNGKey(1), (batch, BENCH_FEATURES))

    icsml_t, base_t = [], []
    for depth in depths:
        m, p = build(depth)
        planned = jax.jit(jax.vmap(m.apply_planned, in_axes=(None, 0)))
        baseline = jax.jit(jax.vmap(m.apply, in_axes=(None, 0)))
        t_i = time_fn(lambda: planned(p, xb)) / batch
        t_b = time_fn(lambda: baseline(p, xb)) / batch
        icsml_t.append(t_i)
        base_t.append(t_b)
        rows.append({"name": f"layer_stacking/icsml/L{depth}", "us_per_call": t_i,
                     "derived": f"baseline_us={t_b:.3f}"})

    slope_i, _, r2_i = linear_fit(depths, icsml_t)
    slope_b, _, r2_b = linear_fit(depths, base_t)
    ratio = sum(i / b for i, b in zip(icsml_t, base_t)) / len(depths)
    rows.append({"name": "layer_stacking/us_per_layer_icsml",
                 "us_per_call": slope_i, "derived": f"R2={r2_i:.4f}"})
    rows.append({"name": "layer_stacking/us_per_layer_baseline",
                 "us_per_call": slope_b, "derived": f"R2={r2_b:.4f}"})
    rows.append({"name": "layer_stacking/icsml_vs_baseline_ratio",
                 "us_per_call": ratio,
                 "derived": "paper=29.38x_vs_TFLite"})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
