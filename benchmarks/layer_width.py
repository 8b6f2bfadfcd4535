"""§5.3: inference time vs layer width (neurons doubled each step, 32-feature
input, single ReLU dense layer).  Paper: near-linear scaling in neurons."""

from __future__ import annotations

import jax

from benchmarks.common import emit, linear_fit, time_fn
from repro.core import layers as L, sequential
from repro.launch.compile_cache import enable_compile_cache

WIDTHS = (32, 64, 128, 256, 512, 1024)


def main(quick: bool = False):
    widths = WIDTHS[:4] if quick else WIDTHS
    rows, times = [], []
    batch = 512  # amortize dispatch: see layer_stacking
    xb = jax.random.normal(jax.random.PRNGKey(1), (batch, 32))
    for w in widths:
        m = sequential([L.Input(), L.Dense(units=w, activation="relu")], (32,))
        p = m.init_params(jax.random.PRNGKey(0))
        fn = jax.jit(jax.vmap(m.apply_planned, in_axes=(None, 0)))
        t = time_fn(lambda: fn(p, xb)) / batch
        times.append(t)
        rows.append({"name": f"layer_width/icsml/W{w}", "us_per_call": t,
                     "derived": ""})
    slope, _, r2 = linear_fit(widths, times)
    rows.append({"name": "layer_width/us_per_neuron", "us_per_call": slope,
                 "derived": f"R2={r2:.4f};paper_bbb=9.326us_per_neuron"})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
