"""§6.3: multipart inference — per-cycle cost vs number of segments.

The paper runs a MobileNet-style model on a 90 ms scan cycle with 1.17 s
output latency.  We measure (a) the §7 detector and (b) a small conv model
(Conv2D + BatchNorm/ReLU + DepthwiseConv blocks, the paper's multipart demo
family): per-segment wall time must be ≈ total/segments, and output latency
= segments x cycle."""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import layers as L, runtime, sequential
from repro.sim.detector import build_detector
from repro.launch.compile_cache import enable_compile_cache

SEGMENTS = (1, 2, 4, 8)


def mobilenet_ish():
    layers = [L.Input(features=(16, 16, 3))]
    ch = 8
    for i in range(3):
        layers += [
            L.Conv2D(filters=ch, kernel_size=(3, 3), strides=(2, 2)),
            L.BatchNorm(activation="relu"),
            L.DepthwiseConv2D(kernel_size=(3, 3)),
            L.BatchNorm(activation="relu"),
        ]
        ch *= 2
    layers += [L.GlobalAvgPool(), L.Dense(units=10, activation="softmax")]
    return sequential(layers, (16, 16, 3))


def main(quick: bool = False):
    rows = []
    for tag, model, x_shape in (
        ("detector", build_detector(), (400,)),
        ("conv", mobilenet_ish(), (16, 16, 3)),
    ):
        params = model.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), x_shape)
        segs = SEGMENTS[:3] if quick else SEGMENTS
        full = None
        for n in segs:
            mi = runtime.MultipartInference(model, params, n)

            def one_pass():
                state = mi.start(x)
                while not state.finished(mi.n_segments):
                    state = mi.step(state)
                return mi.output(state)

            t_total = time_fn(one_pass, warmup=1, iters=5)
            per_cycle = t_total / mi.n_segments
            if full is None:
                full = t_total
            rows.append({
                "name": f"multipart/{tag}/segments{n}",
                "us_per_call": per_cycle,
                "derived": (f"total_us={t_total:.1f};"
                            f"latency_cycles={mi.n_segments};"
                            f"seg_flops={mi.segment_flops()}")})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
