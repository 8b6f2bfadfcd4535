"""§5.1 + Table 2 + Fig. 3: memory accounting.

* Table 2 byte-exact reproduction for the 512x512 layer under
  SINT/INT/DINT/REAL (analytic, asserted).
* §5.1 linear relation between layer size and memory use.
* Fig. 3 style accounting: which PLCs could hold which Keras-size models,
  plus the dataMem arena-reuse saving our planner provides on top.
"""

from __future__ import annotations

from benchmarks.common import emit
from repro.core import layers as L, memory, quantize, sequential
from repro.launch.compile_cache import enable_compile_cache

PAPER_TABLE2 = {
    "SINT": 266_244, "INT": 528_388, "DINT": 1_052_676, "REAL": 1_050_624,
}

# (name, RAM bytes) — from paper Table 1 / Fig. 3
PLCS = [
    ("AB_Micro810", 2 * 1024),
    ("Mitsubishi_iQ-R", 4 * 1024 ** 2),
    ("Schneider_M241", 64 * 1024 ** 2),
    ("WAGO_PFC100", 256 * 1024 ** 2),
    ("WAGO_PFC200", 512 * 1024 ** 2),
]

# (model, parameter count) — Keras Applications (Fig. 3), 32-bit params
KERAS_MODELS = [
    ("MobileNetV2", 3_538_984),
    ("MobileNet", 4_253_864),
    ("EfficientNetB0", 5_330_571),
    ("DenseNet121", 8_062_504),
    ("ResNet50", 25_636_712),
    ("NASNetLarge", 88_949_818),
]


def main(quick: bool = False):
    rows = []

    # ---- Table 2 byte-exact ----
    for scheme, want in PAPER_TABLE2.items():
        got = quantize.memory_report(512, 512, scheme)["total"]
        assert got == want, (scheme, got, want)
        rows.append({"name": f"memory/table2/{scheme}_bytes",
                     "us_per_call": float(got),
                     "derived": f"paper={want};match={got == want}"})

    # ---- §5.1 linearity: layer memory vs size ----
    for width in (64, 128, 256, 512):
        m = sequential([L.Input(),
                        L.Dense(units=width, activation="relu")], (width,))
        plan = m.memory_plan()
        total = m.param_bytes() + plan.arena_bytes
        rows.append({"name": f"memory/layer_total_bytes/W{width}",
                     "us_per_call": float(total),
                     "derived": f"params={m.param_bytes()};arena={plan.arena_bytes}"})

    # ---- Fig. 3: which PLC fits which model (f32 vs SINT) ----
    for mname, n_params in KERAS_MODELS:
        f32 = n_params * 4
        sint = n_params * 1
        fits_f32 = sum(1 for _, ram in PLCS if f32 <= ram)
        fits_sint = sum(1 for _, ram in PLCS if sint <= ram)
        rows.append({"name": f"memory/fig3/{mname}",
                     "us_per_call": float(f32),
                     "derived": f"plcs_fitting_f32={fits_f32};sint={fits_sint}"})

    # ---- dataMem arena reuse (our planner on a deep model) ----
    deep = sequential([L.Input()] + [L.Dense(units=256, activation="relu")
                                     for _ in range(16)], (256,))
    ab = memory.activation_bytes(deep.graph, (256,))
    rows.append({"name": "memory/arena_reuse_saving",
                 "us_per_call": float(ab["naive"] - ab["planned"]),
                 "derived": f"naive={ab['naive']};planned={ab['planned']}"})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
