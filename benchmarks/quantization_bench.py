"""§6.1 / Fig. 5: dense-layer (512 in / 512 out, ReLU) inference latency under
SINT/INT/DINT/REAL quantization, split into dot-product / activation / other —
plus the analytic op-count decomposition the paper derives.

Paper findings to reproduce directionally: quantization cuts the dot-product
portion (SINT −59.71 %, INT −56.52 %, DINT −37.23 % total latency on the
WAGO); activation time unaffected; dequantization negligible.  On CPU/XLA the
int8 path's advantage is smaller (no MXU), so we report the measured ratios
alongside the §6.1 op counts and the Pallas-kernel grid economics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import layers as L, quantize, sequential
from repro.configs.icsml_mlp import QUANT_LAYER
from repro.launch.compile_cache import enable_compile_cache


def main(quick: bool = False):
    rows = []
    n_in, n_out = QUANT_LAYER
    m = sequential([L.Input(),
                    L.Dense(units=n_out, activation="relu")], (n_in,))
    p = m.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (n_in,)) * 0.5

    fn_real = jax.jit(m.apply)
    t_real = time_fn(lambda: fn_real(p, x))
    rows.append({"name": "quantization/REAL_total", "us_per_call": t_real,
                 "derived": "baseline"})

    for scheme in ("SINT", "INT", "DINT"):
        qp = quantize.quantize_params(m, p, scheme, calibration=[x])
        fn_q = jax.jit(m.apply)
        t_q = time_fn(lambda: fn_q(qp, x))
        delta = (1 - t_q / t_real) * 100
        paper = {"SINT": 59.71, "INT": 56.52, "DINT": 37.23}[scheme]
        rows.append({"name": f"quantization/{scheme}_total",
                     "us_per_call": t_q,
                     "derived": f"latency_delta_pct={delta:.1f};paper_pct={paper}"})
        # numerical error vs REAL
        err = float(jnp.abs(m.apply(qp, x) - m.apply(p, x)).max())
        rows.append({"name": f"quantization/{scheme}_abs_err",
                     "us_per_call": err * 1e6,  # report in micro-units
                     "derived": "max_abs_err_x1e6"})

    # analytic op decomposition (§6.1) — asserted in tests, reported here
    for quantized, tag in ((False, "REAL"), (True, "SINT")):
        c = quantize.op_counts(n_in, n_out, quantized=quantized)
        rows.append({"name": f"quantization/op_counts/{tag}",
                     "us_per_call": float(c["int_mul"] + c["float_mul"]),
                     "derived": (f"fmul={c['float_mul']};fadd={c['float_add']};"
                                 f"imul={c['int_mul']};iadd={c['int_add']}")})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
