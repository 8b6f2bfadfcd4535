"""§5.4: decomposing the ICSML-vs-optimized-framework gap.

The paper attributes its ~20-30x gap to TFLite as ≈2x profiler overhead x
≈4x missing compiler optimizations x ≈3x no optimized math libraries.  Our
analogue: the ICSML-faithful interpretation-style execution (arena reads/
writes per layer, unfused) vs progressively optimized variants:

  A. arena execution, jit disabled        (no compiler: the -O0 analogue)
  B. arena execution, jit                 (compiler on)
  C. reference execution, jit             (no arena copy discipline)
  D. batched vmap execution, jit          (library-grade vectorization)

Ratios A/B ≈ compiler factor, B/C ≈ memory-discipline overhead, C/D ≈
vectorized-library factor.
"""

from __future__ import annotations

import jax

from benchmarks.common import emit, time_fn
from repro.core import layers as L, sequential
from repro.launch.compile_cache import enable_compile_cache


def main(quick: bool = False):
    m = sequential([L.Input()] + [L.Dense(units=64, activation="relu")
                                  for _ in range(8)], (64,))
    p = m.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64,))
    xb = jax.random.normal(jax.random.PRNGKey(2), (64, 64))

    a = time_fn(lambda: m.apply_planned(p, x), warmup=1, iters=3)
    jit_planned = jax.jit(m.apply_planned)
    b = time_fn(lambda: jit_planned(p, x))
    jit_ref = jax.jit(m.apply)
    c = time_fn(lambda: jit_ref(p, x))
    batched = jax.jit(jax.vmap(m.apply, in_axes=(None, 0)))
    d = time_fn(lambda: batched(p, xb)) / 64.0   # per-sample

    rows = [
        {"name": "perf_gap/A_unjitted_arena", "us_per_call": a, "derived": ""},
        {"name": "perf_gap/B_jit_arena", "us_per_call": b,
         "derived": f"compiler_factor={a / b:.1f}x;paper~4x"},
        {"name": "perf_gap/C_jit_reference", "us_per_call": c,
         "derived": f"arena_overhead={b / c:.2f}x"},
        {"name": "perf_gap/D_jit_vmap_per_sample", "us_per_call": d,
         "derived": f"library_factor={c / d:.1f}x;paper~3x"},
        {"name": "perf_gap/total", "us_per_call": a / d,
         "derived": "paper_total~29x_vs_TFLite"},
    ]
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
