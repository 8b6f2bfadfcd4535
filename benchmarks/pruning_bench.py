"""§6.2: pruning + operation skipping, TPU-adapted.

Paper experiment (784-in/512-out dense layer, WAGO): zeroed weights don't
speed up dense dot products (no runtime skipping), per-element IF-skip only
pays under quantization.  TPU adaptation: block-granular skipping — the
Pallas block-sparse kernel's grid shrinks with sparsity, so work drops
structurally.  We measure the XLA dense matvec vs the block-skip path at
several sparsities and report the kernel-grid economics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.configs.icsml_mlp import PRUNE_LAYER
from repro.core import prune
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache

SPARSITIES = (0.0, 0.25, 0.5, 0.75)


def main(quick: bool = False):
    rows = []
    n_in, n_out = PRUNE_LAYER          # 784 x 512
    n_in_pad = 896                     # pad 784 -> 7 blocks of 128
    w = jax.random.normal(jax.random.PRNGKey(0), (n_in_pad, n_out))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, n_in_pad))

    dense = jax.jit(lambda x, w: x @ w)
    t_dense = time_fn(lambda: dense(x, w))
    rows.append({"name": "pruning/dense_matmul", "us_per_call": t_dense,
                 "derived": "paper_wago=52.13ms_dense"})

    # zeroed weights, still dense: no automatic skipping (paper: 47.62ms)
    wz = jnp.zeros_like(w)
    t_zero = time_fn(lambda: dense(x, wz))
    rows.append({"name": "pruning/dense_all_zero", "us_per_call": t_zero,
                 "derived": f"speedup={t_dense / max(t_zero, 1e-9):.2f}x;"
                            "paper=no_auto_skip"})

    for s in SPARSITIES:
        wp = prune.block_magnitude_prune(w, s, (128, 128))
        bs = prune.compress_blocks(wp, (128, 128))
        sparse = jax.jit(lambda x: ops.sparse_dense(x, bs, backend="ref"))
        t_s = time_fn(lambda: sparse(x))
        total_blocks = (n_in_pad // 128) * (n_out // 128)
        rows.append({
            "name": f"pruning/block_skip/s{int(s * 100)}",
            "us_per_call": t_s,
            "derived": (f"nnz_blocks={bs.nnz_blocks}/{total_blocks};"
                        f"flop_frac={bs.nnz_blocks / total_blocks:.2f}")})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
