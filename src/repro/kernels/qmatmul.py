"""Pallas TPU kernel: integer-quantized matmul with fused dequantization.

This is the compute hot-spot of the paper's §6.1 quantization: the integer
dot product (N*M int mult + N*M int add) followed by the REAL rescale
(M float mult) and bias add (M float add).  On the PLC the win comes from
integer ALU ops being cheaper than float; on TPU the win is structural — the
MXU executes int8×int8→int32 at twice the bf16 rate (≈394 TOP/s vs 197 TF/s
on v5e) and the weights move over HBM at 1/4 the bytes of f32.

TPU adaptation (DESIGN.md §2): the per-element arithmetic of the ST loop is
re-tiled for the memory hierarchy — HBM→VMEM block staging via BlockSpecs,
128×128-aligned tiles for the MXU systolic array, int32 accumulation in a VMEM
scratch across the K grid dimension, and the dequant epilogue fused into the
final K step so the int32 accumulator never round-trips to HBM.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _qmatmul_kernel(
    x_ref,        # (bm, bk) int8/int16 — quantized activations
    w_ref,        # (bk, bn) int8/int16 — quantized weights
    scale_ref,    # (1, bn) f32 — combined x_scale * w_scale (per channel)
    bias_ref,     # (1, bn) f32
    out_ref,      # (bm, bn) f32
    acc_ref,      # (bm, bn) int32 VMEM scratch
    *,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Integer dot product on the MXU with a wide accumulator.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # Fused dequantization: REAL rescale + bias (the paper's M float
        # mults + M float adds) applied once, in VMEM.
        out_ref[...] = (
            acc_ref[...].astype(jnp.float32) * scale_ref[...] + bias_ref[...]
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def qmatmul(
    xq: jax.Array,
    wq: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Quantized matmul: ``out = (xq @ wq) * scale + bias`` in f32.

    Args:
      xq: (M, K) integer activations.
      wq: (K, N) integer weights.
      scale: () or (N,) f32 combined scale (x_scale * w_scale).
      bias: optional (N,) f32.
      block_*: VMEM tile sizes; MXU-aligned multiples of 128 on real TPUs.
      interpret: run the kernel body in Python (CPU validation mode).
    """
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2, (xq.shape, wq.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shape {(m, k, n)} not divisible by blocks {(block_m, block_k, block_n)}"
    )
    scale2d = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (n,))[None, :]
    bias2d = (
        jnp.zeros((1, n), jnp.float32)
        if bias is None
        else jnp.asarray(bias, jnp.float32)[None, :]
    )
    k_steps = k // block_k
    grid = (m // block_m, n // block_n, k_steps)

    return pl.pallas_call(
        functools.partial(_qmatmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xq, wq, scale2d, bias2d)
