"""Pallas TPU kernel: Mamba-2 SSD (state-space duality) chunked scan.

The mamba2-370m assigned architecture is attention-free; its hot loop is the
selective-state-space recurrence

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * (x_t ⊗ B_t)        (state update)
    y_t = C_t · S_t                                              (readout)

[arXiv:2405.21060].  The SSD formulation evaluates it chunk-parallel: within a
chunk of L steps the output is a masked (decay-weighted) L×L matmul — MXU
work — and only a compressed (P×N) state crosses chunk boundaries.

TPU mapping: grid = (heads, chunks) with heads parallel and chunks sequential
('arbitrary'); the running state lives in a VMEM scratch that persists across
the sequential chunk dimension, so the recurrence never round-trips to HBM.
All per-chunk math is 2-D matmuls (L×N @ N×P, L×L @ L×P, P×L @ L×N) aligned
to the MXU.  ICSML applicability (DESIGN.md §4): the in/out projections around
this kernel are int8-quantized via qmatmul; the scan itself stays f32 exactly
like the paper keeps scales/biases REAL — state accumulation needs precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(
    x_ref,      # (L, 1, P) f32 — inputs for this (chunk, head)
    dt_ref,     # (L, 1) f32 — positive step sizes
    a_ref,      # (1, 1) f32 — negative decay rate A_h
    b_ref,      # (L, 1, N) f32
    c_ref,      # (L, 1, N) f32
    y_ref,      # (L, 1, P) f32 out
    state_ref,  # (P, N) f32 VMEM scratch — carried across chunks
):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[:, 0, :]          # (L, P)
    dt = dt_ref[...]            # (L, 1)
    a = a_ref[0, 0]             # ()
    b = b_ref[:, 0, :]          # (L, N)
    c = c_ref[:, 0, :]          # (L, N)

    alpha = dt * a                              # (L, 1) log-decay per step
    s = jnp.cumsum(alpha, axis=0)               # (L, 1) cumulative log-decay
    s_total = s[-1, 0]                          # ()

    # Inter-chunk: prior state read out through the decayed C.
    #   y_inter[t] = exp(s_t) * C_t @ S_prev^T          (L,N)@(N,P)
    y_inter = jnp.exp(s) * jnp.dot(
        c, state_ref[...].T, preferred_element_type=jnp.float32
    )

    # Intra-chunk: masked decay-weighted attention-like matmul.
    #   M[t,τ] = exp(s_t - s_τ) for τ <= t else 0
    mask = jnp.tril(jnp.ones((s.shape[0], s.shape[0]), bool))
    decay = jnp.exp(jnp.where(mask, s - s[:, 0][None, :], -jnp.inf))  # (L, L)
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (L, L)
    y_intra = jnp.dot(
        decay * cb * dt[:, 0][None, :], x, preferred_element_type=jnp.float32
    )

    y_ref[:, 0, :] = y_inter + y_intra

    # State update: decay old state, add decayed chunk contributions.
    #   S_new = exp(s_L) S + Σ_τ exp(s_L - s_τ) dt_τ x_τ ⊗ B_τ   (P,L)@(L,N)
    w = jnp.exp(s_total - s) * dt                       # (L, 1)
    state_ref[...] = jnp.exp(s_total) * state_ref[...] + jnp.dot(
        (x * w).T, b, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Chunked SSD scan over one sequence.

    Args:
      x:  (T, H, P) f32 inputs (post in-projection, per-head channels).
      dt: (T, H) f32 positive step sizes (softplus already applied).
      a:  (H,) f32 negative decay rates.
      b:  (T, H, N) f32 input-projection states (already broadcast to heads).
      c:  (T, H, N) f32 output-projection states.
      chunk: SSD chunk length L (sequence must divide; wrapper pads).

    Returns:
      y: (T, H, P) f32.
    """
    t, h, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, f"T={t} must be a multiple of chunk={chunk}"
    assert dt.shape == (t, h) and a.shape == (h,)
    assert b.shape == (t, h, n) and c.shape == (t, h, n)

    grid = (h, t // chunk)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, 1, p), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((chunk, 1), lambda hh, cc: (cc, hh)),
            pl.BlockSpec((1, 1), lambda hh, cc: (0, hh)),
            pl.BlockSpec((chunk, 1, n), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((chunk, 1, n), lambda hh, cc: (cc, hh, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, 1, p), lambda hh, cc: (cc, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((t, h, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt.reshape(t, h), a.reshape(1, h), b, c)
