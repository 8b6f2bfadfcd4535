"""Pallas TPU kernel: a whole Dense-stack MLP fused into ONE dispatch.

The paper's §6 domain-specific optimizations (loop unrolling, fused quantized
arithmetic) exist because per-layer dispatch overhead dominates small-MLP
inference on constrained hardware.  The TPU port had the same pathology: each
fleet verdict step issued one ``qmatmul``/matmul dispatch per Dense layer with
inter-layer HBM round-trips, for detector-sized networks whose weights fit in
a sliver of one VMEM tile.

This kernel executes **all** Dense layers in a single ``pallas_call``:

* every layer's weights/scales/biases are staged HBM→VMEM once,
* activations stay resident in VMEM between layers (no HBM round-trip),
* activation functions are applied in-kernel,
* quantized (SINT) layers run an **in-kernel requantize epilogue**: the f32
  activations out of layer *i* are re-quantized against layer *i+1*'s
  activation scale inside the kernel, so the int8 MXU path is used
  layer-to-layer without host-side ``x/x_scale`` re-quantization dispatches.

Layer kinds (mirroring ``layers._quantized_matvec`` / §6.1 semantics):

* f32 weights      -> f32 MXU dot + bias,
* int8 (SINT)      -> in-kernel quantize, int8×int8→int32 MXU dot, fused
                      rescale+bias dequant epilogue,
* int16/int32      -> in-kernel quantize with the integer grid's clip, dot
  (INT/DINT)          emulated in f32 (no int16/int32 MXU mode — DESIGN.md §2),
                      rescale+bias.

Every f32 dot runs at ``F32_DOT`` (``Precision.HIGHEST``): the MXU's default
f32 contraction is one bf16 pass, which keeps 8 significant bits of each
operand — REAL would no longer be f32, and the INT/DINT integer grid would
not survive the operand rounding.

Grid: ``(M/block_m, K0/block_k)`` — rows tile as before, and the **first
layer is K-gridded**: its input width (the detector's 400-wide window — the
widest dimension of both §7 workloads) streams through VMEM one
``(block_m, block_k)`` x-tile and ``(block_k, N1)`` weight slab at a time,
accumulating into a VMEM scratch (int32 for an int8 first layer — split-K
integer accumulation is exact — f32 otherwise).  The last K step runs the
dequant/bias/activation epilogue and every remaining layer back to back in
VMEM.  This lifts the old whole-net-in-VMEM restriction to a *widest-layer*
budget: the VMEM bill charges layer 0 one K-slab (not its full K extent)
plus every later layer in full, so wide-input stacks — and the autoencoder's
400-wide decoder output — fuse as long as each resident layer fits.

Padding contract (the ``ops.fused_forward`` wrapper maintains it): weights
are zero-padded, scales and biases zero-padded, so padded output lanes carry
``act(0)`` garbage that the *zero-padded rows* of the next layer's weights
annihilate — correctness never depends on masking inside the kernel.  K
padding of layer 0 is likewise zero x-lanes times zero weight rows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layers import ACTIVATIONS

# Full f32 contraction for every f32 dot in these kernels (see above).
F32_DOT = jax.lax.Precision.HIGHEST

# Softmax normalizes across the (padded) lane axis, so it cannot run on
# zero-padded tiles without masking; every other §4.1 activation is
# element-wise and pad-safe (garbage lanes are killed by the next layer's
# zero-padded weight rows).  The *grouped* megakernel additionally supports a
# FINAL-layer softmax by masking against the group's true output width in
# SMEM (the one place a softmax head can fuse).
FUSED_ACTIVATIONS = frozenset(ACTIVATIONS) - {"softmax"}

# Stable activation-id table for the grouped kernel's SMEM act selector
# (softmax included: it is legal at the final position, where the kernel
# masks pad lanes against the group's true output width).
GROUPED_ACT_IDS = {name: i for i, name in enumerate(sorted(ACTIVATIONS))}

# Grouped-payload kinds: what the in-kernel epilogue writes per group.
GROUPED_KIND_LOGITS = 0     # classifier: the final activations themselves
GROUPED_KIND_SCORE = 1      # score head: mean squared error vs the target

# VMEM is ~16 MB/core; the *resident set* — one K-slab of the first layer,
# every later layer in full, one activation tile per layer, the split-K
# scratch — must fit, since the whole point is never spilling to HBM between
# layers.  ops.can_fuse applies the same budget so auto-selection falls back
# to the per-layer path for oversized stacks instead of failing at dispatch.
VMEM_BUDGET_BYTES = 12 * 2**20

# Default K tile of the first layer: one 512-lane slab covers both detector
# workloads' padded 400-wide input in a single K step (nk=1 — bit-identical
# to un-split accumulation) while capping the resident slab for wider inputs.
DEFAULT_BLOCK_K = 512


class FusedLayer(NamedTuple):
    """One Dense layer, padded and ready for the fused kernel.

    ``w``: (Kp, Np) f32 weights, or int8/int16/int32 quantized weights.
    ``bias``: (1, Np) f32 (zeros when the layer has no bias).
    ``scale``: (1, Np) f32 combined x_scale * w_scale — quantized layers only.
    ``x_scale``: (1, 1) f32 activation scale — quantized layers only.
    ``act``: activation name from ``FUSED_ACTIVATIONS``.
    """

    w: jax.Array
    bias: jax.Array
    scale: Optional[jax.Array]
    x_scale: Optional[jax.Array]
    act: str

    @property
    def quantized(self) -> bool:
        return self.scale is not None


def _layer_mode(dtype) -> str:
    if dtype == jnp.float32:
        return "real"
    if dtype == jnp.int8:
        return "int8"
    if dtype in (jnp.int16, jnp.int32):
        return "emu"
    raise ValueError(f"unsupported fused-layer weight dtype {dtype}")


def fused_vmem_bytes(
    layer_shapes: Sequence[tuple],
    *,
    block_m: int = 128,
    block_k: Optional[int] = None,
) -> int:
    """The kernel's VMEM resident-set estimate for a padded stack.

    ``layer_shapes`` is ``[(K, N, itemsize), ...]``; layer 0 is charged one
    ``block_k`` K-slab (the K grid streams the rest), later layers their full
    extent, plus per-layer activation tiles, 8 B/lane of scale+bias, and the
    split-K accumulator scratch.  ``ops.can_fuse`` and :func:`fused_mlp`
    share this accounting so auto-selection and dispatch agree.
    """
    k0 = layer_shapes[0][0]
    bk = min(block_k or DEFAULT_BLOCK_K, k0)
    total = block_m * layer_shapes[0][1] * 4        # split-K scratch
    for i, (k, n, itemsize) in enumerate(layer_shapes):
        k_res = bk if i == 0 else k
        total += k_res * n * itemsize + 8 * n
        # Activation tiles: max(k_res, n) covers both the layer's input tile
        # (the x slab for layer 0) and its output tile at the 4 B f32 width.
        total += block_m * max(k_res, n) * 4
    return total


def _fused_kernel(*refs, modes: Sequence[str], acts: Sequence[str],
                  qmaxes: Sequence[int], nk: int):
    """One grid step: accumulate layer 0 over a K slab; on the last K step,
    run its epilogue and every remaining layer in VMEM."""
    x_ref, out_ref, acc_ref = refs[0], refs[-2], refs[-1]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # -- first layer: partial product over this (block_m, block_k) tile.
    idx = 1
    if modes[0] == "real":
        w0_ref, b0_ref = refs[idx], refs[idx + 1]
        idx += 2
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w0_ref[...], (((1,), (0,)), ((), ())),
            precision=F32_DOT, preferred_element_type=jnp.float32,
        )

        def _finish0(acc):
            return acc + b0_ref[...]
    else:
        xs0_ref, w0_ref, s0_ref, b0_ref = refs[idx:idx + 4]
        idx += 4
        # In-kernel (re)quantization is element-wise, so quantizing one K
        # slab at a time is identical to quantizing the whole row.
        hq = jnp.clip(jnp.round(x_ref[...] / xs0_ref[0, 0]),
                      -qmaxes[0], qmaxes[0])
        if modes[0] == "int8":
            # int32 scratch: split-K integer accumulation is exact, so the
            # K grid cannot perturb SINT numerics.
            acc_ref[...] += jax.lax.dot_general(
                hq.astype(jnp.int8), w0_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        else:
            acc_ref[...] += jax.lax.dot_general(
                hq, w0_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=F32_DOT,
            )

        def _finish0(acc):
            return acc.astype(jnp.float32) * s0_ref[...] + b0_ref[...]

    rest = refs[idx:-2]

    @pl.when(j == nk - 1)
    def _epilogue():
        h = ACTIVATIONS[acts[0]](_finish0(acc_ref[...]).astype(jnp.float32))
        i = 0
        for mode, act, qmax in zip(modes[1:], acts[1:], qmaxes[1:]):
            if mode == "real":
                w_ref, b_ref = rest[i], rest[i + 1]
                i += 2
                h = jax.lax.dot_general(
                    h, w_ref[...], (((1,), (0,)), ((), ())),
                    precision=F32_DOT, preferred_element_type=jnp.float32,
                ) + b_ref[...]
            else:
                xs_ref, w_ref, s_ref, b_ref = rest[i:i + 4]
                i += 4
                xs = xs_ref[0, 0]
                # In-kernel requantization: the §6.1 activation-quantization
                # step, fused so f32 activations never leave VMEM.
                hq = jnp.clip(jnp.round(h / xs), -qmax, qmax)
                if mode == "int8":
                    acc = jax.lax.dot_general(
                        hq.astype(jnp.int8), w_ref[...],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32,
                    ).astype(jnp.float32)
                else:
                    # INT/DINT: integer grid, f32 arithmetic (emulated — the
                    # MXU has no int16/int32 mode and int32 accumulation
                    # overflows).
                    acc = jax.lax.dot_general(
                        hq, w_ref[...].astype(jnp.float32),
                        (((1,), (0,)), ((), ())), precision=F32_DOT,
                    )
                # Fused dequant epilogue: REAL rescale + bias, still in VMEM.
                h = acc * s_ref[...] + b_ref[...]
            h = ACTIVATIONS[act](h)
        out_ref[...] = h


def fused_mlp(
    x: jax.Array,
    layers: Sequence[FusedLayer],
    *,
    block_m: int = 128,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Run a whole Dense stack as ONE Pallas dispatch.

    Args:
      x: (M, K0) f32 activations; M divisible by ``block_m``, K0 and every
        layer dim already padded to the 128-lane tile.
      layers: padded :class:`FusedLayer` specs; layer i's ``w.shape[0]`` must
        equal layer i-1's ``w.shape[1]`` (and ``x.shape[1]`` for layer 0).
      block_m: row tile.
      block_k: K tile of the *first* layer (default ``DEFAULT_BLOCK_K``,
        clamped to K0); K0 must divide by it.  One K step (nk=1) is
        bit-identical to the un-split kernel; more steps stream the first
        layer's weights through VMEM one slab at a time.
      interpret: run the kernel body in Python (CPU validation mode).

    Returns (M, N_last) f32 logits (padded lanes included — callers slice).
    """
    if not layers:
        raise ValueError("fused_mlp needs at least one layer")
    m, k0 = x.shape
    assert m % block_m == 0, (m, block_m)
    assert k0 % 128 == 0, x.shape
    block_k = min(block_k or DEFAULT_BLOCK_K, k0)
    assert block_k % 128 == 0, block_k
    assert k0 % block_k == 0, (k0, block_k)
    nk = k0 // block_k
    prev_n = k0
    shapes = []
    for i, layer in enumerate(layers):
        k, n = layer.w.shape
        assert k == prev_n, f"layer {i}: K {k} != previous width {prev_n}"
        assert k % 128 == 0 and n % 128 == 0, layer.w.shape
        assert layer.bias.shape == (1, n), layer.bias.shape
        if layer.quantized:
            assert layer.scale.shape == (1, n), layer.scale.shape
            assert layer.x_scale.shape == (1, 1), layer.x_scale.shape
        if layer.act not in FUSED_ACTIVATIONS:
            raise ValueError(
                f"activation {layer.act!r} is not fusable (padded lanes); "
                f"pick from {sorted(FUSED_ACTIVATIONS)}")
        shapes.append((k, n, layer.w.dtype.itemsize))
        prev_n = n
    vmem_bytes = fused_vmem_bytes(shapes, block_m=block_m, block_k=block_k)
    if vmem_bytes > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused stack needs ~{vmem_bytes} B of VMEM resident (> "
            f"{VMEM_BUDGET_BYTES}); the K grid already streams the first "
            "layer, so a later layer is too wide to keep in VMEM — fall "
            "back to the per-layer path")

    modes = tuple(_layer_mode(layer.w.dtype) for layer in layers)
    acts = tuple(layer.act for layer in layers)
    qmaxes = tuple(
        int(jnp.iinfo(layer.w.dtype).max) if layer.quantized else 0
        for layer in layers
    )

    n1 = layers[0].w.shape[1]
    acc_dtype = jnp.int32 if modes[0] == "int8" else jnp.float32

    operands = [x]
    in_specs = [pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))]
    for li, layer in enumerate(layers):
        k, n = layer.w.shape
        if layer.quantized:
            operands.append(layer.x_scale)
            in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                                         memory_space=pltpu.SMEM))
        operands.append(layer.w)
        if li == 0:
            # The only K-gridded operand: one (block_k, N1) slab per K step.
            in_specs.append(pl.BlockSpec((block_k, n), lambda i, j: (j, 0)))
        else:
            in_specs.append(pl.BlockSpec((k, n), lambda i, j: (0, 0)))
        if layer.quantized:
            operands.append(layer.scale)
            in_specs.append(pl.BlockSpec((1, n), lambda i, j: (0, 0)))
        operands.append(layer.bias)
        in_specs.append(pl.BlockSpec((1, n), lambda i, j: (0, 0)))

    n_last = layers[-1].w.shape[1]
    return pl.pallas_call(
        functools.partial(_fused_kernel, modes=modes, acts=acts,
                          qmaxes=qmaxes, nk=nk),
        grid=(m // block_m, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, n_last), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_last), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, n1), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_mlp",
    )(*operands)


# ---------------------------------------------------------------------------
# Grouped megakernel: a whole heterogeneous fleet in ONE dispatch
# ---------------------------------------------------------------------------
#
# The grouped-GEMM / MoE-expert-batching idea applied to the detector zoo:
# every group's (padded) weight/bias/scale slabs for layer position l live in
# one (G, K_l, N_l) arena, the grid spans (group, M-blocks), and per-group
# geometry is resolved by index maps plus small SMEM scalar tables — kind,
# true output width, activation id and skip flag per position, and each
# position's activation scale — passed whole and read at the group's row
# (``pl.program_id(0)``).  Groups
# shallower than the deepest stack "skip" their trailing positions: the SMEM
# flag passes activations through untouched, and the union width at those
# positions is kept at least as wide as every finished group's true output so
# nothing is truncated.  Pad lanes obey the same zero-row annihilation
# contract as the single-stack kernel; a group's garbage lanes beyond its
# true width are killed by ITS zero-padded next-layer rows because each group
# reads only its own arena slice.
#
# The epilogue also runs in-kernel, per group: classifiers write their final
# activations (with softmax masked to the true lane count — the one fused-
# scope gap the single-stack kernel cannot close), score heads write
# ``mean((h - tgt)^2)`` over true lanes into payload lane 0.


class GroupedLayer(NamedTuple):
    """One layer *position* of the packed fleet, arena layout.

    ``w``: (G, K, N) weights — one dtype per position (f32/int8/int16/int32).
    ``bias``: (G, 1, N) f32; ``scale``: (G, 1, N) f32 combined
    x_scale * w_scale (zeros on real/skip slots); ``x_scale``: (G, 1) f32
    activation scale (ones on real/skip slots — a 0 would round ``h/0`` into
    NaNs even though the zero weight slab annihilates the product).
    """

    w: jax.Array
    bias: jax.Array
    scale: jax.Array
    x_scale: jax.Array


def grouped_vmem_bytes(pos_shapes: Sequence[tuple], *,
                       block_m: int = 128, n_pay: int = 128) -> int:
    """VMEM resident-set estimate for the grouped megakernel.

    ``pos_shapes`` is ``[(K, N, itemsize), ...]`` — the *union* (widest-slab)
    arena geometry per layer position, padded.  Each position charges two
    arena slabs (the revolving group axis double-buffers the next group's
    slab while the current one computes), scale+bias lanes, and an activation
    tile; the x block, target block and payload block ride on top.  There is
    no K grid — the whole union input width is resident — so the budget is
    the honest whole-fleet bill.
    """
    total = block_m * pos_shapes[0][0] * 4            # x block
    total += 2 * block_m * n_pay * 4                  # target + payload
    for k, n, itemsize in pos_shapes:
        total += 2 * (k * n * itemsize + 8 * n)       # double-buffered slabs
        total += block_m * n * 4                      # activation tile
    return total


def _grouped_kernel(*refs, modes: Sequence[str], qmaxes: Sequence[int],
                    pos_acts: Sequence[Sequence[str]], n_layers: int):
    """One (group, M-block) grid step: the group's whole stack + epilogue.

    Ref order: meta (SMEM), x, then per position (x_scale SMEM, w, scale,
    bias), then tgt, out.  The SMEM tables arrive whole — ``meta`` (G, 2+2L)
    with rows ``[kind, n_out_true, act_id * L, skip * L]`` and each
    position's (G, 1) ``x_scale`` — and the kernel reads its group's row at
    ``pl.program_id(0)``: the TPU lowering accepts no (1, C) block over
    them.
    """
    meta_ref, x_ref = refs[0], refs[1]
    tgt_ref, out_ref = refs[-2], refs[-1]
    gi = pl.program_id(0)
    kind = meta_ref[gi, 0]
    n_out = meta_ref[gi, 1]
    h = x_ref[0]
    for l in range(n_layers):
        xs_ref, w_ref, s_ref, b_ref = refs[2 + 4 * l: 6 + 4 * l]
        w = w_ref[0]
        if modes[l] == "real":
            y = jax.lax.dot_general(
                h, w, (((1,), (0,)), ((), ())),
                precision=F32_DOT, preferred_element_type=jnp.float32,
            ) + b_ref[0]
        else:
            hq = jnp.clip(jnp.round(h / xs_ref[gi, 0]),
                          -qmaxes[l], qmaxes[l])
            if modes[l] == "int8":
                acc = jax.lax.dot_general(
                    hq.astype(jnp.int8), w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32,
                ).astype(jnp.float32)
            else:
                acc = jax.lax.dot_general(
                    hq, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    precision=F32_DOT,
                )
            y = acc * s_ref[0] + b_ref[0]
        # Per-group activation: select among the distinct activations used at
        # this position by the SMEM act id (statically unrolled — typically
        # one).  Softmax is masked to the group's true output width.
        act_id = meta_ref[gi, 2 + l]
        out_l = y
        for name in pos_acts[l]:
            if name == "softmax":
                lanes = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
                z = jnp.where(lanes < n_out, y, -jnp.inf)
                zmax = jnp.max(z, axis=1, keepdims=True)
                ez = jnp.exp(z - zmax)
                a = ez / jnp.sum(ez, axis=1, keepdims=True)
            else:
                a = ACTIVATIONS[name](y)
            if len(pos_acts[l]) == 1:
                out_l = a
            else:
                out_l = jnp.where(act_id == GROUPED_ACT_IDS[name], a, out_l)
        # Skip pass-through for groups shallower than this position: carry
        # the previous activations (their true payload sits in the leading
        # lanes; the union width never truncates it).
        skip = meta_ref[gi, 2 + n_layers + l]
        n_l = out_l.shape[1]
        prev = h
        if prev.shape[1] < n_l:
            prev = jnp.pad(prev, ((0, 0), (0, n_l - prev.shape[1])))
        elif prev.shape[1] > n_l:
            prev = prev[:, :n_l]
        h = jnp.where(skip == 1, prev, out_l)
    # In-kernel head epilogue: logits pass through, score heads reduce to a
    # masked mean-squared-error against the (full-width) target block in
    # payload lane 0.  The payload block is narrower than the target block —
    # pad128(max payload width) vs the last position's union width.
    n_pay = out_ref.shape[2]
    tgt = tgt_ref[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    d = jnp.where(lanes < n_out, h - tgt, 0.0)
    score = jnp.sum(d * d, axis=1, keepdims=True) / n_out.astype(jnp.float32)
    # A payload-wide iota of its own: slicing ``lanes`` down to n_pay aborts
    # the TPU compiler (jaxlib 0.9) when n_pay < the last union width.
    pay_lanes = jax.lax.broadcasted_iota(jnp.int32, (h.shape[0], n_pay), 1)
    pay_score = jnp.where(pay_lanes == 0, score, 0.0)
    out_ref[0] = jnp.where(kind == GROUPED_KIND_LOGITS,
                           h[:, :n_pay], pay_score)


def grouped_fused_mlp(
    x: jax.Array,
    layers: Sequence[GroupedLayer],
    meta: jax.Array,
    tgt: jax.Array,
    *,
    n_pay: int,
    modes: Sequence[str],
    qmaxes: Sequence[int],
    pos_acts: Sequence[Sequence[str]],
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Run a whole heterogeneous fleet as ONE Pallas dispatch.

    Args:
      x: (G, M, K0) f32 — every group's (padded) input windows; M divisible
        by ``block_m``, K0 and all arena dims padded to the 128-lane tile.
      layers: :class:`GroupedLayer` arenas per position; position l's
        ``w.shape[1]`` feeds position l+1's ``w.shape[2]``.
      meta: (G, 2 + 2L) int32 table —
        ``[kind, n_out_true, act_id x L, skip x L]`` per group.  It and
        every position's (G, 1) ``x_scale`` go to SMEM whole (the TPU
        lowering accepts no ``(1, C)`` block over them); the kernel reads
        row ``pl.program_id(0)``.
      tgt: (G, M, N_last) f32 epilogue targets at the last position's union
        width (window / tail / center rows; zeros for classifiers).
      n_pay: payload lane count (128-padded max over groups: a classifier's
        true output width, 1 for score heads); at most ``N_last``.
      modes/qmaxes/pos_acts: static per-position dtype mode, quantization
        clip rail and the distinct activation names used at that position.

    Returns (G, M, n_pay) f32 payloads: final activations for
    ``GROUPED_KIND_LOGITS`` groups (softmax masked to true lanes), masked
    MSE-vs-target in lane 0 for ``GROUPED_KIND_SCORE`` groups.
    """
    if not layers:
        raise ValueError("grouped_fused_mlp needs at least one position")
    g, m, k0 = x.shape
    n_layers = len(layers)
    assert m % block_m == 0, (m, block_m)
    assert k0 % 128 == 0, x.shape
    assert meta.shape == (g, 2 + 2 * n_layers), meta.shape
    n_last = layers[-1].w.shape[2]
    assert tgt.shape == (g, m, n_last), (tgt.shape, n_last)
    assert n_pay % 128 == 0 and n_pay <= n_last, (n_pay, n_last)
    prev_n = k0
    shapes = []
    for l, layer in enumerate(layers):
        gw, k, n = layer.w.shape
        assert gw == g and k == prev_n, (l, layer.w.shape, prev_n)
        assert k % 128 == 0 and n % 128 == 0, layer.w.shape
        assert layer.bias.shape == (g, 1, n), layer.bias.shape
        assert layer.scale.shape == (g, 1, n), layer.scale.shape
        assert layer.x_scale.shape == (g, 1), layer.x_scale.shape
        shapes.append((k, n, layer.w.dtype.itemsize))
        prev_n = n
    vmem = grouped_vmem_bytes(shapes, block_m=block_m, n_pay=n_pay)
    if vmem > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"grouped arena needs ~{vmem} B of VMEM resident (> "
            f"{VMEM_BUDGET_BYTES}); fall back to per-group dispatch")

    # The scalar tables ride whole in SMEM (no block shape); the kernel
    # indexes its group's row by program id.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    operands = [meta, x]
    in_specs = [
        smem,
        pl.BlockSpec((1, block_m, k0), lambda gi, i: (gi, i, 0)),
    ]
    for layer in layers:
        _, k, n = layer.w.shape
        operands += [layer.x_scale, layer.w, layer.scale, layer.bias]
        in_specs += [
            smem,
            pl.BlockSpec((1, k, n), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda gi, i: (gi, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda gi, i: (gi, 0, 0)),
        ]
    operands.append(tgt)
    in_specs.append(pl.BlockSpec((1, block_m, n_last),
                                 lambda gi, i: (gi, i, 0)))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, modes=tuple(modes),
                          qmaxes=tuple(qmaxes),
                          pos_acts=tuple(tuple(a) for a in pos_acts),
                          n_layers=n_layers),
        grid=(g, m // block_m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_m, n_pay),
                               lambda gi, i: (gi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, m, n_pay), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel"),
        ),
        interpret=interpret,
        name="grouped_fused_mlp",
    )(*operands)
