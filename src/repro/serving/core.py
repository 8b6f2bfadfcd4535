"""The shared fleet-serving core behind ``StreamEngine`` and
``GroupedStreamEngine``.

Both public engines used to carry a private copy of the same pipeline —
ring-arena geometry (pending trim, span/``eff_pos`` write-position math,
wraparound scatter), the pad-stream contract, device placement, warmup
schedules, serve accounting and the adapt-recalibration host loop — ~400
mirrored lines that had to be fixed twice per bug.  :class:`ServingCore`
is now the single owner; the engines are thin façades that translate
their constructor vocabulary (one model vs a list of
:class:`~repro.serving.grouped.ModelGroup`) into :class:`ServingUnit`
specs and inherit everything else.

**The unit model.**  A serving core drives a list of *units*: contiguous
stream-axis slices, each with its own model, detector head, window
geometry, quantization scales, fused/per-layer step flavor and optional
drift adaptation.  ``StreamEngine`` is the one-unit special case (its
unit is anonymous, so verdicts keep ``group=None``); ``GroupedStreamEngine``
is the N-unit case with named groups.  Per verdict cadence the core runs
ONE jitted, donated step over the tuple of ready units' ring arenas —
each distinct ready-combination ``((unit, block_len), ...)`` compiles
once and steady state reuses a single executable.

**Megakernel (single-dispatch multi-group steps).**  When every unit's
stack packs (``ops.grouped_fuse_reason``: all-Dense, one MXU mode per
layer position, packed-arena VMEM in budget) and every head exposes an
in-kernel epilogue (``DetectorHead.kernel_epilogue``), a multi-unit ready
step lowers to exactly ONE dispatch: the co-firing units' rings are
stacked, scattered and windowed batched over a leading group axis, and
``ops.grouped_apply`` runs the whole fleet — per-group quantization,
activations (a final-layer softmax masked to each group's true class
count) and head epilogues included — as one grouped Pallas call.
Compiled mega steps are keyed on the *block shape* (the hashable
``GroupedPlan`` + serving geometry), not the ready subset, so the
per-ready-combination step-cache explosion collapses to one compiled
step per shape.  ``megakernel=None`` auto-enables on the unsharded
path; ``False`` pins the per-group path; ``True`` forces it (sharded
included) and raises with the packing reason when the fleet cannot
lower.  Sharded fleets stay per-group by default: the megakernel's
sharded step bit-matches the canonical *unsharded* math, but the
per-group sharded graph it would replace rounds 1 ulp differently
(XLA fusion context), so the default would perturb REAL verdicts
bitwise — opt in with ``megakernel=True``.  Ready subsets whose
geometry cannot stack (mixed window or padded-stream extents) fall
back to the per-group step for that boundary only.  Verdicts
bit-match (REAL) / epsilon-match (quantized) the per-group path —
the oracle route is the identical op sequence.

**Async double-buffering (``async_depth=1``).**  Synchronous serving
blocks the host on every verdict step: dispatch, ``block_until_ready``,
build verdicts, repeat — so host ingest and device compute take turns
and the wall is their *sum*.  With ``async_depth=1`` the core pipelines
them: ``ingest()`` at a ready boundary first **harvests** the previous
step's in-flight outputs (they have had a whole inter-boundary interval
to finish), then **dispatches** the new step and returns immediately —
device compute for step N overlaps the host-side ingest of the cycles
feeding step N+1.  Consequences, all deliberate:

* Verdicts are delivered one ready boundary late, but are **bit-identical**
  to synchronous mode (same executables, same operands — the harvest
  happens before the next dispatch, so adapt-threshold recalibration sees
  exactly the state ordering of the sync loop).  ``Verdict.cycle`` still
  names the boundary the window completed at.
* ``flush()`` drains the last in-flight step (a no-op returning ``[]``
  in sync mode).  ``run()`` does NOT auto-flush — streaming may continue.
* ``latency_s``/``deadline_miss`` are redefined as **dispatch→harvest**
  time: the window completes at dispatch, the verdict exists on host at
  harvest, and everything between (including the overlapped host ingest)
  is genuine verdict-visibility delay.  ``stats.steps`` counts at
  dispatch; ``windows``/``deadline_misses``/``latencies_s`` count at
  harvest.
* ``stats.wall_s`` still accumulates host time spent inside
  ``ingest()``/``flush()`` only — the overlapped device time is exactly
  what it no longer contains, which is the point: ``windows_per_s()``
  measures *sustained host throughput under continuous arrival*.

**2-D ``("data", "model")`` mesh.**  Stream-axis data sharding composes
with model-axis weight sharding (``launch.mesh.make_fleet_mesh(...,
model_shards=m)``): wide Dense layers (output width >=
``MODEL_SHARD_MIN_WIDTH``) are column-sharded over the model axis —
every model rank computes its own column slice of the layer (weights,
bias and per-channel quantization scales sliced by ``axis_index``) and
one tiled ``all_gather`` recombines the activations, mesh-transformer-jax
``TransformerLayerShard`` style (but gathered, not ``psum``-paired, so
each output column is the SAME full-K dot product as the unsharded oracle
and REAL parity stays bit-exact).  Narrow layers stay replicated — a
collective per 2-wide layer would cost more than it shards — so the §7
detector runs exactly ONE collective per step.  Ring arenas, pending
blocks and outputs keep their ``P("data", ...)`` shardings (replicated
over the model axis).  On this host-emulation target the sliced weights
are compile-time constants on every rank (each rank *computes* 1/m of
the wide layers; weight *storage* sharding is part of the ROADMAP TPU
validation pass).  The fused whole-MLP kernel cannot span the gather, so
``fused=None`` auto-resolves to the per-layer path under a model-sharded
mesh and ``fused=True`` raises.

**Tracing.**  ``ingest()`` writes host spans with
``jax.profiler.TraceAnnotation``, on the profiler's clock beside the
device's ops: ``serve.ingest`` (carrying ``cycle``, the scan cycle's
index) around each call, and inside it ``serve.normalize``,
``serve.operands``, ``serve.dispatch`` (carrying ``h2d_bytes``, the host
operands the step hands the device) and ``serve.finalize`` (carrying the
``cycle`` of the step it finalizes, so a step's spans share one
identifier in sync and async mode), which holds ``serve.block``,
``serve.unpack`` (carrying ``d2h_bytes``, the outputs copied back) and,
per unit, ``serve.head`` and ``serve.rows``; ``serve.finalize`` also
carries ``recalibrations`` and ``calib_d2h_bytes``, the adapting units it
recalibrates and the bytes of calibration state they read.  A step has a
fixed number of spans, none per plant; with no trace active each costs
well under a microsecond.  On the device, each step's ring write and
window gather run under the named scope ``ring_scatter``.
``StreamStats.h2d_bytes`` / ``d2h_bytes`` / ``recalibrations`` /
``calib_d2h_bytes`` sum the same counts.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import msf_detector as spec
from repro.core.layers import ACTIVATIONS, Dense, Input
from repro.core.model import Model, ParamTree
from repro.kernels import ops
from repro.launch.mesh import make_fleet_mesh
from repro.sim.heads import (ClassifierHead, DetectorHead, ForecastHead,
                             ScoreHead)

# Host spans on the profiler's clock (a no-op unless a trace is active).
_span = jax.profiler.TraceAnnotation
# Host bytes of a per-group step's scalar operands: the int32 write
# position and the float32 threshold.
_SCALAR_OPERAND_BYTES = 4 + 4

# Column-shard a Dense layer over the mesh's "model" axis only when its
# output is at least this wide: below it the all_gather costs more than the
# sharded columns save (the detector's 2-wide logit layer is the extreme
# case), and the recombination stops being "minimal-collective".
MODEL_SHARD_MIN_WIDTH = 64


@dataclasses.dataclass(slots=True)
class Verdict:
    """One per-stream verdict on a completed window.

    The payload depends on the engine's :class:`~repro.sim.heads.DetectorHead`:
    a classifier head fills ``pred``/``prob`` (argmax class + its softmax
    probability, ``score``/``threshold`` None); a reconstruction head fills
    ``pred``/``score``/``threshold`` (pred = score over threshold, ``prob``
    None).  ``pred != 0`` always means "anomalous".
    """

    stream: int               # stream index in the fleet
    cycle: int                # scan cycle at which the window completed
    pred: int                 # verdict class (0 = normal)
    prob: Optional[float]     # classifier: softmax prob of the predicted class
    latency_s: float          # window-completion -> verdict-on-host wall time
                              # (async: dispatch -> harvest)
    deadline_miss: bool       # latency_s > deadline_s
    score: Optional[float] = None       # score heads: anomaly score
    threshold: Optional[float] = None   # score heads: calibrated cutoff
    group: Optional[str] = None         # model-group name (grouped fleets)


# Default reservoir seeds come from a process-global counter, so every
# engine's reservoir draws a distinct replacement sequence: with a shared
# fixed seed, split engines (the grouped-vs-split bench) replaced the SAME
# retained indices in lockstep, correlating their percentile estimates.
_reservoir_seeds = itertools.count()


class LatencyReservoir:
    """Bounded uniform sample of verdict latencies (Vitter's Algorithm R).

    A long-lived fleet engine emits one latency per verdict step forever; an
    unbounded list leaks O(steps) host memory at millions of cycles.  The
    reservoir retains the first ``capacity`` samples verbatim (append order
    preserved, so short runs — tests, bench passes — see an exact list) and
    thereafter replaces a uniformly random retained sample with probability
    ``capacity / seen``, keeping the retained set a uniform draw from the
    whole history — percentile estimates stay statistically valid while
    memory stays O(capacity).

    List-like where it matters: ``len`` / truthiness / iteration / indexing
    and slicing cover every pre-reservoir consumer.  Slicing is only
    meaningful while the retained items are the exact append-ordered list,
    so once ``seen`` exceeds ``capacity`` (Algorithm R has replaced random
    retained indices) slice access **raises** instead of silently returning
    a uniform jumble — per-pass latency tails should come from
    :meth:`StreamStats.reset_latencies` instead.

    ``seed=None`` (the default) draws an engine-unique seed from a process
    counter; pass an explicit seed for reproducible replacement sequences.
    """

    __slots__ = ("capacity", "seen", "seed", "_items", "_rng")

    def __init__(self, capacity: int = 4096, seed: Optional[int] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.seen = 0                 # total appends ever observed
        self.seed = next(_reservoir_seeds) if seed is None else seed
        self._items: List[float] = []
        self._rng = np.random.default_rng(self.seed)

    def append(self, value: float) -> None:
        self.seen += 1
        if len(self._items) < self.capacity:
            self._items.append(float(value))
        else:
            j = int(self._rng.integers(self.seen))
            if j < self.capacity:
                self._items[j] = float(value)

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def __iter__(self):
        return iter(self._items)

    def __getitem__(self, idx):
        if isinstance(idx, slice) and self.seen > self.capacity:
            raise ValueError(
                f"latency tail slices are only exact below the reservoir "
                f"capacity ({self.capacity}); after {self.seen} appends "
                "Algorithm R has replaced random retained indices, so a "
                "slice is a uniform jumble, not a pass tail — take "
                "per-pass tails via StreamStats.reset_latencies()")
        return self._items[idx]

    def percentile(self, q: float) -> float:
        """Latency percentile of the retained sample.

        Raises on an empty reservoir: an engine that never fired a verdict
        step has no latency distribution, and the old ``0.0`` read as a
        perfect 0 ms tail in dashboards — check ``len(reservoir)`` (or
        ``stats.windows``) before asking for a percentile.
        """
        if not self._items:
            raise ValueError(
                "percentile of an empty latency reservoir: no verdict step "
                "has fired yet (returning 0.0 here would report a perfect "
                "0 ms tail for an engine that never served)")
        return float(np.percentile(self._items, q))


@dataclasses.dataclass
class StreamStats:
    """Aggregate serve accounting (ServeStats conventions).

    ``latencies_s`` is a bounded :class:`LatencyReservoir`, not a list: the
    engine appends one latency per verdict step for the life of the process,
    and the reservoir keeps ``latency_p`` statistically valid at O(1)
    memory (exact below its capacity).  ``latency_p`` raises while the
    reservoir is empty (no verdict step has fired yet).

    Under ``async_depth=1`` the split matters: ``steps`` counts at
    dispatch, ``windows``/``deadline_misses``/``latencies_s`` at harvest,
    and ``wall_s`` is host time inside ``ingest()``/``flush()`` only —
    device compute overlapped with ingest is deliberately absent, so
    ``windows_per_s`` reads as sustained host throughput.

    ``dispatches`` counts *logical kernel dispatches* per step: a megakernel
    step is 1 regardless of how many groups co-fired; the per-group path
    charges each ready unit its flavor's cost (fused = 1, per-layer = one
    per Dense layer).  ``dispatches == steps`` is the single-dispatch
    guarantee the grouped benches assert.

    ``h2d_bytes`` counts the host arrays and scalars each verdict step
    hands the device (pending blocks, write positions, thresholds);
    ``d2h_bytes`` the step outputs copied back at finalize.  Both are exact
    and repeat exactly for a given fleet.

    ``recalibrations`` counts the streaming threshold recalibrations run
    (one per adapting unit per ``AdaptConfig.every`` of its fired steps),
    and ``calib_d2h_bytes`` the calibration rings and admission counts
    copied to the host for them (pad rows included, as copied);
    ``d2h_bytes`` leaves that state out."""

    steps: int                       # jitted detector steps executed
    cycles: int                      # scan cycles ingested
    windows: int                     # verdicts emitted (streams x steps)
    deadline_misses: int
    wall_s: float                    # total time spent inside ingest()
    dispatches: int = 0              # logical kernel dispatches issued
    h2d_bytes: int = 0               # host->device bytes of step operands
    d2h_bytes: int = 0               # device->host bytes of step outputs
    recalibrations: int = 0          # unit threshold recalibrations run
    calib_d2h_bytes: int = 0         # device->host bytes of calib state
    latencies_s: LatencyReservoir = dataclasses.field(
        default_factory=LatencyReservoir)

    def latency_p(self, q: float) -> float:
        return self.latencies_s.percentile(q)

    def reset_latencies(self) -> LatencyReservoir:
        """Swap in a fresh (same-capacity, fresh-seed) reservoir and return
        the retired one — the sanctioned way to take per-pass latency tails
        (benchmark passes): tail *slices* of a reservoir past its capacity
        are silently wrong, because Algorithm R replaces random retained
        indices, and therefore raise."""
        old = self.latencies_s
        self.latencies_s = LatencyReservoir(capacity=old.capacity)
        return old

    def windows_per_s(self) -> float:
        return self.windows / self.wall_s if self.wall_s > 0 else 0.0


@dataclasses.dataclass(frozen=True)
class AdaptConfig:
    """Streaming threshold-recalibration policy (online drift adaptation).

    ``capacity`` is the per-stream rolling score-ring length (the sketch
    window: the live threshold is the conservative quantile of the trailing
    ``<= capacity`` admitted scores per stream, pooled fleet-wide).
    ``every`` recalibrates once per that many fired verdict steps; the
    device-side state update runs every step regardless.  ``min_count``
    holds the threshold at its offline-calibrated seed until that many
    scores have been admitted fleet-wide (early tiny pools make noisy
    quantiles).  ``headroom`` is the admission gate: scores at most
    ``headroom`` times the live threshold enter the calibration state —
    wide enough that gradual benign drift passes through the gate even when
    it crosses the threshold, tight enough that attack scores (orders of
    magnitude out) never poison the state.
    """

    capacity: int = 32
    every: int = 1
    min_count: int = 16
    headroom: float = 4.0

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.every < 1:
            raise ValueError(f"every must be >= 1, got {self.every}")
        if self.min_count < 1:
            raise ValueError(f"min_count must be >= 1, got {self.min_count}")
        if self.headroom < 1.0:
            raise ValueError(
                f"headroom must be >= 1 (the gate must at least admit "
                f"sub-threshold scores), got {self.headroom}")


def _resolve_adapt(adapt: Union[bool, AdaptConfig, None],
                   head: DetectorHead, what: str = "") -> Optional[AdaptConfig]:
    """Validate and normalize an ``adapt=`` knob: None/False off, True the
    default policy, an :class:`AdaptConfig` verbatim.  Adaptation requires a
    calibrated :class:`ScoreHead` with a recorded ``target_fpr`` (the
    streaming quantile chases the same operating point the offline
    calibration chose)."""
    if adapt is None or adapt is False:
        return None
    cfg = AdaptConfig() if adapt is True else adapt
    if not isinstance(cfg, AdaptConfig):
        raise ValueError(f"{what}adapt must be None/bool/AdaptConfig, "
                         f"got {cfg!r}")
    if not isinstance(head, ScoreHead):
        raise ValueError(
            f"{what}adapt=True needs a score-vs-threshold head (ScoreHead); "
            f"the {head.name!r} head has no score distribution to "
            "recalibrate on")
    if head.threshold is None or head.target_fpr is None:
        raise ValueError(
            f"{what}adapt=True needs a calibrated head with a recorded "
            "target_fpr to seed and steer the live threshold "
            "(head.calibrate / the sim.detector trainers set both)")
    return cfg


def _layer_stack(model: Model, params: ParamTree) -> List[Tuple[Dict, str]]:
    """(params, activation) per Dense node in schedule order."""
    stack = ops.dense_stack(model, params)
    if not stack:
        raise ValueError("model has no Dense layers to serve")
    return stack


def _dense_batched(x: jax.Array, p: Dict, act: str, backend: str) -> jax.Array:
    """One Dense layer over a (M, K) batch, float or quantized (§6.1)."""
    if "qw" in p:
        qw = p["qw"]
        # Symmetric activation clip, matching quantize.quantize_tensor and
        # layers._quantized_matvec (the scale decodes [-qmax, qmax]).
        qmax = jnp.iinfo(qw.dtype).max
        xq = jnp.clip(jnp.round(x / p["x_scale"]), -qmax, qmax)
        scale = p["x_scale"] * p["w_scale"]
        if qw.dtype == jnp.int8:
            # SINT: native int8 dot product — the Pallas qmatmul MXU path.
            y = ops.quantized_matmul(xq.astype(qw.dtype), qw, scale,
                                     p.get("b"), backend=backend)
        else:
            # INT/DINT: int16/int32 products overflow int32 accumulation on
            # TPU, so the integer arithmetic is emulated in f32 (storage
            # compression is what these schemes buy — see layers.py).  No
            # round-trip through the int dtype: int32's qmax is not f32-
            # representable, so the cast would overflow at the clip rail.
            y = xq @ qw.astype(jnp.float32) * scale
            if p.get("b") is not None:
                y = y + p["b"]
    else:
        y = x @ p["w"]
        if "b" in p:
            y = y + p["b"]
    return ACTIVATIONS[act](y)


def _pad_layer_cols(p: Dict, n_pad: int) -> Dict:
    """Pad a Dense layer's output columns to ``n_pad`` (host-side, once at
    engine build) so every model rank owns an equal column slice.  Bias and
    per-channel weight scales are normalized to per-column vectors and
    padded alongside; pad columns are sliced off after the gather, so their
    values never surface."""
    wkey = "qw" if "qw" in p else "w"
    w = np.asarray(p[wkey])
    n = w.shape[1]
    q = dict(p)
    q[wkey] = jnp.asarray(np.pad(w, ((0, 0), (0, n_pad - n))))
    if p.get("b") is not None:
        b = np.broadcast_to(np.asarray(p["b"], np.float32), (n,))
        q["b"] = jnp.asarray(np.pad(b, (0, n_pad - n)))
    if "w_scale" in p:
        s = np.broadcast_to(np.asarray(p["w_scale"], np.float32), (n,))
        # Pad scale 1.0, not 0.0: a zero scale would make the (discarded)
        # pad columns 0 * 0 under emulated-int math — fine — but keeps the
        # invariant that every stored scale decodes *some* grid.
        q["w_scale"] = jnp.asarray(np.pad(s, (0, n_pad - n),
                                          constant_values=1.0))
    return q


def _model_shard_plan(stack, model_shards: int):
    """Per layer: ``(params, act, cols_per_rank | None, true_width)``.

    ``cols_per_rank`` is set (and the params column-padded) only for layers
    wide enough to shard; ``None`` keeps the replicated
    :func:`_dense_batched` path."""
    plan = []
    for p, act in stack:
        n_out = int((p["qw"] if "qw" in p else p["w"]).shape[1])
        if model_shards > 1 and n_out >= MODEL_SHARD_MIN_WIDTH:
            nc = -(-n_out // model_shards)
            plan.append((_pad_layer_cols(p, nc * model_shards), act, nc,
                         n_out))
        else:
            plan.append((p, act, None, n_out))
    return plan


def _dense_model_sharded(x: jax.Array, p: Dict, act: str, backend: str,
                         nc: int, n_out: int, axis: str) -> jax.Array:
    """One Dense layer column-sharded over the mesh's ``axis``.

    Each model rank slices its ``nc`` output columns (weights, bias and
    per-channel scales) by ``axis_index`` and computes the full-K dot for
    just those columns — the exact arithmetic of the unsharded layer, so
    REAL recombines bit-exactly.  One tiled ``all_gather`` rebuilds the
    full activation row for the next layer (mesh-transformer-jax's
    ``TransformerLayerShard`` recombination, gather flavor)."""
    j = jax.lax.axis_index(axis) * nc
    if "qw" in p:
        qw = jax.lax.dynamic_slice_in_dim(p["qw"], j, nc, axis=1)
        w_scale = jax.lax.dynamic_slice_in_dim(p["w_scale"], j, nc, axis=0)
        b = p.get("b")
        if b is not None:
            b = jax.lax.dynamic_slice_in_dim(b, j, nc, axis=0)
        qmax = jnp.iinfo(qw.dtype).max
        xq = jnp.clip(jnp.round(x / p["x_scale"]), -qmax, qmax)
        scale = p["x_scale"] * w_scale
        if qw.dtype == jnp.int8:
            y = ops.quantized_matmul(xq.astype(qw.dtype), qw, scale, b,
                                     backend=backend)
        else:
            y = xq @ qw.astype(jnp.float32) * scale
            if b is not None:
                y = y + b
    else:
        y = x @ jax.lax.dynamic_slice_in_dim(p["w"], j, nc, axis=1)
        if p.get("b") is not None:
            y = y + jax.lax.dynamic_slice_in_dim(p["b"], j, nc, axis=0)
    y = ACTIVATIONS[act](y)
    return jax.lax.all_gather(y, axis, axis=1, tiled=True)[:, :n_out]


@dataclasses.dataclass
class ServingUnit:
    """One detector population inside a serving core (the façades build
    these from their constructor vocabulary).

    ``name=None`` marks the anonymous single-model case — its verdicts
    carry ``group=None``.  ``window`` overrides the head-derived ring
    extent (``StreamEngine``'s explicit-window knob); ``what`` prefixes
    this unit's constructor error messages (``"group 'x': "`` for grouped
    fleets) so the façades keep their historical diagnostics."""

    name: Optional[str]
    model: Model
    params: ParamTree
    n_streams: int
    head: Optional[DetectorHead] = None
    fused: Optional[bool] = None
    adapt: Union[bool, AdaptConfig, None] = None
    window: Optional[int] = None
    what: str = ""


class _UnitState:
    """Per-unit serving state: geometry, compiled-body closure, ring."""

    __slots__ = ("name", "head", "window", "offset", "n_streams", "s_pad",
                 "body", "pos", "consumed", "use_fused", "windows",
                 "adapt", "live_threshold", "fires", "stack", "kernel_epi",
                 "fused_knob", "all_dense", "dispatch_cost")

    def __init__(self, name, head, window, offset, n_streams):
        self.name = name
        self.head = head
        self.window = window
        self.offset = offset          # first global stream index
        self.n_streams = n_streams
        self.pos = 0                  # next ring write index (host-tracked)
        self.consumed = 0             # scan count at the last fired step
        self.windows = 0              # verdicts emitted for this unit
        self.fires = 0                # steps this unit participated in


def _unpack_pergroup(outs) -> List[np.ndarray]:
    """Per-group step outputs -> one host array per ready unit."""
    return [np.asarray(o) for o in outs]


class _InFlight:
    """One dispatched-but-unharvested verdict step (async_depth=1)."""

    __slots__ = ("key", "outs", "cycle", "t0", "unpack")

    def __init__(self, key, outs, cycle, t0, unpack=_unpack_pergroup):
        self.key = key                # ready-combination the step ran under
        self.outs = outs              # per-unit output futures
        self.cycle = cycle            # boundary cycle the windows completed at
        self.t0 = t0                  # dispatch wall-clock (latency origin)
        self.unpack = unpack          # outs -> [host array per ready unit]


class _MegaPack:
    """One ready-subset's packed megakernel operands + static geometry.

    ``sig`` is the step-cache key material: the hashable
    :class:`~repro.kernels.ops.GroupedPlan` plus the per-slot serving
    geometry, epilogue selectors and adapt policy — everything the traced
    step closes over.  Two identity-distinct subsets with equal ``sig``
    share ONE compiled step; their numbers (``arrays``/``centers``) enter
    as runtime operands.  (``calib_update`` must therefore be instance-
    stateless, which the :class:`~repro.sim.heads.ScoreHead` base impl is.)
    """

    __slots__ = ("plan", "arrays", "centers", "tgt_sels", "widths",
                 "heads", "adapts", "sig", "unpack")

    def __init__(self, plan, arrays, centers, tgt_sels, widths, heads,
                 adapts, sig):
        self.plan = plan
        self.arrays = arrays          # packed arenas + meta (operands)
        self.centers = centers        # (G, 1, plan.n_out) margin centers
        self.tgt_sels = tgt_sels      # per slot: none|window|tail|center
        self.widths = widths          # true payload width per slot
        self.heads = heads
        self.adapts = adapts
        self.sig = sig

        def unpack(payload) -> List[np.ndarray]:
            pay = np.asarray(payload)
            return [pay[k, :, :w] for k, w in enumerate(widths)]
        self.unpack = unpack


class ServingCore:
    """Batched sliding-window serving over a list of :class:`ServingUnit`.

    This is the machinery layer — see the module docstring for the serving
    model and :class:`~repro.serving.streams.StreamEngine` /
    :class:`~repro.serving.grouped.GroupedStreamEngine` for the public
    constructor contracts.  Everything here is unit-count agnostic: the
    single-model engine is served exactly like a one-group fleet.
    """

    def __init__(self, units: Sequence[ServingUnit], *,
                 n_features: int = spec.N_FEATURES,
                 stride: int = spec.STRIDE,
                 deadline_s: float = spec.DEADLINE_S,
                 norm_mean: Sequence[float] = spec.NORM_MEAN,
                 norm_std: Sequence[float] = spec.NORM_STD,
                 backend: str = "auto",
                 shard: Optional[bool] = None,
                 mesh: Optional[Mesh] = None,
                 async_depth: int = 0,
                 megakernel: Optional[bool] = None):
        if not units:
            raise ValueError("need at least one serving unit")
        if any(u.n_streams < 1 for u in units):
            raise ValueError("every unit needs n_streams >= 1")
        if not 1 <= stride:
            raise ValueError("stride must be >= 1")
        if async_depth not in (0, 1):
            raise ValueError(
                f"async_depth must be 0 (synchronous) or 1 (double-"
                f"buffered), got {async_depth!r}")
        self.n_features = n_features
        self.stride = stride
        self.deadline_s = deadline_s
        self.async_depth = async_depth
        self._mean = np.asarray(norm_mean, np.float32)
        self._std = np.asarray(norm_std, np.float32)
        if self._mean.shape != (n_features,) or \
                self._std.shape != (n_features,):
            raise ValueError("norm_mean/norm_std must have one entry per "
                             "feature")
        self._backend = backend
        self.n_streams = sum(u.n_streams for u in units)

        # -- mesh ("data" stream sharding x optional "model" axis) ---------
        if shard is False and mesh is not None:
            raise ValueError("shard=False contradicts an explicit mesh")
        if mesh is None and (shard or (shard is None
                                       and len(jax.devices()) > 1)):
            # Never mesh wider than the smallest unit: pure-pad shards would
            # burn a dispatch per device on zero streams every cadence.
            mesh = make_fleet_mesh(min(len(jax.devices()),
                                       *(u.n_streams for u in units)))
        if mesh is not None:
            if "data" not in mesh.axis_names:
                raise ValueError(f"fleet mesh needs a 'data' axis, got "
                                 f"{mesh.axis_names}")
            extra = [a for a in mesh.axis_names
                     if a not in ("data", "model") and mesh.shape[a] != 1]
            if extra:
                raise ValueError(
                    f"non-'data' mesh axes must have size 1, got {extra} "
                    "(weight sharding lives on the 'model' axis)")
        self.mesh = mesh
        self.n_shards = 1 if mesh is None else mesh.shape["data"]
        self.model_shards = (mesh.shape["model"]
                             if mesh is not None
                             and "model" in mesh.axis_names else 1)
        self._model_axis = "model" if self.model_shards > 1 else None
        if mesh is None:
            self._arena_sharding = None
            self._calib_sharding = None
            self._counts_sharding = None
            self._block4_sharding = None
        else:
            self._arena_sharding = NamedSharding(mesh, P("data", None, None))
            self._calib_sharding = NamedSharding(mesh, P("data", None))
            self._counts_sharding = NamedSharding(mesh, P("data"))
            # Megakernel block operand: (group, stream, reading, feature).
            self._block4_sharding = NamedSharding(
                mesh, P(None, "data", None, None))

        # -- per-unit geometry, bodies, rings -----------------------------
        self._units: List[_UnitState] = []
        self._rings: List[jax.Array] = []
        self._calibs: List[jax.Array] = []
        self._counts: List[jax.Array] = []
        offset = 0
        for u in units:
            head = ClassifierHead() if u.head is None else u.head
            (input_size,) = u.model.input_shape
            # Window geometry is the head's contract: for every head but
            # forecast the window IS the model input; the forecast head asks
            # the ring for one extra reading (its prediction target) and
            # slices the model input out of the window on device.
            window = (head.ring_window(input_size, n_features)
                      if u.window is None else u.window)
            if head.model_input_size(window, n_features) != input_size:
                raise ValueError(
                    f"window {window} x features {n_features} (head "
                    f"{head.name!r}) != model input {input_size}")
            stack = _layer_stack(u.model, u.params)
            last = stack[-1][0]
            n_out = (last["qw"] if "qw" in last else last["w"]).shape[1]
            head.validate(input_size, n_out)
            fusable = ops.model_fusable(u.model, stack)
            if u.fused and not fusable:
                reason = ops.fuse_reason(stack) or \
                    "the model graph has non-Dense nodes"
                raise ValueError(
                    f"{u.what}fused=True but the model cannot fuse: {reason}")
            if u.fused and self._model_axis is not None:
                raise ValueError(
                    f"{u.what}fused=True cannot serve on a model-sharded "
                    "mesh: the all_gather between column-sharded layers "
                    "cannot live inside one pallas_call — use fused=None/"
                    "False, or a mesh with model_shards=1")
            # Constructor-only knob: captured in the body closure so a
            # post-compile mutation can't desynchronize traced steps.  The
            # fused kernel cannot span the model-axis gather, so a model-
            # sharded mesh auto-selects the per-layer path.
            use_fused = ((fusable and self._model_axis is None)
                         if u.fused is None else u.fused)
            st = _UnitState(u.name, head, window, offset, u.n_streams)
            # Pad-stream contract per unit: every device owns an equal
            # contiguous shard of each unit's arena; pad rows are zero
            # streams sliced off before verdicts.
            st.s_pad = -(-u.n_streams // self.n_shards) * self.n_shards
            st.use_fused = use_fused
            st.stack = stack
            st.kernel_epi = head.kernel_epilogue()
            st.fused_knob = u.fused
            st.all_dense = all(isinstance(n.layer, (Input, Dense))
                               for n in u.model.graph.nodes)
            st.dispatch_cost = 1 if use_fused else len(stack)
            st.adapt = _resolve_adapt(u.adapt, head, what=u.what)
            st.live_threshold = (head.threshold
                                 if isinstance(head, ScoreHead) else None)
            st.body = self._make_body(stack, head, use_fused, window,
                                      st.adapt)
            self._units.append(st)
            self._rings.append(self._place(
                jnp.zeros((st.s_pad, window, n_features), jnp.float32)))
            calib, counts = self._calib_state(st)
            self._calibs.append(calib)
            self._counts.append(counts)
            offset += u.n_streams
        self.max_window = max(st.window for st in self._units)

        # Compiled steps keyed by the ready-combination signature
        # ((unit_idx, block_len), ...): steady state — every unit ready
        # with a stride-long block — is one key reused forever; window
        # fill-in transitions each compile once.
        self._steps: Dict[Tuple, Callable] = {}

        # -- megakernel (single-dispatch multi-group steps) ---------------
        # Packs are cached per ready subset; compiled steps are keyed by
        # (pack.sig, block length) — the BLOCK SHAPE, not the subset — so
        # identity-distinct equal-geometry subsets share one executable and
        # the per-ready-combination step-cache explosion collapses.
        self._mega_packs: Dict[Tuple[int, ...], _MegaPack] = {}
        self._mega_steps: Dict[Tuple, Callable] = {}
        self._mega_reason = self._compute_mega_reason()
        if megakernel and self._mega_reason is not None:
            raise ValueError(
                "megakernel=True but the fleet cannot pack into one "
                f"dispatch: {self._mega_reason}")
        # Auto-enable only on the unsharded path.  The megakernel's sharded
        # step is bit-stable against the canonical unsharded math, but the
        # per-group SHARDED graph it replaces rounds a few dot products
        # differently at 1 ulp (XLA codegen is fusion-context dependent), so
        # flipping the default under a mesh would perturb REAL verdicts
        # bitwise against the seed behavior.  ``megakernel=True`` opts the
        # sharded path in explicitly (REAL agreement vs the per-group
        # sharded step is then epsilon-level, not bitwise).
        self._mega = (self._mega_reason is None
                      and (megakernel is True
                           or (megakernel is None and self.mesh is None)))

        self._count = 0
        self._pending: List[np.ndarray] = []
        self._inflight: Optional[_InFlight] = None
        self.last_outputs: Dict[Optional[str], np.ndarray] = {}
        self.stats = StreamStats(steps=0, cycles=0, windows=0,
                                 deadline_misses=0, wall_s=0.0)

    @property
    def mega_reason(self) -> Optional[str]:
        """Why this fleet cannot pack into the single-dispatch megakernel
        (None when it can — the engine may still serve per-group if the
        megakernel is disabled by the knob or the sharded default)."""
        return self._mega_reason

    # -- construction helpers ----------------------------------------------

    def _place(self, arr, sharding=None) -> jax.Array:
        """Commit an array to the fleet mesh (no-op unsharded); ``sharding``
        defaults to the 3-D arena sharding."""
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(
            arr, self._arena_sharding if sharding is None else sharding)

    def _calib_state(self, st: _UnitState) -> Tuple[jax.Array, jax.Array]:
        """A unit's (placed) rolling calibration state.  Non-adaptive units
        carry a minimal dummy so every step has one uniform
        ``(ring, calib, counts, block, pos, thr)`` signature per unit —
        the dummy rides through the donated step untouched."""
        if st.adapt is not None:
            calib, counts = st.head.calib_state(st.s_pad, st.adapt.capacity)
        else:
            calib = jnp.zeros((st.s_pad, 1), jnp.float32)
            counts = jnp.zeros((st.s_pad,), jnp.int32)
        return (self._place(calib, self._calib_sharding),
                self._place(counts, self._counts_sharding))

    @staticmethod
    def _thr(st: _UnitState) -> jnp.float32:
        """The unit's live threshold as the step's scalar operand (0.0 for
        heads with no threshold — the body never reads it then)."""
        return jnp.float32(0.0 if st.live_threshold is None
                           else st.live_threshold)

    def _make_body(self, stack, head, use_fused, window, adapt_cfg):
        """One unit's device step body: ring scatter, oldest-first unroll,
        the head's ``prepare`` view, the (fused Pallas / model-sharded)
        forward, the head's device epilogue and, when the unit adapts, the
        rolling calibration-state write.  Identical math for every façade,
        so grouped serving bit-matches an independent per-model engine."""
        backend = self._backend
        w = window
        plan = _model_shard_plan(stack, self.model_shards)
        axis = self._model_axis

        def _forward(x):
            if use_fused:
                return ops.fused_forward(x, stack, backend=backend)
            for p, act, nc, n_out in plan:
                x = (_dense_batched(x, p, act, backend) if nc is None else
                     _dense_model_sharded(x, p, act, backend, nc, n_out,
                                          axis))
            return x

        def body(ring, calib, counts, block, pos, thr):
            # block: (S, L, F) pending readings; L static per compile (the
            # warmup block is `window` long, steady-state blocks
            # `min(stride, window)` — ingest() trims longer spans host-side).
            # The device trim below is defense in depth for direct callers:
            # only the last `window` readings can ever land, and trimming
            # before scattering keeps the indices provably unique
            # (duplicate-index scatter-set order is undefined off-CPU).
            with jax.named_scope("ring_scatter"):
                length = block.shape[1]
                offset = max(length - w, 0)
                idx = (pos + offset + jnp.arange(length - offset)) % w
                ring = ring.at[:, idx, :].set(block[:, offset:])
                # Window unroll, oldest reading first: the ring holds
                # exactly the last `window` readings, ending at
                # (pos + L - 1) mod window.
                end = (pos + length) % w
                widx = (end + jnp.arange(w)) % w
                win = jnp.take(ring, widx, axis=1).reshape(ring.shape[0], -1)
            out = head.epilogue(win, _forward(head.prepare(win)))
            if adapt_cfg is not None:
                # The rolling benign-score state advances INSIDE the donated
                # step: one row-local ring write per stream, gated on the
                # live threshold — no extra dispatch, no new collectives.
                calib, counts = head.calib_update(
                    calib, counts, out, thr, adapt_cfg.headroom)
            return ring, calib, counts, out

        return body

    def _get_step(self, key: Tuple) -> Callable:
        """The jitted donated step for one ready-combination."""
        step = self._steps.get(key)
        if step is not None:
            return step
        bodies = [self._units[gi].body for gi, _ in key]

        def _step(rings, calibs, countss, blocks, poss, thrs):
            outs = [body(ring, calib, counts, block, pos, thr)
                    for body, ring, calib, counts, block, pos, thr
                    in zip(bodies, rings, calibs, countss, blocks, poss,
                           thrs)]
            return (tuple(o[0] for o in outs), tuple(o[1] for o in outs),
                    tuple(o[2] for o in outs), tuple(o[3] for o in outs))

        if self.mesh is not None:
            # One shard_map over the whole multi-unit body: every unit body
            # is stream-local over "data" (the calibration-state write
            # included), so each device serves its contiguous shard of every
            # ready unit; the only collectives are the model-axis gathers of
            # column-sharded wide layers (none on a 1-D mesh).
            # check_vma=False: pallas_call carries no replication rule.
            n = len(key)
            _step = jax.shard_map(
                _step, mesh=self.mesh,
                in_specs=((P("data", None, None),) * n,
                          (P("data", None),) * n, (P("data"),) * n,
                          (P("data", None, None),) * n,
                          (P(),) * n, (P(),) * n),
                out_specs=((P("data", None, None),) * n,
                           (P("data", None),) * n, (P("data"),) * n,
                           (P("data", None),) * n),
                check_vma=False)
        step = self._steps[key] = jax.jit(_step, donate_argnums=(0, 1, 2))
        return step

    def _single_step_view(self):
        """The classic single-model step signature over unit 0's body —
        ``(ring, block, pos) -> (ring, out)`` without adaptation,
        ``(ring, calib, counts, block, pos, thr) -> (ring, calib, counts,
        out)`` with — re-jitted from the exact body (and shard_map
        configuration) the serving steps run.  Back-compat introspection
        surface: the jaxpr dispatch-count suites trace
        ``StreamEngine._step`` through this."""
        st = self._units[0]
        body = st.body
        if st.adapt is not None:
            def step(ring, calib, counts, block, pos, thr):
                return body(ring, calib, counts, block, pos, thr)
            in_specs = (P("data", None, None), P("data", None), P("data"),
                        P("data", None, None), P(), P())
            out_specs = (P("data", None, None), P("data", None), P("data"),
                         P("data", None))
            donate = (0, 1, 2)
        else:
            def step(ring, block, pos):
                ring, _, _, out = body(
                    ring, jnp.zeros((ring.shape[0], 1), jnp.float32),
                    jnp.zeros((ring.shape[0],), jnp.int32),
                    block, pos, jnp.float32(0.0))
                return ring, out
            in_specs = (P("data", None, None), P("data", None, None), P())
            out_specs = (P("data", None, None), P("data", None))
            donate = 0
        if self.mesh is not None:
            step = jax.shard_map(step, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)
        return jax.jit(step, donate_argnums=donate)

    # -- megakernel: the whole ready fleet in ONE dispatch -----------------

    def _compute_mega_reason(self) -> Optional[str]:
        """None when multi-unit ready steps can lower to one grouped
        megakernel dispatch, else why the engine serves per-group.  The
        checks compose: engine-level prerequisites first (unit count, mesh,
        per-unit step flavor, head epilogue hooks), then the kernel-level
        packing contract (``ops.grouped_fuse_reason`` — per-position MXU
        mode, packed-arena VMEM budget)."""
        if len(self._units) < 2:
            return ("fleet has a single unit; its step is already "
                    "single-dispatch")
        if self._model_axis is not None:
            return ("the megakernel cannot span the model-axis all_gather "
                    "of column-sharded layers")
        for st in self._units:
            what = f"group {st.name!r}: " if st.name else ""
            if st.fused_knob is False:
                return f"{what}fused=False pins the per-layer path"
            if not st.all_dense:
                return f"{what}the model graph has non-Dense nodes"
            epi = st.kernel_epi
            if epi is None:
                return (f"{what}head {st.head.name!r} has no in-kernel "
                        "epilogue (kernel_epilogue() returned None)")
            if epi[0] not in ("logits", "mse") or \
                    epi[1] not in ("none", "window", "tail", "center"):
                return f"{what}unknown kernel epilogue spec {epi!r}"
            if epi[1] == "center" and not hasattr(st.head, "_center"):
                return (f"{what}'center' epilogue needs a head exposing a "
                        "_center() row")
            if type(st.head).prepare not in (DetectorHead.prepare,
                                             ForecastHead.prepare):
                return (f"{what}head {st.head.name!r} overrides prepare(); "
                        "the megakernel feeds the raw window and only "
                        "subsumes the base window/forecast views via the "
                        "zero-row contract")
        return ops.grouped_fuse_reason(
            [st.stack for st in self._units],
            names=[st.name or f"unit{i}"
                   for i, st in enumerate(self._units)],
            k0=max(st.window * self.n_features for st in self._units))

    def _mega_applicable(self, key: Tuple) -> bool:
        """True when THIS ready-combination runs as one megakernel dispatch:
        the engine packs, more than one unit co-fired, and the co-firing
        units agree on (padded streams, window, block length) — stacking
        their rings needs one shape.  Units with equal windows always fire
        with equal block lengths, so steady state of a uniform-geometry
        fleet (the heterogeneous bench fleet) is always mega."""
        if not self._mega or len(key) < 2:
            return False
        sts = [self._units[gi] for gi, _ in key]
        return (len({(st.s_pad, st.window) for st in sts}) == 1
                and len({length for _, length in key}) == 1)

    def _mega_pack(self, subset: Tuple[int, ...]) -> _MegaPack:
        """The packed arenas + static geometry for one ready subset."""
        pack = self._mega_packs.get(subset)
        if pack is not None:
            return pack
        sts = [self._units[gi] for gi in subset]
        kinds = [ops.GROUPED_KIND_LOGITS if st.kernel_epi[0] == "logits"
                 else ops.GROUPED_KIND_SCORE for st in sts]
        plan, arrays = ops.build_grouped_plan(
            [st.stack for st in sts], kinds,
            k0=max(st.window * self.n_features for st in sts))
        centers = np.zeros((len(sts), 1, plan.n_out), np.float32)
        for k, st in enumerate(sts):
            if st.kernel_epi[1] == "center":
                c = np.asarray(st.head._center(), np.float32)
                centers[k, 0, :c.shape[0]] = c
        widths = tuple(
            plan.n_outs[k] if kinds[k] == ops.GROUPED_KIND_LOGITS else 1
            for k in range(len(sts)))
        adapt_sig = tuple(
            None if st.adapt is None else
            (type(st.head).calib_update, st.adapt.capacity,
             st.adapt.headroom) for st in sts)
        sig = (plan, tuple((st.s_pad, st.window) for st in sts),
               tuple(st.kernel_epi for st in sts), adapt_sig)
        pack = _MegaPack(
            plan=plan, arrays=arrays, centers=jnp.asarray(centers),
            tgt_sels=tuple(st.kernel_epi[1] for st in sts), widths=widths,
            heads=tuple(st.head for st in sts),
            adapts=tuple(st.adapt for st in sts), sig=sig)
        self._mega_packs[subset] = pack
        return pack

    def _get_mega_step(self, subset: Tuple[int, ...],
                       length: int) -> Tuple[Callable, _MegaPack]:
        """The jitted single-dispatch step for a ready subset + block shape.

        The step is cached on ``(pack.sig, length)`` — geometry, not unit
        identity — so every equal-shape ready-combination reuses one
        executable; the packed arenas, margin centers, positions and live
        thresholds are runtime operands."""
        pack = self._mega_pack(subset)
        cache_key = (pack.sig, length)
        step = self._mega_steps.get(cache_key)
        if step is not None:
            return step, pack
        plan = pack.plan
        heads, adapts = pack.heads, pack.adapts
        backend = self._backend
        n = len(subset)
        w = self._units[subset[0]].window
        f = self.n_features
        # Per-slot epilogue-target selectors as (G, 1, 1) closure constants:
        # deterministic from pack.sig, so step sharing stays sound.
        t_win = np.asarray([s == "window" for s in pack.tgt_sels]
                           ).reshape(n, 1, 1)
        t_tail = np.asarray([s == "tail" for s in pack.tgt_sels]
                            ).reshape(n, 1, 1)

        def _mega(rings, calibs, countss, block, poss, thrs, arrays,
                  centers):
            # block: (G, S, L, F) stacked pending readings; poss/thrs are
            # (G,) vectors.  Same trim-then-scatter contract as the
            # per-group body, batched over the group axis.
            with jax.named_scope("ring_scatter"):
                arena = jnp.stack(rings)                       # (G, S, W, F)
                s = arena.shape[1]
                length_ = block.shape[2]
                off = max(length_ - w, 0)
                idx = (poss[:, None] + off
                       + jnp.arange(length_ - off)[None, :]) % w
                arena = arena.at[
                    jnp.arange(n)[:, None, None],
                    jnp.arange(s)[None, :, None],
                    idx[:, None, :]].set(block[:, :, off:])
                end = (poss + length_) % w
                widx = (end[:, None] + jnp.arange(w)[None, :]) % w
                win = jnp.take_along_axis(
                    arena, widx[:, None, :, None], axis=2)
                win = win.reshape(n, s, w * f)
            with jax.named_scope("megakernel/group_pack"):
                # Uniform geometry makes the window width the union input
                # width (plan.k0 == w * f); heads whose model eats less
                # (forecast) are handled by zero weight rows, not slicing.
                win_no = ops._fit_cols(win, plan.n_out)
                tail_no = ops._fit_cols(win[:, :, w * f - f:], plan.n_out)
                tgt = jnp.where(
                    t_win, win_no,
                    jnp.where(t_tail, tail_no,
                              jnp.broadcast_to(centers, win_no.shape)))
            payload = ops.grouped_apply(win, plan, arrays, tgt,
                                        backend=backend)
            new_calibs, new_counts = [], []
            for k in range(n):
                if adapts[k] is not None:
                    c, cnt = heads[k].calib_update(
                        calibs[k], countss[k], payload[k][:, :1], thrs[k],
                        adapts[k].headroom)
                else:
                    c, cnt = calibs[k], countss[k]
                new_calibs.append(c)
                new_counts.append(cnt)
            return (tuple(arena[k] for k in range(n)), tuple(new_calibs),
                    tuple(new_counts), payload)

        if self.mesh is not None:
            # Rings/calib state keep their per-unit P("data", ...) specs;
            # the stacked block and payload shard their STREAM axis (axis
            # 1); packed arenas, meta, centers, positions and thresholds
            # are replicated operands.  check_vma=False: pallas_call
            # carries no replication rule.
            _mega = jax.shard_map(
                _mega, mesh=self.mesh,
                in_specs=((P("data", None, None),) * n,
                          (P("data", None),) * n, (P("data"),) * n,
                          P(None, "data", None, None), P(), P(), P(), P()),
                out_specs=((P("data", None, None),) * n,
                           (P("data", None),) * n, (P("data"),) * n,
                           P(None, "data", None)),
                check_vma=False)
        step = jax.jit(_mega, donate_argnums=(0, 1, 2))
        self._mega_steps[cache_key] = step
        return step, pack

    def _dispatch_mega(self, key: Tuple) -> Tuple[Any, _MegaPack]:
        """Build operands for a ready-combination, advance per-unit serving
        state and fire the single-dispatch step.  Returns (payload future,
        pack) — the caller wraps them into an :class:`_InFlight`."""
        with _span("serve.operands"):
            sts = [self._units[gi] for gi, _ in key]
            length = key[0][1]
            full = np.stack(self._pending[-length:], axis=1)  # (streams, L, F)
            blocks, poss, thrs = [], [], []
            for (gi, _), st in zip(key, sts):
                span = self._count - st.consumed
                block = full[st.offset:st.offset + st.n_streams]
                if st.s_pad != st.n_streams:
                    block = np.pad(
                        block,
                        ((0, st.s_pad - st.n_streams), (0, 0), (0, 0)))
                blocks.append(block)
                poss.append((st.pos + (span - length)) % st.window)
                thrs.append(0.0 if st.live_threshold is None
                            else st.live_threshold)
                st.pos = (st.pos + span) % st.window
                st.consumed = self._count
                st.fires += 1
            step, pack = self._get_mega_step(tuple(gi for gi, _ in key),
                                             length)
            block = np.stack(blocks)
            # Host numpy operands: jnp.asarray of a Python list would
            # compile a conversion on the first hot-path step.
            poss = np.asarray(poss, np.int32)
            thrs = np.asarray(thrs, np.float32)
            h2d = block.nbytes + poss.nbytes + thrs.nbytes
            block = self._place(block, self._block4_sharding)
        self.stats.h2d_bytes += h2d
        with _span("serve.dispatch", h2d_bytes=h2d):
            new_rings, new_calibs, new_counts, payload = step(
                tuple(self._rings[gi] for gi, _ in key),
                tuple(self._calibs[gi] for gi, _ in key),
                tuple(self._counts[gi] for gi, _ in key),
                block, poss, thrs, pack.arrays, pack.centers)
        self._adopt(key, new_rings, new_calibs, new_counts)
        return payload, pack

    def _dispatch_pergroup(self, ready) -> Tuple[List, Any]:
        """Build each ready unit's operands, advance its serving state and
        fire the per-group step.  Returns (step key, output futures)."""
        with _span("serve.operands"):
            key, rings, calibs, countss, blocks, poss, thrs = \
                [], [], [], [], [], [], []
            h2d = 0
            for gi, st in ready:
                # span = cycles elapsed since the unit's last fired step;
                # the pruned pending tail holds at least the last
                # min(span, window) readings.
                span = self._count - st.consumed
                length = min(span, st.window)
                block = np.stack(self._pending[-length:], axis=1)  # (S,L,F)
                block = block[st.offset:st.offset + st.n_streams]
                if st.s_pad != st.n_streams:
                    block = np.pad(
                        block,
                        ((0, st.s_pad - st.n_streams), (0, 0), (0, 0)))
                # The ring write always ends at (pos + span - 1) mod window;
                # host-side trimming of long spans shifts the start to
                # match.
                eff_pos = (st.pos + (span - length)) % st.window
                key.append((gi, length))
                rings.append(self._rings[gi])
                calibs.append(self._calibs[gi])
                countss.append(self._counts[gi])
                blocks.append(self._place(block))
                poss.append(jnp.int32(eff_pos))
                thrs.append(self._thr(st))
                h2d += block.nbytes + _SCALAR_OPERAND_BYTES
                st.pos = (st.pos + span) % st.window
                st.consumed = self._count
                st.fires += 1
            step = self._get_step(tuple(key))
        self.stats.h2d_bytes += h2d
        with _span("serve.dispatch", h2d_bytes=h2d):
            new_rings, new_calibs, new_counts, outs = step(
                tuple(rings), tuple(calibs), tuple(countss), tuple(blocks),
                tuple(poss), tuple(thrs))
        self._adopt(key, new_rings, new_calibs, new_counts)
        return key, outs

    @staticmethod
    def _recalibrates(st: _UnitState) -> bool:
        """Whether the unit's last fired step recalibrates its threshold
        when finalized (``fires`` counts at dispatch, and a step is
        finalized before the next dispatch)."""
        return st.adapt is not None and st.fires % st.adapt.every == 0

    def _adopt(self, key, rings, calibs, countss) -> None:
        """Install a fired step's new ring and calibration states, and start
        the host copy of each state that this step's finalize recalibrates
        from: the copies queue behind the step on the device and overlap
        the wait for its outputs, so ``_finalize`` pays no round trip for
        them."""
        for (gi, _), ring, calib, counts in zip(key, rings, calibs, countss):
            self._rings[gi] = ring
            self._calibs[gi] = calib
            self._counts[gi] = counts
            if self._recalibrates(self._units[gi]):
                calib.copy_to_host_async()
                counts.copy_to_host_async()

    def _mega_example_args(self, key: Tuple) -> Tuple[Callable, Tuple]:
        """(step, zeroed operands) for a ready-combination's megakernel
        step — the warmup compile driver, and the introspection surface the
        jaxpr dispatch-count suites trace (``jax.make_jaxpr(step)(*args)``
        shows exactly one ``pallas_call`` under ``backend='pallas'``)."""
        subset = tuple(gi for gi, _ in key)
        length = key[0][1]
        step, pack = self._get_mega_step(subset, length)
        sts = [self._units[gi] for gi in subset]
        rings = tuple(self._place(jnp.zeros(
            (st.s_pad, st.window, self.n_features), jnp.float32))
            for st in sts)
        states = [self._calib_state(st) for st in sts]
        block = self._place(
            jnp.zeros((len(sts), sts[0].s_pad, length, self.n_features),
                      jnp.float32), self._block4_sharding)
        poss = jnp.zeros((len(sts),), jnp.int32)
        thrs = jnp.asarray([0.0 if st.live_threshold is None
                            else st.live_threshold for st in sts],
                           jnp.float32)
        return step, (rings, tuple(c for c, _ in states),
                      tuple(cnt for _, cnt in states), block, poss, thrs,
                      pack.arrays, pack.centers)

    # -- readiness schedule ------------------------------------------------

    def _ready(self, st: _UnitState, count: int) -> bool:
        return (count >= st.window
                and (count - st.window) % self.stride == 0)

    def _schedule_keys(self) -> List[Tuple]:
        """Every distinct ready-combination key the serve loop will hit, by
        simulating the (deterministic) readiness schedule through window
        fill-in plus one full steady-state stride period."""
        keys: List[Tuple] = []
        consumed = {i: 0 for i in range(len(self._units))}
        for count in range(1, self.max_window + self.stride + 1):
            key = []
            for gi, st in enumerate(self._units):
                if self._ready(st, count):
                    span = count - consumed[gi]
                    key.append((gi, min(span, st.window)))
                    consumed[gi] = count
            if key and tuple(key) not in keys:
                keys.append(tuple(key))
        return keys

    def _step_examples(self):
        """Yield ``(jitted step, zeroed operands)`` for every step shape the
        readiness schedule can produce — what :meth:`warmup` compiles, and
        what a caller lowers to inspect the programs serving will run.

        Routing mirrors :meth:`ingest`: multi-unit uniform-geometry keys
        yield the megakernel step (cached per BLOCK SHAPE, so distinct
        ready-combinations of equal shape share one), everything else the
        per-group step."""
        for key in self._schedule_keys():
            if self._mega_applicable(key):
                yield self._mega_example_args(key)
                continue
            rings = tuple(self._place(jnp.zeros(
                (self._units[gi].s_pad, self._units[gi].window,
                 self.n_features), jnp.float32)) for gi, _ in key)
            states = [self._calib_state(self._units[gi]) for gi, _ in key]
            blocks = tuple(self._place(jnp.zeros(
                (self._units[gi].s_pad, length, self.n_features),
                jnp.float32)) for gi, length in key)
            poss = tuple(jnp.int32(0) for _ in key)
            thrs = tuple(self._thr(self._units[gi]) for gi, _ in key)
            yield self._get_step(key), (
                rings, tuple(c for c, _ in states),
                tuple(n for _, n in states), blocks, poss, thrs)

    def warmup(self) -> None:
        """Compile every step shape the readiness schedule can produce —
        each unit's window-fill firing and the steady-state all-ready step
        — outside the serve clock, with the serve-time arena sharding."""
        for step, args in self._step_examples():
            *_, outs = step(*args)
            jax.block_until_ready(outs)

    # -- ingestion ---------------------------------------------------------

    def ingest(self, readings: np.ndarray) -> List[Verdict]:
        """One scan cycle of fleet readings -> verdicts (usually empty).

        ``readings`` is ``(n_streams, n_features)`` raw sensor values over
        the whole fleet (unit slices concatenated in unit order); the
        engine applies the PLC-side normalization itself.

        Synchronous mode returns this boundary's verdicts.  Under
        ``async_depth=1`` a ready boundary first harvests the *previous*
        in-flight step's verdicts (returned now, one boundary late, with
        dispatch→harvest latency accounting), then dispatches this
        boundary's step without blocking on it.
        """
        t0 = time.perf_counter()
        with _span("serve.ingest", cycle=self._count):
            verdicts = self._ingest(readings, t0)
        self.stats.wall_s += time.perf_counter() - t0
        return verdicts

    def _ingest(self, readings: np.ndarray, t0: float) -> List[Verdict]:
        """The body of :meth:`ingest`; ``t0`` is the call's start, the
        latency origin of a step dispatched here."""
        with _span("serve.normalize"):
            readings = np.asarray(readings, np.float32)
            if readings.shape != (self.n_streams, self.n_features):
                raise ValueError(
                    f"expected ({self.n_streams}, {self.n_features}) "
                    f"readings, got {readings.shape}")
            self._pending.append((readings - self._mean) / self._std)
            # stride > window: readings older than the last `max_window`
            # can never land in any ring, so drop them HERE — host memory,
            # host->device transfer and the compiled block shapes all stay
            # capped at the window.
            if len(self._pending) > self.max_window:
                del self._pending[:len(self._pending) - self.max_window]
        self._count += 1
        self.stats.cycles += 1

        ready = [(gi, st) for gi, st in enumerate(self._units)
                 if self._ready(st, self._count)]
        if not ready:
            return []

        # Async: harvest BEFORE dispatching — the harvested step's calib
        # state is about to be donated into the new step, and recalibrating
        # the live threshold first reproduces the sync loop's operand
        # ordering exactly (the new step's thr operand bit-matches).
        verdicts = self._harvest() if self.async_depth else []

        mega_key = tuple(
            (gi, min(self._count - st.consumed, st.window))
            for gi, st in ready)
        if self._mega_applicable(mega_key):
            # Single-dispatch megakernel step over the whole ready subset.
            outs, pack = self._dispatch_mega(mega_key)
            key, unpack = list(mega_key), pack.unpack
            self.stats.dispatches += 1
        else:
            key, outs = self._dispatch_pergroup(ready)
            unpack = _unpack_pergroup
            self.stats.dispatches += sum(
                self._units[gi].dispatch_cost for gi, _ in key)
        self.stats.steps += 1

        flight = _InFlight(tuple(key), outs, self._count - 1, t0, unpack)
        if self.async_depth:
            # Dispatch-and-return: the step's outputs stay in flight until
            # the next ready boundary (or flush) harvests them — device
            # compute overlaps the host ingest of the next stride.
            self._inflight = flight
        else:
            verdicts = self._finalize(flight)
        return verdicts

    def _harvest(self) -> List[Verdict]:
        """Finalize the in-flight step, if any (async_depth=1)."""
        flight, self._inflight = self._inflight, None
        return [] if flight is None else self._finalize(flight)

    def _finalize(self, flight: _InFlight) -> List[Verdict]:
        """Block on a dispatched step's outputs and turn them into verdicts
        (+ harvest-side accounting + adapt recalibration).  Shared verbatim
        between the sync path (called right after dispatch) and the async
        path (called at the next boundary / flush), so verdict content is
        bit-identical across modes."""
        recal = [gi for gi, _ in flight.key
                 if self._recalibrates(self._units[gi])]
        calib_d2h = sum(self._calibs[gi].nbytes + self._counts[gi].nbytes
                        for gi in recal)
        self.stats.recalibrations += len(recal)
        self.stats.calib_d2h_bytes += calib_d2h
        with _span("serve.finalize", cycle=flight.cycle,
                   recalibrations=len(recal), calib_d2h_bytes=calib_d2h):
            with _span("serve.block"):
                jax.block_until_ready(flight.outs)
            outs = flight.outs if isinstance(flight.outs, tuple) \
                else (flight.outs,)
            d2h = sum(math.prod(a.shape) * a.dtype.itemsize for a in outs)
            self.stats.d2h_bytes += d2h
            with _span("serve.unpack", d2h_bytes=d2h):
                outs = flight.unpack(flight.outs)
            latency = time.perf_counter() - flight.t0
            miss = latency > self.deadline_s
            verdicts: List[Verdict] = []
            for (gi, _), out in zip(flight.key, outs):
                st = self._units[gi]
                # Gathers each device's shard of outputs to the host (the
                # mega unpack also slices each slot's true payload width);
                # pad-stream rows are dropped here and never surface as
                # verdicts.
                out = out[:st.n_streams]
                self.last_outputs[st.name] = out
                # Streaming recalibration: re-host the offline score-then-
                # quantile sequence on the rolling state (pad rows sliced
                # off — zero streams still score, so they must stay out of
                # the pool).  The state's host copy started at dispatch
                # (``_adopt``), so np.asarray here waits on no new round
                # trip.  In async mode this runs before the NEXT dispatch,
                # so the state read here is exactly this step's output.
                if gi in recal:
                    thr = st.head.streaming_threshold(
                        np.asarray(self._calibs[gi])[:st.n_streams],
                        np.asarray(self._counts[gi])[:st.n_streams],
                        min_count=st.adapt.min_count)
                    if thr is not None:
                        st.live_threshold = thr
                # Host epilogue via the head: classifier -> argmax/softmax,
                # score heads -> score vs the unit's LIVE threshold (the
                # offline cutoff unless adaptation has moved it).
                with _span("serve.head"):
                    pred, prob, score, thr = st.head.host_verdicts(
                        out, threshold=st.live_threshold)
                # One C-level map over whole columns builds the unit's rows,
                # positionally in Verdict field order; tolist() yields
                # Python ints and floats.
                with _span("serve.rows"):
                    n = st.n_streams
                    verdicts.extend(map(
                        Verdict, range(st.offset, st.offset + n),
                        itertools.repeat(flight.cycle, n), pred.tolist(),
                        itertools.repeat(None, n) if prob is None
                        else prob.tolist(),
                        itertools.repeat(latency, n),
                        itertools.repeat(miss, n),
                        itertools.repeat(None, n) if score is None
                        else score.tolist(),
                        itertools.repeat(thr, n),
                        itertools.repeat(st.name, n)))
                    st.windows += st.n_streams
                    self.stats.windows += st.n_streams
                    self.stats.deadline_misses += int(miss) * st.n_streams
            self.stats.latencies_s.append(latency)
            return verdicts

    def flush(self) -> List[Verdict]:
        """Drain the in-flight verdict step (``async_depth=1``); returns
        ``[]`` when nothing is in flight (always, in sync mode).  Call at
        end of stream — ``run()`` deliberately does not auto-flush, because
        a live fleet may keep streaming."""
        t0 = time.perf_counter()
        verdicts = self._harvest()
        self.stats.wall_s += time.perf_counter() - t0
        return verdicts

    def run(self, streams: Sequence[Any], n_cycles: int,
            on_verdict: Optional[Callable[[Verdict], None]] = None,
            ) -> List[Verdict]:
        """Drive a fleet of ``PlantStream``-likes for ``n_cycles`` cycles.

        Each stream's ``step()`` must yield an object with ``tb0_meas`` /
        ``wd_meas`` attributes (simulation cost is *not* counted into the
        engine's serve stats — only ingest time is).  Under ``async_depth=1``
        the returned verdicts trail one ready boundary and the final step
        stays in flight until :meth:`flush`.
        """
        if len(streams) != self.n_streams:
            raise ValueError(
                f"fleet size {len(streams)} != engine streams "
                f"{self.n_streams}")
        if self.n_features != 2:
            raise ValueError("run() reads the MSF (tb0_meas, wd_meas) "
                             "layout; use ingest() directly for other "
                             "feature sets")
        out: List[Verdict] = []
        readings = np.zeros((self.n_streams, self.n_features), np.float32)
        for _ in range(n_cycles):
            for i, s in enumerate(streams):
                r = s.step()
                readings[i, 0] = r.tb0_meas
                readings[i, 1] = r.wd_meas
            for v in self.ingest(readings):
                out.append(v)
                if on_verdict is not None:
                    on_verdict(v)
        return out
