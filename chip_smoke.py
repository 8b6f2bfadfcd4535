"""Smoke run of the fleet detection service on a TPU chip.

Drives the service's main path once, through the entry points a user
calls — ``StreamEngine`` / ``GroupedStreamEngine``, ``warmup()``, then
``ingest()`` scan cycle by scan cycle — with the full-width §7 models and
fleets the size one chip would monitor, and checks every verdict against a
reference that never touches the device (``codegen.verify.numpy_mlp_ref``
over the same windows).  Weights come from ``init_params`` and traffic from
``sim.scenarios.fleet_readings``, both from fixed seeds.

One chip (the default):

* **A** — SINT classifier (400-64-32-16-2), 4096 plants, fused kernel,
  unsharded, 400 scan cycles.
* **B** — autoencoder (400-64-16-64-400) with a ``ReconstructionHead``,
  REAL and SINT, 1024 plants, fused kernel.
* **C** — the four-head mixed fleet (classifier, autoencoder, margin,
  forecast), 4 x 256 plants, SINT and REAL, served by the grouped
  megakernel (``megakernel=True``: a packing failure raises).

``--chips 4`` runs only the paths that exist across chips, each against
the same fleet served unsharded on device 0 of the same process: fleet A
on a 4-way ``("data",)`` mesh and on a ``(2, 2)`` ``("data", "model")``
mesh, and the SINT fleet C on the 4-way mesh.

Every phase lowers each verdict step the engine will run and requires the
Mosaic kernel call (``tpu_custom_call``) in it, so neither the jnp oracle
nor interpret mode can pass for the kernel.  Any mismatch raises and the
script exits nonzero; without a TPU it exits nonzero before doing anything.

The numbers printed before the last line are smoke-run diagnostics, not
benchmark metrics.  The last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# SINT verdicts must match the reference exactly and their f32 tails (the
# classifier's probability, score heads' scores) to this relative
# tolerance: the repo's jit-vs-oracle contract.
SINT_RTOL = 1e-4
# REAL tails: the same relative tolerance against the f32 reference (the
# kernels' f32 dots run at full f32 precision), plus an absolute floor for
# score heads.  A unit-scale model output carries about 1e-6 of f32
# rounding after a 400-term dot in any summation order, and a mean squared
# error of score s moves by about 2 * sqrt(s) * 1e-6 for it: at most 1e-6
# for s <= 0.25, and far more than 1e-4 * s for the tiny scores of a
# well-fitted forecaster or margin head.
REAL_RTOL = 1e-4
REAL_ATOL = 1e-6
# The chip's f32 divide is not correctly rounded: on a v5e it lands up to
# 2 ulp from IEEE division.  A SINT layer requantizes with
# round(h / x_scale), so a quotient this close to a half-integer may round
# the other way on the chip than in the reference.
NEAR_TIE_ULPS = 4

SEED = 0
N_CYCLES = 400                 # 200-reading fill + verdicts every 10 cycles
BASE_PLANTS = 256              # simulated plants; wider fleets tile them
SENSOR_NOISE = 0.01            # per-copy noise, in units of NORM_STD


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


# ---------------------------------------------------------------------------
# Traffic and reference


class Traffic:
    """Raw ``(N_CYCLES, n, F)`` fleet readings from one simulated base fleet.

    ``fleet_readings`` steps every plant in Python, so the smoke run
    simulates ``BASE_PLANTS`` plants once and tiles them out to the fleet
    size, each copy with its own seeded sensor noise so no two streams
    carry the same windows."""

    def __init__(self, seed: int):
        from repro.sim import fleet_readings
        self.seed = seed
        self.base = fleet_readings(BASE_PLANTS, N_CYCLES, seed=seed)

    def readings(self, n: int) -> np.ndarray:
        from repro.configs import msf_detector as spec
        reps = -(-n // BASE_PLANTS)
        tiled = np.tile(self.base, (1, reps, 1))[:, :n]
        rng = np.random.default_rng(self.seed + n)
        noise = rng.standard_normal(tiled.shape).astype(np.float32)
        noise *= np.float32(SENSOR_NOISE) * np.asarray(spec.NORM_STD,
                                                       np.float32)
        return (tiled + noise).astype(np.float32)


def windows(readings: np.ndarray) -> np.ndarray:
    """``(n_windows, S, W*F)`` normalized windows, as the engine sees them."""
    from repro.codegen import verify as V
    from repro.configs import msf_detector as spec
    per_stream = [V.normalize_windows(
        V.stream_windows(readings[:, s, :], spec.WINDOW, spec.STRIDE),
        spec.NORM_MEAN, spec.NORM_STD) for s in range(readings.shape[1])]
    return np.stack(per_stream, axis=1)


def requantize_trace(x: np.ndarray, stack, flips=()):
    """``numpy_mlp_ref``'s arithmetic over rows ``x``, with each requantize
    quotient named in ``flips`` (``(layer, row, unit)``) rounded to its
    other integer neighbour.  Returns the outputs and the near-ties: every
    ``(layer, row, unit, quotient, ulps)`` whose quotient ``h / x_scale``
    lies within ``NEAR_TIE_ULPS`` ulp of a half-integer inside the clip
    range, where the chip's divide may round it the other way."""
    from repro.codegen import verify as V
    out = np.asarray(x, np.float32)
    ties = []
    for li, (p, act) in enumerate(stack):
        p = {k: (None if v is None else np.asarray(v)) for k, v in p.items()}
        if "qw" in p:
            xs = np.float32(p["x_scale"])
            t = (out / xs).astype(np.float32)
            q = np.rint(t)
            half = np.floor(t) + np.float32(0.5)
            ulps = np.abs(t - half) / np.spacing(np.abs(half))
            near = (ulps <= NEAR_TIE_ULPS) & (np.abs(half) < 127)
            ties += [(li, r, u, t[r, u], ulps[r, u])
                     for r, u in zip(*np.nonzero(near))]
            for fl, r, u in flips:
                if fl == li:
                    q[r, u] = 2 * np.floor(t[r, u]) + 1 - q[r, u]
            xq = np.clip(q, -127, 127).astype(np.int32)
            acc = xq @ p["qw"].astype(np.int32)
            s = (xs * p["w_scale"].astype(np.float32)).astype(np.float32)
            y = (acc.astype(np.float32) * s).astype(np.float32)
        else:
            y = (out @ p["w"].astype(np.float32)).astype(np.float32)
        if p.get("b") is not None:
            y = (y + p["b"].astype(np.float32)).astype(np.float32)
        out = V._np_act(act, y)
    return out, ties


class Reference:
    """Reference verdicts of one model over ``(n, S, W*F)`` windows,
    computed in numpy off the device: ``pred`` and the f32 ``tail``
    (probability or score).  A score head without ``threshold`` gives only
    ``tail``."""

    def __init__(self, stack, head, wins: np.ndarray, threshold=None):
        from repro.codegen import verify as V
        n, s, width = wins.shape
        self.stack, self.head, self.threshold = stack, head, threshold
        self.shape = (n, s)
        self.rows = wins.reshape(n * s, width)
        p0 = stack[0][0]
        self.k0 = int(np.asarray(p0.get("qw", p0.get("w"))).shape[0])
        y = V.numpy_mlp_ref(self.rows[:, :self.k0], stack)
        self.n_out = y.shape[1]
        self.pred, self.tail = self.verdicts(y, self.rows)
        # The verdict's decision boundary in tail units: 1/2 for a binary
        # classifier's probability, the threshold for a score head.
        self.boundary = 0.5 if head is None else threshold

    def verdicts(self, y: np.ndarray, rows: np.ndarray):
        """Model outputs ``y`` of ``rows`` -> ``(pred, tail)``."""
        from repro.codegen import verify as V
        from repro.configs import msf_detector as spec
        from repro.sim import ForecastHead, MarginHead, ReconstructionHead
        from repro.sim.heads import softmax_np
        if self.head is None:                            # classifier
            pred = np.argmax(y, axis=-1)
            return pred, softmax_np(y)[np.arange(len(y)), pred]
        if isinstance(self.head, ReconstructionHead):
            target = rows
        elif isinstance(self.head, MarginHead):
            target = np.broadcast_to(
                np.asarray(self.head.center, np.float32), y.shape)
        elif isinstance(self.head, ForecastHead):
            target = rows[:, -spec.N_FEATURES:]
        else:
            raise TypeError(f"no reference for head {self.head!r}")
        score = V.sequential_f32_mse(y, target)
        if self.threshold is None:
            return None, score
        return (score > self.threshold).astype(np.int64), score

    def explain(self, i: int, pred, tail: float, rtol: float):
        """Why the chip's window ``i`` departs from the reference: the one
        or two requantize near-ties which, rounded the other way, make the
        reference give the chip's ``pred`` and ``tail`` (within ``rtol``),
        as a printable line; None when none do."""
        from repro.codegen import verify as V
        row = self.rows[i:i + 1]
        x = row[:, :self.k0]
        base, ties = requantize_trace(x, self.stack)
        assert np.array_equal(base, V.numpy_mlp_ref(x, self.stack))
        for combo in itertools.chain(((t,) for t in ties),
                                     itertools.combinations(ties, 2)):
            y, _ = requantize_trace(x, self.stack,
                                    [(li, r, u) for li, r, u, *_ in combo])
            p, t = self.verdicts(y, row)
            if (p[0] == pred and abs(float(t[0]) - tail)
                    <= rtol * max(abs(float(t[0])), 1e-30)):
                return "; ".join(
                    f"layer {li} unit {u}: reference quotient {float(q)!r} is "
                    f"{d:.2f} ulp from {np.floor(q) + 0.5}, the chip rounds "
                    f"it the other way" for li, _, u, q, d in combo)
        return None


def gap_threshold(scores: np.ndarray, q: float = 0.9) -> float:
    """A threshold flagging about ``1 - q`` of the windows, placed midway
    in the gap between two neighbouring reference scores so a verdict
    flips only on an error larger than half that gap."""
    s = np.sort(np.asarray(scores, np.float64))
    i = int(q * (len(s) - 1))
    return float((s[i] + s[i + 1]) / 2)


def verdict_arrays(verdicts, n_steps: int, n_streams: int):
    """Engine verdicts -> ``(pred, tail)`` arrays of shape (steps, streams)
    in (cycle, stream) order."""
    assert len(verdicts) == n_steps * n_streams, (len(verdicts), n_steps,
                                                  n_streams)
    vs = sorted(verdicts, key=lambda v: (v.cycle, v.stream))
    pred = np.asarray([v.pred for v in vs]).reshape(n_steps, n_streams)
    tail = np.asarray([v.prob if v.score is None else v.score for v in vs],
                      np.float64).reshape(n_steps, n_streams)
    return pred, tail


def compare(label: str, scheme: str, pred, tail, ref: Reference):
    """Chip verdicts vs the reference; returns the mask of windows excused
    as requantize near-ties.

    Tails are compared as the same quantity on both sides: a score, or the
    probability of the *reference's* predicted class (a binary classifier's
    chip tail is the probability of its own class, so a flipped window
    compares ``1 - tail``).  SINT: every PRED identical and every tail
    within ``SINT_RTOL``, except windows that :meth:`Reference.explain`
    traces to a requantize near-tie the chip rounded the other way; each
    is printed.  REAL: every tail within ``REAL_RTOL`` relative plus
    ``REAL_ATOL``; a PRED may differ only where the reference tail lies
    within that tolerance of the decision boundary (a borderline flip,
    printed with its margin)."""
    pred = np.asarray(pred).reshape(-1)
    ref_pred = np.asarray(ref.pred).reshape(-1)
    chip_tail = np.asarray(tail, np.float64).reshape(-1)
    ref_tail = np.asarray(ref.tail, np.float64).reshape(-1)
    flips = pred != ref_pred
    same_tail = chip_tail
    if ref.head is None and flips.any():
        if ref.n_out != 2:
            raise AssertionError(
                f"{label} [{scheme}]: PRED differs on a {ref.n_out}-class "
                "classifier, whose verdict gives no probability of the "
                "reference's class")
        same_tail = np.where(flips, 1.0 - chip_tail, chip_tail)
    diff = np.abs(same_tail - ref_tail)
    if scheme == "SINT":
        rtol, atol = SINT_RTOL, 0.0
    else:
        rtol, atol = REAL_RTOL, REAL_ATOL
    tol = rtol * np.abs(ref_tail) + atol
    margin = np.abs(ref_tail - ref.boundary)
    borderline = flips & (scheme == "REAL") & (margin <= tol)
    off = (flips & ~borderline) | (diff > tol)
    explained = {}
    if scheme == "SINT":
        for i in np.flatnonzero(off)[:64]:
            why = ref.explain(int(i), pred[i], float(chip_tail[i]), rtol)
            if why is not None:
                explained[int(i)] = why
    ok = ~off
    ok[list(explained)] = True
    rel = diff / np.maximum(np.abs(ref_tail), 1e-30)
    worst = float((diff / tol).max()) if tol.all() else float("nan")
    print(f"# smoke {label} [{scheme}]: PRED {int((~flips).sum())}/"
          f"{pred.size} agree (reference flags {int((ref_pred != 0).sum())}), "
          f"tails within {rtol} rel + {atol} abs on "
          f"{int((diff <= tol).sum())}/{pred.size}, max |tail diff| "
          f"{float(diff.max())!r} (max rel {float(rel.max())!r}, worst "
          f"|diff|/tolerance {worst!r}), "
          f"requantize near-tie windows {len(explained)}, borderline REAL "
          f"flips {int(borderline.sum())}", flush=True)
    for i, why in explained.items():
        print(f"#   window {i}: chip pred {pred[i]} tail "
              f"{float(chip_tail[i])!r}, reference pred {ref_pred[i]} tail "
              f"{float(ref_tail[i])!r}: {why}", flush=True)
    for i in np.flatnonzero(borderline):
        print(f"#   window {i}: borderline flip, chip pred {pred[i]}, "
              f"reference pred {ref_pred[i]} tail {float(ref_tail[i])!r} is "
              f"{float(margin[i])!r} from the boundary {ref.boundary!r} "
              f"(tolerance {float(tol[i])!r}); chip tail of the same class "
              f"{float(same_tail[i])!r}", flush=True)
    if not ok.all():
        i = int(np.flatnonzero(~ok)[0])
        raise AssertionError(
            f"{label} [{scheme}]: {int((~ok).sum())} windows off the "
            f"reference; first at flat window {i}: chip pred {pred[i]} tail "
            f"{float(chip_tail[i])!r}, reference pred {ref_pred[i]} tail "
            f"{float(ref_tail[i])!r} (|diff| {float(diff[i])!r} vs "
            f"tolerance {float(tol[i])!r}, reference "
            f"{float(margin[i])!r} from its boundary), not a borderline "
            "REAL flip and no requantize near-tie explains it")
    excused = np.zeros(pred.size, bool)
    excused[list(explained)] = True
    return excused.reshape(ref.shape)


def agree(label: str, run, base) -> None:
    """Two chip serving paths of one SINT fleet over the same windows,
    each ``(pred, tail, near_tie_mask)`` as :func:`check` returns it: every
    PRED identical, and every tail bit-identical except in windows that
    either run's reference comparison traced to a requantize near-tie
    (the sharded and unsharded programs may requantize differently only
    there)."""
    pred, tail, ties = run
    bpred, btail, bties = base
    pred, bpred = np.asarray(pred), np.asarray(bpred)
    tail, btail = np.asarray(tail, np.float64), np.asarray(btail, np.float64)
    excused = np.asarray(ties) | np.asarray(bties)
    d = np.abs(tail - btail)
    same = int((pred == bpred).sum())
    differ = (d != 0) & ~excused
    print(f"# smoke {label}: PRED {same}/{pred.size} agree, identical tails "
          f"{int((d == 0).sum())}/{d.size}, max |tail diff| "
          f"{float(d.max())!r} ({int((d != 0).sum())} differing windows, "
          f"{int(excused.sum())} excused as near-ties)", flush=True)
    if same != pred.size:
        raise AssertionError(f"{label}: {pred.size - same} PRED differ")
    if differ.any():
        raise AssertionError(
            f"{label}: {int(differ.sum())} tails differ outside near-tie "
            f"windows (largest {float(d[differ].max())!r})")


# ---------------------------------------------------------------------------
# Serving


class CompileCounter:
    """Counts XLA compiles (persistent-cache hits included) and persistent
    cache hits, through JAX's monitoring events."""

    def __init__(self):
        from jax._src import dispatch
        self.compiles = 0
        self.cache_hits = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, secs, **kw):
            if name == event:
                self.compiles += 1

        def on_event(name, **kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def require_kernel(engine, label: str) -> int:
    """Lower every verdict step the engine will run; each must carry the
    Mosaic kernel call.  Returns the number of steps checked."""
    n = 0
    for step, args in engine._step_examples():
        text = step.lower(*args).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(
                f"{label}: a verdict step lowered without tpu_custom_call — "
                "the Pallas kernel did not lower for the chip (oracle or "
                "interpret path)")
        n += 1
    return n


def serve(engine, readings: np.ndarray, label: str, counter: CompileCounter,
          dev: jax.Device) -> dict:
    """warmup() then ingest() every cycle; returns verdicts and smoke
    diagnostics.  The kernel check runs before warmup so its lowering is
    outside both clocks."""
    n_steps_checked = require_kernel(engine, label)
    hits0 = counter.cache_hits
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    compiles0 = counter.compiles
    verdicts = []
    t0 = time.perf_counter()
    for c in range(readings.shape[0]):
        verdicts.extend(engine.ingest(readings[c]))
    serve_s = time.perf_counter() - t0
    late_compiles = counter.compiles - compiles0
    stats = dev.memory_stats() or {}
    ring_bytes = sum(int(r.size) * r.dtype.itemsize for r in engine._rings)
    print(f"# smoke {label}: {engine.n_streams} plants, "
          f"{engine.stats.steps} verdict steps, {engine.stats.windows} "
          f"windows, warmup {compile_s:.3f} s ({counter.cache_hits - hits0} "
          f"persistent-cache hits), serve loop {serve_s:.3f} s over "
          f"{readings.shape[0]} cycles, {late_compiles} compiles after "
          f"warmup, {n_steps_checked} step programs carry tpu_custom_call, "
          f"device 0 bytes_in_use after serving "
          f"{stats.get('bytes_in_use', 'n/a')} (process peak so far "
          f"{stats.get('peak_bytes_in_use', 'n/a')}) vs ring arenas "
          f"{ring_bytes} logical bytes", flush=True)
    if late_compiles:
        raise AssertionError(f"{label}: {late_compiles} compiles after "
                             "warmup()")
    return {"verdicts": verdicts, "steps": engine.stats.steps}


def classifier(scheme: str, calib):
    from repro.core import quantize
    from repro.sim import build_detector
    model = build_detector()
    params = model.init_params(jax.random.PRNGKey(SEED))
    if scheme != "REAL":
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


def autoencoder(scheme: str, calib):
    from repro.core import quantize
    from repro.sim import build_autoencoder
    model = build_autoencoder()
    params = model.init_params(jax.random.PRNGKey(SEED + 1))
    if scheme != "REAL":
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


def calibration(wins: np.ndarray):
    """SINT activation-calibration samples: the first window of the first
    64 streams (benign: every scenario's attack starts later)."""
    from repro.core import quantize
    return quantize.calibration_samples(wins[0, :64], k=16)


def check(label: str, scheme: str, engine, readings: np.ndarray, refs,
          counter: CompileCounter, dev: jax.Device):
    """Serve ``readings`` and compare every verdict with ``refs``, a list of
    ``(name, Reference)`` covering the fleet's streams in order (one per
    model group).  Returns the chip's ``(pred, tail)`` arrays and the mask
    of windows excused as requantize near-ties, all (steps, streams)."""
    out = serve(engine, readings, label, counter, dev)
    pred, tail = verdict_arrays(out["verdicts"], refs[0][1].shape[0],
                                engine.n_streams)
    ties = np.zeros(pred.shape, bool)
    off = 0
    for name, ref in refs:
        cols = slice(off, off + ref.shape[1])
        ties[:, cols] = compare(f"{label}{name} vs reference", scheme,
                                pred[:, cols], tail[:, cols], ref)
        off += ref.shape[1]
    return pred, tail, ties


def phase_a(traffic, counter, dev):
    from repro.kernels import ops
    from repro.serving import StreamEngine
    readings = traffic.readings(4096)
    wins = windows(readings)
    model, params = classifier("SINT", calibration(wins))
    ref = Reference(ops.dense_stack(model, params), None, wins)
    engine = StreamEngine(model, params, n_streams=4096, fused=True,
                          shard=False)
    check("A classifier 4096", "SINT", engine, readings, [("", ref)],
          counter, dev)


def phase_b(traffic, counter, dev):
    from repro.kernels import ops
    from repro.serving import StreamEngine
    from repro.sim import ReconstructionHead
    readings = traffic.readings(1024)
    wins = windows(readings)
    calib = calibration(wins)
    for scheme in ("REAL", "SINT"):
        model, params = autoencoder(scheme, calib)
        stack = ops.dense_stack(model, params)
        thr = gap_threshold(Reference(stack, ReconstructionHead(), wins).tail)
        head = ReconstructionHead(threshold=thr)
        ref = Reference(stack, head, wins, threshold=thr)
        engine = StreamEngine(model, params, n_streams=1024, fused=True,
                              shard=False, head=head)
        check("B autoencoder 1024", scheme, engine, readings, [("", ref)],
              counter, dev)


def mixed_fleet(scheme: str, wins: np.ndarray, n_per: int):
    """The four-head fleet's ModelGroups, built the way the detection
    benchmark builds its megakernel rows (fixed thresholds, no training)."""
    sys.path.insert(0, ROOT)
    from benchmarks.detection_bench import mixed_group_detectors
    from repro.serving import ModelGroup
    calib = [np.asarray(c) for c in calibration(wins)]
    return [ModelGroup(name, m, p, n_per, head)
            for name, m, p, head in mixed_group_detectors(scheme, calib)]


def grouped_references(groups, wins: np.ndarray) -> list:
    """One Reference per model group, over the group's stream slice."""
    from repro.kernels import ops
    refs, off = [], 0
    for g in groups:
        refs.append((f" group {g.name}", Reference(
            ops.dense_stack(g.model, g.params), g.head,
            wins[:, off:off + g.n_streams],
            threshold=None if g.head is None else g.head.threshold)))
        off += g.n_streams
    return refs


def check_mega(groups, readings, refs, scheme, label, counter, dev, **kw):
    """Serve a mixed fleet through the grouped megakernel (one dispatch per
    verdict step) and check it against ``refs``."""
    from repro.serving import GroupedStreamEngine
    engine = GroupedStreamEngine(groups, megakernel=True, **kw)
    if engine.mega_reason is not None:
        raise AssertionError(f"{label}: {engine.mega_reason}")
    result = check(label, scheme, engine, readings, refs, counter, dev)
    if engine.stats.dispatches != engine.stats.steps:
        raise AssertionError(
            f"{label}: {engine.stats.dispatches} dispatches for "
            f"{engine.stats.steps} steps — not one megakernel per step")
    return engine, result


def phase_c(traffic, counter, dev):
    readings = traffic.readings(4 * 256)
    wins = windows(readings)
    for scheme in ("SINT", "REAL"):
        groups = mixed_fleet(scheme, wins, 256)
        check_mega(groups, readings, grouped_references(groups, wins),
                   scheme, "C four-head 4x256", counter, dev, shard=False)


# ---------------------------------------------------------------------------
# Four chips


def require_spread(engine, label: str, n: int) -> None:
    for ring in engine._rings:
        if len(ring.sharding.device_set) != n:
            raise AssertionError(
                f"{label}: ring arena spans {len(ring.sharding.device_set)} "
                f"devices, not {n}")


def four_chips(traffic, counter, dev):
    from repro.kernels import ops
    from repro.launch.mesh import make_fleet_mesh
    from repro.serving import StreamEngine
    n_dev = len(jax.devices())
    if n_dev != 4:
        sys.exit(f"chip_smoke --chips 4: JAX sees {n_dev} devices, not 4")
    readings = traffic.readings(4096)
    wins = windows(readings)
    model, params = classifier("SINT", calibration(wins))
    refs = [("", Reference(ops.dense_stack(model, params), None, wins))]

    def run_a(label, **kw):
        engine = StreamEngine(model, params, n_streams=4096, **kw)
        if engine.mesh is not None:
            require_spread(engine, label, 4)
        return check(label, "SINT", engine, readings, refs, counter, dev)

    base = run_a("A unsharded on device 0", fused=True, shard=False)
    for label, mesh in (("A data=4", make_fleet_mesh(4)),
                        ("A data=2 x model=2",
                         make_fleet_mesh(2, model_shards=2))):
        agree(f"{label} vs unsharded", run_a(label, mesh=mesh), base)

    mwins, mreadings = wins[:, :1024], readings[:, :1024]
    groups = mixed_fleet("SINT", mwins, 256)
    mrefs = grouped_references(groups, mwins)
    _, base = check_mega(groups, mreadings, mrefs, "SINT",
                         "C unsharded on device 0", counter, dev,
                         shard=False)
    engine, run = check_mega(groups, mreadings, mrefs, "SINT", "C data=4",
                             counter, dev, mesh=make_fleet_mesh(4))
    require_spread(engine, "C data=4", 4)
    agree("C data=4 megakernel vs unsharded", run, base)
    return n_dev


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on a four-chip host")
    args = ap.parse_args()
    dev = require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    counter = CompileCounter()
    print(f"# smoke run on {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    traffic = Traffic(SEED)
    print(f"# smoke traffic: {BASE_PLANTS} simulated plants x {N_CYCLES} "
          f"cycles in {time.perf_counter() - t0:.3f} s", flush=True)
    if args.chips == 4:
        count = four_chips(traffic, counter, dev)
    else:
        phase_a(traffic, counter, dev)
        phase_b(traffic, counter, dev)
        phase_c(traffic, counter, dev)
        count = len(jax.devices())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
