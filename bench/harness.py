"""One run of one cell: set up, measure, check, report.

A run is one process.  It sets up (imports and device init, the traffic
pool, the weights, the engine, ``warmup()``, the ring fill), then hands the
engine scan cycles back to back for the window, as a monitoring consumer
would: it reads the alarm (``pred``) of every verdict and keeps the verdict
lists of a sample of steps, drawn from the seed.  After the window it reads
the device's peak memory, frees the engine and compares the sampled steps
with the reference.  The last line of standard output is the result; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of the result.

The harness consumes ``ingest()``'s return as a sequence of rows with
``stream``, ``cycle``, ``pred`` and ``prob`` (classifier) or ``score``
(score heads), and, where the configuration sets ``adapt``, each score
head's ``threshold``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference as R
from bench import reference_adapt as RA
from bench import reference_real as RR
from bench import trace as T
from bench import traffic as TR
from bench import weights as WT
from bench import work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Fixed paths inside the checkout: a compile cache that moves never hits.
CACHE_DIR = os.path.join(".bench_cache", "jax")
TRACE_DIR = os.path.join(".bench_cache", "trace")
# SINT activation scales come from this many benign windows of the traffic.
CALIBRATION_WINDOWS = 16
# A score head's threshold flags about a tenth of the first windows; an
# adapting head recalibrates to the same false-positive rate.
THRESHOLD_QUANTILE = 0.9
TARGET_FPR = 1 - THRESHOLD_QUANTILE
# The comparison covers at least this many windows, in at least MIN_STEPS
# sampled verdict steps.
SAMPLE_WINDOWS = 65536
MIN_STEPS = 8
# Verdict steps after the ring fill, before the window opens.
SETTLE_STEPS = 2
# Per arithmetic of a configuration (its ``scheme``): the Dense parameters
# the engine is handed, and the reference's group class.
SCHEMES = {
    "SINT": (("qw", "w_scale", "x_scale", "b"), R.GroupReference),
    "REAL": (("w", "b"), RR.GroupReference),
}


# ---------------------------------------------------------------------------
# Discovery


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    def reader(self, metric: str) -> Callable:
        return load_reader(self.root, metric)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def scheme(config: dict) -> tuple:
    """``(parameter keys, reference group class)`` of the configuration's
    arithmetic; a scheme the benchmark has no reference for raises."""
    try:
        return SCHEMES[config["scheme"]]
    except KeyError:
        raise ValueError(f"config {config.get('name')!r}: scheme "
                         f"{config.get('scheme')!r} is not one of "
                         f"{sorted(SCHEMES)}") from None


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix and the metrics it reports."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = _json(os.path.join(root, c["file"]))
    scheme(config)
    RA.policy(config)
    mix = TR.validate(_json(os.path.join(root, "bench", "traffic",
                                         w["traffic"] + ".json")),
                      w["traffic"])
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def load_reader(root: str, metric: str) -> Callable:
    """``read(ctx)`` of ``<root>/bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Devices


def devices(cell: Cell):
    """The chips the cell runs on; exits without a result where JAX finds
    no accelerator or another number of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) != cell.chips:
        sys.exit(f"bench: cell {cell.name} asks for {cell.chips} chips, JAX "
                 f"sees {len(devs)}")
    return devs


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it however quick to compile, and never evicted (an
    evicting cache set up by the environment keeps files of its own beside
    the entries, and fails its writes where they are missing)."""
    import jax
    path = os.path.join(root, CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits through JAX's
    monitoring events."""

    def __init__(self):
        import jax
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


# ---------------------------------------------------------------------------
# Set-up


def calibration_windows(config: dict, group: dict, pool: np.ndarray,
                        plants: slice) -> np.ndarray:
    """The group's SINT calibration windows: the first complete window of
    evenly spaced plants of the group, as its model sees them.  They are
    drawn under every scheme, so a seed gives a REAL fleet the very float
    weights that a SINT fleet quantizes."""
    k = CALIBRATION_WINDOWS
    win = R.windows(pool, config, int(config["window"]) - 1, plants)
    idx = np.linspace(0, len(win) - 1, min(k, len(win))).astype(int)
    return win[idx, :int(group["widths"][0])]


def score_thresholds(config: dict, host_layers, pool: np.ndarray) -> list:
    """Per group, the threshold a score head flags about
    ``1 - THRESHOLD_QUANTILE`` of the first windows at (None for a
    classifier), from the reference of the configuration's scheme."""
    _, reference = scheme(config)
    win = R.windows(pool, config, int(config["window"]) - 1)
    out = []
    for g, sl, layers in zip(config["groups"],
                             R.group_slices(config, pool.shape[1]),
                             host_layers):
        if g["head"] == "classifier":
            out.append(None)
            continue
        _, score = reference(g, config, layers)(win[sl])
        out.append(R.thresholds(score, THRESHOLD_QUANTILE))
    return out


def build_engine(config: dict, plants: int, device_layers, thresholds):
    """The system under test, built from the configuration with the
    program's own defaults, handed the Dense parameters of its scheme; its
    score heads adapt where the configuration sets ``adapt``."""
    keys, _ = scheme(config)
    adapt = RA.policy(config)
    from repro.core.layers import Dense, Input
    from repro.core.model import sequential
    from repro.serving import (AdaptConfig, GroupedStreamEngine, ModelGroup,
                               StreamEngine)
    from repro.sim.heads import ForecastHead, MarginHead, ReconstructionHead

    per = plants // len(config["groups"])
    units = []
    for g, layers, thr in zip(config["groups"], device_layers, thresholds):
        widths, acts = g["widths"], g["activations"]
        model = sequential(
            [Input()] + [Dense(units=int(n), activation=a)
                         for n, a in zip(widths[1:], acts)],
            (int(widths[0]),))
        params, it = {}, iter(layers)
        for node in model.graph.nodes:
            if isinstance(node.layer, Dense):
                p = next(it)
                params[node.uid] = {k: p[k] for k in keys}
            else:
                params[node.uid] = {}
        score = dict(threshold=thr, target_fpr=TARGET_FPR)
        head = {"classifier": lambda: None,
                "reconstruction": lambda: ReconstructionHead(**score),
                "margin": lambda: MarginHead(
                    center=(0.0,) * int(widths[-1]), **score),
                "forecast": lambda: ForecastHead(
                    n_features=int(config["n_features"]), **score),
                }[g["head"]]()
        unit_adapt = (None if adapt is None or head is None
                      else AdaptConfig(**adapt))
        units.append((g["name"], model, params, head, unit_adapt))
    common = dict(n_features=int(config["n_features"]),
                  stride=int(config["stride"]),
                  deadline_s=float(config["deadline_s"]),
                  norm_mean=tuple(config["norm_mean"]),
                  norm_std=tuple(config["norm_std"]), async_depth=0)
    if config["engine"] == "stream":
        (_, model, params, head, unit_adapt), = units
        return StreamEngine(model, params, n_streams=plants, head=head,
                            adapt=unit_adapt, **common)
    if config["engine"] == "grouped":
        return GroupedStreamEngine(
            [ModelGroup(n, m, p, per, h, adapt=a)
             for n, m, p, h, a in units], **common)
    raise ValueError(f"unknown engine {config['engine']!r}")


# ---------------------------------------------------------------------------
# The window


class Sampler:
    """A uniform sample of ``k`` verdict steps (Algorithm R), drawn from the
    seed; keeps each sampled step's verdict list."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.kept: List[tuple] = []

    def offer(self, cycle: int, verdicts) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append((cycle, verdicts))
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.kept[j] = (cycle, verdicts)


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    steps: int = 0
    windows: int = 0
    missing: int = 0
    alarms: int = 0
    verdict_s: List[float] = dataclasses.field(default_factory=list)
    nonverdict_s: List[float] = dataclasses.field(default_factory=list)


def drive(engine, pool: np.ndarray, config: dict, first: int,
          seconds: float, sampler: Optional[Sampler],
          span=contextlib.nullcontext) -> Window:
    """Hand cycles ``first, first + 1, ...`` of the pool to ``ingest()``
    back to back until the first verdict return at or after ``seconds``."""
    plants, n_pool = pool.shape[1], pool.shape[0]
    w, s = int(config["window"]), int(config["stride"])
    out = Window()
    perf = time.perf_counter
    c = first
    t_start = t1 = perf()
    while True:
        readings = pool[c % n_pool]
        if c + 1 >= w and (c + 1 - w) % s == 0:
            with span("ingest.verdict"):
                t0 = perf()
                verdicts = engine.ingest(readings)
                t1 = perf()
            with span("harness"):
                out.verdict_s.append(t1 - t0)
                out.steps += 1
                out.windows += len(verdicts)
                out.missing += max(0, plants - len(verdicts))
                out.alarms += sum(v.pred for v in verdicts)
                if sampler is not None:
                    sampler.offer(c, verdicts)
            if t1 - t_start >= seconds:
                break
        else:
            with span("ingest.nonverdict"):
                t0 = perf()
                verdicts = engine.ingest(readings)
                t1 = perf()
            with span("harness"):
                out.nonverdict_s.append(t1 - t0)
                # Sync serving returns verdicts only at verdict cycles.
                out.missing += len(verdicts)
        c += 1
    out.seconds = t1 - t_start
    return out


def fill(engine, pool: np.ndarray, config: dict) -> int:
    """Fill every ring (the first verdict step) and run ``SETTLE_STEPS``
    more verdict steps; returns the next cycle."""
    end = int(config["window"]) + int(config["stride"]) * SETTLE_STEPS
    for c in range(end):
        engine.ingest(pool[c % pool.shape[0]])
    return end


def step_arrays(verdicts, cycle: int, plants: int):
    """(pred, tail, seen) of one sampled step in stream order: ``seen``
    marks the plants whose verdict came back once, for this cycle."""
    pred = np.zeros(plants, np.int64)
    tail = np.full(plants, np.nan)
    seen = np.zeros(plants, bool)
    dup = np.zeros(plants, bool)
    for v in verdicts:
        i = int(v.stream)
        if v.cycle != cycle or not 0 <= i < plants:
            continue
        dup[i] |= seen[i]
        seen[i] = True
        pred[i] = int(v.pred)
        tail[i] = float(v.prob if v.prob is not None else v.score)
    return pred, tail, seen & ~dup


def step_thresholds(verdicts, cycle: int, config: dict, plants: int):
    """Each group's threshold as its rows report it in one sampled step
    (None for a classifier), the value most of the group's rows give; and
    the plants whose row gives another, or none."""
    thr = np.full(plants, np.nan)
    for v in verdicts:
        i = int(v.stream)
        if v.cycle == cycle and 0 <= i < plants and v.threshold is not None:
            thr[i] = float(v.threshold)
    off = np.zeros(plants, bool)
    out = []
    for g, sl in zip(config["groups"], R.group_slices(config, plants)):
        if g["head"] == "classifier":
            out.append(None)
            continue
        values, counts = np.unique(thr[sl], return_counts=True)
        top = values[np.argmax(counts)]
        out.append(float(top))
        off[sl] = thr[sl] != top
    return out, off


# ---------------------------------------------------------------------------
# A run


@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    config: dict
    chips: int
    plants: int
    setup_s: float
    window: Window
    peaks: dict
    trace: Optional[dict] = None
    lo: int = 0
    hi: int = 0


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_process: float) -> dict:
    """One run of ``cell``; returns the result object."""
    phases = {}
    t = time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    import jax
    enable_cache(cell.root)
    devs = devices(cell)
    counter = CompileCounter()
    phase("import_and_devices")
    config, mix = cell.config, cell.traffic
    plants = int(mix["plants"])
    pool = TR.pool(mix, config, seed)
    phase("traffic_pool")
    slices = R.group_slices(config, plants)
    calibs = [calibration_windows(config, g, pool, sl)
              for g, sl in zip(config["groups"], slices)]
    device_layers = WT.make(config, seed, calibs)
    host_layers = WT.to_host(device_layers)
    phase("weights")
    thresholds = score_thresholds(config, host_layers, pool)
    phase("thresholds")
    engine = build_engine(config, plants, device_layers, thresholds)
    phase("engine")
    compiles0 = counter.compiles
    engine.warmup()
    phase("warmup")
    phases["warmup_compiles"] = counter.compiles - compiles0
    phases["cache_hits"] = counter.cache_hits
    first = fill(engine, pool, config)
    phase("ring_fill")
    k = max(MIN_STEPS, math.ceil(SAMPLE_WINDOWS / plants))
    sampler = Sampler(k, TR.rng_for(seed, "sample"))
    span = contextlib.nullcontext
    if traced:
        trace_dir = os.path.join(cell.root, TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    compiles0 = counter.compiles
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    phases["before_run"] = setup_s - sum(
        v for k, v in phases.items()
        if k not in ("warmup_compiles", "cache_hits"))
    gc0 = [g["collections"] for g in gc.get_stats()]
    win = drive(engine, pool, config, first, seconds, sampler, span)
    gc_runs = [g["collections"] - c for g, c in zip(gc.get_stats(), gc0)]
    compiles = counter.compiles - compiles0
    if traced:
        jax.profiler.stop_trace()
    if compiles:
        raise RuntimeError(f"{compiles} compiles inside the measured window")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    # The program's state goes before the reference runs.
    del engine, device_layers
    gc.collect()

    t_check = time.perf_counter()
    adapt = RA.policy(config)
    failed = win.missing
    steps: Dict[int, tuple] = {}
    reported: Dict[int, list] = {}
    for cycle, verdicts in sampler.kept:
        # A sampled step's due verdicts are checked one by one: each that
        # did not come back once, for its plant and cycle, has failed.
        pred, tail, ok = step_arrays(verdicts, cycle, plants)
        failed += int((~ok).sum()) - max(0, plants - len(verdicts))
        if adapt is not None:
            # So has each whose threshold is not its unit's.
            thr, off = step_thresholds(verdicts, cycle, config, plants)
            failed += int((off & ok).sum())
            ok &= ~off
        if ok.all():
            steps[cycle] = (pred, tail)
            if adapt is not None:
                reported[cycle] = thr
    limit = float(config["tail_rel_err"])
    refs = references(config, host_layers, thresholds)
    if adapt is None:
        tally = R.compare_steps(config, pool, refs, steps)
    else:
        step_thr, pools = RA.replay(
            config, pool, refs, thresholds, TARGET_FPR,
            max(steps, default=int(config["window"]) - 1), keep=set(steps))
        tally, band = RA.compare_steps(config, pool, refs, steps, step_thr,
                                       reported)
    # JSON has no infinity: a NaN or infinite answer reads as the largest
    # float.
    checks = {
        "failed": {"value": failed, "limit": 0},
        "pred_off": {"value": tally.pred_off, "limit": 0},
        "tail_rel_err": {"value": min(tally.tail_rel_err, sys.float_info.max),
                         "limit": limit},
    }
    if adapt is not None:
        checks["thr_rel_err"] = {
            "value": min(RA.thr_rel_err(reported, step_thr),
                         sys.float_info.max),
            "limit": float(config["thr_rel_err"])}
    correct = (all(v["value"] <= v["limit"] for v in checks.values())
               and tally.windows > 0)
    check_s = time.perf_counter() - t_check

    ctx = Context(config=config, chips=cell.chips, plants=plants,
                  setup_s=setup_s, window=win,
                  peaks=work.peaks(devs[0].device_kind))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": win.steps * plants, "failed": int(failed)}
    breakdown = None
    if traced:
        ctx.trace = T.load(T.find_xplane(trace_dir))
        ctx.lo, ctx.hi = T.window(ctx.trace)
        used = [n for n in ctx.trace["devices"]]
        busy = [T.busy_ns(ctx.trace["devices"][n], ctx.lo, ctx.hi)
                for n in used]
        device["busy_s"] = (sum(busy) / len(busy) / 1e9) if busy else 0.0
        device["window_s"] = (ctx.hi - ctx.lo) / 1e9
        busiest, _ = T.busiest(ctx.trace, ctx.lo, ctx.hi)
        breakdown = {"device_ops": T.top_ops(ctx.trace, ctx.lo, ctx.hi),
                     "idle_gaps": T.top_gaps(ctx.trace, busiest, ctx.lo,
                                             ctx.hi)}
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    values = {}
    for m in metrics:
        v = cell.reader(m["name"])(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = values
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_diagnostics"] = {
        "steps": win.steps, "windows": win.windows, "alarms": win.alarms,
        "window_s": win.seconds, "setup_s": setup_s,
        "sampled_steps": len(steps), "compared_windows": tally.windows,
        "windows_with_near_ties": tally.with_ties,
        "near_ties_excused": tally.near_ties,
        "borderline_flips": tally.borderline,
        "rel_over": {str(k): v for k, v in tally.over.items()},
        "setup_phases": phases, "check_s": check_s,
        "first_off": tally.first_off, "thresholds": thresholds,
        "compiles_in_window": compiles, "gc_collections": gc_runs,
        "verdict_ms_percentiles": {
            str(q): _percentile(win.verdict_s, q) * 1e3
            for q in (50, 90, 95, 99, 100)} if win.verdict_s else {}}
    # For the readings of the control, which compare the reference at a
    # lower precision on the same windows; never printed.
    result["_state"] = {"pool": pool, "host_layers": host_layers,
                        "thresholds": thresholds, "steps": steps}
    if adapt is not None:
        last = max(steps, default=None)
        result["_diagnostics"].update(
            live_thresholds=None if last is None else
            {"program": reported[last], "reference": step_thr[last]},
            thr_rank_gap=RA.rank_gap(reported, step_thr, pools),
            threshold_band_flips=band)
        result["_state"].update(step_thresholds=step_thr, reported=reported,
                                pools=pools)
    return result


def references(config: dict, host_layers, thresholds) -> list:
    """Per group, the reference of the configuration's scheme."""
    _, reference = scheme(config)
    return [reference(g, config, layers, threshold=thr)
            for g, layers, thr in zip(config["groups"], host_layers,
                                      thresholds)]


def report(result: dict) -> None:
    """Diagnostics, then the numbers compared beside their limits, on
    standard error; the result as the last line of standard output."""
    result.pop("_state", None)
    diag = result.pop("_diagnostics")
    print("bench: " + json.dumps(diag), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
