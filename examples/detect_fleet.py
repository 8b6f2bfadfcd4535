"""Fleet-scale anomaly detection over the MSF scenario library.

Trains a detector (established-framework stage), ports it to the ICSML
core (§4.3), optionally quantizes it (§6.1), then serves a heterogeneous
fleet of simulated plants — each running a named scenario from
``repro.sim.scenarios`` — through the batched ``StreamEngine``: per-stream
ring-buffer windows, one jitted donated detector step per verdict cadence,
per-window latency/deadline accounting.

``--detector`` picks the workload: ``mlp`` is the paper's supervised
400-64-32-16-2 classifier; ``ae`` is the unsupervised 400-64-16-64-400
autoencoder — trained on benign windows only, anomaly score = per-window
reconstruction error, verdict threshold calibrated to
``spec.AE_TARGET_FPR`` false positives on held-out normal traces (and
re-calibrated on the quantized model when ``--quant`` is not REAL, so the
served scores match the served arithmetic).  Both serve through the same
fused single-dispatch detector step.

``--mixed`` serves a *heterogeneous model-group fleet* instead: the plants
are partitioned into four model groups — supervised classifier,
reconstruction autoencoder, one-class margin detector, next-step
forecaster — each group carrying its own trained model, verdict head,
calibrated threshold and quantization scales, all batched by ONE
``GroupedStreamEngine`` whose jitted step runs one fused dispatch per
group per verdict cadence.

With ``--devices N`` the engine shards the fleet's stream axis over an
N-device ``("data",)`` mesh — on a CPU host the devices are fanned out via
``XLA_FLAGS=--xla_force_host_platform_device_count`` (set here before jax
loads), on real hardware the mesh maps onto the visible accelerators.

``--async`` serves double-buffered (``async_depth=1``): each ready
boundary dispatches the detector step and returns to ingesting the next
scan cycle while the device works, harvesting the previous step's
verdicts — bit-identical to synchronous serving, one boundary later
(``flush()`` drains the last in-flight step).  After the serve it prints
a sync-vs-async sustained windows/s comparison on fresh engines.

``--drift`` overlays fleet-wide benign parameter drift (flash-gain decay +
warming seawater, the ``seasonal-drift`` physics) on every plant's scenario
and switches score-head detectors to **online threshold recalibration**
(``adapt=True``): the engine's live threshold then tracks the sliding
benign-score quantile instead of flooding with false alarms as the
operating point creeps away from the offline calibration.  The pooled
quantile assumes a mostly-benign fleet: sharp attacks overshoot the
headroom gate and stay out of the calibration pool, but serving the full
attack gauntlet under ``--drift`` puts a *sustained, slowly-ramping*
attack on nearly every stream — those ramp inside the headroom and get
absorbed into the live threshold (any self-calibrating detector's
poisoning window).  The drift demo is the mostly-benign + sharp-attack
mix below.

Run:
  PYTHONPATH=src python examples/detect_fleet.py --list
  PYTHONPATH=src python examples/detect_fleet.py --scenarios stealth-drift
  PYTHONPATH=src python examples/detect_fleet.py --plants 16 --quant SINT
  PYTHONPATH=src python examples/detect_fleet.py --plants 64 --devices 4
  PYTHONPATH=src python examples/detect_fleet.py --mixed --fast --plants 16
  PYTHONPATH=src python examples/detect_fleet.py --async --fast --plants 16
  PYTHONPATH=src python examples/detect_fleet.py --detector ae --drift \
      --scenarios baseline,seasonal-drift,tb0-spoof,wd-spoof --plants 16
"""

import argparse
import collections
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _fan_out_devices() -> int:
    """--devices must act before jax initializes: host-device fan-out only
    works through XLA_FLAGS at backend-creation time."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--devices", type=int, default=1)
    args, _ = ap.parse_known_args()
    if args.devices > 1 and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}").strip()
    return args.devices


_fan_out_devices()

import numpy as np

from repro.configs import msf_detector as spec
from repro.core import porting, quantize
from repro.launch.mesh import make_fleet_mesh
from repro.sim import (SCENARIOS, ParamDrift, build_dataset, build_fleet,
                       get_scenario, recalibrate_threshold, scenario_table,
                       train_autoencoder, train_detector, train_forecaster,
                       train_one_class)
from repro.sim.msf import SCAN_DT
from repro.serving import GroupedStreamEngine, ModelGroup, StreamEngine
from repro.launch.compile_cache import enable_compile_cache


def _budget(fast: bool, smoke: bool):
    """(normal_cycles, attack_cycles, epochs, patience) for a training run.
    ``--smoke`` is the CI-subprocess budget: just enough data/steps to prove
    the pipeline end to end in seconds, not a useful detector."""
    if smoke:
        # Floor: the score heads refuse to train/calibrate on < 768 benign
        # windows, and the mixed fleet trains an autoencoder too.
        return 5_200, 800, 2, 2
    scale = 0.2 if fast else 0.5
    return int(42_000 * scale), int(5_700 * scale), 30 if fast else 60, 8


def train_and_port(fast: bool, quant: str, detector: str, smoke: bool = False):
    normal, attack, epochs, patience = _budget(fast, smoke)
    print("== dataset + training (established-framework stage) ==")
    # jittered normal plants in training: the fleet is heterogeneous, and
    # per-plant operating-point spread must read as benign
    x, y = build_dataset(normal_cycles=normal, attack_cycles=attack,
                         stride=8, seed=0, jitter=0.015, jitter_plants=4)
    head = None
    if detector == "ae":
        model, res = train_autoencoder(x, y, epochs=epochs,
                                       patience=patience, lr=1e-3)
        head = res.head
        print(f"val mse {res.best_val_mse:.6f}  threshold {res.threshold:.6f}"
              f"  calib FPR {res.calib_fpr:.4f}"
              f"  attack-window detection {res.test_detection_rate:.4f}")
    else:
        model, res = train_detector(x, y, epochs=epochs,
                                    patience=patience, lr=1e-3)
        print(f"val acc {res.best_val_acc:.4f}  test acc {res.test_acc:.4f}")
    print("== porting to ICSML (§4.3) ==")
    with tempfile.TemporaryDirectory() as tmp:
        model, params = porting.port_mlp(model, res.params, tmp)
    if quant != "REAL":
        print(f"== quantizing to {quant} (§6.1) ==")
        # Activation scales from benign-trace ranges (quantize.py docstring:
        # weight absmax alone leaves the AE decoder's scales wildly off).
        calib = quantize.calibration_samples(x, y)
        params = quantize.quantize_params(model, params, quant,
                                          calibration=calib)
        if head is not None:
            # Re-calibrate the verdict threshold against the *quantized*
            # model's scores — on the same held-out normal windows the REAL
            # threshold came from (recalibrate_threshold owns that invariant).
            head, _ = recalibrate_threshold(model, params, res.calib_windows)
            print(f"re-calibrated {quant} threshold {head.threshold:.6f}")
    return model, params, head


def _port_and_quantize(model, res, head, quant, x, y):
    """Shared §4.3 port + §6.1 quantize + (score heads) threshold
    re-calibration against the quantized arithmetic."""
    with tempfile.TemporaryDirectory() as tmp:
        model, params = porting.port_mlp(model, res.params, tmp)
    if quant != "REAL":
        calib = quantize.calibration_samples(x, y)
        if head is not None:
            # Heads with non-identity window geometry (the forecaster) eat a
            # slice of the window; quantization scales must see the same view.
            calib = [head.prepare(c) for c in calib]
        params = quantize.quantize_params(model, params, quant,
                                          calibration=calib)
        if head is not None:
            head, _ = recalibrate_threshold(model, params, res.calib_windows,
                                            head=head)
    return model, params, head


def train_mixed(fast: bool, quant: str, smoke: bool = False):
    """Train/port/quantize all four detector types for the grouped fleet."""
    normal, attack, epochs, patience = _budget(fast, smoke)
    print("== dataset + training x4 (mixed model-group fleet) ==")
    x, y = build_dataset(normal_cycles=normal, attack_cycles=attack,
                         stride=8, seed=0, jitter=0.015, jitter_plants=4)
    trained = []
    model, res = train_detector(x, y, epochs=epochs, patience=patience,
                                lr=1e-3)
    print(f"  mlp:      val acc {res.best_val_acc:.4f}  "
          f"test acc {res.test_acc:.4f}")
    trained.append(("mlp", model, res, None))
    for name, trainer in (("ae", train_autoencoder),
                          ("margin", train_one_class),
                          ("forecast", train_forecaster)):
        model, res = trainer(x, y, epochs=epochs, patience=patience, lr=1e-3)
        print(f"  {name + ':':<9} threshold {res.threshold:.6f}  "
              f"calib FPR {res.calib_fpr:.4f}  "
              f"attack-window detection {res.test_detection_rate:.4f}")
        trained.append((name, model, res, res.head))
    print("== porting to ICSML (§4.3)"
          + (f" + quantizing to {quant} (§6.1)" if quant != "REAL" else "")
          + " ==")
    out = []
    for name, model, res, head in trained:
        model, params, head = _port_and_quantize(model, res, head, quant, x, y)
        if head is not None and quant != "REAL":
            print(f"  {name}: re-calibrated {quant} threshold "
                  f"{head.threshold:.6f}")
        out.append((name, model, params, head))
    return out


def sustained_side_by_side(make_engine, n_streams, n_cycles=800):
    """Sync-vs-async sustained windows/s under continuous per-cycle arrival.

    Fresh engines (built by ``make_engine(async_depth)``), synthetic normal
    readings (serving throughput is content-independent), ring fill
    untimed, ``flush()`` inside the timed region so every dispatched window
    is also harvested."""
    readings = (np.asarray(spec.NORM_MEAN, np.float32)
                + np.random.default_rng(0)
                .normal(size=(n_cycles, n_streams, spec.N_FEATURES))
                .astype(np.float32) * np.asarray(spec.NORM_STD, np.float32))
    wps = {}
    for depth in (0, 1):
        eng = make_engine(depth)
        eng.warmup()
        for c in range(min(spec.WINDOW, n_cycles)):
            eng.ingest(readings[c])
        eng.flush()
        w0 = eng.stats.windows
        t0 = time.perf_counter()
        for c in range(n_cycles):
            eng.ingest(readings[c])
        eng.flush()
        wps[depth] = (eng.stats.windows - w0) / (time.perf_counter() - t0)
    print(f"\nsustained throughput ({n_cycles} cycles, continuous arrival):")
    print(f"  sync   {wps[0]:>8.0f} windows/s")
    print(f"  async  {wps[1]:>8.0f} windows/s ({wps[1] / wps[0]:.2f}x, "
          f"double-buffered: ingest of cycle N+1 overlaps step N)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", default="all",
                    help="comma-separated scenario names, or 'all'")
    ap.add_argument("--plants", type=int, default=spec.FLEET_STREAMS)
    ap.add_argument("--cycles", type=int, default=1600)
    ap.add_argument("--quant", default="SINT",
                    choices=("REAL",) + quantize.SCHEMES)
    ap.add_argument("--detector", default="mlp", choices=("mlp", "ae"),
                    help="mlp: supervised §7 classifier; ae: unsupervised "
                         "reconstruction-error autoencoder")
    ap.add_argument("--mixed", action="store_true",
                    help="serve a heterogeneous model-group fleet "
                         "(classifier + autoencoder + margin + forecast "
                         "groups in one GroupedStreamEngine)")
    ap.add_argument("--jitter", type=float, default=None,
                    help="override per-scenario plant jitter")
    ap.add_argument("--drift", action="store_true",
                    help="overlay fleet-wide benign parameter drift and "
                         "enable streaming threshold recalibration on "
                         "score-head detectors")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true", help="small training budget")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-subprocess budget: tiny dataset, 2 epochs, and "
                         "(unless overridden) 4 plants x 240 cycles — proves "
                         "the pipeline, not the detector")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the fleet over this many devices "
                         "(host devices are fanned out automatically)")
    ap.add_argument("--async", dest="async_serve", action="store_true",
                    help="serve double-buffered (async_depth=1: verdicts "
                         "arrive one ready boundary late, bit-identical) "
                         "and print sync-vs-async sustained windows/s")
    ap.add_argument("--list", action="store_true",
                    help="print the scenario library and exit")
    args = ap.parse_args()

    if args.list:
        print(scenario_table())
        return

    if args.smoke:
        if args.plants == spec.FLEET_STREAMS:
            args.plants = 4
        if args.cycles == 1600:
            args.cycles = 240

    names = (list(SCENARIOS) if args.scenarios == "all"
             else [s.strip() for s in args.scenarios.split(",")])
    for n in names:
        get_scenario(n)   # fail fast on typos

    mesh = make_fleet_mesh(args.devices) if args.devices > 1 else None
    shard_note = (f", sharded over {args.devices} devices "
                  f"({-(-args.plants // args.devices)} streams/device)"
                  if mesh is not None else "")
    # Fleet-wide benign drift: the seasonal-drift physics overlaid on every
    # plant's scenario (attacks compose on top of the drifted base).
    drift = (ParamDrift({"k_flash": -0.08, "t_sea": 0.04},
                        start=300, ramp=1200) if args.drift else None)
    drift_note = ", drifting+adaptive" if args.drift else ""
    fleet = build_fleet(names, args.plants, seed=args.seed + 1000,
                        jitter=args.jitter, drift=drift)
    # --devices 1 pins sharding OFF even in a multi-device process, so the
    # flag always means what the serve header prints.
    shard_kw = {"mesh": mesh} if mesh is not None else {"shard": False}
    async_note = ", async double-buffered" if args.async_serve else ""
    if args.mixed:
        detectors = train_mixed(args.fast, args.quant, args.smoke)
        if args.plants < len(detectors):
            ap.error(f"--mixed needs at least {len(detectors)} plants")
        base, extra = divmod(args.plants, len(detectors))
        groups = [ModelGroup(name, model, params,
                             base + (1 if i < extra else 0), head,
                             adapt=args.drift and head is not None)
                  for i, (name, model, params, head) in enumerate(detectors)]

        def make_engine(depth):
            return GroupedStreamEngine(groups, async_depth=depth, **shard_kw)

        engine = make_engine(1 if args.async_serve else 0)
        split = " + ".join(f"{n}x{name}" for name, _, n in engine.groups)
        print(f"== serving {args.plants} plants x {args.cycles} cycles "
              f"(mixed: {split} / {args.quant}{shard_note}{drift_note}"
              f"{async_note}) ==")
    else:
        model, params, head = train_and_port(args.fast, args.quant,
                                             args.detector, args.smoke)
        if args.drift and head is None:
            print("note: --drift serves a drifting fleet, but the "
                  "classifier has no score threshold to recalibrate "
                  "(use --detector ae for adaptation)")

        def make_engine(depth):
            return StreamEngine(model, params, n_streams=args.plants,
                                head=head,
                                adapt=args.drift and head is not None or None,
                                async_depth=depth, **shard_kw)

        engine = make_engine(1 if args.async_serve else 0)
        print(f"== serving {args.plants} plants x {args.cycles} cycles "
              f"({args.detector}/{args.quant}{shard_note}{drift_note}"
              f"{async_note}) ==")
    engine.warmup()
    flagged = collections.defaultdict(list)   # stream -> attack-verdict cycles
    verdicts = engine.run(fleet, args.cycles)
    verdicts += engine.flush()   # async: drain the final in-flight step
    for v in verdicts:
        if v.pred != 0:
            flagged[v.stream].append(v.cycle)

    group_of = {}
    if args.mixed:
        for gname, off, n in engine.groups:
            for s in range(off, off + n):
                group_of[s] = gname
    gcol = f"{'group':<9} " if args.mixed else ""
    print(f"{'plant':<26} {gcol}{'onset':>6} {'first-flag':>10} "
          f"{'latency':>9} {'pre-onset FPs':>13}")
    for i, plant in enumerate(fleet):
        sc = get_scenario(plant.name.split("#")[0])
        onset = sc.onset
        cycles = flagged.get(i, [])
        g = f"{group_of[i]:<9} " if args.mixed else ""
        if onset is None:
            print(f"{plant.name:<26} {g}{'-':>6} {'-':>10} {'-':>9} "
                  f"{len(cycles):>13}")
            continue
        hits = [c for c in cycles if c >= onset]
        fps = len([c for c in cycles if c < onset])
        first = hits[0] if hits else None
        lat = f"{(first - onset) * SCAN_DT:.1f}s" if first is not None else "miss"
        print(f"{plant.name:<26} {g}{onset:>6} "
              f"{first if first is not None else 'miss':>10} {lat:>9} {fps:>13}")

    if args.mixed:
        gw = engine.group_windows()
        print("\nper-group verdicts: "
              + "  ".join(f"{k}={v}" for k, v in gw.items()))
    if args.drift:
        if args.mixed:
            moved = "  ".join(
                f"{k}={v:.6f}" for k, v in engine.live_thresholds().items()
                if v is not None)
            if moved:
                print(f"live thresholds after drift: {moved}")
        elif engine.live_threshold is not None:
            print(f"live threshold after drift: {engine.live_threshold:.6f} "
                  f"(offline calibration: {engine.head.threshold:.6f})")
    st = engine.stats
    print(f"\nserve stats: {st.steps} detector steps, {st.windows} windows, "
          f"{st.windows_per_s():.0f} windows/s | verdict latency "
          f"p50={st.latency_p(50) * 1e3:.1f}ms p99={st.latency_p(99) * 1e3:.1f}ms "
          f"| deadline({spec.DEADLINE_S * 1e3:.0f}ms) misses: "
          f"{st.deadline_misses}/{st.windows}")
    if args.async_serve:
        sustained_side_by_side(make_engine, args.plants)


if __name__ == "__main__":
    enable_compile_cache()
    main()
