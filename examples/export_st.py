"""Export a detector to IEC 61131-3 Structured Text and prove it serves.

The paper's deployment artifact end to end: train (or, under ``--smoke``,
just initialize) a detector, port it to the ICSML core (§4.3), quantize it
(§6.1), calibrate the verdict head, emit one self-contained
``FUNCTION_BLOCK`` (``repro.codegen.st``) with the serving engines' ingest
normalization baked in — then *verify the export before anything ships*:
the in-suite ST emulator replays attack-scenario windows through the
emitted block while a ``StreamEngine`` serves the same raw readings, and
every per-window verdict is compared.

The verification contract (exit code 1 on any violation):

* SINT exports are **bit-exact against the reference semantics**: model
  outputs bit-match the eager two-op §6.1 oracle (``numpy_mlp_ref``),
  classifier ``CONF`` bit-matches the host softmax over those oracle
  logits, and score-head ``SCORE`` bit-matches the sequential-f32 MSE
  oracle.  Versus the live engine, ``PRED`` and ``THRESHOLD`` must agree
  exactly, and the f32 tails (``CONF``/``SCORE``) to 1e-4 relative — the
  engine's jitted XLA program FMA-contracts the requantize mul+add, so it
  sits an ulp off the two-op arithmetic a PLC actually executes.
* REAL exports: everything holds to epsilon (1e-4 relative), and verdicts
  may legitimately differ only when a score sits within epsilon of the
  threshold (reassociation error — reported, not failed).

Threshold calibration uses benign windows from the SAME simulated plants
over a DISJOINT later time range: the realistic held-out-trace workflow,
and what keeps the conservative-quantile cutoff (an actual calibration
score) from replaying at exactly ``score == threshold``.

Run:
  PYTHONPATH=src python examples/export_st.py --smoke --detector mlp
  PYTHONPATH=src python examples/export_st.py --smoke --detector ae --quant REAL
  PYTHONPATH=src python examples/export_st.py --detector ae --fast
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.codegen import st as stgen
from repro.codegen import verify as V
from repro.codegen.emulator import STFunctionBlock
from repro.configs import msf_detector as spec
from repro.core import porting, quantize
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import (build_dataset, get_scenario, recalibrate_threshold,
                       train_autoencoder, train_detector)
from repro.sim.detector import build_autoencoder, build_detector
from repro.sim.heads import ClassifierHead, softmax_np
from repro.sim.scenarios import fleet_readings


def calibration_windows(n_streams, replay_cycles, seed, stride):
    """Benign calibration windows from the replay's own plants (same fleet
    seed) over a disjoint later time range — held-out normal traces."""
    horizon = replay_cycles + 60 + spec.WINDOW + 8 * stride
    raw = fleet_readings(n_streams, horizon,
                         names=["baseline"] * n_streams, seed=seed)
    norm = ((np.asarray(raw, np.float32)
             - np.asarray(spec.NORM_MEAN, np.float32))
            / np.asarray(spec.NORM_STD, np.float32))
    tail = norm[replay_cycles + 60:]
    return np.concatenate([V.stream_windows(tail[:, s, :], spec.WINDOW,
                                            stride)
                           for s in range(n_streams)])


# Init seeds of the untrained smoke detectors.  Export correctness does not
# depend on them, but the replay should show both verdicts: with JAX's
# threefry stream, the classifier drawn from key 0 puts every replay window
# in class 0, while key 2 flags the attack windows and passes the baseline.
SMOKE_SEEDS = {"mlp": 2, "ae": 1}


def smoke_detector(kind, quant, calib_wins):
    """Untrained (init-params) detector — the CI path: export correctness
    is a property of the arithmetic, not of detection quality."""
    model = build_detector() if kind == "mlp" else build_autoencoder()
    params = model.init_params(jax.random.PRNGKey(SMOKE_SEEDS[kind]))
    if quant != "REAL":
        params = quantize.quantize_params(
            model, params, quant,
            calibration=quantize.calibration_samples(calib_wins, k=16))
    if kind == "mlp":
        return model, params, ClassifierHead()
    head, _ = recalibrate_threshold(model, params, calib_wins)
    return model, params, head


def trained_detector(kind, quant, calib_wins, fast):
    """The real workflow: train -> port -> quantize -> calibrate on the
    held-out benign scenario windows."""
    scale = 0.2 if fast else 0.5
    x, y = build_dataset(normal_cycles=int(42_000 * scale),
                         attack_cycles=int(5_700 * scale), stride=8, seed=0,
                         jitter=0.015, jitter_plants=4)
    epochs = 30 if fast else 60
    if kind == "ae":
        model, res = train_autoencoder(x, y, epochs=epochs, patience=8,
                                       lr=1e-3)
    else:
        model, res = train_detector(x, y, epochs=epochs, patience=8, lr=1e-3)
    with tempfile.TemporaryDirectory() as tmp:
        model, params = porting.port_mlp(model, res.params, tmp)
    if quant != "REAL":
        params = quantize.quantize_params(
            model, params, quant,
            calibration=quantize.calibration_samples(x, y))
    if kind == "mlp":
        return model, params, ClassifierHead()
    head, _ = recalibrate_threshold(model, params, calib_wins)
    return model, params, head


def verify_export(export, model, params, head, raw, stride):
    """Replay raw fleet readings through engine and emulator; return a
    result dict (printed by main) with a ``failures`` count."""
    n_cycles, n_streams, _ = raw.shape
    sint = export.scheme == "SINT"
    engine_verdicts = V.run_engine(model, params, raw, stride=stride,
                                   head=head)
    fb = STFunctionBlock(export.text)
    emulated = {s: V.emulate_stream(export, raw[:, s, :], stride=stride,
                                    fb=fb)
                for s in range(n_streams)}
    norm = ((np.asarray(raw, np.float32)
             - np.asarray(spec.NORM_MEAN, np.float32))
            / np.asarray(spec.NORM_STD, np.float32))
    # The bit-oracle is the eager two-op reference; the engine's jitted
    # program agrees only to an ulp (XLA contracts the requantize mul+add
    # into an FMA once biases are nonzero), so engine-side f32 tails are
    # compared to epsilon while PRED/THRESHOLD stay exact.
    stack = ops.dense_stack(model, params)
    oracle_y = {s: V.numpy_mlp_ref(
        V.stream_windows(norm[:, s, :], export.window, stride), stack)
        for s in range(n_streams)}

    failures = borderline = 0
    n = 0
    max_body = 0.0
    for v in engine_verdicts:
        em = emulated[v.stream]
        idx = int(np.searchsorted(em["cycle"], v.cycle))
        assert em["cycle"][idx] == v.cycle
        n += 1
        # Body: emulated Y vs the per-layer JAX oracle.
        ydiff = float(np.abs(np.float32(em["Y"][idx])
                             - oracle_y[v.stream][idx]).max())
        max_body = max(max_body, ydiff)
        scale_y = 1.0 + float(np.abs(oracle_y[v.stream][idx]).max())
        if (sint and ydiff != 0.0) or (not sint
                                       and ydiff > 1e-5 * scale_y):
            failures += 1
            continue
        if export.head_name == "classifier":
            logits = oracle_y[v.stream][idx]
            oracle_conf = np.float32(
                softmax_np(logits[None])[0, int(np.argmax(logits))])
            conf = np.float32(em["CONF"][idx])
            if int(em["PRED"][idx]) != v.pred:
                failures += 1
            elif sint and conf != oracle_conf:
                failures += 1          # bit contract vs the oracle logits
            elif not np.isclose(float(conf), v.prob, rtol=1e-4):
                failures += 1          # epsilon vs the engine's softmax
        else:
            sc = float(em["SCORE"][idx])
            thr_ok = float(np.float32(em["THRESHOLD"][idx])) == np.float32(
                v.threshold)
            if not thr_ok or not np.isclose(sc, v.score, rtol=1e-4):
                failures += 1
                continue
            if sint:
                seq = V.sequential_f32_mse(
                    oracle_y[v.stream][idx:idx + 1],
                    V.stream_windows(norm[:, v.stream, :], export.window,
                                     stride)[idx:idx + 1])[0]
                if np.float32(sc) != seq:
                    failures += 1
                    continue
            if int(em["PRED"][idx]) != v.pred:
                if sint or abs(sc - v.threshold) > 1e-5 * v.threshold:
                    failures += 1
                else:
                    borderline += 1
    return {"windows": n, "failures": failures, "borderline": borderline,
            "max_body_diff": max_body,
            "anomalous": sum(v.pred != 0 for v in engine_verdicts)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--detector", default="mlp", choices=("mlp", "ae"))
    ap.add_argument("--quant", default="SINT", choices=("REAL", "SINT"))
    ap.add_argument("--scenarios",
                    default="baseline,tb0-spoof,drift-then-spoof,steam-pulse",
                    help="comma-separated replay scenarios (one stream each;"
                         " includes a composed multi-attack by default)")
    ap.add_argument("--cycles", type=int, default=460,
                    help="replay length (default wraps the serving ring "
                         "more than twice)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out-dir", default="st-out",
                    help="directory the .st file is written into")
    ap.add_argument("--smoke", action="store_true",
                    help="skip training: export an init-params detector "
                         "(the arithmetic contract is training-independent)")
    ap.add_argument("--fast", action="store_true",
                    help="small training budget (ignored with --smoke)")
    args = ap.parse_args()

    names = [s.strip() for s in args.scenarios.split(",")]
    for nm in names:
        get_scenario(nm)
    stride = spec.STRIDE
    raw = fleet_readings(len(names), args.cycles, names=names,
                         seed=args.seed)

    print(f"== calibration (held-out benign windows, same plants) ==")
    calib = calibration_windows(len(names), args.cycles, args.seed, stride)
    print(f"{calib.shape[0]} windows x {calib.shape[1]}")

    if args.smoke:
        print(f"== init-params {args.detector} ({args.quant}, --smoke) ==")
        model, params, head = smoke_detector(args.detector, args.quant,
                                             calib)
    else:
        print(f"== training {args.detector} ({args.quant}) ==")
        model, params, head = trained_detector(args.detector, args.quant,
                                               calib, args.fast)
    if getattr(head, "threshold", None) is not None:
        print(f"calibrated threshold {head.threshold:.6g}")

    fb_name = f"{args.detector}_{args.quant}".upper()
    export = stgen.export_st(model, params, head=head, name=fb_name,
                             normalize=(spec.NORM_MEAN, spec.NORM_STD))
    os.makedirs(args.out_dir, exist_ok=True)
    path = os.path.join(args.out_dir, f"{fb_name.lower()}.st")
    with open(path, "w") as f:
        f.write(export.text)
    print(f"== emitted {path} ==")
    print(f"{export.scheme} scheme, {len(export.text.splitlines())} lines, "
          f"window {export.window} readings, verdict outputs "
          f"{export.verdict_outputs}")

    print(f"== replaying {len(names)} streams x {args.cycles} cycles "
          f"through engine + ST emulator ==")
    t0 = time.time()
    res = verify_export(export, model, params, head, raw, stride)
    contract = ("bit-exact (SINT)" if export.scheme == "SINT"
                else "epsilon (REAL, 1e-4 rel)")
    print(f"windows compared : {res['windows']} "
          f"({res['anomalous']} anomalous verdicts)")
    print(f"max body |diff|  : {res['max_body_diff']:.3g}")
    print(f"borderline       : {res['borderline']} "
          f"(REAL-only: score within epsilon of threshold)")
    print(f"verdict parity   : {res['windows'] - res['failures']}"
          f"/{res['windows']} under the {contract} contract "
          f"[{time.time() - t0:.1f}s]")
    if res["failures"]:
        print(f"FAILED: {res['failures']} windows violate the contract")
        sys.exit(1)
    print("OK: exported ST serves identically to the fleet engine")


if __name__ == "__main__":
    enable_compile_cache()
    main()
