"""§7 case study, end to end: ML-based anomaly detection for an MSF
desalination plant, running *on the controller* via the ICSML runtime.

Pipeline (paper §4.3 + §7):
  1. HITL data collection: simulate the plant + cascading PID, record the
     PLC's ADC readings (ARRBIN binary files).
  2. Train the 400-64-32-16-2 ReLU classifier in the 'established framework'.
  3. Extract weights -> binary files -> statically reconstruct in ICSML ->
     BINARR load (port_mlp), optionally with SINT quantization (§6.1).
  4. Deploy in the scan-cycle runtime as a sliding-window detector with
     multipart inference (§6.3) and inject an unseen attack: measure
     detection latency (paper: injected cycle 436, detected 486).
  5. Non-intrusiveness (§7.2): compare Wd statistics with/without defense.

Run:  PYTHONPATH=src python examples/casestudy_msf.py [--fast]
"""

import argparse
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp

from repro.core import ScanCycleRuntime, SlidingWindowDetector, porting, quantize
from repro.core.runtime import MultipartInference
from repro.sim import build_dataset, simulate, train_detector
from repro.sim.msf import SCAN_DT, CascadePID, adc
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller dataset")
    ap.add_argument("--quant", choices=("SINT", "INT", "DINT"))
    ap.add_argument("--segments", type=int, default=4,
                    help="multipart inference segments per window")
    args = ap.parse_args()

    # ---- 1+2. dataset + training ------------------------------------------
    scale = 0.25 if args.fast else 1.0
    print("== building dataset (HITL simulation) ==")
    x, y = build_dataset(normal_cycles=int(42_000 * scale),
                         attack_cycles=int(5_700 * scale),
                         stride=8, seed=0)
    print(f"dataset: {x.shape[0]} windows of {x.shape[1]} features, "
          f"{y.mean():.1%} attack")

    print("== training detector (established-framework stage) ==")
    model, res = train_detector(x, y, epochs=40 if args.fast else 120,
                                patience=10 if args.fast else 15, lr=1e-3)
    print(f"val acc {res.best_val_acc:.4f}  test acc {res.test_acc:.4f} "
          f"(paper: ~0.9368)")

    # ---- 3. port to ICSML ---------------------------------------------------
    print("== porting to ICSML (extract -> binary -> reconstruct -> load) ==")
    with tempfile.TemporaryDirectory() as tmp:
        ported_model, ported_params = porting.port_mlp(model, res.params, tmp)
    xq = jnp.asarray(x[:8])
    import jax
    ref_out = jax.vmap(model.apply, (None, 0))(res.params, xq)
    port_out = jax.vmap(ported_model.apply, (None, 0))(ported_params, xq)
    assert np.allclose(np.asarray(ref_out), np.asarray(port_out)), "port mismatch"
    print("ported model output bit-identical to trained model ✓")

    if args.quant:
        print(f"== quantizing ported model to {args.quant} (§6.1) ==")
        calib = [jnp.asarray(x[i]) for i in range(0, 256, 8)]
        ported_params = quantize.quantize_params(
            ported_model, ported_params, args.quant, calibration=calib)
        qacc = np.mean(
            np.argmax(np.asarray(jax.vmap(ported_model.apply, (None, 0))(
                ported_params, jnp.asarray(x[-512:]))), -1) == y[-512:])
        print(f"quantized accuracy on tail split: {qacc:.4f}")

    # ---- 4. on-PLC deployment: attack detection -----------------------------
    print("== scan-cycle deployment: attack injection + detection ==")
    detector = SlidingWindowDetector(ported_model, ported_params,
                                     window=200, n_features=2,
                                     n_segments=args.segments)
    attack_start = 800
    detections = []

    def hook(cycle, reading):
        # normalize like build_dataset
        r = np.array([(reading[0] - 89.6) / 2.0,
                      (reading[1] - 19.18) / 0.5], np.float32)
        detector.push(r)
        result = detector.tick(cycle)
        if result is not None:
            done_cycle, pred, latency = result
            if pred != 0:
                detections.append((done_cycle, latency))

    # unseen attack parameters: seed never used during dataset generation
    simulate(1600, attack_id=2, attack_start=attack_start, seed=777,
             defense_hook=hook)
    if detections:
        first = detections[0][0]
        print(f"attack injected at cycle {attack_start}, first detection at "
              f"cycle {first} -> latency {(first - attack_start) * SCAN_DT:.1f}s "
              f"(paper: 5.0s)")
    else:
        print("attack NOT detected (unexpected)")

    # ---- 5. non-intrusiveness (§7.2) ----------------------------------------
    print("== non-intrusiveness: Wd stats with / without defense ==")
    tr_off = simulate(3000, seed=123)
    det2 = SlidingWindowDetector(ported_model, ported_params, window=200,
                                 n_features=2, n_segments=args.segments)

    def hook2(cycle, reading):
        det2.push(np.array([(reading[0] - 89.6) / 2.0,
                            (reading[1] - 19.18) / 0.5], np.float32))
        det2.tick(cycle)

    tr_on = simulate(3000, seed=123, defense_hook=hook2)
    seg = slice(1500, None)
    print(f"  defense OFF: Wd mean {tr_off.wd_meas[seg].mean():.4f} "
          f"std {tr_off.wd_meas[seg].std():.2e}")
    print(f"  defense ON : Wd mean {tr_on.wd_meas[seg].mean():.4f} "
          f"std {tr_on.wd_meas[seg].std():.2e}")
    same = np.allclose(tr_off.wd_meas, tr_on.wd_meas)
    print(f"  process output identical: {same} (defense never touches control)")

    # multipart cost profile
    mi = MultipartInference(ported_model, ported_params, args.segments)
    print(f"multipart segments: {args.segments}, per-segment FLOPs "
          f"{mi.segment_flops()}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
