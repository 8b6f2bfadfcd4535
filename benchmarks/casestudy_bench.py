"""§7: case-study metrics — detection accuracy, detection latency over the
seven attack families, and §7.2 non-intrusiveness (Wd statistics with and
without the defense in the loop)."""

from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.core import SlidingWindowDetector, porting
from repro.sim import build_dataset, simulate, train_detector
from repro.sim.msf import SCAN_DT
from repro.launch.compile_cache import enable_compile_cache


def main(quick: bool = False):
    rows = []
    scale = 0.12 if quick else 0.4
    x, y = build_dataset(normal_cycles=int(42_000 * scale),
                         attack_cycles=int(5_700 * scale), stride=8, seed=0)
    model, res = train_detector(x, y, epochs=25 if quick else 80,
                                patience=8 if quick else 15, lr=1e-3)
    rows.append({"name": "casestudy/test_accuracy",
                 "us_per_call": res.test_acc * 100,
                 "derived": "paper=93.68pct"})

    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        ported, pparams = porting.port_mlp(model, res.params, tmp)

    # detection latency per attack family, unseen seeds
    attack_start = 600
    for attack_id in range(1, 8):
        detector = SlidingWindowDetector(ported, pparams, window=200,
                                         n_features=2, n_segments=2)
        detections = []

        def hook(cycle, reading):
            r = np.array([(reading[0] - 89.6) / 2.0,
                          (reading[1] - 19.18) / 0.5], np.float32)
            detector.push(r)
            out = detector.tick(cycle)
            if out is not None and out[1] != 0:
                detections.append(out[0])

        simulate(1400 if quick else 2200, attack_id=attack_id,
                 attack_start=attack_start, seed=500 + attack_id,
                 defense_hook=hook)
        first = [d for d in detections if d >= attack_start]
        lat = (first[0] - attack_start) * SCAN_DT if first else float("nan")
        fp = sum(1 for d in detections if d < attack_start)
        rows.append({"name": f"casestudy/detect_latency_s/attack{attack_id}",
                     "us_per_call": lat * 1e6 if first else -1.0,
                     "derived": f"latency_s={lat:.1f};false_pos={fp};paper=5.0s"})

    # §7.2 non-intrusiveness
    n = 1500 if quick else 3000
    off = simulate(n, seed=321)
    det = SlidingWindowDetector(ported, pparams, window=200, n_features=2,
                                n_segments=2)

    def hook2(cycle, reading):
        det.push(np.array([(reading[0] - 89.6) / 2.0,
                           (reading[1] - 19.18) / 0.5], np.float32))
        det.tick(cycle)

    on = simulate(n, seed=321, defense_hook=hook2)
    seg = slice(n // 2, None)
    rows.append({"name": "casestudy/nonintrusive_wd_mean_off",
                 "us_per_call": off.wd_meas[seg].mean() * 1e3,
                 "derived": f"std={off.wd_meas[seg].std():.2e};paper_mean=19.18"})
    rows.append({"name": "casestudy/nonintrusive_wd_mean_on",
                 "us_per_call": on.wd_meas[seg].mean() * 1e3,
                 "derived": (f"std={on.wd_meas[seg].std():.2e};"
                             f"identical={bool(np.allclose(off.wd_meas, on.wd_meas))}")})
    return emit(rows)


if __name__ == "__main__":
    enable_compile_cache()
    main()
