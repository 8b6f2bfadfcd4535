"""The readings a cell's limits are set from, on many seeds in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it makes one run of the cell as ``bench/run.py`` does (a
short window at the cell's own load) and prints the numbers compared.
Then it puts the control in the program's place: the reference computed at
the precision below the one the configuration states (int4 for SINT's
int8), over the same sampled windows, compared with the reference as the
program is.  The lower reading of a number is the largest the program
gives; the upper, the smallest the control gives.  The benchmark's own runs
never run the control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402


def control_tally(cell, state: dict, qmax: int):
    """The control's numbers: the reference at ``qmax`` in the program's
    place, over the windows of the run's sampled steps."""
    from bench import harness
    from bench import reference as R
    config, pool = cell.config, state["pool"]
    refs = harness.references(config, state["host_layers"],
                              state["thresholds"])
    lower = harness.references(config, state["host_layers"],
                               state["thresholds"], qmax=qmax)
    tally = R.Tally()
    slices = R.group_slices(config, pool.shape[1])
    for cycle in state["cycles"]:
        win = R.windows(pool, config, cycle)
        for sl, ref, low in zip(slices, refs, lower):
            pred, tail = low(win[sl])
            tally.add(f"cycle {cycle} group {ref.group['name']}", pred, tail,
                      ref, win[sl])
    return tally


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run  # bench/run.py, beside this script
    run.use_checkout()
    from bench import harness
    from bench import reference as R
    cell = harness.load_cell(run.ROOT, args.workload)
    t0 = T_PROCESS
    for seed in args.seeds:
        result = harness.run(cell, seed, args.seconds, False, t0)
        state = result.pop("_state")
        diag = result.pop("_diagnostics")
        ctrl = control_tally(cell, state, R.CONTROL_QMAX)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "control": {"pred_off": ctrl.pred_off,
                        "tail_rel_err": ctrl.tail_rel_err,
                        "windows": ctrl.windows},
            "diagnostics": {k: diag[k] for k in (
                "steps", "compared_windows", "windows_with_near_ties",
                "near_ties_excused", "borderline_flips", "rel_over",
                "first_off", "setup_s")},
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
