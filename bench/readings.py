"""The readings a cell's limits are set from, on many seeds in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it makes one run of the cell as ``bench/run.py`` does (a
short window at the cell's own load) and prints the numbers compared.
Then it puts the control in the program's place: the reference computed at
the precision below the one the configuration states (int4 for SINT's
int8; for REAL's float32 one bfloat16 pass, the chip's default f32 dot),
over the same sampled windows, compared with the reference as the program
is.  Where the configuration sets ``adapt``, two more controls report a
score head's threshold in the program's place: *offline*, the offline one
at every step (no recalibration), and *stale*, the reference's of the step
before (recalibration one step late); each reads ``thr_rel_err``.  The
lower reading of a number is the largest the program gives; the upper, the
smallest a control gives.  The benchmark's own runs never run a control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402


def controls(config: dict, host_layers, thresholds) -> list:
    """Per group, the reference one precision below the configuration's."""
    from bench import harness
    from bench import reference as R
    from bench import reference_real as RR
    harness.scheme(config)
    make = {"SINT": functools.partial(R.GroupReference, qmax=R.CONTROL_QMAX),
            "REAL": functools.partial(RR.GroupReference, one_pass=True),
            }[config["scheme"]]
    return [make(g, config, layers, threshold=thr)
            for g, layers, thr in zip(config["groups"], host_layers,
                                      thresholds)]


def group_tallies(cell, state: dict) -> list:
    """Per group, ``(name, program tally, control tally)`` over the run's
    sampled steps: which head sets each reading."""
    from bench import harness
    from bench import reference as R
    from bench import reference_adapt as RA
    config, pool = cell.config, state["pool"]
    refs = harness.references(config, state["host_layers"],
                              state["thresholds"])
    lower = controls(config, state["host_layers"], state["thresholds"])
    out = [(ref.group["name"], R.Tally(), R.Tally()) for ref in refs]
    slices = R.group_slices(config, pool.shape[1])
    # Where the heads adapt, each step is judged at its reference
    # thresholds, the control's too, as the harness judges it.
    step_thr = state.get("step_thresholds", {})
    reported = state.get("reported", {})
    none = [None] * len(refs)
    for cycle, (pred, tail) in sorted(state["steps"].items()):
        win = R.windows(pool, config, cycle)
        for sl, ref, low, thr, got, (name, prog, ctrl) in zip(
                slices, refs, lower, step_thr.get(cycle, none),
                reported.get(cycle, none), out):
            label = f"cycle {cycle} group {name}"
            p = pred[sl]
            if thr is not None:
                p, _ = RA.apart(ref, thr, got, p, win[sl])
            ref = RA.at(ref, thr)
            prog.add(label, p, tail[sl], ref, win[sl])
            ctrl.add(label, *RA.at(low, thr)(win[sl]), ref, win[sl])
    return out


def total(tallies):
    """One tally of the worst readings over ``tallies``."""
    from bench import reference as R
    out = R.Tally()
    for t in tallies:
        out.windows += t.windows
        out.pred_off += t.pred_off
        out.tail_rel_err = max(out.tail_rel_err, t.tail_rel_err)
    return out


def control_tally(cell, state: dict):
    """The control's numbers over every group: the control in the
    program's place, over the windows of the run's sampled steps."""
    return total(c for _, _, c in group_tallies(cell, state))


def threshold_controls(cell, state: dict) -> dict:
    """``thr_rel_err`` of the *offline* and *stale* controls over the run's
    sampled steps, and how many ranks of the pooled scores their thresholds
    lie from the reference's (empty where the configuration sets no
    ``adapt``)."""
    from bench import reference_adapt as RA
    if "step_thresholds" not in state:
        return {}
    ref = state["step_thresholds"]
    got = RA.controls(cell.config, ref, state["thresholds"],
                      sorted(state["steps"]))
    return {name: {"thr_rel_err": RA.thr_rel_err(reported, ref),
                   "rank_gap": RA.rank_gap(reported, ref, state["pools"])}
            for name, reported in got.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run  # bench/run.py, beside this script
    run.use_checkout()
    from bench import harness
    cell = harness.load_cell(run.ROOT, args.workload)
    t0 = T_PROCESS
    for seed in args.seeds:
        result = harness.run(cell, seed, args.seconds, False, t0)
        state = result.pop("_state")
        diag = result.pop("_diagnostics")
        groups = group_tallies(cell, state)
        ctrl = total(c for _, _, c in groups)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": {k: v["value"] for k, v in result["checks"].items()},
            "control": {"pred_off": ctrl.pred_off,
                        "tail_rel_err": ctrl.tail_rel_err,
                        "windows": ctrl.windows},
            "thr_controls": threshold_controls(cell, state),
            "by_group": {name: {"program": [p.pred_off, p.tail_rel_err],
                                "control": [c.pred_off, c.tail_rel_err]}
                         for name, p, c in groups},
            "diagnostics": {k: diag[k] for k in (
                "steps", "compared_windows", "windows_with_near_ties",
                "near_ties_excused", "borderline_flips", "rel_over",
                "first_off", "setup_s")},
            "thr_rank_gap": diag.get("thr_rank_gap"),
            "threshold_band_flips": diag.get("threshold_band_flips"),
            "live_thresholds": diag.get("live_thresholds"),
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
