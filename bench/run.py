"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

Run from the root of a checkout.  Exits nonzero, printing no result, where
JAX finds no TPU or another number of chips than the cell asks for.  The
last line of standard output is one JSON object; see ``bench/harness.py``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_checkout() -> None:
    """Import ``bench`` and the program from the checkout; the script's own
    directory would shadow top-level modules (``trace``)."""
    here = os.path.join(ROOT, "bench")
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    use_checkout()
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS)
    harness.report(result)


if __name__ == "__main__":
    main()
