"""Benchmark harness: one module per paper table/figure (DESIGN.md §6 index).

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks sweeps.

Modules that return their rows also get a machine-readable perf record
``BENCH_<name>.json`` written into ``--out-dir`` (e.g. ``BENCH_detection.json``
for the fleet-detection fused-vs-per-layer comparison, with the serving bench
record alongside) — CI uploads these as artifacts so perf history is diffable
per commit.

``--compare OLD.json`` diffs this run's rows against a baseline record:
every row present in both is printed with its old→new ``us_per_call``
ratio, and any row more than 20% slower than the baseline makes the run
exit nonzero.  With ``--compare-to NEW.json`` no modules run at all — the
two records are diffed directly (the CI wiring: the bench-artifacts job
diffs its fresh ``--quick`` artifact against the committed baseline as a
non-blocking step, so a regression flags the PR without failing it).
"""

import argparse
import json
import os
import sys
import traceback

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

MODULES = [
    ("layer_stacking", "Fig.4/§5.2"),
    ("layer_width", "§5.3"),
    ("memory_bench", "Table2/Fig.3/§5.1"),
    ("quantization_bench", "Fig.5/§6.1"),
    ("pruning_bench", "§6.2"),
    ("multipart_bench", "§6.3"),
    ("perf_gap", "§5.4"),
    ("casestudy_bench", "§7"),
    ("serving_bench", "PR1-continuous"),
    ("detection_bench", "§7-fleet"),
]


def bench_json_name(module: str) -> str:
    short = module[:-len("_bench")] if module.endswith("_bench") else module
    return f"BENCH_{short}.json"


def write_bench_json(out_dir: str, module: str, ref: str, quick: bool,
                     rows) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, bench_json_name(module))
    with open(path, "w") as f:
        json.dump({"module": module, "paper_ref": ref, "quick": quick,
                   "rows": rows}, f, indent=2)
        f.write("\n")
    return path


REGRESSION_THRESHOLD = 0.20


def load_rows(path: str) -> list:
    with open(path) as f:
        record = json.load(f)
    return record["rows"] if isinstance(record, dict) else record


def compare_rows(old_rows, new_rows, *,
                 threshold: float = REGRESSION_THRESHOLD) -> int:
    """Print per-row old→new ``us_per_call`` ratios; return how many rows
    regressed by more than ``threshold``.

    Rows are matched by name: rows only in the new run are reported as new
    (a --quick run vs a full baseline legitimately differs in row sets),
    baseline rows the new run lacks are listed but never counted as
    regressions — only a matched row that got slower fails the gate."""
    old = {r["name"]: r for r in old_rows}
    new_names = {r["name"] for r in new_rows}
    regressed = 0
    for r in new_rows:
        o = old.get(r["name"])
        if o is None:
            print(f"# compare {r['name']}: no baseline row")
            continue
        if not o.get("us_per_call") or not r.get("us_per_call"):
            continue
        ratio = r["us_per_call"] / o["us_per_call"]
        tag = "REGRESSION" if ratio > 1.0 + threshold else "ok"
        print(f"# compare {r['name']}: {o['us_per_call']:.1f} -> "
              f"{r['us_per_call']:.1f} us/call ({ratio:.2f}x) {tag}")
        regressed += ratio > 1.0 + threshold
    missing = sorted(n for n in old if n not in new_names)
    if missing:
        print(f"# compare: {len(missing)} baseline rows not in this run: "
              + ",".join(missing))
    return regressed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<name>.json perf records")
    ap.add_argument("--compare", default=None, metavar="OLD.json",
                    help="baseline perf record; this run's matching rows "
                         f"more than {REGRESSION_THRESHOLD:.0%} slower "
                         "exit nonzero")
    ap.add_argument("--compare-to", default=None, metavar="NEW.json",
                    help="with --compare: diff two records directly, "
                         "running no benchmark modules")
    args = ap.parse_args()

    if args.compare_to:
        if not args.compare:
            sys.exit("--compare-to needs --compare OLD.json")
        regressed = compare_rows(load_rows(args.compare),
                                 load_rows(args.compare_to))
        if regressed:
            sys.exit(f"{regressed} rows regressed more than "
                     f"{REGRESSION_THRESHOLD:.0%} vs {args.compare}")
        return

    only = set(args.only.split(",")) if args.only else None
    failures = 0
    all_rows = []
    for name, ref in MODULES:
        if only and name not in only:
            continue
        print(f"# --- {name} ({ref}) ---", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            rows = mod.main(quick=args.quick)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
            continue
        if isinstance(rows, list) and rows and isinstance(rows[0], dict):
            path = write_bench_json(args.out_dir, name, ref, args.quick, rows)
            all_rows.extend(rows)
            print(f"# wrote {path}", flush=True)
    if failures:
        sys.exit(f"{failures} benchmark modules failed")
    if args.compare:
        regressed = compare_rows(load_rows(args.compare), all_rows)
        if regressed:
            sys.exit(f"{regressed} rows regressed more than "
                     f"{REGRESSION_THRESHOLD:.0%} vs {args.compare}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
