"""The command exits nonzero, printing no result, without a TPU, and in a
directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ARGS = ["--workload", "cls_sint.fleet4k", "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0"]


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_nonzero_without_a_tpu():
    proc = run_bench(ROOT)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
    assert "needs a TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_bench(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
