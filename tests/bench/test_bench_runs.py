"""Whole runs of the harness on the CPU at a small fleet, past its look for a
chip: a sound run comes out correct; the timed path broken underneath, or
the control in the program's place, comes out not correct; and a cell,
configuration, traffic mix and metric added as files and entries alone are
taken up."""

import copy
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness as H  # noqa: E402
from bench import readings  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import reference_real as RR  # noqa: E402
from bench import work  # noqa: E402

SEED = 2**31 + 11
PLANTS = 32
SECONDS = 0.3


@pytest.fixture
def cpu_harness(monkeypatch):
    """The harness on the CPU: its chip check, its compile cache in the
    checkout and the device's row of the peak table stand aside; everything
    else runs as on the chip."""
    import jax
    monkeypatch.setattr(H, "devices", lambda cell: jax.devices()[:1])
    monkeypatch.setattr(H, "enable_cache", lambda root: None)
    peaks = work.peaks
    monkeypatch.setattr(work, "peaks",
                        lambda kind, path=work.PEAKS: peaks("TPU v5 lite"))
    return H


def small(cell):
    cell.traffic = dict(cell.traffic, plants=PLANTS)
    return cell


def run(cell):
    return H.run(cell, SEED, SECONDS, False, time.perf_counter())


class Fault:
    """An engine whose ``ingest()`` is broken; the rest passes through."""

    def __init__(self, engine):
        self.engine = engine

    def __getattr__(self, name):
        return getattr(self.engine, name)


class Stale(Fault):
    """Every verdict step after the first returns the first step's
    answers: the state never advances."""

    def __init__(self, engine):
        self.engine, self.first = engine, None

    def ingest(self, readings):
        verdicts = self.engine.ingest(readings)
        if not verdicts:
            return verdicts
        if self.first is None:
            self.first = verdicts
        out = []
        for old, new in zip(self.first, verdicts):
            v = copy.copy(old)
            v.cycle = new.cycle
            out.append(v)
        return out


class Half(Fault):
    """Half of the fleet's verdicts left out."""

    def ingest(self, readings):
        verdicts = self.engine.ingest(readings)
        return verdicts[:len(verdicts) // 2]


class Altered(Fault):
    """One answer of every verdict step altered where it is produced."""

    def __init__(self, engine):
        self.engine, self.steps = engine, 0

    def ingest(self, readings):
        verdicts = self.engine.ingest(readings)
        if verdicts:
            self.steps += 1
            v = verdicts[self.steps % len(verdicts)]
            v.pred = 1 - v.pred
        return verdicts


@pytest.fixture(scope="module")
def cls_cell():
    return small(H.load_cell(ROOT, "cls_sint.fleet4k"))


def test_sound_run_is_correct(cpu_harness, cls_cell):
    result = run(cls_cell)
    assert result["correct"] is True
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"windows_per_s", "verdict_p95_ms",
                                      "setup_s"}
    assert list(result)[-3:] == ["checks", "_diagnostics", "_state"]
    assert result["_diagnostics"]["compared_windows"] > 0


@pytest.mark.parametrize("fault", [Stale, Half, Altered],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered"])
def test_broken_timed_path_is_not_correct(cpu_harness, cls_cell, monkeypatch,
                                          fault):
    build = H.build_engine
    monkeypatch.setattr(H, "build_engine",
                        lambda *a, **kw: fault(build(*a, **kw)))
    result = run(cls_cell)
    assert result["correct"] is False


@pytest.mark.parametrize("fault", [Stale, Half, Altered],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered"])
def test_broken_real_timed_path_is_not_correct(cpu_harness, monkeypatch,
                                               fault):
    cell = small(H.load_cell(ROOT, "mixed4_real.fleet4k"))
    build = H.build_engine
    monkeypatch.setattr(H, "build_engine",
                        lambda *a, **kw: fault(build(*a, **kw)))
    result = run(cell)
    assert result["correct"] is False


@pytest.mark.parametrize("cell", ["cls_sint.fleet4k", "mixed4_sint.fleet4k",
                                  "mixed4_real.fleet4k"])
def test_control_at_int4_is_not_correct(cpu_harness, cell):
    """The control one precision below the configuration's (int4 for SINT,
    one bfloat16 pass for REAL) fails a limit."""
    c = small(H.load_cell(ROOT, cell))
    result = run(c)
    assert result["correct"] is True
    tally = readings.control_tally(c, result["_state"])
    assert tally.windows == result["_diagnostics"]["compared_windows"]
    assert (tally.pred_off > 0
            or tally.tail_rel_err > c.config["tail_rel_err"])


def test_control_follows_the_scheme():
    cfg = H.load_cell(ROOT, "mixed4_real.fleet4k").config
    layers = [[{"w": None, "b": None}] * len(g["widths"][1:])
              for g in cfg["groups"]]
    ctrl = readings.controls(cfg, layers, [None] * 4)
    assert all(isinstance(c, RR.GroupReference) and c.one_pass
               for c in ctrl)
    sint = H.load_cell(ROOT, "mixed4_sint.fleet4k").config
    layers = [[{"w": np.float32([[0.5]]), "b": np.zeros(1, np.float32),
                "x_absmax": 1.0}]] * 4
    ctrl = readings.controls(sint, layers, [None] * 4)
    assert {(type(c), c.qmax) for c in ctrl} == {
        (R.GroupReference, R.CONTROL_QMAX)}


# The SINT numbers compared and the score thresholds of one verdict step
# (a window that closes at its first verdict), as the harness read them
# before it took REAL configurations: every SINT path is unchanged.
SINT_AS_BEFORE = {
    "cls_sint.fleet4k": (32, 8.050546862579223e-08, [None]),
    "mixed4_sint.fleet4k": (64, 1.0012065874320674e-06,
                            [None, 0.8321727514266968, 0.358742892742157,
                             1.6554803252220154]),
}


@pytest.mark.parametrize("cell", sorted(SINT_AS_BEFORE))
def test_sint_checks_and_thresholds_read_as_before(cpu_harness, cell):
    plants, tail, thresholds = SINT_AS_BEFORE[cell]
    c = H.load_cell(ROOT, cell)
    c.traffic = dict(c.traffic, plants=plants)
    result = H.run(c, SEED, 1e-9, False, time.perf_counter())
    diag = result["_diagnostics"]
    assert (diag["steps"], diag["compared_windows"]) == (1, plants)
    assert diag["thresholds"] == thresholds
    assert result["checks"] == {
        "failed": {"value": 0, "limit": 0},
        "pred_off": {"value": 0, "limit": 0},
        "tail_rel_err": {"value": tail,
                         "limit": c.config["tail_rel_err"]}}


def test_unknown_scheme_raises_before_any_engine_is_built(cpu_harness,
                                                          tmp_path,
                                                          monkeypatch):
    def no_engine(*a, **kw):
        raise AssertionError("an engine was built")

    monkeypatch.setattr(H, "build_engine", no_engine)
    root = copy_benchmark(tmp_path)
    cfg = root / "bench/configs/msf_mixed4_real.json"
    cfg.write_text(cfg.read_text().replace('"REAL"', '"DINT"'))
    with pytest.raises(ValueError, match="'DINT'"):
        H.load_cell(str(root), "mixed4_real.fleet4k")
    cell = small(H.load_cell(ROOT, "mixed4_real.fleet4k"))
    cell.config = dict(cell.config, scheme="INT")
    with pytest.raises(ValueError, match="'INT'"):
        run(cell)


def copy_benchmark(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def test_new_cell_config_traffic_and_metric_are_taken_up(cpu_harness,
                                                         tmp_path):
    """Files and entries alone: no line of the harness changes."""
    copy_benchmark(tmp_path)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cfg = json.loads((tmp_path / "bench/configs/msf_cls_sint.json")
                     .read_text())
    cfg["name"] = "msf_tiny_sint"
    cfg["groups"][0]["widths"] = [400, 32, 2]
    cfg["groups"][0]["activations"] = ["relu", "linear"]
    (tmp_path / "bench/configs/msf_tiny_sint.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/fleet24.json").write_text(json.dumps(
        {"plants": 24}))
    (tmp_path / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.window.steps)\n")
    bench["configs"].append({"name": "msf_tiny_sint",
                             "source": "https://arxiv.org/abs/2202.10075",
                             "file": "bench/configs/msf_tiny_sint.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny.fleet24",
                               "config": "msf_tiny_sint",
                               "traffic": "fleet24", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "host ingest",
                               "moves": "windows_per_s",
                               "workloads": ["tiny.fleet24"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = H.load_cell(str(tmp_path), "tiny.fleet24")
    assert cell.config["groups"][0]["widths"] == [400, 32, 2]
    assert cell.traffic["plants"] == 24
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    assert "steps_in_window" not in [
        m["name"] for m in H.load_cell(str(tmp_path),
                                       "cls_sint.fleet4k").per_layer]
    result = H.run(cell, SEED, SECONDS, True, time.perf_counter())
    assert result["correct"] is True
    assert result["metrics"]["steps_in_window"]["value"] == \
        result["_diagnostics"]["steps"]


def test_new_real_config_is_taken_up(cpu_harness, tmp_path, monkeypatch):
    """A REAL configuration of another plant shape, as files and entries
    alone: the engine is handed float weights and the f32 reference
    judges it."""
    copy_benchmark(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "bench/configs/msf_cls_sint.json")
                     .read_text())
    cfg.update(name="tiny_real", scheme="REAL", n_features=3,
               norm_mean=[1.0, 2.0, 3.0], norm_std=[0.5, 0.5, 0.5],
               window=20, stride=5)
    cfg["groups"][0].update(widths=[60, 16, 2],
                            activations=["relu", "linear"])
    (tmp_path / "bench/configs/tiny_real.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/fleet24.json").write_text(json.dumps(
        {"plants": 24}))
    bench["configs"].append({"name": "tiny_real",
                             "source": "https://arxiv.org/abs/2202.10075",
                             "file": "bench/configs/tiny_real.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny_real.fleet24",
                               "config": "tiny_real", "traffic": "fleet24",
                               "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    import repro.serving
    handed = []
    engine = repro.serving.StreamEngine

    def spy(model, params, **kw):
        handed.extend(sorted(p) for p in params.values() if p)
        return engine(model, params, **kw)

    monkeypatch.setattr(repro.serving, "StreamEngine", spy)
    cell = H.load_cell(str(tmp_path), "tiny_real.fleet24")
    result = H.run(cell, SEED, SECONDS, False, time.perf_counter())
    assert handed == [["b", "w"], ["b", "w"]]
    assert result["correct"] is True
    assert result["_diagnostics"]["compared_windows"] > 0
    assert all(isinstance(r, RR.GroupReference) for r in H.references(
        cell.config, result["_state"]["host_layers"],
        result["_state"]["thresholds"]))
