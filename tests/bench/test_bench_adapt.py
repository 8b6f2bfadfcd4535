"""A configuration that sets ``adapt``: the reference's recalibration by
hand, whole runs of ``mixed4_sint_adapt.fleet4k`` on the CPU at a small
fleet (a sound run is correct; the two threshold controls, a program that
skips recalibration and the timed path broken underneath are not), the
policy checked when the cell is loaded, and every other configuration
printing what it printed before."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness as H  # noqa: E402
from bench import readings  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import reference_adapt as RA  # noqa: E402
from bench import traffic as TR  # noqa: E402
from bench import work  # noqa: E402
from test_bench_runs import (SINT_AS_BEFORE, Altered, Fault,  # noqa: E402
                             Half, Stale, copy_benchmark)

SEED = 2**31 + 11
PLANTS = 32
SECONDS = 0.3
CELL = "mixed4_sint_adapt.fleet4k"


@pytest.fixture(scope="module")
def cpu():
    """The harness on the CPU (as ``test_bench_runs.cpu_harness``), for a
    module's runs."""
    import jax
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "devices", lambda cell: jax.devices()[:1])
        mp.setattr(H, "enable_cache", lambda root: None)
        peaks = work.peaks
        mp.setattr(work, "peaks",
                   lambda kind, path=work.PEAKS: peaks("TPU v5 lite"))
        yield H


def small(name, plants=PLANTS):
    cell = H.load_cell(ROOT, name)
    cell.traffic = dict(cell.traffic, plants=plants)
    return cell


def run(cell, seconds=SECONDS):
    return H.run(cell, SEED, seconds, False, time.perf_counter())


# ---------------------------------------------------------------------------
# The reference by hand


def test_recalibration_by_hand():
    """Two plants, a ring of 3: an admission refused at the gate,
    ``min_count`` holding the offline threshold, then the quantile, and a
    ring that wraps round."""
    u = RA.Recalibration(2, 1.0, 0.4, capacity=3, every=1, min_count=4,
                         headroom=2.0)
    # Gate 2 x 1.0: plant 1's 3.0 is refused; one score, under min_count.
    assert u.step([1.0, 3.0]) == 1.0
    assert u.counts.tolist() == [1, 0]
    assert u.step([0.5, 1.5]) == 1.0               # three scores: held
    # Five scores 0.25 0.5 1.0 1.5 1.75: the higher 0.6 quantile, index 3.
    assert u.step([1.75, 0.25]) == 1.5
    assert u.ring.tolist() == [[1.0, 0.5, 1.75], [1.5, 0.25, 0.0]]
    # Gate 2 x 1.5: plant 0's 2.5 wraps into slot 0 over 1.0; plant 1's 4.0
    # is refused, so its empty slot stays out of the pool.
    assert u.step([2.5, 4.0]) == 1.75
    assert u.ring.tolist() == [[2.5, 0.5, 1.75], [1.5, 0.25, 0.0]]
    assert u.counts.tolist() == [4, 2]


def test_recalibration_every_other_step():
    u = RA.Recalibration(1, 5.0, 0.5, capacity=4, every=2, min_count=1,
                         headroom=4.0)
    assert u.step([1.0]) == 5.0                    # step 1: no recalibration
    assert u.step([3.0]) == 3.0                    # step 2: higher median
    assert u.step([2.0]) == 3.0
    assert u.counts.tolist() == [3]


def test_gate_is_float32():
    """A score admitted at ``f32(headroom) * f32(thr)``, not at the float64
    product."""
    thr = 0.1                                      # not a float32
    gate = np.float32(4.0) * np.float32(thr)
    u = RA.Recalibration(1, thr, 0.5, capacity=2, every=1, min_count=5,
                         headroom=4.0)
    u.step([gate])
    assert float(gate) > 4.0 * thr and u.counts.tolist() == [1]


def test_scored_windows_are_the_reference_windows():
    """Windows cut from the pool normalized once, wrapped round the pool,
    equal :func:`bench.reference.windows` bit for bit."""
    config = H.load_cell(ROOT, CELL).config
    pool = TR.pool({"plants": 16}, config, SEED)

    class Whole:
        def __init__(self, kind):
            self.kind = kind

        def outputs(self, win):
            return win

        def scores(self, y, win):
            return win

    refs = [Whole("classifier")] + [Whole("margin")] * 3
    cycles = [199, 1999, 2009, 2189, 2199, 4009]
    got = RA.group_scores(config, pool, refs, cycles)
    assert sorted(got) == sorted({c % 2000 for c in cycles})
    slices = R.group_slices(config, 16)
    for c in cycles:
        win = R.windows(pool, config, c)
        assert got[c % 2000][0] is None
        for g in (1, 2, 3):
            np.testing.assert_array_equal(got[c % 2000][g], win[slices[g]])


def test_controls_report_the_offline_and_the_step_before():
    config = {"window": 200, "stride": 10}
    ref = {199: [None, 2.0], 209: [None, 3.0], 219: [None, 4.0]}
    got = RA.controls(config, ref, [None, 1.0], [199, 219])
    assert got == {"offline": {199: [None, 1.0], 219: [None, 1.0]},
                   "stale": {199: [None, 1.0], 219: [None, 3.0]}}
    assert RA.thr_rel_err(got["stale"], ref) == 0.5
    assert RA.thr_rel_err({199: [None, None]}, ref) == np.inf


def test_verdicts_the_two_thresholds_decide_apart_by_hand():
    """A verdict between the reported threshold and the reference's is the
    threshold's to answer for; every other verdict is judged as before."""

    class Scores:
        def outputs(self, win):
            return win

        def scores(self, y, win):
            return win[:, 0]

    win = np.array([[0.5], [1.1], [1.1], [1.5], [1.0]], np.float32)
    pred = np.array([1, 0, 1, 1, 0])
    got, n = RA.apart(Scores(), 1.0, 1.2, pred, win)
    assert got.tolist() == [1, 1, 1, 1, 0] and n == 1
    got, n = RA.apart(Scores(), 1.2, 1.0, pred, win)
    assert got.tolist() == [1, 0, 0, 1, 0] and n == 1
    assert RA.apart(Scores(), 1.0, float("nan"), pred, win)[1] == 0


def test_rank_gap_by_hand():
    pools = {9: [None, np.array([1.0, 2.0, 3.0, 4.0])]}
    ref = {9: [None, 2.0]}
    assert RA.rank_gap({9: [None, 2.0 * (1 + 1e-6)]}, ref, pools) == 0
    assert RA.rank_gap({9: [None, 2.0 * (1 - 1e-6)]}, ref, pools) == 0
    assert RA.rank_gap({9: [None, 3.0]}, ref, pools) == 1
    assert RA.rank_gap({9: [None, 0.5]}, ref, pools) == 2


# ---------------------------------------------------------------------------
# Whole runs


@pytest.fixture(scope="module")
def adapt_run(cpu):
    cell = small(CELL)
    return cell, run(cell)


def test_adapt_run_is_correct(adapt_run):
    cell, result = adapt_run
    checks = result["checks"]
    assert result["correct"] is True
    assert list(checks) == ["failed", "pred_off", "tail_rel_err",
                            "thr_rel_err"]
    assert checks["thr_rel_err"]["limit"] == cell.config["thr_rel_err"]
    assert checks["thr_rel_err"]["value"] < checks["thr_rel_err"]["limit"]
    live = result["_diagnostics"]["live_thresholds"]
    assert live["program"][0] is None and live["reference"][0] is None
    # The live thresholds have left the offline ones.
    assert live["reference"][1:] != result["_state"]["thresholds"][1:]


def test_threshold_controls_fail_on_the_same_run(adapt_run):
    cell, result = adapt_run
    got = readings.threshold_controls(cell, result["_state"])
    assert set(got) == {"offline", "stale"}
    assert min(g["thr_rel_err"] for g in got.values()) > cell.config[
        "thr_rel_err"]
    # Ranks of the pooled scores apart: the program none on the CPU.
    assert min(g["rank_gap"] for g in got.values()) > 0
    assert result["_diagnostics"]["thr_rank_gap"] == 0
    assert result["_diagnostics"]["threshold_band_flips"] == 0
    assert readings.threshold_controls(
        small("mixed4_sint.fleet4k"), {"steps": {}}) == {}


def test_int4_control_judged_at_the_live_thresholds(adapt_run):
    cell, result = adapt_run
    groups = readings.group_tallies(cell, result["_state"])
    prog = readings.total(p for _, p, _ in groups)
    assert prog.pred_off == 0
    assert prog.tail_rel_err == result["checks"]["tail_rel_err"]["value"]
    ctrl = readings.total(c for _, _, c in groups)
    assert ctrl.pred_off > 0 or ctrl.tail_rel_err > cell.config[
        "tail_rel_err"]


def test_program_that_skips_recalibration_is_not_correct(cpu, monkeypatch):
    build = H.build_engine
    monkeypatch.setattr(H, "build_engine", lambda config, *a: build(
        {k: v for k, v in config.items() if k != "adapt"}, *a))
    result = run(small(CELL))
    assert result["correct"] is False
    assert result["checks"]["thr_rel_err"]["value"] > result["checks"][
        "thr_rel_err"]["limit"]


class Threshold(Fault):
    """One row of every verdict step reports another threshold."""

    def ingest(self, readings):
        verdicts = self.engine.ingest(readings)
        if verdicts:
            v = verdicts[-1]
            v.threshold = v.threshold * 2
        return verdicts


@pytest.mark.parametrize("fault", [Stale, Half, Altered, Threshold],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered", "threshold-altered"])
def test_broken_adapt_timed_path_is_not_correct(cpu, monkeypatch, fault):
    build = H.build_engine
    monkeypatch.setattr(H, "build_engine",
                        lambda *a, **kw: fault(build(*a, **kw)))
    result = run(small(CELL))
    assert result["correct"] is False
    if fault is Threshold:
        assert result["failed"] > 0


# ---------------------------------------------------------------------------
# The policy at load


@pytest.mark.parametrize("change", [
    pytest.param(lambda cfg: cfg.update(adapt={"capacity": 32, "every": 1,
                                               "min_count": 16,
                                               "headroom": 4.0,
                                               "window": 8}),
                 id="unknown-key"),
    pytest.param(lambda cfg: cfg["adapt"].pop("headroom"), id="missing-key"),
    pytest.param(lambda cfg: cfg.update(
        groups=[g for g in cfg["groups"] if g["head"] == "classifier"]),
        id="classifier-only"),
    pytest.param(lambda cfg: cfg.pop("thr_rel_err"), id="no-limit"),
])
def test_bad_adapt_raises_at_load(tmp_path, monkeypatch, change):
    def no_engine(*a, **kw):
        raise AssertionError("an engine was built")

    monkeypatch.setattr(H, "build_engine", no_engine)
    root = copy_benchmark(tmp_path)
    path = root / "bench/configs/msf_mixed4_sint_adapt.json"
    cfg = json.loads(path.read_text())
    change(cfg)
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="adapt"):
        H.load_cell(str(root), CELL)


def test_adapt_on_a_classifier_config_raises_at_load(tmp_path):
    root = copy_benchmark(tmp_path)
    path = root / "bench/configs/msf_cls_sint.json"
    cfg = json.loads(path.read_text())
    cfg.update(adapt={"capacity": 32, "every": 1, "min_count": 16,
                      "headroom": 4.0}, thr_rel_err=1e-3)
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="classifier"):
        H.load_cell(str(root), "cls_sint.fleet256")


def test_adapt_config_is_mixed4_sint_plus_adapt():
    adapt = H.load_cell(ROOT, CELL).config
    sint = H.load_cell(ROOT, "mixed4_sint.fleet4k").config
    differ = {k for k in set(adapt) | set(sint) if adapt.get(k) != sint.get(k)}
    assert differ == {"name", "source", "adapt", "thr_rel_err", "assumed"}
    assert adapt["adapt"] == {"capacity": 32, "every": 1, "min_count": 16,
                              "headroom": 4.0}


# ---------------------------------------------------------------------------
# Every other configuration as before


# The numbers compared and the score thresholds of one verdict step on a
# fixed seed, as the harness read them before it took ``adapt``.
AS_BEFORE = dict(SINT_AS_BEFORE, **{
    "mixed4_real.fleet4k": (64, 3.4011528746875918e-06,
                            [None, 0.8322496712207794, 0.3571900725364685,
                             1.636208176612854])})


@pytest.mark.parametrize("name", sorted(AS_BEFORE))
def test_configs_without_adapt_print_as_before(cpu, name):
    plants, tail, thresholds = AS_BEFORE[name]
    cell = small(name, plants)
    result = run(cell, 1e-9)
    assert result["_diagnostics"]["thresholds"] == thresholds
    assert "live_thresholds" not in result["_diagnostics"]
    assert "step_thresholds" not in result["_state"]
    assert (result["correct"], result["failed"], result["attempted"]) == (
        True, 0, plants)
    assert result["checks"] == {
        "failed": {"value": 0, "limit": 0},
        "pred_off": {"value": 0, "limit": 0},
        "tail_rel_err": {"value": tail,
                         "limit": cell.config["tail_rel_err"]}}
