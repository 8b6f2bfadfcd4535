"""The serving core's stages and device scopes in a traced run, and the
per-layer metrics that read them: on a hand-made trace, on the trace
recorded before the core had spans (every new reader reads nothing, every
older one what it read before), and on the profile a run leaves in the
checkout."""

import json
import os
import sys
import types

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import harness as H  # noqa: E402
from bench import stages as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench import work  # noqa: E402

OLD_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                           "trace_cls_sint_fleet4k.json")
NEW_METRICS = ("stage_ms.operands", "stage_ms.dispatch", "stage_ms.block",
               "stage_ms.unpack", "stage_ms.head", "stage_ms.rows",
               "stage_us.normalize", "scope_ms.ring_scatter",
               "h2d_kb_per_step", "d2h_kb_per_step")
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"


def ctx_of(trace, **kw):
    lo, hi = T.window(trace)
    return types.SimpleNamespace(trace=trace, lo=lo, hi=hi, **kw)


def read(metric, ctx):
    return H.load_reader(ROOT, metric)(ctx)


# Two verdict steps' stages, nested as the core writes them; the second
# has a child inside ``serve.operands``.
STEP_1 = [["serve.ingest", 12, 99, {"cycle": 9}],
          ["serve.normalize", 13, 15, {}],
          ["serve.operands", 15, 35, {}],
          ["serve.dispatch", 35, 45, {"h2d_bytes": 2048}],
          ["serve.finalize", 45, 98, {"cycle": 9}],
          ["serve.block", 46, 62, {}],
          ["serve.unpack", 62, 66, {"d2h_bytes": 512}],
          ["serve.head", 66, 70, {}],
          ["serve.rows", 70, 96, {}]]
STEP_2 = [["serve.ingest", 105, 198, {"cycle": 19}],
          ["serve.normalize", 106, 108, {}],
          ["serve.operands", 108, 130, {}],
          ["serve.place", 110, 120, {}],
          ["serve.dispatch", 130, 140, {"h2d_bytes": 2048}],
          ["serve.finalize", 140, 196, {"cycle": 19}],
          ["serve.block", 141, 170, {}],
          ["serve.unpack", 170, 172, {"d2h_bytes": 512}],
          ["serve.head", 172, 180, {}],
          ["serve.rows", 180, 195, {}]]


@pytest.fixture
def hand():
    """A nonverdict cycle and two verdict steps, the second with a child
    inside ``serve.operands``; two devices, the first the busier."""
    return {
        "spans": [["ingest.nonverdict", 0, 10], ["harness", 10, 12],
                  ["ingest.verdict", 12, 100], ["harness", 100, 104],
                  ["ingest.verdict", 104, 200]],
        "devices": {TPU0: [["a", 40, 50, "fusion"],
                           ["k", 50, 60, "tpu_custom_call"],
                           ["b", 150, 170, "fusion"]],
                    TPU1: [["a", 40, 45, "fusion"]]},
        "stages": [["serve.ingest", 1, 9, {"cycle": 8}],
                   ["serve.normalize", 2, 5, {}]] + STEP_1 + STEP_2,
        "device_scopes": {TPU0: [["ring_scatter", 40, 50],
                                 ["ring_scatter", 45, 48],
                                 ["ring_scatter", 150, 160]],
                          TPU1: [["ring_scatter", 40, 45]]},
    }


def test_innermost_and_self_time_by_hand(hand):
    pieces = S.innermost(hand["stages"])
    assert pieces[:4] == [["serve.ingest", 1, 2], ["serve.normalize", 2, 5],
                          ["serve.ingest", 5, 9], ["serve.ingest", 12, 13]]
    # Disjoint, in order, covering the stages' union.
    assert all(a[2] <= b[1] for a, b in zip(pieces, pieces[1:]))
    assert sum(e - s for _, s, e in pieces) == 8 + 87 + 93
    own = S.self_ns(hand["stages"], 0, 200)
    assert own["serve.operands"] == 20 + 12
    assert own["serve.finalize"] == 3 + 2
    assert own["serve.ingest"] == 5 + 2 + 3
    assert S.total_ns(hand["stages"], 0, 200)["serve.operands"] == 20 + 22
    # Cut to a window, a span counts only its part inside.
    assert S.self_ns(hand["stages"], 20, 200)["serve.operands"] == 15 + 12


def test_idle_by_stage_by_hand(hand):
    lo, hi = T.window(hand)
    by = S.idle_by_stage(hand["devices"][TPU0], hand["stages"],
                         hand["spans"], lo, hi)
    assert by == {"serve.ingest": 10, "serve.normalize": 7,
                  "serve.operands": 32, "serve.dispatch": 15,
                  "serve.block": 11, "serve.unpack": 6, "serve.head": 12,
                  "serve.rows": 41, "serve.finalize": 4, "serve.place": 10,
                  "ingest.nonverdict": 2, "harness": 6, "ingest.verdict": 4}
    assert sum(by.values()) == sum(e - s for s, e in T.gaps(
        hand["devices"][TPU0], lo, hi))
    # Without stages the split is the harness's own.
    assert S.idle_by_stage(hand["devices"][TPU0], [], hand["spans"], lo,
                           hi) == T.idle_by_span(hand["devices"][TPU0],
                                                 hand["spans"], lo, hi)


def test_minus_by_hand():
    cut = [["x", 2, 4], ["y", 3, 6], ["z", 9, 12]]
    assert S.minus([(0, 5), (7, 10), (12, 14)], cut) == [
        (0, 2), (7, 9), (12, 14)]


def test_new_readers_by_hand(hand):
    ctx = ctx_of(hand)
    assert read("stage_ms.operands", ctx) == (20 + 12) / 2 / 1e6
    assert read("stage_ms.dispatch", ctx) == (10 + 10) / 2 / 1e6
    assert read("stage_ms.block", ctx) == (16 + 29) / 2 / 1e6
    assert read("stage_ms.unpack", ctx) == (4 + 2) / 2 / 1e6
    assert read("stage_ms.head", ctx) == (4 + 8) / 2 / 1e6
    assert read("stage_ms.rows", ctx) == (26 + 15) / 2 / 1e6
    assert read("stage_us.normalize", ctx) == (3 + 2 + 2) / 3 / 1e3
    # The busiest device's scoped time, overlaps counted once.
    assert read("scope_ms.ring_scatter", ctx) == (10 + 10) / 2 / 1e6
    assert read("h2d_kb_per_step", ctx) == 2 * 2048 / 2 / 1024
    assert read("d2h_kb_per_step", ctx) == 2 * 512 / 2 / 1024


@pytest.fixture
def old():
    with open(OLD_FIXTURE) as fh:
        return json.load(fh)


def test_new_readers_read_nothing_in_a_trace_without_stages(
        old, monkeypatch, tmp_path):
    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    ctx = ctx_of(old)
    for metric in NEW_METRICS:
        assert read(metric, ctx) is None, metric
    untraced = types.SimpleNamespace(trace=None, lo=0, hi=0)
    for metric in NEW_METRICS:
        assert read(metric, untraced) is None, metric


def test_older_readers_read_what_they_read_before(old):
    cell = H.load_cell(ROOT, "cls_sint.fleet4k")
    win = H.Window(seconds=0.0199, steps=2, windows=8192)
    ctx = H.Context(config=cell.config, chips=1, plants=4096, setup_s=1.0,
                    window=win, peaks=work.peaks("TPU v5 lite"), trace=old,
                    lo=T.window(old)[0], hi=T.window(old)[1])
    assert read("verdict_host_ms", ctx) == 9.1427095
    assert read("device_busy_ms_per_step", ctx) == 0.070095
    assert read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 140190 / 19948399), rel=1e-12)
    assert read("fused_mlp_roofline", ctx) == pytest.approx(
        52.84777994385767, rel=1e-12)
    assert read("step_mfu", ctx) == pytest.approx(0.005906091884358177,
                                                  rel=1e-12)


def test_stages_are_found_in_the_profile_of_the_run(monkeypatch, tmp_path):
    """A run's plain trace has no stages: the readers take them from the
    profile under the checkout whose harness window is the run's."""
    trace_dir = tmp_path / ".bench_cache" / "trace" / "cell"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("ingest.verdict"):
                with jax.profiler.TraceAnnotation("serve.dispatch",
                                                  h2d_bytes=3072):
                    pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    plain = T.load(T.find_xplane(str(trace_dir)))
    assert "stages" not in plain
    ctx = ctx_of(plain)
    assert read("h2d_kb_per_step", ctx) == 3.0
    assert read("stage_ms.dispatch", ctx) > 0
    assert read("stage_ms.rows", ctx) is None
    other = types.SimpleNamespace(trace=plain, lo=ctx.lo, hi=ctx.hi + 1)
    assert read("h2d_kb_per_step", other) is None


CHIP_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                            "trace_stages_cls_sint_fleet256.json")


@pytest.fixture(scope="module")
def chip():
    """Two verdict steps of a traced ``cls_sint.fleet256`` run on a TPU v5
    lite, with the serving core's stages and device scopes."""
    with open(CHIP_FIXTURE) as fh:
        return json.load(fh)


def test_recorded_stages_nest_and_carry_their_step(chip):
    stages = chip["stages"]
    ingests = [s for s in stages if s[0] == "serve.ingest"]
    assert len(ingests) == 20
    assert [s[3]["cycle"] for s in ingests] == list(range(1230, 1250))
    for s in stages:
        held = [i for i in ingests if i[1] <= s[1] and s[2] <= i[2]]
        assert len(held) == 1, s
        if s[0] == "serve.finalize":
            assert s[3]["cycle"] == held[0][3]["cycle"]
    assert {s[0] for s in stages} == {
        "serve.ingest", "serve.normalize", "serve.operands",
        "serve.dispatch", "serve.finalize", "serve.block", "serve.unpack",
        "serve.head", "serve.rows"}


def test_new_readers_on_the_recorded_trace(chip):
    ctx = ctx_of(chip)
    assert T.window(chip) == (0, 6030780)
    assert read("stage_ms.operands", ctx) == 2146860 / 2 / 1e6
    assert read("stage_ms.dispatch", ctx) == 618380 / 2 / 1e6
    assert read("stage_ms.block", ctx) == 1026250 / 2 / 1e6
    assert read("stage_ms.unpack", ctx) == 811390 / 2 / 1e6
    assert read("stage_ms.head", ctx) == 100830 / 2 / 1e6
    assert read("stage_ms.rows", ctx) == 733400 / 2 / 1e6
    assert read("stage_us.normalize", ctx) == 227520 / 20 / 1e3
    assert read("scope_ms.ring_scatter", ctx) == 9804 / 2 / 1e6
    # 256 plants x 10 readings x 2 features in f32, a position and a
    # threshold; the (256, 2) f32 logits back.
    assert read("h2d_kb_per_step", ctx) == (256 * 10 * 2 * 4 + 8) / 1024
    assert read("d2h_kb_per_step", ctx) == 256 * 2 * 4 / 1024


def test_recorded_stages_cover_the_verdict_step(chip):
    """The six stages of a verdict step cover at least 90% of the
    harness's verdict span, and the device's busy time lies inside the
    dispatch and the wait."""
    lo, hi = T.window(chip)
    own = S.self_ns(chip["stages"], lo, hi)
    total = S.total_ns(chip["stages"], lo, hi)
    covered = own["serve.operands"] + sum(total[n] for n in S.STEP_STAGES[1:])
    verdict = sum(e - s for n, s, e in chip["spans"] if n == "ingest.verdict")
    assert covered >= 0.9 * verdict
    _, busy = T.busiest(chip, lo, hi)
    assert total["serve.dispatch"] + total["serve.block"] >= 0.9 * busy


def test_recorded_idle_split_by_stage(chip):
    lo, hi = T.window(chip)
    ops = chip["devices"][TPU0]
    by = S.idle_by_stage(ops, chip["stages"], chip["spans"], lo, hi)
    assert sum(by.values()) == sum(e - s for s, e in T.gaps(ops, lo, hi))
    assert max(by, key=by.get) == "serve.operands"
    assert by["serve.block"] == 1026250
    # Outside every stage the split is the harness's own, less the stages.
    by_span = T.idle_by_span(ops, chip["spans"], lo, hi)
    assert by["harness"] == by_span["harness"]


def test_finalize_reader_by_hand_and_without_stages(hand, old, monkeypatch,
                                                    tmp_path):
    """``stage_ms.finalize``: the self time of ``serve.finalize`` per
    verdict step; nothing where the trace has no stages."""
    assert read("stage_ms.finalize", ctx_of(hand)) == (3 + 2) / 2 / 1e6
    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    assert read("stage_ms.finalize", ctx_of(old)) is None
    untraced = types.SimpleNamespace(trace=None, lo=0, hi=0)
    assert read("stage_ms.finalize", untraced) is None


def test_finalize_reader_on_the_recorded_trace(chip):
    """Outside its block, unpack, head and rows stages the recorded
    non-adapting step's harvest takes 34 us."""
    ctx = ctx_of(chip)
    assert read("stage_ms.finalize", ctx) == 67460 / 2 / 1e6
    lo, hi = T.window(chip)
    total = S.total_ns(chip["stages"], lo, hi)
    assert 67460 == total["serve.finalize"] - sum(
        total[n] for n in ("serve.block", "serve.unpack", "serve.head",
                           "serve.rows"))
