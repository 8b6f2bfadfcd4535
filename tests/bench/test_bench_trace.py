"""The reduction from a profiler trace to per-layer numbers, on hand-made
intervals and on a small trace recorded on a TPU v5 lite (two verdict steps
of ``cls_sint.fleet4k``, kept in plain form as a fixture)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, os.path.abspath(ROOT))
from bench import trace as T  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_cls_sint_fleet4k.json")
DEVICE = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as fh:
        return json.load(fh)


def test_union_merges_overlaps_and_drops_empty():
    got = T.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 20), (14, 15)])
    assert got == [(0, 3), (5, 12), (14, 15)]


def test_busy_gaps_and_labels_by_hand():
    ops = [["a", 2, 4, "fusion"], ["k", 3, 6, "tpu_custom_call"],
           ["b", 12, 15, "copy"]]
    spans = [["ingest.nonverdict", 0, 5], ["harness", 5, 7],
             ["ingest.verdict", 7, 20]]
    assert T.busy_ns(ops, 0, 20) == 4 + 3
    assert T.gaps(ops, 0, 20) == [(0, 2), (6, 12), (15, 20)]
    assert T.idle_by_span(ops, spans, 0, 20) == {
        "ingest.nonverdict": 2, "harness": 1, "ingest.verdict": 5 + 5}
    assert T.label((6, 12), spans) == "ingest.verdict"
    assert T.kernel_ops(ops) == [ops[1]]
    assert T.clip_ops(ops, 4, 13) == [["k", 4, 6, "tpu_custom_call"],
                                      ["b", 12, 13, "copy"]]


def test_short_op_names_and_kernel_category():
    assert T.short_op("%copy.2 = f32[8,4]{1,0:T(8,128)S(1)} copy(f32[8,4] "
                      "%reshape.5)") == ("copy.2 copy", "copy")
    name, cat = T.short_op(
        '%_step.1 = f32[4096,128]{1,0} custom-call(f32[4096,512]{1,0} %pad.0'
        '), custom_call_target="tpu_custom_call", backend_config="x"')
    assert (name, cat) == ("_step.1 custom-call tpu_custom_call",
                           T.KERNEL_CATEGORY)


def test_recorded_trace_busy_against_a_timeline(recorded):
    lo, hi = T.window(recorded)
    ops = recorded["devices"][DEVICE]
    timeline = np.zeros(hi - lo, bool)
    for _, s, e, _ in ops:
        timeline[max(s, lo) - lo:min(e, hi) - lo] = True
    assert (lo, hi) == (0, 19948399)
    assert T.busy_ns(ops, lo, hi) == int(timeline.sum()) == 140190
    idle = sum(e - s for s, e in T.gaps(ops, lo, hi))
    assert idle == (hi - lo) - 140190
    assert T.busiest(recorded, lo, hi) == (DEVICE, 140190)


def test_recorded_trace_kernel_time(recorded):
    kernels = T.kernel_ops(recorded["devices"][DEVICE])
    assert [(s, e) for _, s, e, _ in kernels] == [(1638589, 1653874),
                                                  (11052166, 11067450)]
    assert sum(e - s for _, s, e, _ in kernels) == 15285 + 15284


def test_recorded_trace_idle_labelled_by_span(recorded):
    lo, hi = T.window(recorded)
    ops = recorded["devices"][DEVICE]
    by = T.idle_by_span(ops, recorded["spans"], lo, hi)
    assert by == {"ingest.nonverdict": 1149963, "harness": 336827,
                  "ingest.verdict": 18285419, "none": 36000}
    assert sum(by.values()) == (hi - lo) - 140190
    top = T.top_gaps(recorded, DEVICE, lo, hi, n=6)
    assert top[0] == ["all idle in ingest.verdict", 0.018285419]
    assert top[4] == ["gap in ingest.verdict", (10998715 - 1655170) / 1e9]
    assert len(top) == 6


def test_recorded_trace_top_ops(recorded):
    lo, hi = T.window(recorded)
    assert T.top_ops(recorded, lo, hi, n=2) == [
        ["fusion fusion", 5.7913e-05],
        ["_step.1 custom-call tpu_custom_call", 3.0569e-05]]
