"""The serving core's verdict rows: ``_finalize`` builds each unit's rows
in bulk, and every row must equal, field by field and by Python type, the
row built the per-plant way from the same step outputs.

The per-plant reference reads ``engine.last_outputs`` (each unit's host
outputs of the step just finalized, pad streams already cut) through the
unit's ``head.host_verdicts``, as ``_finalize`` does, and builds one
``Verdict`` per plant with ``int()``/``float()`` of numpy scalars.
"""

import copy
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_fleet_mesh
from repro.serving import GroupedStreamEngine, StreamEngine
from repro.serving.core import Verdict
from test_fused import small_detector
from test_grouped import NO_NORM, mixed_groups

WINDOW, STRIDE, CYCLES = 4, 3, 13
FIELDS = ("stream", "cycle", "pred", "prob", "latency_s", "deadline_miss",
          "score", "threshold", "group")
# Pad streams need a fleet that does not divide over the mesh.
PAD_DEVICES, PAD_STREAMS = 2, 5


def stream_engine(**kw):
    model, params = small_detector("SINT", 0)
    return StreamEngine(model, params, n_streams=5, window=WINDOW,
                        stride=STRIDE, n_features=2, **NO_NORM, **kw)


def grouped_engine(**kw):
    return GroupedStreamEngine(mixed_groups("SINT", n_per=3), n_features=2,
                               stride=STRIDE, **NO_NORM, **kw)


def pad_engine(**kw):
    model, params = small_detector("SINT", 0)
    return StreamEngine(model, params, n_streams=PAD_STREAMS, window=WINDOW,
                        stride=STRIDE, n_features=2,
                        mesh=make_fleet_mesh(PAD_DEVICES), **NO_NORM, **kw)


def pad_case(async_depth):
    engine = pad_engine(async_depth=async_depth)
    unit, = engine._units
    assert unit.s_pad > unit.n_streams
    return check_rows(engine, async_depth)


def per_plant_rows(unit, out, cycle, latency, miss):
    """One unit's rows, one ``Verdict`` per plant from numpy scalars."""
    pred, prob, score, thr = unit.head.host_verdicts(
        out, threshold=unit.live_threshold)
    return [Verdict(stream=unit.offset + i, cycle=cycle, pred=int(pred[i]),
                    prob=None if prob is None else float(prob[i]),
                    latency_s=latency, deadline_miss=miss,
                    score=None if score is None else float(score[i]),
                    threshold=thr, group=unit.name)
            for i in range(unit.n_streams)]


def check_rows(engine, async_depth):
    """Serve ``CYCLES`` cycles; every verdict step's rows must equal the
    per-plant rows in order, count, value and Python type. Returns the
    number of verdict steps checked."""
    n_plants = sum(u.n_streams for u in engine._units)
    rng = np.random.default_rng(0)
    readings = rng.normal(size=(CYCLES, n_plants, 2)).astype(np.float32)
    boundaries, checked = [], 0
    for c, r in enumerate(readings):
        if c + 1 >= WINDOW and (c + 1 - WINDOW) % STRIDE == 0:
            boundaries.append(c)
        got = engine.ingest(r)
        if not got:
            continue
        assert type(got) is list
        latency = got[0].latency_s
        assert type(latency) is float
        miss = latency > engine.deadline_s
        # Sync rows carry this call's cycle; async ones the boundary before.
        cycle = boundaries[-1 - async_depth]
        want = [row for u in engine._units
                for row in per_plant_rows(u, engine.last_outputs[u.name],
                                          cycle, latency, miss)]
        assert len(got) == len(want) == n_plants
        assert [v.stream for v in got] == list(range(n_plants))
        for g, w in zip(got, want):
            for f in FIELDS:
                a, b = getattr(g, f), getattr(w, f)
                assert type(a) is type(b), (f, type(a), type(b))
                assert a == b, (f, a, b)
            assert g.threshold is w.threshold
        checked += 1
    assert checked == len(boundaries) - async_depth
    return checked


_PAD_CHILD = """
import test_serving_rows as T
n = T.pad_case({depth})
print("PAD_ROWS_OK", n)
"""


def check_pad_rows(async_depth):
    """The pad-stream case on a 2-device mesh; in a child process that
    makes two host devices when this process has fewer."""
    if len(jax.devices()) >= PAD_DEVICES:
        return pad_case(async_depth)
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={PAD_DEVICES}").strip())
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c", _PAD_CHILD.format(depth=async_depth)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "PAD_ROWS_OK" in out.stdout
    return int(out.stdout.split("PAD_ROWS_OK")[1])


@pytest.mark.parametrize("build,async_depth", [
    (stream_engine, 0), (stream_engine, 1),
    (lambda **kw: grouped_engine(megakernel=True, **kw), 0),
    (lambda **kw: grouped_engine(megakernel=True, **kw), 1),
    (lambda **kw: grouped_engine(megakernel=False, **kw), 0),
    (None, 0), (None, 1)],
    ids=["stream-sync", "stream-async", "grouped-mega-sync",
         "grouped-mega-async", "grouped-pergroup-sync", "pad-2dev-sync",
         "pad-2dev-async"])
def test_rows_match_the_per_plant_build(build, async_depth):
    if build is None:
        n = check_pad_rows(async_depth)
    else:
        engine = build(async_depth=async_depth)
        if isinstance(engine, GroupedStreamEngine):
            heads = {type(u.head).__name__ for u in engine._units}
            assert "ClassifierHead" in heads and len(heads) == 4
        n = check_rows(engine, async_depth)
    assert n == 4 - async_depth


def test_rows_are_mutable_dataclasses():
    """The benchmark's fault checks copy rows and edit them in place."""
    assert tuple(f.name for f in dataclasses.fields(Verdict)) == FIELDS
    engine = stream_engine()
    rng = np.random.default_rng(1)
    rows = []
    for r in rng.normal(size=(WINDOW, 5, 2)).astype(np.float32):
        rows = engine.ingest(r) or rows
    v = rows[0]
    w = copy.copy(v)
    assert w == v and w is not v
    w.pred, w.cycle = 1 - v.pred, v.cycle - 1
    assert (w.pred, w.cycle) == (1 - v.pred, v.cycle - 1)
    assert (rows[0].pred, rows[0].cycle) != (w.pred, w.cycle)
    v.pred = 7
    assert rows[0].pred == 7
