"""The serving core's tracing: host spans inside ``ingest()`` on the
profiler's clock, the device step's ``ring_scatter`` scope, the kernels'
names, and the byte counters.

Spans are recorded under ``jax.profiler`` on the CPU and read back with the
benchmark's loaders (``bench.trace`` for the harness's spans,
``bench.stages`` for the core's), as a traced benchmark run reads them.
"""

import os
import sys

import jax
import numpy as np
import pytest

from repro.serving import AdaptConfig, GroupedStreamEngine, StreamEngine
from repro.sim import ReconstructionHead
from test_drift import energy_detector
from test_fused import small_detector
from test_grouped import NO_NORM, mixed_groups
from _jaxpr import equations

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)
from bench import stages as S  # noqa: E402
from bench import trace as T  # noqa: E402

WINDOW, STRIDE, CYCLES = 4, 3, 13
# Verdict boundaries at scan counts 4, 7, 10, 13: blocks of 4, 3, 3, 3.
BLOCKS = (4, 3, 3, 3)
STAGES = ("serve.ingest", "serve.normalize", "serve.operands",
          "serve.dispatch", "serve.finalize", "serve.block", "serve.unpack",
          "serve.head", "serve.rows")


def stream_engine(**kw):
    model, params = small_detector("SINT", 0)
    return StreamEngine(model, params, n_streams=5, window=WINDOW,
                        stride=STRIDE, n_features=2, **NO_NORM, **kw)


def grouped_engine(**kw):
    return GroupedStreamEngine(mixed_groups("SINT"), n_features=2,
                               stride=STRIDE, **NO_NORM, **kw)


def readings(engine, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(CYCLES, engine.n_streams, 2)).astype(np.float32)


def is_boundary(c: int) -> bool:
    return c + 1 >= WINDOW and (c + 1 - WINDOW) % STRIDE == 0


def traced(tmp_path, engine):
    """Serve ``CYCLES`` cycles under the profiler, each ``ingest()`` inside
    a harness span as the benchmark writes them; the plain forms of
    ``bench.trace`` and ``bench.stages``."""
    engine.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for c, r in enumerate(readings(engine)):
            name = "ingest.verdict" if is_boundary(c) else "ingest.nonverdict"
            with jax.profiler.TraceAnnotation(name):
                engine.ingest(r)
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    return T.load(path), S.load(path)


def enclosing(stage, ingests):
    """The ``serve.ingest`` span that holds ``stage``."""
    held = [i for i in ingests if i[1] <= stage[1] and stage[2] <= i[2]]
    assert len(held) == 1, stage
    return held[0]


@pytest.mark.parametrize("build,units,async_depth", [
    (stream_engine, 1, 0), (grouped_engine, 4, 0), (stream_engine, 1, 1)],
    ids=["stream-sync", "grouped-mega-sync", "stream-async"])
def test_every_stage_nests_under_ingest(tmp_path, build, units, async_depth):
    engine = build(async_depth=async_depth)
    if units > 1:
        assert engine._mega
    plain, got = traced(tmp_path, engine)
    stages = got["stages"]
    assert {s[0] for s in stages} == set(STAGES)
    ingests = [s for s in stages if s[0] == "serve.ingest"]
    assert [i[3]["cycle"] for i in ingests] == list(range(CYCLES))
    for s in stages:
        if s[0] != "serve.ingest":
            enclosing(s, ingests)
    n = S.count(stages, *got["window"])
    steps = len(BLOCKS)
    finalized = steps - async_depth
    assert n == {"serve.ingest": CYCLES, "serve.normalize": CYCLES,
                 "serve.operands": steps, "serve.dispatch": steps,
                 "serve.finalize": finalized, "serve.block": finalized,
                 "serve.unpack": finalized,
                 "serve.head": finalized * units,
                 "serve.rows": finalized * units}
    # The harness's spans stay the harness's alone.
    assert {s[0] for s in plain["spans"]} <= set(T.SPANS)
    assert len(plain["spans"]) == CYCLES
    assert got["window"] == list(T.window(plain))


@pytest.mark.parametrize("async_depth", [0, 1], ids=["sync", "async"])
def test_finalize_carries_the_cycle_of_its_step(tmp_path, async_depth):
    plain, got = traced(tmp_path, stream_engine(async_depth=async_depth))
    stages = got["stages"]
    ingests = [s for s in stages if s[0] == "serve.ingest"]
    boundaries = [c for c in range(CYCLES) if is_boundary(c)]
    finals = [s for s in stages if s[0] == "serve.finalize"]
    # Sync: the step finalizes inside the call that dispatched it; async:
    # inside the next boundary's call, one step late.
    assert [f[3]["cycle"] for f in finals] == \
        boundaries[:len(boundaries) - async_depth]
    for f in finals:
        called = enclosing(f, ingests)[3]["cycle"]
        want = boundaries[boundaries.index(f[3]["cycle"]) + async_depth]
        assert called == want


def test_byte_counters_of_a_single_model_fleet(tmp_path):
    engine = stream_engine()
    plain, got = traced(tmp_path, engine)
    # Pending block (5 plants x L readings x 2 features, f32) plus the
    # write position (int32) and threshold (f32) per step; the (5, 2) f32
    # logits back.
    h2d = sum(5 * length * 2 * 4 + 4 + 4 for length in BLOCKS)
    d2h = len(BLOCKS) * 5 * 2 * 4
    assert (engine.stats.h2d_bytes, engine.stats.d2h_bytes) == (h2d, d2h)
    lo, hi = got["window"]
    assert S.stat_sum(got["stages"], "serve.dispatch", "h2d_bytes",
                      lo, hi) == h2d
    assert S.stat_sum(got["stages"], "serve.unpack", "d2h_bytes",
                      lo, hi) == d2h


@pytest.mark.parametrize("megakernel", [None, False],
                         ids=["mega", "per-group"])
def test_byte_counters_of_a_grouped_fleet(megakernel):
    engine = grouped_engine(megakernel=megakernel)
    for r in readings(engine):
        engine.ingest(r)
    if megakernel is None:
        # One (4 groups, 2 plants, L, 2) f32 block, 4 positions (int32) and
        # 4 thresholds (f32) a step; the (4, 2, 2) f32 payload back.
        h2d = sum(4 * 2 * length * 2 * 4 + 16 + 16 for length in BLOCKS)
        d2h = len(BLOCKS) * 4 * 2 * 2 * 4
    else:
        # Per group: a (2, L, 2) f32 block, a position and a threshold;
        # back, the classifier's (2, 2) logits and three (2, 1) scores.
        h2d = sum(4 * (2 * length * 2 * 4 + 8) for length in BLOCKS)
        d2h = len(BLOCKS) * (2 * 2 + 3 * 2 * 1) * 4
    assert (engine.stats.h2d_bytes, engine.stats.d2h_bytes) == (h2d, d2h)


@pytest.mark.parametrize("async_depth", [0, 1], ids=["sync", "async"])
def test_finalize_carries_the_recalibration_counters(tmp_path, async_depth):
    model, params = energy_detector(WINDOW, 2)
    engine = StreamEngine(
        model, params, n_streams=5, window=WINDOW, stride=STRIDE,
        n_features=2, head=ReconstructionHead(threshold=1.0, target_fpr=0.2),
        adapt=AdaptConfig(capacity=3, min_count=2), async_depth=async_depth,
        **NO_NORM)
    plain, got = traced(tmp_path, engine)
    lo, hi = got["window"]
    # One recalibration per finalized step: the (5, 3) f32 ring and the
    # (5,) int32 counts come to the host for it, apart from d2h_bytes.
    recals = len(BLOCKS) - async_depth
    assert engine.stats.recalibrations == recals
    assert engine.stats.calib_d2h_bytes == recals * (5 * 3 * 4 + 5 * 4)
    assert engine.stats.d2h_bytes == len(BLOCKS) * 5 * 1 * 4 - \
        async_depth * 5 * 1 * 4
    assert S.stat_sum(got["stages"], "serve.finalize", "recalibrations",
                      lo, hi) == engine.stats.recalibrations
    assert S.stat_sum(got["stages"], "serve.finalize", "calib_d2h_bytes",
                      lo, hi) == engine.stats.calib_d2h_bytes


def scoped(jaxpr, scope: str, inside: bool = False):
    """Primitives run under ``scope``: an equation whose name stack holds
    it, and everything nested in such an equation."""
    for e in jaxpr.eqns:
        here = inside or scope in str(e.source_info.name_stack).split("/")
        if here:
            yield e.primitive.name
        for v in e.params.values():
            for u in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(u, "jaxpr", u)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from scoped(sub, scope, here)


def test_ring_write_and_gather_run_under_their_scope():
    engine = stream_engine(backend="pallas")
    step, args = next(engine._step_examples())
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    prims = set(scoped(jaxpr, "ring_scatter"))
    assert {"scatter", "gather"} <= prims
    assert "pallas_call" not in prims
    assert "ring_scatter" in step.lower(*args).compile().as_text()


@pytest.mark.parametrize("build,name", [
    (lambda: stream_engine(backend="pallas"), "fused_mlp"),
    (lambda: grouped_engine(backend="pallas"), "grouped_fused_mlp")],
    ids=["fused", "grouped"])
def test_kernels_carry_their_names(build, name):
    step, args = next(build()._step_examples())
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    calls = [e for e in equations(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert str(calls[0].params["name"]) == name
