"""Async double-buffered serving (``async_depth=1``): verdict parity suite.

The contract (serving/core.py): at a ready boundary the async engine first
harvests the previous step's in-flight outputs, then dispatches the new
step and returns — so verdicts arrive one boundary late but must be
**bit-identical** to synchronous mode (same executables, same operands,
same adapt-threshold ordering), across stride/window/adapt/ring-wraparound
compositions, grouped fleets, and sharded meshes.  ``flush()`` drains the
final in-flight step; latency/deadline accounting moves to
dispatch→harvest; the one-dispatch-per-step jaxpr guarantee is untouched.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.launch.mesh import make_fleet_mesh
from repro.serving import GroupedStreamEngine, ModelGroup, StreamEngine
from repro.sim import ReconstructionHead, fleet_readings
from test_drift import energy_detector
from _jaxpr import count_pallas_calls
from test_fused import detector_params, small_detector
from test_streams import identity_probe

N_DEVICES = len(jax.devices())


def verdict_key(v):
    """Everything a verdict says except its timing (latency/deadline are
    mode-dependent by design)."""
    return (v.stream, v.cycle, v.pred, v.prob, v.score, v.threshold, v.group)


def serve(eng, readings, flush=True):
    out = []
    for c in range(readings.shape[0]):
        out.extend(eng.ingest(readings[c]))
    if flush:
        out.extend(eng.flush())
    return out


def assert_verdicts_match(sync_vs, async_vs):
    assert len(sync_vs) == len(async_vs) > 0
    for a, b in zip(sync_vs, async_vs):
        assert verdict_key(a) == verdict_key(b)


class TestAsyncParity:
    @settings(max_examples=15, deadline=None)
    @given(window=st.integers(3, 8), stride=st.integers(1, 5),
           extra=st.integers(0, 20), adapt=st.booleans())
    def test_async_bit_matches_sync(self, window, stride, extra, adapt):
        """The hypothesis property: over arbitrary window/stride/wraparound
        compositions, with and without streaming threshold adaptation, the
        async verdict stream (+ flush) equals the sync one verdict-for-
        verdict — scores, thresholds and live-threshold trajectory
        bit-exact."""
        n_streams, n_feat = 3, 1
        model, params = energy_detector(window, n_feat)
        head_kw = dict(threshold=0.7, target_fpr=0.1)
        kw = dict(n_streams=n_streams, n_features=n_feat, window=window,
                  stride=stride, norm_mean=(0.0,), norm_std=(1.0,),
                  shard=False, adapt=adapt)
        rng = np.random.default_rng(window * 100 + stride * 10 + extra)
        readings = rng.normal(size=(window + extra, n_streams, n_feat)) \
            .astype(np.float32)
        engines = {}
        for depth in (0, 1):
            eng = StreamEngine(model, params,
                               head=ReconstructionHead(**head_kw),
                               async_depth=depth, **kw)
            engines[depth] = (eng, serve(eng, readings))
        (sync, sv), (asy, av) = engines[0], engines[1]
        assert_verdicts_match(sv, av)
        assert sync.stats.windows == asy.stats.windows
        assert sync.stats.steps == asy.stats.steps
        assert sync.live_threshold == asy.live_threshold

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_classifier_fleet_parity(self, scheme):
        """Scenario fleet + classifier head (small detector), quantized and
        float."""
        model, params = small_detector(scheme, seed=2)
        readings = fleet_readings(4, 33, seed=5)
        kw = dict(n_streams=4, n_features=2, window=4, stride=3, shard=False)
        sync = StreamEngine(model, params, **kw)
        asy = StreamEngine(model, params, async_depth=1, **kw)
        assert_verdicts_match(serve(sync, readings), serve(asy, readings))

    def test_one_boundary_delay_and_flush(self):
        """The async schedule itself: a ready boundary returns the PREVIOUS
        boundary's verdicts (first one returns []), flush returns the final
        in-flight batch exactly once."""
        window, stride, n = 4, 3, 2
        model, params = identity_probe(window, 2)
        eng = StreamEngine(model, params, n_streams=n, n_features=2,
                           window=window, stride=stride, shard=False,
                           norm_mean=(0.0, 0.0), norm_std=(1.0, 1.0),
                           async_depth=1)
        rng = np.random.default_rng(0)
        boundaries = {}
        for c in range(10):                      # ready at cycles 3, 6, 9
            vs = eng.ingest(rng.normal(size=(n, 2)).astype(np.float32))
            if vs:
                boundaries[c] = sorted({v.cycle for v in vs})
        assert boundaries == {6: [3], 9: [6]}    # one boundary late
        assert eng.stats.steps == 3              # cycle 9's step in flight
        assert eng.stats.windows == 2 * n
        flushed = eng.flush()
        assert sorted({v.cycle for v in flushed}) == [9]
        assert eng.stats.windows == 3 * n
        assert eng.flush() == []                 # drain is idempotent

    def test_sync_flush_is_noop(self):
        model, params = identity_probe(3, 2)
        eng = StreamEngine(model, params, n_streams=2, n_features=2,
                           window=3, stride=1, shard=False,
                           norm_mean=(0.0, 0.0), norm_std=(1.0, 1.0))
        assert eng.flush() == []
        rng = np.random.default_rng(1)
        for c in range(5):
            eng.ingest(rng.normal(size=(2, 2)).astype(np.float32))
        assert eng.flush() == []
        assert eng.stats.windows == 3 * 2

    def test_async_depth_validation(self):
        model, params = identity_probe(3, 2)
        with pytest.raises(ValueError, match="async_depth"):
            StreamEngine(model, params, n_streams=2, n_features=2, window=3,
                         shard=False, async_depth=2)

    def test_latency_accounting_is_dispatch_to_harvest(self):
        """Async latencies span the whole inter-boundary interval (the
        overlapped host ingest is genuine verdict-visibility delay), and
        misses are judged against that span."""
        model, params = identity_probe(3, 2)
        eng = StreamEngine(model, params, n_streams=2, n_features=2,
                           window=3, stride=2, shard=False, deadline_s=1e-9,
                           norm_mean=(0.0, 0.0), norm_std=(1.0, 1.0),
                           async_depth=1)
        rng = np.random.default_rng(2)
        vs = serve(eng, rng.normal(size=(7, 2, 2)).astype(np.float32))
        assert all(v.latency_s > 0 for v in vs)
        assert all(v.deadline_miss for v in vs)  # 1ns deadline always missed
        assert eng.stats.deadline_misses == eng.stats.windows == len(vs)
        assert len(eng.stats.latencies_s) == eng.stats.steps


class TestAsyncGrouped:
    def test_grouped_async_matches_sync(self):
        """Mixed-head, mixed-window grouped fleet: async == sync verdict-
        for-verdict, including the adaptive group's threshold trajectory."""
        det_model, det_params = small_detector("SINT", seed=1)
        ae_model, ae_params = energy_detector(6, 2)
        readings = fleet_readings(5, 40, seed=9)

        def make(depth):
            return GroupedStreamEngine(
                [ModelGroup("det", det_model, det_params, 3),
                 ModelGroup("ae", ae_model, ae_params, 2,
                            head=ReconstructionHead(threshold=2.0,
                                                    target_fpr=0.1),
                            adapt=True)],
                n_features=2, stride=3, shard=False, async_depth=depth)

        sync, asy = make(0), make(1)
        assert_verdicts_match(serve(sync, readings), serve(asy, readings))
        assert sync.group_windows() == asy.group_windows()
        assert sync.live_thresholds() == asy.live_thresholds()

    def test_run_interface_with_flush(self):
        """run() drives async engines too (no auto-flush — the final step
        stays in flight until flush())."""
        class _Reading:
            def __init__(self, a, b):
                self.tb0_meas, self.wd_meas = a, b

        class _Stream:
            def __init__(self, seed):
                self.rng = np.random.default_rng(seed)

            def step(self):
                return _Reading(self.rng.normal(), self.rng.normal())

        model, params = small_detector("REAL", seed=0)
        kw = dict(n_streams=2, n_features=2, window=4, stride=2, shard=False)
        sync = StreamEngine(model, params, **kw)
        asy = StreamEngine(model, params, async_depth=1, **kw)
        sv = sync.run([_Stream(0), _Stream(1)], 12)
        av = asy.run([_Stream(0), _Stream(1)], 12)
        assert len(av) == len(sv) - 2            # one boundary in flight
        av += asy.flush()
        assert_verdicts_match(sv, av)


class TestAsyncSharded:
    @pytest.mark.parametrize("n_devices",
                             [n for n in (1, 2, 4) if n <= N_DEVICES])
    def test_sharded_async_matches_sync(self, n_devices):
        """The pipeline composes with the ("data",) mesh: async verdicts on
        a non-divisible padded fleet bit-match the sync sharded engine."""
        model, params = small_detector("REAL", seed=3)
        readings = fleet_readings(5, 30, seed=4)
        engines = {}
        for depth in (0, 1):
            eng = StreamEngine(model, params, n_streams=5, n_features=2,
                               window=4, stride=3,
                               mesh=make_fleet_mesh(n_devices),
                               async_depth=depth)
            eng.warmup()
            engines[depth] = serve(eng, readings)
        assert_verdicts_match(engines[0], engines[1])


class TestAsyncDispatch:
    def test_one_dispatch_per_step_preserved(self):
        """async_depth changes host scheduling only: the traced verdict
        step of an async fused engine is still exactly ONE pallas_call."""
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=4, backend="pallas",
                           fused=True, shard=False, async_depth=1)
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 1
