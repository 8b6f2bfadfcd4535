"""GroupedStreamEngine: heterogeneous model-group fleet serving.

Acceptance: grouped verdicts bit-match (REAL) / epsilon-match (quantized)
N independent single-model StreamEngines over ring-wraparound runs, with
exactly one fused Pallas dispatch per group per verdict step — sharded and
unsharded — and mixed-head Verdict field invariants (per-group thresholds
never cross-contaminate)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import layers as L
from repro.core import quantize, sequential
from repro.launch.mesh import make_fleet_mesh
from repro.serving import GroupedStreamEngine, ModelGroup, StreamEngine
from repro.sim import (ClassifierHead, ForecastHead, MarginHead,
                       ReconstructionHead)

from _jaxpr import count_pallas_calls

SCHEMES = ("REAL", "SINT", "INT", "DINT")
N_DEVICES = len(jax.devices())
NO_NORM = dict(norm_mean=(0.0, 0.0), norm_std=(1.0, 1.0))


def small_model(n_in, n_out, scheme, seed):
    model = sequential([L.Input(), L.Dense(units=6, activation="relu"),
                        L.Dense(units=n_out, activation="linear")], (n_in,))
    params = model.init_params(jax.random.PRNGKey(seed))
    if scheme != "REAL":
        calib = [jax.random.normal(jax.random.PRNGKey(600 + seed + i),
                                   (n_in,)) * 2.0 for i in range(4)]
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


def mixed_groups(scheme, n_per=2, seed=0):
    """Four heterogeneous groups over a 4-reading window (2 features):
    classifier, reconstruction, margin, forecast — the forecast group's
    model eats 3 readings and predicts the 4th, so its ring window (4)
    matches the others through a different input geometry."""
    clf = small_model(8, 2, scheme, seed)
    ae = small_model(8, 8, scheme, seed + 1)
    mg = small_model(8, 3, scheme, seed + 2)
    fc = small_model(6, 2, scheme, seed + 3)
    return [
        ModelGroup("clf", *clf, n_per, ClassifierHead()),
        ModelGroup("ae", *ae, n_per, ReconstructionHead(threshold=0.25)),
        ModelGroup("mg", *mg, n_per,
                   MarginHead(threshold=0.5, center=(0.1, -0.2, 0.3))),
        ModelGroup("fc", *fc, n_per,
                   ForecastHead(threshold=0.75, n_features=2)),
    ]


def drive_both(groups, n_cycles, *, stride, seed=0, engine_kw=None,
               single_kw=None):
    """Run a GroupedStreamEngine and per-group independent StreamEngines
    over identical readings; returns (grouped_engine, grouped_verdicts,
    {name: (offset, single_engine, single_verdicts)})."""
    base = dict(NO_NORM, n_features=2, stride=stride)
    single_kw = dict(base, **(single_kw if single_kw is not None
                              else (engine_kw or {})))
    engine_kw = dict(base, **(engine_kw or {}))
    ge = GroupedStreamEngine(groups, **engine_kw)
    singles, off = {}, 0
    for g in groups:
        singles[g.name] = (off, StreamEngine(
            g.model, g.params, n_streams=g.n_streams, head=g.head,
            **single_kw), [])
        off += g.n_streams
    rng = np.random.default_rng(seed)
    readings = rng.normal(size=(n_cycles, ge.n_streams, 2)).astype(np.float32)
    gv = []
    for c in range(n_cycles):
        gv += ge.ingest(readings[c])
        for name, (o, eng, sv) in singles.items():
            sv += eng.ingest(readings[c][o:o + eng.n_streams])
    return ge, gv, singles


def assert_parity(ge, gv, singles, scheme):
    """Grouped verdicts partition exactly into the independent engines'
    verdict streams: bit-match for REAL, epsilon for quantized schemes
    (the grouped step traces all bodies into one XLA program, so fusion
    context may reassociate quantized arithmetic)."""
    assert len(gv) == sum(len(sv) for _, _, sv in singles.values())
    for name, (off, eng, sv) in singles.items():
        mine = [v for v in gv if v.group == name]
        assert len(mine) == len(sv)
        for a, b in zip(mine, sv):
            assert a.stream == off + b.stream
            assert a.cycle == b.cycle
            assert a.threshold == b.threshold
            assert (a.prob is None) == (b.prob is None)
            assert (a.score is None) == (b.score is None)
            if scheme == "REAL":
                assert a.pred == b.pred
                assert a.prob == b.prob and a.score == b.score
            else:
                for x, y in ((a.prob, b.prob), (a.score, b.score)):
                    if x is not None:
                        np.testing.assert_allclose(x, y, rtol=1e-5,
                                                   atol=1e-5)
        if scheme == "REAL":
            np.testing.assert_array_equal(ge.last_outputs[name],
                                          eng.last_logits)
        else:
            np.testing.assert_allclose(ge.last_outputs[name],
                                       eng.last_logits, rtol=1e-5, atol=1e-5)


class TestGroupedParity:
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_matches_independent_engines_over_wraparound(self, scheme):
        """3 ring wraps (window 4, 30 cycles) across all four head types."""
        ge, gv, singles = drive_both(mixed_groups(scheme), 30, stride=3,
                                     engine_kw={"shard": False},
                                     single_kw={"shard": False})
        assert gv
        assert_parity(ge, gv, singles, scheme)

    def test_heterogeneous_windows_fire_on_their_own_cadence(self):
        """Groups whose ring windows differ become ready at different
        cycles; each fires exactly when its own independent engine does."""
        groups = [
            ModelGroup("w4", *small_model(8, 8, "REAL", 0), 2,
                       ReconstructionHead(threshold=0.5)),
            ModelGroup("w5", *small_model(10, 10, "REAL", 1), 3,
                       ReconstructionHead(threshold=0.5)),
        ]
        ge, gv, singles = drive_both(groups, 27, stride=2,
                                     engine_kw={"shard": False},
                                     single_kw={"shard": False})
        assert {v.cycle for v in gv if v.group == "w4"} == \
            set(range(3, 27, 2))
        assert {v.cycle for v in gv if v.group == "w5"} == \
            set(range(4, 27, 2))
        assert_parity(ge, gv, singles, "REAL")

    @settings(max_examples=6, deadline=None)
    @given(scheme=st.sampled_from(SCHEMES), stride=st.integers(1, 5),
           extra=st.integers(0, 18), seed=st.integers(0, 3))
    def test_parity_property(self, scheme, stride, extra, seed):
        """Property form of the acceptance criterion: any stride/length/seed,
        grouped == N independent engines (bit for REAL, epsilon quantized),
        including runs that wrap the ring several times."""
        ge, gv, singles = drive_both(mixed_groups(scheme, seed=seed),
                                     6 + extra, stride=stride, seed=seed,
                                     engine_kw={"shard": False},
                                     single_kw={"shard": False})
        assert_parity(ge, gv, singles, scheme)

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_sharded_matches_unsharded(self, scheme):
        """The sharded grouped step (explicit mesh, per-group pad contract)
        against the unsharded one — and both against independent engines on
        the same mesh (same shard widths -> REAL stays bit-exact)."""
        mesh = make_fleet_mesh(2)
        ge_s, gv_s, singles = drive_both(mixed_groups(scheme), 30, stride=3,
                                         engine_kw={"mesh": mesh},
                                         single_kw={"mesh": mesh})
        assert_parity(ge_s, gv_s, singles, scheme)
        ge_u, gv_u, _ = drive_both(mixed_groups(scheme), 30, stride=3,
                                   engine_kw={"shard": False},
                                   single_kw={"shard": False})
        assert len(gv_s) == len(gv_u)
        for a, b in zip(gv_s, gv_u):
            assert (a.stream, a.cycle, a.group) == (b.stream, b.cycle,
                                                    b.group)
            for x, y in ((a.prob, b.prob), (a.score, b.score)):
                if x is not None:
                    np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    def test_pad_stream_contract_per_group(self):
        """Group sizes that don't divide the mesh: pad streams are served
        but never surface in verdicts or last_outputs."""
        groups = mixed_groups("REAL", n_per=3)       # 3 streams per group,
        mesh = make_fleet_mesh(2)                    # padded to 4 per group
        ge, gv, singles = drive_both(groups, 18, stride=3,
                                     engine_kw={"mesh": mesh},
                                     single_kw={"shard": False})
        assert all(r.shape[0] == 4 for r in ge._rings)
        assert {v.stream for v in gv} == set(range(12))
        assert all(ge.last_outputs[n].shape[0] == 3 for n in ge.last_outputs)
        assert_parity(ge, gv, singles, "REAL")


class TestSingleDispatchPerGroup:
    """Acceptance: one fused pallas_call per group per verdict step, in the
    jaxpr, sharded and unsharded."""

    def _dispatch_count(self, mesh):
        groups = mixed_groups("SINT")
        kw = {"mesh": mesh} if mesh is not None else {"shard": False}
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 backend="pallas", **NO_NORM, **kw)
        key = tuple((gi, ge.stride) for gi in range(len(groups)))
        step = ge._get_step(key)
        rings = tuple(jnp.zeros_like(r) for r in ge._rings)
        calibs = tuple(jnp.zeros_like(c) for c in ge._calibs)
        countss = tuple(jnp.zeros_like(c) for c in ge._counts)
        blocks = tuple(jnp.zeros((ge._groups[gi].s_pad, length, 2),
                                 jnp.float32) for gi, length in key)
        poss = tuple(jnp.int32(0) for _ in key)
        thrs = tuple(ge._thr(ge._groups[gi]) for gi, _ in key)
        jaxpr = jax.make_jaxpr(step)(rings, calibs, countss, blocks, poss,
                                     thrs)
        return count_pallas_calls(jaxpr.jaxpr), len(groups)

    def test_unsharded_step_is_one_dispatch_per_group(self):
        n, n_groups = self._dispatch_count(None)
        assert n == n_groups == 4

    def test_sharded_step_is_one_dispatch_per_group(self):
        """Under shard_map each device runs the same program: still exactly
        one fused dispatch per group in the (per-shard) jaxpr — a 1-wide
        mesh exercises the shard_map path in any process."""
        n, n_groups = self._dispatch_count(make_fleet_mesh(min(N_DEVICES, 2)))
        assert n == n_groups == 4

    def test_partial_ready_step_dispatches_only_ready_groups(self):
        """A fill-in step where only some groups fire compiles a program
        with exactly one dispatch per READY group."""
        groups = mixed_groups("SINT")
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 backend="pallas", shard=False, **NO_NORM)
        key = ((1, 4), (3, 4))                       # two of four ready
        step = ge._get_step(key)
        rings = tuple(jnp.zeros_like(ge._rings[gi]) for gi, _ in key)
        calibs = tuple(jnp.zeros_like(ge._calibs[gi]) for gi, _ in key)
        countss = tuple(jnp.zeros_like(ge._counts[gi]) for gi, _ in key)
        blocks = tuple(jnp.zeros((ge._groups[gi].s_pad, length, 2),
                                 jnp.float32) for gi, length in key)
        thrs = tuple(ge._thr(ge._groups[gi]) for gi, _ in key)
        jaxpr = jax.make_jaxpr(step)(rings, calibs, countss, blocks,
                                     (jnp.int32(0), jnp.int32(0)), thrs)
        assert count_pallas_calls(jaxpr.jaxpr) == 2

    def test_warmup_precompiles_every_schedule_key(self):
        """After warmup, serving never compiles on the hot path: every
        ready-combination the readiness schedule can produce is already in
        the step cache."""
        groups = [
            ModelGroup("w4", *small_model(8, 8, "REAL", 0), 2,
                       ReconstructionHead(threshold=0.5)),
            ModelGroup("w5", *small_model(10, 10, "REAL", 1), 2,
                       ReconstructionHead(threshold=0.5)),
        ]
        ge = GroupedStreamEngine(groups, n_features=2, stride=2,
                                 shard=False, **NO_NORM)
        ge.warmup()
        compiled = set(ge._steps)
        rng = np.random.default_rng(0)
        for c in range(30):
            ge.ingest(rng.normal(size=(4, 2)).astype(np.float32))
        assert set(ge._steps) == compiled


class TestMixedVerdictInvariants:
    """Satellite: Verdict field contracts per head type, and per-group
    thresholds never cross-contaminate."""

    def test_verdict_fields_by_head(self):
        ge, gv, _ = drive_both(mixed_groups("REAL"), 12, stride=4,
                               engine_kw={"shard": False},
                               single_kw={"shard": False})
        by_group = {}
        for v in gv:
            by_group.setdefault(v.group, []).append(v)
        assert set(by_group) == {"clf", "ae", "mg", "fc"}
        for v in by_group["clf"]:
            assert v.prob is not None and 0.0 <= v.prob <= 1.0
            assert v.score is None and v.threshold is None
            assert v.pred in (0, 1)
        for name in ("ae", "mg", "fc"):
            for v in by_group[name]:
                assert v.prob is None
                assert v.score is not None and v.threshold is not None
                assert v.pred == int(v.score > v.threshold)

    def test_thresholds_never_cross_contaminate(self):
        """Each score group's verdicts carry ITS calibrated threshold —
        three deliberately different values stay with their groups."""
        ge, gv, _ = drive_both(mixed_groups("REAL"), 12, stride=4,
                               engine_kw={"shard": False},
                               single_kw={"shard": False})
        want = {"ae": 0.25, "mg": 0.5, "fc": 0.75, "clf": None}
        seen = {}
        for v in gv:
            seen.setdefault(v.group, set()).add(v.threshold)
        assert seen == {k: {want[k]} for k in seen}

    def test_stream_attribution(self):
        """Verdict.stream is the GLOBAL fleet index; each group covers its
        contiguous slice exactly."""
        groups = mixed_groups("REAL", n_per=3)
        ge, gv, _ = drive_both(groups, 8, stride=4,
                               engine_kw={"shard": False},
                               single_kw={"shard": False})
        slices = {name: set(range(off, off + n))
                  for name, off, n in ge.groups}
        for v in gv:
            assert v.stream in slices[v.group]
        for name, want in slices.items():
            assert {v.stream for v in gv if v.group == name} == want


class TestGroupedEngineContract:
    def test_validation(self):
        g = mixed_groups("REAL")
        with pytest.raises(ValueError, match="at least one"):
            GroupedStreamEngine([], n_features=2, **NO_NORM)
        with pytest.raises(ValueError, match="duplicate"):
            GroupedStreamEngine(
                [g[0], ModelGroup("clf", g[1].model, g[1].params, 2,
                                  g[1].head)],
                n_features=2, shard=False, **NO_NORM)
        with pytest.raises(ValueError, match="n_streams"):
            GroupedStreamEngine(
                [ModelGroup("x", g[0].model, g[0].params, 0, g[0].head)],
                n_features=2, shard=False, **NO_NORM)
        with pytest.raises(ValueError):
            GroupedStreamEngine(g, n_features=2, stride=0, shard=False,
                                **NO_NORM)

    def test_wrong_reading_shape_rejected(self):
        ge = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                 shard=False, **NO_NORM)
        with pytest.raises(ValueError, match="readings"):
            ge.ingest(np.zeros((3, 2), np.float32))

    def test_stats_accounting(self):
        ge, gv, _ = drive_both(mixed_groups("REAL"), 10, stride=3,
                               engine_kw={"shard": False},
                               single_kw={"shard": False})
        st_ = ge.stats
        # window 4, stride 3 -> steps at cycles 4, 7, 10 (all groups ready
        # together: every group's ring window is 4).
        assert st_.cycles == 10
        assert st_.steps == 3
        assert st_.windows == 3 * 8 == len(gv)
        assert len(st_.latencies_s) == st_.steps
        assert ge.group_windows() == {"clf": 6, "ae": 6, "mg": 6, "fc": 6}
        assert st_.wall_s > 0 and st_.windows_per_s() > 0

    def test_fused_true_on_unfusable_group_raises(self):
        model = sequential([L.Input(),
                            L.Dense(units=6, activation="softmax"),
                            L.Dense(units=2, activation="linear")], (8,))
        params = model.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="clf.*cannot fuse"):
            GroupedStreamEngine(
                [ModelGroup("clf", model, params, 2, ClassifierHead(),
                            fused=True)],
                n_features=2, shard=False, **NO_NORM)

    def test_run_drives_plant_fleet(self):
        """run() over real PlantStreams: MSF reading layout, group
        attribution intact."""
        from repro.sim import build_autoencoder, build_detector, build_fleet
        clf = build_detector()
        ae = build_autoencoder()
        groups = [
            ModelGroup("clf", clf, clf.init_params(jax.random.PRNGKey(0)), 2),
            ModelGroup("ae", ae, ae.init_params(jax.random.PRNGKey(1)), 2,
                       ReconstructionHead(threshold=1.0)),
        ]
        ge = GroupedStreamEngine(groups, shard=False)
        ge.warmup()
        verdicts = ge.run(build_fleet(["baseline"], 4, seed=0), 210)
        assert {v.group for v in verdicts} == {"clf", "ae"}
        assert {v.stream for v in verdicts} == {0, 1, 2, 3}
        with pytest.raises(ValueError, match="fleet size"):
            ge.run(build_fleet(["baseline"], 3, seed=0), 10)


class TestMarginHead:
    """The one-class margin head (Deep-SVDD style): score = mean squared
    distance of the embedding from a fixed benign center."""

    def test_batch_scores_math(self):
        head = MarginHead(threshold=1.0, center=(1.0, -1.0))
        out = jnp.asarray([[1.0, -1.0], [2.0, 0.0], [0.0, 0.0]])
        np.testing.assert_allclose(
            np.asarray(head.batch_scores(out, out)), [0.0, 1.0, 1.0])

    def test_epilogue_reduces_to_one_score_per_stream(self):
        head = MarginHead(threshold=1.0, center=(0.5, 0.5, 0.5))
        out = jnp.asarray(np.random.default_rng(0)
                          .normal(size=(4, 3)).astype(np.float32))
        red = head.epilogue(jnp.zeros((4, 8)), out)
        assert red.shape == (4, 1)
        np.testing.assert_allclose(
            np.asarray(red)[:, 0],
            np.mean((np.asarray(out) - 0.5) ** 2, axis=-1), rtol=1e-6)

    def test_validate_requires_matching_center(self):
        with pytest.raises(ValueError, match="center"):
            MarginHead(threshold=1.0).validate(8, 3)
        with pytest.raises(ValueError, match="center"):
            MarginHead(threshold=1.0, center=(0.0, 0.0)).validate(8, 3)
        MarginHead(threshold=1.0, center=(0.0, 0.0, 0.0)).validate(8, 3)

    def test_window_geometry_is_default(self):
        head = MarginHead(threshold=1.0, center=(0.0,))
        assert head.ring_window(8, 2) == 4
        assert head.model_input_size(4, 2) == 8
        win = jnp.ones((3, 8))
        assert head.prepare(win) is win


class TestForecastHead:
    """The next-step-prediction head: the ring holds one reading MORE than
    the model eats; the extra (newest) reading is the prediction target."""

    def test_window_geometry(self):
        head = ForecastHead(threshold=1.0, n_features=2)
        assert head.ring_window(6, 2) == 4       # 3 readings in, 1 target
        assert head.model_input_size(4, 2) == 6
        with pytest.raises(ValueError):
            head.ring_window(6, 3)               # engine/head feature clash
        with pytest.raises(ValueError):
            head.ring_window(7, 2)               # not a whole reading count

    def test_prepare_drops_target_reading(self):
        head = ForecastHead(threshold=1.0, n_features=2)
        win = jnp.arange(16.0).reshape(2, 8)
        np.testing.assert_array_equal(np.asarray(head.prepare(win)),
                                      np.asarray(win[:, :-2]))

    def test_batch_scores_against_last_reading(self):
        head = ForecastHead(threshold=1.0, n_features=2)
        win = jnp.asarray(np.random.default_rng(0)
                          .normal(size=(5, 8)).astype(np.float32))
        pred = jnp.asarray(np.random.default_rng(1)
                           .normal(size=(5, 2)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(head.batch_scores(pred, win)),
            np.mean((np.asarray(pred) - np.asarray(win)[:, -2:]) ** 2,
                    axis=-1), rtol=1e-6)

    def test_validate_output_width(self):
        head = ForecastHead(threshold=1.0, n_features=2)
        head.validate(6, 2)
        with pytest.raises(ValueError):
            head.validate(6, 3)

    def test_engine_derives_ring_window_from_head(self):
        """A 6-input forecaster over 2 features rings 4 readings; the
        served window's newest reading is the target the score is
        measured against (identity probe: outputs == model inputs)."""
        model, params = small_model(6, 2, "REAL", 0)
        eng = StreamEngine(model, params, n_streams=2, n_features=2,
                           head=ForecastHead(threshold=1e9, n_features=2),
                           shard=False, **NO_NORM)
        assert eng.window == 4


class TestScoreHeadTraining:
    """Smoke the margin/forecast training recipes on synthetic windows:
    calibrated head comes back thresholded at the target FPR, servable."""

    def _windows(self, n=240, w=400):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, w)).astype(np.float32)
        y = np.zeros(n, np.int64)
        y[-40:] = 1
        x[-40:] += 3.0                           # separable "attacks"
        return x, y

    def test_train_one_class_smoke(self):
        from repro.sim import train_one_class
        x, y = self._windows()
        model, res = train_one_class(x, y, epochs=2, batch_size=64,
                                     patience=2)
        assert isinstance(res.head, MarginHead)
        assert res.head.threshold == res.threshold > 0
        assert len(res.head.center) == model.graph.nodes[-1].layer.units
        assert 0.0 <= res.calib_fpr <= 0.015     # conservative: never above
        assert res.calib_windows.ndim == 2

    def test_train_forecaster_smoke(self):
        from repro.sim import train_forecaster
        x, y = self._windows()
        model, res = train_forecaster(x, y, epochs=2, batch_size=64,
                                      patience=2)
        assert isinstance(res.head, ForecastHead)
        assert model.input_shape == (398,)
        assert res.head.threshold == res.threshold > 0
        assert 0.0 <= res.calib_fpr <= 0.015

    def test_trained_heads_serve_in_grouped_engine(self):
        """The full seam: train both score heads, serve them as groups
        beside a classifier, verdicts carry the trained thresholds."""
        from repro.sim import train_forecaster, train_one_class
        x, y = self._windows()
        mg_model, mg_res = train_one_class(x, y, epochs=1, batch_size=64)
        fc_model, fc_res = train_forecaster(x, y, epochs=1, batch_size=64)
        groups = [
            ModelGroup("mg", mg_model, mg_res.params, 2, mg_res.head),
            ModelGroup("fc", fc_model, fc_res.params, 2, fc_res.head),
        ]
        ge = GroupedStreamEngine(groups, shard=False)
        assert ge.max_window == 200
        rng = np.random.default_rng(1)
        gv = []
        for c in range(205):
            gv += ge.ingest(rng.normal(size=(4, 2)).astype(np.float32))
        thr = {v.group: v.threshold for v in gv}
        assert thr == {"mg": mg_res.threshold, "fc": fc_res.threshold}
