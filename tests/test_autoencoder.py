"""Autoencoder detector workload: head semantics, threshold calibration,
StreamEngine parity (fused vs per-layer, sharded vs unsharded) over
ring-wraparound scenario runs, quantization-calibration parity, and the
on-device score-reduction guarantee."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import layers as L
from repro.core import quantize, sequential
from repro.kernels import ops
from repro.launch.mesh import make_fleet_mesh
from repro.serving import StreamEngine
from repro.sim import (ClassifierHead, ReconstructionHead, build_autoencoder,
                       fleet_readings, softmax_np, train_autoencoder)
from repro.sim.detector import batched_forward

from _jaxpr import count_pallas_calls
from test_fused import autoencoder_params

SCHEMES = ("REAL", "SINT", "INT", "DINT")
N_DEVICES = len(jax.devices())


def small_autoencoder(scheme, seed):
    """An autoencoder-shaped all-Dense stack over a 4-reading window
    (2 features -> 8 inputs -> 8 outputs), cheap for property-test volumes."""
    model = sequential([L.Input(),
                        L.Dense(units=6, activation="relu"),
                        L.Dense(units=8, activation="linear")], (8,))
    params = model.init_params(jax.random.PRNGKey(seed))
    if scheme != "REAL":
        calib = [jax.random.normal(jax.random.PRNGKey(400 + i), (8,)) * 2.0
                 for i in range(4)]
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


class TestSoftmaxNp:
    """Satellite regression: the host softmax must be batched-stable —
    per-row max subtracted along axis -1 — so extreme logits never overflow
    and rows never contaminate each other."""

    def test_extreme_logits_stable(self):
        logits = np.array([[1e4, -1e4],
                           [-1e4, 1e4],
                           [88.0, 89.0],
                           [0.0, 0.0]], np.float32)
        p = softmax_np(logits)
        assert np.isfinite(p).all()
        np.testing.assert_allclose(p.sum(axis=-1), 1.0, rtol=1e-6)
        assert p[0, 0] > 0.999 and p[1, 1] > 0.999
        np.testing.assert_allclose(p[3], [0.5, 0.5])

    def test_rows_are_independent(self):
        """Each row's softmax equals that row computed alone — the per-row
        (not global) max is what gets subtracted."""
        rng = np.random.default_rng(0)
        logits = rng.normal(scale=200.0, size=(6, 4)).astype(np.float32)
        batched = softmax_np(logits)
        for i in range(len(logits)):
            np.testing.assert_allclose(batched[i],
                                       softmax_np(logits[i][None])[0],
                                       rtol=1e-6, atol=0)

    def test_classifier_head_uses_stable_softmax(self):
        head = ClassifierHead()
        out = np.array([[1e4, 0.0], [0.0, 1e4]], np.float32)
        pred, prob, score, thr = head.host_verdicts(out)
        assert list(pred) == [0, 1]
        assert np.isfinite(prob).all() and (prob > 0.999).all()
        assert score is None and thr is None


class TestReconstructionHead:
    def test_epilogue_reduces_to_one_score_per_stream(self):
        head = ReconstructionHead(threshold=1.0)
        win = jnp.asarray(np.random.default_rng(0).normal(size=(5, 8))
                          .astype(np.float32))
        out = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8))
                          .astype(np.float32))
        red = head.epilogue(win, out)
        assert red.shape == (5, 1)
        np.testing.assert_allclose(
            np.asarray(red)[:, 0],
            np.mean((np.asarray(out) - np.asarray(win)) ** 2, axis=-1),
            rtol=1e-6)

    def test_calibrate_hits_target_fpr(self):
        scores = np.linspace(0.0, 1.0, 1000)
        head = ReconstructionHead().calibrate(scores, target_fpr=0.05)
        realized = np.mean(scores > head.threshold)
        assert abs(realized - 0.05) < 0.01
        # monotone: tighter FPR -> higher threshold
        tighter = ReconstructionHead().calibrate(scores, target_fpr=0.01)
        assert tighter.threshold > head.threshold

    def test_small_sample_calibration_fpr_never_exceeds_target(self):
        """Satellite regression: quantile interpolation used to let the
        calibration-set FPR land ABOVE target_fpr on small score sets (an
        interpolated threshold sits below the next order statistic, so the
        strict > comparison flags more than target_fpr of the very windows
        it was calibrated on).  The conservative (method='higher') quantile
        guarantees realized FPR <= target on the calibration set itself —
        for every small-set size and target."""
        rng = np.random.default_rng(0)
        for n in (5, 7, 13, 50, 99):
            for target in (0.01, 0.05, 0.1, 0.25):
                scores = rng.normal(size=n) ** 2
                head = ReconstructionHead().calibrate(scores,
                                                      target_fpr=target)
                realized = np.mean(scores > head.threshold)
                assert realized <= target, (n, target, realized)
                # the threshold is an actual observed score, never an
                # interpolated value between two of them
                assert head.threshold in scores

    def test_conservative_quantile_shared_by_all_score_heads(self):
        """Margin and forecast heads calibrate through the same
        conservative quantile (the fix is in the ScoreHead base, not
        patched per head)."""
        from repro.sim import ForecastHead, MarginHead, conservative_quantile
        scores = np.array([0.1, 0.2, 0.3, 0.4, 0.5])
        want = conservative_quantile(scores, 0.25)
        assert want == 0.4
        for head in (MarginHead(center=(0.0,)), ForecastHead(),
                     ReconstructionHead()):
            assert head.calibrate(scores, 0.25).threshold == want

    def test_calibrate_validation(self):
        with pytest.raises(ValueError):
            ReconstructionHead().calibrate(np.ones(4), target_fpr=0.0)
        with pytest.raises(ValueError):
            ReconstructionHead().calibrate(np.ones(4), target_fpr=1.5)
        with pytest.raises(ValueError):
            ReconstructionHead().calibrate(np.zeros(0), target_fpr=0.1)

    def test_host_verdicts_threshold_semantics(self):
        head = ReconstructionHead(threshold=0.5)
        pred, prob, score, thr = head.host_verdicts(
            np.array([[0.4], [0.6], [0.5]], np.float32))
        assert list(pred) == [0, 1, 0]          # strict >
        assert prob is None and thr == 0.5
        np.testing.assert_allclose(score, [0.4, 0.6, 0.5])

    def test_uncalibrated_head_rejected(self):
        with pytest.raises(ValueError):
            ReconstructionHead().host_verdicts(np.zeros((2, 1), np.float32))
        model, params = small_autoencoder("REAL", 0)
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2, n_features=2, window=4,
                         head=ReconstructionHead())

    def test_head_model_width_mismatch_rejected(self):
        from repro.sim import build_detector
        model = build_detector()
        params = model.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2,
                         head=ReconstructionHead(threshold=1.0))


def drive_ae(eng, readings):
    out = []
    for c in range(readings.shape[0]):
        vs = eng.ingest(readings[c])
        if vs:
            out.append((c, vs, eng.last_logits.copy()))
    return out


def engine_ae(model, params, n_streams, *, window, stride, threshold=0.01,
              **kw):
    return StreamEngine(model, params, n_streams=n_streams, n_features=2,
                        window=window, stride=stride,
                        head=ReconstructionHead(threshold=threshold), **kw)


class TestEngineServesAutoencoder:
    def test_scores_match_offline_reconstruction_error(self):
        """The engine's served scores equal the reconstruction error of the
        naively-sliced window through batched_forward — the whole ring/
        scatter/epilogue pipeline against offline math."""
        model, params = autoencoder_params("REAL")
        eng = StreamEngine(model, params, n_streams=3,
                           head=ReconstructionHead(threshold=0.01))
        readings = fleet_readings(3, 200, seed=4)
        for c in range(200):
            vs = eng.ingest(readings[c])
        assert eng.last_logits.shape == (3, 1)
        norm = (readings - np.asarray(eng._mean)) / np.asarray(eng._std)
        win = jnp.asarray(norm.transpose(1, 0, 2).reshape(3, -1))
        recon = batched_forward(model, params, win)
        want = np.mean((np.asarray(recon) - np.asarray(win)) ** 2, axis=-1)
        np.testing.assert_allclose(eng.last_logits[:, 0], want,
                                   rtol=1e-5, atol=1e-6)
        for v in vs:
            assert v.prob is None
            assert v.threshold == 0.01
            assert v.pred == int(v.score > 0.01)

    def test_step_output_is_reduced_on_device(self):
        """The jitted step's verdict output aval is (S, 1) — the (S, 400)
        reconstruction never crosses the device boundary."""
        model, params = autoencoder_params("REAL")
        eng = StreamEngine(model, params, n_streams=16,
                           head=ReconstructionHead(threshold=0.01))
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert jaxpr.out_avals[1].shape == (eng._s_pad, 1)

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_fused_vs_per_layer_wraparound_regression(self, scheme):
        """Pinned full-size run: 430 cycles wraps the 200-reading ring and
        the fused and per-layer autoencoder engines must agree verdict for
        verdict (REAL bit-match, quantized epsilon)."""
        model, params = autoencoder_params(scheme, seed=1)
        readings = fleet_readings(3, 430, seed=11)
        results = {}
        for fused in (True, False):
            eng = engine_ae(model, params, 3, window=200, stride=10,
                            fused=fused)
            results[fused] = drive_ae(eng, readings)
        got, want = results[True], results[False]
        assert len(got) == len(want) == 24
        assert [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in got] == \
               [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in want]
        for (_, gvs, gl), (_, wvs, wl) in zip(got, want):
            if scheme == "REAL":
                np.testing.assert_array_equal(gl, wl)
            else:
                np.testing.assert_allclose(gl, wl, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose([v.score for v in gvs],
                                       [v.score for v in wvs],
                                       rtol=1e-5, atol=1e-6)

    @settings(max_examples=6, deadline=None)
    @given(scheme=st.sampled_from(SCHEMES), seed=st.integers(0, 2**20),
           extra=st.integers(8, 40))
    def test_small_ae_fused_vs_per_layer_property(self, scheme, seed, extra):
        model, params = small_autoencoder(scheme, seed % 7)
        window, stride = 4, 3
        readings = fleet_readings(3, window + extra, seed=seed)
        results = {}
        for fused in (True, False):
            eng = engine_ae(model, params, 3, window=window, stride=stride,
                            fused=fused, threshold=0.5)
            results[fused] = drive_ae(eng, readings)
        got, want = results[True], results[False]
        assert len(got) == len(want) >= 3
        assert [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in got] == \
               [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in want]
        for (_, _, gl), (_, _, wl) in zip(got, want):
            np.testing.assert_allclose(gl, wl, rtol=1e-6, atol=1e-7)

    def test_warmup_and_stats(self):
        model, params = autoencoder_params("SINT")
        eng = StreamEngine(model, params, n_streams=4,
                           head=ReconstructionHead(threshold=0.01))
        eng.warmup()
        readings = fleet_readings(4, 230, seed=2)
        n = 0
        for c in range(230):
            n += len(eng.ingest(readings[c]))
        assert n == eng.stats.windows == 16
        assert eng.stats.steps == 4


@pytest.mark.skipif(N_DEVICES < 2, reason="needs >=2 host devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
class TestShardedAutoencoderParity:
    """Issue acceptance: sharded-vs-unsharded parity for the autoencoder
    fleet over ring-wraparound scenario runs — the head's score reduction
    runs per shard, inside shard_map."""

    @pytest.mark.parametrize("n_streams", (4, 5))   # divisible + pad
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_small_ae_sharded_parity(self, scheme, n_streams):
        n_devices = min(2, N_DEVICES)
        model, params = small_autoencoder(scheme, seed=n_streams)
        readings = fleet_readings(n_streams, 30, seed=13 + n_streams)
        base = engine_ae(model, params, n_streams, window=4, stride=3,
                         threshold=0.5, shard=False)
        shard = engine_ae(model, params, n_streams, window=4, stride=3,
                          threshold=0.5, mesh=make_fleet_mesh(n_devices))
        want = drive_ae(base, readings)
        got = drive_ae(shard, readings)
        assert len(got) == len(want) >= 9           # the ring wrapped
        assert [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in got] == \
               [(c, [(v.stream, v.cycle, v.pred) for v in vs])
                for c, vs, _ in want]
        exact = scheme == "REAL" and shard.shard_streams > 1
        for (_, gvs, gl), (_, wvs, wl) in zip(got, want):
            if exact:
                np.testing.assert_array_equal(gl, wl)
            else:
                np.testing.assert_allclose(gl, wl, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_full_ae_sharded_wraparound_regression(self, scheme):
        """Full-size 400-64-16-64-400 fleet, non-divisible 6-plant fleet on
        the widest mesh, 430 cycles (ring wraps twice)."""
        n_devices = max(n for n in (1, 2, 4) if n <= N_DEVICES)
        model, params = autoencoder_params(scheme, seed=1)
        readings = fleet_readings(6, 430, seed=11)
        base = engine_ae(model, params, 6, window=200, stride=10,
                         shard=False)
        shard = engine_ae(model, params, 6, window=200, stride=10,
                          mesh=make_fleet_mesh(n_devices))
        want = drive_ae(base, readings)
        got = drive_ae(shard, readings)
        assert len(got) == len(want) == 24
        for (_, gvs, gl), (_, wvs, wl) in zip(got, want):
            assert [(v.stream, v.pred) for v in gvs] == \
                   [(v.stream, v.pred) for v in wvs]
            if scheme == "REAL":
                np.testing.assert_array_equal(gl, wl)
            else:
                np.testing.assert_allclose(gl, wl, rtol=1e-5, atol=1e-6)

    def test_sharded_ae_step_is_one_dispatch_per_shard(self):
        model, params = autoencoder_params("SINT")
        eng = StreamEngine(model, params, n_streams=6, backend="pallas",
                           fused=True, head=ReconstructionHead(threshold=0.01),
                           mesh=make_fleet_mesh(min(2, N_DEVICES)))
        ring = jnp.zeros((eng._s_pad, eng.window, 2), jnp.float32)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 1
        assert jaxpr.out_avals[1].shape == (eng._s_pad, 1)


class TestSingleDispatchAEEngine:
    """The engine's autoencoder verdict step — forward AND score epilogue —
    is one fused Pallas dispatch (vs one per layer on the per-layer path)."""

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_ae_verdict_step_is_one_dispatch(self, scheme):
        model, params = autoencoder_params(scheme)
        eng = StreamEngine(model, params, n_streams=16, backend="pallas",
                           fused=True,
                           head=ReconstructionHead(threshold=0.01))
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((16, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_ae_per_layer_step_is_four_dispatches(self):
        model, params = autoencoder_params("SINT")
        eng = StreamEngine(model, params, n_streams=16, backend="pallas",
                           fused=False,
                           head=ReconstructionHead(threshold=0.01))
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((16, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 4


class TestQuantizationCalibration:
    """Satellite: autoencoder output-layer scales must come from benign-
    trace activation ranges — weight absmax alone (the uncalibrated 1/qmax
    default) leaves SINT reconstruction error far from REAL."""

    def _benign_windows(self, n=64):
        readings = fleet_readings(3, 200 + n, seed=0, names=["baseline"])
        norm = ((readings - np.array([89.6, 19.18], np.float32))
                / np.array([2.0, 0.5], np.float32))
        wins = [norm[c:c + 200, s].reshape(-1)
                for s in range(3) for c in range(0, n, 8)]
        return np.stack(wins).astype(np.float32)

    def test_calibrated_sint_scores_within_epsilon_of_real(self):
        model = build_autoencoder()
        params = model.init_params(jax.random.PRNGKey(3))
        # Trained autoencoders carry hidden activations well outside the
        # default's [-1, 1] assumption; scale the init weights to put this
        # stack in that regime without a training run.
        params = {uid: {k: (v * 3.0 if k == "w" else v)
                        for k, v in p.items()}
                  for uid, p in params.items()}
        x = jnp.asarray(self._benign_windows())
        calib = quantize.calibration_samples(np.asarray(x), k=16)
        qp_cal = quantize.quantize_params(model, params, "SINT",
                                          calibration=calib)
        qp_def = quantize.quantize_params(model, params, "SINT")
        head = ReconstructionHead()
        real = np.asarray(head.scores(batched_forward(model, params, x), x))
        cal = np.asarray(head.scores(batched_forward(model, qp_cal, x), x))
        deflt = np.asarray(head.scores(batched_forward(model, qp_def, x), x))
        # Pinned epsilon: calibrated SINT tracks REAL scores closely...
        np.testing.assert_allclose(cal, real, rtol=0.35, atol=5e-3)
        # ...and beats the uncalibrated default by a wide margin.
        err_cal = np.abs(cal - real).mean()
        err_def = np.abs(deflt - real).mean()
        assert err_cal * 10 < err_def, (err_cal, err_def)

    def test_calibration_samples_benign_only(self):
        x = np.arange(20, dtype=np.float32).reshape(10, 2)
        y = np.array([0, 1, 0, 1, 0, 1, 0, 1, 0, 1])
        samples = quantize.calibration_samples(x, y, k=3)
        assert len(samples) == 3
        for s in samples:
            assert float(s[0]) % 4 == 0          # benign rows are even rows
        with pytest.raises(ValueError):
            quantize.calibration_samples(x, np.ones(10), k=3)


class TestTrainAutoencoder:
    def test_train_calibrate_smoke(self):
        """Head-generic training on synthetic benign windows: the result
        carries a calibrated head whose realized FPR is near target."""
        rng = np.random.default_rng(0)
        x = rng.normal(size=(900, 400)).astype(np.float32)
        model, res = train_autoencoder(x, None, epochs=2, batch_size=128,
                                       patience=2, target_fpr=0.05)
        assert res.threshold > 0
        assert res.head.threshold == res.threshold
        assert 0.0 <= res.calib_fpr <= 0.15
        assert len(res.history) >= 1
        assert res.best_val_mse > 0

    def test_labels_drop_attack_windows(self):
        """With labels, attack windows never reach training; detection rate
        is reported against them."""
        rng = np.random.default_rng(1)
        normal = rng.normal(size=(800, 400)).astype(np.float32)
        attacks = rng.normal(loc=25.0, size=(50, 400)).astype(np.float32)
        x = np.concatenate([normal, attacks])
        y = np.concatenate([np.zeros(800, np.int64), np.ones(50, np.int64)])
        model, res = train_autoencoder(x, y, epochs=2, batch_size=128,
                                       patience=2)
        # off-manifold attacks reconstruct badly -> all flagged
        assert res.test_detection_rate == 1.0

    def test_too_few_benign_windows_rejected(self):
        with pytest.raises(ValueError):
            train_autoencoder(np.zeros((10, 400), np.float32), None,
                              batch_size=256)


@pytest.mark.slow
class TestEndToEndAutoencoder:
    def test_fleet_detection_regression(self):
        """Seeded small-budget train -> calibrate -> port -> quantize ->
        serve: the unsupervised path must flag attacked plants after onset
        and respect the FPR budget on the benign one."""
        import tempfile
        from repro.core import porting
        from repro.sim import build_dataset, build_fleet, get_scenario

        x, y = build_dataset(normal_cycles=8000, attack_cycles=2500,
                             stride=8, seed=0)
        model, res = train_autoencoder(x, y, epochs=30, patience=30, lr=1e-3)
        assert res.test_detection_rate > 0.5
        with tempfile.TemporaryDirectory() as tmp:
            model, params = porting.port_mlp(model, res.params, tmp)
        params = quantize.quantize_params(
            model, params, "SINT",
            calibration=quantize.calibration_samples(x, y))
        # threshold re-calibrated on the quantized model's scores over the
        # SAME held-out normal windows the REAL threshold came from
        from repro.sim import recalibrate_threshold
        head, _ = recalibrate_threshold(model, params, res.calib_windows,
                                        target_fpr=0.01)

        names = ["baseline", "recycle-starve", "tb0-spoof", "steam-throttle"]
        fleet = build_fleet(names, seed=4242, jitter=0.0)
        eng = StreamEngine(model, params, n_streams=len(fleet), head=head)
        eng.warmup()
        verdicts = eng.run(fleet, 1400)

        by_stream = {}
        for v in verdicts:
            by_stream.setdefault(v.stream, []).append(v)
        for i, name in enumerate(names):
            onset = get_scenario(name).onset
            vs = by_stream[i]
            if onset is None:
                fp = sum(v.pred != 0 for v in vs) / len(vs)
                assert fp < 0.25, f"{name}: false-positive rate {fp:.2f}"
            else:
                post = [v for v in vs if v.cycle >= onset]
                assert any(v.pred != 0 for v in post), \
                    f"{name}: attack never flagged"
