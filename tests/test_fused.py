"""Fused whole-detector MLP kernel: oracle equivalence, fused-vs-per-layer
parity at the real serving shapes, and the single-dispatch guarantee."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from _jaxpr import count_pallas_calls
from repro.core import layers as L
from repro.core import quantize, sequential
from repro.kernels import fused_mlp as fused_mlp_mod
from repro.kernels import ops
from repro.serving import StreamEngine
from repro.serving.streams import _dense_batched
from repro.sim import build_autoencoder, build_detector, fleet_readings
from repro.sim.detector import batched_forward

SCHEMES = ("REAL", "SINT", "INT", "DINT")


dense_stack = ops.dense_stack


def detector_params(scheme, seed=0):
    model = build_detector()
    params = model.init_params(jax.random.PRNGKey(seed))
    if scheme != "REAL":
        calib = [jax.random.normal(jax.random.PRNGKey(100 + i), (400,))
                 for i in range(4)]
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


def per_layer_forward(x, stack, backend="ref"):
    """The engine's per-layer loop (one dispatch per Dense layer)."""
    for p, act in stack:
        x = _dense_batched(x, p, act, backend)
    return x


class TestFusedVsPerLayer:
    """Issue acceptance: bit-match (REAL) / within-epsilon (SINT/INT/DINT)
    at the detector's real batched-window shapes."""

    @pytest.mark.parametrize("m", (5, 16, 23))
    def test_real_bit_match(self, m):
        model, params = detector_params("REAL")
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(m), (m, 400))
        fused = ops.fused_forward(x, stack, backend="ref")
        per_layer = per_layer_forward(x, stack, backend="ref")
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(per_layer))

    @pytest.mark.parametrize("m", (5, 16, 23))
    @pytest.mark.parametrize("scheme", ("SINT", "INT", "DINT"))
    def test_quantized_within_epsilon(self, m, scheme):
        model, params = detector_params(scheme)
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(m), (m, 400))
        fused = ops.fused_forward(x, stack, backend="ref")
        per_layer = per_layer_forward(x, stack, backend="ref")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(per_layer),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("m", (5, 16, 23))
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_pallas_kernel_matches_per_layer(self, m, scheme):
        """The actual Pallas kernel (interpret mode) against the per-layer
        oracle path, every scheme, fleet-sized M."""
        model, params = detector_params(scheme)
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(7 * m), (m, 400))
        fused = ops.fused_forward(x, stack, backend="pallas")
        per_layer = per_layer_forward(x, stack, backend="ref")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(per_layer),
                                   rtol=1e-5, atol=1e-4)

    def test_dint_saturation_rail_parity(self):
        """Regression: int32's qmax is not f32-representable, so an integer
        round-trip at the DINT clip rail overflows (saturated positives
        flipped to -2^31).  Neither path may cast; they must agree — and
        keep the sign — when the activation grid saturates."""
        p = {"qw": jnp.full((8, 4), 5, jnp.int32),
             "w_scale": jnp.full((4,), 2e-9, jnp.float32),
             "x_scale": jnp.asarray(1e-9, jnp.float32),
             "b": jnp.zeros((4,), jnp.float32)}
        stack = [(p, "linear")]
        x = jnp.full((3, 8), 10.0)          # x / x_scale = 1e10 >> qmax
        per_layer = np.asarray(per_layer_forward(x, stack, backend="ref"))
        fused_ref = np.asarray(ops.fused_forward(x, stack, backend="ref"))
        fused_pl = np.asarray(ops.fused_forward(x, stack, backend="pallas"))
        assert (per_layer > 0).all(), "saturated positives flipped sign"
        np.testing.assert_array_equal(fused_ref, per_layer)
        np.testing.assert_allclose(fused_pl, per_layer, rtol=1e-6)

    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_batched_forward_matches_vmapped_apply(self, scheme):
        """sim.detector.batched_forward (the fused evaluation path) against
        per-sample model.apply — f32 batched-vs-matvec reassociation only."""
        model, params = detector_params(scheme)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 400))
        got = batched_forward(model, params, x)
        want = jax.vmap(model.apply, (None, 0))(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


class TestSingleDispatch:
    """Issue acceptance: one verdict step of the all-Dense detector is a
    single fused Pallas dispatch (vs one per layer on the per-layer path)."""

    def test_fused_forward_is_one_dispatch(self):
        model, params = detector_params("SINT")
        stack = dense_stack(model, params)
        x = jnp.zeros((16, 400))
        fused = jax.make_jaxpr(
            lambda a: ops.fused_forward(a, stack, backend="pallas"))(x)
        assert count_pallas_calls(fused.jaxpr) == 1

    def test_per_layer_sint_is_four_dispatches(self):
        model, params = detector_params("SINT")
        stack = dense_stack(model, params)
        x = jnp.zeros((16, 400))
        per_layer = jax.make_jaxpr(
            lambda a: per_layer_forward(a, stack, backend="pallas"))(x)
        assert count_pallas_calls(per_layer.jaxpr) == len(stack) == 4

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_engine_verdict_step_is_one_dispatch(self, scheme):
        model, params = detector_params(scheme)
        eng = StreamEngine(model, params, n_streams=16, backend="pallas",
                           fused=True)
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((16, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_per_layer_engine_step_dispatch_count(self):
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=16, backend="pallas",
                           fused=False)
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((16, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 4


def autoencoder_params(scheme, seed=0):
    """The 400-64-16-64-400 reconstruction detector, optionally quantized
    with input-range calibration."""
    model = build_autoencoder()
    params = model.init_params(jax.random.PRNGKey(seed))
    if scheme != "REAL":
        calib = [jax.random.normal(jax.random.PRNGKey(300 + i), (400,))
                 for i in range(4)]
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


class TestKGriddedFirstLayer:
    """The K grid streams the first layer's input width through VMEM one
    (block_k, N1) slab at a time: parity across split factors, K widths not
    divisible by the slab, exact-at-budget stacks, and wide-input stacks
    the old whole-net-in-VMEM accounting rejected."""

    @pytest.mark.parametrize("scheme", SCHEMES)
    @pytest.mark.parametrize("block_k", (128, 256))
    @pytest.mark.parametrize("build", (detector_params, autoencoder_params))
    def test_kgrid_matches_oracle(self, scheme, block_k, build):
        model, params = build(scheme)
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(block_k), (9, 400))
        want = ops.fused_forward(x, stack, backend="ref")
        got = ops.fused_forward(x, stack, backend="pallas", block_k=block_k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_kgrid_int8_split_is_bit_exact(self):
        """int8 first layers accumulate split-K partials in an int32
        scratch — integer accumulation is associative, so any split factor
        bit-matches the unsplit kernel."""
        model, params = detector_params("SINT")
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 400))
        unsplit = ops.fused_forward(x, stack, backend="pallas")
        for block_k in (128, 256):
            split = ops.fused_forward(x, stack, backend="pallas",
                                      block_k=block_k)
            np.testing.assert_array_equal(np.asarray(split),
                                          np.asarray(unsplit))

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_k_not_divisible_by_grid_block(self, scheme):
        """block_k=384 over the 512-padded 400-wide input: K pads up to 768
        (zero x-lanes times zero weight rows), and parity holds."""
        model, params = autoencoder_params(scheme)
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(5), (7, 400))
        want = ops.fused_forward(x, stack, backend="ref")
        got = ops.fused_forward(x, stack, backend="pallas", block_k=384)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_kgrid_is_still_one_dispatch(self):
        model, params = detector_params("SINT")
        stack = dense_stack(model, params)
        x = jnp.zeros((16, 400))
        jaxpr = jax.make_jaxpr(
            lambda a: ops.fused_forward(a, stack, backend="pallas",
                                        block_k=128))(x)
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_widest_layer_exactly_at_budget_fits(self, monkeypatch):
        """The budget check is <=: a stack whose resident set is EXACTLY the
        VMEM budget fuses (and dispatches); one byte less and it falls back."""
        model, params = detector_params("SINT")
        stack = dense_stack(model, params)
        shapes, bk = ops._padded_shapes(stack, None)
        exact = fused_mlp_mod.fused_vmem_bytes(shapes, block_m=128,
                                               block_k=bk)
        monkeypatch.setattr(fused_mlp_mod, "VMEM_BUDGET_BYTES", exact)
        assert ops.can_fuse(stack)
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 400))
        got = ops.fused_forward(x, stack, backend="pallas")
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ops.fused_forward(x, stack, backend="ref")),
            rtol=1e-5, atol=1e-4)
        monkeypatch.setattr(fused_mlp_mod, "VMEM_BUDGET_BYTES", exact - 1)
        assert not ops.can_fuse(stack)
        with pytest.raises(ValueError):
            ops.fused_forward(x, stack, backend="pallas")

    def test_wide_input_fuses_only_via_kgrid(self):
        """An 8192-wide first layer (16 MB f32 — over budget in full) fuses
        now: the K grid keeps one 512-row slab resident.  The old
        whole-net accounting would have rejected it."""
        model = sequential([L.Input(),
                            L.Dense(units=512, activation="relu"),
                            L.Dense(units=2, activation="linear")], (8192,))
        params = model.init_params(jax.random.PRNGKey(0))
        stack = dense_stack(model, params)
        w0 = stack[0][0]["w"]
        assert w0.size * w0.dtype.itemsize > fused_mlp_mod.VMEM_BUDGET_BYTES
        assert ops.can_fuse(stack)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 8192)) * 0.1
        got = ops.fused_forward(x, stack, backend="pallas")
        want = ops.fused_forward(x, stack, backend="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_wide_later_layer_still_falls_back(self):
        """The K grid only streams layer 0 — a later layer past the budget
        keeps the stack on the per-layer path (the widest-layer check)."""
        model = sequential([L.Input(),
                            L.Dense(units=2048, activation="relu"),
                            L.Dense(units=2048, activation="linear")], (128,))
        params = model.init_params(jax.random.PRNGKey(0))
        stack = dense_stack(model, params)
        assert not ops.can_fuse(stack)    # layer 1: 2048x2048 f32 = 16 MB


class TestSingleDispatchAutoencoder:
    """Issue acceptance: the 400-64-16-64-400 autoencoder shape runs as ONE
    fused Pallas dispatch — the 400-wide decoder output rides the same
    kernel as the classifier head."""

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_fused_forward_is_one_dispatch(self, scheme):
        model, params = autoencoder_params(scheme)
        stack = dense_stack(model, params)
        x = jnp.zeros((16, 400))
        jaxpr = jax.make_jaxpr(
            lambda a: ops.fused_forward(a, stack, backend="pallas"))(x)
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_autoencoder_pallas_matches_per_layer(self):
        model, params = autoencoder_params("SINT")
        stack = dense_stack(model, params)
        x = jax.random.normal(jax.random.PRNGKey(9), (23, 400))
        fused = ops.fused_forward(x, stack, backend="pallas")
        per_layer = per_layer_forward(x, stack, backend="ref")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(per_layer),
                                   rtol=1e-5, atol=1e-4)


def small_detector(scheme, seed):
    """A detector-shaped all-Dense stack over a 4-reading window (2 features
    -> 8 inputs), cheap enough for property-test volumes."""
    model = sequential([L.Input(),
                        L.Dense(units=6, activation="relu"),
                        L.Dense(units=2, activation="linear")], (8,))
    params = model.init_params(jax.random.PRNGKey(seed))
    if scheme != "REAL":
        calib = [jax.random.normal(jax.random.PRNGKey(200 + i), (8,)) * 2.0
                 for i in range(4)]
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=calib)
    return model, params


def scenario_readings(n_streams, n_cycles, seed):
    return fleet_readings(n_streams, n_cycles, seed=seed)


def drive_pair(model, params, readings, *, window, stride):
    """Run fused and per-layer engines over the same readings; return both
    verdict streams and final logits."""
    results = {}
    for fused in (True, False):
        eng = StreamEngine(model, params, n_streams=readings.shape[1],
                           n_features=2, window=window, stride=stride,
                           fused=fused)
        verdicts = []
        for c in range(readings.shape[0]):
            verdicts.extend(eng.ingest(readings[c]))
        results[fused] = (verdicts, eng.last_logits)
    return results


class TestEngineFusedVsPerLayer:
    @settings(max_examples=6, deadline=None)
    @given(scheme=st.sampled_from(SCHEMES), seed=st.integers(0, 2**20),
           extra=st.integers(8, 40))
    def test_identical_verdicts_over_wraparound_run(self, scheme, seed,
                                                    extra):
        """Fused and per-layer engines emit identical verdicts over a
        scenario run long enough to wrap the ring several times."""
        model, params = small_detector(scheme, seed % 7)
        window, stride = 4, 3
        readings = scenario_readings(3, window + extra, seed)
        results = drive_pair(model, params, readings, window=window,
                             stride=stride)
        vf, lf = results[True]
        vp, lp = results[False]
        # extra >= 8 guarantees count > 2*window, i.e. the ring wrapped.
        assert len(vf) == len(vp) >= 3 * 3
        assert [(v.stream, v.cycle, v.pred) for v in vf] == \
               [(v.stream, v.cycle, v.pred) for v in vp]
        np.testing.assert_allclose([v.prob for v in vf],
                                   [v.prob for v in vp], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(lf, lp, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_full_detector_wraparound_regression(self, scheme):
        """Pinned full-size run: 430 cycles wraps the 200-reading ring and
        the two paths must agree verdict for verdict."""
        model, params = detector_params(scheme, seed=1)
        readings = scenario_readings(3, 430, seed=11)
        results = drive_pair(model, params, readings, window=200, stride=10)
        vf, lf = results[True]
        vp, lp = results[False]
        assert [(v.stream, v.cycle, v.pred) for v in vf] == \
               [(v.stream, v.cycle, v.pred) for v in vp]
        np.testing.assert_allclose(lf, lp, rtol=1e-6, atol=1e-6)


class TestFusedGuards:
    def test_softmax_head_not_fusable(self):
        model = sequential([L.Input(),
                            L.Dense(units=4, activation="relu"),
                            L.Dense(units=2, activation="softmax")], (8,))
        params = model.init_params(jax.random.PRNGKey(0))
        stack = dense_stack(model, params)
        assert not ops.can_fuse(stack)
        with pytest.raises(ValueError):
            ops.fused_forward(jnp.zeros((4, 8)), stack)
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2, n_features=2, window=4,
                         fused=True)
        # auto mode falls back to the per-layer loop and still serves
        eng = StreamEngine(model, params, n_streams=2, n_features=2, window=4)
        assert not eng.fused
        for c in range(4):
            eng.ingest(np.zeros((2, 2), np.float32))
        assert eng.last_logits is not None

    def test_fused_flag_default_on_detector(self):
        model, params = detector_params("REAL")
        assert StreamEngine(model, params, n_streams=2).fused
        assert not StreamEngine(model, params, n_streams=2,
                                fused=False).fused

    def test_oversized_stack_falls_back_to_per_layer(self):
        """A fusable-shaped stack past the VMEM budget must not auto-fuse
        (the kernel can't keep it resident) — the engine serves it through
        the per-layer loop instead of failing at dispatch time."""
        model = sequential([L.Input(),
                            L.Dense(units=2048, activation="relu"),
                            L.Dense(units=2048, activation="linear")], (2048,))
        params = model.init_params(jax.random.PRNGKey(0))
        stack = dense_stack(model, params)
        assert not ops.can_fuse(stack)        # 2 x 16 MB f32 > 12 MB budget
        eng = StreamEngine(model, params, n_streams=2, n_features=2,
                           window=1024)
        assert not eng.fused
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2, n_features=2,
                         window=1024, fused=True)

    def test_non_dense_model_not_fused(self):
        model = sequential([L.Input(),
                            L.Dense(units=4, activation="relu"),
                            L.Activation(fn="tanh"),
                            L.Dense(units=4, activation="linear")], (4,))
        params = model.init_params(jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2, n_features=2, window=2,
                         fused=True)
