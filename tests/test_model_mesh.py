"""2-D ``("data", "model")`` fleet mesh: column-sharded wide layers.

``make_fleet_mesh(n, model_shards=m)`` builds an ``(n, m)`` mesh; the
serving core column-shards every Dense layer whose output width reaches
``MODEL_SHARD_MIN_WIDTH`` over the model axis — each rank computes a
full-K dot for its own slice of output columns and one tiled
``all_gather`` recombines them, so each output column is the same full-K
dot product as the unsharded engine's (columns of a matmul are
independent).  The ``(1, 2)`` mesh and every SINT program are bit-exact;
on ``(2, 2)`` the CPU program rounds some REAL logits an ulp apart, so
those tests hold REAL PRED exact and tails to the 1e-5 sharded contract.
Pad-stream data sharding composes unchanged; the fused single-dispatch
kernel cannot span the gather, so the model axis forces the per-layer
step.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _jaxpr import count_primitive
from repro.launch.mesh import make_fleet_mesh, make_host_mesh
from repro.serving import GroupedStreamEngine, ModelGroup, StreamEngine
from repro.serving.core import MODEL_SHARD_MIN_WIDTH
from repro.sim import ReconstructionHead, fleet_readings
from test_drift import energy_detector
from test_fused import detector_params, small_detector
from test_streams import drive, identity_probe

N_DEVICES = len(jax.devices())

needs2 = pytest.mark.skipif(N_DEVICES < 2, reason="needs >= 2 devices")
needs4 = pytest.mark.skipif(N_DEVICES < 4, reason="needs >= 4 devices")


def verdict_key(v):
    return (v.stream, v.cycle, v.pred, v.prob, v.score, v.threshold, v.group)


def serve_all(eng, readings):
    out = []
    for c in range(readings.shape[0]):
        out.extend(eng.ingest(readings[c]))
    return out


class TestMeshConstruction:
    def test_default_stays_1d(self):
        mesh = make_fleet_mesh(1)
        assert mesh.axis_names == ("data",)
        assert mesh.devices.shape == (1,)

    @needs2
    def test_2d_shape_and_axes(self):
        mesh = make_fleet_mesh(1, model_shards=2)
        assert mesh.axis_names == ("data", "model")
        assert mesh.devices.shape == (1, 2)

    @needs4
    def test_2d_data_default_divides(self):
        mesh = make_fleet_mesh(model_shards=2)
        assert mesh.devices.shape == (N_DEVICES // 2, 2)

    def test_model_shards_validation(self):
        with pytest.raises(RuntimeError, match="model_shards"):
            make_fleet_mesh(1, model_shards=0)

    def test_too_many_devices(self):
        with pytest.raises(RuntimeError, match="needs"):
            make_fleet_mesh(N_DEVICES, model_shards=2)


@needs2
class TestModelShardedParity:
    """Sharded-vs-unsharded on the REAL serving shapes (the 400-64-32-16-2
    detector's 64-wide first layer crosses MODEL_SHARD_MIN_WIDTH).
    Full-K-per-column math makes these assertions bit-exact, not epsilon."""

    @pytest.mark.parametrize("scheme", ("REAL", "SINT", "INT", "DINT"))
    def test_detector_parity_model2(self, scheme):
        model, params = detector_params(scheme)
        readings = fleet_readings(3, 230, seed=11)     # ring wraps (W=200)
        logits = {}
        for key, kw in (("base", {"shard": False}),
                        ("shard", {"mesh": make_fleet_mesh(1,
                                                           model_shards=2)})):
            eng = StreamEngine(model, params, n_streams=3, **kw)
            vs = serve_all(eng, readings)
            logits[key] = (eng.last_logits, [verdict_key(v) for v in vs])
        np.testing.assert_array_equal(logits["shard"][0], logits["base"][0])
        assert logits["shard"][1] == logits["base"][1]

    @needs4
    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    @pytest.mark.parametrize("n_streams", (4, 5))      # divisible and padded
    def test_detector_parity_data2_model2(self, scheme, n_streams):
        """SINT bit-exact.  REAL: exact PRED, logits at the 1e-5 sharded
        contract — the (2, 2) program rounds some REAL logits an ulp
        (7.45e-9) apart from the unsharded one on 4 host devices."""
        model, params = detector_params(scheme)
        readings = fleet_readings(n_streams, 230, seed=13)
        logits, preds = {}, {}
        for key, kw in (("base", {"shard": False}),
                        ("shard", {"mesh": make_fleet_mesh(2,
                                                           model_shards=2)})):
            eng = StreamEngine(model, params, n_streams=n_streams, **kw)
            preds[key] = [(v.stream, v.cycle, v.pred)
                          for v in serve_all(eng, readings)]
            logits[key] = eng.last_logits
        assert preds["shard"] == preds["base"]
        if scheme == "SINT":
            np.testing.assert_array_equal(logits["shard"], logits["base"])
        else:
            np.testing.assert_allclose(logits["shard"], logits["base"],
                                       rtol=1e-5, atol=1e-6)

    def test_identity_window_oracle(self):
        """Ground truth, not just parity: a 64-wide identity layer sharded
        over the model axis must still return the exact window contents."""
        window, n_feat, n = 32, 2, 3                   # 64 = min shard width
        assert window * n_feat >= MODEL_SHARD_MIN_WIDTH
        model, params = identity_probe(window, n_feat)
        eng = StreamEngine(model, params, n_streams=n, n_features=n_feat,
                           window=window, stride=5,
                           norm_mean=(0.0, 0.0), norm_std=(1.0, 1.0),
                           mesh=make_fleet_mesh(1, model_shards=2))
        rng = np.random.default_rng(3)
        readings = rng.normal(size=(70, n, n_feat)).astype(np.float32)
        batches = drive(eng, readings)
        assert batches
        for cycle, logits in batches:
            want = readings[cycle - window + 1:cycle + 1]
            want = want.transpose(1, 0, 2).reshape(n, -1)
            np.testing.assert_array_equal(logits, want)

    def test_adaptive_parity(self):
        """Threshold adaptation state is row-local, so it composes with the
        model axis: live-threshold trajectory matches unsharded exactly."""
        model, params = energy_detector(32, 2)         # single 64-wide Dense
        readings = np.random.default_rng(7).normal(
            size=(80, 3, 2)).astype(np.float32)
        results = {}
        for key, kw in (("base", {"shard": False}),
                        ("shard", {"mesh": make_fleet_mesh(1,
                                                           model_shards=2)})):
            eng = StreamEngine(model, params, n_streams=3, n_features=2,
                               window=32, stride=4, norm_mean=(0.0, 0.0),
                               norm_std=(1.0, 1.0),
                               head=ReconstructionHead(threshold=0.8,
                                                       target_fpr=0.1),
                               adapt=True, **kw)
            vs = serve_all(eng, readings)
            results[key] = ([verdict_key(v) for v in vs], eng.live_threshold)
        assert results["shard"] == results["base"]

    def test_grouped_model_mesh_parity(self):
        det_model, det_params = small_detector("SINT", seed=1)
        ae_model, ae_params = energy_detector(32, 2)
        readings = fleet_readings(5, 70, seed=21)

        def make(**kw):
            return GroupedStreamEngine(
                [ModelGroup("det", det_model, det_params, 3),
                 ModelGroup("ae", ae_model, ae_params, 2,
                            head=ReconstructionHead(threshold=2.0))],
                n_features=2, stride=5, **kw)

        base = make(shard=False)
        shard = make(mesh=make_fleet_mesh(1, model_shards=2))
        bk = [verdict_key(v) for v in serve_all(base, readings)]
        sk = [verdict_key(v) for v in serve_all(shard, readings)]
        assert bk == sk
        for name in ("det", "ae"):
            np.testing.assert_array_equal(shard.last_outputs[name],
                                          base.last_outputs[name])


@needs2
class TestFusedInteraction:
    def test_fused_true_rejected_on_model_mesh(self):
        model, params = detector_params("SINT")
        with pytest.raises(ValueError,
                           match="cannot serve on a model-sharded mesh"):
            StreamEngine(model, params, n_streams=4, fused=True,
                         backend="pallas",
                         mesh=make_fleet_mesh(1, model_shards=2))

    def test_fused_auto_resolves_false_on_model_mesh(self):
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=4, backend="pallas",
                           mesh=make_fleet_mesh(1, model_shards=2))
        assert eng.fused is False

    def test_host_mesh_model_axis_of_one_keeps_fusion(self):
        """A size-1 model axis is NOT model sharding — auto-fuse stays on."""
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=4, backend="pallas",
                           mesh=make_host_mesh())
        assert eng.fused is True

    def test_one_all_gather_per_step(self):
        """Minimal-collective recombination: only the 64-wide layer crosses
        MODEL_SHARD_MIN_WIDTH, so the whole detector step carries exactly
        ONE all_gather."""
        model, params = detector_params("REAL")
        eng = StreamEngine(model, params, n_streams=4,
                           mesh=make_fleet_mesh(1, model_shards=2))
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_primitive(jaxpr.jaxpr, "all_gather") == 1

    def test_narrow_model_skips_collectives(self):
        """Every layer under MODEL_SHARD_MIN_WIDTH: the model axis is inert
        and the step stays collective-free."""
        model, params = small_detector("REAL", seed=0)   # widths 6 / 2
        eng = StreamEngine(model, params, n_streams=4, n_features=2,
                           window=4, stride=3,
                           mesh=make_fleet_mesh(1, model_shards=2))
        ring = jnp.zeros_like(eng._ring)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_primitive(jaxpr.jaxpr, "all_gather") == 0


_SUBPROCESS_PARITY_2D = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax
assert len(jax.devices()) == 4, jax.devices()
from _jaxpr import count_primitive
from repro.launch.mesh import make_fleet_mesh
from repro.serving import StreamEngine
from repro.sim import fleet_readings
from test_fused import detector_params

for scheme in ("REAL", "SINT"):
    model, params = detector_params(scheme)
    readings = fleet_readings(5, 230, seed=17)         # 5 plants, (2, 2) mesh
    logits, verdicts = {}, {}
    for key, kw in (("base", {"shard": False}),
                    ("shard", {"mesh": make_fleet_mesh(2, model_shards=2)})):
        eng = StreamEngine(model, params, n_streams=5, **kw)
        verdicts[key] = []
        for c in range(readings.shape[0]):
            verdicts[key].extend(eng.ingest(readings[c]))
        logits[key] = eng.last_logits
    base, shard = verdicts["base"], verdicts["shard"]
    assert [(v.stream, v.cycle, v.pred) for v in shard] == \
        [(v.stream, v.cycle, v.pred) for v in base]
    if scheme == "SINT":
        assert [v.prob for v in shard] == [v.prob for v in base]
        np.testing.assert_array_equal(logits["shard"], logits["base"])
    else:
        np.testing.assert_allclose([v.prob for v in shard],
                                   [v.prob for v in base], rtol=1e-5)
        np.testing.assert_allclose(logits["shard"], logits["base"],
                                   rtol=1e-5, atol=1e-6)
print("MODEL_MESH_PARITY_OK")
"""


@pytest.mark.skipif(N_DEVICES >= 4,
                    reason="in-process tests already cover the (2, 2) mesh")
def test_2x2_parity_subprocess():
    """Single-device environments still certify the (data=2, model=2) mesh:
    a child process fans out 4 host devices and re-checks parity on a
    non-divisible fleet — SINT bit-exact; REAL with every PRED identical
    and f32 tails at the 1e-5 sharded contract (the 4-device CPU program
    rounds some REAL logits an ulp apart from the 1-device one)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.dirname(__file__)] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PARITY_2D],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MODEL_MESH_PARITY_OK" in out.stdout
