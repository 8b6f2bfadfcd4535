"""Sharded-parity suite for the stream-axis fleet sharding of StreamEngine.

The sharded engine (ring arena + detector step partitioned over a
``("data",)`` fleet mesh, one shard_map'd step per device) must serve
*identically* to the classic unsharded engine: verdicts bit-match under REAL
and epsilon-match under SINT/INT/DINT, over scenario runs long enough to wrap
the ring, at 1/2/4 host devices, and for fleet sizes not divisible by the
device count (the pad-stream contract).

Device counts above the process's visible device count skip; the CI
``tier1-multidevice`` job runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so every count runs.
A subprocess test keeps 4-device coverage alive even in single-device runs.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.launch.mesh import make_fleet_mesh
from repro.serving import StreamEngine
from repro.sim import fleet_readings

from _jaxpr import count_pallas_calls
from test_fused import detector_params, small_detector
from test_streams import identity_probe

SCHEMES = ("REAL", "SINT", "INT", "DINT")
N_DEVICES = len(jax.devices())
DEVICE_COUNTS = [n for n in (1, 2, 4) if n <= N_DEVICES]


def needs(n_devices):
    return pytest.mark.skipif(
        N_DEVICES < n_devices,
        reason=f"needs {n_devices} host devices "
               "(XLA_FLAGS=--xla_force_host_platform_device_count)")


# (devices, streams) grid: every multi-device count paired with a divisible
# fleet and one that is NOT divisible (pad-stream contract).
DEVICE_FLEETS = [
    pytest.param(1, 3, id="d1-s3"),
    pytest.param(2, 4, id="d2-s4", marks=needs(2)),
    pytest.param(2, 5, id="d2-s5-pad", marks=needs(2)),
    pytest.param(4, 8, id="d4-s8", marks=needs(4)),
    pytest.param(4, 6, id="d4-s6-pad", marks=needs(4)),
    pytest.param(4, 3, id="d4-s3-pad", marks=needs(4)),
]


def drive_batches(eng, readings):
    """[(cycle, verdicts, logits)] per verdict batch over a (C, S, F) run."""
    out = []
    for c in range(readings.shape[0]):
        vs = eng.ingest(readings[c])
        if vs:
            out.append((c, vs, eng.last_logits.copy()))
    return out


def engine_pair(model, params, n_streams, *, n_devices, window, stride,
                **kw):
    """(unsharded, sharded-over-n_devices) engines with identical knobs."""
    base = StreamEngine(model, params, n_streams=n_streams, n_features=2,
                        window=window, stride=stride, shard=False, **kw)
    shard = StreamEngine(model, params, n_streams=n_streams, n_features=2,
                         window=window, stride=stride,
                         mesh=make_fleet_mesh(n_devices), **kw)
    return base, shard


def assert_batches_match(got, want, *, exact):
    assert [(c, [(v.stream, v.cycle, v.pred) for v in vs])
            for c, vs, _ in got] == \
           [(c, [(v.stream, v.cycle, v.pred) for v in vs])
            for c, vs, _ in want]
    for (_, gvs, gl), (_, wvs, wl) in zip(got, want):
        if exact:
            np.testing.assert_array_equal(gl, wl)
            assert [v.prob for v in gvs] == [v.prob for v in wvs]
        else:
            np.testing.assert_allclose(gl, wl, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose([v.prob for v in gvs],
                                       [v.prob for v in wvs],
                                       rtol=1e-5, atol=1e-5)


class TestShardedParity:
    @pytest.mark.parametrize("n_devices,n_streams", DEVICE_FLEETS)
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_small_detector_parity(self, n_devices, n_streams, scheme):
        """Sharded == unsharded verdict-for-verdict over a ring-wraparound
        scenario run, bit-exact under REAL, within epsilon quantized."""
        model, params = small_detector(scheme, seed=n_devices + n_streams)
        window, stride = 4, 3
        readings = fleet_readings(n_streams, window + 26,
                                  seed=17 * n_devices + n_streams)
        base, shard = engine_pair(model, params, n_streams,
                                  n_devices=n_devices, window=window,
                                  stride=stride)
        assert shard.n_shards == n_devices
        want = drive_batches(base, readings)
        got = drive_batches(shard, readings)
        assert len(got) == len(want) >= 9       # the ring wrapped
        # REAL is bit-exact except when a shard holds a single stream: XLA
        # lowers the per-shard M=1 forward as gemv, whose accumulation
        # order differs from the unsharded gemm in the last ulp.
        assert_batches_match(
            got, want,
            exact=(scheme == "REAL" and shard.shard_streams > 1))

    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_full_detector_wraparound_regression(self, scheme):
        """Pinned full-size run: 430 cycles wraps the 200-reading ring; the
        widest available mesh serves a non-divisible 6-plant fleet."""
        n_devices = DEVICE_COUNTS[-1]
        model, params = detector_params(scheme, seed=1)
        readings = fleet_readings(6, 430, seed=11)
        base, shard = engine_pair(model, params, 6, n_devices=n_devices,
                                  window=200, stride=10)
        want = drive_batches(base, readings)
        got = drive_batches(shard, readings)
        assert len(got) == len(want) == 24
        assert_batches_match(got, want, exact=(scheme == "REAL"))

    @pytest.mark.parametrize("n_devices,n_streams", DEVICE_FLEETS)
    def test_pad_streams_never_surface(self, n_devices, n_streams):
        """Pad-stream contract: padded arenas emit exactly n_streams
        verdicts per batch, stats count real streams only, and logits are
        sliced to the real fleet."""
        model, params = small_detector("REAL", seed=0)
        eng = StreamEngine(model, params, n_streams=n_streams, n_features=2,
                           window=4, stride=2, mesh=make_fleet_mesh(n_devices))
        pad = -(-n_streams // n_devices) * n_devices
        assert eng.shard_streams * eng.n_shards == pad
        assert eng._ring.shape[0] == pad
        readings = fleet_readings(n_streams, 10, seed=3)
        batches = drive_batches(eng, readings)
        assert len(batches) == 4                 # cycles 3,5,7,9
        for _, vs, logits in batches:
            assert logits.shape[0] == n_streams
            assert {v.stream for v in vs} == set(range(n_streams))
        assert eng.stats.windows == 4 * n_streams
        assert eng.stats.steps == 4

    def test_warmup_compiles_sharded_shapes(self):
        """warmup() on a sharded engine pre-compiles both block lengths with
        the serve-time arena sharding (steady-state steps reuse them)."""
        n_devices = DEVICE_COUNTS[-1]
        model, params = small_detector("SINT", seed=2)
        eng = StreamEngine(model, params, n_streams=5, n_features=2,
                           window=4, stride=3, mesh=make_fleet_mesh(n_devices))
        eng.warmup()
        readings = fleet_readings(5, 12, seed=5)
        assert drive_batches(eng, readings)
        assert eng.stats.steps == 3

    def test_auto_mesh_never_wider_than_fleet(self):
        """Auto-sharding caps the mesh at the fleet size — pure-pad shards
        would burn a dispatch per device on zero streams."""
        model, params = small_detector("REAL", seed=0)
        eng = StreamEngine(model, params, n_streams=2, n_features=2, window=4)
        assert eng.n_shards == (min(2, N_DEVICES) if N_DEVICES > 1 else 1)

    def test_shard_flag_validation(self):
        model, params = small_detector("REAL", seed=0)
        with pytest.raises(ValueError):
            StreamEngine(model, params, n_streams=2, n_features=2, window=4,
                         shard=False, mesh=make_fleet_mesh(1))
        from repro.launch.mesh import make_host_mesh
        # a ("data", "model") mesh is fine while model has size 1
        eng = StreamEngine(model, params, n_streams=2, n_features=2, window=4,
                           mesh=make_host_mesh())
        assert eng.n_shards == 1


class TestShardedWindowing:
    """The identity-probe model of test_streams, re-run through the sharded
    ring scatter: window contents under sharding equal naive slicing of the
    raw stream for random interleavings, including non-divisible fleets."""

    @settings(max_examples=15, deadline=None)
    @given(window=st.integers(3, 8), stride=st.integers(1, 4),
           n_streams=st.integers(1, 6), extra=st.integers(0, 20),
           n_devices=st.sampled_from(DEVICE_COUNTS))
    def test_sharded_windows_equal_naive_slicing(self, window, stride,
                                                 n_streams, extra, n_devices):
        n_features = 2
        model, params = identity_probe(window, n_features)
        eng = StreamEngine(model, params, n_streams=n_streams,
                           n_features=n_features, window=window,
                           stride=stride, mesh=make_fleet_mesh(n_devices),
                           norm_mean=(0.0,) * n_features,
                           norm_std=(1.0,) * n_features)
        n_cycles = window + extra
        rng = np.random.default_rng(
            window * 1000 + stride * 100 + n_streams * 10 + extra + n_devices)
        readings = rng.normal(size=(n_cycles, n_streams, n_features)) \
            .astype(np.float32)
        batches = drive_batches(eng, readings)
        assert len(batches) == (n_cycles - window) // stride + 1
        for cycle, _, logits in batches:
            want = readings[cycle - window + 1:cycle + 1]      # (W, S, F)
            want = want.transpose(1, 0, 2).reshape(n_streams, -1)
            np.testing.assert_allclose(logits, want, rtol=0, atol=0)


class TestShardedDispatch:
    """The single-dispatch guarantee survives sharding: each device shard of
    the verdict step runs ONE pallas_call for all-Dense models (the fused
    kernel executes per shard, inside shard_map)."""

    @pytest.mark.parametrize("n_streams", (16, 6))
    def test_sharded_fused_step_is_one_dispatch_per_shard(self, n_streams):
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=n_streams,
                           backend="pallas", fused=True,
                           mesh=make_fleet_mesh(DEVICE_COUNTS[-1]))
        ring = jnp.zeros((eng._s_pad, eng.window, 2), jnp.float32)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_sharded_per_layer_step_dispatch_count(self):
        model, params = detector_params("SINT")
        eng = StreamEngine(model, params, n_streams=16, backend="pallas",
                           fused=False, mesh=make_fleet_mesh(DEVICE_COUNTS[-1]))
        ring = jnp.zeros((eng._s_pad, eng.window, 2), jnp.float32)
        block = jnp.zeros((eng._s_pad, eng.stride, 2), jnp.float32)
        jaxpr = jax.make_jaxpr(eng._step)(ring, block, jnp.int32(0))
        assert count_pallas_calls(jaxpr.jaxpr) == 4


_SUBPROCESS_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax
assert len(jax.devices()) == 4, jax.devices()
from repro.launch.mesh import make_fleet_mesh
from repro.serving import StreamEngine
from repro.sim import fleet_readings
from test_fused import small_detector

for scheme in ("REAL", "SINT"):
    model, params = small_detector(scheme, seed=3)
    readings = fleet_readings(6, 24, seed=7)           # 6 plants, 4 devices
    logits = {}
    for key, kw in (("base", {"shard": False}),
                    ("shard", {"mesh": make_fleet_mesh(4)})):
        eng = StreamEngine(model, params, n_streams=6, n_features=2,
                           window=4, stride=3, **kw)
        for c in range(readings.shape[0]):
            eng.ingest(readings[c])
        logits[key] = eng.last_logits
    if scheme == "REAL":
        np.testing.assert_array_equal(logits["shard"], logits["base"])
    else:
        np.testing.assert_allclose(logits["shard"], logits["base"],
                                   rtol=1e-5, atol=1e-5)
print("SHARDED_PARITY_OK")
"""


@pytest.mark.skipif(N_DEVICES >= 4,
                    reason="in-process tests already cover 4 devices")
def test_four_device_parity_subprocess():
    """Single-device environments still certify 4-way sharding: a child
    process fans out host devices via XLA_FLAGS and re-checks parity on a
    non-divisible fleet."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.dirname(__file__)] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PARITY],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "SHARDED_PARITY_OK" in out.stdout
