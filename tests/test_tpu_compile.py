"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode accepts block shapes and memory layouts that the TPU
compiler refuses, so these tests compile each kernel of the fleet service
at its real widths for a *described* ``v5e:2x2`` topology — no chip is
attached.  ``ops._on_tpu`` is patched to True so the wrappers take the
Pallas branch with ``interpret=False``, exactly as they do on a chip, and
each compiled program must contain the Mosaic custom call
(``tpu_custom_call``): the jnp oracle or interpret mode cannot pass for the
kernel.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from _jaxpr import equations
from repro.core import quantize
from repro.kernels import ops
from repro.sim import (ForecastHead, MarginHead, ReconstructionHead,
                       build_autoencoder, build_detector, build_forecaster,
                       build_margin_model)

SCHEMES = ("REAL", "SINT")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to a persistent cache cannot be read back on
    # a host without the chip; keep these compiles out of any cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _calibration(n=4):
    rng = np.random.default_rng(100)
    return [jnp.asarray(rng.standard_normal(400).astype(np.float32))
            for _ in range(n)]


def _compiled_text(fn, *args) -> str:
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("m", (16, 4096))
@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("build", (build_detector, build_autoencoder),
                         ids=("classifier", "autoencoder"))
def test_fused_forward_compiles(build, scheme, m, one_chip, on_tpu):
    model = build()
    params = model.init_params(jax.random.PRNGKey(0))
    if scheme != "REAL":
        params = quantize.quantize_params(model, params, scheme,
                                          calibration=_calibration())
    stack = ops.dense_stack(model, params)
    acts = [act for _, act in stack]

    def forward(x, layer_params):
        return ops.fused_forward(x, list(zip(layer_params, acts)))

    text = _compiled_text(
        forward,
        jax.ShapeDtypeStruct((m, 400), jnp.float32, sharding=one_chip),
        _spec([p for p, _ in stack], one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", (16, 4096))
def test_quantized_matmul_compiles(m, one_chip, on_tpu):
    def qmm(xq, wq, scale, bias):
        return ops.quantized_matmul(xq, wq, scale, bias)

    text = _compiled_text(
        qmm,
        jax.ShapeDtypeStruct((m, 400), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((400, 64), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


def _four_head_plan(scheme):
    """The four-head mixed fleet's packed plan: classifier, autoencoder,
    one-class margin and forecast heads over the 200-reading window."""
    heads = [None, ReconstructionHead(threshold=1.0),
             MarginHead(threshold=1.0, center=(0.0,) * 16),
             ForecastHead(threshold=1.0)]
    builders = (build_detector, build_autoencoder, build_margin_model,
                build_forecaster)
    calib = _calibration()
    stacks, kinds = [], []
    for i, (build, head) in enumerate(zip(builders, heads)):
        model = build()
        params = model.init_params(jax.random.PRNGKey(10 + i))
        if scheme != "REAL":
            c = calib if head is None else [head.prepare(s) for s in calib]
            params = quantize.quantize_params(model, params, scheme,
                                              calibration=c)
        stacks.append(ops.dense_stack(model, params))
        kinds.append(ops.GROUPED_KIND_LOGITS if head is None
                     else ops.GROUPED_KIND_SCORE)
    return ops.build_grouped_plan(stacks, kinds, k0=400)


@pytest.mark.parametrize("m", (16, 4096))
@pytest.mark.parametrize("scheme", SCHEMES)
def test_grouped_apply_compiles(scheme, m, one_chip, on_tpu):
    plan, arrays = _four_head_plan(scheme)

    def apply(x, arrs, tgt):
        return ops.grouped_apply(x, plan, arrs, tgt)

    g = plan.n_groups
    text = _compiled_text(
        apply,
        jax.ShapeDtypeStruct((g, m, plan.k0), jnp.float32, sharding=one_chip),
        _spec(arrays, one_chip),
        jax.ShapeDtypeStruct((g, m, plan.n_out), jnp.float32,
                             sharding=one_chip))
    assert "tpu_custom_call" in text


def _f32_dot_precisions(fn, *args) -> list:
    """The precision of every f32 ``dot_general`` in ``fn``'s Pallas
    kernels, as traced for the chip."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [eqn.params["precision"] for eqn in equations(jaxpr.jaxpr)
            if eqn.primitive.name == "dot_general"
            and eqn.invars[0].aval.dtype == jnp.float32]


HIGHEST = (jax.lax.Precision.HIGHEST,) * 2


@pytest.mark.parametrize("build", (build_detector, build_autoencoder),
                         ids=("classifier", "autoencoder"))
def test_fused_real_dots_are_full_f32(build, on_tpu):
    """REAL layers contract in full f32 on the MXU, not one bf16 pass."""
    model = build()
    stack = ops.dense_stack(model, model.init_params(jax.random.PRNGKey(0)))
    acts = [act for _, act in stack]
    precisions = _f32_dot_precisions(
        lambda x, ps: ops.fused_forward(x, list(zip(ps, acts))),
        jnp.zeros((16, 400), jnp.float32), [p for p, _ in stack])
    assert len(precisions) == len(stack)
    assert all(p == HIGHEST for p in precisions), precisions


def test_grouped_real_dots_are_full_f32(on_tpu):
    plan, arrays = _four_head_plan("REAL")
    g = plan.n_groups
    precisions = _f32_dot_precisions(
        lambda x, arrs, tgt: ops.grouped_apply(x, plan, arrs, tgt),
        jnp.zeros((g, 16, plan.k0), jnp.float32), arrays,
        jnp.zeros((g, 16, plan.n_out), jnp.float32))
    assert precisions and all(p == HIGHEST for p in precisions), precisions
