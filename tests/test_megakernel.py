"""Grouped megakernel: the whole heterogeneous fleet in ONE dispatch.

Acceptance: a packable multi-group fleet's verdict step lowers to exactly
ONE ``pallas_call`` — proven in the jaxpr for a 4-group fleet, sharded and
unsharded — and the megakernel's verdicts bit-match (REAL) / epsilon-match
(quantized) the per-group path over ring-wraparound runs for all four head
types.  Sharded REAL agreement is epsilon-level, mirroring the seed
contract of ``test_grouped.TestGroupedParity.test_sharded_matches_unsharded``
(XLA rounds 1 ulp differently across fusion contexts), which is why the
engine auto-packs only unsharded fleets and sharded megakernel serving is
the explicit ``megakernel=True`` opt-in.

Also covered here: the packed-arena VMEM / MXU-mode fuse reasons
(``ops.grouped_fuse_reason``), the in-kernel masked final-layer softmax
(closing the softmax-fold roadmap item), the block-shape step cache +
warmup compile counts, and the ``StreamStats.dispatches`` accounting.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import layers as L
from repro.core import sequential
from repro.kernels import ops, ref
from repro.launch.mesh import make_fleet_mesh
from repro.serving import GroupedStreamEngine, ModelGroup, StreamEngine
from repro.sim import ReconstructionHead

from _jaxpr import count_pallas_calls
from test_grouped import NO_NORM, SCHEMES, mixed_groups, small_model

N_DEVICES = len(jax.devices())


def drive(engine, n_cycles, *, seed=0):
    """Feed identical pseudo-random readings and collect every verdict
    (flush drains the async tail, a no-op in sync mode)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_cycles):
        r = rng.normal(size=(engine.n_streams, 2)).astype(np.float32)
        out.extend(engine.ingest(r.copy()))
    out.extend(engine.flush())
    return out


def assert_verdicts_match(va, vb, scheme, *, bitwise=None):
    """Same verdict stream from two engine configurations: bit for REAL
    (unless ``bitwise=False`` opts into the sharded epsilon contract),
    epsilon for quantized schemes."""
    bitwise = (scheme == "REAL") if bitwise is None else bitwise
    assert len(va) == len(vb) > 0
    for a, b in zip(va, vb):
        assert (a.stream, a.cycle, a.group) == (b.stream, b.cycle, b.group)
        assert a.threshold == b.threshold
        assert (a.prob is None) == (b.prob is None)
        assert (a.score is None) == (b.score is None)
        if bitwise:
            assert a.pred == b.pred
            assert a.prob == b.prob and a.score == b.score
        else:
            for x, y in ((a.prob, b.prob), (a.score, b.score)):
                if x is not None:
                    np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def engine_pair(scheme, *, mega_kw=None, per_kw=None, groups=None, **kw):
    """(megakernel engine, per-group engine) over identical fleets."""
    base = dict(NO_NORM, n_features=2, stride=3, **kw)
    ge = GroupedStreamEngine(groups or mixed_groups(scheme),
                             **dict(base, **(mega_kw or {})))
    pg = GroupedStreamEngine(groups or mixed_groups(scheme),
                             megakernel=False, **dict(base, **(per_kw or {})))
    return ge, pg


class TestMegaParity:
    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_matches_pergroup_over_wraparound(self, scheme):
        """7 ring wraps (window 4, 30 cycles, stride 3) across all four
        head types: megakernel verdicts == per-group verdicts, bit for
        REAL, epsilon for quantized schemes."""
        ge, pg = engine_pair(scheme, shard=False)
        assert ge._mega and not pg._mega
        va, vb = drive(ge, 30), drive(pg, 30)
        assert_verdicts_match(va, vb, scheme)
        for name in pg.last_outputs:
            if scheme == "REAL":
                np.testing.assert_array_equal(ge.last_outputs[name],
                                              pg.last_outputs[name])
            else:
                np.testing.assert_allclose(ge.last_outputs[name],
                                           pg.last_outputs[name],
                                           rtol=1e-5, atol=1e-5)

    def test_async_mega_matches_sync(self):
        """The double-buffered megakernel pipeline bit-matches sync mode
        (the serving/core async contract holds for the mega step too)."""
        a = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                stride=3, shard=False, async_depth=1,
                                **NO_NORM)
        s = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                stride=3, shard=False, **NO_NORM)
        assert a._mega and s._mega
        assert_verdicts_match(drive(a, 24), drive(s, 24), "REAL")

    def test_heterogeneous_windows_fall_back_per_boundary(self):
        """Groups whose ring windows differ can never stack: the engine
        packs, but every ready boundary falls back to the per-group step —
        verdicts stay bit-identical and no mega step is ever compiled."""
        def groups():
            return [
                ModelGroup("w4", *small_model(8, 8, "REAL", 0), 2,
                           ReconstructionHead(threshold=0.5)),
                ModelGroup("w5", *small_model(10, 10, "REAL", 1), 2,
                           ReconstructionHead(threshold=0.5)),
            ]
        ge = GroupedStreamEngine(groups(), n_features=2, stride=3,
                                 shard=False, **NO_NORM)
        pg = GroupedStreamEngine(groups(), n_features=2, stride=3,
                                 shard=False, megakernel=False, **NO_NORM)
        assert ge._mega
        assert_verdicts_match(drive(ge, 27), drive(pg, 27), "REAL")
        assert not ge._mega_steps
        assert ge.stats.dispatches == pg.stats.dispatches

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    def test_auto_stays_pergroup_under_mesh(self):
        """Default sharded serving is bit-identical to the seed: the
        megakernel needs the explicit opt-in under a mesh."""
        mesh = make_fleet_mesh(2)
        auto = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                   mesh=mesh, **NO_NORM)
        assert not auto._mega and auto._mega_reason is None
        forced = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                     mesh=mesh, megakernel=True, **NO_NORM)
        assert forced._mega

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    @pytest.mark.parametrize("scheme", ("REAL", "SINT"))
    def test_forced_sharded_matches_pergroup(self, scheme):
        """``megakernel=True`` on a fleet mesh: one dispatch per step,
        verdicts match the sharded per-group path and the unsharded
        megakernel at the seed's sharded tolerance (rtol 1e-5 — the
        ``test_sharded_matches_unsharded`` contract)."""
        mesh = make_fleet_mesh(2)
        ge, pg = engine_pair(scheme, mesh=mesh,
                             mega_kw={"megakernel": True})
        assert ge._mega and not pg._mega
        vs, vp = drive(ge, 30), drive(pg, 30)
        assert_verdicts_match(vs, vp, scheme, bitwise=False)
        gu = GroupedStreamEngine(mixed_groups(scheme), n_features=2,
                                 stride=3, shard=False, **NO_NORM)
        assert_verdicts_match(vs, drive(gu, 30), scheme, bitwise=False)
        assert ge.stats.dispatches == ge.stats.steps
        assert pg.stats.dispatches == pg.stats.steps * 4

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    def test_pad_stream_contract(self):
        """Group sizes that don't divide the mesh: pad rows ride through the
        stacked mega arena but never surface in verdicts or last_outputs."""
        mesh = make_fleet_mesh(2)
        ge = GroupedStreamEngine(mixed_groups("REAL", n_per=3),
                                 n_features=2, stride=3, mesh=mesh,
                                 megakernel=True, **NO_NORM)
        pg = GroupedStreamEngine(mixed_groups("REAL", n_per=3),
                                 n_features=2, stride=3, shard=False,
                                 megakernel=False, **NO_NORM)
        assert ge._mega
        vs = drive(ge, 18)
        assert all(r.shape[0] == 4 for r in ge._rings)
        assert {v.stream for v in vs} == set(range(12))
        assert all(ge.last_outputs[n].shape[0] == 3 for n in ge.last_outputs)
        assert_verdicts_match(vs, drive(pg, 18), "REAL", bitwise=False)


class TestSingleDispatch:
    """Acceptance: ONE pallas_call per megakernel step for a 4-group fleet,
    in the jaxpr, sharded and unsharded (vs 4 for the per-group step)."""

    def _mega_jaxpr(self, mesh, **kw):
        kwargs = {"mesh": mesh} if mesh is not None else {"shard": False}
        ge = GroupedStreamEngine(mixed_groups("SINT"), n_features=2,
                                 stride=3, backend="pallas", **NO_NORM,
                                 **kwargs, **kw)
        assert ge._mega, ge._mega_reason
        key = tuple((gi, ge.stride) for gi in range(4))
        assert ge._mega_applicable(key)
        step, args = ge._mega_example_args(key)
        return jax.make_jaxpr(step)(*args)

    def test_unsharded_step_is_one_dispatch(self):
        assert count_pallas_calls(self._mega_jaxpr(None).jaxpr) == 1

    def test_sharded_step_is_one_dispatch(self):
        """Under shard_map each device runs the same program: exactly one
        grouped dispatch in the per-shard jaxpr — a 1-wide mesh exercises
        the shard_map path in any process."""
        mesh = make_fleet_mesh(min(N_DEVICES, 2))
        jaxpr = self._mega_jaxpr(mesh, megakernel=True)
        assert count_pallas_calls(jaxpr.jaxpr) == 1

    def test_pergroup_step_is_four(self):
        """The collapsed dispatch count is real: the same fleet's per-group
        step carries one pallas_call per group."""
        ge = GroupedStreamEngine(mixed_groups("SINT"), n_features=2,
                                 stride=3, backend="pallas", shard=False,
                                 megakernel=False, **NO_NORM)
        key = tuple((gi, ge.stride) for gi in range(4))
        step = ge._get_step(key)
        rings = tuple(jnp.zeros_like(r) for r in ge._rings)
        calibs = tuple(jnp.zeros_like(c) for c in ge._calibs)
        counts = tuple(jnp.zeros_like(c) for c in ge._counts)
        blocks = tuple(jnp.zeros((ge._groups[gi].s_pad, n, 2), jnp.float32)
                       for gi, n in key)
        poss = tuple(jnp.int32(0) for _ in key)
        thrs = tuple(ge._thr(ge._groups[gi]) for gi, _ in key)
        jaxpr = jax.make_jaxpr(step)(rings, calibs, counts, blocks, poss,
                                     thrs)
        assert count_pallas_calls(jaxpr.jaxpr) == 4


class TestStepCacheAndWarmup:
    """Satellite: the mega step cache is keyed on BLOCK SHAPE, not ready
    subset — warmup compiles at most one step per shape and the hot path
    never compiles."""

    def test_warmup_compiles_one_step_per_block_shape(self):
        ge = GroupedStreamEngine(mixed_groups("SINT"), n_features=2,
                                 stride=3, shard=False, **NO_NORM)
        assert ge._mega
        ge.warmup()
        # Schedule: fill-in fires all four groups with a 4-long block once,
        # then steady state fires 3-long blocks — two shapes, one pack.
        assert {length for key in ge._schedule_keys()
                for _, length in key} == {3, 4}
        assert len(ge._mega_steps) == 2
        assert len(ge._mega_packs) == 1
        compiled = set(ge._mega_steps)
        rng = np.random.default_rng(0)
        for _ in range(30):
            ge.ingest(rng.normal(size=(8, 2)).astype(np.float32))
        assert set(ge._mega_steps) == compiled
        assert not ge._steps          # per-group path never built
        assert ge.stats.dispatches == ge.stats.steps > 0

    def test_equal_geometry_subsets_share_one_executable(self):
        """Identity-distinct subsets with equal plans (same shapes, dtypes,
        activations, heads) hit one compiled step: the cache key is the
        hashable GroupedPlan + serving geometry, not the unit tuple."""
        groups = [
            ModelGroup(f"g{i}", *small_model(8, 8, "SINT", i), 2,
                       ReconstructionHead(threshold=0.5))
            for i in range(4)
        ]
        ge = GroupedStreamEngine(groups, n_features=2, stride=2,
                                 shard=False, **NO_NORM)
        assert ge._mega
        s01, p01 = ge._get_mega_step((0, 1), 2)
        s23, p23 = ge._get_mega_step((2, 3), 2)
        assert p01 is not p23 and p01.sig == p23.sig
        assert s01 is s23
        assert len(ge._mega_steps) == 1 and len(ge._mega_packs) == 2


class TestDispatchAccounting:
    """Satellite: StreamStats.dispatches counts logical kernel dispatches —
    1 per mega step, n_groups per fused per-group step, len(stack) per
    per-layer unit."""

    def test_mega_one_per_step(self):
        ge = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                 stride=3, shard=False, **NO_NORM)
        drive(ge, 18)
        assert ge.stats.steps > 0
        assert ge.stats.dispatches == ge.stats.steps

    def test_pergroup_counts_each_group(self):
        ge = GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                 stride=3, shard=False, megakernel=False,
                                 **NO_NORM)
        drive(ge, 18)
        assert ge.stats.dispatches == ge.stats.steps * 4

    def test_perlayer_unit_charges_stack_length(self):
        """fused=False groups pay one dispatch per layer (the 2-layer test
        models: 2 per group per step)."""
        groups = mixed_groups("REAL")
        for g in groups:
            g.fused = False
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 shard=False, **NO_NORM)
        assert "fused=False" in ge._mega_reason
        drive(ge, 18)
        assert ge.stats.dispatches == ge.stats.steps * 8

    def test_single_engine_fused_is_one_per_step(self):
        model, params = small_model(8, 2, "REAL", 0)
        eng = StreamEngine(model, params, n_streams=3, n_features=2,
                           stride=3, shard=False, **NO_NORM)
        rng = np.random.default_rng(0)
        for _ in range(12):
            eng.ingest(rng.normal(size=(3, 2)).astype(np.float32))
        assert eng.stats.dispatches == eng.stats.steps > 0


class TestPackReasons:
    """Satellite: ``ops.grouped_fuse_reason`` / engine fallback semantics —
    every non-packable fleet serves per-group with a diagnosable reason,
    and ``megakernel=True`` surfaces it."""

    def test_mixed_dtype_position_rejected_and_served(self):
        groups = mixed_groups("REAL")[:2] + mixed_groups("SINT")[2:]
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 shard=False, **NO_NORM)
        assert not ge._mega
        assert "mixes weight dtypes" in ge._mega_reason
        assert "one MXU mode per position" in ge._mega_reason
        with pytest.raises(ValueError, match="mixes weight dtypes"):
            GroupedStreamEngine(groups, n_features=2, stride=3,
                                shard=False, megakernel=True, **NO_NORM)
        drive(ge, 12)
        assert ge.stats.dispatches == ge.stats.steps * 4

    def test_vmem_overflow_names_the_widest_slab(self):
        """The packed-arena VMEM message carries the per-group slab bytes,
        the budget, and which group's slab drives the union arena."""
        def stack(name, k, n):
            return [({"w": jnp.zeros((k, n), jnp.float32),
                      "b": jnp.zeros((n,), jnp.float32)}, "relu"),
                    ({"w": jnp.zeros((n, 2), jnp.float32),
                      "b": jnp.zeros((2,), jnp.float32)}, "linear")]
        stacks = [stack("small", 128, 128), stack("big", 2048, 2048)]
        reason = ops.grouped_fuse_reason(stacks, names=["small", "big"])
        assert reason is not None
        assert "packed-arena VMEM resident set" in reason
        assert str(ops._fused_mod.VMEM_BUDGET_BYTES) in reason
        assert "small=" in reason and "big=" in reason
        assert "widest slab 'big'" in reason
        assert "serve this fleet per-group" in reason
        assert not ops.can_fuse_grouped(stacks)

    def test_fused_false_group_pins_perlayer(self):
        groups = mixed_groups("REAL")
        groups[1].fused = False
        with pytest.raises(ValueError, match="fused=False"):
            GroupedStreamEngine(groups, n_features=2, stride=3,
                                shard=False, megakernel=True, **NO_NORM)

    def test_head_without_kernel_epilogue(self):
        class HostOnlyHead(ReconstructionHead):
            def kernel_epilogue(self):
                return None
        groups = mixed_groups("REAL")
        groups[1] = ModelGroup("ae", groups[1].model, groups[1].params, 2,
                               HostOnlyHead(threshold=0.25))
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 shard=False, **NO_NORM)
        assert "no in-kernel epilogue" in ge._mega_reason
        with pytest.raises(ValueError, match="no in-kernel epilogue"):
            GroupedStreamEngine(groups, n_features=2, stride=3,
                                shard=False, megakernel=True, **NO_NORM)

    def test_custom_prepare_falls_back(self):
        class SlicingHead(ReconstructionHead):
            def prepare(self, win):
                return win[..., :4]
        groups = mixed_groups("REAL")
        groups[1] = ModelGroup("ae", *small_model(4, 4, "REAL", 9), 2,
                               SlicingHead(threshold=0.25))
        ge = GroupedStreamEngine(groups, n_features=2, stride=3,
                                 shard=False, **NO_NORM)
        assert "overrides prepare()" in ge._mega_reason

    def test_single_unit_is_already_single_dispatch(self):
        g = mixed_groups("REAL")[0]
        ge = GroupedStreamEngine([g], n_features=2, stride=3, shard=False,
                                 **NO_NORM)
        assert "single unit" in ge._mega_reason and not ge._mega

    @pytest.mark.skipif(N_DEVICES < 2, reason="needs a multi-device process")
    def test_model_sharded_mesh_cannot_pack(self):
        mesh = make_fleet_mesh(1, model_shards=2)
        with pytest.raises(ValueError, match="model-axis"):
            GroupedStreamEngine(mixed_groups("REAL"), n_features=2,
                                stride=3, mesh=mesh, megakernel=True,
                                **NO_NORM)


class TestGroupedKernel:
    """Kernel-level contracts of ``ops.grouped_apply``: the ref path is
    bit-identical to the per-group oracle loop, the Pallas (interpret)
    path is epsilon-close, and the final-layer softmax is masked to each
    group's true class count in-kernel (the closed softmax-fold item —
    the single-stack ``fuse_reason`` still rejects softmax)."""

    def _fleet(self, scheme, softmax_clf=False):
        act2 = "softmax" if softmax_clf else "linear"
        models = [small_model(8, 3, scheme, 0),
                  small_model(8, 8, scheme, 1),
                  small_model(6, 2, scheme, 3)]
        if softmax_clf:
            m = sequential([L.Input(),
                            L.Dense(units=6, activation="relu"),
                            L.Dense(units=3, activation=act2)], (8,))
            models[0] = (m, m.init_params(jax.random.PRNGKey(0)))
        stacks = [ops.dense_stack(m, p) for m, p in models]
        kinds = [ops.GROUPED_KIND_LOGITS, ops.GROUPED_KIND_SCORE,
                 ops.GROUPED_KIND_SCORE]
        return models, stacks, kinds

    def _expected(self, models, stacks, kinds, plan, win, tgt):
        exp = np.zeros((len(stacks), win.shape[1], plan.payload_width),
                       np.float32)
        for g, stack in enumerate(stacks):
            h = jnp.asarray(win[g][:, :plan.true_k0s[g]])
            for p, act in stack:
                h = ref.dense_layer_ref(h, p, act)
            if kinds[g] == ops.GROUPED_KIND_LOGITS:
                exp[g, :, :h.shape[1]] = np.asarray(h)
            else:
                n = plan.n_outs[g]
                exp[g, :, 0] = np.asarray(jnp.mean(
                    jnp.square(h - tgt[g][:, :n]), axis=-1))
        return exp

    @pytest.mark.parametrize("scheme", SCHEMES)
    def test_ref_bitwise_pallas_close(self, scheme):
        models, stacks, kinds = self._fleet(scheme)
        assert ops.grouped_fuse_reason(stacks, k0=8) is None
        plan, arrays = ops.build_grouped_plan(stacks, kinds, k0=8)
        rng = np.random.default_rng(0)
        win = rng.normal(size=(3, 5, 8)).astype(np.float32)
        tgt = np.zeros((3, 5, plan.n_out), np.float32)
        tgt[1, :, :8] = win[1]                       # ae: window target
        tgt[2, :, :2] = win[2][:, -2:]               # forecast: tail target
        exp = self._expected(models, stacks, kinds, plan, win,
                             jnp.asarray(tgt))
        pay_ref = ops.grouped_apply(jnp.asarray(win), plan, arrays,
                                    jnp.asarray(tgt), backend="ref")
        pay_pal = ops.grouped_apply(jnp.asarray(win), plan, arrays,
                                    jnp.asarray(tgt), backend="pallas")
        np.testing.assert_array_equal(np.asarray(pay_ref), exp)
        np.testing.assert_allclose(np.asarray(pay_pal), exp, rtol=2e-5,
                                   atol=2e-5)

    def test_masked_final_softmax(self):
        """A 3-class softmax classifier packed beside an 8-wide group: the
        in-kernel softmax normalizes over the TRUE class count (pad lanes
        annihilated before the exp), so probabilities sum to 1 — while the
        single-stack fuse path still rejects softmax entirely."""
        models, stacks, kinds = self._fleet("REAL", softmax_clf=True)
        assert ops.fuse_reason(stacks[0]) is not None      # single: reject
        assert ops.grouped_fuse_reason(stacks, k0=8) is None
        plan, arrays = ops.build_grouped_plan(stacks, kinds, k0=8)
        rng = np.random.default_rng(1)
        win = jnp.asarray(rng.normal(size=(3, 5, 8)).astype(np.float32))
        tgt = jnp.zeros((3, 5, plan.n_out))
        tgt = tgt.at[1, :, :8].set(win[1])
        tgt = tgt.at[2, :, :2].set(win[2][:, -2:])
        pay_ref = ops.grouped_apply(win, plan, arrays, tgt, backend="ref")
        pay_pal = ops.grouped_apply(win, plan, arrays, tgt,
                                    backend="pallas")
        probs = np.asarray(pay_ref)[0, :, :3]
        assert (probs > 0).all()
        np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(pay_ref)[0, :, 3:], 0.0)
        np.testing.assert_allclose(np.asarray(pay_pal),
                                   np.asarray(pay_ref), rtol=2e-5,
                                   atol=2e-5)

    def test_non_final_softmax_rejected(self):
        _, stacks, _ = self._fleet("REAL")
        stacks[0][0] = (stacks[0][0][0], "softmax")
        reason = ops.grouped_fuse_reason(stacks, names=["a", "b", "c"])
        assert reason is not None and "softmax" in reason
