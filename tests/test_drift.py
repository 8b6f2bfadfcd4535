"""Online drift adaptation: ParamDrift plant physics, streaming threshold
recalibration (head hooks + engines), the drift-FPR acceptance run, and the
serving-accounting satellite regressions (reservoir seeds, per-pass latency
tails, stride>window pending cap).

The acceptance question (ISSUE 7): a threshold calibrated once, offline,
floods with false alarms when the plant drifts benignly; the streaming
recalibration must hold the false-positive rate near the calibrated
``target_fpr`` on a drifting fleet while the frozen threshold exceeds 10x —
without touching detection of real attacks (scores beyond the admission
headroom never enter the calibration state).

The detector under test is a zero-weight "autoencoder": reconstruction is
identically zero, so the ReconstructionHead's score is the mean squared
normalized window — an energy detector whose benign score tracks the
operating point, with no training inside the test."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st
from repro.configs import msf_detector as spec
from repro.core import layers as L
from repro.core import quantize, sequential
from repro.launch.mesh import make_fleet_mesh
from repro.serving import (AdaptConfig, GroupedStreamEngine, LatencyReservoir,
                           ModelGroup, StreamEngine)
from repro.sim import (DRIFTABLE, ClassifierHead, ParamDrift, PlantParams,
                       ReconstructionHead, conservative_quantile,
                       fleet_readings, get_scenario, scenario_table)

TARGET_FPR = 0.05
N_DEVICES = len(jax.devices())


def energy_detector(window: int, n_features: int):
    """Zero-weight single-Dense 'autoencoder' (see module docstring)."""
    size = window * n_features
    model = sequential([L.Input(), L.Dense(units=size, activation="linear")],
                       (size,))
    params = model.init_params(jax.random.PRNGKey(0))
    (uid,) = [n.uid for n in model.graph.nodes
              if isinstance(n.layer, L.Dense)]
    params[uid]["w"] = jnp.zeros((size, size), jnp.float32)
    params[uid]["b"] = jnp.zeros((size,), jnp.float32)
    return model, params


def energy_scores(readings, window, stride, mean, std):
    """(steps, S) naive-slicing energy scores — the calibration oracle."""
    norm = (readings - mean) / std
    return np.stack([(norm[c - window + 1:c + 1] ** 2).mean(axis=(0, 2))
                     for c in range(window - 1, readings.shape[0], stride)])


class TestParamDrift:
    def test_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            ParamDrift({})
        with pytest.raises(ValueError, match="cannot drift"):
            ParamDrift({"wd_setpoint": 0.1})
        with pytest.raises(ValueError, match="through zero"):
            ParamDrift({"k_flash": -1.0})
        with pytest.raises(ValueError, match="ramp"):
            ParamDrift({"k_flash": 0.1}, ramp=0)

    def test_dict_normalized_sorted_and_hashable(self):
        d = ParamDrift({"t_sea": 0.1, "k_flash": -0.1})
        assert d.shifts == (("k_flash", -0.1), ("t_sea", 0.1))
        assert hash(d) == hash(ParamDrift({"k_flash": -0.1, "t_sea": 0.1}))

    def test_fraction_ramp(self):
        d = ParamDrift({"k_flash": -0.5}, start=100, ramp=200)
        assert d.fraction(0) == 0.0
        assert d.fraction(100) == 0.0
        assert d.fraction(200) == 0.5
        assert d.fraction(300) == 1.0
        assert d.fraction(10_000) == 1.0     # holds, never overshoots

    def test_apply_multiplicative_and_preonset_identity(self):
        base = PlantParams()
        d = ParamDrift({"k_flash": -0.5}, start=0, ramp=10)
        assert d.apply(base, 0) is base       # pre-onset: no allocation
        drifted = d.apply(base, 10)
        assert drifted.k_flash == pytest.approx(base.k_flash * 0.5)
        # every non-shifted field untouched
        for f in sorted(DRIFTABLE - {"k_flash"}):
            assert getattr(drifted, f) == getattr(base, f)

    def test_seasonal_drift_moves_operating_point(self):
        """The builtin seasonal-drift scenario must move the PID-held TB0
        operating point by >= 1 sigma of the detector normalization — the
        threshold-killer the adaptation exists for."""
        kw = dict(names=["baseline"], seed=7)
        benign = fleet_readings(1, 2600, **kw)
        kw["names"] = ["seasonal-drift"]
        drifted = fleet_readings(1, 2600, **kw)
        delta = abs(drifted[-500:, 0, 0].mean() - benign[-500:, 0, 0].mean())
        assert delta >= 1.0 * spec.NORM_STD[0]

    def test_builtin_drift_scenarios_registered(self):
        assert get_scenario("seasonal-drift").drift is not None
        assert get_scenario("seasonal-drift").onset is None    # benign
        sc = get_scenario("drift-then-throttle")
        assert sc.drift is not None and sc.onset == 1300       # composes
        assert "drift" in scenario_table()


class TestStreamingThresholdProperty:
    """ScoreHead streaming hooks vs a pure-python oracle: the streaming
    threshold IS the conservative quantile of the trailing <= capacity
    admitted scores per stream, pooled fleet-wide — exact below the sketch
    window and across ring wraparound."""

    @settings(max_examples=20, deadline=None)
    @given(n_streams=st.integers(1, 4), capacity=st.integers(1, 6),
           n_steps=st.integers(1, 20), headroom=st.floats(1.0, 4.0),
           seed=st.integers(0, 10_000))
    def test_matches_trailing_quantile_oracle(self, n_streams, capacity,
                                              n_steps, headroom, seed):
        head = ReconstructionHead(threshold=1.0, target_fpr=0.1)
        rng = np.random.default_rng(seed)
        ring, counts = head.calib_state(n_streams, capacity)
        thr = jnp.float32(1.0)
        admitted = [[] for _ in range(n_streams)]
        for _ in range(n_steps):
            # lognormal-ish positives spanning the admission gate
            s = rng.exponential(1.0, size=n_streams).astype(np.float32)
            ring, counts = head.calib_update(ring, counts, jnp.asarray(s),
                                             thr, headroom)
            for i in range(n_streams):
                if s[i] <= headroom * 1.0:
                    admitted[i].append(s[i])
        pooled = np.concatenate(
            [np.asarray(a[-capacity:], np.float32) for a in admitted]
        ) if any(admitted) else np.zeros((0,), np.float32)
        # the pooled valid ring scores are exactly the trailing admitted set
        got = head.streaming_scores(ring, counts)
        np.testing.assert_array_equal(np.sort(got), np.sort(pooled))
        for min_count in (1, pooled.size, pooled.size + 1):
            want = (None if pooled.size < max(min_count, 1)
                    else conservative_quantile(pooled, 0.1))
            assert head.streaming_threshold(
                ring, counts, min_count=min_count) == want

    def test_wraparound_pinned(self):
        """capacity=3, 7 admissions: the ring holds exactly the last 3."""
        head = ReconstructionHead(threshold=1.0, target_fpr=0.25)
        ring, counts = head.calib_state(1, 3)
        for v in (1, 2, 3, 4, 5, 6, 7):
            ring, counts = head.calib_update(
                ring, counts, jnp.asarray([float(v)], jnp.float32),
                jnp.float32(10.0), 1.0)
        np.testing.assert_array_equal(
            np.sort(head.streaming_scores(ring, counts)), [5.0, 6.0, 7.0])
        assert head.streaming_threshold(ring, counts) == 7.0

    # Counts of 8 streams at capacity 6: below, at and past it, mixed;
    # pooled sizes 1, MIN_COUNT - 1, MIN_COUNT and S x capacity.
    MIN_COUNT = 5
    MIXED = [0, 3, 6, 9, 6, 1, 12, 5]
    FULL = [6, 7, 6, 13, 6, 6, 9, 6]
    SELECT_CASES = {
        "mixed": MIXED, "pool-1": [0, 0, 1, 0, 0, 0, 0, 0],
        "pool-min-1": [0, 2, 0, 1, 0, 0, 1, 0],
        "pool-min": [0, 2, 0, 1, 0, 0, 1, 1], "pool-full": FULL,
        "ties": MIXED, "ties-full": FULL, "nan": MIXED, "nan-full": FULL}

    @pytest.mark.parametrize("target_fpr", [0.1, 0.05])
    @pytest.mark.parametrize("case", list(SELECT_CASES))
    def test_selection_is_the_conservative_quantile_bitwise(self, case,
                                                            target_fpr):
        """The selected order statistic is bit for bit the float64
        ``conservative_quantile`` of the pooled valid scores (None below
        ``min_count``); a NaN outside the valid slots stays out of the
        pool, one inside gives what ``np.quantile`` gives."""
        head = ReconstructionHead(threshold=1.0, target_fpr=target_fpr)
        rng = np.random.default_rng(len(case))
        ring = rng.exponential(1.0, size=(8, 6)).astype(np.float32)
        counts = np.asarray(self.SELECT_CASES[case], np.int32)
        if case.startswith("ties"):
            ring = np.round(ring * 2) / 2          # a handful of values
        short = np.flatnonzero(counts < ring.shape[1])
        if short.size:                             # an unused slot
            ring[short[0], counts[short[0]]] = np.nan
        if case.startswith("nan"):
            ring[3, 2] = np.nan                    # a pooled slot
        pool = head.streaming_scores(ring, counts)
        assert pool.size == np.minimum(counts, ring.shape[1]).sum()
        assert np.isnan(pool).any() == case.startswith("nan")
        for min_count in (1, self.MIN_COUNT):
            got = head.streaming_threshold(ring, counts, min_count=min_count)
            if pool.size < min_count:
                assert got is None
                continue
            want = conservative_quantile(pool, target_fpr)
            assert np.float64(got).tobytes() == np.float64(want).tobytes()

    def test_requires_target_fpr(self):
        head = ReconstructionHead(threshold=1.0)
        ring, counts = head.calib_state(1, 4)
        with pytest.raises(ValueError, match="target_fpr"):
            head.streaming_threshold(ring, counts)


class TestEngineAdaptation:
    def _drive_with_reference(self, *, stride=1, every=1, n_cycles=40,
                              spike_cycle=None):
        """Drive an adaptive engine on random readings and replay the
        recalibration host-side from the engine's OWN verdict scores: admit
        through the headroom gate at the pre-step live threshold, pool the
        trailing <= capacity scores per stream, conservative-quantile them.
        Every verdict's threshold must equal the oracle's, exactly."""
        window, n_feat, n_streams = 6, 1, 3
        cfg = AdaptConfig(capacity=4, every=every, min_count=3, headroom=2.0)
        model, params = energy_detector(window, n_feat)
        head = ReconstructionHead(threshold=1.0, target_fpr=0.25)
        eng = StreamEngine(model, params, n_streams=n_streams,
                           n_features=n_feat, window=window, stride=stride,
                           norm_mean=(0.0,), norm_std=(1.0,),
                           head=head, adapt=cfg)
        rng = np.random.default_rng(3)
        readings = rng.normal(size=(n_cycles, n_streams, n_feat)) \
            .astype(np.float32)
        if spike_cycle is not None:    # a fat attack burst on stream 0
            readings[spike_cycle:spike_cycle + window, 0] = 50.0
        thr = head.threshold
        admitted = [[] for _ in range(n_streams)]
        fires = 0
        for c in range(n_cycles):
            verdicts = eng.ingest(readings[c])
            if not verdicts:
                continue
            fires += 1
            scores = [v.score for v in verdicts]
            for i, s in enumerate(scores):
                if s <= cfg.headroom * thr:
                    admitted[i].append(np.float32(s))
            if fires % cfg.every == 0:
                pooled = np.concatenate(
                    [np.asarray(a[-cfg.capacity:], np.float32)
                     for a in admitted])
                if pooled.size >= cfg.min_count:
                    thr = conservative_quantile(pooled, head.target_fpr)
            for v in verdicts:
                assert v.threshold == thr
                assert v.pred == int(v.score > thr)
        assert fires > cfg.capacity + 2          # the score rings wrapped
        assert eng.live_threshold == thr
        assert thr != head.threshold             # it actually moved
        return eng, thr, admitted

    def test_live_threshold_matches_host_oracle(self):
        self._drive_with_reference()

    def test_stride_and_cadence_compose(self):
        self._drive_with_reference(stride=3, every=2, n_cycles=70)

    def test_headroom_gate_blocks_attack_scores(self):
        """A 50-sigma burst on stream 0 must never enter the calibration
        state: its admitted-score list stays spike-free, so the fleet
        threshold cannot be dragged up after the attack."""
        eng, thr, admitted = self._drive_with_reference(spike_cycle=20)
        assert max(max(a) for a in admitted) < 10.0
        assert thr < 10.0
        counts = np.asarray(eng._calib_counts)[:3]
        assert counts[0] < counts[1]             # stream 0 skipped admissions

    def test_nonadaptive_score_head_keeps_offline_threshold(self):
        window, n_feat = 4, 1
        model, params = energy_detector(window, n_feat)
        head = ReconstructionHead(threshold=0.5, target_fpr=0.1)
        eng = StreamEngine(model, params, n_streams=2, n_features=n_feat,
                           window=window, stride=1,
                           norm_mean=(0.0,), norm_std=(1.0,), head=head)
        rng = np.random.default_rng(0)
        for c in range(12):
            for v in eng.ingest(rng.normal(size=(2, 1)).astype(np.float32)):
                assert v.threshold == 0.5
        assert eng.live_threshold == 0.5

    def test_adapt_validation(self):
        window, n_feat = 4, 1
        model, params = energy_detector(window, n_feat)
        kw = dict(n_streams=2, n_features=n_feat, window=window,
                  norm_mean=(0.0,), norm_std=(1.0,))
        with pytest.raises(ValueError, match="ScoreHead"):
            StreamEngine(model, params, head=ClassifierHead(), adapt=True,
                         **kw)
        with pytest.raises(ValueError, match="target_fpr"):
            StreamEngine(model, params, adapt=True,
                         head=ReconstructionHead(threshold=1.0), **kw)
        with pytest.raises(ValueError, match="calibrate"):
            StreamEngine(model, params, adapt=True,
                         head=ReconstructionHead(target_fpr=0.1), **kw)
        with pytest.raises(ValueError, match="AdaptConfig"):
            StreamEngine(model, params, adapt="yes",
                         head=ReconstructionHead(threshold=1.0,
                                                 target_fpr=0.1), **kw)
        for bad in (dict(capacity=0), dict(every=0), dict(min_count=0),
                    dict(headroom=0.5)):
            with pytest.raises(ValueError):
                AdaptConfig(**bad)


@pytest.mark.parametrize("n_devices",
                         [n for n in (1, 2, 4) if n <= N_DEVICES])
def test_sharded_adaptation_bit_matches_unsharded(n_devices):
    """Adaptive serving under the ("data",) fleet mesh: calibration state is
    row-local, so verdicts, live thresholds AND the gathered calibration
    state must bit-match the unsharded engine — including a fleet size not
    divisible by the device count (pad rows admit nothing)."""
    window, n_feat, n_streams = 10, 2, 6
    model, params = energy_detector(window, n_feat)
    head = ReconstructionHead(threshold=2.0, target_fpr=0.1)
    cfg = AdaptConfig(capacity=5, min_count=4, headroom=3.0)
    readings = fleet_readings(n_streams, 60, seed=13)
    engines = {}
    for name, mesh_kw in (("unsharded", dict(shard=False)),
                          ("sharded",
                           dict(mesh=make_fleet_mesh(n_devices)))):
        eng = StreamEngine(model, params, n_streams=n_streams,
                           n_features=n_feat, window=window, stride=4,
                           head=head, adapt=cfg, **mesh_kw)
        eng.warmup()
        verdicts = []
        for c in range(60):
            verdicts.extend(eng.ingest(readings[c]))
        engines[name] = (eng, verdicts)
    (u, uv), (s, sv) = engines["unsharded"], engines["sharded"]
    assert len(uv) == len(sv) > 0
    for a, b in zip(uv, sv):
        assert (a.stream, a.cycle, a.pred) == (b.stream, b.cycle, b.pred)
        assert a.score == b.score and a.threshold == b.threshold
    assert u.live_threshold == s.live_threshold != head.threshold
    # pad rows (fleet not divisible by the mesh) are sliced out of the
    # recalibration pool; the real rows of the gathered state must bit-match
    np.testing.assert_array_equal(np.asarray(u._calib_ring)[:n_streams],
                                  np.asarray(s._calib_ring)[:n_streams])
    np.testing.assert_array_equal(np.asarray(u._calib_counts)[:n_streams],
                                  np.asarray(s._calib_counts)[:n_streams])


class TestGroupedAdaptation:
    def test_grouped_matches_standalone_adaptive_engine(self):
        """One GroupedStreamEngine serving an adaptive AE group next to a
        frozen-threshold group must produce, for the adaptive group, exactly
        the verdicts a standalone adaptive StreamEngine produces on the same
        sub-fleet — and leave the frozen group's threshold pinned."""
        window, n_feat, n_per = 8, 2, 3
        model, params = energy_detector(window, n_feat)
        head_a = ReconstructionHead(threshold=2.0, target_fpr=0.2)
        head_b = ReconstructionHead(threshold=2.0, target_fpr=0.2)
        cfg = AdaptConfig(capacity=4, min_count=3, headroom=3.0)
        ge = GroupedStreamEngine(
            [ModelGroup("adapt", model, params, n_per, head_a, adapt=cfg),
             ModelGroup("frozen", model, params, n_per, head_b)],
            stride=3)
        se = StreamEngine(model, params, n_streams=n_per,
                          n_features=n_feat, window=window, stride=3,
                          head=head_a, adapt=cfg)
        readings = fleet_readings(2 * n_per, 50, seed=29)
        gv, sv = [], []
        for c in range(50):
            gv.extend(ge.ingest(readings[c]))
            sv.extend(se.ingest(readings[c][:n_per]))
        ga = [v for v in gv if v.group == "adapt"]
        assert len(ga) == len(sv) > 0
        for a, b in zip(ga, sv):
            assert (a.stream, a.cycle, a.pred) == (b.stream, b.cycle, b.pred)
            assert a.score == b.score and a.threshold == b.threshold
        live = ge.live_thresholds()
        assert live["adapt"] == se.live_threshold != 2.0
        assert live["frozen"] == 2.0
        assert all(v.threshold == 2.0 for v in gv if v.group == "frozen")

    @pytest.mark.parametrize("megakernel", [None, False],
                             ids=["mega", "per-group"])
    @pytest.mark.parametrize("async_depth", [0, 1], ids=["sync", "async"])
    @pytest.mark.parametrize("every", [1, 2])
    def test_recalibration_counters(self, every, async_depth, megakernel):
        """``StreamStats.recalibrations`` adds one per recalibration the
        adaptive group runs (every ``every``-th of its finalized steps) and
        ``calib_d2h_bytes`` its ring and counts each time; a fleet without
        an adapting unit counts neither, and ``d2h_bytes`` (the payload)
        reads the same in both."""
        window, n_feat, n_per, capacity = 8, 2, 3, 4
        model, params = energy_detector(window, n_feat)
        cfg = AdaptConfig(capacity=capacity, every=every, min_count=3,
                          headroom=3.0)

        def build(adapt):
            return GroupedStreamEngine(
                [ModelGroup("adapt", model, params, n_per,
                            ReconstructionHead(threshold=2.0,
                                               target_fpr=0.2),
                            adapt=adapt),
                 ModelGroup("frozen", model, params, n_per,
                            ReconstructionHead(threshold=2.0))],
                stride=3, async_depth=async_depth, megakernel=megakernel)

        adaptive, frozen = build(cfg), build(None)
        s_pad = -(-n_per // adaptive.n_shards) * adaptive.n_shards
        per = s_pad * capacity * 4 + s_pad * 4
        readings = fleet_readings(2 * n_per, 40, seed=29)
        finalized = recals = 0
        for c in range(41):
            if c < 40:
                got = adaptive.ingest(readings[c])
                frozen.ingest(readings[c])
            else:
                got, _ = adaptive.flush(), frozen.flush()
            if any(v.group == "adapt" for v in got):
                finalized += 1
                recals += finalized % every == 0
            assert adaptive.stats.recalibrations == recals
            assert adaptive.stats.calib_d2h_bytes == recals * per
        assert finalized > 2 * every and recals == finalized // every
        assert frozen.stats.recalibrations == 0
        assert frozen.stats.calib_d2h_bytes == 0
        assert adaptive.stats.d2h_bytes == frozen.stats.d2h_bytes > 0

    def test_group_adapt_validation(self):
        model, params = energy_detector(4, 1)
        with pytest.raises(ValueError, match="group 'g'"):
            GroupedStreamEngine(
                [ModelGroup("g", model, params, 2,
                            ReconstructionHead(threshold=1.0), adapt=True)],
                norm_mean=(0.0,), norm_std=(1.0,), n_features=1)


@pytest.fixture(scope="module")
def drift_workload():
    """Shared drifting-fleet workload for the acceptance tests: calibrated
    energy head + 12000 cycles of the 16-plant seasonal-drift fleet."""
    window, n_feat, stride, n_streams = 50, 2, 10, 16
    model, params = energy_detector(window, n_feat)
    mean = np.asarray(spec.NORM_MEAN, np.float32)
    std = np.asarray(spec.NORM_STD, np.float32)
    calib = fleet_readings(n_streams, 2000, names=["baseline"], seed=11)
    scores = energy_scores(calib, window, stride, mean, std).ravel()
    head = ReconstructionHead(threshold=None).calibrate(scores, TARGET_FPR)
    drift = fleet_readings(n_streams, 12_000, names=["seasonal-drift"],
                           seed=23)
    return dict(window=window, n_feat=n_feat, stride=stride,
                n_streams=n_streams, model=model, params=params,
                mean=tuple(mean), std=tuple(std), head=head, drift=drift)


@pytest.mark.parametrize("scheme", ("REAL", "SINT"))
def test_drift_fpr_acceptance(drift_workload, scheme):
    """THE acceptance run: on a benignly drifting 16-plant fleet the
    adaptive engine holds false positives within 2x of target_fpr while the
    frozen offline threshold exceeds 10x — under float and quantized
    serving."""
    w = drift_workload
    params = w["params"]
    if scheme == "SINT":
        size = w["window"] * w["n_feat"]
        params = quantize.quantize_params(
            w["model"], params, "SINT",
            calibration=[jnp.zeros((size,), jnp.float32)])
    fpr = {}
    for label, adapt in (("fixed", None),
                         ("adaptive", AdaptConfig(capacity=16, min_count=8))):
        eng = StreamEngine(w["model"], params, n_streams=w["n_streams"],
                           n_features=w["n_feat"], window=w["window"],
                           stride=w["stride"], norm_mean=w["mean"],
                           norm_std=w["std"], head=w["head"], adapt=adapt)
        eng.warmup()
        flags = total = 0
        for c in range(w["drift"].shape[0]):
            for v in eng.ingest(w["drift"][c]):
                total += 1
                flags += v.pred != 0
        fpr[label] = flags / total
    assert fpr["adaptive"] <= 2.0 * TARGET_FPR, fpr
    assert fpr["fixed"] >= 10.0 * TARGET_FPR, fpr


def test_drift_adaptation_preserves_attack_detection(drift_workload):
    """A hard TB0 spoof landing on an already-drifted plant: the adaptive
    engine must cut benign-ramp false alarms well below the frozen
    engine's, flood with flags after onset, and FREEZE its live threshold
    there — the attack scores blow past the admission headroom, so not one
    enters the calibration state.  (During the deterministic monotone drift
    ramp the current score leads its own trailing quantile, so the
    ramp-phase rate is physics, not zero — the steady-state claim is the
    FPR acceptance test.)"""
    from repro.sim import AttackEvent, Scenario, registered
    w = drift_workload
    onset = 1300
    sc = Scenario(name="drift-then-tb0spoof",
                  description="hard TB0 spoof on an already-drifted plant",
                  events=(AttackEvent(4, start=onset, intensity=5.0),),
                  drift=ParamDrift({"k_flash": -0.08}, start=300, ramp=800))
    with registered(sc):
        readings = fleet_readings(4, 2600, names=[sc.name], seed=31)
    rates = {}
    for label, adapt in (("fixed", None),
                         ("adaptive", AdaptConfig(capacity=16, min_count=8))):
        eng = StreamEngine(w["model"], w["params"], n_streams=4,
                           n_features=w["n_feat"], window=w["window"],
                           stride=w["stride"], norm_mean=w["mean"],
                           norm_std=w["std"], head=w["head"], adapt=adapt)
        eng.warmup()
        pre, post = [], []
        thr_onset = None
        for c in range(2600):
            for v in eng.ingest(readings[c]):
                if 600 <= v.cycle < onset - w["window"]:
                    pre.append(v.pred != 0)
                elif v.cycle >= onset + w["window"]:
                    post.append(v.pred != 0)
                if v.cycle >= onset and thr_onset is None:
                    thr_onset = v.threshold
        rates[label] = (float(np.mean(pre)), float(np.mean(post)))
        if adapt is not None:
            # zero admissions after onset -> the streaming quantile is
            # recomputed from an unchanged state: frozen, exactly
            assert eng.live_threshold == thr_onset
    (pre_f, post_f), (pre_a, post_a) = rates["fixed"], rates["adaptive"]
    assert post_a >= 0.98, rates             # detection intact
    assert pre_a <= 0.6, rates               # ramp-phase rate bounded
    assert pre_a <= pre_f - 0.2, rates       # and far below the frozen one


class TestAccountingSatellites:
    """The serving-accounting bugfix sweep riding along with adaptation."""

    def test_slice_past_capacity_raises(self):
        r = LatencyReservoir(capacity=8, seed=0)
        for i in range(8):
            r.append(float(i))
        assert r[2:5] == [2.0, 3.0, 4.0]         # exact below capacity
        r.append(8.0)
        with pytest.raises(ValueError, match="reset_latencies"):
            r[2:5]
        assert isinstance(r[3], float)           # scalar indexing still fine

    def test_reset_latencies_swaps_reservoir(self):
        from repro.serving.streams import StreamStats
        stats = StreamStats(steps=0, cycles=0, windows=0, deadline_misses=0,
                            wall_s=0.0,
                            latencies_s=LatencyReservoir(capacity=4))
        for i in range(9):
            stats.latencies_s.append(float(i))
        old = stats.reset_latencies()
        assert old.seen == 9 and len(old) == 4
        assert stats.latencies_s.seen == 0
        assert stats.latencies_s.capacity == 4
        assert stats.latencies_s.seed != old.seed    # fresh replacement draw
        # the bench per-pass pattern: the new reservoir is an exact list
        stats.latencies_s.append(1.5)
        assert list(stats.latencies_s) == [1.5]

    def test_default_reservoir_seeds_diverge(self):
        """Regression: a shared fixed default seed made split engines
        replace the SAME retained indices in lockstep, correlating their
        percentile estimates.  Default seeds now come from a process
        counter, so identical append sequences retain different samples."""
        r1, r2 = LatencyReservoir(capacity=32), LatencyReservoir(capacity=32)
        assert r1.seed != r2.seed
        for i in range(5000):
            r1.append(float(i))
            r2.append(float(i))
        assert list(r1) != list(r2)
        # explicit seeds stay reproducible
        a, b = LatencyReservoir(capacity=32, seed=5), \
            LatencyReservoir(capacity=32, seed=5)
        for i in range(5000):
            a.append(float(i))
            b.append(float(i))
        assert list(a) == list(b)

    def test_stride_longer_than_window_caps_pending(self):
        """Regression: stride > window used to accumulate `stride` pending
        readings host-side (and compile a stride-long block shape) even
        though only the last `window` can ever land in the ring."""
        from test_streams import drive, identity_probe
        window, stride = 3, 50
        model, params = identity_probe(window, 1)
        eng = StreamEngine(model, params, n_streams=2, n_features=1,
                           window=window, stride=stride,
                           norm_mean=(0.0,), norm_std=(1.0,))
        readings = np.arange(153 * 2, dtype=np.float32).reshape(153, 2, 1)
        peak = 0

        orig = eng.ingest

        def spying_ingest(r):
            nonlocal peak
            out = orig(r)
            peak = max(peak, len(eng._pending))
            return out

        eng.ingest = spying_ingest
        batches = drive(eng, readings)
        assert peak <= window                    # host memory capped
        assert [c for c, _ in batches] == [2, 52, 102, 152]
        for cycle, logits in batches:            # parity with naive slicing
            want = readings[cycle - window + 1:cycle + 1]
            want = want.transpose(1, 0, 2).reshape(2, -1)
            np.testing.assert_array_equal(logits, want)
