"""The benchmark's reference and the comparison that decides ``correct``:
windows built as the serving ring builds them, and a comparison that fails
on one perturbed verdict and excuses only what it can explain."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench import reference as R  # noqa: E402
from bench import reference_real as RR  # noqa: E402
from bench import traffic as TR  # noqa: E402


def config(name="msf_cls_sint"):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as fh:
        return json.load(fh)


def pool(plants=6, seed=3):
    return TR.pool(TR.validate({"plants": plants}), config(), seed)


def test_pool_is_made_from_the_seed():
    assert np.array_equal(pool(seed=2**40 + 1), pool(seed=2**40 + 1))
    assert not np.array_equal(pool(seed=1), pool(seed=2))
    assert pool().dtype == np.float32
    assert pool().shape == (TR.POOL_CYCLES, 6, 2)
    with pytest.raises(ValueError):
        TR.validate({"plants": 0})


def test_windows_follow_the_serving_schedule_and_layout():
    # The repository's own replay of the ring: oldest reading first,
    # features interleaved per reading, normalized per reading.
    from repro.codegen import verify as V
    cfg, p = config(), pool()
    got = R.windows(p, cfg, 209)
    for s in range(p.shape[1]):
        want = V.normalize_windows(
            V.stream_windows(p[:, s, :], 200, 10), cfg["norm_mean"],
            cfg["norm_std"])
        assert np.array_equal(got[s], want[1])      # cycles 199, 209, ...
    with pytest.raises(ValueError):
        R.windows(p, cfg, 198)


def tiny_layers():
    """One 4 -> 2 SINT layer with unit activation scale, so an input of
    2.5 is an exact requantize tie."""
    return [{"qw": np.array([[1, 0], [0, 1], [2, 0], [0, 3]], np.int8),
             "w_scale": np.array([0.5, 0.25], np.float32),
             "x_scale": np.float32(1.0), "b": np.zeros(2, np.float32),
             "w": np.zeros((4, 2), np.float32), "x_absmax": 4.0}]


def tiny_ref():
    group = {"name": "t", "head": "classifier", "widths": [4, 2],
             "activations": ["linear"]}
    cfg = {"n_features": 2}
    return R.GroupReference(group, cfg, tiny_layers())


def test_integer_stack_matches_the_program_arithmetic():
    from repro.codegen.verify import numpy_mlp_ref
    rng = np.random.default_rng(0)
    x = rng.normal(size=(50, 4)).astype(np.float32) * 3
    stack = [({k: v for k, v in tiny_layers()[0].items()
               if k in ("qw", "w_scale", "x_scale", "b")}, "linear")]
    assert np.array_equal(R.mlp(x, tiny_layers(), ["linear"]),
                          numpy_mlp_ref(x, stack))


def program_answers(ref, win):
    pred, tail = ref(win)
    return np.asarray(pred).copy(), np.asarray(tail, np.float64).copy()


def test_one_perturbed_verdict_fails():
    ref = tiny_ref()
    win = np.array([[1.0, 2.0, -1.0, 0.3], [3.0, -2.0, 1.0, 1.0],
                    [0.2, 0.4, 0.6, 0.8]], np.float32)
    pred, tail = program_answers(ref, win)
    t = R.Tally()
    t.add("sound", pred, tail, ref, win)
    assert (t.pred_off, t.tail_rel_err) == (0, 0.0)

    bad = tail.copy()
    bad[1] *= 1.01
    t = R.Tally()
    t.add("tail", pred, bad, ref, win)
    assert t.pred_off == 0 and t.tail_rel_err > 1e-3

    flipped = pred.copy()
    flipped[2] = 1 - flipped[2]
    t = R.Tally()
    t.add("pred", flipped, tail, ref, win)
    assert t.pred_off == 1

    nan = tail.copy()
    nan[0] = np.nan
    t = R.Tally()
    t.add("nan", pred, nan, ref, win)
    assert t.tail_rel_err == np.inf


def test_requantize_near_tie_is_excused_only_when_it_explains():
    ref = tiny_ref()
    win = np.array([[2.5, 1.0, 0.0, 0.0], [1.0, 1.0, 1.0, 1.0]], np.float32)
    # The program rounded the tie 2.5 up to 3 (IEEE rounds it to 2, even).
    chip = ref.verdicts(ref.outputs(win, flips=[(0, 0, 0)]), win)
    pred, tail = np.asarray(chip[0]), np.asarray(chip[1], np.float64)
    t = R.Tally()
    t.add("tie", pred, tail, ref, win)
    assert t.near_ties == 1 and t.pred_off == 0 and t.tail_rel_err == 0.0

    # The same departure on a row with no near-tie is not excused.
    pred2 = pred.copy()
    tail2 = tail.copy()
    tail2[1] = tail2[1] * 0.9
    t = R.Tally()
    t.add("no tie", pred2, tail2, ref, win)
    assert t.near_ties == 1 and t.tail_rel_err > 1e-3


def test_borderline_flip_is_excused_other_flips_are_not():
    group = {"name": "s", "head": "reconstruction", "widths": [4, 4],
             "activations": ["linear"]}
    layers = [{"qw": np.eye(4, dtype=np.int8), "w_scale": np.full(4, 0.5,
               np.float32), "x_scale": np.float32(0.25),
               "b": np.zeros(4, np.float32), "w": np.zeros((4, 4)),
               "x_absmax": 1.0}]
    win = np.array([[0.5, 0.5, 0.5, 0.5], [1.0, 0.0, 0.0, 1.0]], np.float32)
    score = R.GroupReference(group, {"n_features": 2}, layers)(win)[1]
    # A threshold a hair above the first window's score: a flip there is
    # within tolerance of the boundary.
    thr = float(score[0]) * (1 + 1e-5)
    ref = R.GroupReference(group, {"n_features": 2}, layers, threshold=thr)
    pred, tail = program_answers(ref, win)
    pred[0] = 1
    t = R.Tally()
    t.add("border", pred, tail, ref, win)
    assert (t.borderline, t.pred_off) == (1, 0)
    pred[1] = 1 - pred[1]
    t = R.Tally()
    t.add("flip", pred, tail, ref, win)
    assert t.pred_off == 1


def test_control_quantizes_at_int4():
    layers = [{"w": np.array([[0.7, -0.2], [0.1, 0.35]], np.float32),
               "b": np.zeros(2, np.float32), "x_absmax": 2.0}]
    low = R.requantize_layers(layers, R.CONTROL_QMAX)[0]
    assert np.abs(low["qw"]).max() == 7
    assert low["x_scale"] == np.float32(2.0 / 7)


def test_bf16_rounds_to_nearest_even_as_jax_does():
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(size=4096).astype(np.float32) * 100,
                        np.float32([0.0, -0.0, 1.0, 1 + 2**-8, 1 + 3 * 2**-8,
                                    -(1 + 2**-8), 3e-39])])
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(RR.bf16(x), want)


def real_layers():
    rng = np.random.default_rng(7)
    return [{"w": rng.normal(size=(6, 5)).astype(np.float32),
             "b": rng.normal(size=5).astype(np.float32)},
            {"w": rng.normal(size=(5, 3)).astype(np.float32),
             "b": rng.normal(size=3).astype(np.float32)}]


def test_real_stack_is_the_f32_answer_rounded_once():
    x = np.random.default_rng(8).normal(size=(40, 6)).astype(np.float32)
    layers, acts = real_layers(), ["relu", "linear"]
    h = np.float64(x) @ np.float64(layers[0]["w"])
    h = np.maximum(np.float32(h) + layers[0]["b"], np.float32(0))
    y = np.float32(np.float64(h) @ np.float64(layers[1]["w"])) + layers[1]["b"]
    assert np.array_equal(RR.mlp(x, layers, acts), y)
    low = RR.mlp(x, layers, acts, one_pass=True)
    assert not np.array_equal(low, y)
    assert np.allclose(low, y, rtol=0.05, atol=0.05)


def test_real_reference_records_no_near_ties():
    group = {"name": "t", "head": "classifier", "widths": [6, 5, 3],
             "activations": ["relu", "linear"]}
    ref = RR.GroupReference(group, {"n_features": 2}, real_layers())
    ties = []
    # Inputs on requantize half-integers would be near-ties under SINT.
    ref.outputs(np.full((4, 6), 2.5, np.float32), ties=ties)
    assert ties == []


def test_real_reference_matches_the_program_oracle_path():
    """``GroupedStreamEngine`` on the CPU, packed into the megakernel with
    REAL parameters, 4 x 16 plants of the four heads, against the f32
    reference on seeded weights."""
    from bench import harness as H
    from bench import weights as WT
    cfg, plants, seed = config("msf_mixed4_real"), 64, 2**31 + 5
    p = TR.pool(TR.validate({"plants": plants}), cfg, seed)
    calibs = [H.calibration_windows(cfg, g, p, sl) for g, sl in
              zip(cfg["groups"], R.group_slices(cfg, plants))]
    device = WT.make(cfg, seed, calibs)
    host = WT.to_host(device)
    thr = H.score_thresholds(cfg, host, p)
    engine = H.build_engine(cfg, plants, device, thr)
    assert engine.mega_reason is None
    steps = {}
    for c in range(int(cfg["window"]) + 4 * int(cfg["stride"])):
        verdicts = engine.ingest(p[c])
        if verdicts:
            pred, tail, ok = H.step_arrays(verdicts, c, plants)
            assert ok.all()
            steps[c] = (pred, tail)
    assert len(steps) == 5
    tally = R.compare_steps(cfg, p, H.references(cfg, host, thr), steps)
    assert tally.windows == 5 * plants
    assert tally.pred_off == 0 and tally.near_ties == 0
    assert tally.tail_rel_err <= cfg["tail_rel_err"]
