"""Operations and bytes of both kernels against hand counts, and the peak
table."""

import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)
from bench import work  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as fh:
        return json.load(fh)


def test_fused_mlp_work_by_hand():
    # 400-64-32-16-2 over 4096 plants.
    macs = 400 * 64 + 64 * 32 + 32 * 16 + 16 * 2
    assert macs == 28192
    ops, nbytes = work.fused_mlp(config("msf_cls_sint"), 4096)
    assert ops == 2 * 4096 * 28192
    weights = macs                                   # int8
    biases = (64 + 32 + 16 + 2) * 4
    # Per output channel, and one activation scale per layer.
    scales = (64 + 32 + 16 + 2) * 4 + 4 * 4
    windows_in = 4096 * 400 * 4
    logits_out = 4096 * 2 * 4
    assert nbytes == windows_in + weights + biases + scales + logits_out


def test_grouped_fused_mlp_work_by_hand():
    m = 1024
    layers = {
        "mlp": [(400, 64), (64, 32), (32, 16), (16, 2)],
        "ae": [(400, 64), (64, 16), (16, 64), (64, 400)],
        "margin": [(400, 64), (64, 32), (32, 16)],
        "forecast": [(398, 64), (64, 32), (32, 2)],
    }
    outs = {"mlp": 2, "ae": 1, "margin": 1, "forecast": 1}
    ops = nbytes = 0
    for name, dims in layers.items():
        ops += 2 * m * sum(k * n for k, n in dims)
        nbytes += (m * 400 * 4 + sum(k * n for k, n in dims)
                   + sum(n * 4 * 2 + 4 for _, n in dims) + m * outs[name] * 4)
    got = work.grouped_fused_mlp(config("msf_mixed4_sint"), m)
    assert got == (ops, nbytes)


def test_roofline_takes_the_larger_bound():
    row = work.peaks("TPU v5 lite")
    ops, nbytes = work.fused_mlp(config("msf_cls_sint"), 4096)
    least = work.roofline_s(ops, nbytes, row, "SINT")
    assert least == pytest.approx(nbytes / 819e9)    # memory-bound
    assert least > ops / 393e12


def test_peak_table_names_its_source_and_raises_for_an_unknown_kind():
    row = work.peaks("TPU v5 lite")
    assert row["int8_ops_per_s"] == 393e12
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(ValueError, match="not in the peak table"):
        work.peaks("cpu")


def test_grouped_fused_mlp_work_by_hand_under_real():
    # The REAL fleet: four-byte weights, biases, no scales; the same
    # operations, held to the bf16 peak (an upper bound on f32 at HIGHEST).
    cfg = config("msf_mixed4_real")
    m = 1024
    weights = 400 * 64 + 64 * 32 + 32 * 16 + 16 * 2 \
        + 400 * 64 + 64 * 16 + 16 * 64 + 64 * 400 \
        + 400 * 64 + 64 * 32 + 32 * 16 + 398 * 64 + 64 * 32 + 32 * 2
    assert weights == 137184
    biases = (64 + 32 + 16 + 2) + (64 + 16 + 64 + 400) + (64 + 32 + 16) \
        + (64 + 32 + 2)
    ops, nbytes = work.grouped_fused_mlp(cfg, m)
    assert ops == 2 * m * weights
    assert nbytes == (4 * m * 400 * 4 + weights * 4 + biases * 4
                      + m * (2 + 1 + 1 + 1) * 4)
    row = work.peaks("TPU v5 lite")
    assert work.peak_ops(row, "REAL") == 197e12
    assert work.roofline_s(ops, nbytes, row, "REAL") == pytest.approx(
        nbytes / 819e9)
