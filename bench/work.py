"""Operations and bytes each kernel's work needs, and the chip's peaks.

The work is reckoned from the configuration's widths and the real plant
count, not from the kernel's padded operands, so it does not change when a
later version of a kernel pads, tiles or packs differently.

* Operations: ``2 * M * sum(k * n)`` over the Dense layers (one multiply
  and one add per weight per window).
* Bytes: the float32 windows read (``window * n_features`` per plant), the
  weights (one byte each under SINT, four under REAL), the float32
  per-channel weight scales, activation scale and biases, and the float32
  outputs written (the logits of a classifier, one score of a score head).
"""

from __future__ import annotations

import json
import os
from typing import Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(HERE, "peaks.json")
F32 = 4


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(path) as fh:
        table = json.load(fh)
    try:
        row = dict(table["kinds"][device_kind])
    except KeyError:
        raise ValueError(f"device kind {device_kind!r} is not in the peak "
                         f"table {path}") from None
    row["source"] = table["source"]
    return row


def peak_ops(row: dict, scheme: str) -> float:
    """The compute peak a scheme's matrix products run at."""
    return row["int8_ops_per_s" if scheme == "SINT" else "bf16_flops_per_s"]


def layer_dims(group: dict) -> list:
    w = group["widths"]
    return [(int(w[i]), int(w[i + 1])) for i in range(len(w) - 1)]


def ops_per_window(group: dict) -> int:
    return 2 * sum(k * n for k, n in layer_dims(group))


def weight_bytes(group: dict, scheme: str) -> int:
    per_weight = 1 if scheme == "SINT" else F32
    total = 0
    for k, n in layer_dims(group):
        total += k * n * per_weight + n * F32              # weights, bias
        if scheme != "REAL":
            total += n * F32 + F32                         # scales
    return total


def out_width(group: dict) -> int:
    return int(group["widths"][-1]) if group["head"] == "classifier" else 1


def group_work(config: dict, group: dict, m: int) -> Tuple[int, int]:
    """(operations, bytes) of one group's verdict step over ``m`` plants."""
    win = int(config["window"]) * int(config["n_features"])
    ops = m * ops_per_window(group)
    nbytes = (m * win * F32 + weight_bytes(group, config["scheme"])
              + m * out_width(group) * F32)
    return ops, nbytes


def fused_mlp(config: dict, m: int) -> Tuple[int, int]:
    """One single-model fused kernel call over ``m`` plants."""
    (group,) = config["groups"]
    return group_work(config, group, m)


def grouped_fused_mlp(config: dict, m_per_group: int) -> Tuple[int, int]:
    """One grouped kernel call over every group, ``m_per_group`` plants
    each."""
    ops = nbytes = 0
    for group in config["groups"]:
        o, b = group_work(config, group, m_per_group)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def roofline_s(ops: int, nbytes: int, row: dict, scheme: str) -> float:
    """The least time the chip could take for the work."""
    return max(ops / peak_ops(row, scheme), nbytes / row["hbm_bytes_per_s"])


def model_ops_per_window(config: dict) -> float:
    """Mean operations per verdict over the fleet (groups share it
    equally)."""
    groups = config["groups"]
    return sum(ops_per_window(g) for g in groups) / len(groups)
