"""The plain reference of a REAL configuration: the PLC's 32-bit ``REAL``
arithmetic, the paper's float baseline.

It imports nothing of the program.  Each Dense layer is the float32 answer
rounded once, ``f32(f64(x) @ f64(w))``, then ``+ b`` and the activation in
float32; the heads, the windows and the comparison are those of
``bench/reference.py``.  Nothing is requantized, so a window has no
requantize near-ties and none is excused as one; a PRED flip with the
reference within ``BORDER_REL`` of its boundary is excused as for SINT.

``one_pass=True`` is the control: each layer's ``x`` and ``w`` rounded to
bfloat16 and the products summed in float32, bias and activation in float32
-- one MXU pass, which is what the chip does with an f32 dot at its default
precision.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from bench import reference as R


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def mlp(x: np.ndarray, layers: Sequence[dict], acts: Sequence[str], *,
        one_pass: bool = False) -> np.ndarray:
    """REAL Dense stack over rows ``x``.  The product runs in float64 and
    is rounded to float32 once; under ``one_pass`` its operands are first
    rounded to bfloat16 (a product of two bfloat16 numbers is exact in
    float32, so only the order of the float32 sum differs from the chip)."""
    out = np.asarray(x, np.float32)
    for p, act in zip(layers, acts):
        w = np.asarray(p["w"], np.float32)
        if one_pass:
            out, w = bf16(out), bf16(w)
        y = (out.astype(np.float64) @ w.astype(np.float64)).astype(np.float32)
        y = (y + np.asarray(p["b"], np.float32)).astype(np.float32)
        out = R._act(act, y)
    return out


class GroupReference(R.GroupReference):
    """Reference verdicts of one REAL model group; the heads are SINT's."""

    def __init__(self, group: dict, config: dict, layers: Sequence[dict],
                 threshold: Optional[float] = None, one_pass: bool = False):
        super().__init__(group, config, layers, threshold=threshold)
        self.one_pass = one_pass

    def outputs(self, win: np.ndarray, ties: Optional[list] = None
                ) -> np.ndarray:
        # No requantize, so ``ties`` is left empty: nothing is excused.
        return mlp(win[:, :self.k0], self.layers, self.acts,
                   one_pass=self.one_pass)
