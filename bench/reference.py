"""The plain reference of every SINT configuration in ``bench/configs/``
(REAL's is ``bench/reference_real.py``), and the comparison that decides
``correct``.

It imports nothing of the program.  From the pool of raw readings it builds
each verdict's window itself (normalization, then the last ``window``
readings oldest first, features interleaved per reading), runs the Dense
stack in numpy with the paper's §6.1 integer arithmetic, and applies the
head: a classifier's argmax and softmax probability, or a score head's mean
squared error against its target, computed sequentially in float32 as a
PLC's scan-cycle loop does, compared strictly with the threshold.

The arithmetic is copied from the repository's ``codegen.verify``
(``numpy_mlp_ref``, ``sequential_f32_mse``, ``normalize_windows``) and the
requantize near-tie excusal from its chip smoke script: the chip's float32
divide is not correctly rounded (on a v5e it lands up to 2 ulp from IEEE
division), so a requantize quotient within a few ulp of a half-integer may
round the other way on the chip.  Such a window is excused only when
rounding one, two or three of its near-tie quotients the other way in the
reference reproduces the program's verdict.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence

import numpy as np

NEAR_TIE_ULPS = 4
EXPLAIN_AT_MOST = 4096        # off windows with near-ties traced per run
MAX_FLIPS = 3                 # near-ties rounded the other way at once
MAX_COMBOS = 64               # combinations tried per window
SINT_QMAX = 127
CONTROL_QMAX = 7              # int4: the precision below the stated int8
# A tail further than this from the reference, relative, is more than
# float32 rounding: such a window is traced for near-ties.
EXACT_REL = 1e-6
# A PRED flip is borderline, and a near-tie explains a window, within this
# relative distance: the repository's jit-versus-reference SINT tolerance.
BORDER_REL = 1e-4


# ---------------------------------------------------------------------------
# Windows


def normalize(readings: np.ndarray, config: dict) -> np.ndarray:
    mean = np.asarray(config["norm_mean"], np.float32)
    std = np.asarray(config["norm_std"], np.float32)
    return ((np.asarray(readings, np.float32) - mean) / std).astype(
        np.float32)


def windows(pool: np.ndarray, config: dict, cycle: int,
            plants: Optional[slice] = None) -> np.ndarray:
    """``(plants, window * n_features)`` normalized windows completing at
    scan cycle ``cycle`` (0-based index of the newest reading), from the
    pool replayed cyclically."""
    w = int(config["window"])
    if cycle + 1 < w:
        raise ValueError(f"no window completes at cycle {cycle}")
    idx = np.arange(cycle + 1 - w, cycle + 1) % pool.shape[0]
    raw = pool[idx] if plants is None else pool[idx][:, plants]
    x = normalize(raw, config)                       # (W, N, F)
    return np.ascontiguousarray(x.transpose(1, 0, 2)).reshape(
        x.shape[1], -1)


# ---------------------------------------------------------------------------
# The Dense stack


def requantize_layers(layers: Sequence[dict], qmax: int) -> List[dict]:
    """The same float weights and calibration at another integer width:
    symmetric per-output-channel weights, one activation scale per layer."""
    out = []
    for p in layers:
        w = np.asarray(p["w"], np.float32)
        w_scale = (np.maximum(np.abs(w).max(axis=0), np.float32(1e-12))
                   / np.float32(qmax)).astype(np.float32)
        qw = np.clip(np.rint(w / w_scale), -qmax, qmax).astype(np.int8)
        x_scale = np.float32(max(float(p["x_absmax"]), 1e-12) / qmax)
        out.append({"qw": qw, "w_scale": w_scale, "x_scale": x_scale,
                    "b": np.asarray(p["b"], np.float32)})
    return out


def _act(name: str, y: np.ndarray) -> np.ndarray:
    if name == "relu":
        return np.maximum(y, np.float32(0.0))
    if name == "linear":
        return y
    raise ValueError(f"activation {name!r} has no reference here")


def mlp(x: np.ndarray, layers: Sequence[dict], acts: Sequence[str], *,
        qmax: int = SINT_QMAX, flips=(), ties: Optional[list] = None
        ) -> np.ndarray:
    """§6.1 integer Dense stack over rows ``x``: requantize the input with
    ``round(x / x_scale)`` clipped to ``[-qmax, qmax]``, integer dot
    product, ``f32(acc) * f32(x_scale * w_scale)`` then ``+ b``, each op
    rounded to float32.  The integer dot runs as a float64 matrix product,
    exact for these widths.

    ``flips`` names requantize quotients ``(layer, row, unit)`` to round to
    their other integer neighbour; when ``ties`` is a list, every quotient
    within ``NEAR_TIE_ULPS`` ulp of a half-integer is appended to it as
    ``(layer, row, unit, quotient, ulps)``."""
    out = np.asarray(x, np.float32)
    for li, (p, act) in enumerate(zip(layers, acts)):
        xs = np.float32(p["x_scale"])
        t = (out / xs).astype(np.float32)
        q = np.rint(t)
        if ties is not None:
            half = np.floor(t) + np.float32(0.5)
            ulps = np.abs(t - half) / np.spacing(np.abs(half))
            near = (ulps <= NEAR_TIE_ULPS) & (np.abs(half) < qmax)
            ties += [(li, r, u, t[r, u], ulps[r, u])
                     for r, u in zip(*np.nonzero(near))]
        for fl, r, u in flips:
            if fl == li:
                q[r, u] = 2 * np.floor(t[r, u]) + 1 - q[r, u]
        xq = np.clip(q, -qmax, qmax).astype(np.float64)
        acc = xq @ np.asarray(p["qw"], np.float64)
        s = (xs * np.asarray(p["w_scale"], np.float32)).astype(np.float32)
        y = (acc.astype(np.float32) * s).astype(np.float32)
        y = (y + np.asarray(p["b"], np.float32)).astype(np.float32)
        out = _act(act, y)
    return out


def sequential_f32_mse(y: np.ndarray, target: np.ndarray) -> np.ndarray:
    y = np.asarray(y, np.float32)
    target = np.asarray(target, np.float32)
    acc = np.zeros(y.shape[0], np.float32)
    for i in range(y.shape[1]):
        t = (y[:, i] - target[:, i]).astype(np.float32)
        acc = (acc + t * t).astype(np.float32)
    return (acc / np.float32(y.shape[1])).astype(np.float32)


def softmax(y: np.ndarray) -> np.ndarray:
    z = np.asarray(y, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# One model group


HEADS = ("classifier", "reconstruction", "margin", "forecast")


class GroupReference:
    """Reference verdicts of one model group: ``pred`` and the float
    ``tail`` (the classifier's probability of its class, or the score)."""

    def __init__(self, group: dict, config: dict, layers: Sequence[dict],
                 threshold: Optional[float] = None, qmax: int = SINT_QMAX):
        if group["head"] not in HEADS:
            raise ValueError(f"head {group['head']!r} has no reference")
        self.group = group
        self.kind = group["head"]
        self.acts = list(group["activations"])
        self.k0 = int(group["widths"][0])
        self.n_features = int(config["n_features"])
        self.qmax = qmax
        self.layers = (list(layers) if qmax == SINT_QMAX
                       else requantize_layers(layers, qmax))
        self.threshold = threshold

    def outputs(self, win: np.ndarray, **kw) -> np.ndarray:
        return mlp(win[:, :self.k0], self.layers, self.acts, qmax=self.qmax,
                   **kw)

    def scores(self, y: np.ndarray, win: np.ndarray) -> np.ndarray:
        if self.kind == "reconstruction":
            target = win
        elif self.kind == "margin":
            target = np.zeros_like(y)
        else:                                            # forecast
            target = win[:, -self.n_features:]
        return sequential_f32_mse(y, target)

    def verdicts(self, y: np.ndarray, win: np.ndarray):
        if self.kind == "classifier":
            pred = np.argmax(y, axis=-1)
            return pred, softmax(y)[np.arange(len(y)), pred]
        score = self.scores(y, win).astype(np.float64)
        if self.threshold is None:
            return None, score
        return (score > self.threshold).astype(np.int64), score

    def __call__(self, win: np.ndarray):
        return self.verdicts(self.outputs(win), win)

    @property
    def boundary(self) -> float:
        return 0.5 if self.kind == "classifier" else float(self.threshold)

    def explain(self, row: np.ndarray, ties: Sequence[tuple], pred: int,
                tail: float, limit: float) -> bool:
        """True when rounding one, two or three of the window's requantize
        near-ties ``ties`` (as :func:`mlp` lists them, for this one row)
        the other way makes the reference give ``pred`` and ``tail``
        (within ``limit``, relative)."""
        win = row[None, :]
        combos = itertools.chain.from_iterable(
            itertools.combinations(ties, n)
            for n in range(1, min(len(ties), MAX_FLIPS) + 1))
        for combo in itertools.islice(combos, MAX_COMBOS):
            y = self.outputs(win, flips=[(li, 0, u) for li, _, u, *_ in combo])
            p, t = self.verdicts(y, win)
            if p[0] == pred and abs(float(t[0]) - tail) <= limit * max(
                    abs(float(t[0])), 1e-30):
                return True
        return False


def thresholds(score: np.ndarray, quantile: float) -> float:
    """A threshold flagging about ``1 - quantile`` of the windows, midway in
    the gap between two neighbouring scores."""
    s = np.sort(np.asarray(score, np.float64))
    i = int(quantile * (len(s) - 1))
    return float((s[i] + s[i + 1]) / 2)


# ---------------------------------------------------------------------------
# The comparison


class Tally:
    """Running worst readings over many compared windows."""

    def __init__(self):
        self.over = {1e-6: 0, 1e-5: 0, 1e-4: 0, 1e-3: 0}
        self.budget = EXPLAIN_AT_MOST
        self.windows = 0
        self.with_ties = 0
        self.pred_off = 0
        self.tail_rel_err = 0.0
        self.near_ties = 0
        self.borderline = 0
        self.first_off: Optional[str] = None

    def add(self, label: str, pred: np.ndarray, tail: np.ndarray,
            ref: GroupReference, win: np.ndarray) -> None:
        """Program verdicts ``pred``/``tail`` of the windows ``win`` against
        the reference.

        A window is off where its PRED differs, unless the reference lies
        within ``BORDER_REL`` of its decision boundary (a borderline flip),
        or where its tail departs by more than ``EXACT_REL`` relative.  An off
        window with requantize near-ties is excused where rounding them the
        other way reproduces the program's verdict."""
        ties: list = []
        y = ref.outputs(win, ties=ties)
        ref_pred, ref_tail = ref.verdicts(y, win)
        pred = np.asarray(pred).reshape(-1)
        tail = np.asarray(tail, np.float64).reshape(-1)
        flips = pred != ref_pred
        same = tail
        if ref.kind == "classifier" and flips.any():
            # A binary classifier's tail is the probability of its own
            # class; compare the probability of the reference's class.
            same = np.where(flips, 1.0 - tail, tail)
        diff = np.abs(same - ref_tail)
        rel = diff / np.maximum(np.abs(ref_tail), 1e-30)
        rel[~np.isfinite(rel)] = np.inf           # a NaN answer is off
        tol = BORDER_REL * np.abs(ref_tail)
        border = flips & (np.abs(ref_tail - ref.boundary) <= tol)
        off = (flips & ~border) | (rel > EXACT_REL)
        by_row: Dict[int, list] = {}
        for t in ties:
            by_row.setdefault(int(t[1]), []).append(t)
        excused = np.zeros_like(off)
        for i in np.flatnonzero(off):
            if i in by_row and self.budget > 0:
                self.budget -= 1
                excused[i] = ref.explain(win[i], by_row[i], int(pred[i]),
                                         float(tail[i]), BORDER_REL)
        keep = ~excused
        self.windows += pred.size
        self.with_ties += len(by_row)
        self.near_ties += int(excused.sum())
        self.borderline += int(border.sum())
        self.pred_off += int((flips & ~border & keep).sum())
        if keep.any():
            self.tail_rel_err = max(self.tail_rel_err,
                                    float(rel[keep].max()))
        for k in self.over:
            self.over[k] += int((rel[keep] > k).sum())
        bad = np.flatnonzero(off & keep)
        if bad.size and self.first_off is None:
            i = int(bad[0])
            self.first_off = (f"{label} window {i}: program pred {pred[i]} "
                              f"tail {float(tail[i])!r}, reference pred "
                              f"{ref_pred[i]} tail {float(ref_tail[i])!r}, "
                              f"{len(by_row.get(i, []))} near-ties")


def group_slices(config: dict, plants: int) -> List[slice]:
    """Each group's contiguous slice of the fleet (equal shares)."""
    g = len(config["groups"])
    if plants % g:
        raise ValueError(f"{plants} plants do not split into {g} groups")
    n = plants // g
    return [slice(i * n, (i + 1) * n) for i in range(g)]


def compare_steps(config: dict, pool: np.ndarray,
                  refs: Sequence[GroupReference],
                  steps: Dict[int, tuple]) -> Tally:
    """``steps`` maps a verdict cycle to the program's ``(pred, tail)``
    arrays over the whole fleet, in stream order."""
    tally = Tally()
    plants = pool.shape[1]
    for cycle in sorted(steps):
        pred, tail = steps[cycle]
        win = windows(pool, config, cycle)
        for sl, ref in zip(group_slices(config, plants), refs):
            tally.add(f"cycle {cycle} group {ref.group['name']}",
                      pred[sl], tail[sl], ref, win[sl])
    return tally
