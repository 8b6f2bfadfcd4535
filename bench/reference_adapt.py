"""The plain reference of a configuration that sets ``adapt``: score heads
whose thresholds the fleet recalibrates at every verdict step.

It imports nothing of the program.  The policy is the one the
configuration writes out (``capacity``, ``every``, ``min_count``,
``headroom``), applied to every score group; a classifier never adapts.
Each score group keeps a ``(plants, capacity)`` float32 ring of admitted
scores and a count per plant.  At verdict step ``t`` (the first is the one
whose windows close at cycle ``window - 1``):

* a plant's reference score ``s`` is admitted where ``f32(s) <=
  f32(headroom) * f32(thr_{t-1})``, ``thr_0`` the offline threshold; it goes
  into slot ``count % capacity`` and its count goes up by one;
* every ``every``-th step, once at least ``min_count`` scores have been
  admitted across the group, ``thr_t`` is the ``method="higher"`` quantile at
  ``1 - target_fpr`` of the valid slots pooled (slot ``j`` of a plant is
  valid where ``j < count``), in float64; otherwise ``thr_t = thr_{t-1}``;
* the step's verdicts are ``score > thr_t``: the threshold already holds
  that step's own scores.

The scores are the reference's own (:mod:`bench.reference` or
:mod:`bench.reference_real`, by scheme), computed once for each distinct
window the replayed pool closes.
"""

from __future__ import annotations

import copy
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import reference as R

KEYS = ("capacity", "every", "min_count", "headroom")
# Windows scored in one pass of the reference (rows = this x plants).
CYCLES_PER_BLOCK = 16
# Two thresholds this close, relative, are of one rank of the pooled scores
# (ten times the program's float32 score gap, ``EXACT_REL``).
RANK_REL = 10 * R.EXACT_REL


def policy(config: dict) -> Optional[dict]:
    """The configuration's ``adapt`` policy, or None where it sets none;
    raises ``ValueError`` where a key is unknown or missing, or where no
    group has a score head to adapt."""
    adapt = config.get("adapt")
    if adapt is None:
        return None
    name = config.get("name")
    if not isinstance(adapt, dict) or set(adapt) != set(KEYS):
        raise ValueError(f"config {name!r}: adapt must give exactly "
                         f"{list(KEYS)}, got {adapt!r}")
    if all(g["head"] == "classifier" for g in config["groups"]):
        raise ValueError(f"config {name!r}: adapt needs a score head; every "
                         "group is a classifier")
    if "thr_rel_err" not in config:
        raise ValueError(f"config {name!r}: adapt needs the limit "
                         "thr_rel_err")
    return {"capacity": int(adapt["capacity"]), "every": int(adapt["every"]),
            "min_count": int(adapt["min_count"]),
            "headroom": float(adapt["headroom"])}


class Recalibration:
    """One score group's rolling calibration state and live threshold."""

    def __init__(self, plants: int, threshold: float, target_fpr: float, *,
                 capacity: int, every: int, min_count: int,
                 headroom: float):
        self.ring = np.zeros((plants, capacity), np.float32)
        self.counts = np.zeros(plants, np.int64)
        self.threshold = float(threshold)
        self.quantile = 1.0 - target_fpr
        self.every, self.min_count = every, min_count
        self.headroom = np.float32(headroom)
        self.steps = 0

    def step(self, scores: np.ndarray) -> float:
        """Admit one verdict step's scores; returns the step's threshold."""
        s = np.asarray(scores, np.float32)
        cap = self.ring.shape[1]
        rows = np.flatnonzero(s <= self.headroom * np.float32(self.threshold))
        self.ring[rows, self.counts[rows] % cap] = s[rows]
        self.counts[rows] += 1
        self.steps += 1
        if self.steps % self.every == 0:
            pooled = self.pooled()
            if pooled.size >= max(self.min_count, 1):
                self.threshold = float(np.quantile(
                    pooled.astype(np.float64), self.quantile,
                    method="higher"))
        return self.threshold

    def pooled(self) -> np.ndarray:
        """The valid slots of every plant's ring."""
        cap = self.ring.shape[1]
        if self.counts.min() >= cap:
            return self.ring.ravel()
        return self.ring[np.arange(cap)[None, :] < self.counts[:, None]]


def group_scores(config: dict, pool: np.ndarray, refs: Sequence,
                 cycles: Sequence[int]) -> Dict[int, List]:
    """Per distinct window the cycles close (keyed by cycle modulo the
    pool), each group's reference scores (None for a classifier).  The
    windows are :func:`bench.reference.windows`'s, cut from the pool
    normalized once (normalization is per reading)."""
    n_pool, w = pool.shape[0], int(config["window"])
    keys = sorted({c % n_pool for c in cycles})
    norm = R.normalize(pool, config)
    # (plants, pool + window - 1, features): a window that wraps round the
    # pool is one slice.
    by_plant = np.ascontiguousarray(
        np.concatenate([norm, norm[:w - 1]]).transpose(1, 0, 2))
    slices = R.group_slices(config, pool.shape[1])
    out: Dict[int, List] = {k: [None] * len(refs) for k in keys}
    for i in range(0, len(keys), CYCLES_PER_BLOCK):
        block = keys[i:i + CYCLES_PER_BLOCK]
        starts = [(k + 1 - w) % n_pool for k in block]
        for gi, (sl, ref) in enumerate(zip(slices, refs)):
            if ref.kind == "classifier":
                continue
            plants = by_plant[sl]
            win = np.concatenate([plants[:, a:a + w].reshape(len(plants), -1)
                                  for a in starts])
            score = ref.scores(ref.outputs(win), win)
            for k, part in zip(block, np.split(score, len(block))):
                out[k][gi] = part
    return out


def replay(config: dict, pool: np.ndarray, refs: Sequence,
           thresholds: Sequence[Optional[float]], target_fpr: float,
           last: int, keep=()) -> tuple:
    """Each verdict cycle's reference thresholds, one per group (None for a
    classifier), from the first verdict step through cycle ``last``;
    ``thresholds`` are the offline ones, ``thr_0``.  Also, at the cycles
    in ``keep``, each score group's pooled scores, sorted."""
    adapt = policy(config)
    slices = R.group_slices(config, pool.shape[1])
    units = [None if thr is None else
             Recalibration(sl.stop - sl.start, thr, target_fpr, **adapt)
             for sl, thr in zip(slices, thresholds)]
    cycles = range(int(config["window"]) - 1, last + 1,
                   int(config["stride"]))
    scores = group_scores(config, pool, refs, cycles)
    out, pools = {}, {}
    for c in cycles:
        out[c] = [None if u is None else u.step(s)
                  for u, s in zip(units, scores[c % pool.shape[0]])]
        if c in keep:
            pools[c] = [None if u is None else
                        np.sort(u.pooled().astype(np.float64)) for u in units]
    return out, pools


def at(ref, threshold: Optional[float]):
    """``ref`` judging at ``threshold`` (``ref`` itself where None)."""
    if threshold is None:
        return ref
    out = copy.copy(ref)
    out.threshold = threshold
    return out


def apart(ref, threshold: float, reported: float, pred: np.ndarray,
          win: np.ndarray) -> tuple:
    """``pred`` with each verdict that the reported threshold and the
    reference's decide apart on the reference's score set to the
    reference's, and how many of them the program gave otherwise.  Such a
    window lies between the two thresholds, and their gap is
    ``thr_rel_err``'s to judge: a threshold one rank of the pooled scores
    from the reference's flips the step's own score at that rank."""
    if not np.isfinite(reported):
        return pred, 0
    s = ref.scores(ref.outputs(win), win).astype(np.float64)
    ref_pred = (s > threshold).astype(pred.dtype)
    moved = ref_pred != (s > reported)
    return np.where(moved, ref_pred, pred), int((moved & (pred != ref_pred))
                                               .sum())


def compare_steps(config: dict, pool: np.ndarray, refs: Sequence,
                  steps: Dict[int, tuple],
                  thresholds: Dict[int, List[Optional[float]]],
                  reported: Dict[int, List[Optional[float]]]) -> tuple:
    """:func:`bench.reference.compare_steps` with each score group judged
    at its reference threshold of that step, verdicts the two thresholds
    decide apart excused (:func:`apart`); returns the tally and the number
    excused."""
    tally, excused = R.Tally(), 0
    slices = R.group_slices(config, pool.shape[1])
    for cycle in sorted(steps):
        pred, tail = steps[cycle]
        win = R.windows(pool, config, cycle)
        for sl, ref, thr, got in zip(slices, refs, thresholds[cycle],
                                     reported[cycle]):
            p = pred[sl]
            if thr is not None:
                p, n = apart(ref, thr, got, p, win[sl])
                excused += n
            tally.add(f"cycle {cycle} group {ref.group['name']}",
                      p, tail[sl], at(ref, thr), win[sl])
    return tally, excused


def rank_gap(reported: Dict[int, List[Optional[float]]],
             reference: Dict[int, List[Optional[float]]],
             pools: Dict[int, List]) -> int:
    """How many ranks of the pooled scores, at most over the steps of
    ``reported`` and the score groups, a reported threshold lies from the
    reference's: a threshold's rank counts the scores at or below it, a
    float32 rounding above (``RANK_REL``) included."""
    worst = 0
    for cycle, got in reported.items():
        for g, r, p in zip(got, reference[cycle], pools[cycle]):
            if r is None or g is None or not np.isfinite(g):
                continue
            a, b = np.searchsorted(p, np.array([g, r]) * (1 + RANK_REL),
                                   "right")
            worst = max(worst, abs(int(a) - int(b)))
    return worst


def thr_rel_err(reported: Dict[int, List[Optional[float]]],
                reference: Dict[int, List[Optional[float]]]) -> float:
    """The largest relative gap, over the steps of ``reported`` and the
    score groups, between a reported threshold and the reference's; a
    missing or non-finite one reads as infinite."""
    worst = 0.0
    for cycle, got in reported.items():
        for g, r in zip(got, reference[cycle]):
            if r is None:
                continue
            gap = (abs(g - r) / abs(r) if g is not None and np.isfinite(g)
                   else np.inf)
            worst = max(worst, gap)
    return worst


def controls(config: dict, reference: Dict[int, List[Optional[float]]],
             thresholds: Sequence[Optional[float]], cycles) -> dict:
    """The thresholds two controls would report at ``cycles``: *offline*
    keeps ``thr_0`` (no recalibration), *stale* reports ``thr_{t-1}``
    (recalibration one step late)."""
    s = int(config["stride"])
    first = int(config["window"]) - 1
    return {
        "offline": {c: list(thresholds) for c in cycles},
        "stale": {c: list(thresholds) if c == first else reference[c - s]
                  for c in cycles},
    }
