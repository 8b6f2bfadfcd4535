"""Detector heads: what a detection workload computes *after* the MLP body.

The §7 case study hardwired one head — a 2-class softmax classifier (CE loss,
argmax verdict) — into three layers at once: training (`sim.detector`),
serving (`serving.streams`' inlined softmax/argmax epilogue) and the fused
kernel contract.  The dominant ICS-defense pattern is *unsupervised* anomaly
detection (train on benign traffic only, flag by an anomaly score), which
shares the whole MLP body / fused-kernel / fleet-serving machinery and differs
only in the head.  This module makes the head a first-class object:

* :class:`ClassifierHead` — supervised: sparse-CE loss over labeled windows,
  verdict = argmax class with its softmax probability.
* :class:`ReconstructionHead` — unsupervised: MSE loss on benign windows
  only, anomaly score = per-window mean squared reconstruction error.
* :class:`MarginHead` — unsupervised one-class margin (Deep-SVDD-style):
  the body embeds a window near a fixed benign ``center``; the anomaly
  score is the mean squared distance of the embedding from the center, and
  the calibrated threshold is the margin radius.
* :class:`ForecastHead` — unsupervised next-step prediction: the body maps
  the window's first ``W - 1`` readings to a forecast of the ``W``-th; the
  anomaly score is the squared forecast error against the reading that
  actually arrived.  The head owns the window/model-width asymmetry: it
  asks the engine for one extra ring reading (:meth:`ring_window`) and
  slices the model's input off the front of the window (:meth:`prepare`).

A head contributes:

1. ``loss(outputs, x, y)`` — the training objective (``sim.detector``'s
   head-generic Adam loop calls it on batched model outputs).
2. ``prepare(win)`` — the **device-side** model-input view of the window
   (identity for every head except forecast), applied before the forward
   both in training and inside the engine's jitted step.
3. ``epilogue(win, out)`` — the **device-side** verdict reduction, traced
   into the engine's jitted step (sharded and unsharded): score heads reduce
   the (S, out) model outputs to an (S, 1) score **on device**, so the host
   never materializes fleet x out_width payloads.
4. ``host_verdicts(out)`` — the host-side epilogue turning the step output
   into per-stream ``(pred, prob, score, threshold)`` verdict fields.
5. ``ring_window(input_size, n_features)`` / ``model_input_size(window,
   n_features)`` — the window-geometry contract between the serving ring
   and the model input (identity-coupled for every head except forecast).

Heads are stream-local (row-wise), so the epilogue rides through
``shard_map`` untouched — the fleet mesh sees zero new collectives, and a
heterogeneous model-group fleet (``serving.grouped``) mixes heads freely.

**Threshold calibration** (every :class:`ScoreHead`) uses the *conservative*
empirical quantile (``np.quantile(..., method="higher")``): the cutoff is an
actual calibration score at or above the interpolated position, so the
realized false-positive rate **on the calibration set itself** never exceeds
``target_fpr``.  (The default linear interpolation can place the cutoff
*between* order statistics on small calibration sets, letting the empirical
FPR overshoot the target it was calibrated to.)

**Streaming recalibration** (online drift adaptation): real plants drift —
sensor recalibration, seasonal load, wear — and a threshold calibrated once,
offline, turns a calibrated FPR into an alarm flood as the benign score
distribution creeps.  Every :class:`ScoreHead` therefore also owns the
*streaming* half of its calibration contract:

* :meth:`calib_state` — a per-stream rolling ring of recently admitted
  benign-looking scores plus per-stream admission counts, shaped for the
  serving engines' device arenas (row-local, so it shards with the
  ``("data",)`` fleet mesh with zero new collectives);
* :meth:`calib_update` — the **device-side** state transition, traced into
  the engines' donated jitted step: scores at most ``headroom`` times the
  live threshold are written into their stream's ring (scores beyond it are
  treated as attacks and never poison the calibration state; the headroom
  is what lets *gradual* benign drift through the gate even when it crosses
  the threshold itself);
* :meth:`streaming_threshold` — the **host-side** re-host of
  ``recalibrate_threshold``'s score-then-quantile sequence onto that state:
  :func:`conservative_quantile` of the pooled valid ring scores at the
  head's recorded ``target_fpr``.  It selects that order statistic from
  the f32 pool with ``np.partition`` at the index ``np.quantile``'s
  ``method="higher"`` takes, so it returns the same value bit for bit
  without a float64 copy or a sort (and, once every stream's ring is full,
  without the valid-slot gather).

The engines keep a *live* threshold (seeded by the offline-calibrated one)
that tracks the streaming quantile; ``Verdict.threshold`` reports the live
value.  :meth:`calibrate` records ``target_fpr`` on the head so streaming
recalibration chases the same operating point the offline calibration chose.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def softmax_np(logits: np.ndarray) -> np.ndarray:
    """Batched-stable host softmax: subtracts the per-row max along the last
    axis before exponentiating, so rows of extreme logits (|z| ~ 1e4, the
    saturated-detector regime) never overflow ``exp``."""
    z = logits - logits.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def conservative_quantile(scores: np.ndarray, target_fpr: float) -> float:
    """The ``(1 - target_fpr)`` empirical quantile, rounded UP to an actual
    order statistic (``method="higher"``), so ``mean(scores > q)`` — the
    realized FPR on the calibration scores themselves — is ≤ ``target_fpr``
    even on small calibration sets."""
    return float(np.quantile(np.asarray(scores, np.float64), 1.0 - target_fpr,
                             method="higher"))


@functools.lru_cache(maxsize=None)
def _higher_rank(n: int, q: float) -> int:
    """The sorted-order index of the value ``np.quantile(scores, q,
    method="higher")`` returns for ``n`` scores: the same quantile of ``0,
    1, ..., n - 1``, so the index arithmetic is numpy's own."""
    return int(np.quantile(np.arange(n, dtype=np.float64), q,
                           method="higher"))


class DetectorHead:
    """Base: the loss / device epilogue / host verdict of one workload."""

    name: str = "?"

    def loss(self, outputs: jax.Array, x: jax.Array,
             y: Optional[jax.Array]) -> jax.Array:
        """Training objective over batched model outputs."""
        raise NotImplementedError

    def metric(self, outputs: jax.Array, x: jax.Array,
               y: Optional[jax.Array]) -> jax.Array:
        """Scalar model-selection metric — greater is better (checkpoint-best
        and early stopping in the head-generic trainer key on it)."""
        raise NotImplementedError

    def validate(self, input_size: int, n_outputs: int) -> None:
        """Raise early (engine construction) if the model can't carry this
        head; the default accepts any output width."""

    def ring_window(self, input_size: int, n_features: int) -> int:
        """Ring readings per verdict window for a model of ``input_size``.
        Default: the window IS the model input (``input_size / n_features``
        readings); the forecast head asks for one extra reading (the
        prediction target)."""
        if input_size % n_features:
            raise ValueError(
                f"model input {input_size} is not a whole number of "
                f"{n_features}-feature readings")
        return input_size // n_features

    def model_input_size(self, window: int, n_features: int) -> int:
        """Model input width for a ``window``-reading ring — the inverse of
        :meth:`ring_window`, used to validate an explicit ``window=``."""
        return window * n_features

    def prepare(self, win: jax.Array) -> jax.Array:
        """Device-side model-input view of the batched ``(S, window x F)``
        window; traced into the jitted step *and* the training loop.  The
        default feeds the whole window."""
        return win

    def epilogue(self, win: jax.Array, out: jax.Array) -> jax.Array:
        """Device-side reduction from raw model outputs to the per-stream
        verdict payload; traced into the engine's jitted detector step."""
        raise NotImplementedError

    def kernel_epilogue(self) -> Optional[Tuple[str, str]]:
        """The head's in-kernel epilogue spec for the grouped megakernel
        (``serving/core.py`` single-dispatch fleets), or None when the
        epilogue cannot run in-kernel and the engine must fall back to
        per-group dispatch.  The spec is ``(payload, target)``:
        ``("logits", "none")`` passes the final activations through;
        ``("mse", "window" | "tail" | "center")`` reduces to the mean
        squared error against the whole window, its newest reading, or a
        fixed center row.  The default is None — custom heads opt in."""
        return None

    def host_verdicts(self, out: np.ndarray,
                      threshold: Optional[float] = None) -> Tuple[
            np.ndarray, Optional[np.ndarray], Optional[np.ndarray],
            Optional[float]]:
        """Step output -> (pred, prob|None, score|None, threshold|None),
        each an array over streams (threshold is one float for the fleet).
        ``threshold`` overrides the head's own calibrated cutoff — the
        engines pass their *live* (streaming-recalibrated) threshold here,
        so verdicts track drift while the head stays frozen."""
        raise NotImplementedError

    # -- IEC 61131-3 Structured Text export (repro.codegen.st) --------------
    #
    # The ST exporter asks the head for the *verdict epilogue* of the emitted
    # FUNCTION_BLOCK: the statements that turn the model-output array into
    # the PLC-side verdict variables, mirroring epilogue/host_verdicts.  The
    # writer is duck-typed (codegen.st.STWriter) so this module never imports
    # the codegen package; ``ctx`` is a codegen.st.STContext carrying the
    # array names and widths of the surrounding block.

    def st_verdict_outputs(self) -> Tuple[str, ...]:
        """Names of the VAR_OUTPUTs the head's ST epilogue produces, in
        Verdict-field order — the verification harness compares exactly
        these against the engine's verdicts."""
        raise NotImplementedError(
            f"{type(self).__name__} has no Structured Text export epilogue")

    def st_epilogue(self, w, ctx) -> None:
        """Write the verdict epilogue into an ST writer: declare the verdict
        VAR_OUTPUTs and emit the statements computing them from the model
        output array ``ctx.y`` (and the model-input view ``ctx.x``)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no Structured Text export epilogue")


@dataclasses.dataclass(frozen=True)
class ClassifierHead(DetectorHead):
    """Supervised classifier: CE loss, argmax verdict (§7's head)."""

    name: str = "classifier"

    def loss(self, outputs, x, y):
        logz = jax.scipy.special.logsumexp(outputs, axis=-1)
        gold = jnp.take_along_axis(outputs, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def metric(self, outputs, x, y):
        return jnp.mean(jnp.argmax(outputs, axis=-1) == y)

    def epilogue(self, win, out):
        return out                      # the logits ARE the verdict payload

    def kernel_epilogue(self):
        # Pass-through logits; a final-layer softmax is masked in-kernel to
        # the group's true class count.
        return ("logits", "none")

    def host_verdicts(self, out, threshold=None):
        pred = out.argmax(axis=-1)
        prob = softmax_np(out)[np.arange(len(out)), pred]
        return pred.astype(np.int64), prob, None, None

    def st_verdict_outputs(self):
        return ("PRED", "CONF")

    def st_epilogue(self, w, ctx):
        # Argmax with strict `>` keeps the FIRST maximum — np.argmax's tie
        # rule — and the softmax probability of the argmax class collapses to
        # 1/sum(exp(y_i - max)): exp(0) = 1.0 exactly, so the winning term
        # needs no batch-varying index, and the sequential f32 sum matches
        # softmax_np for the few-class heads this exports.
        w.output("PRED", "DINT")
        w.output("CONF", "REAL")
        w.var("I", "DINT")
        w.var("BEST", "REAL")
        w.var("ESUM", "REAL")
        w.comment("verdict: argmax class + softmax confidence of that class")
        w.line(f"BEST := {ctx.y}[0];")
        w.line("PRED := 0;")
        w.line(f"FOR I := 1 TO {ctx.n_outputs - 1} DO")
        w.line(f"    IF {ctx.y}[I] > BEST THEN")
        w.line(f"        BEST := {ctx.y}[I];")
        w.line("        PRED := I;")
        w.line("    END_IF;")
        w.line("END_FOR;")
        w.line("ESUM := 0.0;")
        w.line(f"FOR I := 0 TO {ctx.n_outputs - 1} DO")
        w.line(f"    ESUM := ESUM + EXP({ctx.y}[I] - BEST);")
        w.line("END_FOR;")
        w.line("CONF := 1.0 / ESUM;")


@dataclasses.dataclass(frozen=True)
class ScoreHead(DetectorHead):
    """Base for score-vs-threshold heads (every unsupervised workload).

    Subclasses define :meth:`batch_scores` — per-window anomaly scores from
    batched model outputs — and inherit the whole training objective
    (mean score on benign windows), device epilogue ((S, 1) on-device score
    reduction), host verdict (strict ``score > threshold``) and conservative
    FPR calibration.

    ``threshold`` is None until calibrated (:meth:`calibrate` /
    the ``sim.detector`` trainers); serving requires it.  ``target_fpr`` is
    recorded by :meth:`calibrate` so streaming recalibration
    (:meth:`streaming_threshold`) chases the same false-positive operating
    point the offline calibration chose.
    """

    threshold: Optional[float] = None
    target_fpr: Optional[float] = None
    name: str = "score"

    def batch_scores(self, outputs: jax.Array, x: jax.Array) -> jax.Array:
        """Per-window anomaly scores ``(B,)`` from batched model outputs
        (``x`` is the full window batch, pre-:meth:`prepare`)."""
        raise NotImplementedError

    def loss(self, outputs, x, y):
        return jnp.mean(self.batch_scores(outputs, x))

    def metric(self, outputs, x, y):
        # Lower anomaly score on benign data is better; the trainer maximizes.
        return -self.loss(outputs, x, y)

    def validate(self, input_size: int, n_outputs: int) -> None:
        if self.threshold is None:
            raise ValueError(
                f"{type(self).__name__} has no threshold; calibrate it on "
                "held-out normal traces first (head.calibrate / the "
                "sim.detector trainers)")

    def epilogue(self, win, out):
        # On-device score reduction: (S, out) model outputs -> (S, 1) scores
        # before anything leaves the device, so a sharded fleet ships one
        # float per stream to the host rather than the full payload.
        return self.batch_scores(out, win)[:, None]

    def calibrate(self, normal_scores: np.ndarray,
                  target_fpr: float) -> "ScoreHead":
        """A new head whose threshold realizes at most ``target_fpr`` false
        positives on the given held-out *normal* window scores (conservative
        order-statistic cutoff — module docstring)."""
        if not 0.0 < target_fpr < 1.0:
            raise ValueError(f"target_fpr must be in (0, 1), got {target_fpr}")
        scores = np.asarray(normal_scores, np.float64)
        if scores.size == 0:
            raise ValueError("cannot calibrate on zero normal scores")
        return dataclasses.replace(
            self, threshold=conservative_quantile(scores, target_fpr),
            target_fpr=target_fpr)

    def host_verdicts(self, out, threshold=None):
        thr = self.threshold if threshold is None else threshold
        if thr is None:
            raise ValueError(
                f"{type(self).__name__} has no threshold; calibrate it on "
                "held-out normal traces first (head.calibrate / the "
                "sim.detector trainers)")
        score = out[:, 0] if out.ndim == 2 else out
        pred = (score > thr).astype(np.int64)
        return pred, None, score, thr

    def st_verdict_outputs(self):
        return ("PRED", "SCORE", "THRESHOLD")

    def st_score(self, w, ctx) -> None:
        """Write the statements assigning the head's anomaly score to the
        REAL output ``SCORE`` — sequential f32 accumulation, the ST-side
        contract the verification oracle replays."""
        raise NotImplementedError

    def st_epilogue(self, w, ctx):
        if self.threshold is None:
            raise ValueError(
                f"{type(self).__name__} has no threshold; calibrate before "
                "exporting to Structured Text (the cutoff is baked into the "
                "block as a constant)")
        w.output("SCORE", "REAL")
        w.output("PRED", "DINT")
        w.output("THRESHOLD", "REAL")
        # The calibrated cutoff is an actual f32 calibration score
        # (conservative_quantile returns an order statistic), so snapping to
        # f32 is exact and the strict REAL compare below decides identically
        # to the engine's float64 `score > threshold`.
        w.const("THR", "REAL", float(np.float32(self.threshold)))
        self.st_score(w, ctx)
        w.comment("verdict: strict score > calibrated threshold")
        w.line("THRESHOLD := THR;")
        w.line("IF SCORE > THR THEN")
        w.line("    PRED := 1;")
        w.line("ELSE")
        w.line("    PRED := 0;")
        w.line("END_IF;")

    # -- streaming recalibration (online drift adaptation) -----------------

    def calib_state(self, n_streams: int,
                    capacity: int) -> Tuple[jax.Array, jax.Array]:
        """Zeroed per-stream rolling calibration state: a ``(n_streams,
        capacity)`` ring of admitted scores plus ``(n_streams,)`` admission
        counts.  Row-local by construction, so the serving engines shard it
        with the ring arena (``P("data", ...)``) with zero new collectives."""
        return (jnp.zeros((n_streams, capacity), jnp.float32),
                jnp.zeros((n_streams,), jnp.int32))

    def calib_update(self, ring: jax.Array, counts: jax.Array,
                     scores: jax.Array, threshold: jax.Array,
                     headroom: float) -> Tuple[jax.Array, jax.Array]:
        """Device-side state transition, traced into the engines' jitted
        step: each stream's score is admitted into its rolling ring iff it
        is at most ``headroom`` times the live ``threshold``.  Sub-headroom
        scores are what gradual benign drift looks like (they may exceed the
        threshold itself — that excess is exactly the drift the state must
        learn); scores beyond the headroom are treated as attacks and never
        enter the calibration state, so an attacked stream cannot drag the
        fleet threshold up after itself.  Rows are independent (each stream
        writes its own ring slot), so the update rides through ``shard_map``
        untouched."""
        s = scores[:, 0] if scores.ndim == 2 else scores
        admit = s <= headroom * threshold
        pos = counts % ring.shape[1]
        rows = jnp.arange(ring.shape[0])
        ring = ring.at[rows, pos].set(jnp.where(admit, s, ring[rows, pos]))
        return ring, counts + admit.astype(counts.dtype)

    def streaming_scores(self, ring, counts) -> np.ndarray:
        """Host-side: the pooled valid scores in a gathered calibration
        state (ring slot ``j`` of a stream holds a real score iff ``j <
        count`` — below one full ring the state is exactly the admitted
        score list, after wraparound it is the trailing ``capacity``)."""
        ring = np.asarray(ring)
        counts = np.asarray(counts)
        valid = np.arange(ring.shape[1])[None, :] < counts[:, None]
        return ring[valid]

    def streaming_threshold(self, ring, counts, *,
                            min_count: int = 1) -> Optional[float]:
        """Host-side re-host of ``recalibrate_threshold``'s score-then-
        quantile sequence onto the streaming state: the conservative
        ``(1 - target_fpr)`` quantile of the pooled valid ring scores,
        bit for bit ``conservative_quantile(streaming_scores(ring, counts),
        target_fpr)``.  That quantile is an order statistic, so it is
        selected (``np.partition`` of the f32 pool) rather than sorted out
        of a float64 copy.  Returns None (leave the live threshold alone)
        until ``min_count`` scores have been admitted fleet-wide."""
        if self.target_fpr is None:
            raise ValueError(
                f"{type(self).__name__} has no target_fpr; calibrate via "
                "head.calibrate / the sim.detector trainers (or construct "
                "with target_fpr=) before streaming recalibration")
        ring = np.asarray(ring)
        counts = np.asarray(counts)
        if counts.size and counts.min() >= ring.shape[1]:
            pool = ring.ravel()        # every slot holds a score: no gather
        else:
            pool = self.streaming_scores(ring, counts)
        n = pool.size
        if n < max(min_count, 1):
            return None
        k = _higher_rank(n, 1.0 - self.target_fpr)
        tail = np.partition(pool, k)[k:]
        # NaN sorts last, so any NaN in the pool lies in the tail; as in
        # np.quantile, a pool holding one yields a NaN.
        nan = np.isnan(tail)
        return float(tail[nan][-1] if nan.any() else tail[0])


@dataclasses.dataclass(frozen=True)
class ReconstructionHead(ScoreHead):
    """Unsupervised autoencoder: MSE loss on benign windows, anomaly score =
    per-window mean squared reconstruction error, verdict = score exceeding
    a threshold calibrated to ``target_fpr`` on held-out normal traces.

    ``threshold`` is None until calibrated (:meth:`calibrate` /
    ``sim.detector.train_autoencoder``); serving requires it.
    """

    name: str = "reconstruction"

    def validate(self, input_size: int, n_outputs: int) -> None:
        if n_outputs != input_size:
            raise ValueError(
                f"ReconstructionHead needs an autoencoder whose output width "
                f"({n_outputs}) equals its input width ({input_size})")
        super().validate(input_size, n_outputs)

    def batch_scores(self, outputs, x):
        return jnp.mean(jnp.square(outputs - x), axis=-1)

    def kernel_epilogue(self):
        return ("mse", "window")

    def scores(self, recon: jax.Array, x: jax.Array) -> jax.Array:
        """Per-window anomaly scores from batched reconstructions."""
        return self.batch_scores(recon, x)

    def st_score(self, w, ctx):
        w.var("I", "DINT")
        w.var("T", "REAL")
        w.comment("anomaly score: mean squared reconstruction error")
        w.line("SCORE := 0.0;")
        w.line(f"FOR I := 0 TO {ctx.n_outputs - 1} DO")
        w.line(f"    T := {ctx.y}[I] - {ctx.x}[I];")
        w.line("    SCORE := SCORE + T * T;")
        w.line("END_FOR;")
        w.line(f"SCORE := SCORE / {w.real(float(ctx.n_outputs))};")


@dataclasses.dataclass(frozen=True)
class MarginHead(ScoreHead):
    """Unsupervised one-class margin (Deep-SVDD-style): the model embeds a
    window; benign training pulls embeddings toward a fixed ``center`` (the
    mean initial embedding of benign windows — ``sim.detector.
    train_one_class`` computes it), and the anomaly score is the mean
    squared distance from it.  The calibrated ``threshold`` is the margin
    radius: scores beyond it are flagged.
    """

    center: Optional[Tuple[float, ...]] = None
    name: str = "margin"

    def _center(self) -> jax.Array:
        return jnp.asarray(self.center, jnp.float32)

    def validate(self, input_size: int, n_outputs: int) -> None:
        if self.center is None:
            raise ValueError(
                "MarginHead has no center; fit one on benign windows first "
                "(sim.detector.train_one_class)")
        if len(self.center) != n_outputs:
            raise ValueError(
                f"MarginHead center has {len(self.center)} dims but the "
                f"model embeds into {n_outputs}")
        super().validate(input_size, n_outputs)

    def batch_scores(self, outputs, x):
        return jnp.mean(jnp.square(outputs - self._center()), axis=-1)

    def kernel_epilogue(self):
        return ("mse", "center")

    def st_score(self, w, ctx):
        w.var("I", "DINT")
        w.var("T", "REAL")
        w.const("CENTER", "REAL",
                [float(np.float32(c)) for c in self.center])
        w.comment("anomaly score: mean squared distance from the benign "
                  "center")
        w.line("SCORE := 0.0;")
        w.line(f"FOR I := 0 TO {ctx.n_outputs - 1} DO")
        w.line(f"    T := {ctx.y}[I] - CENTER[I];")
        w.line("    SCORE := SCORE + T * T;")
        w.line("END_FOR;")
        w.line(f"SCORE := SCORE / {w.real(float(ctx.n_outputs))};")


@dataclasses.dataclass(frozen=True)
class ForecastHead(ScoreHead):
    """Unsupervised next-step prediction: the model maps the window's first
    ``W - 1`` readings to a forecast of the ``W``-th, and the anomaly score
    is the mean squared forecast error against the reading that actually
    arrived — physics violations surface as unforecastable transitions.

    The head owns the geometry asymmetry: the serving ring holds one more
    reading than the model consumes (:meth:`ring_window`), and
    :meth:`prepare` slices the model input off the front of each window —
    on device, inside the jitted step, for training and serving alike.
    """

    n_features: int = 2
    name: str = "forecast"

    def ring_window(self, input_size: int, n_features: int) -> int:
        if n_features != self.n_features:
            raise ValueError(
                f"ForecastHead was built for {self.n_features} features, "
                f"engine has {n_features}")
        if input_size % n_features:
            raise ValueError(
                f"forecast model input {input_size} is not a whole number "
                f"of {n_features}-feature readings")
        # One extra ring reading: the model eats W-1 readings, the W-th is
        # the forecast target.
        return input_size // n_features + 1

    def model_input_size(self, window: int, n_features: int) -> int:
        return (window - 1) * n_features

    def prepare(self, win):
        return win[..., :-self.n_features]

    def validate(self, input_size: int, n_outputs: int) -> None:
        if n_outputs != self.n_features:
            raise ValueError(
                f"ForecastHead predicts one {self.n_features}-feature "
                f"reading but the model outputs {n_outputs}")
        super().validate(input_size, n_outputs)

    def batch_scores(self, outputs, x):
        # x is the FULL window batch; the target is its last reading.
        return jnp.mean(
            jnp.square(outputs - x[..., -self.n_features:]), axis=-1)

    def kernel_epilogue(self):
        # The megakernel feeds the FULL window as x and zero-pads the model's
        # weight rows past its true input width, so prepare()'s slice is
        # subsumed by the zero-row contract; the target is the window tail.
        return ("mse", "tail")

    def st_score(self, w, ctx):
        # ctx.x is the FULL window array (the block keeps the extra ring
        # reading); the forecast target is its last reading, starting at the
        # model-input width the body consumed.
        w.var("I", "DINT")
        w.var("T", "REAL")
        w.comment("anomaly score: mean squared next-step forecast error")
        w.line("SCORE := 0.0;")
        w.line(f"FOR I := 0 TO {ctx.n_outputs - 1} DO")
        w.line(f"    T := {ctx.y}[I] - {ctx.x}[I + {ctx.in_width}];")
        w.line("    SCORE := SCORE + T * T;")
        w.line("END_FOR;")
        w.line(f"SCORE := SCORE / {w.real(float(ctx.n_outputs))};")
