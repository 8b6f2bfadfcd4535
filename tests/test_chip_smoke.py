"""The verdict gate of ``chip_smoke.py``, on synthetic verdicts.

The smoke run itself needs a TPU, but the comparison that decides its exit
code is plain numpy: these cases pin which chip verdicts it accepts against
the reference and which it refuses.
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke as C  # noqa: E402


def classifier_ref(pred, tail):
    """A binary classifier's reference: ``tail`` is the probability of the
    reference's own predicted class."""
    pred = np.asarray(pred)
    return SimpleNamespace(pred=pred, tail=np.asarray(tail, np.float64),
                           head=None, n_out=2, boundary=0.5, threshold=None,
                           shape=(1, pred.size),
                           explain=lambda *a: None)


def score_ref(score, threshold=1.0):
    score = np.asarray(score, np.float64)
    return SimpleNamespace(pred=(score > threshold).astype(np.int64),
                           tail=score, head=object(), n_out=1,
                           boundary=threshold, threshold=threshold,
                           shape=(1, score.size), explain=lambda *a: None)


# (id, scheme, reference, chip pred, chip tail, accepted)
CASES = [
    ("real-classifier-equal", "REAL",
     classifier_ref([0, 1], [0.9, 0.7]), [0, 1], [0.9, 0.7], True),
    ("real-classifier-within-rtol", "REAL",
     classifier_ref([0, 1], [0.9, 0.7]), [0, 1], [0.90005, 0.7], True),
    # A kernel that reverses the logits: each side's own class has p=0.9.
    ("real-classifier-reversed", "REAL",
     classifier_ref([0, 1], [0.9, 0.9]), [1, 0], [0.9, 0.9], False),
    # The reference sits 1e-5 from 1/2; the chip's probability of the
    # reference's class is 0.49999, 2e-5 away from the reference's.
    ("real-classifier-borderline-flip", "REAL",
     classifier_ref([0], [0.50001]), [1], [0.50001], True),
    ("real-classifier-flip-far-from-half", "REAL",
     classifier_ref([0], [0.6]), [1], [0.5], False),
    ("real-classifier-tail-off", "REAL",
     classifier_ref([0], [0.9]), [0], [0.9002], False),
    ("real-score-within-floor", "REAL",
     score_ref([3e-4]), [0], [3e-4 + 9e-7], True),
    ("real-score-beyond-floor", "REAL",
     score_ref([3e-4]), [0], [3e-4 + 2e-6], False),
    ("real-score-borderline-flip", "REAL",
     score_ref([0.9999995]), [1], [1.0000004], True),
    ("real-score-flip-far-from-threshold", "REAL",
     score_ref([0.5]), [1], [0.5], False),
    ("sint-equal", "SINT",
     classifier_ref([0, 1], [0.9, 0.7]), [0, 1], [0.9, 0.7], True),
    ("sint-score-no-absolute-floor", "SINT",
     score_ref([3e-4]), [0], [3e-4 + 9e-7], False),
    ("sint-borderline-flip-refused", "SINT",
     classifier_ref([0], [0.50001]), [1], [0.50001], False),
]


@pytest.mark.parametrize("scheme,ref,pred,tail,accepted",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_compare_gate(scheme, ref, pred, tail, accepted):
    pred = np.asarray(pred).reshape(ref.shape)
    tail = np.asarray(tail, np.float64).reshape(ref.shape)
    if accepted:
        ties = C.compare("case", scheme, pred, tail, ref)
        assert ties.shape == ref.shape and not ties.any()
    else:
        with pytest.raises(AssertionError):
            C.compare("case", scheme, pred, tail, ref)


def _run(pred, tail, ties=(False, False, False)):
    return (np.asarray(pred), np.asarray(tail, np.float64),
            np.asarray(ties))


@pytest.mark.parametrize("run,accepted", [
    (_run([0, 1, 0], [0.9, 0.8, 0.7]), True),
    (_run([0, 1, 0], [0.9, 0.8, 0.7001], (False, False, True)), True),
    (_run([0, 1, 0], [0.9, 0.8, 0.7001]), False),
    (_run([0, 1, 1], [0.9, 0.8, 0.7]), False),
], ids=["identical", "near-tie-excused", "tail-differs", "pred-differs"])
def test_agree_gate(run, accepted):
    base = _run([0, 1, 0], [0.9, 0.8, 0.7])
    if accepted:
        C.agree("case", run, base)
    else:
        with pytest.raises(AssertionError):
            C.agree("case", run, base)
