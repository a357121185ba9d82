"""Evaluator tests (reference src/test/scala/evaluation/*Suite.scala)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.evaluation import multiclass
from keystone_tpu.evaluation.multiclass import (
    BinaryClassifierEvaluator,
    MulticlassClassifierEvaluator,
    confusion_matrix,
)


def test_multiclass_perfect():
    actual = [0, 1, 2, 1, 0]
    m = MulticlassClassifierEvaluator(actual, actual, 3)
    assert m.total_accuracy == 1.0
    assert m.total_error == 0.0
    assert m.macro_precision == 1.0


def test_multiclass_confusion_and_metrics():
    actual = [0, 0, 1, 1, 2, 2]
    pred = [0, 1, 1, 1, 2, 0]
    m = MulticlassClassifierEvaluator(pred, actual, 3)
    cm = m.confusion_matrix  # rows=actual, cols=pred
    assert cm[0, 0] == 1 and cm[0, 1] == 1
    assert cm[1, 1] == 2
    assert cm[2, 2] == 1 and cm[2, 0] == 1
    assert abs(m.total_error - 2.0 / 6.0) < 1e-9
    assert abs(m.total_accuracy - 4.0 / 6.0) < 1e-9
    # class-1 precision: predicted 1 three times, 2 correct
    assert abs(m.class_metrics[1].precision - 2.0 / 3.0) < 1e-9
    s = m.summary(["a", "b", "c"])
    assert "Total Accuracy" in s and "Macro F1" in s


def test_binary_metrics():
    pred = [True, True, False, False, True]
    act = [True, False, False, True, True]
    b = BinaryClassifierEvaluator(pred, act)
    assert b.tp == 2 and b.fp == 1 and b.tn == 1 and b.fn == 1
    assert abs(b.accuracy - 3.0 / 5.0) < 1e-9
    assert abs(b.precision - 2.0 / 3.0) < 1e-9
    assert abs(b.recall - 2.0 / 3.0) < 1e-9
    assert abs(b.f_score() - 2.0 / 3.0) < 1e-9


def test_multiclass_matches_sklearn_style_micro(rng):
    n, k = 500, 7
    actual = rng.integers(0, k, n)
    pred = actual.copy()
    flip = rng.random(n) < 0.3
    pred[flip] = (pred[flip] + 1 + rng.integers(0, k - 1, flip.sum())) % k
    m = MulticlassClassifierEvaluator(pred, actual, k)
    acc = (pred == actual).mean()
    assert abs(m.total_accuracy - acc) < 1e-9
    assert abs(m.total_error - (1 - acc)) < 1e-9


@pytest.mark.parametrize("labels_on", ["host", "device", "list"])
@pytest.mark.parametrize("preds_on", ["host", "device"])
def test_confusion_matrix_program_equals_the_numpy_count(rng, preds_on, labels_on):
    """The counts as one compiled program, wherever predictions and labels
    come from: rows the actual class, columns the predicted."""
    n, k = 333, 5
    actual = rng.integers(0, k, n)
    pred = rng.integers(0, k, n)
    want = np.zeros((k, k), np.int64)
    np.add.at(want, (actual, pred), 1)
    place = {"host": np.asarray, "device": jnp.asarray, "list": list}
    got = confusion_matrix(place[preds_on](pred), place[labels_on](actual), k)
    np.testing.assert_array_equal(np.asarray(got), want)
    m = MulticlassClassifierEvaluator(place[preds_on](pred), place[labels_on](actual), k)
    np.testing.assert_array_equal(m.confusion_matrix, want)


def test_confusion_matrix_compiles_once_a_shape(rng):
    multiclass._confusion_counts.clear_cache()
    for _ in range(3):
        confusion_matrix(rng.integers(0, 4, 50), rng.integers(0, 4, 50), 4)
    assert multiclass._confusion_counts._cache_size() == 1
    confusion_matrix(rng.integers(0, 4, 50), rng.integers(0, 4, 50), 6)
    assert multiclass._confusion_counts._cache_size() == 2
