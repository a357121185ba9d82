"""Multiclass / binary evaluation
(reference src/main/scala/evaluation/MulticlassClassifierEvaluator.scala:21-152,
BinaryClassifierEvaluator.scala:17-65).

The confusion matrix is computed in one fused device pass (scatter-add /
segment-sum) — the reference's single ``aggregate`` pass over the zipped RDD.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import trace


@dataclass(frozen=True)
class BinaryClassificationMetrics:
    """Contingency-table metrics (reference BinaryClassifierEvaluator.scala:17-47)."""

    tp: float
    fp: float
    tn: float
    fn: float

    def merge(self, o: "BinaryClassificationMetrics"):
        return BinaryClassificationMetrics(
            self.tp + o.tp, self.fp + o.fp, self.tn + o.tn, self.fn + o.fn
        )

    @property
    def accuracy(self):
        return (self.tp + self.tn) / (self.tp + self.fp + self.tn + self.fn)

    @property
    def error(self):
        return (self.fp + self.fn) / (self.tp + self.fp + self.tn + self.fn)

    @property
    def recall(self):
        return self.tp / (self.tp + self.fn)

    @property
    def precision(self):
        return self.tp / (self.tp + self.fp)

    @property
    def specificity(self):
        return self.tn / (self.fp + self.tn)

    def f_score(self, beta: float = 1.0) -> float:
        num = (1.0 + beta * beta) * self.tp
        denom = (1.0 + beta * beta) * self.tp + beta * beta * self.fn + self.fp
        return num / denom


class MulticlassMetrics:
    """Confusion-matrix metrics; rows = true labels, cols = predicted
    (reference MulticlassClassifierEvaluator.scala:21-152)."""

    def __init__(self, confusion_matrix):
        cm = np.asarray(confusion_matrix, dtype=np.float64)
        if cm.shape[0] != cm.shape[1]:
            raise ValueError("Confusion matrix must be square")
        self.confusion_matrix = cm
        self.num_classes = cm.shape[0]
        total = cm.sum()
        actual_sums = cm.sum(axis=1)
        predicted_sums = cm.sum(axis=0)
        self.class_metrics = []
        for c in range(self.num_classes):
            tp = cm[c, c]
            fp = predicted_sums[c] - tp
            tn = total - actual_sums[c] - fp
            fn = total - tp - fp - tn
            self.class_metrics.append(BinaryClassificationMetrics(tp, fp, tn, fn))

    def _class_avg(self, f) -> float:
        return sum(f(m) for m in self.class_metrics) / self.num_classes

    def _micro(self, f) -> float:
        merged = self.class_metrics[0]
        for m in self.class_metrics[1:]:
            merged = merged.merge(m)
        return f(merged)

    @property
    def avg_accuracy(self):
        return self._class_avg(lambda m: m.accuracy)

    @property
    def avg_error(self):
        return self._class_avg(lambda m: m.error)

    @property
    def macro_precision(self):
        return self._class_avg(lambda m: m.precision)

    @property
    def macro_recall(self):
        return self._class_avg(lambda m: m.recall)

    def macro_f_score(self, beta: float = 1.0):
        return self._class_avg(lambda m: m.f_score(beta))

    @property
    def total_accuracy(self):
        return self._micro(lambda m: m.precision)

    @property
    def total_error(self):
        return self._micro(lambda m: m.fn / (m.fn + m.tp))

    @property
    def micro_precision(self):
        return self._micro(lambda m: m.precision)

    @property
    def micro_recall(self):
        return self._micro(lambda m: m.recall)

    def micro_f_score(self, beta: float = 1.0):
        return self._micro(lambda m: m.f_score(beta))

    def pprint_confusion_matrix(self, classes) -> str:
        """Mahout-style pretty print (reference :62-81)."""
        labels = [_small_label(i) for i in range(self.num_classes)]
        width = max(6, max(len(l) for l in labels) + 1)
        lines = ["".join(l.rjust(width) for l in labels) + "   <-- Classified As"]
        for r in range(self.num_classes):
            row = "".join(
                str(int(self.confusion_matrix[r, c])).rjust(width)
                for c in range(self.num_classes)
            )
            lines.append(f"{row}   {labels[r]} = {classes[r]}")
        return "\n".join(lines)

    def summary(self, classes) -> str:
        return (
            f"{self.pprint_confusion_matrix(classes)}\n"
            f"Avg Accuracy:\t{self.avg_accuracy:2.3f}\n"
            f"Macro Precision:\t{self.macro_precision:2.3f}\n"
            f"Macro Recall:\t{self.macro_recall:2.3f}\n"
            f"Macro F1:\t{self.macro_f_score():2.3f}\n"
            f"Total Accuracy:\t{self.total_accuracy:2.3f}\n"
            f"Micro Precision:\t{self.micro_precision:2.3f}\n"
            f"Micro Recall:\t{self.micro_recall:2.3f}\n"
            f"Micro F1:\t{self.micro_f_score():2.3f}\n"
        )


def _small_label(i: int) -> str:
    """Base-26 column header (reference :108-123, bug-for-bug: digit order and
    the off-by-one 'a'+n are reproduced so printed headers match)."""
    if i == 0:
        return "a"
    out = ""
    while i > 0:
        out = out + chr(ord("a") + (i % 26))
        i //= 26
    return out


@functools.partial(jax.jit, static_argnames="num_classes")
def _confusion_counts(predictions, actuals, num_classes: int):
    flat = actuals.astype(jnp.int32) * num_classes + predictions.astype(jnp.int32)
    counts = jnp.bincount(flat, length=num_classes * num_classes)
    return counts.reshape(num_classes, num_classes)


def confusion_matrix(predictions, actuals, num_classes: int):
    """One-pass confusion matrix on device: rows=actual, cols=predicted.
    One compiled program a shape and ``num_classes``."""
    if isinstance(actuals, np.ndarray):  # the labels come from the host
        with trace.h2d("labels", actuals.nbytes):
            actuals = jnp.asarray(actuals)
    with trace.host("dispatch", "confusion_counts"):
        return _confusion_counts(
            jnp.asarray(predictions), jnp.asarray(actuals), num_classes
        )


class MulticlassClassifierEvaluator:
    """Callable matching the reference companion object
    (MulticlassClassifierEvaluator.scala:126-163)."""

    @staticmethod
    def apply(predictions, actuals, num_classes: int) -> MulticlassMetrics:
        # The host blocks here until the predictions and their counts exist
        # on the device, then reads the counts: a round trip an evaluation.
        counts = trace.wait(
            confusion_matrix(predictions, actuals, num_classes), "evaluator"
        )
        with trace.d2h("confusion_matrix", counts.nbytes):
            counts = np.asarray(counts)
        return MulticlassMetrics(counts)

    def __new__(cls, predictions, actuals, num_classes: int) -> MulticlassMetrics:  # type: ignore[misc]
        return cls.apply(predictions, actuals, num_classes)


class BinaryClassifierEvaluator:
    """Contingency table from boolean predictions/actuals
    (reference BinaryClassifierEvaluator.scala:50-65)."""

    @staticmethod
    def apply(predictions, actuals) -> BinaryClassificationMetrics:
        p = np.asarray(predictions, dtype=bool)
        a = np.asarray(actuals, dtype=bool)
        tp = float(np.sum(p & a))
        fp = float(np.sum(p & ~a))
        tn = float(np.sum(~p & ~a))
        fn = float(np.sum(~p & a))
        return BinaryClassificationMetrics(tp, fp, tn, fn)

    def __new__(cls, predictions, actuals) -> BinaryClassificationMetrics:  # type: ignore[misc]
        return cls.apply(predictions, actuals)
