"""NewsgroupsPipeline — 20 Newsgroups text classification
(reference src/main/scala/pipelines/text/NewsgroupsPipeline.scala:14-75).

Trim -> LowerCase -> Tokenizer -> NGrams(1..n) -> TermFrequency(x=>1) ->
CommonSparseFeatures(k) -> NaiveBayes -> MaxClassifier ->
MulticlassClassifierEvaluator (pretty summary per class).
"""

from __future__ import annotations

import argparse
import contextlib
import time

from dataclasses import dataclass

import numpy as np

from ..core.logging import Logging, configure_logging
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.newsgroups import CLASSES, NewsgroupsData, newsgroups_loader
from ..ops.nlp import LowerCase, NGramsFeaturizer, TermFrequency, Tokenizer, Trim
from ..ops.sparse import CommonSparseFeatures
from ..ops.util import MaxClassifier
from ..parallel.mesh import parse_mesh, use_mesh
from ..solvers.naive_bayes import NaiveBayesEstimator
from ..utils.platform import init_device


@dataclass
class NewsgroupsConfig:
    """Flag-parity with the reference scopt config (:46-50)."""

    train_location: str = ""
    test_location: str = ""
    n_grams: int = 2
    common_features: int = 100000
    classes: tuple = tuple(CLASSES)


class _Log(Logging):
    pass


def run(
    conf: NewsgroupsConfig,
    train: NewsgroupsData,
    test: NewsgroupsData,
    mesh=None,
) -> dict:
    """With ``mesh``: naive-Bayes scoring runs data-parallel over the mesh —
    per-device COO shards contracted against the replicated ``theta`` under
    ``shard_map`` (see NaiveBayesModel._apply_csr_mesh).  The text
    featurization and the NB count aggregation stay host-side, like the
    reference's per-executor text processing feeding MLlib
    (NewsgroupsPipeline.scala:14-75)."""
    configure_logging()
    log = _Log()
    t0 = time.perf_counter()
    num_classes = len(conf.classes)
    mesh_ctx = use_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    log.log_info("Training classifier")
    text_pipe = (
        Trim()
        .then(LowerCase())
        .then(Tokenizer())
        .then(NGramsFeaturizer(range(1, conf.n_grams + 1)))
        .then(TermFrequency(lambda x: 1))
    )
    train_terms = text_pipe(train.data)
    vectorizer = CommonSparseFeatures(conf.common_features).fit(train_terms)
    train_feats = vectorizer(train_terms)
    model = NaiveBayesEstimator(num_classes).fit(train_feats, train.labels)

    log.log_info("Evaluating classifier")
    test_feats = vectorizer(text_pipe(test.data))
    with mesh_ctx:
        predictions = np.asarray(MaxClassifier()(model(test_feats)))
    ev = MulticlassClassifierEvaluator(predictions, test.labels, num_classes)
    results = {
        "test_error": 100.0 * ev.total_error,
        "seconds": time.perf_counter() - t0,
        "evaluator": ev,
    }
    log.log_info("\n%s", ev.summary(list(conf.classes)))
    return results


def main(argv=None):
    p = argparse.ArgumentParser("NewsgroupsPipeline")
    p.add_argument("--trainLocation", required=True)
    p.add_argument("--testLocation", required=True)
    p.add_argument("--nGrams", type=int, default=2)
    p.add_argument("--commonFeatures", type=int, default=100000)
    p.add_argument(
        "--mesh",
        default=None,
        help="device mesh, e.g. '8' (data) or '4x2' (data x model)",
    )
    a = p.parse_args(argv)
    configure_logging()
    init_device()
    conf = NewsgroupsConfig(
        train_location=a.trainLocation,
        test_location=a.testLocation,
        n_grams=a.nGrams,
        common_features=a.commonFeatures,
    )
    train = newsgroups_loader(conf.train_location)
    test = newsgroups_loader(conf.test_location)
    return run(conf, train, test, mesh=parse_mesh(a.mesh))


if __name__ == "__main__":
    main()
