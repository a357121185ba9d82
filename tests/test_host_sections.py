"""The named host sections a fit records beneath its stages (ISSUE 36), one
small CPU fit a kind: which sections each stage charges
(``stage_host_n.<stage>.<section>``), and that what no section covers
(``other``) stays under a quarter of the stage's host work."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core import trace
from keystone_tpu.core.logging import stage_timer
from keystone_tpu.parallel.mesh import parse_mesh
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator
from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator

SOLVE = {"search", "plan", "place", "dispatch"}


def newest_parts(stages, hists=None) -> dict:
    """``{stage: {part: (ms, occurrences)}}`` of each stage's newest sample
    (in ``hists``, default the registry's now): ``wait``, ``h2d``, the named
    sections and ``other``; the parts sum to the stage's self time."""
    hists = hists or trace.metrics.hist_windows()
    out = {}
    for stage in stages:
        parts = out[stage] = {
            "wait": (hists[f"stage_wait_ms.{stage}"]["samples"][-1], None),
            "h2d": (hists[f"stage_h2d_ms.{stage}"]["samples"][-1], None),
        }
        prefix = f"stage_host_ms.{stage}."
        for name, h in hists.items():
            if name.startswith(prefix):
                part = name[len(prefix):]
                n = hists.get(f"stage_host_n.{stage}.{part}")
                parts[part] = (h["samples"][-1], n["samples"][-1] if n else None)
        total = sum(ms for ms, _ in parts.values())
        assert total == pytest.approx(hists[f"stage_ms.{stage}"]["samples"][-1], abs=1e-6)
    return out


def _problem(rng, rows, columns, classes):
    x = rng.normal(size=(rows, columns)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, rows)]
    return x, 2.0 * y - 1.0


@pytest.fixture(scope="module")
def cifar_parts(tmp_path_factory):
    from keystone_tpu.loaders.cifar import LabeledImageBatch
    from keystone_tpu.workloads import cifar_random_patch as cifar

    rng = np.random.default_rng(5)

    def batch(n):
        labels = rng.integers(0, 4, n).astype(np.int32)
        images = rng.uniform(0, 255, (n, 32, 32, 3)).astype(np.float32)
        images[:, :, :, 0] += 40.0 * labels[:, None, None]
        return LabeledImageBatch(images, labels)

    conf = cifar.RandomCifarConfig(
        num_filters=8, patch_steps=2, lam=10.0, whitener_size=500,
        featurize_chunk=64, num_classes=4,
        pipeline_file=str(tmp_path_factory.mktemp("cifar") / "chain"),
    )
    train, test = batch(250), batch(64)  # a short last chunk: the pad is ``stack``'s
    cifar.run(conf, train, test)
    return newest_parts(["featurize", "featurize_test", "solve", "eval", "checkpoint"])


@pytest.fixture(scope="module")
def mesh_parts(devices):
    x, y = _problem(np.random.default_rng(6), 256, 96, 4)
    with stage_timer("solve"):
        solver = BlockLeastSquaresEstimator(32, 1, 1.0, mesh=parse_mesh("4"))
        solver.fit(jnp.asarray(x), jnp.asarray(y))
    assert solver.last_fit_report.chosen == "fused[mesh 4x1]"
    return newest_parts(["solve"])


@pytest.fixture(scope="module")
def weighted_parts():
    x, y = _problem(np.random.default_rng(7), 120, 24, 3)
    with stage_timer("solve"):
        BlockWeightedLeastSquaresEstimator(8, 1, 0.1, 0.25).fit(jnp.asarray(x), jnp.asarray(y))
    return newest_parts(["solve"])


@pytest.fixture(scope="module")
def voc_parts():
    from test_voc_chunked_fit import CONF, _images, _padded, _split
    from keystone_tpu.workloads import voc_sift_fisher as voc

    train, train_y = _images(22, 1)
    test, test_y = _images(12, 2)
    voc.run(
        CONF,
        _split({"x": train, "y": _padded(train_y)}),
        _split({"x": test, "y": _padded(test_y)}),
    )
    return newest_parts(["sample_descriptors", "featurize", "featurize_test", "solve"])


@pytest.fixture(scope="module")
def timit_parts():
    from keystone_tpu.loaders.timit import TimitFeaturesData, TimitSplit
    from keystone_tpu.workloads import timit

    rng = np.random.default_rng(8)

    def split(n):
        return TimitSplit(
            rng.normal(size=(n, 12)).astype(np.float32),
            rng.integers(0, 3, n).astype(np.int32),
        )

    conf = timit.TimitConfig(
        num_cosines=3, num_cosine_features=32, num_epochs=2, gamma=0.2,
        lam=1e-2, num_classes=3, dimension=12,
    )
    timit.run(conf, TimitFeaturesData(split(96), split(32)))
    return newest_parts(["featurize", "solve", "eval"])


@pytest.mark.parametrize("fit, stage, sections", [
    ("cifar", "solve", SOLVE),
    ("mesh", "solve", SOLVE),
    ("weighted", "solve", {"sort", "place", "dispatch", "search", "plan"}),
    ("voc", "sample_descriptors", {"stack", "draw", "dispatch", "concat"}),
    ("voc", "featurize", {"stack", "dispatch", "concat"}),
    ("voc", "featurize_test", {"stack", "dispatch", "concat"}),
    ("cifar", "featurize", {"stack", "dispatch", "concat"}),
    ("cifar", "checkpoint", {"write"}),
    ("cifar", "eval", {"dispatch"}),
    ("timit", "featurize", {"dispatch"}),
    ("timit", "solve", SOLVE),
    ("timit", "eval", {"dispatch"}),
])
def test_stage_charges_its_sections(request, fit, stage, sections):
    parts = request.getfixturevalue(f"{fit}_parts")[stage]
    charged = {part for part, (_, n) in parts.items() if n}
    assert sections <= charged <= trace.SECTIONS, (charged, parts)
    host = sum(ms for part, (ms, _) in parts.items() if part not in ("wait", "h2d"))
    assert 0 <= parts["other"][0] < 0.25 * host, parts
