"""ImageNetSiftLcsFV's chunked two-branch fit (``imagenet_sift_lcs_fv.run`` on
images held in memory), at 16 classes and 48 x 64 images:

* stage by stage against the plain reference ``benchmark/reference/
  imagenet_fv.py`` (LCS against its definition; both branches' PCA subspace,
  mixture likelihood and Fisher vectors; the weighted model and the scores),
  through the cell's own comparison;
* against the resident form (``branch_features``: every descriptor of a split
  held, ``sample_columns`` on them) on the same images: the same samples and
  the same model to rounding;
* what it counts: one trip of an image a pass, each descriptor node once a
  pass, one class system a class, and a second fit that traces nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.loaders.image_loaders import LabeledImages
from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator
from keystone_tpu.workloads import fv_common
from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

SEED = 2_147_483_659  # more than 32 signed bits hold
CELL = "imagenet_fv_fit"

#: the comparison's numbers and what a float32 CPU run may read (the cell's
#: own limits are set on the chip, where products round to bfloat16)
TIGHT = {
    "sift_off_share": 0.03, "lcs_gap": 1e-5,
    "sift_pca_subspace_gap": 1e-4, "lcs_pca_subspace_gap": 1e-4,
    "sift_gmm_llh_gap": 1e-4, "lcs_gmm_llh_gap": 1e-4,
    "sift_em_step_gap": 1e-3, "lcs_em_step_gap": 1e-3,
    "sift_fv_gap": 0.25, "lcs_fv_gap": 1e-3,
    "model_gap": 5e-3, "scores_rms_gap": 2e-3, "scores_max_gap": 1e-2, "top5_gap": 1e-9,
}


@pytest.fixture(scope="module")
def cell():
    parts = manifest.cell(CELL)
    conf = manifest.resized(parts["config"], True)
    rows = manifest.resized(parts["traffic"], True)["rows"]
    pipeline = manifest.load_module("pipelines", conf["pipeline"])
    data = manifest.load_module("datagen", pipeline.DATAGEN).generate(conf["data"], rows, SEED)
    return conf, pipeline, data


@pytest.fixture(scope="module")
def fitted(cell, tmp_path_factory):
    """Two fits of the cell's rehearsal size through the pipeline's timed
    entry, the registry's counters around each, and the comparison's numbers
    of the second."""
    conf, pipeline, data = cell
    stem = str(tmp_path_factory.mktemp("inet") / "fit")
    seed = pipeline.program_seed(SEED)
    counters, programs = [], []
    out = None
    for _ in range(2):
        before = trace.metrics.counters()
        sizes = _cache_sizes()
        out = pipeline.fit(conf, data, seed, stem)
        after = trace.metrics.counters()
        counters.append({k: after[k] - before.get(k, 0) for k in after})
        programs.append(sum(_cache_sizes().values()) - sum(sizes.values()))
    reference = manifest.load_module("reference", pipeline.REFERENCE)
    produced = pipeline.produced(out, conf, data, SEED)
    values = reference.compare(conf, data, SEED, produced, {})
    return {"out": out, "counters": counters, "programs": programs, "values": values,
            "produced": produced}


def _cache_sizes() -> dict:
    from keystone_tpu.solvers import weighted

    fns = {
        "describe": fv_common._describe_chunk, "describe_lcs": fv_common._describe_lcs_chunk,
        "sample": fv_common._sample_chunk, "encode": fv_common._encode_chunk,
        "gather": fv_common._gather_samples, "bwls": weighted._fused_bwls_fit_variant((0, 1)),
    }
    return {k: f._cache_size() for k, f in fns.items()}


@pytest.mark.parametrize("name", sorted(TIGHT))
def test_chunked_fit_against_the_plain_reference(fitted, cell, name):
    conf = cell[0]
    assert name in conf["limits"], f"the configuration states no limit for {name}"
    value = fitted["values"][name]
    assert np.isfinite(value) and value <= TIGHT[name], (name, value)
    assert value <= conf["limits"][name]


def test_results_hold_the_chain_and_the_scores(fitted, cell):
    conf, _pipeline, data = cell
    res = fitted["out"]["results"]
    assert set(res["pipeline"]) == {"sift_pca", "sift_gmm", "lcs_pca", "lcs_gmm", "lcs_centre", "model"}
    assert res["test_scores"].shape == (len(data["test"]["y"]), conf["num_classes"])
    assert res["solver"]["tier"] == "fused"
    assert 0.0 <= res["top5_err_percent"] <= res["top1_err_percent"] <= 100.0
    assert set(res["gmm_iterations"]) == {"sift", "lcs"}


@pytest.mark.parametrize(
    "counter,per_row",
    [
        ("fv.image_passes", 2), ("fv.descriptor_passes.sift", 2), ("fv.descriptor_passes.lcs", 2),
        ("bwls.class_solves", None), ("bwls.classes", None),
    ],
)
def test_counters_of_a_fit(fitted, cell, counter, per_row):
    conf, _pipeline, data = cell
    rows = len(data["train"]["y"])
    for counted in fitted["counters"]:
        if per_row is None:
            assert counted[counter] == conf["num_classes"]  # one block, one pass
        else:
            assert counted[counter] == per_row * rows
    counted = fitted["counters"][-1]
    assert counted["fv.descriptors_sampled.sift"] > 0 and counted["fv.descriptors_sampled.lcs"] > 0
    assert counted["gmm.iterations"] == counted["gmm.iterations.sift"] + counted["gmm.iterations.lcs"]


def test_a_second_fit_traces_nothing(fitted):
    # (the first fit compiles, unless an earlier test of the process has)
    assert fitted["programs"][1] == 0 and all(_cache_sizes().values()), fitted["programs"]


def _small_conf(conf: dict) -> inet.ImageNetSiftLcsFVConfig:
    return inet.ImageNetSiftLcsFVConfig(
        lam=conf["lam"], mixture_weight=conf["mixture_weight"], desc_dim=conf["desc_dim"],
        vocab_size=conf["vocab_size"], num_pca_samples=1500, num_gmm_samples=1200,
        num_classes=conf["num_classes"], seed=7,
    )


@pytest.fixture(scope="module")
def both_forms(cell):
    """The chunked fit (chunks of 16 so that buckets take several) and the
    resident form's branches, on the same images and seed."""
    conf, _pipeline, data = cell
    ic = _small_conf(conf)
    # float32 levels 0..255, as the image loaders yield them (the resident
    # form's LCS takes its batch as it comes)
    as_loaded = lambda part: LabeledImages(  # noqa: E731
        [x.astype(np.float32) for x in part["x"]], np.asarray(part["y"]), [""] * len(part["y"])
    )
    train, test = as_loaded(data["train"]), as_loaded(data["test"])
    old = fv_common.MAX_CHUNK
    fv_common.MAX_CHUNK = 16
    try:
        chunked = inet.run(ic, train, test)
    finally:
        fv_common.MAX_CHUNK = old
    halves, chains = [], {}
    for name, fn, seed in (
        ("sift", inet.sift_descriptor_buckets, ic.seed), ("lcs", inet.lcs_descriptor_buckets, ic.seed + 100)
    ):
        tr, te, pca, gmm, _plan = inet.branch_features(
            ic, train.images, test.images, fn, None, (None, None, None), seed
        )
        halves.append((tr, te))
        chains[name] = (pca, gmm)
    train_x = np.concatenate([h[0] for h in halves], axis=1)
    test_x = np.concatenate([h[1] for h in halves], axis=1)
    labels = ClassLabelIndicatorsFromIntLabels(ic.num_classes)(train.labels)
    model = BlockWeightedLeastSquaresEstimator(4096, 1, ic.lam, ic.mixture_weight).fit(
        train_x, labels, num_features=train_x.shape[1]
    )
    return chunked, chains, model, np.asarray(model(jnp.asarray(test_x)))


@pytest.mark.parametrize("branch", ["sift", "lcs"])
def test_chunked_dictionary_is_the_resident_one(both_forms, branch):
    chunked, chains, _model, _scores = both_forms
    pca, gmm = chains[branch]
    np.testing.assert_allclose(
        np.asarray(chunked["pipeline"][f"{branch}_pca"].pca_mat), np.asarray(pca.pca_mat), atol=1e-3
    )
    mine = chunked["pipeline"][f"{branch}_gmm"]
    np.testing.assert_allclose(np.asarray(mine.weights), np.asarray(gmm.weights), atol=2e-4)
    means = np.asarray(gmm.means)
    if branch == "lcs":
        # the chunked fit's mixture lies in the centred frame
        centre = np.asarray(chunked["pipeline"]["lcs_centre"].centre)
        means = means - (centre @ np.asarray(pca.pca_mat))[:, None]
    np.testing.assert_allclose(np.asarray(mine.means), means, rtol=2e-3, atol=2e-2)


def test_chunked_model_is_the_resident_one(both_forms):
    chunked, _chains, model, scores = both_forms
    mine = np.concatenate([np.asarray(x) for x in chunked["pipeline"]["model"].xs])
    theirs = np.concatenate([np.asarray(x) for x in model.xs])
    assert np.linalg.norm(mine - theirs) <= 2e-2 * np.linalg.norm(theirs)
    rms = np.sqrt(np.mean(scores**2))
    assert np.sqrt(np.mean((chunked["test_scores"] - scores) ** 2)) <= 1e-2 * rms


def test_one_trip_of_a_chunk_feeds_both_branches(cell):
    """``fv_plan`` reckons both branches' bytes, and a chunk's bytes cross to
    the device once a pass: the host-to-device spans of a sampling pass are
    the chunks, not chunks times branches."""
    conf, _pipeline, data = cell
    branches = inet.descriptor_branches(_small_conf(conf))
    images = data["train"]["x"]
    plan = fv_common.plan_chunks(images, branches, conf["desc_dim"], conf["vocab_size"])
    for shape, cols in plan.cols.items():
        assert cols == tuple(b.cols(*shape) for b in branches)
        assert plan.image_bytes[shape] == sum(
            4 * c * (b.dim + conf["desc_dim"] + conf["vocab_size"]) for c, b in zip(cols, branches)
        )
    assert [b.dim for b in branches] == [128, 96]
    draws = [[fv_common.draw_columns(plan.totals_of(b), 500, 3 + b)] for b in range(2)]
    before = trace.metrics.counters()
    sift_rows, lcs_rows = fv_common.sample_descriptor_columns(plan, images, branches, draws)
    after = trace.metrics.counters()
    chunks = sum(-(-len(i) // plan.chunk[s]) for s, i in plan.index.items())
    crossed = sum(after[k] - before.get(k, 0) for k in after if k.startswith("fv.chunks."))
    assert crossed == chunks
    assert sift_rows[0].shape[1] == 128 and lcs_rows[0].shape[1] == 96
    assert sift_rows[0].shape[0] == sum(len(d) for d in draws[0][0].values())
    # SIFT's rows are whole numbers (they crossed as bytes), LCS's are not
    assert np.all(np.asarray(sift_rows[0]) == np.round(np.asarray(sift_rows[0])))
    assert np.any(np.asarray(lcs_rows[0]) != np.round(np.asarray(lcs_rows[0])))
