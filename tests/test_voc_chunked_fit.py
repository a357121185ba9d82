"""VOCSIFTFisher's chunked two-pass fit (``workloads/fv_common``,
``workloads/voc_sift_fisher.run``), fast and on the CPU:

* the program against the plain reference ``benchmark/reference/voc_fv.py``
  on seeded images of two shapes, stage by stage (SIFT, PCA, EM, Fisher
  features, MAP) and end to end;
* the chunked fit against a resident fit written out here (every
  descriptor held, ``sample_columns`` on them), on the same seed;
* a bucket whose rows are no multiple of the chunk; the budget rule; the
  stages, spans and counters of one fit; both passes on one SIFT program;
* the benchmark's stage-by-stage control read by the same comparison.

The images are this file's own (``tests/test_fisher_pipelines.py`` and the
benchmark's generator draw theirs).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.manifest import load_module
from keystone_tpu.core import trace
from keystone_tpu.evaluation.map import mean_average_precision
from keystone_tpu.loaders.image_loaders import MultiLabeledImages
from keystone_tpu.ops.sift import SIFTExtractor
from keystone_tpu.solvers.gmm import GaussianMixtureModelEstimator
from keystone_tpu.solvers.pca import BatchPCATransformer, compute_pca
from keystone_tpu.workloads import fv_common
from keystone_tpu.workloads import voc_sift_fisher as voc

ref = load_module("reference", "voc_fv")

SHAPES = [(40, 52), (52, 40)]
CLASSES = 4
SIFT = {"step": 6, "bin": 4, "scales": 4, "scale_step": 0}
CONF = voc.SIFTFisherConfig(
    lam=0.05, desc_dim=16, vocab_size=8, num_pca_samples=1500,
    num_gmm_samples=1500, sift_step_size=6, seed=11,
)
#: the reference's view of CONF
REF_CONF = {
    "sift_step": 6, "sift_bin": 4, "sift_scales": 4, "scale_step": 0,
    "desc_dim": 16, "vocab_size": 8, "num_pca_samples": 1500,
    "num_gmm_samples": 1500, "solver_block": 4096, "num_epochs": 1,
    "num_classes": 20, "lam": 0.05, "reference_chunk": 8,
    "compare": {"images": 6},
}


def _images(n, seed):
    """Oriented gratings, one direction a class, two classes overlapping in
    every other image; shapes alternate unevenly."""
    rng = np.random.default_rng(seed)
    images, labels = [], []
    for i in range(n):
        h, w = SHAPES[0 if i % 3 else 1]
        yy, xx = np.mgrid[0:h, 0:w]
        own = [int(rng.integers(0, CLASSES))]
        if i % 2:
            own.append((own[0] + 1) % CLASSES)
        g = np.full((h, w), 128.0)
        for c in own:
            t = c * np.pi / CLASSES + rng.normal(0, 0.1)
            g += 35 * np.cos((xx * np.cos(t) + yy * np.sin(t)) * 0.9 + rng.uniform(0, 6))
        g = g[..., None] + rng.normal(0, 8, (h, w, 3))
        images.append(np.clip(np.rint(g), 0, 255).astype(np.uint8))
        labels.append(sorted(own))
    return images, labels


def _padded(labels):
    out = np.full((len(labels), 2), -1, np.int32)
    for i, row in enumerate(labels):
        out[i, : len(row)] = row
    return out


@pytest.fixture(scope="module")
def data():
    train, train_y = _images(22, 1)
    test, test_y = _images(12, 2)
    return {
        "train": {"x": train, "y": _padded(train_y)},
        "test": {"x": test, "y": _padded(test_y)},
    }


def _split(part):
    return MultiLabeledImages(part["x"], list(part["y"]), [str(i) for i in range(len(part["x"]))])


@pytest.fixture(scope="module")
def fitted(data):
    return voc.run(CONF, _split(data["train"]), _split(data["test"]))


@pytest.fixture(scope="module")
def resident(data):
    """The fit with every training descriptor held, as ``run`` did it before
    the chunked fit: buckets whole through SIFT, ``sample_columns`` on them."""
    sift = voc.sift_node(CONF)
    buckets = {
        shape: (idx, sift(fv_common.grayscale(jnp.asarray(batch))))
        for shape, (idx, batch) in fv_common.bucket_by_shape(data["train"]["x"]).items()
    }
    pca_samples = fv_common.sample_columns(buckets, CONF.num_pca_samples, CONF.seed).T
    pca = BatchPCATransformer(compute_pca(pca_samples, CONF.desc_dim))
    projected = {s: (idx, pca(d)) for s, (idx, d) in buckets.items()}
    gmm_samples = fv_common.sample_columns(projected, CONF.num_gmm_samples, CONF.seed + 1).T
    gmm = GaussianMixtureModelEstimator(CONF.vocab_size).fit(gmm_samples)
    fisher = fv_common.fisher_feature_pipeline(gmm)
    features = np.concatenate([np.asarray(fisher(d)) for _s, (_i, d) in projected.items()])
    return {
        "pca_samples": np.asarray(pca_samples), "gmm_samples": np.asarray(gmm_samples),
        "pca": pca, "gmm": gmm, "features": features,
        "order": np.concatenate([idx for idx, _d in buckets.values()]),
    }


# -- the program against the reference, stage by stage --------------------------


@pytest.mark.parametrize(
    "dtype,most_off,buckets", [(jnp.float32, 1e-3, 2), (jnp.bfloat16, 0.05, 1)]
)
def test_sift_matches_reference(data, dtype, most_off, buckets):
    sift = SIFTExtractor(step_size=6, scale_step=0, compute_dtype=dtype)
    off = entries = 0
    for _idx, batch in list(fv_common.bucket_by_shape(data["test"]["x"]).values())[:buckets]:
        mine = np.asarray(sift(fv_common.grayscale(jnp.asarray(batch))))
        theirs = np.asarray(ref.dense_sift(batch, SIFT))
        assert mine.shape == theirs.shape
        assert mine.shape[2] == ref.num_descriptors(*batch.shape[1:3], SIFT)
        off += int(np.sum(np.abs(mine - theirs) > 1))
        entries += mine.size
    assert off / entries <= most_off


def test_pca_matches_reference(resident):
    mine = np.asarray(resident["pca"].pca_mat)
    theirs = np.asarray(ref.pca_fit(resident["pca_samples"], CONF.desc_dim))
    outside = mine - theirs @ (theirs.T @ mine)
    assert np.linalg.norm(outside) / np.sqrt(CONF.desc_dim) < 1e-3
    # the sign rule makes the leading components equal, not only the subspace
    np.testing.assert_allclose(mine[:, :4], theirs[:, :4], atol=2e-3)


def test_em_matches_reference(resident):
    x = resident["gmm_samples"]
    est = GaussianMixtureModelEstimator(CONF.vocab_size)
    mine = est.fit(x)
    *theirs, iterations = ref.em_fit(x, CONF.vocab_size)
    assert abs(int(est.last_iterations) - iterations) <= 1
    llh_mine = ref.mean_log_likelihood(x, (mine.means, mine.variances, mine.weights))
    llh_theirs = ref.mean_log_likelihood(x, theirs)
    assert abs(llh_mine - llh_theirs) < 1e-3 * abs(llh_theirs)
    np.testing.assert_allclose(np.asarray(mine.weights), np.asarray(theirs[2]), atol=2e-3)


def test_fisher_features_match_reference(data, resident):
    pca, gmm = resident["pca"], resident["gmm"]
    batch = np.stack([im for im in data["test"]["x"] if im.shape[:2] == SHAPES[0]])
    descs = ref.dense_sift(batch, SIFT)
    mine = np.asarray(fv_common.fisher_feature_pipeline(gmm)(pca(descs)))
    theirs = np.asarray(
        ref.fisher_features(descs, pca.pca_mat, gmm.means, gmm.variances, gmm.weights)
    )
    assert mine.shape == (len(batch), 2 * CONF.desc_dim * CONF.vocab_size)
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-3


def test_a_centre_of_weight_zero_has_zero_gradients(data, resident):
    """EM can drive a weight to exactly 0 (its log keeps the centre without
    posterior mass for good); the formula then reads 0 / 0.  Program and
    reference both give that centre zeros, and the rest is unmoved."""
    from keystone_tpu.ops.fisher import FisherVector
    from keystone_tpu.solvers.gmm import GaussianMixtureModel

    pca, gmm = resident["pca"], resident["gmm"]
    weights = np.asarray(gmm.weights).copy()
    weights[2] = 0.0
    dead = GaussianMixtureModel(gmm.means, gmm.variances, jnp.asarray(weights))
    batch = np.stack([im for im in data["test"]["x"] if im.shape[:2] == SHAPES[0]])[:2]
    descs = pca(ref.dense_sift(batch, SIFT))
    fv = np.asarray(FisherVector(dead)(descs))  # [n, d, 2K]
    assert np.isfinite(fv).all()
    k = CONF.vocab_size
    assert not fv[:, :, [2, k + 2]].any() and fv[:, :, [1, k + 1]].any()
    mine = np.asarray(fv_common.fisher_feature_pipeline(dead)(descs))
    theirs = np.asarray(ref.fisher_features(
        ref.dense_sift(batch, SIFT), pca.pca_mat, dead.means, dead.variances, dead.weights
    ))
    assert np.isfinite(theirs).all()
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-3


def test_map_matches_reference_on_overlapping_classes():
    rng = np.random.default_rng(5)
    labels = _padded([sorted({int(c), int((c + k) % 6)}) for c, k in rng.integers(0, 6, (60, 2))])
    scores = rng.normal(size=(60, 6)) + 1.5 * ref.multi_hot(labels, 6)
    mine = mean_average_precision(list(labels), scores, 6)
    theirs = ref.average_precisions(ref.multi_hot(labels, 6), scores)
    np.testing.assert_allclose(mine, theirs, atol=1e-12)
    assert 0.5 < mine.mean() < 1.0


@pytest.fixture(scope="module")
def produced(data, fitted):
    pipeline = load_module("pipelines", "voc_fv")
    return pipeline.produced({"results": fitted, "seed": CONF.seed}, dict(REF_CONF, data=None), data, 3)


def test_fit_matches_reference_end_to_end(data, fitted, produced):
    """The reference's comparison, as the benchmark's ``correct`` makes it:
    every stage of the reference fed what the program produced upstream, the
    program's SIFT read where its sampling pass drew (the chunk programs'
    own output) and spanned by ``fv_gap``."""
    got = ref.compare(REF_CONF, data, 3, produced, {"map": fitted["map"]})
    assert got["sift_images"] == 6
    # every compared image's share of the 1,500 + 1,500 drawn descriptors
    assert got["sift_entries"] >= 128 * 6 * 100
    assert got["sift_off_share"] < 0.05
    for name in ("pca_subspace_gap", "gmm_llh_gap", "em_step_gap", "scores_rms_gap"):
        assert got[name] < 2e-3, (name, got)
    assert got["fv_gap"] < 0.15, got  # spans SIFT's bfloat16 intermediates
    assert got["map_gap"] < 1e-6
    assert fitted["map"] > 0.1  # 4 of the 20 classes have positives


def test_sampled_rows_are_the_drawn_descriptors(data, produced):
    """``compared_chunks`` finds a compared image's rows in the program's
    samples: the reference's descriptors there agree with the program's
    float32 SIFT exactly where it is run in float32."""
    sift = SIFTExtractor(CONF.sift_step_size, scale_step=0)
    train = data["train"]["x"]
    plan = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    draws = [
        fv_common.draw_columns(plan.totals, CONF.num_pca_samples, CONF.seed),
        fv_common.draw_columns(plan.totals, CONF.num_gmm_samples, CONF.seed + 1),
    ]
    samples = fv_common.sample_descriptor_columns(plan, train, sift, draws)
    seen = 0
    for sel, picks in ref.compared_chunks(REF_CONF, train, produced["compare_rows"], CONF.seed):
        descs = np.asarray(ref.dense_sift(np.stack([train[j] for j in sel]), SIFT))
        for (at, im, col), theirs in zip(picks, samples):
            off = np.abs(descs[im, :, col] - np.asarray(theirs)[at]) > 1
            assert off.mean() < 1e-3
            seen += len(at)
    assert seen > 600


def test_control_is_read_over_the_limits_stage_by_stage(data, fitted, produced):
    """The reference in fp8, stage by stage on what the program produced:
    the same comparison reads SIFT, EM and the Fisher vector far off."""
    sound = ref.compare(REF_CONF, data, 3, produced, {})
    ctl = ref.control(REF_CONF, data, produced, "fp8")
    got = ref.compare(REF_CONF, data, 3, ctl, {})
    assert got["sift_off_share"] > 0.2 > 10 * sound["sift_off_share"]
    assert got["fv_gap"] > 0.5 > 3 * sound["fv_gap"]
    assert got["em_step_gap"] > 0.05 > 20 * sound["em_step_gap"]
    # what the control does not touch reads as before
    assert got["pca_subspace_gap"] < 0.05 and produced["gmm_samples"] is ctl["gmm_samples"]


# -- the chunked fit against the resident fit -------------------------------------


@pytest.fixture
def chunk_of_four(monkeypatch):
    monkeypatch.setattr(fv_common, "MAX_CHUNK", 4)


def test_chunked_samples_equal_resident(data, resident, chunk_of_four):
    sift = voc.sift_node(CONF)
    train = data["train"]["x"]
    plan = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    assert max(plan.chunk.values()) == 4 and min(len(i) for i in plan.index.values()) > 4
    draws = [
        fv_common.draw_columns(plan.totals, CONF.num_pca_samples, CONF.seed),
        fv_common.draw_columns(plan.totals, CONF.num_gmm_samples, CONF.seed + 1),
    ]
    pca_samples, gmm_raw = fv_common.sample_descriptor_columns(plan, train, sift, draws)
    np.testing.assert_array_equal(np.asarray(pca_samples), resident["pca_samples"])
    np.testing.assert_allclose(
        np.asarray(gmm_raw @ resident["pca"].pca_mat), resident["gmm_samples"], atol=1e-3
    )


def test_chunked_fit_equals_resident_fit(data, resident, fitted, chunk_of_four):
    """Same samples, so the same model to float32 round-off, whatever the
    chunk: 22 images in chunks of 4 (7 and 15 a bucket, so both last chunks
    are padded) against the buckets held whole."""
    chunked = voc.run(CONF, _split(data["train"]), _split(data["test"]))
    chain = chunked["pipeline"]
    np.testing.assert_allclose(
        np.asarray(chain["pca"].pca_mat), np.asarray(resident["pca"].pca_mat), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(chain["gmm"].means), np.asarray(resident["gmm"].means), rtol=1e-3, atol=1e-3
    )
    # and the same as the fit whose chunks were the whole buckets
    np.testing.assert_allclose(chunked["test_scores"], fitted["test_scores"], atol=2e-3)
    np.testing.assert_allclose(chunked["aps"], fitted["aps"], atol=1e-9)


def test_rows_off_the_chunk_are_padded_and_dropped(data, resident, chunk_of_four):
    sift = voc.sift_node(CONF)
    train = data["train"]["x"]
    plan = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    assert any(len(idx) % plan.chunk[s] for s, idx in plan.index.items())
    feats = fv_common.featurize_chunks(plan, train, sift, resident["pca"], resident["gmm"])
    assert feats.shape == (len(train), 2 * CONF.desc_dim * CONF.vocab_size)
    np.testing.assert_array_equal(plan.order, resident["order"])
    # one fused program a chunk against op by op on a bucket: round-off only
    np.testing.assert_allclose(np.asarray(feats), resident["features"], atol=1e-3)
    assert ref._rel(np.asarray(feats), resident["features"]) < 1e-3


def test_both_passes_run_one_sift_program(data, resident):
    """SIFT's program is the large one (38 MB compiled at VOC's sizes): one a
    shape serves the sampling pass and the featurizing pass."""
    sift = voc.sift_node(CONF)
    train = data["train"]["x"]
    plan = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    draws = [fv_common.draw_columns(plan.totals, 500, 1)]
    fv_common._describe_chunk.clear_cache()
    fv_common.sample_descriptor_columns(plan, train, sift, draws)
    assert fv_common._describe_chunk._cache_size() == len(plan.index) == 2
    fv_common.featurize_chunks(plan, train, sift, resident["pca"], resident["gmm"])
    assert fv_common._describe_chunk._cache_size() == 2


def test_sampling_pass_programs_do_not_move_with_the_draw(data, chunk_of_four):
    """The gathers are padded by ``SAMPLE_CAP_STEP``, so another draw of the
    same size, whose fullest chunk holds a few columns more or fewer, runs
    the programs the first one compiled."""
    sift = voc.sift_node(CONF)
    train = data["train"]["x"]
    plan = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    fv_common._sample_chunk.clear_cache()
    fv_common._gather_samples.clear_cache()
    for seed in (1, 2, 3):
        draws = [fv_common.draw_columns(plan.totals, 700, seed)]
        (rows,) = fv_common.sample_descriptor_columns(plan, train, sift, draws)
        assert rows.shape == (sum(len(d) for d in draws[0].values()), 128)
    # one a shape of a chunk's descriptors, whatever the seed
    assert fv_common._sample_chunk._cache_size() == len({(plan.chunk[s], plan.cols[s][0]) for s in plan.index})
    assert fv_common._gather_samples._cache_size() == 1


@pytest.mark.parametrize("seed", [3, 2865000105])
def test_benchmark_images_have_the_same_shapes_on_every_seed(seed):
    """What moves with the seed is the order of the shapes behind the first
    image of each, never how many images a shape has: the chunk programs a
    fit runs, and with them its padded chunks, are then the same."""
    gen = load_module("datagen", "voc_like")
    params = {
        "shapes": [[12, 16, 0.6], [16, 12, 0.2], [10, 16, 0.2]], "classes": 20,
        "label_share": [0.6, 0.3, 0.1], "periods_px": [6.0, 9.0], "amp_range": [16.0, 37.0],
        "clutter_amp": 17.0, "angle_jitter_deg": 7.0, "gain_range": [0.8, 1.2],
        "mean_level": 128.0, "noise_amp": 26.0,
    }
    data = gen.generate(params, {"train": 41, "test": 24}, seed)
    shapes = [x.shape[:2] for x in data["train"]["x"]]
    assert shapes[:3] == [(12, 16), (16, 12), (10, 16)]
    assert [shapes.count(s) for s in shapes[:3]] == [25, 8, 8]
    assert [x.shape[:2] for x in data["test"]["x"]].count((12, 16)) == 14
    again = gen.generate(params, {"train": 41, "test": 24}, seed)
    assert all(np.array_equal(a, b) for a, b in zip(data["train"]["x"], again["train"]["x"]))


def test_budget_rule_picks_the_chunk(data, monkeypatch):
    sift = voc.sift_node(CONF)
    train = data["train"]["x"]
    monkeypatch.delenv("KEYSTONE_HBM_BUDGET", raising=False)
    whole = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    assert whole.budget is None
    assert whole.chunk == {s: len(i) for s, i in whole.index.items()}
    # descriptors, projections and posteriors of a bucket, in float32
    bucket_bytes = {s: len(i) * whole.image_bytes[s] for s, i in whole.index.items()}
    for shape, (c,) in whole.cols.items():
        assert whole.image_bytes[shape] == 4 * c * (128 + CONF.desc_dim + CONF.vocab_size)
    # a budget whose share holds 5 images: chunks of 4, the power of two below
    tight = int(5 * max(whole.image_bytes.values()) / fv_common.CHUNK_BUDGET_SHARE)
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", str(tight))
    chunked = fv_common.plan_chunks(train, sift, CONF.desc_dim, CONF.vocab_size)
    assert chunked.budget == tight
    assert all(b > fv_common.CHUNK_BUDGET_SHARE * tight for b in bucket_bytes.values())
    assert set(chunked.chunk.values()) == {4}
    # a budget that holds nothing still makes progress, an image at a time
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", "1K")
    assert set(fv_common.plan_chunks(train, sift, 16, 8).chunk.values()) == {1}
    # at VOC's own sizes on a 16 GB chip: never the bucket, never above MAX_CHUNK
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", "15.75G")
    voc07 = fv_common.plan_chunks(
        [np.zeros((375, 500, 3), np.uint8)] * 200, SIFTExtractor(3, scale_step=0), 80, 256
    )
    assert voc07.cols[(375, 500)] == (73866,)
    assert voc07.chunk[(375, 500)] == 64
    assert 200 * voc07.image_bytes[(375, 500)] > voc07.budget


# -- what one fit records ------------------------------------------------------------


def test_stages_spans_and_counters_once_a_fit(data, tmp_path):
    stages = ["sample_descriptors", "pca", "gmm", "featurize", "solve", "eval",
              "featurize_test", "checkpoint"]
    before = trace.metrics.hist_windows()
    counted = trace.metrics.counters()
    conf = voc.SIFTFisherConfig(**{**CONF.__dict__, "pipeline_file": str(tmp_path / "ck")})
    trace.reset()
    trace.enable(str(tmp_path / "spans.json"))
    try:
        results = voc.run(conf, _split(data["train"]), _split(data["test"]))
        events = trace.events()
    finally:
        trace.disable()
        trace.reset()
    after = trace.metrics.hist_windows()
    for stage in stages:
        was = before.get(f"stage_ms.{stage}", {"count": 0})["count"]
        assert after[f"stage_ms.{stage}"]["count"] == was + 1, stage
    now = trace.metrics.counters()
    n = len(data["train"]["x"])

    def grew(name):
        return now.get(name, 0) - counted.get(name, 0)

    assert grew("fv.descriptor_passes") == 2 * n
    assert grew("gmm.iterations") == results["gmm_iterations"] >= 2
    assert 0 < grew("fv.descriptors_sampled") <= CONF.num_pca_samples + CONF.num_gmm_samples
    # a chunk a bucket and pass: two passes of the training rows, one of the test rows
    assert grew("fv.chunks.40x52") == 3 and grew("fv.chunks.52x40") == 3

    spans = [e for e in events if e.get("ph") == "X"]
    root = [e for e in spans if e["cat"] == "fit"]
    assert len(root) == 1 and root[0]["args"]["rows"] == n
    assert sorted(e["name"] for e in spans if e["cat"] == "stage") == sorted(stages)
    assert sum(e["cat"] == "h2d" and e["name"] == "chunk" for e in spans) == 6
    dispatched = [
        e for e in spans
        if e["cat"] == "host" and e["name"] == "dispatch" and e["args"].get("site") == "chunk"
    ]
    assert {e["args"]["bucket"] for e in dispatched} == {"40x52", "52x40"}
    assert {e["cat"] for e in spans} >= {"wait", "d2h"}
    plans = [e for e in events if e.get("ph") == "i" and e["name"] == "fv_plan"]
    assert len(plans) == 2  # the training split's and the test split's
    assert set(plans[0]["args"]) >= {"chunk", "buckets", "chunk_bytes", "budget"}
    assert after["stage_h2d_mb.featurize"]["samples"][-1] == pytest.approx(
        sum(im.nbytes for im in data["train"]["x"]) / 1e6
    )


def test_run_hands_back_chain_and_scores_and_restores(data, tmp_path):
    conf = voc.SIFTFisherConfig(**{**CONF.__dict__, "pipeline_file": str(tmp_path / "ck")})
    test = _split(data["test"])
    first = voc.run(conf, _split(data["train"]), test)
    assert set(first["pipeline"]) == {"pca", "gmm", "model"}
    assert first["test_scores"].shape == (len(test), 20)
    aps = mean_average_precision(test.labels, first["test_scores"], 20)
    np.testing.assert_allclose(aps, first["aps"])
    assert first["solver"]["tier"] == "fused" and not first["solver"]["denials"]
    again = voc.run(conf, MultiLabeledImages([], [], []), test)
    np.testing.assert_allclose(again["test_scores"], first["test_scores"], atol=1e-6)
    assert "solver" not in again
