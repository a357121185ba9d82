"""MnistRandomFFT at its option parser's defaults (`mnist_fft_200`, cell
`mnist_fft_fit`) at small sizes on the CPU: a solver block's FFT chains as
one node (`ops.stats.RandomFFTBlock`) against the per-FFT chains, the signs
drawn by one program against the list's draws, the run that hands the
solver a `BlockSource` against the run that hands it the blocks (and both
against float64 NumPy), the solver's charge for what a make holds beside
the block, the callers that keep the list form, what a made run counts and
saves, and the cell's own reference, limits and counts."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.core.checkpoint import load_pipeline
from keystone_tpu.core.pipeline import Transformer, node
from keystone_tpu.core.memory import HBM_BUDGET_ENV
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.ops.stats import CosineRandomFeatures, RandomFFTBlock, StandardScaler
from keystone_tpu.ops.util import ZipVectors
from keystone_tpu.solvers import block
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator, BlockSource
from keystone_tpu.workloads import mnist_random_fft as mrf
from keystone_tpu.workloads.timit import FeaturizerBlock

CELL = "mnist_fft_fit"
SEED = 2_147_483_743
D, K, N, NT = 64, 5, 256, 96


def _conf(**over):
    """32 FFTs of 64 pixels (32 features each), two to a block of 64
    columns: sixteen blocks."""
    base = dict(num_ffts=32, block_size=1024, lam=1e6, mnist_image_size=D, num_classes=K, seed=3)
    base.update(over)
    return mrf.MnistRandomFFTConfig(**base)


def _data(rng):
    """Non-negative pixel rows around class centres, as images are."""
    centres = rng.uniform(0, 200, (K, D))

    def split(n):
        labels = rng.integers(0, K, n)
        x = np.clip(centres[labels] + rng.normal(0, 40, (n, D)), 0, 255)
        return LabeledData(labels=labels.astype(np.int32), data=x.astype(np.float32))

    return split(N), split(NT)


@pytest.fixture
def data(rng):
    return _data(rng)


def _list_form(conf, train, test):
    """The run as it hands the solver the blocks themselves."""
    return mrf.run(dataclasses.replace(conf, solve_plan=False), train, test)


def _budget_between(monkeypatch, conf, train):
    """A budget under which the solver makes the blocks: between what the
    made form is charged with and what holding the matrix needs."""
    source = BlockSource(jnp.asarray(train.data), mrf.draw_block_featurizers(conf))
    labels = jnp.zeros((len(train.labels), conf.num_classes))
    monkeypatch.setenv(HBM_BUDGET_ENV, "1")
    plan = block._plan_bcd(source, labels, 1, conf.block_size)
    assert plan["made_bytes"] < plan["held_bytes"], plan
    return (plan["made_bytes"] + plan["held_bytes"]) // 2


@pytest.mark.parametrize("d", [64, 100, 784])
def test_block_node_equals_the_per_fft_chains(rng, d):
    """Column for column: block ``b`` of the stacked node is ZipVectors of
    block ``b``'s RandomSign -> PaddedFFT -> LinearRectifier chains, to
    float32's rounding; and float64 ``np.fft.rfft``'s real part, rectified,
    to 1e-5."""
    conf = _conf(mnist_image_size=d, num_ffts=6, block_size=1536)
    x = rng.uniform(0, 255, (40, d)).astype(np.float32)
    stacked = mrf.draw_block_featurizers(conf)
    chains = mrf.build_featurizer_batches(conf)
    n = 1 << (d - 1).bit_length()
    assert stacked.signs.shape == (2, 3, d)
    for b, group in enumerate(chains):
        want = np.asarray(ZipVectors.apply([chain(jnp.asarray(x)) for chain in group]))
        got = np.asarray(RandomFFTBlock(stacked.signs[b])(jnp.asarray(x)))
        assert got.shape == (40, 3 * n // 2)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * np.abs(want).max())
        signs = np.asarray(stacked.signs[b], np.float64)
        exact = np.concatenate(
            [np.maximum(0.0, np.fft.rfft(x * s, n=n).real[:, : n // 2]) for s in signs], axis=1
        )
        assert np.sqrt(np.mean((got - exact) ** 2) / np.mean(exact**2)) < 1e-5


@pytest.mark.parametrize("d", [64, 100, 784])
def test_the_product_form_counts_its_programs_and_its_table(rng, d):
    """``fft_form.product`` counts a traced program, not its calls, and the
    instant gives the shape the table was built for and its bytes."""
    n = 1 << (d - 1).bit_length()
    node_ = RandomFFTBlock(jnp.asarray(np.sign(rng.normal(size=(2, d))).astype(np.float32)))
    fn = jax.jit(node_.__call__)
    before = trace.metrics.get("fft_form.product")
    for _ in range(3):
        fn(jnp.ones((8, d), jnp.float32))
    assert trace.metrics.get("fft_form.product") == before + 1
    last = [e for e in trace.flight_events() if e["name"] == "fft_form"][-1]
    assert last["args"] == {
        "rows": 8, "n": n, "ffts": 2, "width": d, "dtype": "float32",
        "table_bytes": 2 * d * (n // 2) * 4,
    }


@pytest.mark.parametrize("num_ffts", [8, 7])
def test_one_program_draws_the_signs_by_the_key_splits(num_ffts):
    """``key, sub = split(key)`` an FFT from ``PRNGKey(seed)`` in block
    order, then a Bernoulli sign vector, bit for bit, in the stacked node and
    in the list's chains; a count that fills no last block draws it whole."""
    conf = _conf(num_ffts=num_ffts, block_size=2048)
    key, want = jax.random.PRNGKey(conf.seed), []
    for _ in range(8):
        key, sub = jax.random.split(key)
        want.append(jax.random.bernoulli(sub, 0.5, (D,)).astype(jnp.float32) * 2.0 - 1.0)
    want = np.asarray(want).reshape(2, 4, D)
    np.testing.assert_array_equal(np.asarray(mrf.draw_block_featurizers(conf).signs), want)
    chains = mrf.build_featurizer_batches(conf)
    assert [len(group) for group in chains] == [4, 4]
    for b, group in enumerate(chains):
        for f, chain in enumerate(group):
            np.testing.assert_array_equal(np.asarray(chain.nodes[0].signs), want[b, f])


@pytest.mark.parametrize("form", ["held_source", "made"])
def test_a_run_on_a_source_equals_the_list_form(data, monkeypatch, form):
    """The run hands the solver what makes the blocks: held by the solver's
    rule where no budget is known, made (tier ``fused[made]``) under a
    budget between the made and held needs; scores to 1e-5 and the same
    predictions as the run that hands it the blocks, on both splits."""
    train, test = data
    conf = _conf()
    monkeypatch.delenv(HBM_BUDGET_ENV, raising=False)
    listed = _list_form(conf, train, test)
    assert isinstance(listed["featurizers"], list)
    if form == "made":
        monkeypatch.setenv(HBM_BUDGET_ENV, str(_budget_between(monkeypatch, conf, train)))
    got = mrf.run(conf, train, test)
    report = got["fit_report"]
    assert isinstance(got["featurizers"], RandomFFTBlock)
    assert (report.chosen, report.bcd_plan["block_source"]) == (
        ("fused", "held") if form == "held_source" else ("fused[made]", "made")
    )
    for split in ("train", "test"):
        np.testing.assert_allclose(
            np.asarray(got[f"{split}_scores"]), np.asarray(listed[f"{split}_scores"]),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(got[f"{split}_predictions"]), np.asarray(listed[f"{split}_predictions"])
        )
        assert got[f"{split}_error"] == listed[f"{split}_error"]


def _numpy_fit(conf, train, test):
    """Float64 NumPy: signs, ``np.fft.rfft``'s real part, the rectifier,
    each block centred by its own means, one block coordinate sweep."""
    signs = np.asarray(mrf.draw_block_featurizers(conf).signs, np.float64)
    n = 1 << (conf.mnist_image_size - 1).bit_length()

    def feats(x, s):
        x = np.asarray(x, np.float64)
        return np.concatenate(
            [np.maximum(0.0, np.fft.rfft(x * si, n=n).real[:, : n // 2]) for si in s], axis=1
        )

    y = 2.0 * np.eye(conf.num_classes)[train.labels] - 1.0
    intercept = y.mean(0)
    residual = y - intercept
    scores = np.zeros((len(test.labels), conf.num_classes)) + intercept
    for s in signs:
        a = feats(train.data, s)
        mu = a.mean(0)
        a -= mu
        m = np.linalg.solve(a.T @ a + conf.lam * np.eye(a.shape[1]), a.T @ residual)
        residual -= a @ m
        scores += (feats(test.data, s) - mu) @ m
    return scores


def test_both_forms_equal_float64_numpy(data, monkeypatch):
    train, test = data
    conf = _conf()
    want = _numpy_fit(conf, train, test)
    monkeypatch.delenv(HBM_BUDGET_ENV, raising=False)
    runs = [_list_form(conf, train, test)]
    monkeypatch.setenv(HBM_BUDGET_ENV, str(_budget_between(monkeypatch, conf, train)))
    runs.append(mrf.run(conf, train, test))
    assert runs[1]["fit_report"].chosen == "fused[made]"
    for got in runs:
        scores = np.asarray(got["test_scores"], np.float64)
        gap = np.sqrt(np.mean((scores - want) ** 2) / np.mean(want**2))
        assert gap < 2e-3, gap
        assert np.mean(np.asarray(got["test_predictions"]) != want.argmax(1)) <= 0.02


def _cosine_source(rows):
    chains = [
        FeaturizerBlock([
            CosineRandomFeatures.create(D, 64, 0.3, jax.random.PRNGKey(i)),
            StandardScaler().fit(jnp.ones((4, 64))),
        ])
        for i in range(16)
    ]
    return BlockSource.stacked(rows, chains)


def test_the_plan_charges_what_a_make_holds(rng, monkeypatch):
    """What a make holds beyond its block (``_make_scratch``: the compiled
    make's temporaries less the block, whose room the centred copy leaves
    free then) joins the made need, and fewer blocks are kept than the same
    budget keeps without it.  The FFT's make is one product against its
    table, charged less than its block; a cosine block is its product's
    output, charged nothing."""
    rows = jnp.asarray(rng.uniform(0, 255, (N, D)).astype(np.float32))
    labels = jnp.zeros((N, K))
    fft = BlockSource(rows, mrf.draw_block_featurizers(_conf()))
    cosine = _cosine_source(rows)
    assert 0 <= block._make_scratch(fft) < N * fft.block_size * 4
    assert block._make_scratch(cosine) == 0
    monkeypatch.setenv(HBM_BUDGET_ENV, "1")
    assert block._plan_bcd(cosine, labels, 1, 1024)["make_scratch_bytes"] == 0
    scratch = 3 * N * 64 * 4  # three blocks of 64 columns

    def plans(budget):
        monkeypatch.setenv(HBM_BUDGET_ENV, str(budget))
        out = []
        for charge in (scratch, 0):
            monkeypatch.setattr(block, "_make_scratch", lambda s, c=charge: c)
            out.append(block._plan_bcd(fft, labels, 1, 1024))
        return out

    charged, free = plans(1)
    assert (charged["make_scratch_bytes"], free["make_scratch_bytes"]) == (scratch, 0)
    assert charged["made_bytes"] == free["made_bytes"] + scratch
    charged, free = plans((charged["made_bytes"] + charged["held_bytes"]) // 2)
    assert charged["block_source"] == free["block_source"] == "made"
    assert 0 < charged["held_blocks"] < free["held_blocks"], (charged, free)


def test_the_make_is_compiled_for_its_charge_once_a_shape(data, monkeypatch):
    """The first fit of a shape compiles the make to read its temporaries;
    a later fit of that shape finds the figure cached and compiles nothing
    for it, so the charge costs a warm-up fit and never a timed one."""
    train, test = data
    conf = _conf()
    monkeypatch.setenv(HBM_BUDGET_ENV, str(_budget_between(monkeypatch, conf, train)))
    block._make_scratch_of.cache_clear()
    for _ in range(2):
        assert mrf.run(conf, train, test)["fit_report"].chosen == "fused[made]"
    info = block._make_scratch_of.cache_info()
    assert (info.misses, info.currsize) == (1, 1) and info.hits >= 1, info


@node(data_fields=("w",), meta_fields=())
class _RectifiedProjection(Transformer):
    """``max(0, x @ w)``: a column whose weights are all negative is zero on
    every row of non-negative pixels."""

    def __init__(self, w):
        self.w = w

    def __call__(self, x):
        return jnp.maximum(0.0, x @ self.w)


@pytest.mark.parametrize("tier", ["fused[made]", "stepwise[made]"])
def test_a_column_zero_on_every_row_gets_no_weight(rng, monkeypatch, tier):
    """At lambda 0 a rectified column that is never positive leaves its
    block's system singular.  The solver's moments pass finds it and gives
    it a unit diagonal, as a pad column has, so the fit is finite, the
    column's weight is 0, and the rest is float64's solve without it."""
    n, d, bs, nb, k = 200, 12, 8, 3, 4
    rows = rng.uniform(0, 1, (n, d)).astype(np.float32)
    w = rng.normal(0, 1, (nb, d, bs)).astype(np.float32)
    w[1, :, 5] = -np.abs(w[1, :, 5])
    labels = rng.normal(size=(n, k)).astype(np.float32)
    source = BlockSource(jnp.asarray(rows), _RectifiedProjection(jnp.asarray(w)))
    monkeypatch.setenv(HBM_BUDGET_ENV, "1")
    plan = block._plan_bcd(source, jnp.asarray(labels), 1, bs)
    budget = (plan["made_bytes"] + plan["held_bytes"]) // 2 if tier == "fused[made]" else 1024
    monkeypatch.setenv(HBM_BUDGET_ENV, str(budget))
    est = BlockLeastSquaresEstimator(bs, 1, 0.0)
    got = [np.asarray(m) for m in est.fit(source, jnp.asarray(labels)).xs]
    assert est.last_fit_report.chosen == tier
    assert np.all(np.isfinite(got)) and np.all(got[1][5] == 0)
    residual = labels - labels.mean(0)
    for b in range(nb):
        a = np.maximum(0.0, rows.astype(np.float64) @ w[b])
        a -= a.mean(0)
        live = np.any(a != 0, axis=0)
        want = np.zeros((bs, k))
        want[live] = np.linalg.solve(a[:, live].T @ a[:, live], a[:, live].T @ residual)
        residual = residual - a @ want
        np.testing.assert_allclose(got[b], want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("asks", [
    {"mesh": "mesh"}, {"solve_checkpoint": "path"}, {"solve_resume": "path"},
    {"solve_plan": False}, {"auto_shard": True}, {"auto_cache": True},
])
def test_what_a_source_fit_does_not_do_keeps_the_list_form(asks):
    asks = dict(asks)
    mesh = asks.pop("mesh", None)
    assert not mrf._made_form(_conf(**asks), mesh)
    assert mrf._made_form(_conf(), None)


def test_a_checkpointed_solve_runs_on_the_blocks(data):
    """``fit`` refuses a checkpointed source; the run hands it the blocks."""
    train, test = data
    states = []
    got = mrf.run(_conf(solve_checkpoint=states.append), train, test)
    assert got["fit_report"].chosen == "stepwise[checkpoint]"
    assert isinstance(got["featurizers"], list) and len(states) == 16


def test_a_made_run_counts_its_fft_blocks(data, monkeypatch):
    """The solver's counters, rows x blocks at every call that makes them:
    ``bcd.block_rows_made`` its moments pass and its fused program (blocks +
    blocks not kept), ``bcd.block_rows_applied`` both splits' streamed
    apply; ``bcd_plan`` says what a make holds, less than its block; the
    cell's width takes the product form."""
    train, test = data
    conf = _conf()
    monkeypatch.setenv(HBM_BUDGET_ENV, str(_budget_between(monkeypatch, conf, train)))
    before = dict(trace.metrics.counters())
    got = mrf.run(conf, train, test)
    after = trace.metrics.counters()
    counted = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    plan = got["fit_report"].bcd_plan
    nb, h = 16, plan["held_blocks"]
    assert plan["make_scratch_bytes"] < N * 64 * 4 and plan["passes_a_block"] == 3
    assert counted["bcd.block_rows_made"] == N * (nb + nb + (nb - h))
    assert counted["bcd.block_rows_applied"] == (N + NT) * nb
    assert after.get("fft_form.product", 0) >= 1


def test_the_servable_checkpoint_scores_as_the_fit_did(data, tmp_path):
    """The saved chain (one ``RandomFFTBlock`` a group, unstacked from the
    fit's node, the model, the classifier) scores bit for bit what the fit
    path's streamed apply scored, and a second run restores it."""
    train, test = data
    stem = str(tmp_path / "servable")
    got = mrf.run(_conf(pipeline_file=stem), train, test)
    servable = load_pipeline(stem)
    featurize, model = servable.nodes[0], servable.nodes[1]
    assert len(featurize.groups) == 16
    np.testing.assert_array_equal(
        np.asarray(model(featurize(jnp.asarray(test.data)))), np.asarray(got["test_scores"])
    )
    np.testing.assert_array_equal(np.asarray(servable(jnp.asarray(test.data))), got["test_predictions"])
    again = mrf.run(_conf(pipeline_file=stem), train, test)
    assert again["restored"] and again["test_error"] == got["test_error"]


# -- the cell's own files, at the rehearsal's sizes ---------------------------


def _cell_conf(**over) -> dict:
    conf = manifest.resized(manifest.cell(CELL)["config"], True)
    conf.update(over)
    return conf


@pytest.fixture(scope="module")
def cell_data():
    rows = manifest.resized(manifest.cell(CELL)["traffic"], True)["rows"]
    return manifest.load_module("datagen", "digits_like").generate(_cell_conf()["data"], rows, SEED)


def test_the_digits_have_an_empty_border_and_pixel_values(cell_data):
    conf = _cell_conf()
    side, box = conf["data"]["side"], conf["data"]["box"]
    x = np.asarray(cell_data["train"]["x"]).reshape(-1, side, side)
    edge = (side - box) // 2
    assert x[:, :edge].max() == x[:, -edge:].max() == x[:, :, :edge].max() == 0
    assert x.min() >= 0 and x.max() <= 255 and np.array_equal(x, np.round(x))
    assert len(np.unique(cell_data["train"]["y"])) == conf["data"]["classes"]


def test_the_references_padded_fft_is_numpys(rng):
    """The cosine table times the signed rows is the real part of the first
    half of ``np.fft.rfft`` of the rows padded to the next power of two."""
    reference = manifest.load_module("reference", "mnist_fft")
    x = rng.uniform(0, 255, (20, 100))
    got = np.asarray(x @ np.asarray(reference.cosine_table(100), np.float64))
    np.testing.assert_allclose(got, np.fft.rfft(x, n=128).real[:, :64], rtol=1e-5, atol=1e-3)


def test_the_reference_equals_float64_numpy(cell_data):
    """A block at a time, the reference's scores are the float64 sweep's."""
    reference = manifest.load_module("reference", "mnist_fft")
    conf = _cell_conf()
    with jax.default_matmul_precision("highest"):
        ref = reference.fit(conf, cell_data, 7, "highest")
    mc = mrf.MnistRandomFFTConfig(
        num_ffts=conf["num_ffts"], block_size=conf["block_size"], lam=conf["lam"],
        mnist_image_size=conf["mnist_image_size"], num_classes=conf["num_classes"], seed=7,
    )
    split = {k: LabeledData(labels=cell_data[k]["y"], data=np.asarray(cell_data[k]["x"])) for k in ("train", "test")}
    want = _numpy_fit(mc, split["train"], split["test"])
    gap = np.sqrt(np.mean((ref["test_scores"] - want) ** 2) / np.mean(want**2))
    assert gap < 1e-3, gap


def test_the_cell_is_inside_its_limits_and_the_control_outside(cell_data, monkeypatch):
    """One fit through the cell's pipeline file under the rehearsal's budget
    lands on ``fused[made]`` and inside the limits against
    ``benchmark/reference/mnist_fft.py``; the reference with every
    product's operands rounded to float8 is outside them."""
    conf = _cell_conf()
    for key, value in conf["env"].items():
        monkeypatch.setenv(key, value)
    pipeline = manifest.load_module("pipelines", "mnist_fft")
    reference = manifest.load_module("reference", "mnist_fft")
    seed = pipeline.program_seed(SEED)
    out = pipeline.fit(conf, cell_data, seed, "unused")
    assert pipeline.fit_report(out) == {"tier": "fused[made]", "denials": [], "oom_retries": []}
    produced = pipeline.produced(out, conf, cell_data, SEED)
    with jax.default_matmul_precision("highest"):
        ref = reference.fit(conf, cell_data, seed, "highest")
        values = reference.compare(conf, cell_data, SEED, produced, ref)
        control = reference.fit(conf, cell_data, seed, conf["compare"]["control_precision"])
        off = reference.compare(conf, cell_data, SEED, control, ref)
    for name, limit in conf["limits"].items():
        assert values[name] <= limit, (name, values)
        assert not off[name] <= limit, (name, off)


def test_counts_do_not_grow_with_the_passes():
    """``made_fft_bcd`` is the training blocks' FFTs once and the block
    solve, held against two layers: the mathematics, whatever number of
    passes a program makes."""
    counts = manifest.load_module("counts", "mnist_fft")
    conf = {"mnist_image_size": 784, "num_ffts": 200, "block_size": 2048, "num_classes": 10, "num_iters": 1}
    rows = {"train": 60000, "test": 10000}
    kernel = counts.kernels(conf, rows)["made_fft_bcd"]
    bcd = manifest.load_module("counts", "cifar_rp").bcd(60000, [2048] * 50, 10, 1)
    fft = 2.5 * 1024 * 10 * 60000 * 200
    assert kernel["layers"] == ["featurizers", "solvers"]
    assert kernel["flops"] == bcd["flops"] + fft
    assert kernel["bytes"] == bcd["bytes"] + 4.0 * 50 * (60000 * 784 + 4 * 784 + 60000 * 2048)
    total = counts.fit(conf, rows)
    assert total["fft"]["flops"] == 2.5 * 1024 * 10 * 70000 * 200
    assert total["total_flops"] == total["fft"]["flops"] + bcd["flops"] + total["predict"]["flops"]
