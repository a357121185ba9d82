"""TimitPipeline at its documented 50 blocks (`timit_rf_50`, cell
`timit_rf_fit_full`) at small sizes on the CPU: the solver fed *what makes*
its blocks (`solvers.block.BlockSource`) against the same fit fed the blocks
themselves, the rule that picks held or made from bytes (reached here
through ``KEYSTONE_HBM_BUDGET``), the made form's two tiers, the plain
reference that the cell's ``correct`` rests on and its control, the
reference's block-at-a-time form against the whole form, and what a made fit
records."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.core.memory import HBM_BUDGET_ENV
from keystone_tpu.ops.stats import CosineRandomFeatures
from keystone_tpu.parallel.mesh import mask_pad_rows
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator, BlockSource

CELL = "timit_rf_fit_full"
SEED = 2_147_483_711
ROWS = {"train": 512, "test": 128}

D, BS, K = 12, 16, 4


def _chains(nb):
    """Cosine features with no scaler behind them: the blocks' means are far
    from zero, so a wrong mean shows."""
    return [
        CosineRandomFeatures.create(D, BS, 0.3, jax.random.PRNGKey(i)) for i in range(nb)
    ]


def _problem(rng, n, nb=4, pad=0):
    rows = rng.normal(size=(n, D)).astype(np.float32)
    labels = rng.normal(size=(n, K)).astype(np.float32)
    if pad:
        rows = np.pad(rows, ((0, pad), (0, 0)))
        labels = np.pad(labels, ((0, pad), (0, 0)))
    test = rng.normal(size=(40, D)).astype(np.float32)
    return jnp.asarray(rows), jnp.asarray(labels), jnp.asarray(test), _chains(nb)


def _held_blocks(chains, rows, widths, nvalid=None):
    return [mask_pad_rows(f(rows), nvalid)[:, :w] for f, w in zip(chains, widths)]


def _fit_made(monkeypatch, est, source, labels, **kw):
    """The fit with the budget one byte under what holding the matrix
    needs: the rule makes the blocks, and the made form is admitted."""
    monkeypatch.delenv(HBM_BUDGET_ENV, raising=False)
    est.fit(source, labels, **kw)
    assert est.last_fit_report.block_source == "held"
    monkeypatch.setenv(HBM_BUDGET_ENV, str(est.last_fit_report.bcd_plan["held_bytes"] - 1))
    model = est.fit(source, labels, **kw)
    monkeypatch.delenv(HBM_BUDGET_ENV)
    return model


def _streamed_scores(model, blocks):
    seen = []
    model.apply_and_evaluate(blocks, seen.append)
    return seen


@pytest.mark.parametrize("case, epochs, widths, pad", [
    ("one_epoch", 1, (BS,) * 4, 0),
    ("five_epochs", 5, (BS,) * 4, 0),
    ("short_last_block", 3, (BS, BS, BS, 10), 0),
    ("pad_rows", 3, (BS,) * 4, 24),
])
def test_made_fit_equals_held_fit(rng, monkeypatch, case, epochs, widths, pad):
    """The same blocks, once handed over as arrays and once as the rows and
    the chains that make them: model and test scores to float32 rounding."""
    n = 96
    rows, labels, test, chains = _problem(rng, n, pad=pad)
    nvalid = n if pad else None
    est = BlockLeastSquaresEstimator(BS, epochs, 0.1)
    held = est.fit(_held_blocks(chains, rows, widths, nvalid), labels, nvalid=nvalid)
    assert est.last_fit_report.chosen == "fused"
    source = BlockSource.stacked(rows, chains, widths=None if min(widths) == BS else widths)
    made = _fit_made(monkeypatch, est, source, labels, nvalid=nvalid)
    report = est.last_fit_report
    assert (report.chosen, report.block_source, report.denials) == ("fused[made]", "made", [])
    for a, b in zip(held.xs, made.xs):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(made.b), np.asarray(held.b), rtol=1e-6, atol=1e-7)
    want = held(jnp.concatenate(_held_blocks(chains, test, widths), axis=1))
    seen = _streamed_scores(made, BlockSource.stacked(test, chains, widths=source.widths))
    assert len(seen) == len(chains)
    np.testing.assert_allclose(np.asarray(seen[-1]), np.asarray(want), rtol=2e-4, atol=2e-5)


def test_a_source_that_fits_is_held_by_the_held_program(rng, monkeypatch):
    """No budget known (or room for the matrix): the source's blocks are
    written side by side once and the fit is the one a list of arrays gets,
    bit for bit."""
    monkeypatch.delenv(HBM_BUDGET_ENV, raising=False)
    rows, labels, _, chains = _problem(rng, 96)
    est = BlockLeastSquaresEstimator(BS, 2, 0.1)
    held = est.fit(_held_blocks(chains, rows, (BS,) * 4), labels)
    assert est.last_fit_report.bcd_plan["operand_bytes"] == 0
    from_source = est.fit(BlockSource.stacked(rows, chains), labels)
    plan = est.last_fit_report.bcd_plan
    assert (est.last_fit_report.chosen, plan["block_source"], plan["passes_a_block"]) == ("fused", "held", 1)
    for a, b in zip(held.xs, from_source.xs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stepwise_made_is_the_floor_and_agrees(rng, monkeypatch):
    rows, labels, _, chains = _problem(rng, 96)
    est = BlockLeastSquaresEstimator(BS, 3, 0.1)
    source = BlockSource.stacked(rows, chains)
    fused = _fit_made(monkeypatch, est, source, labels)
    monkeypatch.setenv(HBM_BUDGET_ENV, "1K")
    before = trace.metrics.get("bcd.block_rows_made")
    stepwise = est.fit(source, labels)
    report = est.last_fit_report
    assert (report.chosen, report.denials) == ("stepwise[made]", ["fused[made]"])
    assert list(report.plans) == ["fused[made]", "stepwise[made]"]  # no host_staged rung
    # the solver's own moments pass, a factor and three steps a block
    assert trace.metrics.get("bcd.block_rows_made") - before == 96 * 4 * (1 + 1 + 3)
    for a, b in zip(fused.xs, stepwise.xs):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-5)


def test_a_list_of_arrays_still_runs_the_held_program(rng, monkeypatch):
    """Whatever the budget says, blocks a caller hands over are held: the
    rule reads bytes only where there is something to make them from."""
    monkeypatch.setenv(HBM_BUDGET_ENV, "1K")
    rows, labels, _, chains = _problem(rng, 96)
    est = BlockLeastSquaresEstimator(BS, 1, 0.1)
    est.fit(_held_blocks(chains, rows, (BS,) * 4), labels)
    report = est.last_fit_report
    assert report.block_source == "held" and not report.chosen.endswith("[made]")
    assert "fused[made]" not in report.plans


def test_a_made_source_under_a_mesh_is_refused_by_name(rng, mesh8):
    rows, labels, _, chains = _problem(rng, 96)
    est = BlockLeastSquaresEstimator(BS, 1, 0.1, mesh=mesh8)
    with pytest.raises(ValueError, match="BlockSource.*does not run under a mesh"):
        est.fit(BlockSource.stacked(rows, chains), labels)
    with pytest.raises(ValueError, match="BlockSource fit cannot be checkpointed"):
        BlockLeastSquaresEstimator(BS, 1, 0.1).fit(
            BlockSource.stacked(rows, chains), labels, checkpoint=lambda state: None
        )


# -- the cell's own files, at the rehearsal's sizes ---------------------------


def _conf(**over) -> dict:
    conf = manifest.resized(manifest.cell(CELL)["config"], True)
    conf.update(over)
    return conf


@pytest.fixture(scope="module")
def data():
    datagen = manifest.load_module("datagen", "gaussian_classes")
    return datagen.generate(_conf()["data"], ROWS, SEED)


@pytest.fixture(scope="module")
def made_fit(data):
    """One fit through the cell's pipeline file under the rehearsal's
    budget, what it produced, and what the registry and the flight ring saw."""
    pipeline = manifest.load_module("pipelines", "timit_rf_full")
    conf = _conf()
    with pytest.MonkeyPatch.context() as mp:
        for key, value in conf["env"].items():
            mp.setenv(key, value)
        before = dict(trace.metrics.counters())
        out = pipeline.fit(conf, data, pipeline.program_seed(SEED), "unused")
        after = dict(trace.metrics.counters())
    plans = [e for e in trace.flight_events() if e["name"] == "bcd_plan"]
    return {
        "conf": conf, "pipeline": pipeline, "out": out,
        "produced": pipeline.produced(out, conf, data, SEED),
        "counted": {k: after.get(k, 0) - before.get(k, 0) for k in after},
        "plan": plans[-1]["args"],
    }


def test_the_cell_lands_on_the_made_tier_with_no_denial(made_fit):
    assert made_fit["pipeline"].fit_report(made_fit["out"]) == {
        "tier": "fused[made]", "denials": [], "oom_retries": [],
    }
    results = made_fit["out"]["results"]
    assert {"model", "featurizers", "test_scores", "test_predictions", "fit_report"} <= set(results)
    assert len(results["model"].xs) == 50


def test_the_cell_is_inside_its_limits_and_the_control_outside(made_fit, data):
    """Against ``benchmark/reference/timit_rf_full.py``, by the comparison
    and the limits that decide the cell's ``correct``; the same reference
    with every product's operands rounded to float8 is outside them."""
    reference = manifest.load_module("reference", "timit_rf_full")
    conf = made_fit["conf"]
    seed = made_fit["pipeline"].program_seed(SEED)
    with jax.default_matmul_precision("highest"):
        ref = reference.fit(conf, data, seed, "highest")
        values = reference.compare(conf, data, SEED, made_fit["produced"], ref)
        control = reference.fit(conf, data, seed, conf["compare"]["control_precision"])
        off = reference.compare(conf, data, SEED, control, ref)
    assert conf["limits"]
    for name, limit in conf["limits"].items():
        assert values[name] <= limit, (name, values)
    assert any(not off[name] <= limit for name, limit in conf["limits"].items()), off


def test_the_reference_a_block_at_a_time_equals_the_whole_form(data):
    conf = _conf(num_cosines=3)
    whole = manifest.load_module("reference", "timit_rf").fit(conf, data, 7, "highest")
    blocks = manifest.load_module("reference", "timit_rf_full").fit(conf, data, 7, "highest")
    assert sorted(whole) == sorted(blocks)
    for name in ("feature_mean", "feature_std", "test_scores"):
        np.testing.assert_allclose(blocks[name], whole[name], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(blocks["test_predictions"], whole["test_predictions"])


def test_a_made_fit_says_what_ran(made_fit):
    """``bcd.block_rows_made`` from the shapes where the making programs are
    called: the workload's moments pass, then a gram and every epoch a block
    (the chains end in their scalers, so the solver takes no means);
    ``bcd_source.made`` once; the ``bcd_plan`` instant's bytes."""
    conf = made_fit["conf"]
    n, nb, bs, d, epochs = ROWS["train"], 50, 128, conf["dimension"], conf["num_epochs"]
    counted = made_fit["counted"]
    assert counted["bcd.block_rows_made"] == n * nb * (epochs + 2)
    assert counted["bcd_source.made"] == 1 and not counted.get("bcd_source.held")
    plan = made_fit["plan"]
    assert plan == made_fit["out"]["results"]["fit_report"].bcd_plan
    operands = 4 * (n * d + nb * (bs * d + 4 * bs))  # rows; W, b, mean, std, block means
    assert {key: plan[key] for key in (
        "rows", "blocks", "block_width", "block_source", "passes_a_block",
        "matrix_bytes", "operand_bytes", "factor_bytes", "block_bytes",
    )} == {
        "rows": n, "blocks": nb, "block_width": bs, "block_source": "made",
        "passes_a_block": epochs + 1, "matrix_bytes": 4 * n * nb * bs, "operand_bytes": operands,
        "factor_bytes": 4 * nb * bs * bs, "block_bytes": 4 * n * bs,
    }
    assert plan["made_bytes"] <= plan["budget_bytes"] < plan["held_bytes"]
    from keystone_tpu.core import telemetry

    surface = telemetry.prometheus_text()
    assert "keystone_bcd_block_rows_made " in surface and "keystone_bcd_source_made " in surface


def test_counts_do_not_grow_with_the_passes():
    """``made_bcd`` is a training block's features once and the block solve:
    the mathematics, whatever number of passes a program makes."""
    counts = manifest.load_module("counts", "timit_rf_full")
    conf = {"dimension": 4, "num_cosine_features": 8, "num_cosines": 3, "num_classes": 2, "num_epochs": 5}
    rows = {"train": 10, "test": 6}
    kernel = counts.kernels(conf, rows)["made_bcd"]
    bcd = manifest.load_module("counts", "cifar_rp").bcd(10, [8, 8, 8], 2, 5)
    assert kernel["layer"] == "solvers"
    assert kernel["flops"] == bcd["flops"] + 2 * 10 * 4 * 8 * 3
    assert kernel["bytes"] == bcd["bytes"] + 4 * 3 * (40 + 32 + 80)
    assert counts.fit(conf, rows)["total_flops"] == manifest.load_module("counts", "timit_rf").fit(conf, rows)["total_flops"]
