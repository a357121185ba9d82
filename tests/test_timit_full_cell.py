"""TimitPipeline at its documented 50 blocks (`timit_rf_50`, cell
`timit_rf_fit_full`) at small sizes on the CPU: the solver fed *what makes*
its blocks (`solvers.block.BlockSource`) against the same fit fed the blocks
themselves, the rule that picks held or made from bytes (reached here
through ``KEYSTONE_HBM_BUDGET``) and how many made blocks it keeps, the
made form's two tiers, the plain
reference that the cell's ``correct`` rests on and its control, the
reference's block-at-a-time form against the whole form, and what a made fit
records."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from benchmark.lib.compile_meter import CompileMeter
from keystone_tpu.core import memory as kmem
from keystone_tpu.core import trace
from keystone_tpu.core.memory import HBM_BUDGET_ENV
from keystone_tpu.ops.stats import CosineRandomFeatures
from keystone_tpu.parallel.mesh import mask_pad_rows
from keystone_tpu.solvers import block
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


def _fit_made(monkeypatch, est, source, labels, keep=None, **kw):
    """The fit with the budget one byte under what holding the matrix
    needs: the rule makes the blocks, and the made form is admitted.  With
    ``keep``, the budget under which the rule keeps that many of them."""
    monkeypatch.delenv(HBM_BUDGET_ENV, raising=False)
    est.fit(source, labels, **kw)
    assert est.last_fit_report.block_source == "held"
    budget = est.last_fit_report.bcd_plan["held_bytes"] - 1
    if keep is not None:
        budget = _budget_keeping(monkeypatch, est, source, labels, keep)
    monkeypatch.setenv(HBM_BUDGET_ENV, str(budget))
    model = est.fit(source, labels, **kw)
    monkeypatch.delenv(HBM_BUDGET_ENV)
    return model


def _budget_keeping(monkeypatch, est, source, labels, keep):
    """A budget under which the rule makes the blocks and keeps ``keep`` of
    them: ``keep + 1`` slots past the made need and a tenth; for none, a
    budget under the made need plus one kept block."""
    def plan(budget):
        monkeypatch.setenv(HBM_BUDGET_ENV, str(budget))
        return block._plan_bcd(source, labels, est.num_iter, est.block_size)

    made = plan(1)["made_bytes"]
    kept_block = source.rows.shape[0] * source.block_size * 4
    budget = made + kept_block // 2 if keep == 0 else 10 * (made + (keep + 1) * kept_block) // 9 + 10
    got = plan(budget)
    assert (got["block_source"], got["held_blocks"]) == ("made", keep), got
    return budget


def _streamed_scores(model, blocks):
    seen = []
    model.apply_and_evaluate(blocks, seen.append)
    return seen


@pytest.mark.parametrize("keep", ["none", "one", "all_but_one"])
@pytest.mark.parametrize("case, epochs, widths, pad", [
    ("one_epoch", 1, (BS,) * 4, 0),
    ("five_epochs", 5, (BS,) * 4, 0),
    ("short_last_block", 3, (BS, BS, BS, 10), 0),
    ("pad_rows", 3, (BS,) * 4, 24),
])
def test_made_fit_equals_held_fit(rng, monkeypatch, case, epochs, widths, pad, keep):
    """The same blocks, once handed over as arrays and once as the rows and
    the chains that make them: model and test scores to float32 rounding,
    whether the made fit keeps none of its made blocks, one, or all but one.
    No budget reaches all but one here: a kept block is float32 off the TPU,
    so keeping all but one needs what holding the matrix needs, and the rule
    holds it; that program is called as the fit would call it."""
    n = 96
    rows, labels, test, chains = _problem(rng, n, pad=pad)
    nvalid = n if pad else None
    est = BlockLeastSquaresEstimator(BS, epochs, 0.1)
    held = est.fit(_held_blocks(chains, rows, widths, nvalid), labels, nvalid=nvalid)
    assert est.last_fit_report.chosen == "fused"
    source = BlockSource.stacked(rows, chains, widths=None if min(widths) == BS else widths)
    if keep == "all_but_one":
        made = _fit_keeping_all_but_one(est, source, labels, nvalid or n + pad)
    else:
        h = {"none": 0, "one": 1}[keep]
        before = trace.metrics.get("bcd.block_rows_held")
        made = _fit_made(monkeypatch, est, source, labels, keep=h, nvalid=nvalid)
        report = est.last_fit_report
        assert (report.chosen, report.block_source, report.denials) == ("fused[made]", "made", [])
        assert report.bcd_plan["held_blocks"] == h
        assert trace.metrics.get("bcd.block_rows_held") - before == (nvalid or n) * h * epochs
    for a, b in zip(held.xs, made.xs):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(made.b), np.asarray(held.b), rtol=1e-6, atol=1e-7)
    want = held(jnp.concatenate(_held_blocks(chains, test, widths), axis=1))
    seen = _streamed_scores(made, BlockSource.stacked(test, chains, widths=source.widths))
    assert len(seen) == len(chains)
    np.testing.assert_allclose(np.asarray(seen[-1]), np.asarray(want), rtol=2e-4, atol=2e-5)


def _fit_keeping_all_but_one(est, source, labels, nvalid):
    """The fused made program keeping all blocks but the last, on the
    source with its means (what the fit hands it), cut into the model."""
    sums, _ = block.block_moments(source, nvalid)
    source = BlockSource(source.rows, source.featurizers, source.widths, sums / nvalid)
    widths = source.block_widths()
    models, label_mean, means, *_ = block._fused_bcd_fit(
        source, labels, jnp.float32(est.lam), jnp.int32(nvalid), est.num_iter, widths, None,
        hold=len(widths) - 1, hold_dtype="float32",
    )
    return block.BlockLinearMapper(
        [models[i, :w] for i, w in enumerate(widths)], BS, label_mean,
        [block.StandardScalerModel(means[i, :w]) for i, w in enumerate(widths)],
    )


def test_the_rule_keeps_what_fits_beside_the_made_need(rng, monkeypatch):
    """``held_blocks`` from the budget and the bytes: a stack of h + 1 kept
    blocks (the last slot takes the block being made) in what is left past
    the made need and a tenth of the capacity, none where only the made
    need fits, never all of them; a kept block is what the products read,
    so on a TPU (a bfloat16 block) twice as many keep."""
    n = 512
    rows, labels, _, chains = _problem(rng, n)
    source = BlockSource.stacked(rows, chains)

    def plan(budget):
        monkeypatch.setenv(HBM_BUDGET_ENV, str(budget))
        return block._plan_bcd(source, labels, 3, BS)

    held, made = plan(1)["held_bytes"], plan(1)["made_bytes"]
    kept_block = n * BS * 4
    assert made == 4 * (n * D + 4 * (BS * D + BS)) + 4 * (  # rows, chains
        n * K + 2 * n * BS + 2 * n * K + 4 * BS * K  # labels, the made program's temporaries
        + 4 * BS * K + K + 4 * BS + 4 * BS * BS  # models, label mean, means, factors
    )
    assert plan(made + kept_block // 2)["held_blocks"] == 0
    got = plan(10 * (made + 2 * kept_block) // 9 + 10)  # two slots past the made need and a tenth
    assert (got["block_source"], got["held_blocks"]) == ("made", 1)
    assert (got["held_stack_bytes"], got["held_dtype"]) == (2 * kept_block, "float32")
    kept = [plan(b)["held_blocks"] for b in range(made, held, 997)]
    assert kept == sorted(kept) and kept[-1] == 1
    monkeypatch.setattr(block, "_kept_dtype", lambda dtype: np.dtype(jnp.bfloat16))
    got = plan(held - 1)
    assert (got["held_blocks"], got["held_stack_bytes"]) == (3, 4 * kept_block // 2)
    monkeypatch.setattr(block, "_KEEP_HEADROOM", 10**9)  # room for five slots: capped at B - 1
    assert (held - 1 - made) // (kept_block // 2) - 1 > 3 == plan(held - 1)["held_blocks"]


def test_every_fit_of_a_process_keeps_the_same_blocks(rng, monkeypatch):
    """On the chip the budget is the free bytes, which differ between a
    process's first fit and the next (the caller still holds the last
    model): the number kept comes from the device's capacity, so both fits
    keep as many, run the one program planned for the first, and trace
    nothing new; free bytes that do not admit that many cap it."""
    n, nb = 512, 8
    rows, labels, _, chains = _problem(rng, n, nb=nb)
    source = BlockSource.stacked(rows, chains)
    est = BlockLeastSquaresEstimator(BS, 2, 0.1)
    monkeypatch.setenv(HBM_BUDGET_ENV, "1")
    made = block._plan_bcd(source, labels, 2, BS)["made_bytes"]
    monkeypatch.delenv(HBM_BUDGET_ENV)
    kept_block = n * BS * 4
    resident = source.operand_bytes() + labels.nbytes
    capacity = 10 * (made + 5 * kept_block) // 9 + 100  # five slots past the made need and a tenth
    room = [capacity - resident]
    monkeypatch.setattr(kmem, "hbm_capacity", lambda device=None: capacity)
    monkeypatch.setattr(kmem, "hbm_budget", lambda device=None: room[0])
    meter = CompileMeter()
    kept = []
    for free in (capacity - resident, capacity - resident - kept_block // 16):
        room[0] = free
        compiled = kmem.compile_count("bcd_fused_made")
        requests = meter.read()["requests"]
        est.fit(source, labels)
        report = est.last_fit_report
        assert (report.chosen, report.block_source, report.denials) == ("fused[made]", "made", [])
        kept.append(report.bcd_plan["held_blocks"])
    assert kept == [4, 4]
    assert kmem.compile_count("bcd_fused_made") == compiled
    assert meter.read()["requests"] == requests
    room[0] = made - resident + made // 64 + 3 * kept_block + 64  # the caller holds more than a tenth
    est.fit(source, labels)
    report = est.last_fit_report
    assert (report.bcd_plan["held_blocks"], report.chosen, report.denials) == (2, "fused[made]", [])


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
    called: the workload's moments pass, then a gram a block and every
    epoch each block not kept (the chains end in their scalers, so the
    solver takes no means); ``bcd.block_rows_held`` each kept block an
    epoch; ``bcd_source.made`` once; the ``bcd_plan`` instant's bytes.  The
    rehearsal's budget leaves no room to keep a block beside the made need
    and a tenth (``benchmark/tests/test_timit_full.py`` reads 150 passes)."""
    conf = made_fit["conf"]
    n, nb, bs, d, epochs = ROWS["train"], 50, 128, conf["dimension"], conf["num_epochs"]
    counted = made_fit["counted"]
    plan = made_fit["plan"]
    h = plan["held_blocks"]
    assert (h, plan["held_stack_bytes"], plan["held_dtype"]) == (0, 0, "float32")
    assert counted["bcd.block_rows_made"] == n * (2 * nb + (nb - h) * epochs)
    assert counted["bcd.block_rows_held"] == n * h * epochs
    assert counted["bcd_source.made"] == 1 and not counted.get("bcd_source.held")
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
    assert "keystone_bcd_block_rows_held " in surface


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
