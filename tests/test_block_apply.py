"""The fitted block model applied as one compiled program
(`solvers/block._block_apply`, `_block_step`): equal to the eager per-block
formula written out here, `Σ scaler_i(blk_i) @ x_i + b` in block order, for
every kind of mapper and input the repo makes, and traced once a shape; and
the fitted model cut into those blocks by one program
(`solvers/block._model_blocks`), equal to the eager slices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.core import trace
from keystone_tpu.core.checkpoint import load_pipeline, save_pipeline
from keystone_tpu.core.pipeline import Identity
from keystone_tpu.ops.stats import StandardScalerModel
from keystone_tpu.ops.util import MaxClassifier
from keystone_tpu.parallel.mesh import make_mesh, row_sharding
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator, BlockLinearMapper

#: uneven fitted widths, every block narrower than the nominal block size
#: (MnistRandomFFT's case): only the model's own widths cut the matrix right
WIDTHS = (12, 7, 16, 5)
BLOCK_SIZE = 16
K = 3
ROWS = 24


def _mapper(rng, b=True, scalers="mean", host=False):
    xp = np.asarray if host else jnp.asarray

    def arr(*shape, low=None):
        a = rng.normal(size=shape) if low is None else rng.uniform(low, 2.0, shape)
        return xp(a.astype(np.float32))

    xs = [arr(w, K) for w in WIDTHS]
    made = {
        None: None,
        "identity": [Identity() for _ in WIDTHS],
        "mean": [StandardScalerModel(arr(w)) for w in WIDTHS],
        "std": [StandardScalerModel(arr(w), arr(w, low=0.5)) for w in WIDTHS],
    }[scalers]
    return BlockLinearMapper(xs, BLOCK_SIZE, arr(K) if b else None, made)


def _blocks(rng, rows=ROWS):
    return [rng.normal(size=(rows, w)).astype(np.float32) for w in WIDTHS]


def _formula(model, blocks):
    """The per-block formula, in float64 on the host."""
    out = 0.0
    for blk, x, scaler in zip(blocks, model.xs, model.feature_scalers):
        blk = np.asarray(blk, np.float64)
        if isinstance(scaler, StandardScalerModel):
            blk = blk - np.asarray(scaler.mean, np.float64)
            if scaler.std is not None:
                blk = blk / np.asarray(scaler.std, np.float64)
        out = out + blk @ np.asarray(x, np.float64)
    return out if model.b is None else out + np.asarray(model.b, np.float64)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("as_list", [False, True], ids=["matrix", "blocks"])
@pytest.mark.parametrize(
    "b, scalers",
    [(True, "mean"), (False, "mean"), (True, None), (True, "identity"), (True, "std")],
    ids=["mean_b", "no_b", "default_scalers", "identity", "std"],
)
def test_compiled_apply_equals_the_per_block_formula(rng, as_list, b, scalers):
    model = _mapper(rng, b=b, scalers=scalers)
    blocks = _blocks(rng)
    batch = [jnp.asarray(blk) for blk in blocks] if as_list else jnp.asarray(
        np.concatenate(blocks, axis=1)
    )
    want = _formula(model, blocks)
    _close(model(batch), want)
    _close(model.apply_blocks([jnp.asarray(blk) for blk in blocks]), want)


def test_compiled_apply_on_a_tuple_of_blocks_and_one_item(rng):
    model = _mapper(rng)
    blocks = _blocks(rng)
    want = _formula(model, blocks)
    _close(model(tuple(jnp.asarray(blk) for blk in blocks)), want)
    row = jnp.asarray(np.concatenate(blocks, axis=1)[5])
    _close(model.apply_item(row), want[5])


def test_compiled_apply_takes_host_blocks(rng):
    """A mapper holding numpy blocks (what a hand-built or host-loaded model
    is) and a numpy batch: the program takes both as they are."""
    model = _mapper(rng, host=True)
    blocks = _blocks(rng)
    _close(model(np.concatenate(blocks, axis=1)), _formula(model, blocks))


def test_restored_mapper_applies_as_the_fitted_one(rng, tmp_path):
    model = _mapper(rng, scalers="std")
    blocks = _blocks(rng)
    batch = jnp.asarray(np.concatenate(blocks, axis=1))
    fitted = np.asarray(model(batch))
    traced = trace.metrics.get("block_apply.traced")
    restored = load_pipeline(save_pipeline(str(tmp_path / "blm"), model))
    assert isinstance(restored, BlockLinearMapper)
    np.testing.assert_array_equal(np.asarray(restored(batch)), fitted)
    _close(fitted, _formula(model, blocks))
    # the restored model is the same pytree: the fitted one's program serves it
    assert trace.metrics.get("block_apply.traced") == traced


def test_row_sharded_input_keeps_its_scores_row_sharded(rng, devices):
    """Rows over a 4-way ``data`` axis, the model replicated: the scores lie
    where the rows are, a device its own rows'."""
    mesh = make_mesh(data=4, model=1, devices=devices[:4])
    model = _mapper(rng)
    blocks = _blocks(rng, rows=32)
    want = _formula(model, blocks)
    model = jax.device_put(model, NamedSharding(mesh, P()))
    batch = jax.device_put(np.concatenate(blocks, axis=1), row_sharding(mesh))
    got = model(batch)
    _close(got, want)
    assert got.sharding.spec[0] == "data"
    assert {s.data.shape for s in got.addressable_shards} == {(8, K)}
    text = jax.jit(lambda m, x: m(x)).lower(model, batch).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text


def test_model_axis_split_classes_apply(rng, devices):
    """A 2x2 mesh with the model's class columns over ``model`` (the mesh
    tier's own layout of its blocks): rows stay over ``data``."""
    mesh = make_mesh(data=2, model=2, devices=devices[:4])
    model = BlockLinearMapper(
        [jnp.asarray(rng.normal(size=(w, 4)), jnp.float32) for w in WIDTHS],
        BLOCK_SIZE,
        jnp.asarray(rng.normal(size=4), jnp.float32),
        [StandardScalerModel(jnp.asarray(rng.normal(size=w), jnp.float32)) for w in WIDTHS],
    )
    blocks = _blocks(rng)
    want = _formula(model, blocks)
    model.xs = [jax.device_put(x, NamedSharding(mesh, P(None, "model"))) for x in model.xs]
    got = model(jax.device_put(np.concatenate(blocks, axis=1), row_sharding(mesh)))
    _close(got, want)
    assert got.sharding.spec[0] == "data"


def test_apply_inlines_into_an_enclosing_program(rng):
    """Called under another trace (the served chain) the apply is a nested
    ``jit``: one enclosing program, the same scores."""
    model = _mapper(rng)
    blocks = _blocks(rng)
    batch = jnp.asarray(np.concatenate(blocks, axis=1))

    @jax.jit
    def served(m, x):
        return MaxClassifier()(m(x))

    np.testing.assert_array_equal(
        np.asarray(served(model, batch)), _formula(model, blocks).argmax(axis=-1)
    )


@pytest.mark.parametrize("as_list", [False, True], ids=["matrix", "blocks"])
@pytest.mark.parametrize("b", [True, False], ids=["b", "no_b"])
def test_streamed_apply_calls_the_evaluator_once_a_block(rng, as_list, b):
    """After block i the evaluator sees the sum of blocks 0..i with the
    intercept added once, and the running sum carries no intercept."""
    model = _mapper(rng, b=b)
    blocks = _blocks(rng)
    batch = [jnp.asarray(blk) for blk in blocks] if as_list else jnp.asarray(
        np.concatenate(blocks, axis=1)
    )
    seen = []
    model.apply_and_evaluate(batch, lambda p: seen.append(np.asarray(p)))
    assert len(seen) == len(WIDTHS)
    for i, got in enumerate(seen):
        head = BlockLinearMapper(
            model.xs[: i + 1], BLOCK_SIZE, model.b, model.feature_scalers[: i + 1]
        )
        _close(got, _formula(head, blocks[: i + 1]))
    _close(seen[-1], np.asarray(model(batch)))


def test_streamed_apply_makes_one_block_at_a_time(rng):
    """An iterable of known length is not turned into a list: a block is
    asked for when the loop reaches it, after the evaluator has seen the
    block before it.  Fifty made blocks (a ``BlockSource`` over the rows to
    score): fifty running sums, the last the dense apply's."""
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.solvers.block import BlockSource

    nb, d, bs = 50, 6, 8
    chains = [CosineRandomFeatures.create(d, bs, 0.5, jax.random.PRNGKey(i)) for i in range(nb)]
    rows = jnp.asarray(rng.normal(size=(ROWS, d)), jnp.float32)
    model = BlockLinearMapper(
        [jnp.asarray(rng.normal(size=(bs, K)), jnp.float32) for _ in range(nb)],
        bs, jnp.asarray(rng.normal(size=K), jnp.float32),
        [StandardScalerModel(jnp.asarray(rng.normal(size=bs), jnp.float32)) for _ in range(nb)],
    )
    order = []

    class Watched:
        def __len__(self):
            return nb

        def __iter__(self):
            for i, blk in enumerate(BlockSource.stacked(rows, chains)):
                order.append(("made", i))
                yield blk

    seen = []

    def evaluator(scores):
        order.append(("seen", len(seen)))
        seen.append(np.asarray(scores))

    model.apply_and_evaluate(Watched(), evaluator)
    assert order == [(what, i) for i in range(nb) for what in ("made", "seen")]
    dense = model(jnp.concatenate([f(rows) for f in chains], axis=1))
    _close(seen[-1], np.asarray(dense))


@pytest.mark.parametrize(
    "batch, message",
    [
        (np.zeros((4, sum(WIDTHS) + 1), np.float32), "wide but the model's blocks sum"),
        ([np.zeros((4, w), np.float32) for w in WIDTHS[:-1]], "feature blocks vs"),
    ],
    ids=["width", "count"],
)
def test_apply_refuses_what_does_not_match_the_fitted_blocks(rng, batch, message):
    model = _mapper(rng)
    with pytest.raises(ValueError, match=message):
        model(batch)
    with pytest.raises(ValueError, match=message):
        model.apply_and_evaluate(batch, lambda p: None)


def _fit_and_score(rng, rows, classes=K):
    d = 40
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(rows, classes)), jnp.float32)
    model = BlockLeastSquaresEstimator(16, 1, 0.1).fit(x, y)
    return np.asarray(MaxClassifier()(model(x)))


def test_a_second_fit_at_the_same_shapes_traces_nothing(rng, tmp_path):
    """`block_apply.traced` counts programs traced, not calls: two fits at
    the same shapes leave it at its first value, a new shape raises it by
    one, and the instant says what was traced."""
    _fit_and_score(rng, rows=56)
    first = trace.metrics.get("block_apply.traced")
    _fit_and_score(rng, rows=56)
    _fit_and_score(rng, rows=56)
    assert trace.metrics.get("block_apply.traced") == first
    trace.reset()
    trace.enable(str(tmp_path / "trace.json"))
    try:
        _fit_and_score(rng, rows=72)
        events = [e for e in trace.events() if e.get("name") == "block_apply"]
    finally:
        trace.disable()
        trace.reset()
    assert trace.metrics.get("block_apply.traced") == first + 1
    assert [e["args"] for e in events] == [
        {"rows": 72, "columns": 40, "blocks": 3, "classes": K, "axes": []}
    ]


def test_streamed_apply_traces_its_step_once_a_shape(rng):
    """Blocks of one width: a first step (no running sum yet) and a later
    step are the two programs of every pass, whatever the number of blocks
    and however often the model is refitted."""
    def run():
        model = BlockLinearMapper(
            [jnp.asarray(rng.normal(size=(9, 2)), jnp.float32) for _ in range(5)],
            9, jnp.asarray(rng.normal(size=2), jnp.float32),
        )
        data = [jnp.asarray(rng.normal(size=(11, 9)), jnp.float32) for _ in range(5)]
        model.apply_and_evaluate(data, lambda p: None)

    before = trace.metrics.get("block_apply.traced")
    run()
    assert trace.metrics.get("block_apply.traced") == before + 2
    run()
    assert trace.metrics.get("block_apply.traced") == before + 2


#: fitted widths a fit cuts its stacked model at: every block full
#: (MnistRandomFFT's 2,048-wide blocks) and a short last block (TIMIT's and
#: RandomPatchCifar's remainder)
SPLIT_WIDTHS = {"full": (16, 16, 16, 16), "short_last": (16, 16, 16, 9)}
#: where a solve leaves its stacked model: one device, rows over a 4-way
#: ``data`` axis with the model replicated (the four-chip cell), and a 2x2
#: mesh whose ``model`` axis splits the classes (the mesh tier's own
#: ``P(None, None, "model")``)
SPLIT_LAYOUTS = {"one_device": None, "data4": (4, 1), "data2_model2": (2, 2)}


@pytest.mark.parametrize("layout", list(SPLIT_LAYOUTS), ids=list(SPLIT_LAYOUTS))
@pytest.mark.parametrize("widths", list(SPLIT_WIDTHS), ids=list(SPLIT_WIDTHS))
def test_model_split_equals_the_eager_slices(rng, devices, widths, layout):
    """The one split program's blocks are the eager slices ``models[i, :w]``
    / ``means[i, :w]`` bit for bit, with their shapes, dtypes and
    shardings: the apply programs keyed on them trace nothing new."""
    from keystone_tpu.solvers.block import _model_blocks

    widths = SPLIT_WIDTHS[widths]
    nb, bs, k = len(widths), max(widths), 4
    models = jnp.asarray(rng.normal(size=(nb, bs, k)), jnp.float32)
    means = jnp.asarray(rng.normal(size=(nb, bs)), jnp.float32)
    if SPLIT_LAYOUTS[layout] is not None:
        data, model = SPLIT_LAYOUTS[layout]
        mesh = make_mesh(data=data, model=model, devices=devices[:4])
        models = jax.device_put(models, NamedSharding(mesh, P(None, None, "model")))
        means = jax.device_put(means, NamedSharding(mesh, P()))
    blocks, mean_blocks = _model_blocks(models, means, widths)
    _, no_means = _model_blocks(models, None, widths)
    assert no_means is None
    for i, w in enumerate(widths):
        for got, want in ((blocks[i], models[i, :w]), (mean_blocks[i], means[i, :w])):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert got.sharding == want.sharding
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_second_fit_splits_its_model_with_the_first_program(rng):
    """`model_blocks.traced` counts the split programs traced and
    `model_blocks.split` the fits cut by one: a second fit at the same
    shapes adds a split, traces no split and no apply program."""
    _fit_and_score(rng, rows=64)
    traced = trace.metrics.get("model_blocks.traced")
    split = trace.metrics.get("model_blocks.split")
    applied = trace.metrics.get("block_apply.traced")
    _fit_and_score(rng, rows=64)
    assert trace.metrics.get("model_blocks.traced") == traced
    assert trace.metrics.get("model_blocks.split") == split + 1
    assert trace.metrics.get("block_apply.traced") == applied


def _spy_split(monkeypatch, module):
    """Records what ``module``'s fit hands the split: the stacked model,
    its means and the fitted widths."""
    seen = []
    real = module.split_model

    def spy(models, means, widths):
        seen.append((models, means, tuple(widths)))
        return real(models, means, widths)

    monkeypatch.setattr(module, "split_model", spy)
    return seen


def _assert_eager_blocks(xs, stacked, widths):
    """``xs`` are ``stacked[i, :w]`` for the fitted widths, bit for bit, with
    the eager slices' shapes, dtypes and shardings."""
    assert len(xs) == len(widths)
    for i, (x, w) in enumerate(zip(xs, widths)):
        want = stacked[i, :w]
        assert x.shape == want.shape and x.dtype == want.dtype
        assert x.sharding == want.sharding
        np.testing.assert_array_equal(np.asarray(x), np.asarray(want))


@pytest.mark.parametrize("layout", ["data4", "data2_model2"])
def test_block_fit_on_a_mesh_keeps_the_eager_blocks(rng, devices, monkeypatch, layout):
    """A fit on four devices (rows over ``data``; on 2x2 the classes over
    ``model`` too): the mapper's model and mean blocks are the eager slices
    of what the mesh tier returned, one split counted."""
    from keystone_tpu.solvers import block

    seen = _spy_split(monkeypatch, block)
    data, model_axis = SPLIT_LAYOUTS[layout]
    mesh = make_mesh(data=data, model=model_axis, devices=devices[:4])
    x = jnp.asarray(rng.normal(size=(64, 40)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
    split = trace.metrics.get("model_blocks.split")
    est = BlockLeastSquaresEstimator(16, 1, 0.1, mesh=mesh)
    model = est.fit(x, y)
    assert est.last_fit_report.chosen == f"fused[mesh {data}x{model_axis}]"
    assert trace.metrics.get("model_blocks.split") == split + 1
    (models, means, widths), = seen
    assert widths == (16, 16, 8)
    _assert_eager_blocks(model.xs, models, widths)
    _assert_eager_blocks([s.mean for s in model.feature_scalers], means, widths)


def test_weighted_fit_keeps_the_eager_blocks(rng, monkeypatch):
    """The class-weighted solver cuts its stacked model by the same program
    (no means): its mapper's blocks are the eager slices, one split
    counted."""
    from keystone_tpu.solvers import weighted

    seen = _spy_split(monkeypatch, weighted)
    classes = rng.integers(0, K, 60)
    x = jnp.asarray(rng.normal(size=(60, 10)) + classes[:, None], jnp.float32)
    y = jnp.asarray(2.0 * np.eye(K)[classes] - 1.0, jnp.float32)
    split = trace.metrics.get("model_blocks.split")
    model = weighted.BlockWeightedLeastSquaresEstimator(4, 2, 0.1, 0.5).fit(x, y)
    assert trace.metrics.get("model_blocks.split") == split + 1
    (models, means, widths), = seen
    assert means is None and widths == (4, 4, 2)
    _assert_eager_blocks(model.xs, models, widths)
