"""Block linear models: feature-dimension model parallelism
(reference src/main/scala/nodes/learning/BlockLinearMapper.scala:21-204).

The reference splits the feature axis into blocks (VectorSplitter), solves
block coordinate descent over them, and applies the model block-by-block with
a partial-sum reduce over zipped RDDs.  Here blocks are slices of an HBM
array, and applying the fitted model is one compiled program a shape
(``_block_apply``: every block's slice and centring an operand of its MXU
gemm, the fitted model the program's argument, so a refit compiles
nothing); the streaming ``applyAndEvaluate`` form is preserved for models
wider than memory, one step program a block (``_block_step``).
"""

from __future__ import annotations

import functools
import io
import logging
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import autoshard
from ..core import memory as kmem
from ..core import numerics as knum
from ..core import profiler as kprof
from ..core import trace
from ..core.checkpoint import CheckpointError, _atomic_write_bytes
from ..core.pipeline import Identity, LabelEstimator, Transformer
from ..ops.stats import StandardScalerModel
from ..ops.util import VectorSplitter
from ..parallel.collectives import count_psum
from ..parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    current_mesh,
    enumerate_meshes,
    mesh_desc,
    on_one_device,
    pad_shard_inputs,
    reduced_mesh,
    row_sharding,
    split_axes,
)

_logger = logging.getLogger("keystone_tpu.solvers.block")


class BlockLinearMapper(Transformer):
    """Linear model stored as feature blocks
    (reference BlockLinearMapper.scala:21-137).

    xs: list of [d_i, k] weight blocks; b: optional [k] intercept;
    feature_scalers: per-block transformers applied before the gemm.
    """

    def __init__(
        self,
        xs: Sequence,
        block_size: int,
        b=None,
        feature_scalers: Sequence[Transformer] | None = None,
    ):
        self.xs = list(xs)
        self.block_size = block_size
        self.b = b
        self.feature_scalers = (
            list(feature_scalers)
            if feature_scalers is not None
            else [Identity() for _ in self.xs]
        )
        self.vector_splitter = VectorSplitter(block_size)

    def _split_features(self, batch):
        """Cut a concatenated [n, D] feature matrix into this model's OWN
        fitted block widths.  The nominal ``vector_splitter`` (block_size
        cuts) only agrees with the fitted blocks when every block except
        the last is exactly block_size wide; a model fit on pre-split
        batches narrower than block_size (MnistRandomFFT's per-FFT-group
        batches) needs the true widths — the serving path applies the model
        to ``GroupConcatFeaturizer``'s concatenation and must recover the
        fit-path blocks bit-exactly."""
        widths = [int(x.shape[0]) for x in self.xs]
        if int(batch.shape[-1]) != sum(widths):
            raise ValueError(
                f"feature matrix is {int(batch.shape[-1])} wide but the "
                f"model's blocks sum to {sum(widths)} ({widths})"
            )
        out = []
        i = 0
        for w in widths:
            out.append(batch[..., i : i + w])
            i += w
        return out

    def _blocks_of(self, batch_or_blocks):
        """The input as this model's feature blocks: a concatenated matrix
        cut at the fitted widths; anything else (a list of blocks, a
        :class:`BlockSource` over the rows to score) is an iterable of known
        length and is handed back as it is, so that a source makes a block
        when the caller's loop reaches it."""
        if hasattr(batch_or_blocks, "shape"):
            blocks = self._split_features(batch_or_blocks)
        else:
            blocks = batch_or_blocks
        if len(blocks) != len(self.xs):
            raise ValueError(
                f"{len(blocks)} feature blocks vs {len(self.xs)} model blocks"
            )
        return blocks

    def apply_blocks(self, blocks: Sequence):
        """Apply to pre-split feature blocks (reference :47-74)."""
        return _block_apply(self, list(blocks))

    def __call__(self, batch):
        """Scores of a concatenated [n, D] matrix or of a list of blocks:
        one compiled program a shape, this model its argument."""
        return _block_apply(self, batch)

    def apply_and_evaluate(
        self, batch_or_blocks, evaluator: Callable[[jnp.ndarray], None]
    ):
        """Invoke ``evaluator`` on the running prediction after each block —
        streaming evaluation without materializing all block products
        (reference BlockLinearMapper.scala:104-137).  Blocks that are made
        as the loop reaches them (a :class:`BlockSource`) are alive one at a
        time: each is dropped after its step."""
        running = None
        blocks = iter(self._blocks_of(batch_or_blocks))
        for i, (x, scaler) in enumerate(zip(self.xs, self.feature_scalers)):
            # one section a block: its one step program (after the program
            # that makes it, where the blocks come from a source), then the
            # evaluator's round trip to the host (its ``wait`` and ``d2h``
            # nest here and are charged as themselves)
            with trace.host("dispatch", "block", block=i):
                blk = next(blocks)
                running, with_intercept = _block_step(
                    running, blk, x, scaler, self.b
                )
                del blk
                evaluator(with_intercept)


jax.tree_util.register_pytree_node(
    BlockLinearMapper,
    lambda m: ((m.xs, m.b, m.feature_scalers), m.block_size),
    lambda block_size, kids: BlockLinearMapper(
        kids[0], block_size, kids[1], kids[2]
    ),
)


def _add_block(running, blk, x, scaler):
    """The running scores plus one block's: the block centred by its own
    scaler, times its own model.  The order is the fit's (the model was
    fitted on centred blocks, and one bf16 pass rounds a product's
    *operands*), so nothing here is re-associated."""
    part = scaler(blk) @ x
    return part if running is None else running + part


def _count_block_apply(blocks: Sequence, xs: Sequence) -> None:
    """Counted where a block-apply program is traced: once a process and
    shape, so a second fit at the same shapes leaves the counter alone."""
    trace.metrics.inc("block_apply.traced")
    trace.instant(
        "block_apply",
        rows=int(np.prod(blocks[0].shape[:-1])),
        columns=sum(int(blk.shape[-1]) for blk in blocks),
        blocks=len(blocks),
        classes=int(xs[0].shape[-1]),
        axes=list(split_axes(blocks[0])),
    )


@jax.jit
def _block_apply(model: BlockLinearMapper, batch):
    """The dense apply, ``Σ scaler_i(blk_i) @ x_i + b`` in block order, as
    one program: the slices and the centring fuse into the products'
    operands, so no ``[N, block]`` copy is written.  The fitted model is an
    argument, so every fit at the same shapes runs the same executable."""
    blocks = model._blocks_of(batch)
    _count_block_apply(blocks, model.xs)
    out = None
    for blk, x, scaler in zip(blocks, model.xs, model.feature_scalers):
        out = _add_block(out, blk, x, scaler)
    return out if model.b is None else out + model.b


@jax.jit
def _block_step(running, blk, x, scaler, b):
    """One step of the streamed apply: (the running sum with this block's
    scores added, the same with the intercept)."""
    _count_block_apply([blk], [x])
    running = _add_block(running, blk, x, scaler)
    return running, running if b is None else running + b


@functools.partial(jax.jit, static_argnames="widths")
def _model_blocks(models, means, widths):
    """The fitted ``[B, bs, k]`` model and its ``[B, bs]`` block means (or
    ``None``) cut at the fitted widths, every block's ``models[i, :w]`` and
    ``means[i, :w]`` in one program: a fit pays one dispatch for its split,
    not two a block.  Each output is ``models[i, :w]`` (``means[i, :w]``)
    exactly: its values, shape, dtype and the sharding the slice
    propagates, so the programs that apply the model trace nothing new."""
    trace.metrics.inc("model_blocks.traced")
    blocks = tuple(models[i, :w] for i, w in enumerate(widths))
    if means is None:
        return blocks, None
    return blocks, tuple(means[i, :w] for i, w in enumerate(widths))


def split_model(models, means, widths):
    """``(model blocks, mean blocks)`` of a finished solve by
    :func:`_model_blocks`, counted once a fit (``model_blocks.split``)."""
    trace.metrics.inc("model_blocks.split")
    return _model_blocks(models, means, tuple(int(w) for w in widths))


class BlockSource:
    """A design matrix's feature blocks as *what makes them*: the raw rows
    ``[N, d]`` and one pure featurizer a block, its parameters arrays (the
    reference's ``fit(Seq[RDD], ...)`` over lazy chains,
    BlockLinearMapper.scala:156-203: a block is computed from the rows when
    the sweep reaches it).

    ``featurizers`` is ONE transformer pytree whose every array leaf carries
    a leading block axis (:meth:`stacked` makes it from a list); block ``i``
    is ``featurizers[i](rows)``, ``[N, bs]``.  ``widths``: the true width of
    each block where some are narrower than ``bs`` (their columns past it
    are made zero, as ``_blocked_design_matrix`` pads them).  ``means``
    ``[B, bs]``: the blocks' column means over the valid rows where the
    caller has them (a scaler at the chain's end makes them zero); ``None``
    and the solver takes them in one more pass (``_block_moments``), which
    also tells it ``zero_columns`` ``[B, bs]``: 1.0 where a column is zero
    on every valid row (a rectified feature never positive), whose system
    is then singular at lambda 0; the solver gives such a column a unit
    diagonal, as it gives a pad column, so its coefficient is 0.

    ``BlockLeastSquaresEstimator.fit`` holds the blocks as one matrix when
    that fits the device and makes them inside its programs otherwise;
    iterating a source makes one block an item (``_make_block``), which is
    how ``apply_and_evaluate`` streams a test split.
    """

    def __init__(self, rows, featurizers, widths=None, means=None, zero_columns=None):
        self.rows = rows
        self.featurizers = featurizers
        self.widths = None if widths is None else tuple(int(w) for w in widths)
        self.means = means
        self.zero_columns = zero_columns

    @classmethod
    def stacked(cls, rows, featurizers: Sequence, **kw) -> "BlockSource":
        """From a list of same-shaped featurizer pytrees, their leaves
        stacked along a new block axis."""
        with trace.host("place", "featurizers"):
            return cls(
                rows, jax.tree.map(lambda *a: jnp.stack(a), *featurizers), **kw
            )

    def __len__(self) -> int:
        return int(jax.tree.leaves(self.featurizers)[0].shape[0])

    def featurizer(self, i):
        """Block ``i``'s featurizer; ``i`` may be traced."""
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            self.featurizers,
        )

    @functools.cached_property
    def _block_aval(self):
        """Shape and dtype of one made block, worked out abstractly once."""
        one = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), self.featurizers
        )
        rows = jax.ShapeDtypeStruct(self.rows.shape, self.rows.dtype)
        return jax.eval_shape(lambda f, r: f(r), one, rows)

    @property
    def block_size(self) -> int:
        return int(self._block_aval.shape[-1])

    @property
    def dtype(self):
        return self._block_aval.dtype

    def block_widths(self) -> tuple:
        return self.widths or (self.block_size,) * len(self)

    def operand_bytes(self) -> int:
        """What a program that makes the blocks holds in their place."""
        return kmem.array_bytes(
            *jax.tree.leaves((self.rows, self.featurizers, self.means, self.zero_columns))
        )

    def make(self, i):
        """Block ``i`` ``[N, bs]``, columns past its width zero; every row
        is made, pad rows too (callers mask them).  Traceable."""
        xi = self.featurizer(i)(self.rows)
        widths = self.block_widths()
        if min(widths) < xi.shape[-1]:
            keep = jnp.arange(xi.shape[-1]) < jnp.asarray(widths)[i]
            xi = jnp.where(keep, xi, 0)
        return xi

    def __iter__(self):
        """The blocks one by one, each made when it is asked for and cut to
        its own width: what ``apply_and_evaluate`` streams."""
        for i, w in enumerate(self.block_widths()):
            trace.metrics.inc("bcd.block_rows_applied", int(self.rows.shape[0]))
            yield _make_block(self, i)[:, :w]


jax.tree_util.register_pytree_node(
    BlockSource,
    lambda s: ((s.rows, s.featurizers, s.means, s.zero_columns), s.widths),
    lambda widths, kids: BlockSource(kids[0], kids[1], widths, kids[2], kids[3]),
)


@jax.jit
def _make_block(source: BlockSource, i):
    """One made block as an array of its own (the streamed apply's input);
    ``i`` is traced, so one program serves every block."""
    return source.make(i)


def _count_blocks_made(rows: int, blocks: int) -> None:
    """``bcd.block_rows_made``: training rows x blocks made, reckoned from
    shapes where the programs that make them are called (every pass); the
    streamed apply counts its own makes, ``bcd.block_rows_applied``."""
    trace.metrics.inc("bcd.block_rows_made", int(rows) * int(blocks))


def _make_scratch(source: BlockSource) -> int:
    """Bytes a make of one block holds beyond the block itself: the
    compiled ``_make_block``'s temporaries less one block, whose room the
    centred copy that ``_made_need`` charges leaves free while the make
    runs.  Zero for a featurizer whose product is the block.  Compiled once
    a process, shape and device."""
    leaves, treedef = jax.tree.flatten(source)
    return _make_scratch_of(
        treedef, tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves),
        getattr(source.rows, "sharding", None),
    )


@functools.lru_cache(maxsize=None)
def _make_scratch_of(treedef, leaves: tuple, where) -> int:
    shapes = jax.tree.unflatten(
        treedef, [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where) for a in leaves]
    )
    with trace.host("plan", "make_scratch"):
        analysis = _make_block.lower(
            shapes, jax.ShapeDtypeStruct((), jnp.int32, sharding=where)
        ).compile().memory_analysis()
    temps = 0 if analysis is None else int(analysis.temp_size_in_bytes)
    block = shapes.rows.shape[0] * shapes.block_size * np.dtype(shapes.dtype).itemsize
    return max(0, temps - block)


def _valid_rows(n: int, nvalid):
    return (jnp.arange(n) < nvalid)[:, None]


def block_moments(source: BlockSource, nvalid: int):
    """(Σx, Σx²) of every made block's columns over the first ``nvalid``
    rows, ``[B, bs]`` each: one pass over the blocks, none of them kept
    (program ``_block_moments``).  A workload's scaler takes its mean and
    deviation from it, and the solver a source's block means where the
    source states none."""
    _count_blocks_made(nvalid, len(source))
    if not on_one_device(source.rows):  # the column sums cross the chips
        count_psum(2 * len(source) * source.block_size * source.dtype.itemsize)
    return _block_moments(source, nvalid)


@jax.jit
def _block_moments(source: BlockSource, nvalid):
    valid = _valid_rows(source.rows.shape[0], nvalid)

    def one(_, i):
        xi = jnp.where(valid, source.make(i), 0)
        return None, (jnp.sum(xi, axis=0), jnp.sum(xi * xi, axis=0))

    _, sums = jax.lax.scan(one, None, jnp.arange(len(source)))
    return sums


@jax.jit
def _hold_blocks(source: BlockSource, nvalid):
    """The made blocks side by side: the ``[N, B*bs]`` matrix of
    ``_blocked_design_matrix``'s contract (pad rows and pad columns zero),
    for a source whose matrix fits the device."""
    n, bs = source.rows.shape[0], source.block_size
    valid = _valid_rows(n, nvalid)

    def one(x, i):
        xi = jnp.where(valid, source.make(i), 0)
        return jax.lax.dynamic_update_slice_in_dim(x, xi, i * bs, axis=1), None

    x0 = jnp.zeros((n, len(source) * bs), source.dtype)
    return jax.lax.scan(one, x0, jnp.arange(len(source)))[0]


def _block(x, mu, i, bs: int):
    """(block ``i`` of the design matrix ``[N, bs]``, its column means
    ``[bs]``): sliced out of a held matrix and of ``mu`` ``[B*bs]``, or made
    from the rows of a :class:`BlockSource` that carries its means (``mu``
    is then None).  The one place the solver's programs differ between the
    two forms."""
    if isinstance(x, BlockSource):
        return x.make(i), jax.lax.dynamic_index_in_dim(x.means, i, keepdims=False)
    return (
        jax.lax.dynamic_slice_in_dim(x, i * bs, bs, axis=1),
        jax.lax.dynamic_slice_in_dim(mu, i * bs, bs, axis=0),
    )


def _fused_bcd_impl(x, labels, lam, nvalid, num_iter: int, widths, mesh,
                    specs=None, hold: int = 0, hold_dtype=None):
    """The ENTIRE block-least-squares fit as one compiled program.

    Centering (label + per-block feature means over the ``nvalid`` true
    rows), pad-row masking, the per-block grams, the Cholesky factors, and
    ``num_iter`` BCD epochs (a lax.scan over epochs around a lax.scan over
    blocks) all fuse into a single XLA executable instead of dozens of
    eager dispatches.  The reference's analog is one Spark job per block
    (BlockLinearMapper.scala:147-204); ours is one program per fit.

    x: ONE [N, B*bs] design matrix with bs = max(widths); feature block i
    occupies columns [i*bs, i*bs + widths[i]) and everything else — pad
    columns of short blocks AND rows at index >= nvalid — must be zero
    (``fit`` and ``pad_shard_inputs`` guarantee both).  Each scan step
    dynamic-slices its block out of ``x`` and materializes the centered
    masked copy of THAT block only, so peak HBM is one design matrix plus a
    single [N, bs] block — the round-4 form stacked all blocks into a
    [B, N, bs] tensor plus a centered copy, transiently TRIPLING the
    design-matrix footprint, which capped the largest fittable solve at a
    third of HBM.  Pad columns get a unit diagonal shift (their gram rows
    are zero, so their solutions are exactly zero and the factorization
    stays positive-definite even at lam=0).

    ``x`` may instead be a :class:`BlockSource` with its ``means`` (no
    mesh): each scan step then *makes* its block from the rows and the
    block's featurizer where the held form slices it, so nothing wider than
    one block is ever alive and the featurizer runs ``num_iter + 1`` times a
    block.  Everything else (grams, factors, steps, the epoch scan, the
    pad-column shift, the update order) is the same code.

    With ``mesh``: rows shard over the data axis (grams lower to local
    MXU gram + ICI all-reduce), models/labels' class columns shard over the
    model axis.  ``specs`` (static; a sorted tuple of
    ``(operand, spec-string)`` pairs from a searched spec assignment —
    core.autoshard ISSUE 10) overrides the per-operand layout: ``"x"``
    defaults to ``data@dim0``, ``"labels"`` to the caller's placement,
    ``"models"`` to ``model@dim2``; each chosen spec lowers through
    ``autoshard.spec_sharding`` into the very ``NamedSharding`` constraint
    executed here, so a searched layout is REAL, not just byte accounting.
    ``specs=None`` is bit-for-bit the PR 9 program.

    Returns (models [B, bs, k], label_mean [k], means [B, bs]); for a
    ``BlockSource`` also the factor stack ``[B, bs, bs]``, the fit's one
    large state (3.36 GB at fifty blocks of 4,096).  As a result it is a
    buffer the device's allocator owns and counts — what ``memory_stats()``,
    the live budget of the next admission and a plan's ``out_bytes`` read —
    where as a temporary it is program scratch that none of them sees; the
    bytes are the same either way, and the held form, whose matrix is the
    large thing, keeps its three results.

    ``hold`` (static, a source only): the first ``hold`` made blocks are
    **kept** — made once, centred and masked, as ``hold_dtype`` (what the
    products read of them: :func:`_kept_dtype`) into a ``[hold + 1, N, bs]``
    stack whose last slot takes each later block when a pass reaches it;
    every gram and step reads its block from the stack, so each pass makes
    only the blocks past the kept ones, in the same order.  The results are
    then (models, label_mean, means, the factors, the stack), the stack a
    result for the same reason as the factors.  ``hold=0`` is the program
    above, unchanged.
    """
    bs = max(widths)
    nb = len(widths)
    dtype = labels.dtype
    n = labels.shape[0]

    col_spec = None
    mrow_spec = None
    if mesh is not None:
        sp = dict(specs) if specs else {}
        x = jax.lax.with_sharding_constraint(
            x, autoshard.spec_sharding(sp.get("x", "data@dim0"), mesh, 2)
        )
        lspec = sp.get("labels")
        if lspec is not None:
            labels = jax.lax.with_sharding_constraint(
                labels, autoshard.spec_sharding(lspec, mesh, 2)
            )
        mspec = sp.get("models", "model@dim2")
        if mspec == "model@dim2":
            col_spec = NamedSharding(mesh, P(None, None, MODEL_AXIS))
            mrow_spec = NamedSharding(mesh, P(None, MODEL_AXIS))
        elif mspec != "replicated":  # replicated: no constraint at all
            raise ValueError(f"unsupported models spec {mspec!r}")

    mask = (jnp.arange(n) < nvalid).astype(dtype)[:, None]
    nv = jnp.asarray(nvalid, dtype)
    label_mean = jnp.sum(labels * mask, axis=0) / nv
    residual = (labels - label_mean) * mask
    if isinstance(x, BlockSource):
        mu, means = None, x.means
    else:
        # All block means in one gemv (pad rows are zero by contract).
        mu = (mask[:, 0] @ x) / nv  # [B*bs]
        means = mu.reshape(nb, bs)

    def centered_block(i):
        """(x_block_i - mean_i) * row_mask — the per-step [N, bs] transient
        (identical numerics to centering the whole matrix, without ever
        materializing more than one centered block)."""
        xi, mu_i = _block(x, mu, i, bs)
        return (xi - mu_i) * mask, mu_i

    pad_diag = jnp.stack(
        [
            (jnp.arange(bs) >= w).astype(dtype)  # 1.0 on pad columns
            for w in widths
        ]
    )
    if isinstance(x, BlockSource) and x.zero_columns is not None:
        pad_diag = pad_diag + x.zero_columns  # zero on every row: as a pad column

    # Regularized grams, factored once (they are constant across epochs —
    # the reference caches them the same way via its gram RDD persist).
    def factor(a_i, pd):
        reg = a_i.T @ a_i + jnp.diag(lam + pd)
        return jsl.cho_factor(reg)[0]

    def gram_one(_, inp):
        i, pd = inp
        a_i, _ = centered_block(i)
        return None, factor(a_i, pd)

    def step(res, a_i, c_i, m_i):
        r_i = res + a_i @ m_i
        atb = a_i.T @ r_i  # rows contract over the data axis -> one psum
        m_new = jsl.cho_solve((c_i, False), atb)
        if mrow_spec is not None:
            m_new = jax.lax.with_sharding_constraint(m_new, mrow_spec)
        return r_i - a_i @ m_new, m_new

    def block_step(res, inp):
        i, c_i, m_i = inp
        a_i, _ = centered_block(i)
        return step(res, a_i, c_i, m_i)

    if hold:
        # Made blocks [0, hold) kept, each as the products read it, in a
        # stack of hold + 1 slots; the last slot takes each block past them
        # when a pass reaches it.  Every gram and every step reads its block
        # from the stack: one program body each, in the held form's order.
        def make(i):
            return centered_block(i)[0].astype(hold_dtype)

        def block_at(kept, i, first):
            # block i >= first made into the last slot by a loop that runs
            # once or never: it updates the stack where it lies, where a
            # conditional would copy it
            kept = jax.lax.fori_loop(
                0, (i >= first).astype(jnp.int32),
                lambda _, k: jax.lax.dynamic_update_index_in_dim(k, make(i), hold, 0),
                kept,
            )
            a_i = jax.lax.dynamic_index_in_dim(kept, jnp.minimum(i, hold), 0, False)
            return kept, a_i.astype(dtype)

        def gram_kept(kept, inp):
            i, pd = inp
            kept, a_i = block_at(kept, i, hold + 1)
            return kept, factor(a_i, pd)

        def kept_step(carry, inp):
            res, kept = carry
            i, c_i, m_i = inp
            kept, a_i = block_at(kept, i, hold)
            res, m_new = step(res, a_i, c_i, m_i)
            return (res, kept), m_new

        def kept_epoch(carry, _):
            models, res, kept = carry
            (res, kept), models = jax.lax.scan(
                kept_step, (res, kept), (jnp.arange(nb), chol, models)
            )
            return (models, res, kept), None

        _, kept = jax.lax.scan(lambda _, i: (None, make(i)), None, jnp.arange(hold + 1))
        kept, chol = jax.lax.scan(gram_kept, kept, (jnp.arange(nb), pad_diag))
        models = jnp.zeros((nb, bs, labels.shape[1]), dtype)
        (models, _, kept), _ = jax.lax.scan(
            kept_epoch, (models, residual, kept), None, length=num_iter
        )
        return models, label_mean, means, chol, kept

    _, chol = jax.lax.scan(gram_one, None, (jnp.arange(nb), pad_diag))

    models = jnp.zeros((nb, bs, labels.shape[1]), dtype)
    if col_spec is not None:
        models = jax.lax.with_sharding_constraint(models, col_spec)

    def epoch(carry, _):
        models, residual = carry
        residual, models = jax.lax.scan(
            block_step, residual, (jnp.arange(nb), chol, models)
        )
        return (models, residual), None

    (models, residual), _ = jax.lax.scan(
        epoch, (models, residual), None, length=num_iter
    )
    if isinstance(x, BlockSource):
        return models, label_mean, means, chol
    return models, label_mean, means


@functools.lru_cache(maxsize=None)
def _fused_bcd_fit_variant(donate_argnums: tuple = ()):
    """jit of the fused fit with a chosen donation set.  ``(0, 1)`` donates
    the design matrix and labels, letting XLA reuse their HBM for the
    residual/centered-block temps instead of doubling the footprint —
    callers donate only buffers THEY own (host-uploaded or padded copies),
    never a caller-visible passthrough array (VERDICT r5 weak #1)."""
    return jax.jit(
        _fused_bcd_impl,
        static_argnames=("num_iter", "widths", "mesh", "specs", "hold", "hold_dtype"),
        donate_argnums=donate_argnums,
    )


#: The historical non-donating entry point (benches AOT-lower this one).
_fused_bcd_fit = _fused_bcd_fit_variant(())


def _single_device_arrays(*arrays) -> bool:
    """True when no argument is a multi-device (sharded) jax.Array — the
    precondition for executing an AOT program planned on unsharded avals
    (its baked SingleDeviceSharding would reject sharded inputs)."""
    for a in jax.tree.leaves(arrays):
        if isinstance(a, jax.Array):
            try:
                if len(a.sharding.device_set) > 1:
                    return False
            except Exception:  # noqa: BLE001 — unknown sharding: be safe
                return False
    return True


def _execute_fused_bcd(plan, donate_argnums, x, labels, lam, nvalid,
                       num_iter: int, widths):
    """Dispatch the fused program: the planned AOT executable when admission
    ran (so the very program that was planned is the one executed), else the
    jitted variant (jit-cache-friendly when no budget is known, and the
    resilient fallback when a caller hands SHARDED arrays to a mesh-less
    fit — the planned executable baked single-device placements).  Module
    level so the fault harness can intercept it (tests inject
    RESOURCE_EXHAUSTED here to exercise the ladder's step-down)."""
    if (
        plan is not None
        and plan.compiled is not None
        and _single_device_arrays(x, labels)
    ):
        return plan.compiled(x, labels, lam, nvalid)
    return _fused_bcd_fit_variant(donate_argnums)(
        x, labels, lam, nvalid, num_iter, widths, None
    )


def _execute_fused_bcd_mesh(plan, x, labels, lam, nvalid, num_iter: int,
                            widths, mesh, specs=None):
    """Dispatch the GSPMD fused program for one mesh-ladder tier (``specs``:
    the tier's searched per-operand layout assignment, hashable, or None
    for the default layout).  The jitted entry — not ``plan.compiled`` —
    is used deliberately: an AOT executable bakes committed input
    shardings and scalar placements that a later call's padded inputs need
    not match exactly, while the jit cache keys on the same
    (aval, sharding) signature and reuses its own compilation.  Module
    level so the chaos harness can inject RESOURCE_EXHAUSTED here to drive
    the mesh ladder's step-down (the ``spec_mispredict`` family kills the
    top-ranked spec-sharded plan at this very dispatch)."""
    del plan
    if mesh.size == 1:
        # A mesh of one device is one device: no constraint of the mesh
        # program does anything there, so the one-device dispatch serves it
        # (and whatever stands in for that dispatch sees this fit too).
        return _execute_fused_bcd(
            None, (), x, labels, lam, nvalid, num_iter, widths
        )
    return _fused_bcd_fit(x, labels, lam, nvalid, num_iter, widths, mesh,
                          specs)


def _bcd_spec_variants(m) -> list[dict]:
    """Per-operand spec assignments the BCD placement search enumerates
    for one mesh shape, beyond the strategy's default layout (row-sharded
    inputs, model-axis-sharded model columns): model-axis-sharded label
    columns (the wide-class layout), fully-replicated model blocks, and
    fully-replicated small operands.  Every entry is legal by
    construction — the class axis is padded to a model-axis multiple
    before execution — and deterministic, so two searches over one device
    set enumerate identical candidates."""
    d_sz, m_sz = m.shape[DATA_AXIS], m.shape[MODEL_AXIS]
    out: list[dict] = []
    if m_sz > 1:
        out.append({"labels": "model@dim1"})
        out.append({"models": "replicated"})
    if d_sz * m_sz > 1:
        out.append({"labels": "replicated", "models": "replicated"})
    return out


def _blocked_design_matrix(features, block_size: int, num_features=None,
                           nvalid=None):
    """(x, widths): the [N, B*bs] zero-padded blocked layout _fused_bcd_fit
    consumes, from either a monolithic [N, d] array or a list of pre-split
    feature blocks (the reference's fit(Seq[RDD]) form), or from a
    :class:`BlockSource` whose matrix fits the device: its blocks made once
    and written side by side by one program (``nvalid`` says which of its
    rows are true).

    Monolithic input with d a block_size multiple is passed through with NO
    copy — the common production shape (d = 2·2·descDim·vocabSize etc.) pays
    zero extra HBM.  Anything needing column padding costs one copy (np.pad
    host-side for host arrays, so nothing transient lands on device).
    """
    if isinstance(features, BlockSource):
        if nvalid is None:
            nvalid = int(features.rows.shape[0])
        _count_blocks_made(nvalid, len(features))
        return _hold_blocks(features, nvalid), features.block_widths()
    if isinstance(features, (list, tuple)):
        widths = tuple(int(b.shape[1]) for b in features)
        bs = max(widths)
        host = not any(isinstance(b, jax.Array) for b in features)
        xp = np if host else jnp
        parts = [
            xp.pad(xp.asarray(b), ((0, 0), (0, bs - w))) if w < bs else xp.asarray(b)
            for b, w in zip(features, widths)
        ]
        return xp.concatenate(parts, axis=1), widths
    d = num_features or features.shape[1]
    if d > features.shape[1]:
        # Silent clamping here once produced wrong models with no error:
        # widths were computed from d while the matrix stayed narrower, so
        # dynamic_slice re-read the previous block's columns (ADVICE r5).
        raise ValueError(
            f"num_features={d} exceeds the actual feature count "
            f"{features.shape[1]} — the blocked-design contract requires "
            "num_features <= features.shape[1]"
        )
    widths = tuple(
        min(block_size, d - i) for i in range(0, d, block_size)
    )
    bs = max(widths)
    features = features[:, :d]
    col_pad = len(widths) * bs - d
    if col_pad:
        xp = jnp if isinstance(features, jax.Array) else np
        features = xp.pad(xp.asarray(features), ((0, 0), (0, col_pad)))
    return features, widths


def _design_matrix_owned(x, features) -> bool:
    """True when the blocked design matrix ``x`` is a buffer this fit
    created (a host array whose device upload will be ours, or a fresh
    padded/concatenated device copy) — the precondition for donating it.
    A trivial full slice of a monolithic device input returns the SAME
    array object (jnp aliases it), so identity checks are exact."""
    if not isinstance(x, jax.Array):
        return True  # host: the jnp.asarray device copy belongs to the fit
    if x is features:
        return False
    if isinstance(features, (list, tuple)) and any(x is b for b in features):
        return False
    return True


@functools.partial(jax.jit, static_argnames=("bs",))
def _bcd_block_factor(x, mu, mask, lam, pad_diag_i, i, bs: int):
    """Cholesky factor of block i's regularized gram — computed once per
    block and reused across epochs (the factors are constant, exactly as
    the fused path caches them in its first scan)."""
    xi, mu_i = _block(x, mu, i, bs)
    a_i = (xi - mu_i) * mask
    return jsl.cho_factor(a_i.T @ a_i + jnp.diag(lam + pad_diag_i))[0]


@functools.partial(jax.jit, static_argnames=("bs",))
def _bcd_block_solve(x, mu, mask, residual, m_old, c_i, i, bs: int):
    """One BCD block update given the cached factor — identical math to
    one ``block_step`` of ``_fused_bcd_fit``."""
    xi, mu_i = _block(x, mu, i, bs)
    a_i = (xi - mu_i) * mask
    r_i = residual + a_i @ m_old
    m_new = jsl.cho_solve((c_i, False), a_i.T @ r_i)
    return m_new, r_i - a_i @ m_new


@jax.jit
def _hs_block_mean(xi, mask, nv):
    """Per-block feature means over the valid rows — identical per-column
    numerics to the fused path's one-gemv ``(mask @ x) / nv`` (each output
    column is an independent dot product, so blockwise evaluation changes
    nothing)."""
    return (mask[:, 0] @ xi) / nv


@jax.jit
def _hs_block_factor(xi, mu_i, mask, lam, pad_diag_i):
    """Cholesky factor of one HOST-STAGED block's regularized gram: the
    block arrives as its own [N, bs] argument (streamed H2D by the caller)
    instead of being sliced out of a device-resident design matrix."""
    a_i = (xi - mu_i) * mask
    return jsl.cho_factor(a_i.T @ a_i + jnp.diag(lam + pad_diag_i))[0]


@jax.jit
def _hs_block_solve(xi, mu_i, mask, residual, m_old, c_i):
    """One BCD block update on a host-staged block — same math as
    ``_bcd_block_solve`` minus the device-side slice."""
    a_i = (xi - mu_i) * mask
    r_i = residual + a_i @ m_old
    m_new = jsl.cho_solve((c_i, False), a_i.T @ r_i)
    return m_new, r_i - a_i @ m_new


def _host_staged_bcd_fit(x_host, labels, lam, nvalid, num_iter: int, widths):
    """The floor of the degradation ladder: the blocked design matrix lives
    in HOST RAM and exactly one [N, bs] block is on-device at a time (the
    H2D stream re-uploads each block once per epoch).  Device residency is
    one block + the [N, k] residual + the cached per-block factors/means —
    models far bigger than HBM fit, at H2D-bandwidth cost.  This is
    ml-matrix's "models bigger than memory" property (SURVEY L1'), which
    the fused one-program design had lost.  Numerics are identical to
    ``_fused_bcd_fit``: same centering, masking, pad-column shift, and
    update order.
    """
    bs = max(widths)
    nb = len(widths)
    x_host = np.asarray(x_host)
    labels = jnp.asarray(labels)
    dtype = labels.dtype
    n = labels.shape[0]

    mask = (jnp.arange(n) < nvalid).astype(dtype)[:, None]
    nv = jnp.asarray(nvalid, dtype)
    lam_arr = jnp.asarray(lam, dtype)
    label_mean = jnp.sum(labels * mask, axis=0) / nv
    residual = (labels - label_mean) * mask
    pad_diag = np.stack(
        [(np.arange(bs) >= w).astype(np.float64) for w in widths]
    )

    # Per-block means and Cholesky factors are constant across epochs; the
    # caches cost nb*(bs + bs^2) device floats — for production shapes
    # (bs=4096, nb<=8) ~0.5 GB, far below the matrix this tier is avoiding.
    mus: dict[int, jax.Array] = {}
    chols: dict[int, jax.Array] = {}
    models = [jnp.zeros((bs, labels.shape[1]), dtype) for _ in range(nb)]

    for _ in range(num_iter):
        for i in range(nb):
            xi = jnp.asarray(
                np.ascontiguousarray(x_host[:, i * bs : (i + 1) * bs])
            ).astype(dtype)
            if i not in mus:
                mus[i] = _hs_block_mean(xi, mask, nv)
                chols[i] = _hs_block_factor(
                    xi, mus[i], mask, lam_arr, jnp.asarray(pad_diag[i], dtype)
                )
            m_new, residual = _hs_block_solve(
                xi, mus[i], mask, residual, models[i], chols[i]
            )
            models[i] = m_new
            del xi  # the one big device buffer — released before the next H2D
    means = jnp.stack([mus[i] for i in range(nb)])
    return jnp.stack(models), label_mean, means


#: Of the capacity a made fit keeps blocks in, the share it leaves for what
#: the process holds beside the fit (the caller's last model and chains,
#: the test rows: 0.69 GB of `timit_rf_fit_full`'s 16.91) and for the
#: allocator's fragments: a tenth.
_KEEP_HEADROOM = 10


def _kept_dtype(dtype):
    """What a kept made block is stored as: exactly what the fused
    program's products read of it.  On a TPU a float32 operand at the
    default precision is one bfloat16 pass, so the block rounded to
    bfloat16 once is those bits; elsewhere (and under a higher default
    precision) the products read the block itself."""
    dtype = np.dtype(dtype)
    one_pass = jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16", "fastest",
    )
    if jax.default_backend() == "tpu" and one_pass and dtype == np.float32:
        return np.dtype(jnp.bfloat16)
    return dtype


def _made_need(n: int, k: int, nb: int, bs: int, it: int) -> tuple[int, int]:
    """(temporaries, results) the fused made program is charged with at
    least, beside its operands and labels: the block as the featurizer
    leaves it and its centred copy, two residual carries and the models
    carry; the models, label mean, block means and the factor stack."""
    temps = it * (2 * n * bs + 2 * n * k + nb * bs * k)
    results = it * (nb * bs * k + k + nb * bs) + it * nb * bs * bs
    return temps, results


def _plan_bcd(features, labels, num_iter: int, block_size: int,
              num_features=None) -> dict:
    """What a fit is about to hold, and for a :class:`BlockSource` whether
    its blocks are held or made: **a rule from bytes**, no option.  The
    blocks are held as one matrix (the program every other caller runs)
    when the matrix beside the fused program's own footprint fits the
    budget ``core.memory.hbm_budget()`` reports, or no budget is known; they
    are made inside the solver's programs otherwise.  The decision is no
    admission denial: nothing was tried.  Arrays and lists of arrays are
    held, as they always were.  The record is the ``bcd_plan`` instant's
    and ``FitReport.bcd_plan``'s.

    Made, the fit **keeps** the first ``held_blocks`` of them where they
    fit (Spark's ``MEMORY_ONLY``: what fits stays, the rest is made again)
    in a stack of ``h + 1`` slots (the last takes the block being made):
    ``h = min(B - 1, (capacity - made_bytes - capacity / 10) // kept block
    - 1)``, never below 0, where ``made_bytes`` is what the made program is
    charged with at ``h = 0`` (operands, labels, :func:`_made_need` and what
    a make holds beyond it, ``make_scratch_bytes``: :func:`_make_scratch`)
    and a kept block is ``rows x width`` in :func:`_kept_dtype`.  The capacity is
    ``core.memory.hbm_capacity()``, the device's limit, not its free bytes,
    so every fit of a process picks the same ``h`` and the same program;
    the free bytes only cap it where the process holds more than the tenth
    beside the fit (the admission would deny that ``h``)."""
    n, k = (int(d) for d in np.shape(labels))
    dtype = jax.dtypes.canonicalize_dtype(getattr(labels, "dtype", np.float32))
    it = np.dtype(dtype).itemsize
    source = isinstance(features, BlockSource)
    operands = features.operand_bytes() if source else 0
    if source:
        nb, bs = len(features), features.block_size
    elif isinstance(features, (list, tuple)):
        nb, bs = len(features), max(int(b.shape[1]) for b in features)
    else:
        d = num_features or int(np.shape(features)[1])
        nb, bs = -(-d // block_size), min(block_size, d)
    matrix, block = it * n * nb * bs, it * n * bs
    factors = it * nb * bs * bs
    # beside the blocks themselves, either form of the fused program keeps
    # the labels, two residual carries, the factor stack and the models
    shared = it * (3 * n * k + nb * bs * k) + factors
    plan = {
        "rows": n, "blocks": nb, "block_width": bs, "block_source": "held",
        "matrix_bytes": matrix, "operand_bytes": operands,
        "factor_bytes": factors, "block_bytes": block, "passes_a_block": 0,
        "held_blocks": 0, "held_stack_bytes": 0,
    }
    if not source:
        return plan
    budget = kmem.hbm_budget()
    # a live budget is free bytes: the source's operands and device labels
    # are out of it already
    resident = operands + (labels.nbytes if isinstance(labels, jax.Array) else 0)
    credit = resident if kmem.budget_is_live() else 0
    held = operands + matrix + block + shared
    made = operands + it * n * k + sum(_made_need(n, k, nb, bs, it))
    plan.update(held_bytes=held, made_bytes=made, budget_bytes=budget)
    if budget is None or held - credit <= budget:
        plan["passes_a_block"] = 1
        return plan
    scratch = _make_scratch(features)
    made += scratch
    plan.update(made_bytes=made, make_scratch_bytes=scratch)
    kept = _kept_dtype(dtype)
    kept_block = n * bs * kept.itemsize
    capacity = kmem.hbm_capacity() or budget
    room = capacity - made - capacity // _KEEP_HEADROOM
    if kmem.budget_is_live():
        # never more than the free bytes now admit (less a sixty-fourth for
        # the compiled program's padding)
        room = min(room, budget + credit - made - made // 64)
    keep = min(nb - 1, max(0, room // kept_block - 1))
    plan.update(
        block_source="made", passes_a_block=num_iter + 1 + (features.means is None),
        held_blocks=keep, held_stack_bytes=(keep + 1) * kept_block if keep else 0,
        held_dtype=kept.name, capacity_bytes=capacity,
    )
    return plan


BCD_STATE_VERSION = 1


def bcd_checkpoint_path(path: str) -> str:
    """Canonical on-disk location of a BCD state for a stem or path — the
    ONE place the ``.npz`` suffix rule lives (save/load and the workload
    existence/cleanup checks all go through it)."""
    return path if path.endswith(".npz") else path + ".npz"


def save_bcd_checkpoint(path: str, state: dict) -> str:
    """Write a resumable BCD state (one ``.npz``, atomic) — the default
    sink for the per-block checkpoint callback."""
    buf = io.BytesIO()
    np.savez(
        buf,
        version=np.int64(state.get("version", BCD_STATE_VERSION)),
        epoch=np.int64(state["epoch"]),
        block=np.int64(state["block"]),
        models=np.asarray(jax.device_get(state["models"])),
        residual=np.asarray(jax.device_get(state["residual"])),
        widths=np.asarray(state["widths"], np.int64),
        num_iter=np.int64(state["num_iter"]),
        lam=np.float64(state["lam"]),
        nvalid=np.int64(state["nvalid"]),
        data_sum=np.asarray(state["data_sum"], np.float64),
    )
    path = bcd_checkpoint_path(path)
    _atomic_write_bytes(path, buf.getvalue())
    return path


def load_bcd_checkpoint(path: str) -> dict:
    """Read a state written by :func:`save_bcd_checkpoint`."""
    path = bcd_checkpoint_path(path)
    try:
        with np.load(path) as zf:
            state = {k: zf[k] for k in zf.files}
    except (OSError, ValueError) as e:
        raise CheckpointError(f"cannot read BCD checkpoint {path}: {e}") from e
    version = int(state.get("version", -1))
    if version != BCD_STATE_VERSION:
        raise CheckpointError(
            f"{path}: BCD state version {version} (this build reads "
            f"{BCD_STATE_VERSION})"
        )
    return {
        "version": version,
        "epoch": int(state["epoch"]),
        "block": int(state["block"]),
        "models": state["models"],
        "residual": state["residual"],
        "widths": tuple(int(w) for w in state["widths"]),
        "num_iter": int(state["num_iter"]),
        "lam": float(state["lam"]),
        "nvalid": int(state["nvalid"]),
        "data_sum": tuple(float(v) for v in state["data_sum"]),
    }


def bcd_checkpoint_writer(path: str) -> Callable[[dict], None]:
    """Per-block callback persisting each completed block's state to
    ``path`` (atomically, so preemption mid-write loses at most one block
    of progress)."""

    def write(state: dict) -> None:
        save_bcd_checkpoint(path, state)

    return write


def _stepwise_bcd_fit(
    x,
    labels,
    lam,
    nvalid,
    num_iter: int,
    widths,
    checkpoint_cb: Callable[[dict], None] | None = None,
    resume_state: dict | None = None,
    block_solve=None,
):
    """The resumable form of ``_fused_bcd_fit``: same centering, masking,
    pad-column shift, and per-block update, but driven from the host one
    block at a time so ``checkpoint_cb`` fires after every completed block
    and a preempted fit restarts at the last completed block via
    ``resume_state`` instead of from scratch.

    Trades the fused path's single-dispatch latency for preemptibility —
    the per-block program is still one compiled step (``_bcd_block_step``),
    so the extra cost is one dispatch round-trip per block plus whatever
    the callback spends persisting state.

    ``block_solve``: the preflight's AOT-compiled per-block solve executable
    (``plan.compiled`` from the stepwise tier's admission plan — statics
    baked, same avals).  When given, the degraded path executes the very
    program that was planned instead of re-compiling ``_bcd_block_solve``
    at first jit dispatch; ``None`` falls back to the jitted entry.

    ``x`` may be a :class:`BlockSource` with its ``means``: the per-block
    programs then make the block they work on (``_block``).
    """
    bs = max(widths)
    nb = len(widths)
    made = isinstance(x, BlockSource)
    if not made:
        x = jnp.asarray(x)
    labels = jnp.asarray(labels)
    dtype = labels.dtype
    n = labels.shape[0]

    mask = (jnp.arange(n) < nvalid).astype(dtype)[:, None]
    nv = jnp.asarray(nvalid, dtype)
    label_mean = jnp.sum(labels * mask, axis=0) / nv
    mu = None if made else (mask[:, 0] @ x) / nv
    means = x.means if made else mu.reshape(nb, bs)
    pad_diag = np.stack(
        [(np.arange(bs) >= w).astype(np.float64) for w in widths]
    )
    if made and x.zero_columns is not None:
        pad_diag = pad_diag + np.asarray(x.zero_columns)
    # Cheap content fingerprint of the inputs: shape checks alone cannot
    # tell "same fit, resumed" from "different data, same shape" (e.g. a
    # re-featurized train set after a seed change) — resuming across that
    # line would silently mix two models.
    data_sum = (float(jnp.sum(x.rows if made else x)), float(jnp.sum(labels)))

    if resume_state is not None:
        for field, want in (
            ("widths", tuple(widths)),
            ("num_iter", int(num_iter)),
            ("nvalid", int(nvalid)),
            ("lam", float(lam)),
        ):
            got = resume_state.get(field)
            if got != want:
                raise CheckpointError(
                    f"resume_from state disagrees with this fit: {field} is "
                    f"{got!r} in the checkpoint, {want!r} here"
                )
        got_sum = resume_state.get("data_sum")
        if got_sum is not None and not np.allclose(
            got_sum, data_sum, rtol=1e-5, atol=1e-6
        ):
            raise CheckpointError(
                "resume_from state was written for DIFFERENT data (input "
                f"fingerprint {tuple(got_sum)} vs {data_sum}) — refusing to "
                "resume a fit against features it was not computing on"
            )
        models = jnp.asarray(resume_state["models"], dtype)
        residual = jnp.asarray(resume_state["residual"], dtype)
        if models.shape != (nb, bs, labels.shape[1]) or residual.shape != (
            n,
            labels.shape[1],
        ):
            raise CheckpointError(
                "resume_from state shapes do not match this fit "
                f"(models {models.shape}, residual {residual.shape})"
            )
        e0 = int(resume_state["epoch"])
        b0 = int(resume_state["block"]) + 1  # block index last COMPLETED
        if b0 >= nb:
            e0, b0 = e0 + 1, 0
        _logger.info(
            "resuming BCD fit at epoch %d block %d (of %d epochs x %d blocks)",
            e0, b0, num_iter, nb,
        )
    else:
        models = jnp.zeros((nb, bs, labels.shape[1]), dtype)
        residual = (labels - label_mean) * mask
        e0, b0 = 0, 0

    lam_arr = jnp.asarray(lam, dtype)

    def jit_block_solve(*a):
        return _bcd_block_solve(*a, bs)

    solve = block_solve if block_solve is not None else jit_block_solve
    chol_cache: dict[int, jax.Array] = {}  # factors are constant across epochs
    for e in range(e0, num_iter):
        for i in range(b0 if e == e0 else 0, nb):
            if made:  # a block for the step, one more for a new factor
                _count_blocks_made(nvalid, 1 + (i not in chol_cache))
            c_i = chol_cache.get(i)
            if c_i is None:
                c_i = chol_cache[i] = _bcd_block_factor(
                    x,
                    mu,
                    mask,
                    lam_arr,
                    jnp.asarray(pad_diag[i], dtype),
                    jnp.asarray(i, jnp.int32),
                    bs,
                )
            m_new, residual = solve(
                x,
                mu,
                mask,
                residual,
                models[i],
                c_i,
                jnp.asarray(i, jnp.int32),
            )
            models = models.at[i].set(m_new)
            if checkpoint_cb is not None:
                checkpoint_cb(
                    {
                        "version": BCD_STATE_VERSION,
                        "epoch": e,
                        "block": i,
                        "models": models,
                        "residual": residual,
                        "widths": tuple(widths),
                        "num_iter": int(num_iter),
                        "lam": float(lam),
                        "nvalid": int(nvalid),
                        "data_sum": data_sum,
                    }
                )
    return models, label_mean, means


class BlockLeastSquaresEstimator(LabelEstimator):
    """Block coordinate descent least squares with L2
    (reference BlockLinearMapper.scala:147-204).

    Semantics matched to the reference: labels are mean-centered (mean-only
    StandardScaler), each feature block is mean-centered with its own scaler,
    BCD runs ``num_iter`` epochs over blocks, and the intercept is the label
    mean.  The whole fit compiles to ONE device program (_fused_bcd_fit).
    """

    def __init__(
        self,
        block_size: int,
        num_iter: int = 1,
        lam: float = 0.0,
        mesh=None,
    ):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mesh = mesh
        #: core.memory.FitReport of the most recent fit (tier plans, chosen
        #: tier, denials, OOM retries) — workload results embed it.
        self.last_fit_report = None

    def fit(
        self,
        features,
        labels,
        num_features: int | None = None,
        nvalid: int | None = None,
        checkpoint=None,
        resume_from=None,
        donate: bool | None = None,
        plan=None,
    ) -> BlockLinearMapper:
        """``nvalid``: true global row count when inputs were zero-padded for
        sharding — pad rows are masked back to zero after centering so grams
        stay exact (see parallel.mesh.padded_shard_rows).

        With a mesh (explicit or ambient via ``parallel.mesh.use_mesh``) the
        inputs are row-sharded over the data axis (zero-padding rows to a
        multiple of the axis size) and the BCD solve runs with (data, model)
        shardings — the distributed execution of reference
        BlockLinearMapper.scala:147-204.

        Fault tolerance: ``checkpoint`` is a path (state written atomically
        after every completed block — :func:`bcd_checkpoint_writer`) or a
        callback receiving the state dict; ``resume_from`` is a path or a
        state dict from a previous interrupted fit, which restarts at the
        last completed block.  Either switches the solve from the fused
        single-program path to the stepwise per-block path (same math,
        one dispatch per block); both are single-host (mesh unsupported —
        preempted multi-chip fits restart whole).

        Memory resilience: the solve runs a degradation ladder.  Without a
        mesh: fused one-program → stepwise per-block → host-staged block
        streaming, each tier preflighted against the HBM budget
        (core.memory.plan_program; ``KEYSTONE_HBM_BUDGET`` overrides for
        testing) and a runtime ``RESOURCE_EXHAUSTED`` steps down one tier
        instead of killing the fit.  With a mesh the ladder grows mesh
        tiers above those: full ``(data, model)`` mesh → model-axis-
        collapsed mesh → the single-device ladder, with each mesh tier
        admitted PER CHIP against the minimum free HBM across the mesh's
        devices and ``last_fit_report.mesh_shape`` recording which mesh
        actually ran.  ``donate``: tri-state — ``None``
        (default) donates the design matrix/labels into the fused program
        only when they are buffers this fit created (host uploads, padded
        copies), ``True`` forces donation of caller-owned device arrays
        (the caller must not reuse them; an exec-level OOM then cannot
        rebuild them for the step-down), ``False`` never donates.  The
        decision trail is ``self.last_fit_report``.

        Placement search (core.autoshard, on by default): the ladders above
        are the HAND enumeration — the fit actually runs the cost-model
        RANKED candidate list (every (data, model) mesh factorization of
        the live devices x fused/stepwise/host-staged strategy), pruned by
        the zero-cost batch preflight, with the hand order as the
        untrained-model tie-break and the host-staged/single-device floor
        pinned last; runtime RESOURCE_EXHAUSTED steps down the ranked list
        (counted ``autoshard_stepdown``) exactly as the hand ladder did.
        ``plan``: ``None`` honors ``KEYSTONE_AUTOSHARD``, ``False`` forces
        the hand ladder, ``True`` forces the search, a ``PlacementPlan``
        (or candidate-name list) replays a previous ranking.  The searched
        table lands in ``last_fit_report.placement``.
        """
        mesh = self.mesh if self.mesh is not None else current_mesh()
        resumable = checkpoint is not None or resume_from is not None
        if resumable and mesh is not None:
            raise ValueError(
                "checkpoint/resume_from use the stepwise BCD path, which "
                "does not run under a mesh — fit without a mesh or without "
                "checkpointing"
            )
        source = features if isinstance(features, BlockSource) else None
        if source is not None:
            if mesh is not None:
                raise ValueError(
                    "a BlockSource (feature blocks made from the rows inside "
                    "the solver's programs) does not run under a mesh yet: "
                    "its rows would shard over the data axis with the "
                    "featurizers replicated — fit it without a mesh, or hand "
                    "the mesh fit the blocks themselves"
                )
            if resumable:
                raise ValueError(
                    "a BlockSource fit cannot be checkpointed or resumed yet "
                    "— hand the checkpointed fit the blocks themselves"
                )
            if nvalid is None:
                nvalid = int(source.rows.shape[0])
        bcd_plan = _plan_bcd(
            features, labels, self.num_iter, self.block_size, num_features
        )
        if bcd_plan["block_source"] == "made":
            x, widths = source, source.block_widths()
            if x.means is None:
                with trace.host("dispatch", "block_moments"):
                    sums, squares = block_moments(x, nvalid)
                    x = BlockSource(
                        x.rows, x.featurizers, x.widths, sums / nvalid,
                        (squares == 0).astype(sums.dtype),
                    )
        else:
            with trace.host("place", "design_matrix"):  # the eager column pad
                x, widths = _blocked_design_matrix(
                    features, self.block_size, num_features, nvalid
                )
        # Conditioning monitor (ISSUE 15): per-block κ estimates riding
        # the blocked design matrix this fit already formed (row-capped,
        # so the probe never re-uploads a host-staged matrix).  One flag
        # check when the observatory is off.
        cond_rows = (
            knum.design_conditioning(
                x, widths, float(self.lam), label="bcd_fit"
            )
            if knum.active() and not isinstance(x, BlockSource)
            else None
        )
        # Any per-solve κ estimate emitted DURING the fit (the
        # _guarded_solve hook in normal_equations) joins the design-block
        # probes in the report.
        cond_ctx = knum.collect_conditioning()
        solve_cond = cond_ctx.__enter__()
        try:
            model = self._fit_dispatch(
                features, x, labels, num_features, nvalid, widths,
                checkpoint, resume_from, donate, plan, mesh, resumable,
                cond_rows, solve_cond, bcd_plan,
            )
        finally:
            cond_ctx.__exit__(None, None, None)
        # What ran, where every fit says it: the report, one instant and one
        # counter a fit (``bcd_source.held`` / ``bcd_source.made``).
        self.last_fit_report.bcd_plan = bcd_plan
        trace.metrics.inc(f"bcd_source.{bcd_plan['block_source']}")
        trace.instant("bcd_plan", **bcd_plan)
        return model

    def _fit_dispatch(
        self, features, x, labels, num_features, nvalid, widths,
        checkpoint, resume_from, donate, plan, mesh, resumable,
        cond_rows, solve_cond, bcd_plan,
    ):
        if resumable:
            if nvalid is None:
                nvalid = int(jnp.shape(labels)[0])
            self.last_fit_report = kmem.FitReport(
                label="bcd_fit", chosen="stepwise[checkpoint]"
            )
            cb = checkpoint if callable(checkpoint) or checkpoint is None else (
                bcd_checkpoint_writer(checkpoint)
            )
            state = (
                load_bcd_checkpoint(resume_from)
                if isinstance(resume_from, str)
                else resume_from
            )
            # The checkpoint/resume path bypasses run_ladder (its tier is
            # forced), so it emits its own tier span with the report linked.
            with trace.span(
                "tier:stepwise[checkpoint]", cat="solve", solve="bcd_fit",
                resuming=state is not None,
            ):
                models, label_mean, means = _stepwise_bcd_fit(
                    jnp.asarray(x),
                    jnp.asarray(labels),
                    self.lam,
                    nvalid,
                    self.num_iter,
                    widths,
                    checkpoint_cb=cb,
                    resume_state=state,
                )
        elif isinstance(x, BlockSource):
            models, label_mean, means = self._fit_made_ladder(
                x, labels, nvalid, bcd_plan, plan_arg=plan
            )
        elif mesh is not None:
            # Multi-chip path: the MESH degradation ladder — full
            # (data, model) mesh with per-chip admission, then the
            # model-axis-collapsed mesh, then the single-device ladder —
            # searched/ranked by core.autoshard unless plan=False.
            models, label_mean, means = self._fit_mesh_ladder(
                features, x, labels, num_features, nvalid, widths, mesh,
                plan_arg=plan,
            )
        else:
            if nvalid is None:
                nvalid = int(jnp.shape(labels)[0])
            models, label_mean, means = self._fit_ladder(
                features, x, labels, num_features, nvalid, widths, donate,
                plan_arg=plan,
            )
        all_cond = (cond_rows or []) + list(solve_cond)
        if all_cond and self.last_fit_report is not None:
            self.last_fit_report.conditioning = all_cond
        with trace.host("finish", "model_blocks"):  # one compiled split
            model_list, mean_list = split_model(models, means, widths)
            feature_scalers = [StandardScalerModel(mu) for mu in mean_list]
        return BlockLinearMapper(
            model_list, self.block_size, label_mean, feature_scalers
        )

    def _fit_mesh_ladder(
        self, features, x, labels, num_features, nvalid, widths, mesh,
        plan_arg=None,
    ):
        """Distributed solve through the MESH degradation ladder.

        Tiers: the full ``(data, model)`` mesh → the model-axis-collapsed
        mesh (same chips, pure data-parallel: row-sharded operands halve
        per chip while model blocks replicate) → the single-device ladder
        (fused → stepwise → host-staged) on host-pulled inputs.  Each mesh
        tier is preflighted PER CHIP (``plan_program(mesh=...)`` against
        the minimum free HBM across participating chips) and a runtime
        ``RESOURCE_EXHAUSTED`` from any chip steps down exactly one tier —
        the Spark-executor admission/retry discipline, rebuilt for GSPMD.
        ``report.mesh_shape`` records which mesh actually ran the solve.
        """
        bs, nb = max(widths), len(widths)
        n0 = int(np.shape(labels)[0])
        k = int(np.shape(labels)[1])
        nvalid0 = nvalid if nvalid is not None else n0
        dtype = jax.dtypes.canonicalize_dtype(
            getattr(labels, "dtype", np.float32)
        )
        xdt = jax.dtypes.canonicalize_dtype(x.dtype)
        it = np.dtype(dtype).itemsize
        with trace.host("place", "lam"):
            lam_arr = jnp.asarray(self.lam, dtype)

        report = kmem.FitReport(label="bcd_fit")
        self.last_fit_report = report

        itx = np.dtype(xdt).itemsize

        def mesh_tier(m, prior_rank, hand, specs=None):
            """One fused-mesh candidate: ``specs=None`` is the strategy's
            default layout (row-sharded inputs, model-axis-sharded model
            columns — the PR 9 hand rung, bit-for-bit); a spec assignment
            makes the candidate EXECUTE that per-operand layout, with the
            hints charging the chosen specs' actual per-chip bytes instead
            of the best-spec lower bound."""
            name = f"fused[mesh {mesh_desc(m)}]"
            if specs:
                name = f"fused[mesh {mesh_desc(m)}|{autoshard.spec_tag(specs)}]"
            d_sz, m_sz = m.shape[DATA_AXIS], m.shape[MODEL_AXIS]
            # The design matrix may arrive row-padded for the CALLER's data
            # axis (nvalid < its rows); every candidate pads from there.
            n_x = int(np.shape(x)[0])
            n_pad = n_x + (-n_x) % d_sz
            k_pad = k + (-k) % m_sz
            mdict = dict(m.shape)
            lspec = (specs or {}).get("labels", "data@dim0")
            mspec = (specs or {}).get("models", "model@dim2")
            # The residual carries inherit the labels layout; the models
            # carry follows the models spec.  One byte helper feeds the
            # transient floor, the prune figure, and the cost model alike.
            res_b = autoshard.spec_chip_bytes(
                (n_pad, k_pad), dtype, lspec, mdict
            )
            models_b = autoshard.spec_chip_bytes(
                (nb, bs, k_pad), dtype,
                "model@dim2" if mspec == "model@dim2" else "replicated",
                mdict,
            )
            # Analytic per-chip transient floor (CPU backends report
            # temp 0): one centered row-sharded block, the replicated
            # Cholesky stack, two residual carries, the models carry.
            floor = (
                it * (n_pad * bs // d_sz + nb * bs * bs)
                + 2 * res_b + models_b
            )
            if specs:
                # A spec candidate charges the bytes of the layout it
                # will actually execute — the spec dimension is real.
                arg_bytes = (
                    autoshard.spec_chip_bytes(
                        (n_pad, nb * bs), xdt,
                        (specs or {}).get("x", "data@dim0"), mdict,
                    )
                    + autoshard.spec_chip_bytes(
                        (n_pad, k_pad), dtype, lspec, mdict
                    )
                )
            else:
                # Hand accounting: per-operand bytes through the spec
                # enumeration's minimum (the best sharding this mesh shape
                # can achieve) — a lower bound of any layout the compiled
                # admission will charge.
                arg_bytes = sum(
                    autoshard.best_spec(a, mdict)["per_chip_bytes"]
                    for a in (
                        jax.ShapeDtypeStruct((n_pad, nb * bs), xdt),
                        jax.ShapeDtypeStruct((n_pad, k_pad), dtype),
                    )
                )
            hints = {
                "arg_bytes": arg_bytes,
                "temp_bytes": floor,
                "out_bytes": it * (k_pad + nb * bs) + models_b,
                "flops": (
                    2.0 * n_pad * bs * bs * nb
                    + self.num_iter * 4.0 * n_pad * bs * k_pad * nb
                ) / (d_sz * m_sz),
                "dispatches": 1,
                "hbm_passes": self.num_iter + 1,
                "coll_bytes": (
                    it * nb * (bs * bs + self.num_iter * bs * k_pad)
                    if d_sz > 1 else 0
                ),
            }
            spec_t = tuple(sorted(specs.items())) if specs else None

            def plan():
                budget, _worst = kmem.min_chip_budget(m)
                sds = jax.ShapeDtypeStruct
                row = row_sharding(m)
                x_s = sds((n_pad, nb * bs), xdt, sharding=row)
                y_s = sds(
                    (n_pad, k_pad), dtype,
                    sharding=(
                        row if lspec == "data@dim0"
                        else autoshard.spec_sharding(lspec, m, 2)
                    ),
                )
                lam_s, i32_s = sds((), dtype), sds((), jnp.int32)
                return kmem.plan_program(
                    _fused_bcd_fit, x_s, y_s, lam_s, i32_s,
                    self.num_iter, widths, m, spec_t,
                    label=f"bcd_{name}", budget=budget,
                    min_temp_bytes=floor, mesh=m,
                )

            def run(plan):
                report.mesh_shape = dict(m.shape)
                with trace.host("place", "operands"):
                    if spec_t is None or lspec == "data@dim0":
                        (x_p, y_p), nv = pad_shard_inputs(m, nvalid0, x, labels)
                        # Class columns shard over the model axis; zero label
                        # columns stay zero through every BCD update — exact
                        # pad.  Rows: a candidate whose data axis is not the
                        # caller's pads a caller-padded design matrix and the
                        # unpadded labels to different counts; labels take the
                        # design matrix's (zero rows, masked by nv).
                        row_pad = int(jnp.shape(x_p)[0]) - int(jnp.shape(y_p)[0])
                        col_pad = (-int(jnp.shape(y_p)[1])) % m_sz
                        if row_pad or col_pad:
                            y_p = jnp.pad(y_p, ((0, row_pad), (0, col_pad)))
                        nv = nv if nv is not None else int(jnp.shape(y_p)[0])
                    else:
                        # Non-default labels layout: pad rows to the sharded
                        # design matrix's count and columns to a model-axis
                        # multiple, then PLACE per the chosen spec — the
                        # program's constraint and this placement read the
                        # same spec string, so they cannot drift.
                        (x_p,), nv = pad_shard_inputs(m, nvalid0, x)
                        nv = nv if nv is not None else n0
                        row_pad = int(jnp.shape(x_p)[0]) - n0
                        col_pad = (-k) % m_sz
                        if isinstance(labels, jax.Array):
                            y_p = (
                                jnp.pad(labels, ((0, row_pad), (0, col_pad)))
                                if row_pad or col_pad else labels
                            )
                        else:
                            y_p = np.pad(
                                np.asarray(labels),
                                ((0, row_pad), (0, col_pad)),
                            )
                        y_p = jax.device_put(
                            jnp.asarray(y_p), autoshard.spec_sharding(lspec, m, 2)
                        )
                # what the program sums over the data axis, from the
                # shapes: a gram and num_iter cross terms a block, the
                # block means' gemv and the label mean
                count_psum(hints["coll_bytes"] + (it * (nb * bs + k_pad) if d_sz > 1 else 0))
                models, label_mean, means = _execute_fused_bcd_mesh(
                    plan, jnp.asarray(x_p), jnp.asarray(y_p), lam_arr,
                    nv, self.num_iter, widths, m, spec_t,
                )
                if k_pad != k:
                    models = models[:, :, :k]
                    label_mean = label_mean[:k]
                return models, label_mean, means

            return autoshard.Candidate(
                name, "fused_mesh", plan, run, hints=hints,
                mesh_axes=mdict, prior_rank=prior_rank, hand=hand,
                specs=dict(specs) if specs else None,
            )

        def plan_single():
            return kmem.MemoryPlan(
                label="single_device",
                admitted=True,
                reason=(
                    "mesh ladder floor: single-device degradation ladder "
                    "(its own per-tier admission runs inside)"
                ),
            )

        inner_chosen = []

        def run_single(_plan):
            report.mesh_shape = None
            x_h = (
                np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) else x
            )
            y_h = (
                np.asarray(jax.device_get(labels))
                if isinstance(labels, jax.Array)
                else labels
            )
            out = self._fit_ladder(
                x_h, x_h, y_h, num_features, nvalid0, widths, None,
                # The mesh-level search already ranked this floor; the
                # nested single-device ladder walks its hand order (a
                # nested search would overwrite the report's placement).
                plan_arg=False,
                report=report,
            )
            inner_chosen.append(report.chosen)
            return out

        with trace.host("search", "enumerate"):
            cands = [mesh_tier(mesh, 0, True)]
            rm = reduced_mesh(mesh)
            if rm is not None:
                cands.append(mesh_tier(rm, 1, True))
            # The searched candidate set: every remaining (data, model)
            # factorization of the SAME devices, then (KEYSTONE_AUTOSHARD_SPECS)
            # the per-operand SPEC assignments of every mesh shape — e.g.
            # model-axis-sharded label columns, or fully-replicated model
            # blocks — each an executable layout, ranked by the cost model but
            # never promoted past the hand rungs on an untrained prior.  Only
            # enumerated when the search will run — a hand-ladder walk would
            # discard them, and each costs a jax Mesh construction.
            if autoshard.will_search(plan_arg):
                hand_shapes = {
                    mesh_desc(c_mesh) for c_mesh in (mesh, rm) if c_mesh
                }
                searched_meshes = [mesh] + ([rm] if rm is not None else [])
                for extra in enumerate_meshes(list(mesh.devices.flat)):
                    if mesh_desc(extra) not in hand_shapes:
                        searched_meshes.append(extra)
                        cands.append(mesh_tier(extra, len(cands), False))
                if autoshard.specs_enabled():
                    for sm in searched_meshes:
                        for sp in _bcd_spec_variants(sm):
                            cands.append(
                                mesh_tier(sm, len(cands), False, specs=sp)
                            )
            cands.append(autoshard.Candidate(
                "single_device", "single_device", plan_single, run_single,
                hints={
                    # Host pull + refit on one chip: the whole design matrix
                    # crosses back over PCIe and nothing divides — the floor's
                    # predicted cost is honest about why it is the floor.
                    "arg_bytes": itx * n0 * nb * bs + it * n0 * k,
                    "h2d_bytes": itx * n0 * nb * bs + it * n0 * k,
                    "flops": 2.0 * n0 * bs * bs * nb
                    + self.num_iter * 4.0 * n0 * bs * k * nb,
                    "dispatches": 3,
                },
                prior_rank=len(cands), floor=True,
            ))
        # The solver declares its fit as a profiler PHASE (core.profiler):
        # the HBM watermark sampler attributes this solve's high-water
        # mark to "bcd_fit", separable from serving/ingest residency in
        # the same process.  A no-op when the profiler is off.
        with kprof.phase("bcd_fit"):
            out = autoshard.run_search(
                "bcd_fit", cands, report,
                fingerprint=autoshard.fingerprint(
                    "bcd_fit", n0, k, widths, self.num_iter, str(xdt),
                    str(dtype), dict(mesh.shape),
                    autoshard.device_fingerprint(),
                ),
                plan=plan_arg,
            )
        if inner_chosen and report.chosen == "single_device":
            # Keep the inner rung visible: "single_device/host_staged".
            report.chosen = f"single_device/{inner_chosen[0]}"
        return out

    def _fit_made_ladder(self, source: BlockSource, labels, nvalid, bcd_plan,
                         plan_arg=None):
        """The ladder of a fit whose blocks are made: ``fused[made]`` (the
        one fused program, each scan step making its block, the first
        ``bcd_plan["held_blocks"]`` of them kept after the gram pass) ->
        ``stepwise[made]`` (the per-block programs, each making its block;
        the floor).  ``host_staged`` is no rung here: it is the floor for a
        matrix that lives on the host.  Plans and hints charge what this
        form holds (the rows, the featurizers' parameters, the factor stack,
        the kept blocks, the block being made and its centred copy) and what
        it recomputes (every block not kept, once a pass)."""
        nb, bs = len(source), source.block_size
        widths = source.block_widths()
        n, k = int(np.shape(labels)[0]), int(np.shape(labels)[1])
        dtype = jax.dtypes.canonicalize_dtype(labels.dtype)
        it = np.dtype(dtype).itemsize
        with trace.host("plan", "budget"):
            budget = kmem.hbm_budget()
        with trace.host("place", "lam"):
            lam_arr = jnp.asarray(self.lam, dtype)
            nv_arr = jnp.asarray(nvalid, jnp.int32)
        sds = jax.ShapeDtypeStruct
        src_s = jax.tree.map(lambda a: sds(a.shape, a.dtype), source)
        y_s, lam_s, i32_s = sds((n, k), dtype), sds((), dtype), sds((), jnp.int32)
        mask_s = sds((n, 1), dtype)
        res_s, m_s, c_s = sds((n, k), dtype), sds((bs, k), dtype), sds((bs, bs), dtype)
        operands, y_bytes = source.operand_bytes(), it * n * k
        res_dev = operands + (labels.nbytes if isinstance(labels, jax.Array) else 0)
        factors = it * nb * bs * bs
        persist = it * (n * k + nb * bs * k) + factors
        # temporaries: the block as the featurizer leaves it and its centred
        # copy, what the make holds beyond them, two residual carries, the
        # models carry; the factor stack and the kept blocks are results of
        # the fused program, not temporaries
        made_floor, made_out = _made_need(n, k, nb, bs, it)
        made_floor += bcd_plan["make_scratch_bytes"]
        keep, stack = bcd_plan["held_blocks"], bcd_plan["held_stack_bytes"]
        kept = dict(hold=keep, hold_dtype=bcd_plan["held_dtype"]) if keep else {}
        passes = self.num_iter + 1
        fused_makes = nb + (nb - keep) * self.num_iter

        def flops(makes):
            # a featurizer is charged as one [n, d] x [d, bs] product a
            # block it makes: the solver cannot see inside it
            return (
                2.0 * n * bs * bs * nb + self.num_iter * 4.0 * n * bs * k * nb
                + makes * 2.0 * n * int(source.rows.shape[1]) * bs
            )

        def plan_fused():
            return kmem.plan_program(
                _fused_bcd_fit, src_s, y_s, lam_s, i32_s,
                self.num_iter, widths, None,
                label="bcd_fused_made", budget=budget,
                min_temp_bytes=made_floor, resident_bytes=res_dev, **kept,
            )

        def plan_stepwise():
            return kmem.plan_program(
                _bcd_block_solve, src_s, None, mask_s, res_s, m_s, c_s, i32_s,
                bs, label="bcd_stepwise_made", budget=budget,
                extra_bytes=persist, resident_bytes=res_dev,
            )

        def run_fused(plan):
            with trace.host("place", "operands"):
                y_dev = jnp.asarray(labels)
            _count_blocks_made(nvalid, fused_makes)
            trace.metrics.inc("bcd.block_rows_held", nvalid * keep * self.num_iter)
            return _execute_fused_bcd(
                plan, (), source, y_dev, lam_arr, nv_arr, self.num_iter, widths,
            )[:3]  # the factor stack and the kept blocks are dropped here

        def run_stepwise(plan):
            y_dev = jnp.asarray(labels)
            reusable = plan is not None and _single_device_arrays(source, y_dev)
            return _stepwise_bcd_fit(
                source, y_dev, self.lam, nvalid, self.num_iter, widths,
                block_solve=plan.compiled if reusable else None,
            )

        report = kmem.FitReport(label="bcd_fit", budget_bytes=budget)
        self.last_fit_report = report
        common = {"arg_bytes": operands + y_bytes, "resident_bytes": res_dev}
        cands = [
            autoshard.Candidate(
                "fused[made]", "fused", plan_fused, run_fused,
                hints=dict(
                    common, temp_bytes=made_floor, out_bytes=made_out + stack,
                    flops=flops(fused_makes), hbm_passes=fused_makes / nb,
                    dispatches=1,
                ),
                prior_rank=0,
            ),
            autoshard.Candidate(
                "stepwise[made]", "stepwise", plan_stepwise, run_stepwise,
                hints=dict(
                    common, temp_bytes=it * (2 * n * bs + n * k),
                    out_bytes=it * nb * bs * k, extra_bytes=persist,
                    flops=flops(nb * passes), hbm_passes=passes,
                    dispatches=nb * passes + 2,
                ),
                prior_rank=1, floor=True,
            ),
        ]
        with kprof.phase("bcd_fit"):
            return autoshard.run_search(
                "bcd_fit", cands, report,
                fingerprint=autoshard.fingerprint(
                    "bcd_fit", "made", n, k, widths, self.num_iter,
                    str(source.dtype), str(dtype), None,
                    autoshard.device_fingerprint(),
                ),
                plan=plan_arg,
                budget=budget,
            )

    def _fit_ladder(
        self, features, x, labels, num_features, nvalid, widths, donate,
        plan_arg=None, report=None,
    ):
        """Single-device solve through the degradation ladder.

        Preflights each tier on ShapeDtypeStructs (nothing allocated to
        decide), runs the first admitted tier, and steps down one tier on a
        runtime RESOURCE_EXHAUSTED.  Rebuild closures re-derive device
        buffers from the ORIGINAL ``features``/``labels`` — which a default
        (``donate=None``) fit never donates — so a failed donating attempt
        still leaves the next tier a data source.
        """
        bs, nb = max(widths), len(widths)
        n, k = int(np.shape(labels)[0]), int(np.shape(labels)[1])
        dtype = jax.dtypes.canonicalize_dtype(labels.dtype)
        xdt = jax.dtypes.canonicalize_dtype(x.dtype)
        it = np.dtype(dtype).itemsize
        with trace.host("plan", "budget"):  # asks the device for its free bytes
            budget = kmem.hbm_budget()

        donate_x = donate if donate is not None else _design_matrix_owned(x, features)
        donate_y = donate if donate is not None else not isinstance(labels, jax.Array)
        dn = tuple(i for i, d in ((0, donate_x), (1, donate_y)) if d)

        with trace.host("place", "lam"):
            lam_arr = jnp.asarray(self.lam, dtype)
            nv_arr = jnp.asarray(nvalid, jnp.int32)
        sds = jax.ShapeDtypeStruct
        x_s, y_s = sds((n, nb * bs), xdt), sds((n, k), dtype)
        lam_s, i32_s = sds((), dtype), sds((), jnp.int32)
        mu_s, mask_s = sds((nb * bs,), xdt), sds((n, 1), dtype)
        res_s, m_s, c_s = sds((n, k), dtype), sds((bs, k), dtype), sds((bs, bs), dtype)
        # Caller inputs already on device: charged by every tier's plan
        # (they stay resident through the fit — run_host cannot free a
        # caller-owned buffer) and credited back when the budget is live
        # free bytes, which already excludes them.
        res_dev = (x.nbytes if isinstance(x, jax.Array) else 0) + (
            labels.nbytes if isinstance(labels, jax.Array) else 0
        )
        # Persistent device buffers the per-block programs' argument lists
        # do not see: labels + the models stack + the cached Cholesky
        # factors (and, host-staged, the cached block means).
        persist = it * (n * k + nb * bs * k + nb * bs * bs)
        # Analytic transient floor of the fused program — one centered
        # block, the chol stack, two residual carries, the models carry.
        # CPU backends report temp_size 0, which would otherwise rank the
        # fused program cheaper than its own stepwise decomposition.
        fused_floor = it * (n * bs + nb * bs * bs + 2 * n * k + nb * bs * k)

        def plan_fused():
            return kmem.plan_program(
                _fused_bcd_fit_variant(dn), x_s, y_s, lam_s, i32_s,
                self.num_iter, widths, None,
                label="bcd_fused", budget=budget, min_temp_bytes=fused_floor,
                resident_bytes=res_dev,
            )

        def plan_stepwise():
            return kmem.plan_program(
                _bcd_block_solve, x_s, mu_s, mask_s, res_s, m_s, c_s, i32_s,
                bs, label="bcd_stepwise", budget=budget, extra_bytes=persist,
                resident_bytes=res_dev,
            )

        def plan_host():
            return kmem.plan_program(
                _hs_block_solve, sds((n, bs), xdt), sds((bs,), xdt), mask_s,
                res_s, m_s, c_s,
                label="bcd_host_staged", budget=budget,
                extra_bytes=persist + it * nb * bs + res_dev,
                resident_bytes=res_dev,
            )

        def rebuild_x():
            xx, _ = _blocked_design_matrix(
                features, self.block_size, num_features, nvalid
            )
            if isinstance(xx, jax.Array) and xx.is_deleted():
                raise kmem.LadderSourceLost(
                    "design matrix was donated (donate=True) and the source "
                    "features are gone — cannot step the ladder down; refit "
                    "with donate=False to keep OOM recovery possible"
                )
            return xx

        def get_x():
            return rebuild_x() if isinstance(x, jax.Array) and x.is_deleted() else x

        def get_y_dev():
            if isinstance(labels, jax.Array) and labels.is_deleted():
                raise kmem.LadderSourceLost(
                    "labels were donated (donate=True) and cannot be rebuilt "
                    "for the ladder step-down"
                )
            return jnp.asarray(labels)

        def run_fused(plan):
            with trace.host("place", "operands"):
                x_dev, y_dev = jnp.asarray(get_x()), get_y_dev()
            return _execute_fused_bcd(
                plan, dn, x_dev, y_dev, lam_arr, nv_arr, self.num_iter, widths,
            )

        def run_stepwise(plan):
            x_dev, y_dev = jnp.asarray(get_x()), get_y_dev()
            reusable = (
                plan is not None and _single_device_arrays(x_dev, y_dev)
            )
            return _stepwise_bcd_fit(
                x_dev, y_dev, self.lam, nvalid, self.num_iter, widths,
                # The preflight already compiled the per-block solve on
                # these very avals — execute that executable instead of
                # paying a second compile at first jit dispatch.  (Sharded
                # caller inputs fall back to the jitted entry: the planned
                # program baked single-device placements.)
                block_solve=plan.compiled if reusable else None,
            )

        def run_host(plan):
            xx = get_x()
            x_h = (
                np.asarray(jax.device_get(xx))
                if isinstance(xx, jax.Array) else np.asarray(xx)
            )
            if isinstance(xx, jax.Array) and _design_matrix_owned(xx, features):
                # Fit-owned device copy (initial or rebuilt): the host tier
                # must not keep the full matrix resident in HBM while
                # streaming blocks — that residency is what it exists to
                # avoid.  Caller-owned arrays are left alone.
                kmem.free_buffers(xx)
            return _host_staged_bcd_fit(
                x_h, get_y_dev(), self.lam, nvalid, self.num_iter, widths
            )

        if report is None:
            report = kmem.FitReport(label="bcd_fit", budget_bytes=budget)
            self.last_fit_report = report
        itx = np.dtype(xdt).itemsize
        x_bytes, y_bytes = itx * n * nb * bs, it * n * k
        flops = (
            2.0 * n * bs * bs * nb + self.num_iter * 4.0 * n * bs * k * nb
        )
        per_block_dispatches = nb * (self.num_iter + 1) + 2
        cands = [
            autoshard.Candidate(
                "fused", "fused", plan_fused, run_fused,
                hints={
                    "arg_bytes": x_bytes + y_bytes,
                    # The donating variant aliases its donated args — the
                    # zero-cost prune must stay a lower bound of the
                    # compiled admission, which credits them back.
                    "alias_bytes": (
                        (x_bytes if 0 in dn else 0)
                        + (y_bytes if 1 in dn else 0)
                    ),
                    "temp_bytes": fused_floor,
                    "out_bytes": it * (nb * bs * k + k + nb * bs),
                    "resident_bytes": res_dev,
                    "flops": flops,
                    "dispatches": 1,
                    "hbm_passes": self.num_iter + 1,
                },
                prior_rank=0,
            ),
            autoshard.Candidate(
                "stepwise", "stepwise", plan_stepwise, run_stepwise,
                hints={
                    "arg_bytes": x_bytes + y_bytes,
                    "temp_bytes": it * (n * bs + n * k),
                    "out_bytes": it * nb * bs * k,
                    "extra_bytes": persist,
                    "resident_bytes": res_dev,
                    "flops": flops,
                    "dispatches": per_block_dispatches,
                    "hbm_passes": self.num_iter + 1,
                },
                prior_rank=1,
            ),
            autoshard.Candidate(
                "host_staged", "host_staged", plan_host, run_host,
                hints={
                    "arg_bytes": itx * n * bs + y_bytes,
                    "temp_bytes": it * n * k,
                    "extra_bytes": persist + it * nb * bs,
                    "resident_bytes": res_dev,
                    "flops": flops,
                    "dispatches": per_block_dispatches,
                    # Each epoch re-streams every block over PCIe — the
                    # term that keeps the floor at the bottom of every
                    # untrained ranking.
                    "h2d_bytes": self.num_iter * x_bytes,
                },
                prior_rank=2, floor=True,
            ),
        ]
        with kprof.phase("bcd_fit"):
            return autoshard.run_search(
                "bcd_fit", cands, report,
                fingerprint=autoshard.fingerprint(
                    "bcd_fit", n, k, widths, self.num_iter, str(xdt),
                    str(dtype), None, autoshard.device_fingerprint(),
                ),
                plan=plan_arg,
                budget=budget,
            )
