"""Class-weighted block coordinate descent least squares
(reference src/main/scala/nodes/learning/BlockWeightedLeastSquares.scala:35-362).

The reference re-shuffles the data so each Spark partition holds exactly one
class (HashPartitioner on the argmax class index, :324-361), then per pass per
block: tree-reduces population gram/XᵀR statistics, broadcasts them, runs a
per-class local solve on each partition, collects the per-class weight
columns, and updates a cached residual RDD.

TPU-native re-design:

* the class shuffle becomes a host-side stable sort by class (one-time);
* population statistics are plain gemms over the sorted [N, d] block — under
  ``jit`` with row-sharded inputs XLA lowers them to local gram + ICI
  all-reduce (the treeReduce replacement);
* the per-class solves run as a ``lax.scan`` over *chunks* of classes —
  ``class_chunk`` classes are gathered and their mixture-weighted normal
  equations factored and solved together by the solver's own blocked
  routine, ``_factor_solve`` (the reference solves all classes concurrently
  across partitions, :228-263): a left-looking Cholesky over panels 128
  wide that assembles each panel of the systems as it reaches it, inverts
  each diagonal block once and keeps the inverse for both substitutions,
  carries the right-hand side through the factorization as one more row,
  and writes only the panels it later reads, into one ``[chunk, d + 1, d]``
  scratch that is the scan's carry.  Only that scratch and a
  ``[chunk, n_max, d]`` slab are ever materialized: no ``[chunk, d, d]``
  array of systems, never the full [C, n_max, d] tensor;
* with a mesh, features are row-sharded over the data axis.  Where the
  mesh's ``model`` axis is 1 the classes split over ``data``, the
  reference's one-partition-per-class layout (:324-361): one ``all_to_all``
  regroup (``_RegroupPlan``) leaves every chip whole classes, its rows
  contiguous and padded to the largest chip's share (``_class_split``); the
  population mean, gram, class sums and XᵀR are summed across the chips and
  replicated; each chip factors and solves its own classes from its own rows
  and the model block's columns are gathered once a block
  (``_split_bwls_impl``, under ``shard_map``).  Under a ``model`` split the
  population grams lower to local gram + ICI all-reduce and each class chunk
  is sharded over the model axis;
* broadcasts/collects disappear (single-controller, arrays stay in HBM).

Semantics (update order, statistics caching across passes, the λ-shifted
solve, and the joint-means intercept) follow the reference exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from jax.sharding import NamedSharding, PartitionSpec as P

import dataclasses

from ..core import memory as kmem
from ..core import numerics as knum
from ..core import profiler as kprof
from ..core import trace
from ..core.pipeline import LabelEstimator
from ..core.resilience import counters
from ..parallel.collectives import count_psum
from ..parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    current_mesh,
    mesh_desc,
    reduced_mesh,
)
from .block import (
    BlockLinearMapper,
    _blocked_design_matrix,
    _design_matrix_owned,
    split_model,
)

#: The statistics of the normal equations (the population gram, a class's
#: covariance, both XᵀR) ask full float32 products: on a TPU a float32 matmul
#: is otherwise one bfloat16 pass, whose rounding of the gram is of the size
#: of ImageNetSiftLcsFV's λ = 6e-5 on unit-norm Fisher rows (PERF.md, PR 34).
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class _SolveCtx:
    """Mesh-dependent BWLS solve layout for ONE ladder tier: the padded
    row count, the class-chunk rounding, and the sort/pad/shard closures
    all follow the tier's mesh axis sizes, so each rung of the mesh
    degradation ladder builds its own (see ``fit``'s ``prep``)."""

    mesh: object
    p_tot: int
    chunk: int
    sort_pad: object
    sort_labels: object
    valid_d: object
    seg_ids: object
    starts: object
    counts: object
    counts_f: object
    joint_label_mean: object

# Per-row byte budget for the column-chunked device gather in the class
# shuffle: each chunk transiently materializes [p_tot, chunk_bytes] un-sharded
# per device (e.g. 2 KB/row x 1.25M rows = 2.5 GB slab at ImageNet scale).
# Fallback path only — see _RegroupPlan for the all_to_all fast path.
_GATHER_COL_CHUNK = 2048


class _RegroupPlan:
    """Host-precomputed routing for the TRAFFIC-OPTIMAL class shuffle: a
    device-side all_to_all permutation in which each row crosses the ICI
    exactly once (reference BlockWeightedLeastSquares.scala:324-361 — its
    HashPartitioner shuffle likewise moves each row once between executors).

    Traffic model (the reason this path exists): the fallback chunked
    replicated-index gather below makes GSPMD all-gather every column slab,
    so the matrix crosses the interconnect D times (once per device).  At
    the 1.25M x 256k f32 north star that is D x 1.28 TB (41 TB on a
    32-chip pod) versus 1.28 TB moved once here — a D x reduction, worth
    minutes of pod time at ~100 GB/s per-link ICI.

    Construction: rows are grouped by (source shard, destination shard);
    each device locally gathers its send buckets (padded to the max bucket
    ``m_pad``), one lax.all_to_all exchanges them, and a local gather (with
    out-of-range fill) places received rows and zeroes the tail.  The only
    overhead vs optimal is bucket padding (m_pad * D^2 / n rows).  ``place``
    is where each sorted row lands in the ``p_tot`` rows (the first ``n``
    where it is not given; ``_class_split``'s layout where the classes
    split over the chips).
    """

    def __init__(self, order: np.ndarray, n_src: int, p_tot: int, d: int, place=None):
        n = order.shape[0]
        rows_in, rows_out = n_src // d, p_tot // d
        with trace.host("place", "regroup", rows=n, shards=d):
            r = np.arange(n) if place is None else place
            src = order // rows_in
            dst = r // rows_out
            # occurrence rank of each row within its (src, dst) bucket,
            # preserving destination order
            key = src * d + dst
            by_key = np.argsort(key, kind="stable")
            ks = key[by_key]
            change = np.r_[True, ks[1:] != ks[:-1]]
            grp_start = np.maximum.accumulate(np.where(change, np.arange(n), 0))
            j = np.empty(n, np.int64)
            j[by_key] = np.arange(n) - grp_start
            m_pad = int(j.max()) + 1 if n else 1

            send = np.zeros((d, d, m_pad), np.int32)
            send[src, dst, j] = (order % rows_in).astype(np.int32)
            # received layout on dst: [src bucket, j] -> flat src*m_pad + j;
            # out-of-range index for the zero tail (jnp.take mode="fill")
            recv = np.full((d, rows_out), d * m_pad, np.int32)
            recv[dst, r % rows_out] = (src * m_pad + j).astype(np.int32)

        self.d = d
        self.m_pad = m_pad
        self.rows_out = rows_out
        # Skew guard: buckets pad to the GLOBAL max m_pad, so a
        # class-correlated input order (near-identity permutation) would
        # make the per-device exchange buffer [d*m_pad, cols] approach the
        # full unsharded block — exactly the slab the chunked fallback
        # exists to bound.  Usable only while padding stays within 2x of
        # optimal; an unusable plan allocates NO device buffers.
        self.usable = d * m_pad <= 2 * rows_out
        if self.usable:
            self.send_idx = jnp.asarray(send)
            self.recv_idx = jnp.asarray(recv)

    def exchanged_bytes(self, x) -> int:
        """Bytes the all_to_all of ``x`` sends between chips: every chip's
        padded buckets but its own."""
        return self.d * (self.d - 1) * self.m_pad * int(np.prod(x.shape[1:])) * x.dtype.itemsize

    def apply(self, mesh, x):
        """Sorted + zero-tail-padded copy of row-sharded ``x`` via one
        all_to_all; output row-sharded over the data axis."""
        return _regroup_program(mesh, self.d, self.m_pad)(x, self.send_idx, self.recv_idx)


@functools.lru_cache(maxsize=None)
def _regroup_program(mesh, d: int, m_pad: int):
    """The regroup's one program for a mesh and bucket size, kept across
    fits: every fit makes a plan of its own, and a program made anew with it
    would be traced and compiled again each fit."""

    def _regroup_rows(x_l, s_l, r_l):
        cols = x_l.shape[1]
        buf = jnp.take(x_l, s_l[0].reshape(-1), axis=0)
        buf = buf.reshape(d, m_pad, cols)
        recv = jax.lax.all_to_all(buf, DATA_AXIS, 0, 0)
        flat = recv.reshape(d * m_pad, cols)
        return jnp.take(flat, r_l[0], axis=0, mode="fill", fill_value=0)

    return jax.jit(
        shard_map(
            _regroup_rows,
            mesh=mesh,
            in_specs=(
                P(DATA_AXIS, None),
                P(DATA_AXIS, None, None),
                P(DATA_AXIS, None),
            ),
            out_specs=P(DATA_AXIS, None),
        )
    )


#: Panel width of ``_factor_solve``, whatever the systems' width: at 4,096
#: columns and sixteen classes a chunk the sweep over 1,000 classes took
#: 1,839 ms at 128, 2,139 at 256 and 2,346 at 512 on a TPU v5e (ROOFLINE.md,
#: "The class systems").  The panel products run at four fifths of the MXU's
#: peak at 128 already, and a wider diagonal block is factored and inverted
#: by the library in steps of 128, which inverts its blocks a second time.
_FACTOR_PANEL = 128


def _factor_panels(d: int) -> list[tuple[int, int]]:
    """Column ranges of the panels of a system ``d`` wide; the last may be
    narrower."""
    return [(r0, min(r0 + _FACTOR_PANEL, d)) for r0 in range(0, d, _FACTOR_PANEL)]


def _dot(a, b, a_axis: int, b_axis: int):
    """Batched ``HIGHEST`` product over axis ``a_axis`` of ``a`` and ``b_axis``
    of ``b``, the batch their first axes.  (``lax.dot_general`` and not
    ``jnp.einsum``: the blocked routine below makes some 160 products of as
    many shapes, and tracing them as einsums took three times as long.)"""
    return jax.lax.dot_general(
        a, b, (((a_axis,), (b_axis,)), ((0,), (0,))), precision=_HIGHEST
    )


def _factor_solve(panel, d: int, work):
    """Factor a chunk of symmetric positive definite systems and solve each
    for its right-hand side: a left-looking blocked Cholesky that keeps what
    it computes.

    ``panel(r0, r1)`` gives ``[B, d + 1 - r0, r1 - r0]``: columns ``r0:r1`` of
    every system from row ``r0`` down, and beneath them, as row ``d``, the
    same columns of its right-hand side.  A panel is updated by one product
    with the panels to its left (``S = A - L Lᵀ``) and its diagonal block
    factored by ``lax.linalg.cholesky``.  The rows below are then solved
    against that block's transpose with the identity in the dead block's
    place above them, so the one triangular solve (the block inverted
    **once**, one product) yields ``L_kk⁻ᵀ`` above the panel of ``L``, and
    ``work`` takes both as they come.  The right-hand side's row takes part
    in both products, so it leaves the last panel as ``y = L⁻¹ b``: the
    forward substitution costs no pass of its own.  The back substitution
    ``Lᵀ x = y`` reads each panel once, block row by block row, with the
    inverses kept from the factorization.

    ``work`` is ``[B, d + 1, d]`` scratch: the panels of ``L`` below the
    diagonal, ``L_kk⁻ᵀ`` in each diagonal block's place, ``y`` as row ``d``.
    Only what this call wrote is read, and nothing above the diagonal, so
    its content on entry does not matter and nothing has to clear it.  A
    system that is not positive definite makes its diagonal block's factor
    NaN, and the products carry that into every later panel and all of its
    ``x``.  Every product is ``HIGHEST`` (the library's triangular solve
    asks that of its own).

    Returns ``(x [B, d], work)``.
    """
    panels = _factor_panels(d)
    eyes = {
        q: jnp.broadcast_to(jnp.eye(q, dtype=work.dtype), (work.shape[0], q, q))
        for q in {r1 - r0 for r0, r1 in panels}
    }
    for r0, r1 in panels:
        q = r1 - r0
        s = panel(r0, r1)
        if r0:
            s = s - _dot(work[:, r0:, :r0], work[:, r0:r1, :r0], 2, 2)
        l_kk = jax.lax.linalg.cholesky(s[:, :q], symmetrize_input=False)
        solved = jax.lax.linalg.triangular_solve(
            l_kk, jnp.concatenate([eyes[q], s[:, q:]], axis=1),
            left_side=False, lower=True, transpose_a=True,
        )  # [I; S_below] L_kk⁻ᵀ
        work = jax.lax.dynamic_update_slice(work, solved, (0, r0, r0))
    y = work[:, d]
    x = y[:, d:]  # grows upwards from the last block
    for r0, r1 in reversed(panels):
        t = y[:, r0:r1]
        if r1 < d:
            t = t - _dot(work[:, r1:d, r0:r1], x, 1, 1)  # L_belowᵀ x
        x_k = _dot(work[:, r0:r1, r0:r1], t, 2, 1)  # L_kk⁻ᵀ t
        x = jnp.concatenate([x_k, x], axis=1)
    return x, work


@functools.partial(jax.jit, static_argnames=("n_max", "chunk", "mesh"))
def _class_solves(
    xb_pad,  # [N + pad, d] sorted block features, zero tail
    res_pad,  # [N + pad, C] sorted residual, zero tail
    starts,  # [C]
    counts,  # [C]
    pop_cov,  # [d, d]
    pop_mean,  # [d]
    pop_xtr,  # [d, C]
    joint_means,  # [C, d]
    residual_mean,  # [C]
    model_block,  # [d, C]
    lam,
    mixture_weight,
    n_max: int,
    chunk: int,
    mesh=None,
    classes=None,
):
    """Per-class solve sweep (reference :228-263): scan over class chunks,
    ``chunk`` concurrent solves per step — returns ΔW [d, C].

    ``classes`` given, the sweep solves one system a *slot*: slot ``s`` is
    class ``classes[s]`` (its columns of ``res_pad``, ``pop_xtr``,
    ``joint_means``, ``residual_mean``, ``model_block``) on the rows
    ``starts[s]:starts[s] + counts[s]`` of ``xb_pad``, and ΔW is ``[d,
    slots]``: a chip's own classes from its own rows (``_split_bwls_impl``).
    """
    d = xb_pad.shape[1]
    c_total = starts.shape[0]
    w = mixture_weight
    dtype = xb_pad.dtype
    row_ids = jnp.arange(n_max)
    # What every class's system shares (reference :252-258): the population's
    # share of the mixture and λI.  Pad columns hold a unit diagonal here.
    shared = pop_cov * (1.0 - w) + lam * jnp.eye(d, dtype=dtype)

    def one_class(start, cnt, c, xtr_c, jm_c, rm_c, m_c):
        """A class's share of its system: its centred rows, their count, the
        mean's offset from the population's, and the right-hand side."""
        xc = jax.lax.dynamic_slice(xb_pad, (start, 0), (n_max, d))
        mask = (row_ids < cnt).astype(dtype)
        xc = xc * mask[:, None]
        # this class's own residual column (:231)
        r_c = jax.lax.dynamic_slice(res_pad, (start, c), (n_max, 1))[:, 0] * mask
        n_c = cnt.astype(dtype)

        class_mean = jnp.sum(xc, axis=0) / n_c
        zm = (xc - class_mean) * mask[:, None]
        class_xtr = jnp.matmul(xc.T, r_c, precision=_HIGHEST) / n_c

        mean_mixture_wt = rm_c * (1.0 - w) + w * (jnp.sum(r_c) / n_c)
        joint_xtr = xtr_c * (1.0 - w) + class_xtr * w - jm_c * mean_mixture_wt
        # λ-shifted right-hand side (reference :259-260)
        return zm, n_c, class_mean - pop_mean, joint_xtr - m_c * lam

    def solve_chunk(work, inp):
        zm, n_c, mean_diff, rhs = jax.vmap(one_class)(*inp)
        cov_weight = (w / n_c)[:, None, None]
        mean_rows = mean_diff[:, :, None]
        mean_cols = (mean_diff * ((1.0 - w) * w))[:, None, :]
        rhs_row = rhs[:, None, :]

        def panel(r0, r1):
            """Columns ``r0:r1`` of the chunk's systems from row ``r0`` down
            (reference :252-258), the right-hand sides beneath.  The system
            is symmetric positive definite by construction (a mixture of
            covariances, a rank-one term and λI; pad columns carry a unit
            diagonal), and is assembled a panel at a time, each at the
            block's full width: no ``[chunk, d, d]`` array of systems exists
            beside the factor."""
            system = (
                shared[r0:, r0:r1]
                + _dot(zm[:, :, r0:], zm[:, :, r0:r1], 1, 1) * cov_weight
                + mean_rows[:, r0:] * mean_cols[:, :, r0:r1]
            )
            return jnp.concatenate([system, rhs_row[:, :, r0:r1]], axis=1)

        return _factor_solve(panel, d, work)

    # Pad the class axis to a chunk multiple by repeating class 0 (results
    # for the repeats are discarded; repeating a real class keeps every
    # batched solve well-conditioned).
    n_chunks = -(-c_total // chunk)
    cls = jnp.arange(c_total)
    cls_pad = jnp.concatenate(
        [cls, jnp.zeros(n_chunks * chunk - c_total, cls.dtype)]
    )
    ids = cls_pad if classes is None else classes[cls_pad]

    def chunked(x):
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    xs = (
        chunked(starts[cls_pad]),
        chunked(counts[cls_pad]),
        chunked(ids),
        chunked(pop_xtr.T[ids]),
        chunked(joint_means[ids]),
        chunked(residual_mean[ids]),
        chunked(model_block.T[ids]),
    )

    model_spec = work_spec = None
    if mesh is not None and chunk % mesh.shape[MODEL_AXIS] == 0:
        model_spec = NamedSharding(mesh, P(MODEL_AXIS, None))
        work_spec = NamedSharding(mesh, P(MODEL_AXIS, None, None))

    def step(work, inp):
        dws, work = solve_chunk(work, inp)  # [chunk, d]
        if model_spec is not None:
            # Class-partitioned parallelism: each device in the model axis
            # owns chunk/model_size of the concurrent class solves.
            dws = jax.lax.with_sharding_constraint(dws, model_spec)
            work = jax.lax.with_sharding_constraint(work, work_spec)
        return work, dws

    # The factor's scratch is the scan's carry: written once here, and each
    # chunk overwrites the panels it reads.
    work = jnp.zeros((chunk, d + 1, d), dtype)
    _, dws = jax.lax.scan(step, work, xs)  # [n_chunks, chunk, d]
    return dws.reshape(n_chunks * chunk, d)[:c_total].T  # [d, C]


def _bwls_passes(
    x, labels_sorted, valid, seg_ids, counts_f, joint_label_mean, n, w,
    reduce, solve, num_iter: int, num_classes: int, widths,
):
    """The BWLS solve's body (reference :134-311): residual init, per-block
    population statistics (computed once, cached across passes like the
    reference's persisted grams), ``num_iter`` passes of a lax.scan over
    blocks (population XᵀR + class-solve sweep + model and residual updates
    + residual class means), and the joint-means intercept.

    ``reduce`` sums a statistic of the rows at hand over every row of the
    fit (the identity where one program holds them all, ``psum`` over the
    data axis under ``shard_map``); ``solve(xb, res, pop_cov, pop_mean,
    pop_xtr, joint_means, residual_mean, model)`` is the class-solve sweep,
    ΔW ``[bs, C]``.  Pad columns of ``x`` get a unit diagonal shift on the
    population covariance (scaled by (1-w) > 0 in the joint normal
    equations), so their solutions are exactly zero and every batched solve
    stays nonsingular even at lam=0.

    Returns (models [B, bs, C], intercept [C])."""
    bs = max(widths)
    nb = len(widths)
    dtype = labels_sorted.dtype

    def class_means(v):
        return reduce(_class_sums(v, seg_ids, num_classes)) / counts_f[:, None]

    def residual_means(res):
        return jnp.mean(class_means(res), axis=0)

    res = (labels_sorted - joint_label_mean) * valid
    rmean = residual_means(res)

    pad_diag = jnp.stack(
        [(jnp.arange(bs) >= wd).astype(dtype) for wd in widths]
    )  # [B, bs] — 1.0 on pad columns

    def slice_block(i):
        return jax.lax.dynamic_slice_in_dim(x, i * bs, bs, axis=1)

    def stats_one(carry, inp):
        i, pd = inp
        xb = slice_block(i)
        pop_mean = reduce(jnp.sum(xb, axis=0)) / n
        pop_cov = (
            reduce(jnp.matmul(xb.T, xb, precision=_HIGHEST)) / n
            - jnp.outer(pop_mean, pop_mean) + jnp.diag(pd)
        )
        joint_means = w * class_means(xb) + (1.0 - w) * pop_mean
        return carry, (pop_cov, pop_mean, joint_means)

    _, (pop_covs, pop_means, joint_means_all) = jax.lax.scan(
        stats_one, None, (jnp.arange(nb), pad_diag)
    )

    models = jnp.zeros((nb, bs, num_classes), dtype)

    def block_step(carry, inp):
        res, rmean = carry
        i, pop_cov, pop_mean, jm, model = inp
        xb = slice_block(i)
        pop_xtr = reduce(jnp.matmul(xb.T, res, precision=_HIGHEST)) / n
        dw = solve(xb, res, pop_cov, pop_mean, pop_xtr, jm, rmean, model)
        res_new = res - xb @ dw
        return (res_new, residual_means(res_new)), model + dw

    def one_pass(carry, _):
        models, res, rmean = carry
        (res, rmean), models = jax.lax.scan(
            block_step,
            (res, rmean),
            (jnp.arange(nb), pop_covs, pop_means, joint_means_all, models),
        )
        return (models, res, rmean), None

    (models, res, rmean), _ = jax.lax.scan(
        one_pass, (models, res, rmean), None, length=num_iter
    )

    # Intercept from joint means (reference :307-311):
    # b = jointLabelMean − Σ_d jointMeans[c, d] · W[d, c]
    intercept = joint_label_mean - jnp.einsum(
        "bcd,bdc->c", joint_means_all, models
    )
    return models, intercept


def _fused_bwls_impl(
    x, labels_sorted, valid, seg_ids, starts, counts, counts_f,
    joint_label_mean, nvalid, lam, w,
    num_iter: int, n_max: int, chunk: int, num_classes: int, widths, mesh,
):
    """The ENTIRE BWLS solve (:func:`_bwls_passes`) as one compiled program
    (the BlockLeastSquares treatment, solvers/block._fused_bcd_fit): one
    program per fit instead of ~5 eager dispatches per block per pass.

    x: ONE sorted, zero-tail-padded [P, B*bs] design matrix (bs =
    max(widths)); block i occupies columns [i*bs, i*bs + widths[i]) with
    zero pad columns.  Scan steps dynamic-slice their block out of ``x``,
    so peak HBM is one design matrix plus a single [P, bs] block slice —
    the round-4 form stacked blocks into a [B, P, bs] tensor, transiently
    doubling the footprint.

    With ``mesh``: rows shard over the data axis; the sorted labels (and
    through them the residual carries) keep the caller's placement.

    Returns (models [B, bs, C], intercept [C]).
    """
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(DATA_AXIS, None))
        )

    def solve(xb, res, *stats):
        return _class_solves(
            xb, res, starts, counts, *stats, lam, w, n_max, chunk, mesh,
        )

    return _bwls_passes(
        x, labels_sorted, valid, seg_ids, counts_f, joint_label_mean,
        nvalid.astype(labels_sorted.dtype), w, lambda v: v, solve,
        num_iter, num_classes, widths,
    )


@dataclasses.dataclass(frozen=True)
class _ClassSplit:
    """The classes split over a data axis of ``d`` chips: chip ``k`` solves
    classes ``bounds[k]:bounds[k + 1]`` (``C // d`` or one more) from its
    own rows, which the regroup lays out contiguously at ``k * rows_out``,
    sorted by class, its share padded with zero rows to ``rows_out`` (the
    largest share and ``n_max``, so that every class's slice of ``n_max``
    rows stays inside the chip).  A chip's systems are ``slots`` wide: slot
    ``j`` is its ``j``-th class, and a chip with fewer classes repeats its
    first in the slots left over (solved, then dropped).  ``columns[c]`` is
    class ``c``'s column among the chips' gathered ``[d * slots]``."""

    bounds: np.ndarray  # [d + 1]
    rows: np.ndarray  # [d] rows of each chip's classes
    rows_out: int
    place: np.ndarray  # [n] row of each class-sorted row in the layout
    slot_starts: np.ndarray  # [d, slots] first row of a slot's class on its chip
    slot_counts: np.ndarray  # [d, slots]
    slot_classes: np.ndarray  # [d, slots]
    columns: np.ndarray  # [C]


def _class_split(counts: np.ndarray, d: int, n_max: int) -> _ClassSplit:
    """Whole classes to each of ``d`` chips, as evenly by count as they go
    (every chip solves ``C // d`` or one more systems, and a system's cost is
    its width's, whatever its rows); needs ``C >= d``."""
    c = len(counts)
    bounds = np.array([k * c // d for k in range(d + 1)])
    starts = np.concatenate([[0], np.cumsum(counts)])  # [C + 1]
    first = starts[bounds[:-1]]
    rows = starts[bounds[1:]] - first
    rows_out = int(rows.max()) + n_max
    chip_of_class = np.repeat(np.arange(d), np.diff(bounds))
    chip_of_row = np.repeat(chip_of_class, counts)
    place = chip_of_row * rows_out + np.arange(int(starts[-1])) - first[chip_of_row]
    per_chip = np.diff(bounds)
    slots = int(per_chip.max())
    j = np.arange(slots)[None, :]
    slot_classes = np.where(j < per_chip[:, None], bounds[:-1, None] + j, bounds[:-1, None])
    columns = chip_of_class * slots + np.arange(c) - bounds[chip_of_class]
    return _ClassSplit(
        bounds=bounds, rows=rows, rows_out=rows_out, place=place,
        slot_starts=(starts[slot_classes] - first[:, None]).astype(np.int32),
        slot_counts=counts[slot_classes].astype(np.int32),
        slot_classes=slot_classes.astype(np.int32),
        columns=columns.astype(np.int32),
    )


def _split_bwls_impl(
    x, labels_sorted, valid, seg_ids, slot_starts, slot_counts, slot_classes,
    columns, counts_f, joint_label_mean, nvalid, lam, w,
    num_iter: int, n_max: int, chunk: int, num_classes: int, widths, mesh,
):
    """The BWLS solve (:func:`_bwls_passes`) with the classes split over
    ``mesh``'s data axis (``_class_split``'s layout), one program under
    ``shard_map``.  A chip holds its rows of the design matrix, of the sorted
    labels and of the residual; the population mean, gram, class sums and
    XᵀR are its rows' sums summed across the chips (``psum``) and
    replicated; it factors and solves its own classes' systems from its own
    rows (``_class_solves`` over its slots), and the chips' ΔW columns are
    gathered once a block into the replicated model.  Returns (models [B,
    bs, C], intercept [C]), replicated."""

    def local(x, ys, valid, seg, starts, counts, classes, columns, counts_f, jlm, nvalid, lam, w):
        starts, counts, classes = starts[0], counts[0], classes[0]

        def solve(xb, res, *stats):
            own = _class_solves(
                xb, res, starts, counts, *stats, lam, w, n_max, chunk, None, classes,
            )  # [bs, slots]: this chip's classes
            gathered = jax.lax.all_gather(own, DATA_AXIS, axis=1, tiled=True)
            return jnp.take(gathered, columns, axis=1)  # [bs, C]

        return _bwls_passes(
            x, ys, valid, seg, counts_f, jlm, nvalid.astype(ys.dtype), w,
            functools.partial(jax.lax.psum, axis_name=DATA_AXIS), solve,
            num_iter, num_classes, widths,
        )

    rows = P(DATA_AXIS, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(rows, rows, rows, P(DATA_AXIS), rows, rows, rows) + (P(),) * 6,
        out_specs=(P(), P()),
        check_vma=False,
    )(
        x, labels_sorted, valid, seg_ids, slot_starts, slot_counts, slot_classes,
        columns, counts_f, joint_label_mean, nvalid, lam, w,
    )


_BWLS_STATICS = (
    "num_iter", "n_max", "chunk", "num_classes", "widths", "mesh",
)

#: The class-split solve as one program.  It donates the sorted design
#: matrix and sorted labels, copies the fit itself made (``sort_pad``).
_split_bwls_fit = jax.jit(
    _split_bwls_impl, static_argnames=_BWLS_STATICS, donate_argnums=(0, 1)
)


def _split_psum_bytes(itemsize: int, widths, num_classes: int, num_iter: int) -> int:
    """Bytes of the arrays ``_split_bwls_impl`` sums across the chips: a
    block's column sums, gram and class sums once, its XᵀR and the
    residual's class sums once a pass, and the residual's class sums once
    before the first."""
    bs, nb, c = max(widths), len(widths), num_classes
    stats = nb * (bs + bs * bs + c * bs)
    steps = nb * num_iter * (bs * c + c * c)
    return itemsize * (stats + steps + c * c)


def _execute_split_bwls(args, statics):
    """Dispatch the class-split solve.  Module level so that a harness can
    stand in for it."""
    return _split_bwls_fit(*args, *statics)


@functools.lru_cache(maxsize=None)
def _fused_bwls_fit_variant(donate_argnums: tuple = ()):
    """jit of the fused BWLS solve with a chosen donation set.  ``(0, 1)``
    donates the sorted design matrix and sorted labels — both are copies
    the fit itself created in ``sort_pad``, never caller-visible arrays, so
    the single-device fit donates them unconditionally and XLA reuses their
    HBM for the residual/block temps."""
    return jax.jit(
        _fused_bwls_impl,
        static_argnames=_BWLS_STATICS,
        donate_argnums=donate_argnums,
    )


#: Historical non-donating entry point (the mesh path and AOT benches).
_fused_bwls_fit = _fused_bwls_fit_variant(())


def _execute_fused_bwls(plan, args, statics):
    """Dispatch the fused BWLS program: the planned AOT executable when
    admission ran, else the donating jitted variant (also the resilient
    fallback when the sorted inputs are sharded — a single-device plan
    baked single-device placements).  Module level so benches capture the
    exact solve arguments here and the fault harness injects
    RESOURCE_EXHAUSTED to exercise the ladder step-down."""
    from .block import _single_device_arrays

    if (
        plan is not None
        and plan.compiled is not None
        and _single_device_arrays(*args)
    ):
        return plan.compiled(*args)
    return _fused_bwls_fit_variant((0, 1))(*args, *statics)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_cols(out, g, c0):
    """One column-chunk landing in the preallocated gather output.  The
    donated ``out`` buffer is updated in place (TPU aliases it), so the
    chunked sort_pad gather peaks at source + output + ONE chunk — the
    round-5 form accumulated every chunk in a list and concatenated,
    transiently holding ~3x the design matrix (ADVICE r5)."""
    return jax.lax.dynamic_update_slice(out, g, (jnp.int32(0), c0))


@functools.partial(jax.jit, static_argnames=("num_classes",))
def _bwls_block_stats(xb, seg_ids, counts_f, n, w, pad_diag_i, num_classes: int):
    """Population/per-class statistics of ONE block — the per-block body of
    the fused program's stats scan, exposed as its own program for the
    stepwise/host-staged ladder tiers (identical math, one dispatch per
    block)."""
    pop_mean = jnp.sum(xb, axis=0) / n
    pop_cov = (
        jnp.matmul(xb.T, xb, precision=_HIGHEST) / n
        - jnp.outer(pop_mean, pop_mean) + jnp.diag(pad_diag_i)
    )
    class_means = _class_sums(xb, seg_ids, num_classes) / counts_f[:, None]
    return pop_cov, pop_mean, w * class_means + (1.0 - w) * pop_mean


@jax.jit
def _bwls_block_xtr(xb, res, n):
    return jnp.matmul(xb.T, res, precision=_HIGHEST) / n


@jax.jit
def _bwls_block_apply(xb, res, model, dw):
    return model + dw, res - xb @ dw


def _stepwise_bwls_fit(
    get_block, labels_sorted, valid, seg_ids, starts, counts, counts_f,
    joint_label_mean, nvalid, lam, w,
    num_iter: int, n_max: int, chunk: int, num_classes: int, widths,
    class_solves=None,
):
    """The BWLS solve driven from the host one block at a time — the
    stepwise/host-staged rungs of the degradation ladder.  ``get_block(i)``
    returns block i as a device [P, bs] array: a device-side slice of the
    sorted design matrix (stepwise — bounds per-dispatch temps) or an H2D
    upload from a host-resident sorted matrix (host-staged — the design
    matrix never fully occupies HBM; peak device residency is one block +
    the residual + the per-block statistics caches).  Statistics are
    computed once and cached across passes, and the update order matches
    ``_fused_bwls_fit`` exactly, so results are numerically identical.

    ``class_solves``: the preflight's AOT-compiled class-solve executable
    (``plan.compiled`` — statics baked, same avals), so the degraded tier
    executes the very program admission planned instead of recompiling
    ``_class_solves`` at first jit dispatch; ``None`` → the jitted entry.
    """
    bs = max(widths)
    nb = len(widths)
    dtype = labels_sorted.dtype
    n = jnp.asarray(nvalid, dtype)
    w_arr = jnp.asarray(w, dtype)
    lam_arr = jnp.asarray(lam, dtype)

    def jit_class_solves(*a):
        return _class_solves(*a, n_max, chunk, None)

    solves = class_solves if class_solves is not None else jit_class_solves

    res = (labels_sorted - joint_label_mean) * valid
    rmean = _residual_class_means(res, seg_ids, counts_f, num_classes)
    pad_diag = np.stack(
        [(np.arange(bs) >= wd).astype(np.float64) for wd in widths]
    )

    stats = []
    for i in range(nb):
        xb = get_block(i)
        stats.append(
            _bwls_block_stats(
                xb, seg_ids, counts_f, n, w_arr,
                jnp.asarray(pad_diag[i], dtype), num_classes,
            )
        )
        del xb

    models = [jnp.zeros((bs, num_classes), dtype) for _ in range(nb)]
    for _ in range(num_iter):
        for i in range(nb):
            xb = get_block(i)
            pop_cov, pop_mean, jm = stats[i]
            pop_xtr = _bwls_block_xtr(xb, res, n)
            dw = solves(
                xb, res, starts, counts, pop_cov, pop_mean, pop_xtr,
                jm, rmean, models[i], lam_arr, w_arr,
            )
            models[i], res = _bwls_block_apply(xb, res, models[i], dw)
            rmean = _residual_class_means(res, seg_ids, counts_f, num_classes)
            del xb

    joint_means_all = jnp.stack([s[2] for s in stats])
    models_st = jnp.stack(models)
    intercept = joint_label_mean - jnp.einsum(
        "bcd,bdc->c", joint_means_all, models_st
    )
    return models_st, intercept


@functools.partial(jax.jit, static_argnames=("num_classes",))
def _class_sums(x_pad, seg_ids, num_classes: int):
    """Per-class row sums of a (sorted, padded) block via segment sum.

    ``seg_ids`` maps each row to its class, with pad rows mapped to segment
    ``num_classes`` which is dropped — a segment sum replaces round 2's
    [C, N] one-hot matmul (O(N) index memory instead of O(N·C))."""
    sums = jax.ops.segment_sum(
        x_pad, seg_ids, num_segments=num_classes + 1, indices_are_sorted=True
    )
    return sums[:num_classes]


@functools.partial(jax.jit, static_argnames=("num_classes",))
def _residual_class_means(res_pad, seg_ids, counts, num_classes: int):
    """Per-class column means of the residual, averaged over classes with
    equal class weight (reference :165-167, :283-287)."""
    means = _class_sums(res_pad, seg_ids, num_classes) / counts[:, None]
    return jnp.mean(means, axis=0)


class BlockWeightedLeastSquaresEstimator(LabelEstimator):
    """Weighted BCD least squares (reference :35-88).

    ``mixture_weight`` ∈ (0, 1): how much each class's own examples are
    up-weighted relative to the population (per-class effective weights are
    ``(1-w)/n + w/n_c`` on the true-class column, ``(1-w)/n`` elsewhere).
    """

    def __init__(
        self,
        block_size: int,
        num_iter: int,
        lam: float,
        mixture_weight: float,
        class_chunk: int = 16,
        mesh=None,
    ):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.mixture_weight = mixture_weight
        self.class_chunk = class_chunk
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
        donate: bool | None = None,
    ) -> BlockLinearMapper:
        """``features``/``labels`` may be host arrays OR device-resident
        (row-sharded) ``jax.Array``s — the full design matrix is never
        materialized on host.  ``nvalid``: true global row count when the
        inputs carry zero pad rows from ``padded_shard_rows``; pad rows are
        excluded from the class grouping.

        Memory resilience: the solve runs a degradation ladder.  Without a
        mesh: fused one-program → stepwise per-block → host-staged block
        streaming, each tier preflighted against the HBM budget
        (core.memory; ``KEYSTONE_HBM_BUDGET`` overrides) and a runtime
        RESOURCE_EXHAUSTED steps down one tier.  With a mesh, mesh tiers
        sit above those — full ``(data, model)`` mesh → model-axis-
        collapsed mesh → the single-device ladder — each admitted PER CHIP
        against the minimum free HBM across the mesh's devices, with
        ``last_fit_report.mesh_shape`` recording which mesh actually ran; a
        mesh tier whose ``model`` axis is 1 splits the classes over
        ``data`` (the module docstring).  The fused program
        always donates the SORTED design-matrix/label copies (they are
        fit-private).  ``donate=True`` additionally frees the CALLER's
        device-resident inputs as soon as their sorted copies exist —
        halving the peak across the class-sort gather — at the price that
        an exec-level OOM can no longer rebuild them for the step-down.
        The decision trail is ``self.last_fit_report``."""
        mesh = self.mesh if self.mesh is not None else current_mesh()
        self._split_ran = None  # the _ClassSplit the tier that ran solved by
        n = nvalid if nvalid is not None else int(np.shape(labels)[0])
        n_classes = int(np.shape(labels)[1])
        # Class of each valid row: device argmax for device labels, so only
        # the [n] int vector crosses to host (round 2 pulled the whole
        # design matrix); plain numpy argmax for host labels.
        with trace.host("sort", "bwls.class_sort", n=n, classes=n_classes):
            if isinstance(labels, jax.Array):
                class_idx = np.asarray(jnp.argmax(labels[:n], axis=1))
            else:
                class_idx = np.argmax(np.asarray(labels)[:n], axis=1)
            counts_np = np.bincount(class_idx, minlength=n_classes)
            if np.any(counts_np == 0):
                missing = np.nonzero(counts_np == 0)[0]
                raise ValueError(
                    f"classes with no examples: {missing.tolist()}"
                )

            # Class grouping (the reference's HashPartitioner shuffle +
            # per-partition id sort, :324-361): a host argsort of the [n]
            # class vector gives the permutation; rows move device-side via
            # one regroup of the whole design matrix below.
            order = np.argsort(class_idx, kind="stable")
        starts_np = np.concatenate([[0], np.cumsum(counts_np)[:-1]])
        n_max = int(counts_np.max())

        with trace.host("place", "design_matrix"):
            x, widths = _blocked_design_matrix(
                features, self.block_size, num_features
            )
        # Conditioning monitor (ISSUE 15): per-block κ estimates on the
        # blocked design matrix this fit already formed (row-capped probe;
        # one flag check when the observatory is off).
        cond_rows = (
            knum.design_conditioning(
                x, widths, float(self.lam), label="bwls_fit"
            )
            if knum.active()
            else None
        )
        dtype = jnp.asarray(x[:1, :1]).dtype
        w = self.mixture_weight
        # What the regroups of the tier that ran did: their kind and the
        # bytes they sent between chips (the ``bwls_plan`` instant's).
        regroups: list = []

        def prep(m, labels_src, split=None):
            """Mesh-dependent solve context for one ladder tier.

            Padded row layout: sorted valid rows, then a zero tail of >=
            n_max rows (so every dynamic_slice in the class sweep stays in
            bounds).  The zero tail contributes nothing to gemms/sums, so
            population statistics use xb_pad directly with the true count
            n.  With a mesh the tail additionally rounds the row count up
            to a data-axis multiple and the padded blocks are row-sharded:
            population gram/XᵀR gemms lower to local gram + ICI
            all-reduce.  Every quantity that depends on the mesh's axis
            sizes (p_tot, the gather index, seg ids, the class chunk, the
            sort/regroup closures) lives in the returned context, so each
            rung of the mesh degradation ladder rebuilds its own layout.
            With ``split`` (a ``_ClassSplit``) the rows lie as it lays them
            out instead, each chip's classes at the head of its rows.
            """
            pad_total = n_max
            row_shard = None
            if m is not None:
                d_size = m.shape[DATA_AXIS]
                pad_total += (-(n + n_max)) % d_size
                row_shard = NamedSharding(m, P(DATA_AXIS, None))
            p_tot = n + pad_total
            place = dest = None
            if split is not None:
                p_tot = m.shape[DATA_AXIS] * split.rows_out
                place = dest = split.place
            if dest is None:
                dest = slice(None, n)

            # gather index: order for valid rows, then an out-of-range
            # index so ``mode="fill"`` writes exact zero rows for the tail
            # — the sort and the padding are a single device gather, no
            # host round-trip.
            gather_np = np.full(p_tot, n, dtype=order.dtype)
            gather_np[dest] = order
            gather_idx = jnp.asarray(gather_np)
            valid = jnp.asarray((gather_np < n).astype(np.float32))[:, None]

            regroup_plans: dict[int, _RegroupPlan] = {}

            def sort_pad(x):
                """Sorted, zero-tail-padded, (re-)sharded copy of ``x``.

                Host arrays are permuted host-side (no device gather at
                all).  Device-resident arrays under a mesh regroup via the
                traffic-optimal all_to_all plan (each row crosses the ICI
                once — see _RegroupPlan for the D-times-less-traffic
                model).  The fallback for shapes the plan cannot take (row
                count not a data-axis multiple) is a feature-column-chunked
                gather: a replicated-index gather over a row-sharded
                operand makes GSPMD all-gather the operand, so chunking
                bounds the transient unsharded slab to [p_tot, chunk].  The
                tail is exact zero in every path (``mode="fill"`` covers
                sources with exactly n rows; sources carrying their own pad
                rows at >= n need the mask).
                """
                if not isinstance(x, jax.Array):
                    xh = np.asarray(x)
                    out_h = np.zeros((p_tot,) + xh.shape[1:], xh.dtype)
                    out_h[dest] = xh[order]
                    out = jnp.asarray(out_h)
                    if row_shard is not None:
                        out = jax.device_put(out, row_shard)
                    return out

                if m is not None and x.shape[0] % m.shape[DATA_AXIS] == 0:
                    n_src = x.shape[0]
                    if n_src not in regroup_plans:
                        regroup_plans[n_src] = _RegroupPlan(
                            order, n_src, p_tot, m.shape[DATA_AXIS], place
                        )
                    plan = regroup_plans[n_src]
                    if plan.usable:  # else: skew guard — fallback below
                        sent = plan.exchanged_bytes(x)
                        trace.metrics.inc("bwls.regroup_bytes", sent)
                        regroups.append(("all_to_all", sent))
                        return plan.apply(m, jax.device_put(x, row_shard))
                    # A survivable degradation, counted so operators (and
                    # the multichip dryrun) can see which regroup path ran.
                    counters.record(
                        "bwls_regroup_skew_fallback",
                        f"d*m_pad {plan.d * plan.m_pad} > 2*rows_out "
                        f"{2 * plan.rows_out}: bucket padding beyond 2x "
                        "optimal — taking the chunked-gather fallback",
                    )

                if m is not None:
                    # a replicated-index gather of a row-sharded operand:
                    # every chip is sent the other chips' rows
                    sent = (m.shape[DATA_AXIS] - 1) * x.nbytes
                    trace.metrics.inc("bwls.regroup_bytes", sent)
                    regroups.append(("gather", sent))
                chunk_cols = max(1, _GATHER_COL_CHUNK // max(1, x.itemsize))
                if x.shape[1] <= chunk_cols:
                    g = jnp.take(
                        x, gather_idx, axis=0, mode="fill", fill_value=0
                    )
                    g = g * valid.astype(x.dtype)
                    return (
                        g if row_shard is None else jax.device_put(g, row_shard)
                    )
                # Chunks land in a PREALLOCATED output via a donating
                # dynamic-update-slice, so peak HBM is source + output +
                # one chunk (~2x the design matrix).  The round-5 form
                # accumulated all chunks in a list and concatenated —
                # source + chunks + concat output, ~3x transient (ADVICE
                # r5 medium).
                out = jnp.zeros((p_tot, x.shape[1]), x.dtype)
                if row_shard is not None:
                    out = jax.device_put(out, row_shard)
                for c0 in range(0, x.shape[1], chunk_cols):
                    sl = jax.lax.slice_in_dim(
                        x, c0, min(c0 + chunk_cols, x.shape[1]), axis=1
                    )
                    g = jnp.take(
                        sl, gather_idx, axis=0, mode="fill", fill_value=0
                    )
                    g = g * valid.astype(x.dtype)
                    if row_shard is not None:
                        # Reshard each slab as it lands so at most one
                        # unsharded chunk is transient at a time.
                        g = jax.device_put(g, row_shard)
                    out = _scatter_cols(out, g, jnp.int32(c0))
                return out

            counts = jnp.asarray(counts_np)
            starts = jnp.asarray(starts_np)
            # Segment ids: class of each sorted row, pad rows -> segment C.
            seg_np = np.full(p_tot, n_classes, np.int32)
            seg_np[dest] = class_idx[order]
            seg_ids = jnp.asarray(seg_np)
            counts_f = counts.astype(dtype)

            # jointLabelMean[c] = 2w + 2(1-w)·n_c/n − 1 (reference :147-149)
            joint_label_mean = jnp.asarray(
                2.0 * w + 2.0 * (1.0 - w) * counts_np / n - 1.0, dtype
            )
            valid_d = valid.astype(dtype)

            chunk = max(1, min(self.class_chunk, n_classes))
            if m is not None:
                # Round the chunk up to a model-axis multiple so the
                # batched class solves always shard over the model axis
                # (pad classes in a partial chunk are repeats of class 0,
                # discarded afterwards).
                m_size = m.shape[MODEL_AXIS]
                chunk = -(-chunk // m_size) * m_size

            def sort_labels():
                if isinstance(labels_src, jax.Array):
                    return sort_pad(labels_src.astype(dtype))
                return sort_pad(np.asarray(labels_src, dtype))

            return _SolveCtx(
                mesh=m,
                p_tot=p_tot,
                chunk=chunk,
                sort_pad=sort_pad,
                sort_labels=sort_labels,
                valid_d=valid_d,
                seg_ids=seg_ids,
                starts=starts,
                counts=counts,
                counts_f=counts_f,
                joint_label_mean=joint_label_mean,
            )

        if mesh is not None:
            # Multi-chip path: the mesh degradation ladder — full
            # (data, model) mesh with per-chip admission, then the
            # model-axis-collapsed mesh, then the single-device ladder.
            models_st, b = self._fit_mesh_ladder(
                features, x, labels, prep, mesh, order, n, n_max,
                n_classes, widths, dtype, donate, counts_np,
            )
        else:
            with trace.host("place", "solve_context"):
                solve_ctx = prep(None, labels)
            models_st, b = self._fit_ladder(
                features, x, labels, solve_ctx, order, n, n_max,
                n_classes, widths, dtype, donate,
            )
        if cond_rows and self.last_fit_report is not None:
            self.last_fit_report.conditioning = cond_rows
        # One system a class, block and pass, each at the block's full width.
        bs = max(widths)
        chunk = max(1, min(self.class_chunk, n_classes))
        class_solves = n_classes * len(widths) * self.num_iter
        factor_panel = min(_FACTOR_PANEL, bs)
        factor_panels = len(_factor_panels(bs))
        trace.metrics.inc("bwls.class_solves", class_solves)
        trace.metrics.inc("bwls.factor_panels", class_solves * factor_panels)
        trace.metrics.inc("bwls.classes", n_classes)
        split = self._split_ran
        trace.instant(
            "bwls_plan", rows=n, n_max=n_max, classes=n_classes, blocks=len(widths),
            class_chunk=chunk, factor_panel=factor_panel, factor_panels=factor_panels,
            tier=self.last_fit_report.chosen if self.last_fit_report else None,
            slab_bytes=np.dtype(dtype).itemsize * chunk * n_max * bs,
            factor_bytes=np.dtype(dtype).itemsize * chunk * (bs + 1) * bs,
            # the classes and rows each chip solves from (one device: all;
            # a model split: every chip a share of each chunk, so no list),
            # the rows each holds, and how the rows reached them
            classes_by_chip=(
                np.diff(split.bounds).tolist() if split is not None
                else [n_classes] if mesh is None else None
            ),
            rows_by_chip=(
                split.rows.tolist() if split is not None else [n] if mesh is None else None
            ),
            rows_padded=split.rows_out if split is not None else None,
            regroup=regroups[0][0] if regroups else None,
            regroup_bytes=sum(b for _kind, b in regroups),
        )
        with trace.host("finish", "model_blocks"):
            model_list, _ = split_model(models_st, None, widths)
        return BlockLinearMapper(model_list, self.block_size, b)

    def _fit_mesh_ladder(
        self, features, x, labels, prep, mesh, order, n, n_max, n_classes,
        widths, dtype, donate, counts_np,
    ):
        """Distributed BWLS through the MESH degradation ladder: full
        ``(data, model)`` mesh → model-axis-collapsed mesh (row-sharded
        operands halve per chip, model state replicates) → the
        single-device ladder on host-pulled inputs.  Each mesh tier builds
        its own sort/pad layout (``prep(m, ...)``), is admitted PER CHIP
        against the minimum free HBM across the mesh's devices, and a
        runtime ``RESOURCE_EXHAUSTED`` from any chip steps down one tier.
        ``report.mesh_shape`` records which mesh actually ran.  A mesh whose
        ``model`` axis is 1 splits the classes over ``data`` where there are
        at least as many classes as chips (``split_tier``)."""
        bs, nb = max(widths), len(widths)
        d_tot = nb * bs
        it = np.dtype(dtype).itemsize
        xdt = jax.dtypes.canonicalize_dtype(x.dtype)
        with trace.host("search", "ladder"):
            report = kmem.FitReport(label="bwls_fit")
            self.last_fit_report = report

            def mesh_tier(m):
                """One fused-mesh BWLS rung: row-sharded design matrix and
                sorted labels."""
                name = f"fused[mesh {mesh_desc(m)}]"
                d_sz, m_sz = m.shape[DATA_AXIS], m.shape[MODEL_AXIS]
                # The tier's padded layout, computed WITHOUT building the ctx
                # (the O(p_tot) gather/seg/mask buffers stay lazy below).
                p_tot_a = n + n_max + ((-(n + n_max)) % d_sz)
                chunk_a = max(1, min(self.class_chunk, n_classes))
                chunk_a = -(-chunk_a // m_sz) * m_sz
                # Analytic per-chip transient floor (CPU backends report
                # temp 0): two row-sharded residual carries, one row-sharded
                # block slice, the model-axis-sharded class-solve slab, the
                # replicated stats/models stacks.
                floor = it * (
                    2 * (p_tot_a // d_sz) * n_classes
                    + p_tot_a * bs // d_sz
                    + chunk_a * n_max * bs // m_sz
                    + nb * (bs * bs + bs + n_classes * bs)
                    + nb * bs * n_classes
                )
                # Lazy, memoized: a tier's O(p_tot) gather/seg/mask buffers are
                # only built once the ladder actually CONSIDERS the tier (the
                # common admitted-first-tier fit never pays for the rungs
                # below it — same laziness run_ladder gives the plans).
                ctx_box: list = []

                def ctx():
                    if not ctx_box:
                        ctx_box.append(prep(m, labels))
                    return ctx_box[0]

                def plan():
                    ctx_ = ctx()
                    budget, _worst = kmem.min_chip_budget(m)
                    sds = jax.ShapeDtypeStruct
                    i32 = jnp.int32
                    row = NamedSharding(m, P(DATA_AXIS, None))
                    x_s = sds((ctx_.p_tot, d_tot), xdt, sharding=row)
                    y_s = sds((ctx_.p_tot, n_classes), dtype, sharding=row)
                    # valid/seg/stat vectors are replicated — charged whole.
                    v_s = sds((ctx_.p_tot, 1), dtype)
                    seg_s = sds((ctx_.p_tot,), i32)
                    c_i32, c_f = sds((n_classes,), i32), sds((n_classes,), dtype)
                    sc_s, nv_s = sds((), dtype), sds((), i32)
                    return kmem.plan_program(
                        _fused_bwls_fit_variant((0, 1)),
                        x_s, y_s, v_s, seg_s, c_i32, c_i32, c_f, c_f, nv_s,
                        sc_s, sc_s, self.num_iter, n_max, ctx_.chunk, n_classes,
                        widths, m,
                        label=f"bwls_{name}", budget=budget,
                        min_temp_bytes=floor, mesh=m,
                    )

                def run(plan):
                    ctx_ = ctx()
                    report.mesh_shape = dict(m.shape)
                    args = (
                        ctx_.sort_pad(x), ctx_.sort_labels(), ctx_.valid_d,
                        ctx_.seg_ids, ctx_.starts, ctx_.counts, ctx_.counts_f,
                        ctx_.joint_label_mean, jnp.asarray(n),
                        jnp.asarray(self.lam, dtype),
                        jnp.asarray(self.mixture_weight, dtype),
                    )
                    statics = (
                        self.num_iter, n_max, ctx_.chunk, n_classes, widths, m,
                    )
                    # plan=None: the jitted sharded program, not the AOT plan
                    # executable (committed-sharding pitfalls — see
                    # block._execute_fused_bcd_mesh); same injection point.
                    return _execute_fused_bwls(None, args, statics)

                return kmem.Tier(name, plan, run)

            def split_tier(m):
                """One fused-mesh BWLS rung whose chips solve their own
                classes (``_class_split``, ``_split_bwls_impl``)."""
                name = f"fused[mesh {mesh_desc(m)}]"
                d_sz = m.shape[DATA_AXIS]
                split = _class_split(counts_np, d_sz, n_max)
                slots = split.slot_classes.shape[1]
                chunk_a = max(1, min(self.class_chunk, slots))
                # Analytic per-chip transient floor (CPU backends report
                # temp 0): two residual carries and a block slice of a
                # chip's rows, its class-solve slab and factor scratch, the
                # replicated stats/models stacks and a gathered ΔW.
                floor = it * (
                    2 * split.rows_out * n_classes
                    + split.rows_out * bs
                    + chunk_a * (n_max + bs + 1) * bs
                    + nb * (bs * bs + bs + n_classes * bs)
                    + nb * bs * n_classes
                    + d_sz * slots * bs
                )
                ctx_box: list = []

                def ctx():
                    if not ctx_box:
                        ctx_box.append(prep(m, labels, split))
                    return ctx_box[0]

                def plan():
                    ctx_ = ctx()
                    budget, _worst = kmem.min_chip_budget(m)
                    sds = jax.ShapeDtypeStruct
                    i32 = jnp.int32
                    row = NamedSharding(m, P(DATA_AXIS, None))
                    rows1 = NamedSharding(m, P(DATA_AXIS))
                    x_s = sds((ctx_.p_tot, d_tot), xdt, sharding=row)
                    y_s = sds((ctx_.p_tot, n_classes), dtype, sharding=row)
                    v_s = sds((ctx_.p_tot, 1), dtype, sharding=row)
                    seg_s = sds((ctx_.p_tot,), i32, sharding=rows1)
                    slot_s = sds((d_sz, slots), i32, sharding=row)
                    col_s = sds((n_classes,), i32)
                    c_f = sds((n_classes,), dtype)
                    sc_s, nv_s = sds((), dtype), sds((), i32)
                    return kmem.plan_program(
                        _split_bwls_fit,
                        x_s, y_s, v_s, seg_s, slot_s, slot_s, slot_s, col_s,
                        c_f, c_f, nv_s, sc_s, sc_s,
                        self.num_iter, n_max, chunk_a, n_classes, widths, m,
                        label=f"bwls_{name}", budget=budget,
                        min_temp_bytes=floor, mesh=m,
                    )

                def run(plan):
                    ctx_ = ctx()
                    report.mesh_shape = dict(m.shape)
                    row = NamedSharding(m, P(DATA_AXIS, None))
                    rows1 = NamedSharding(m, P(DATA_AXIS))
                    args = (
                        ctx_.sort_pad(x), ctx_.sort_labels(),
                        jax.device_put(ctx_.valid_d, row),
                        jax.device_put(ctx_.seg_ids, rows1),
                        *(
                            jax.device_put(t, row)
                            for t in (split.slot_starts, split.slot_counts, split.slot_classes)
                        ),
                        jnp.asarray(split.columns), ctx_.counts_f,
                        ctx_.joint_label_mean, jnp.asarray(n),
                        jnp.asarray(self.lam, dtype),
                        jnp.asarray(self.mixture_weight, dtype),
                    )
                    statics = (self.num_iter, n_max, chunk_a, n_classes, widths, m)
                    out = _execute_split_bwls(args, statics)
                    count_psum(_split_psum_bytes(it, widths, n_classes, self.num_iter))
                    self._split_ran = split
                    return out

                return kmem.Tier(name, plan, run)

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
                    x_h, x_h, y_h, prep(None, y_h), order, n, n_max,
                    n_classes, widths, dtype, None, report=report,
                )
                inner_chosen.append(report.chosen)
                return out

            def tier_of(m):
                splits = m.shape[MODEL_AXIS] == 1 and n_classes >= m.shape[DATA_AXIS]
                return split_tier(m) if splits else mesh_tier(m)

            tiers = [tier_of(mesh)]
            rm = reduced_mesh(mesh)
            if rm is not None:
                tiers.append(tier_of(rm))
            tiers.append(kmem.Tier("single_device", plan_single, run_single))
        # Profiler phase (core.profiler): the watermark sampler attributes
        # this solve's HBM high-water mark to "bwls_fit".  No-op when off.
        with kprof.phase("bwls_fit"):
            out = kmem.run_ladder("bwls_fit", tiers, report)
        if inner_chosen and report.chosen == "single_device":
            report.chosen = f"single_device/{inner_chosen[0]}"
        return out

    def _fit_ladder(
        self, features, x, labels, ctx, order, n, n_max, n_classes, widths,
        dtype, donate, report=None,
    ):
        """Single-device BWLS through the degradation ladder (preflight
        admission per tier; runtime RESOURCE_EXHAUSTED steps down one tier).

        The SORTED design matrix / labels are fit-private copies, so the
        fused program always donates them; ``donate=True`` additionally
        frees the caller's device inputs once sorted copies exist."""
        sort_pad, sort_labels = ctx.sort_pad, ctx.sort_labels
        valid_d, seg_ids = ctx.valid_d, ctx.seg_ids
        starts, counts, counts_f = ctx.starts, ctx.counts, ctx.counts_f
        joint_label_mean = ctx.joint_label_mean
        chunk, p_tot = ctx.chunk, ctx.p_tot
        bs, nb = max(widths), len(widths)
        d_tot = nb * bs
        it = np.dtype(dtype).itemsize
        xdt = jax.dtypes.canonicalize_dtype(x.dtype)
        with trace.host("plan", "budget"):
            budget = kmem.hbm_budget()
        donate_input = bool(donate)

        with trace.host("place", "lam"):
            lam_arr = jnp.asarray(self.lam, dtype)
            w_arr = jnp.asarray(self.mixture_weight, dtype)
            nv_arr = jnp.asarray(n, jnp.int32)
        with trace.host("search", "ladder"):
            statics = (self.num_iter, n_max, chunk, n_classes, widths, None)

            sds = jax.ShapeDtypeStruct
            i32 = jnp.int32
            x_s = sds((p_tot, d_tot), xdt)
            y_s = sds((p_tot, n_classes), dtype)
            v_s = sds((p_tot, 1), dtype)
            seg_s = sds((p_tot,), i32)
            c_i32 = sds((n_classes,), i32)
            c_f = sds((n_classes,), dtype)
            sc_s, nv_s = sds((), dtype), sds((), i32)
            xb_s = sds((p_tot, bs), xdt)
            cov_s, mean_s = sds((bs, bs), dtype), sds((bs,), dtype)
            xtr_s, jm_s = sds((bs, n_classes), dtype), sds((n_classes, bs), dtype)
            m_s = sds((bs, n_classes), dtype)

            # Resident-set accounting the per-program argument lists do not
            # see: per-block statistics caches, the models stack, the sorted
            # labels, and (unless donated) the caller's device inputs.
            stats_bytes = it * nb * (bs * bs + bs + n_classes * bs)
            models_bytes = it * nb * bs * n_classes
            labels_bytes = it * p_tot * n_classes
            # Device-resident caller inputs stay alive at least through the
            # class-sort gather (source + sorted output coexist) even under
            # donate=True, so they count against every tier's charged total —
            # including host-staged, which pulls the source to RAM but cannot
            # free a non-donated caller buffer.  When the budget is LIVE free
            # bytes they are credited back (free already excludes them).
            src_bytes = (
                (x.nbytes if isinstance(x, jax.Array) else 0)
                + (labels.nbytes if isinstance(labels, jax.Array) else 0)
            )
            # Analytic transient floor of the fused program (CPU backends
            # report temp 0): two residual carries, one block slice, the stats
            # stacks, the models carry, and the per-chunk class-solve slab.
            fused_floor = it * (
                2 * p_tot * n_classes + p_tot * bs + chunk * n_max * bs
            ) + stats_bytes + models_bytes
            slab_floor = it * chunk * n_max * bs

            def plan_fused():
                return kmem.plan_program(
                    _fused_bwls_fit_variant((0, 1)),
                    x_s, y_s, v_s, seg_s, c_i32, c_i32, c_f, c_f, nv_s, sc_s,
                    sc_s, *statics,
                    label="bwls_fused", budget=budget,
                    min_temp_bytes=fused_floor, extra_bytes=src_bytes,
                    resident_bytes=src_bytes,
                )

            def plan_stepwise():
                return kmem.plan_program(
                    _class_solves, xb_s, y_s, c_i32, c_i32, cov_s, mean_s,
                    xtr_s, jm_s, c_f, m_s, sc_s, sc_s, n_max, chunk, None,
                    label="bwls_stepwise", budget=budget,
                    min_temp_bytes=slab_floor,
                    extra_bytes=(
                        it * p_tot * d_tot  # the sorted design matrix
                        + labels_bytes + stats_bytes + models_bytes + src_bytes
                    ),
                    resident_bytes=src_bytes,
                )

            def plan_host():
                return kmem.plan_program(
                    _class_solves, xb_s, y_s, c_i32, c_i32, cov_s, mean_s,
                    xtr_s, jm_s, c_f, m_s, sc_s, sc_s, n_max, chunk, None,
                    label="bwls_host_staged", budget=budget,
                    min_temp_bytes=slab_floor,
                    extra_bytes=(
                        labels_bytes + stats_bytes + models_bytes + src_bytes
                    ),
                    resident_bytes=src_bytes,
                )

            def src_x():
                if isinstance(x, jax.Array) and x.is_deleted():
                    raise kmem.LadderSourceLost(
                        "BWLS design matrix was donated (donate=True) and is "
                        "gone — cannot step the ladder down; refit with "
                        "donate=False to keep OOM recovery possible"
                    )
                return x

            def free_sources():
                if donate_input:
                    kmem.free_buffers(
                        x if isinstance(x, jax.Array) else None,
                        labels if isinstance(labels, jax.Array) else None,
                    )

            def sorted_device_inputs():
                xs = sort_pad(src_x())
                ls = sort_labels()
                free_sources()
                return xs, ls

            def run_fused(plan):
                with trace.host("place", "operands"):
                    xs, ls = sorted_device_inputs()
                args = (xs, ls, valid_d, seg_ids, starts, counts, counts_f,
                        joint_label_mean, nv_arr, lam_arr, w_arr)
                del xs, ls  # the args tuple holds the only refs; donation eats them
                return _execute_fused_bwls(plan, args, statics)

            def run_stepwise(plan):
                from .block import _single_device_arrays

                xs, ls = sorted_device_inputs()
                reusable = plan is not None and _single_device_arrays(xs, ls)

                def get_block(i):
                    return jax.lax.slice_in_dim(xs, i * bs, (i + 1) * bs, axis=1)

                return _stepwise_bwls_fit(
                    get_block, ls, valid_d, seg_ids, starts, counts, counts_f,
                    joint_label_mean, n, self.lam, self.mixture_weight,
                    self.num_iter, n_max, chunk, n_classes, widths,
                    # Reuse the preflight's AOT executable: the class-solve
                    # program compiled exactly once, at admission.  (Sharded
                    # inputs fall back to the jitted entry.)
                    class_solves=plan.compiled if reusable else None,
                )

            def run_host(plan):
                xh = src_x()
                x_np = (
                    np.asarray(jax.device_get(xh))
                    if isinstance(xh, jax.Array) else np.asarray(xh)
                )
                if isinstance(xh, jax.Array) and _design_matrix_owned(xh, features):
                    # Fit-owned device copy (concat/pad product): once pulled to
                    # host it must not keep the full matrix resident in HBM —
                    # that residency is exactly what this tier exists to avoid.
                    kmem.free_buffers(xh)
                ls = sort_labels()
                free_sources()
                # Host-side class sort + zero tail: the device never holds more
                # than one [P, bs] block of the design matrix.
                x_sorted_h = np.zeros((p_tot, x_np.shape[1]), x_np.dtype)
                x_sorted_h[:n] = x_np[order]
                del x_np

                def get_block(i):
                    return jnp.asarray(
                        np.ascontiguousarray(x_sorted_h[:, i * bs : (i + 1) * bs])
                    )

                from .block import _single_device_arrays

                return _stepwise_bwls_fit(
                    get_block, ls, valid_d, seg_ids, starts, counts, counts_f,
                    joint_label_mean, n, self.lam, self.mixture_weight,
                    self.num_iter, n_max, chunk, n_classes, widths,
                    class_solves=(
                        plan.compiled
                        if plan is not None and _single_device_arrays(ls)
                        else None
                    ),
                )

            if report is None:
                report = kmem.FitReport(label="bwls_fit", budget_bytes=budget)
                self.last_fit_report = report
            tiers = [
                kmem.Tier("fused", plan_fused, run_fused),
                kmem.Tier("stepwise", plan_stepwise, run_stepwise),
                kmem.Tier("host_staged", plan_host, run_host),
            ]
        with kprof.phase("bwls_fit"):
            return kmem.run_ladder("bwls_fit", tiers, report)
