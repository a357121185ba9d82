"""Communication backend: XLA collectives over ICI/DCN.

The reference's entire comm surface is Spark primitives (SURVEY §2.9):
``treeAggregate``/``treeReduce`` (reference nodes/stats/StandardScaler.scala:46-48,
nodes/learning/BlockWeightedLeastSquares.scala:186-216), ``broadcast``
(BlockLinearMapper.scala:51), ``partitionBy`` shuffles
(BlockWeightedLeastSquares.scala:335-357) and ``collect``.  Here each maps to
one XLA collective over the ICI fabric:

  treeReduce/treeAggregate  ->  psum (one fused all-reduce): ``sharded_gram``
                                below, wired into the solvers
  broadcast                 ->  implicit XLA replication of unsharded
                                operands under jit / explicit P() shardings
  partitionBy shuffle       ->  host sort of the small key vector + one
                                device gather per block (the BWLS class
                                shuffle, solvers/weighted.py) — measured
                                simpler and no worse than a ragged
                                all_to_all for the one-time preamble; the
                                per-shard COO layout in
                                solvers/naive_bayes.py is the
                                shuffle-free scoring analog
  collect                   ->  device->host transfer of an already-reduced
                                array

These wrappers are thin on purpose — the win is that under ``jit`` with
sharded inputs XLA already inserts the right collective; the explicit
``shard_map`` forms below exist for kernels that want manual control (e.g.
streaming gram accumulation) and for multi-host DCN layouts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


from ..core import trace
from .mesh import DATA_AXIS, on_one_device


def count_psum(nbytes) -> None:
    """Add the bytes of arrays summed across chips to the counter
    ``mesh.psum_bytes``.  Called with numbers reckoned from shapes where a
    sharded program is *called*, not where it is traced, so that every fit
    counts; the bytes are the arrays', not what the fabric moves."""
    trace.metrics.inc("mesh.psum_bytes", int(nbytes))


def psum_gram(x_block, y_block, axis_name: str = DATA_AXIS):
    """Per-shard gram + cross-shard reduce: the treeReduce replacement.

    Inside ``shard_map``: computes local ``XᵀX`` and ``XᵀY`` on the MXU and
    all-reduces over the data axis — one ICI collective replaces the
    reference's multi-hop executor->driver tree
    (BlockWeightedLeastSquares.scala:186-216).
    """
    ata = jax.lax.psum(x_block.T @ x_block, axis_name)
    atb = jax.lax.psum(x_block.T @ y_block, axis_name)
    return ata, atb


@functools.lru_cache(maxsize=None)
def _sharded_gram_fn(mesh):
    fn = shard_map(
        functools.partial(psum_gram, axis_name=DATA_AXIS),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=(P(None, None), P(None, None)),
    )
    return jax.jit(fn)


def sharded_gram(mesh, x, y):
    """``(XᵀX, XᵀY)`` for row-sharded ``x``/``y`` via an explicit shard_map.
    Compiled once per (mesh, shape) — the wrapper is cached per mesh so
    repeated fits hit the jit cache."""
    return _sharded_gram_fn(mesh)(x, y)


@jax.jit
def sharded_moments_jit(x):
    """(count, Σx, Σx²) over rows.  Under jit with a row-sharded input XLA
    lowers the sums to local reductions + one psum over ICI — the
    treeAggregate(MultivariateOnlineSummarizer) replacement
    (reference nodes/stats/StandardScaler.scala:46-48)."""
    cnt = jnp.asarray(x.shape[0], x.dtype)
    s = jnp.sum(x, axis=0)
    sq = jnp.sum(x * x, axis=0)
    return cnt, s, sq


def sharded_moments(x):
    """:func:`sharded_moments_jit`, its two column sums counted as a psum
    where ``x`` spans chips."""
    if not on_one_device(x):
        count_psum(2 * x.shape[1] * x.dtype.itemsize)
    return sharded_moments_jit(x)
