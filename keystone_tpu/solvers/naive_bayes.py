"""Multinomial naive Bayes — own implementation replacing the reference's
Spark-MLlib delegation (reference src/main/scala/nodes/learning/NaiveBayesModel.scala:22-71,
which calls mllib.classification.NaiveBayes.train).

MLlib's multinomial NB semantics (reproduced here):
    pi[c]       = log(n_c + λ) − log(n + C·λ)
    theta[c, d] = log(count_{c,d} + λ) − log(Σ_d count_{c,d} + D·λ)
    score(x)    = pi + theta @ x   (log-posterior up to a constant)

Fitting aggregates per-class feature sums from CSR features with one
host-side scatter-add (the data is already host-resident text); scoring runs
on device — dense inputs hit the MXU directly, CSR inputs use
gather + segment-sum, the TPU-friendly sparse contraction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from jax.sharding import PartitionSpec as P

from ..core.pipeline import LabelEstimator, Transformer, node
from ..ops.sparse import CSRFeatures
from ..parallel.mesh import DATA_AXIS, current_mesh


@node(data_fields=("pi", "theta"))
class NaiveBayesModel(Transformer):
    """Log-posterior scores ``pi + theta @ x``
    (reference NaiveBayesModel.scala:49-55)."""

    def __init__(self, pi, theta):
        self.pi = pi  # [C]
        self.theta = theta  # [C, D]

    def __call__(self, batch):
        if isinstance(batch, CSRFeatures):
            mesh = current_mesh()
            if mesh is not None and mesh.shape[DATA_AXIS] > 1:
                return self._apply_csr_mesh(batch, mesh)
            return self._apply_csr(batch)
        return batch @ self.theta.T + self.pi

    # Chunk the nnz axis so the [chunk, C] gather intermediate stays bounded
    # even for corpora whose nnz dwarfs the dense input.
    NNZ_CHUNK = 1 << 22

    def _apply_csr(self, csr: CSRFeatures):
        # gather theta columns at the nonzeros, scale, segment-sum by row
        n = len(csr)
        # int64 on host: nnz can exceed int32 for large corpora
        row_ids = np.repeat(
            np.arange(n, dtype=np.int64), np.diff(csr.indptr).astype(np.int64)
        )
        nnz = row_ids.shape[0]
        scores = jnp.zeros((n, self.theta.shape[0]), self.theta.dtype)
        for lo in range(0, max(nnz, 1), self.NNZ_CHUNK):
            hi = min(lo + self.NNZ_CHUNK, nnz)
            cols = jnp.asarray(csr.indices[lo:hi])
            vals = jnp.asarray(csr.values[lo:hi])
            contrib = self.theta.T[cols] * vals[:, None]  # [chunk, C]
            scores = scores + jax.ops.segment_sum(
                contrib, jnp.asarray(row_ids[lo:hi]), num_segments=n
            )
        return scores + self.pi

    def _apply_csr_mesh(self, csr: CSRFeatures, mesh):
        """Data-parallel CSR scoring over the mesh: documents are split into
        one contiguous row group per data-axis device; each device runs the
        gather + sorted-segment-sum contraction on its own COO shard against
        the replicated ``theta`` — no cross-device communication at all (the
        shuffle-free analog of the reference scoring an RDD partition per
        executor).  Per-shard COO buffers are zero-padded to the max shard
        nnz (value 0 contributes nothing)."""
        k = mesh.shape[DATA_AXIS]
        n = len(csr)
        rows_per = -(-n // k)
        indptr = csr.indptr.astype(np.int64)
        bounds = [int(indptr[min(j * rows_per, n)]) for j in range(k + 1)]
        nnz_max = max(bounds[j + 1] - bounds[j] for j in range(k))
        cols = np.zeros((k, max(nnz_max, 1)), np.int32)
        vals = np.zeros((k, max(nnz_max, 1)), np.float32)
        # pad entries point at the LAST local row (zero value, so they add
        # nothing) keeping row ids non-decreasing for indices_are_sorted
        rows = np.full((k, max(nnz_max, 1)), rows_per - 1, np.int32)
        for j in range(k):
            lo, hi = bounds[j], bounds[j + 1]
            r0, r1 = j * rows_per, min((j + 1) * rows_per, n)
            m = hi - lo
            cols[j, :m] = csr.indices[lo:hi]
            vals[j, :m] = csr.values[lo:hi]
            rows[j, :m] = (
                np.repeat(np.arange(r0, r1), np.diff(indptr[r0 : r1 + 1])) - r0
            )

        def shard_scores(cols_s, vals_s, rows_s, theta_t, pi):
            contrib = theta_t[cols_s[0]] * vals_s[0][:, None]  # [nnz, C]
            s = jax.ops.segment_sum(
                contrib,
                rows_s[0],
                num_segments=rows_per,
                indices_are_sorted=True,
            )
            return (s + pi)[None]

        fn = shard_map(
            shard_scores,
            mesh=mesh,
            in_specs=(
                P(DATA_AXIS, None),
                P(DATA_AXIS, None),
                P(DATA_AXIS, None),
                P(None, None),
                P(None),
            ),
            out_specs=P(DATA_AXIS, None, None),
        )
        out = jax.jit(fn)(
            jnp.asarray(cols),
            jnp.asarray(vals),
            jnp.asarray(rows),
            self.theta.T,
            self.pi,
        )
        return out.reshape(k * rows_per, -1)[:n]


class NaiveBayesEstimator(LabelEstimator):
    """Fit multinomial NB (reference NaiveBayesEstimator:63-71)."""

    def __init__(self, num_classes: int, lam: float = 1.0):
        self.num_classes = num_classes
        self.lam = lam

    def fit(self, features, labels) -> NaiveBayesModel:
        labels = np.asarray(labels)
        n = labels.shape[0]
        c = self.num_classes
        n_c = np.bincount(labels, minlength=c).astype(np.float64)

        if isinstance(features, CSRFeatures):
            d = features.num_features
            counts = np.zeros((c, d), np.float64)
            row_ids = np.repeat(np.arange(len(features)), np.diff(features.indptr))
            np.add.at(
                counts, (labels[row_ids], features.indices), features.values
            )
        else:
            dense = np.asarray(features, np.float64)
            d = dense.shape[1]
            counts = np.zeros((c, d), np.float64)
            np.add.at(counts, labels, dense)

        lam = self.lam
        pi = np.log(n_c + lam) - np.log(n + c * lam)
        theta = np.log(counts + lam) - np.log(
            counts.sum(axis=1, keepdims=True) + d * lam
        )
        return NaiveBayesModel(
            jnp.asarray(pi, jnp.float32), jnp.asarray(theta, jnp.float32)
        )
