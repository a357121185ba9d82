"""PCA + multi-class LDA (reference src/main/scala/nodes/learning/PCA.scala:16-106,
LinearDiscriminantAnalysis.scala:17-67).

The reference collects samples to the driver and runs LAPACK ``sgesvd`` /
Breeze ``eig`` there.  Here both run on-device: the SVD in float32 (as the
reference's sgesvd) on an HBM-resident sample matrix, and LDA via the
symmetric whitening trick (Cholesky of S_W + ``eigh``) instead of the
non-symmetric ``eig(inv(S_W) S_B)`` — same eigenvalues, same projection
subspace, but a TPU-friendly symmetric eigensolve.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from ..core.pipeline import Estimator, LabelEstimator, Transformer, node
from .linear import LinearMapper


@node(data_fields=("pca_mat",))
class PCATransformer(Transformer):
    """Project vectors: ``in @ pcaMat`` (reference PCA.scala:16-27 computes
    ``pcaMat.t * in`` per item — identical for batched rows)."""

    def __init__(self, pca_mat):
        self.pca_mat = pca_mat

    def __call__(self, batch):
        return batch @ self.pca_mat


@node(data_fields=("pca_mat",))
class BatchPCATransformer(Transformer):
    """Project descriptor matrices with descriptors as *columns*
    (reference PCA.scala:35-40: ``pcaMat.t * in``).  Batch input is
    ``[N, d, cols]`` -> ``[N, dims, cols]``."""

    def __init__(self, pca_mat):
        self.pca_mat = pca_mat

    def __call__(self, batch):
        return jnp.einsum("dk,ndc->nkc", self.pca_mat, batch)


@node(data_fields=("centre",))
class DescriptorCentre(Transformer):
    """Descriptors less a fixed vector: ``[N, d, cols]`` batches (descriptors
    as columns) or ``[n, d]`` sampled rows.  PCA is fitted on centred samples
    and projects without centring (PCA.scala:35-40, 63-106), and a Fisher
    vector does not see a translation of its descriptors and its mixture
    together; float32 products on an accelerator do.  Descriptors that lie
    far from zero against their spread (LCS's window means: levels near 128,
    a spread of tens) lose that spread in one bfloat16 pass of the projection
    and in the moments' ``s1 - mu s0``; centred on their PCA sample's mean
    they keep it (PERF.md, PR 34)."""

    def __init__(self, centre):
        self.centre = centre

    def __call__(self, batch):
        return batch - (self.centre if batch.ndim == 2 else self.centre[:, None])


def compute_pca(data_mat, dims: int):
    """The reference's computePCA (PCA.scala:63-106): mean-center, f32 SVD,
    MATLAB sign convention (largest-|element| of each column positive), first
    ``dims`` columns of V."""
    data_mat = jnp.asarray(data_mat, jnp.float32)
    means = jnp.mean(data_mat, axis=0)
    data = data_mat - means
    # full VT only when n < d; for n >= d the reduced VT is the same [d, d]
    # and full_matrices=True would materialize an [n, n] U (the reference
    # passes jobu="N" because samples are O(1e6) rows, PCA.scala:57,80-86)
    n, d = data.shape
    _, _, vt = jnp.linalg.svd(data, full_matrices=n < d)
    pca = vt.T  # [d, d], columns = components, descending singular value
    col_max = jnp.max(pca, axis=0)
    abs_col_max = jnp.max(jnp.abs(pca), axis=0)
    signs = jnp.where(col_max == abs_col_max, 1.0, -1.0).astype(pca.dtype)
    pca = pca * signs
    return pca[:, :dims]


class PCAEstimator(Estimator):
    """Fit PCA from a sample matrix (reference PCA.scala:46-61; the
    driver-collect disappears — the sample stays on device)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, samples) -> PCATransformer:
        return PCATransformer(compute_pca(jnp.asarray(samples), self.dims))


class LinearDiscriminantAnalysis(LabelEstimator):
    """Multi-class LDA -> LinearMapper
    (reference LinearDiscriminantAnalysis.scala:17-67).

    S_W = Σ_c Σ_{x∈c} (x-μ_c)(x-μ_c)ᵀ,  S_B = Σ_c n_c (μ_c-μ)(μ_c-μ)ᵀ.
    Solved as the symmetric problem ``eigh(L⁻¹ S_B L⁻ᵀ)`` with
    ``S_W = L Lᵀ`` — eigenvalues match ``eig(inv(S_W) S_B)``; eigenvectors
    are ``W = L⁻ᵀ Y`` (differ from the reference only by per-vector scale,
    which is irrelevant to the projection)."""

    def __init__(self, num_dimensions: int):
        self.num_dimensions = num_dimensions

    def fit(self, data, labels) -> LinearMapper:
        data = jnp.asarray(data, jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        labels_np = np.asarray(labels)
        classes = np.unique(labels_np)
        total_mean = jnp.mean(data, axis=0)
        n = data.shape[0]

        # One-hot gemms instead of per-class gathers (no data-dependent
        # shapes; a few gemms total regardless of class count).  S_W is
        # accumulated directly from class-mean-centered rows — no
        # S_total − S_B subtraction, which cancels catastrophically in f32
        # when between-class scatter dominates.
        class_of_row = np.searchsorted(classes, labels_np)
        onehot = jnp.asarray(
            (classes[:, None] == labels_np[None, :]).astype(np.float32), data.dtype
        )  # [C, n]
        counts = jnp.sum(onehot, axis=1)  # [C]
        class_means = (onehot @ data) / counts[:, None]  # [C, d]
        centered = data - class_means[jnp.asarray(class_of_row)]
        sw = centered.T @ centered
        dm = (class_means - total_mean) * jnp.sqrt(counts)[:, None]
        sb = dm.T @ dm

        l = jnp.linalg.cholesky(sw)
        if not bool(jnp.all(jnp.isfinite(l))):
            raise ValueError(
                "S_W is singular (need n_samples - n_classes >= n_features); "
                "LDA projection would be NaN"
            )
        linv_sb = jax.scipy.linalg.solve_triangular(l, sb, lower=True)
        m = jax.scipy.linalg.solve_triangular(l, linv_sb.T, lower=True).T
        m = 0.5 * (m + m.T)  # symmetrize fp error
        eigvals, y = jnp.linalg.eigh(m)
        order = jnp.argsort(-jnp.abs(eigvals))[: self.num_dimensions]
        w = jax.scipy.linalg.solve_triangular(
            l.T, y[:, order], lower=False
        )
        # Breeze's eig returns unit eigenvectors; normalize so the projection
        # matrix matches the reference's (up to per-column sign).
        w = w / jnp.linalg.norm(w, axis=0, keepdims=True)
        return LinearMapper(w)
