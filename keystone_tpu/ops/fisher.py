"""Fisher vector encoding (reference
src/main/scala/nodes/images/external/FisherVector.scala:14-35, delegating to
the vendored enceval ``fisher<float>`` with alpha=1.0, pnorm=0 —
src/main/cpp/EncEval.cxx:67-69,97).

Improved-FV formulation (Perronnin et al.), mean and variance gradients only
(the enceval output length is exactly ``2·d·K``, EncEval.cxx:41):

    G_μk = (1/(N√π_k)) Σ_n q_nk (x_n − μ_k)/σ_k
    G_σk = (1/(N√(2π_k))) Σ_n q_nk [((x_n − μ_k)/σ_k)² − 1]

alpha=1 / pnorm=0 mean *no* power- or L2-normalization inside the encoder —
the pipelines apply SignedHellinger + NormalizeRows as separate nodes
(reference ImageNetSiftLcsFV.scala:29-39), exactly as here.

Output layout matches the reference wrapper: ``[d, 2K]`` per image — columns
0..K-1 the mean gradients, K..2K-1 the variance gradients
(FisherVector.scala:33-34 wraps the flat enceval buffer as
DenseMatrix(numDims, numCentroids*2)).

TPU-native: posteriors are one [n, k] gemm + softmax; the sufficient
statistics (s0, s1, s2) are three gemms; everything vmaps over the image
axis, with an optional validity mask for ragged descriptor counts (XLA needs
static shapes, SURVEY §7 "hard parts").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.pipeline import Transformer, node
from ..solvers.gmm import GaussianMixtureModel, _log_resp
from ..utils.platform import use_pallas_kernels


def _fv_from_stats(s0, s1, s2, means, variances, weights, n_valid):
    """Assemble mean/variance gradients from sufficient statistics.
    Batched: s0 [..., k], s1/s2 [..., d, k], n_valid [...]."""
    sigma = jnp.sqrt(variances)
    n_safe = jnp.maximum(n_valid, 1.0)[..., None, None]
    s0e = s0[..., None, :]
    # A centre whose weight EM drove to exactly 0 takes no posterior mass
    # (log 0), so its gradients are 0 / 0 by the formula: they are zeros.
    alive = weights > 0
    w = jnp.where(alive, weights, 1.0)
    g_mean = (s1 - means * s0e) / (sigma * jnp.sqrt(w) * n_safe)
    g_var = (
        (s2 - 2.0 * means * s1 + (means * means - variances) * s0e)
        / (variances * jnp.sqrt(2.0 * w) * n_safe)
    )
    both = jnp.concatenate([g_mean, g_var], axis=-1)  # [..., d, 2K]
    return jnp.where(jnp.concatenate([alive, alive]), both, 0.0)


def _use_pallas() -> bool:
    """Opt-in (KEYSTONE_PALLAS=1, shared gate utils/platform.py): the
    hand-written fused kernel MEASURED SLOWER than XLA's own fusion on the
    production shape (0.95 vs 1.61 ms — see ops/fv_pallas.py docstring), so
    the XLA path is the default by evidence, and the kernel remains
    available for shapes where the balance tips (much larger vocab K)."""
    return use_pallas_kernels()


def fisher_vector(descriptors, means, variances, weights, mask=None):
    """FV of one descriptor matrix ``[cols, d]`` (descriptors as rows here;
    callers with column-major descriptor matrices transpose first).

    ``mask``: optional [cols] 0/1 validity mask for padded descriptors —
    padded columns contribute nothing and N counts only valid ones.
    """
    x = descriptors
    logr = _log_resp(x, means, variances, weights)
    q = jax.nn.softmax(logr, axis=-1)  # [n, k]
    if mask is not None:
        q = q * mask[:, None]
        n_valid = jnp.sum(mask)
    else:
        n_valid = jnp.asarray(x.shape[0], x.dtype)

    s0 = jnp.sum(q, axis=0)  # [k]
    s1 = x.T @ q  # [d, k]
    s2 = (x * x).T @ q  # [d, k]
    return _fv_from_stats(s0, s1, s2, means, variances, weights, n_valid)


@node(data_fields=("gmm",))
class FisherVector(Transformer):
    """Batched FV node: ``[N, d, cols]`` descriptor matrices (the
    BatchPCATransformer output convention, descriptors as columns) ->
    ``[N, d, 2K]``."""

    def __init__(self, gmm: GaussianMixtureModel):
        self.gmm = gmm

    @property
    def num_dims(self) -> int:
        return self.gmm.dim

    @property
    def num_centroids(self) -> int:
        return self.gmm.k

    @property
    def num_features(self) -> int:
        return self.num_dims * self.num_centroids * 2

    def __call__(self, batch, mask=None):
        """``mask``: optional [N, cols] validity for ragged descriptor counts.

        Under KEYSTONE_PALLAS=1 on TPU the sufficient statistics run as the
        fused single-pass Pallas kernel (ops/fv_pallas.py) — measured slower
        than XLA's fusion at the production shape, kept opt-in; see the
        kernel docstring.  Masked calls always take the XLA path (the kernel
        encodes raggedness as prefix counts, not arbitrary masks)."""
        gmm = self.gmm
        if mask is None and _use_pallas():
            from .fv_pallas import fv_stats_pallas

            s0, s1, s2 = fv_stats_pallas(
                batch, None, gmm.means, gmm.variances, gmm.weights
            )
            n_valid = jnp.full((batch.shape[0],), batch.shape[2], jnp.float32)
            return _fv_from_stats(
                s0, s1, s2, gmm.means, gmm.variances, gmm.weights, n_valid
            )

        def one(mat, m):
            return fisher_vector(mat.T, gmm.means, gmm.variances, gmm.weights, m)

        if mask is None:
            return jax.vmap(lambda mat: one(mat, None))(batch)
        return jax.vmap(one)(batch, mask)
