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

**One node, two forms of the same mathematics** (the pattern of
``ops/conv_fused.py``).  The XLA form (``fisher_vector`` under ``vmap``)
writes the ``[cols, k]`` float32 posteriors to HBM and crosses them seven
times: two log-density products, the softmax's max, sum and normalization,
two moment products.  The kernel form (``ops/fv_pallas.fv_stats_pallas``)
keeps them in VMEM: its HBM operands are the projected descriptors, read
once, and the statistics.  Both take each product at the same precision
(log-density products at full float32, moment products in one bfloat16 pass
with float32 accumulation; see the kernel's docstring).

**Which form runs** (``fv_form``) follows from where the program runs and
what it is given, and, against what was expected, from no shape.  An image's
posteriors cost the XLA form ``7 * 4 B * cols * k`` bytes of HBM traffic; the
descriptors, which both forms read, ``4 B * cols * d``; so the kernel form was
expected to win from some multiple of ``7k/d`` up, as the conv featurizer's
does from ~170 filters (a predecessor of the kernel lost by 1.7x at vocab 16,
d 64, 13,165 descriptors; round 4).  Measured on v5e (tools/fv_form_probe.py,
PR 29; ROOFLINE.md, "At the published sizes"), 64 images of 73,866
descriptors, the device's own time a chunk:

    vocab   d    7k/d   kernel form   XLA form   (ms)
       2   64    0.22      2.69         6.79
       4   80    0.35      3.19         8.58
       8   64    0.88      2.68         8.14
      16   64    1.75      2.84         9.93     (0.54 : 1.23 at 13,165)
      16   80    1.40      3.31        11.46
      64   64    7.0       6.13        20.11
      64   80    5.6       6.31        21.72
     256   64   28.0      22.62        61.47
     256   80   22.4      22.89        62.69     (the benchmark's)

and one image (a served request): 0.046 against 0.086 at vocab 16, 0.36
against 0.91 at 256.  The XLA form won nowhere: it reads the descriptors once
a product, four times (1.2-1.5 GB and 1.6-2.5 ms each at the narrowest vocab)
where the kernel reads them once, and since ``_log_resp`` asks full float32
(PR 28) its two log-density products alone cost more than the whole kernel at
every width.  So the rule has no threshold to set, and the shapes ride the
``fv_form`` instant only.  What it does turn on: the kernel form is a custom
call, which the compiler does not partition (left to GSPMD under a mesh it
would run whole on every chip), so where only the ``data`` axis splits the
input the kernel runs under ``shard_map`` over that axis
(``_sharded_kernel_form``: each chip its own images against the replicated
mixture, nothing exchanged) and under a ``model`` split the XLA form runs;
the kernel knows prefix counts, not arbitrary masks; Mosaic compiles for the
TPU only.  Elsewhere the XLA form runs.  No environment variable or flag
reaches the choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import trace
from ..core.pipeline import Transformer, node
from ..solvers.gmm import GaussianMixtureModel, _log_resp
from ..parallel.mesh import DATA_AXIS
from ..parallel.mesh import input_mesh as _input_mesh
from ..parallel.mesh import split_axes as _split_axes
from .fv_pallas import fv_stats_pallas


def fv_form(
    backend: str, split_axes: tuple, masked: bool, images: int = 1, shards: int = 1
) -> str:
    """``"kernel"`` or ``"xla"`` for ``images`` descriptor matrices split
    over the mesh axes ``split_axes`` (``()`` on one device) into ``shards``
    equal parts: the rule of the module docstring, in one place."""
    kernel = (
        backend == "tpu" and set(split_axes) <= {DATA_AXIS} and not masked
        and images % shards == 0
    )
    return "kernel" if kernel else "xla"


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
        """``mask``: optional [N, cols] validity for ragged descriptor counts
        (always the XLA form: the kernel knows prefix counts, not masks)."""
        n, d, cols = batch.shape
        axes = _split_axes(batch)
        mesh = _input_mesh(batch) if axes else None
        shards = 1 if mesh is None else dict(mesh.shape).get(DATA_AXIS, 1)
        form = fv_form(jax.default_backend(), axes, mask is not None, n, shards)
        # Counted where the program is traced: once a jitted shape; the
        # instant's ``shards`` and ``split`` as ``sift_form``'s.
        trace.metrics.inc(f"fv_form.{form}")
        trace.instant(
            "fv_form", form=form, images=n, cols=cols, d=d, k=self.gmm.k,
            shards=shards if form == "kernel" else 1, split=",".join(axes),
        )
        if form == "xla":
            return self._xla_form(batch, mask)
        if mesh is None:
            return self._kernel_form(batch)
        return self._sharded_kernel_form(batch, mesh)

    def _xla_form(self, batch, mask=None):
        gmm = self.gmm

        def one(mat, m):
            return fisher_vector(mat.T, gmm.means, gmm.variances, gmm.weights, m)

        if mask is None:
            return jax.vmap(lambda mat: one(mat, None))(batch)
        return jax.vmap(one)(batch, mask)

    def _kernel_form(self, batch, interpret: bool = False):
        gmm = self.gmm
        s0, s1, s2 = fv_stats_pallas(
            batch, None, gmm.means, gmm.variances, gmm.weights, interpret=interpret
        )
        n_valid = jnp.full((batch.shape[0],), batch.shape[2], jnp.float32)
        return _fv_from_stats(
            s0, s1, s2, gmm.means, gmm.variances, gmm.weights, n_valid
        )

    def _sharded_kernel_form(self, batch, mesh, interpret: bool = False):
        """The kernel form on descriptor matrices split over ``mesh``'s data
        axis: every chip runs :meth:`_kernel_form` on its own images against
        the whole mixture (the node is replicated), and the Fisher vectors
        stay split as the images were.  No collective."""
        fn = jax.shard_map(
            lambda node_, rows: node_._kernel_form(rows, interpret=interpret),
            mesh=mesh,
            in_specs=(P(), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
            check_vma=False,
        )
        return fn(self, batch)
