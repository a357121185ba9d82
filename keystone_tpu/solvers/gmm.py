"""Gaussian mixture model with diagonal covariances, fit by EM
(reference src/main/scala/nodes/learning/GaussianMixtureModel.scala:18-91,
which delegates to the vendored enceval C++ EM — src/main/cpp/EncEval.cxx:122-193).

The reference collects samples to the driver and runs single-threaded C++ EM.
Here the E-step is one [n, k] batched log-density + softmax on the MXU and
the M-step a handful of gemms — chunked over samples so 1e7-descriptor fits
stream through HBM.  Init follows EncEval.cxx:146-148: seed-42 random samples
as means (the exact enceval RNG is not reproduced; parity target is
distribution recovery, per the reference suite EncEvalSuite.scala:42-64).

Model layout matches the reference: ``means``/``variances`` are [d, k]
(centroid-major columns), ``weights`` [k].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import Estimator, Transformer, node


@node(data_fields=("means", "variances", "weights"))
class GaussianMixtureModel(Transformer):
    """Diagonal-covariance GMM (reference GaussianMixtureModel.scala:18-36).

    ``__call__`` returns the soft cluster assignments (posteriors) — the
    reference declares this surface but leaves it unimplemented (:32-36).
    """

    def __init__(self, means, variances, weights):
        means = jnp.asarray(means)
        variances = jnp.asarray(variances)
        weights = jnp.asarray(weights)
        if means.shape != variances.shape:
            raise ValueError("GMM means and variances must be the same size.")
        if weights.shape[0] != means.shape[1]:
            raise ValueError("Every GMM center must have a weight.")
        self.means = means
        self.variances = variances
        self.weights = weights

    @property
    def k(self) -> int:
        return self.means.shape[1]

    @property
    def dim(self) -> int:
        return self.means.shape[0]

    def log_responsibilities(self, x):
        """[n, d] -> [n, k] log posteriors under the mixture."""
        return _log_resp(x, self.means, self.variances, self.weights)

    def __call__(self, batch):
        return jax.nn.softmax(self.log_responsibilities(batch), axis=-1)

    @staticmethod
    def load(mean_file: str, vars_file: str, weights_file: str) -> "GaussianMixtureModel":
        """CSV artifact loading (reference GaussianMixtureModel.scala:83-90) —
        the load-or-fit checkpoint pattern."""
        means = np.loadtxt(mean_file, delimiter=",", ndmin=2)
        variances = np.loadtxt(vars_file, delimiter=",", ndmin=2)
        weights = np.loadtxt(weights_file, delimiter=",").ravel()
        return GaussianMixtureModel(means, variances, weights)


@jax.jit
def _log_resp(x, means, variances, weights):
    # log N(x; mu_k, diag sigma2_k) + log pi_k, via one gemm per moment.
    # The three terms of the expanded square cancel to a small difference of
    # large numbers (descriptors are projected without centring, so |mu| is
    # many sigma), and a TPU's default single bfloat16 pass rounds each
    # operand to 8 bits: measured on the v5e at 1e6 x 80 samples and 256
    # centres, EM then never converged (100 iterations against 21), four
    # centres died and the Fisher vectors stood 68% off the float32 ones
    # (PERF.md, PR 28).  So these two products ask for full precision; the
    # moment products of EM and of the Fisher vector keep the default.
    inv_var = 1.0 / variances  # [d, k]
    x2 = x * x
    exact = jax.lax.Precision.HIGHEST
    quad = (
        jnp.matmul(x2, inv_var, precision=exact)
        - 2.0 * jnp.matmul(x, means * inv_var, precision=exact)
        + jnp.sum(means * means * inv_var, axis=0)
    )
    log_det = jnp.sum(jnp.log(variances), axis=0)
    d = x.shape[1]
    log_pdf = -0.5 * (quad + log_det + d * jnp.log(2.0 * jnp.pi))
    return log_pdf + jnp.log(weights)


@jax.jit
def _e_stats(x, means, variances, weights):
    """Sufficient statistics (s0, s1, s2, Σ log-norm) for one sample chunk."""
    logr = _log_resp(x, means, variances, weights)
    log_norm = jax.scipy.special.logsumexp(logr, axis=1, keepdims=True)
    q = jnp.exp(logr - log_norm)  # [n, k]
    s0 = jnp.sum(q, axis=0)  # [k]
    s1 = x.T @ q  # [d, k]
    s2 = (x * x).T @ q  # [d, k]
    return s0, s1, s2, jnp.sum(log_norm)


def _em_step(x, means, variances, weights, var_floor, chunk: int):
    """One EM iteration, E-step chunked over samples so the [n, k] posterior
    matrix never exceeds one chunk's footprint."""
    n = x.shape[0]
    d, k = means.shape
    s0 = jnp.zeros((k,), x.dtype)
    s1 = jnp.zeros((d, k), x.dtype)
    s2 = jnp.zeros((d, k), x.dtype)
    llh_sum = jnp.zeros((), x.dtype)
    for i in range(0, n, chunk):
        c0, c1, c2, cl = _e_stats(x[i : i + chunk], means, variances, weights)
        s0, s1, s2, llh_sum = s0 + c0, s1 + c1, s2 + c2, llh_sum + cl
    # Floor the responsibility mass: an empty/collapsed component would give
    # 0/0 = NaN means and poison the whole fit.
    s0_safe = jnp.maximum(s0, 1e-10)
    new_means = s1 / s0_safe
    new_vars = jnp.maximum(s2 / s0_safe - new_means * new_means, var_floor)
    new_weights = s0 / n
    return new_means, new_vars, new_weights, llh_sum / n


@functools.partial(jax.jit, static_argnames=("max_iter", "chunk"))
def _em_fit(x, means, variances, weights, var_floor, tol, max_iter: int, chunk: int):
    """The ENTIRE EM fit as one compiled program: a lax.while_loop runs EM
    steps until the device-side convergence test fires (same test as the
    reference's enceval loop) or ``max_iter`` is hit: one program and one
    host pull per fit, where an eager loop would pull the log-likelihood
    every iteration."""

    def cond(state):
        i, _, _, _, llh, prev = state
        return (i < max_iter) & (
            jnp.abs(llh - prev) >= tol * jnp.maximum(1.0, jnp.abs(llh))
        )

    def body(state):
        i, m, v, w, llh, _ = state
        m2, v2, w2, llh2 = _em_step(x, m, v, w, var_floor, chunk)
        return (i + 1, m2, v2, w2, llh2, llh)

    # +/-inf sentinels make the first two conditions unconditionally true,
    # reproducing the eager loop's "first comparison at iteration 2".
    init = (0, means, variances, weights, jnp.inf, -jnp.inf)
    iters, m, v, w, _, _ = jax.lax.while_loop(cond, body, init)
    return m, v, w, iters


class GaussianMixtureModelEstimator(Estimator):
    """Fit a ``k``-center GMM by EM (reference GaussianMixtureModel.scala:44-80;
    EM semantics from the vendored enceval gaussian_mixture<float>)."""

    def __init__(
        self,
        k: int,
        max_iter: int = 100,
        tol: float = 1e-4,
        seed: int = 42,
        var_floor_factor: float = 1e-3,
        chunk: int = 1 << 18,
    ):
        self.k = k
        self.max_iter = max_iter
        self.tol = tol
        self.seed = seed
        self.var_floor_factor = var_floor_factor
        self.chunk = chunk

    def fit(self, samples) -> GaussianMixtureModel:
        x = jnp.asarray(samples, jnp.float32)
        n, d = x.shape
        if n < self.k:
            raise ValueError(f"need at least k={self.k} samples, got {n}")

        rng = np.random.default_rng(self.seed)  # seed 42 per EncEval.cxx:146
        idx = rng.choice(n, self.k, replace=False)
        means = x[jnp.asarray(idx)].T  # [d, k]
        global_var = jnp.var(x, axis=0)[:, None]  # [d, 1]
        variances = jnp.broadcast_to(global_var, (d, self.k))
        weights = jnp.full((self.k,), 1.0 / self.k, x.dtype)
        var_floor = self.var_floor_factor * jnp.mean(global_var)

        means, variances, weights, iters = _em_fit(
            x, means, variances, weights, var_floor,
            jnp.asarray(self.tol, x.dtype), self.max_iter, self.chunk,
        )
        # EM iterations actually run (device-resident until read; a host
        # pull of this one scalar is the only extra sync a caller pays).
        self.last_iterations = iters
        return GaussianMixtureModel(means, variances, weights)
