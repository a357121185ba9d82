"""The fault only a cell across chips can have, for ``benchmark/tests``:
one chip's partial gram left out of the sum over the data axis."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def partial_gram_dropped():
    """The mesh solve with every block's gram summed over all row shards
    but the last: what a psum that misses one chip's contribution makes.
    The cross terms, the residual and the means stay whole, so only the
    factor is wrong.  Stands in for ``solvers.block._execute_fused_bcd_mesh``,
    which is at module level so that a harness can."""
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl

    from keystone_tpu.solvers import block

    real = block._execute_fused_bcd_mesh

    def broken(plan, x, labels, lam, nvalid, num_iter, widths, mesh, specs=None):
        n, (bs, nb) = x.shape[0], (max(widths), len(widths))
        kept = n - n // mesh.shape["data"]
        mask = (jnp.arange(n) < nvalid).astype(labels.dtype)[:, None]
        label_mean = jnp.sum(labels * mask, axis=0) / nvalid
        residual = (labels - label_mean) * mask
        means = ((mask[:, 0] @ x) / nvalid).reshape(nb, bs)
        blocks, chols = [], []
        for i, w in enumerate(widths):
            a = (x[:, i * bs : (i + 1) * bs] - means[i]) * mask
            pad = (jnp.arange(bs) >= w).astype(labels.dtype)
            gram = a[:kept].T @ a[:kept] + jnp.diag(lam + pad)
            blocks.append(a)
            chols.append(jsl.cho_factor(gram)[0])
        models = [jnp.zeros((bs, labels.shape[1]), labels.dtype)] * nb
        for _ in range(num_iter):
            for i, a in enumerate(blocks):
                r_i = residual + a @ models[i]
                models[i] = jsl.cho_solve((chols[i], False), a.T @ r_i)
                residual = r_i - a @ models[i]
        return jnp.stack(models), label_mean, means

    block._execute_fused_bcd_mesh = broken
    try:
        yield
    finally:
        block._execute_fused_bcd_mesh = real
