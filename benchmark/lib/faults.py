"""Ways to break the timed path underneath the harness, for the tests under
``benchmark/tests`` and for reading each fault on the chip.  Each takes the
pipeline's ``fit`` and returns a broken one."""

from __future__ import annotations

import contextlib

import numpy as np


def half_batch(fit):
    """Half of the training rows left out; the fit is over the rest."""

    def broken(conf, data, seed, stem):
        n = len(data["train"]["y"]) // 2
        half = dict(data, train={k: v[:n] for k, v in data["train"].items()})
        return fit(conf, half, seed, stem)

    return broken


@contextlib.contextmanager
def state_unchanged():
    """The block solve hands its state back as it got it: the models stay
    at their initial zeros.  ``solvers.block._execute_fused_bcd`` is at
    module level so that a harness can stand in for it."""
    import jax.numpy as jnp

    from keystone_tpu.solvers import block

    real = block._execute_fused_bcd

    def unchanged(plan, dn, x, labels, lam, nvalid, num_iter, widths):
        bs, nb = max(widths), len(widths)
        return (
            jnp.zeros((nb, bs, labels.shape[1]), labels.dtype),
            jnp.mean(labels, axis=0),
            jnp.mean(x, axis=0).reshape(nb, bs),
        )

    block._execute_fused_bcd = unchanged
    try:
        yield
    finally:
        block._execute_fused_bcd = real


def answer_altered(produced_fn):
    """One answer altered where the benchmark receives it: the first test
    row's scores (or, where the fit hands back a chain, its first weight
    row) moved by the size of a typical entry, and its prediction with it."""

    def broken(out, conf, data, seed):
        got = dict(produced_fn(out, conf, data, seed))
        key = "test_scores" if "test_scores" in got else "test_scores_sample"
        arr = np.array(got[key], copy=True)
        arr[0] += 10.0 * (np.abs(arr).mean() + 1e-6)
        got[key] = arr
        return got

    return broken
