"""The faults only ImageNetSiftLcsFV's cell can have, for ``benchmark/tests``:
each takes the pipeline's ``fit`` and returns one that breaks the program
underneath for the length of a fit (what the benchmark makes again after the
window, the samples and the features, comes from the sound program)."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name: str, value):
    real = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, real)


def _during_fit(fit, *patches):
    def broken(conf, data, seed, stem):
        with contextlib.ExitStack() as stack:
            for make in patches:
                stack.enter_context(make())
            return fit(conf, data, seed, stem)

    return broken


def mixture_weight_ignored(fit):
    """The solve with the mixture weight at 0: plain least squares on the
    population's statistics, no class up-weighted."""

    def broken(conf, data, seed, stem):
        return fit(dict(conf, mixture_weight=0.0), data, seed, stem)

    return broken


def one_class_unsolved(fit):
    """One class's system left unsolved: its weights stay at their zeros."""
    from keystone_tpu.solvers import weighted

    real = weighted._execute_fused_bwls

    def skipping(plan, args, statics):
        models, intercept = real(plan, args, statics)
        return models.at[:, :, 0].set(0.0), intercept

    return _during_fit(fit, lambda: _patched(weighted, "_execute_fused_bwls", skipping))


def lcs_rows_are_sift_rows(fit):
    """The LCS branch's half of every feature row replaced by the SIFT
    branch's: the model is fitted and scored on rows without colour."""
    import jax.numpy as jnp

    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    real = inet.featurize_chunks

    def doubled(*args, **kwargs):
        rows = real(*args, **kwargs)
        half = rows.shape[1] // 2
        return jnp.concatenate([rows[:, :half], rows[:, :half]], axis=1)

    return _during_fit(fit, lambda: _patched(inet, "featurize_chunks", doubled))


def hellinger_left_out(fit):
    """SIFT's descriptors reach their PCA, EM and Fisher vector without the
    signed square root."""
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    real = inet.branch_preparation

    def no_root(name, centre=None):
        return real("lcs", None) if name == "sift" else real(name, centre)

    return _during_fit(fit, lambda: _patched(inet, "branch_preparation", no_root))
