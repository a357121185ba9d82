"""Faults only a fit that makes random-sign FFT features can have, for
``benchmark/tests/test_mnist_fft.py`` and for reading each fault on the chip
(``lib/faults.py`` has the three every whole-fit cell can have).  Each is a
context around a whole run: one step of the featurizer chain changed while
it lasts, as a program that has the fault has it everywhere (the fits and
the features ``produced`` makes for the comparison), every program that
makes a block traced again with it, and again after, so nothing compiled
under the fault outlives it.  The reference shares none of these nodes."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def planted(cls, call):
    import jax

    real = cls.__call__
    jax.clear_caches()
    cls.__call__ = call
    try:
        yield
    finally:
        cls.__call__ = real
        jax.clear_caches()


def signs_left_out():
    """RandomSign multiplies by one: every FFT of a block sees the same rows."""
    import jax.numpy as jnp

    from keystone_tpu.ops.stats import RandomSignNode

    return planted(RandomSignNode, lambda self, batch: batch * jnp.ones_like(self.signs))


def imaginary_for_real():
    """PaddedFFT keeps the imaginary part of the first half of the bins."""
    import jax.numpy as jnp

    from keystone_tpu.ops.stats import PaddedFFT, next_power_of_two

    def imag(self, batch):
        padded = next_power_of_two(batch.shape[-1])
        return jnp.fft.rfft(batch, n=padded, axis=-1).imag[..., : padded // 2]

    return planted(PaddedFFT, imag)


def rectifier_left_out():
    """LinearRectifier passes its input through."""
    from keystone_tpu.ops.stats import LinearRectifier

    return planted(LinearRectifier, lambda self, batch: batch - self.alpha)


FAULTS = {
    "signs_left_out": signs_left_out,
    "imaginary_for_real": imaginary_for_real,
    "rectifier_left_out": rectifier_left_out,
}
