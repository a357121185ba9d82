"""Seeded dense rows around class centres, made on the device in one
jitted call: ``x = centre[label] * centre_scale + noise``."""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _maker(n_train: int, n_test: int, dim: int, classes: int):
    import jax
    import jax.numpy as jnp

    def make(key, centre_scale, noise_sigma):
        kc, kl, kn = jax.random.split(key, 3)
        centres = centre_scale * jax.random.normal(kc, (classes, dim), jnp.float32)
        labels = jax.random.randint(kl, (n_train + n_test,), 0, classes)
        noise = jax.random.normal(kn, (n_train + n_test, dim), jnp.float32)
        x = centres[labels] + noise_sigma * noise
        return x[:n_train], labels[:n_train], x[n_train:], labels[n_train:]

    return jax.jit(make)


def generate(params: dict, rows: dict, seed: int) -> dict:
    import jax

    make = _maker(rows["train"], rows["test"], params["dim"], params["classes"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**32 - 5)), 0x7131)
    xtr, ytr, xte, yte = make(key, params["centre_scale"], params["noise_sigma"])
    return {
        "train": {"x": xtr, "y": np.asarray(ytr, np.int32)},
        "test": {"x": xte, "y": np.asarray(yte, np.int32)},
    }
