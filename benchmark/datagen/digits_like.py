"""Seeded handwritten-digit-like images, made on the device in one jitted
call and kept there: ``side x side`` pixels of 0-255 as float32 (what
MNIST's bytes and the reference's CSV rows hold), the strokes inside the
central ``box x box`` and an empty border around it, as MNIST centres its
digits in 20 x 20 of 28 x 28.

A class is a template of ``strokes`` line segments drawn once from the
seed; an image of the class moves each end of each segment by ``jitter``
pixels, shifts the whole by up to ``shift`` pixels, draws it with its own
stroke width and intensity, and adds noise.  The jitter lets the classes
overlap, so the test error of a fit is neither 0 nor chance.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _maker(n_train: int, n_test: int, side: int, box: int, classes: int, strokes: int):
    import jax
    import jax.numpy as jnp

    n = n_train + n_test
    lo = (side - box) / 2.0
    grid = jnp.arange(side, dtype=jnp.float32)
    py, px = jnp.meshgrid(grid, grid, indexing="ij")
    inside = (
        (py >= lo) & (py < lo + box) & (px >= lo) & (px < lo + box)
    ).reshape(-1)

    def make(key, jitter, shift, width, noise):
        kt, kl, kj, ks, kw, ka, kn = jax.random.split(key, 7)
        # class templates: segment ends [classes, strokes, 2 ends, (y, x)]
        templates = lo + 1.0 + (box - 2.0) * jax.random.uniform(
            kt, (classes, strokes, 2, 2), jnp.float32
        )
        labels = jax.random.randint(kl, (n,), 0, classes)
        ends = templates[labels] + jitter * jax.random.normal(
            kj, (n, strokes, 2, 2), jnp.float32
        )
        ends = ends + shift * jax.random.uniform(
            ks, (n, 1, 1, 2), jnp.float32, -1.0, 1.0
        )
        w = width * jax.random.uniform(kw, (n, 1, 1), jnp.float32, 0.7, 1.3)
        ink = 255.0 * jax.random.uniform(ka, (n, 1), jnp.float32, 0.6, 1.0)
        p = jnp.stack([py.reshape(-1), px.reshape(-1)], -1)  # [side^2, 2]
        a, b = ends[:, :, None, 0], ends[:, :, None, 1]  # [n, strokes, 1, 2]
        ab = b - a
        t = jnp.clip(
            jnp.sum((p - a) * ab, -1) / jnp.maximum(jnp.sum(ab * ab, -1), 1e-6),
            0.0, 1.0,
        )
        dist = jnp.linalg.norm(p - (a + t[..., None] * ab), axis=-1)  # [n, strokes, side^2]
        stroke = jnp.max(jnp.clip(1.0 - dist / w, 0.0, 1.0), axis=1)
        img = ink * stroke + noise * jax.random.normal(kn, (n, side * side), jnp.float32)
        img = jnp.round(jnp.clip(img, 0.0, 255.0)) * inside
        return img[:n_train], labels[:n_train], img[n_train:], labels[n_train:]

    return jax.jit(make)


def generate(params: dict, rows: dict, seed: int) -> dict:
    import jax

    make = _maker(
        rows["train"], rows["test"], params["side"], params["box"],
        params["classes"], params["strokes"],
    )
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**32 - 5)), 0xD161)
    xtr, ytr, xte, yte = make(
        key, params["jitter"], params["shift"], params["width"], params["noise"]
    )
    return {
        "train": {"x": xtr, "y": np.asarray(ytr, np.int32)},
        "test": {"x": xte, "y": np.asarray(yte, np.int32)},
    }
