"""Seeded 32x32 colour images of separable classes, on the host.

A copy of ``chip_smoke._class_images``: class colour, a class-frequency
stripe on one channel, noise; rounded to whole levels as a decoded byte
image is.  Returns ``[n, size, size, 3]`` float32 and int32 labels.
"""

from __future__ import annotations

import numpy as np


def _images(rng, palette, labels, p):
    n, size = len(labels), p["size"]
    noise = rng.standard_normal((n, 3, size, size), dtype=np.float32)
    img = palette[labels][:, :, None, None] + p["noise_sigma"] * noise
    xx = np.arange(size, dtype=np.float32)[None, None, :]
    stripe = p["stripe_amp"] * np.sin(xx / (2.0 + labels)[:, None, None])
    img[np.arange(n), labels % 3] += stripe.astype(np.float32)
    img = np.rint(np.clip(img, 0, 255))
    return np.ascontiguousarray(img.transpose(0, 2, 3, 1), dtype=np.float32)


def generate(params: dict, rows: dict, seed: int) -> dict:
    """``rows``: ``{"train": n, "test": m}``.  The same seed gives the same
    images; the palette is shared by both splits."""
    rng = np.random.default_rng([seed, 0xC1FA])
    classes = params["classes"]
    palette = rng.uniform(
        params["palette_low"], params["palette_high"], (classes, 3)
    ).astype(np.float32)
    out = {}
    for split in ("train", "test"):
        n = rows[split]
        images = np.empty((n, params["size"], params["size"], 3), np.float32)
        labels = rng.integers(0, classes, n).astype(np.int32)
        for start in range(0, n, 10_000):
            stop = min(n, start + 10_000)
            images[start:stop] = _images(rng, palette, labels[start:stop], params)
        out[split] = {"x": images, "y": labels}
    return out
