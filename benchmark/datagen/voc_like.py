"""Seeded byte images of VOC 2007's sizes with several labels an image, on
the host as an image loader yields them: ``[H, W, 3]`` uint8, channels B, G, R.

A class is an oriented grating: class ``c`` of ``classes`` has the direction
``c * 180 / classes`` degrees and one of ``periods_px``.  An image carries the
gratings of its 1 to ``len(label_share)`` labels, each turned by a draw of
``angle_jitter_deg`` and scaled by a draw from ``amp_range``, one clutter
grating of any direction, a gain a channel and uniform noise a pixel.
Neighbouring classes are ``180 / classes`` degrees apart, less than the
jitter's spread and a fifth of one of SIFT's 8 orientation bins, so classes
overlap and scores separate precisions; the texture covers the image, so
nearly every descriptor clears SIFT's contrast threshold.  How many images
have each of ``shapes`` is fixed by the shares; the seed draws their order
behind one image of each shape.

Every image has its own generator (seed, split, ordinal), so images are made
by a pool of threads and come out the same in any order.  Nothing here is
shared with the repo's tests (``tests/test_fisher_pipelines.write_voc_tar``
draws its own).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np


_scratch = threading.local()


def _buffers(shape):
    """A thread's work arrays for images of ``shape``: made once, so that no
    image allocates (a fresh 2 MB array a step is a page fault a page, and
    the threads then wait on one another in the kernel)."""
    held = _scratch.__dict__.setdefault("held", {})
    if shape not in held:
        held[shape] = (
            np.empty(shape, np.float32), np.empty(shape, np.float32),
            np.empty(shape, np.float32), np.empty(shape + (3,), np.float32),
        )
    return held[shape]


def _image(seed, split, i, labels, grid, p, out):
    """Image ``i`` of ``split`` into ``out`` (``[H, W, 3]`` uint8)."""
    rng = np.random.default_rng([seed, 0x70C, split, i])
    yy, xx = grid
    tex, tmp, tmp2, img = _buffers(yy.shape)
    step = np.pi / p["classes"]
    lo, hi = p["amp_range"]
    draws = [(c * step, p["periods_px"][c % len(p["periods_px"])], rng.uniform(lo, hi)) for c in labels]
    draws.append((rng.uniform(0, np.pi), rng.choice(p["periods_px"]), p["clutter_amp"]))
    tex.fill(0.0)
    for theta, period, amp in draws:
        theta += np.deg2rad(p["angle_jitter_deg"]) * rng.standard_normal()
        k = 2.0 * np.pi / period
        np.multiply(xx, np.float32(k * np.cos(theta)), out=tmp)
        np.multiply(yy, np.float32(k * np.sin(theta)), out=tmp2)
        tmp += tmp2
        tmp += np.float32(rng.uniform(0, 2.0 * np.pi))
        np.cos(tmp, out=tmp)
        tmp *= np.float32(amp)
        tex += tmp
    # uniform noise of +-noise_amp a pixel and channel around the mean level
    rng.random(out=img, dtype=np.float32)
    img *= np.float32(2.0 * p["noise_amp"])
    img += np.float32(p["mean_level"] - p["noise_amp"])
    for ch, gain in enumerate(rng.uniform(*p["gain_range"], 3)):
        np.multiply(tex, np.float32(gain), out=tmp)
        img[..., ch] += tmp
    np.rint(img, out=img)
    np.clip(img, 0.0, 255.0, out=img)
    np.copyto(out, img, casting="unsafe")


def _shape_counts(share, n: int):
    """How many of ``n`` images each shape gets: its share of ``n`` rounded
    down, the images left over to the largest remainders (the first of equal
    ones).  The counts are the same on every seed and only the order is
    drawn: a fit pads each shape's last chunk, so counts that moved with the
    seed would move the number of chunks a fit runs (0.1 s each in a 13 s
    fit: PERF.md, PR 28) and with it the rate from seed to seed."""
    exact = share / share.sum() * n
    counts = np.floor(exact).astype(np.int64)
    left = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:left]] += 1
    return counts


def _split(params: dict, n: int, seed: int, split: int, pool) -> dict:
    rng = np.random.default_rng([seed, 0x70C, split])
    shapes = [tuple(s[:2]) for s in params["shapes"]]
    share = np.asarray([s[2] for s in params["shapes"]], np.float64)
    # one image of each shape first, in the shapes' own order: a program that
    # takes its shape buckets in first-occurrence order then meets them in one
    # order on every seed and runs (and finds cached) the same programs
    rest = np.repeat(np.arange(len(shapes)), _shape_counts(share, n) - 1)
    which = np.concatenate([np.arange(len(shapes)), rng.permutation(rest)])
    counts = 1 + rng.choice(len(params["label_share"]), n, p=params["label_share"])
    labels = np.full((n, len(params["label_share"])), -1, np.int32)
    for i in range(n):
        labels[i, : counts[i]] = np.sort(rng.choice(params["classes"], counts[i], replace=False))
    # one array a shape; the list holds views of them in image order
    store = [np.empty((int(np.sum(which == s)),) + shapes[s] + (3,), np.uint8) for s in range(len(shapes))]
    grids = [np.mgrid[0:h, 0:w].astype(np.float32) for h, w in shapes]
    slot = np.zeros(n, np.int64)
    for s in range(len(shapes)):
        slot[which == s] = np.arange(int(np.sum(which == s)))

    def make(i):
        row = labels[i]
        _image(seed, split, i, row[row >= 0], grids[which[i]], params, store[which[i]][slot[i]])

    list(pool.map(make, range(n), chunksize=16))
    return {"x": [store[which[i]][slot[i]] for i in range(n)], "y": labels}


def generate(params: dict, rows: dict, seed: int) -> dict:
    """``rows``: ``{"train": n, "test": m}``.  ``x`` is a list of ``[H, W, 3]``
    uint8 images of mixed shapes, ``y`` an ``[n, labels]`` int32 array of
    class ids padded with -1.  The same seed gives the same data."""
    with ThreadPoolExecutor(max(1, min(16, (os.cpu_count() or 2) - 1))) as pool:
        return {
            name: _split(params, rows[name], seed, split, pool)
            for split, name in enumerate(("train", "test"))
        }
