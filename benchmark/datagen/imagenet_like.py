"""Seeded byte images of ILSVRC-2012's modal sizes with one label an image, on
the host as an image loader yields them: ``[H, W, 3]`` uint8, channels B, G, R.

A class is a texture and a colour.  The texture is an oriented grating, as in
``voc_like``: one of ``orientations`` directions ``180 / orientations`` degrees
apart and one of ``periods_px``; it is what dense SIFT sees.  The colour is a
constant tint of the whole image, one of ``tint_grid`` x ``tint_grid`` points
``tint_step`` grey levels apart on the (B - G, R - G) plane with the
luminance held; a constant has no gradient, so SIFT is blind to it and only
the LCS branch's window means see it.  Class ``c`` is tint ``c // textures``
and texture ``c % textures``, so a texture is shared by ``tint_grid**2``
classes and a tint by ``orientations * len(periods_px)``: neither branch alone
separates the classes.  Each image turns its grating by a draw of
``angle_jitter_deg`` and moves its tint by a draw of ``tint_jitter``, both a
good part of the distance to the neighbouring class, and carries one clutter
grating of any direction, a gain a channel and uniform noise a pixel: classes
overlap, and the top-5 error separates precisions.

Every class has the same number of rows in a split, and every run of
``classes`` consecutive rows holds each class once (in an order drawn from the
seed), so any leading part of a split that is a multiple of ``classes`` long
is balanced too.  How many images have each of ``shapes`` is fixed by the
shares; the seed draws their order behind one image of each shape.

It imports ``voc_like``'s helpers (the threads' work arrays, the fixed counts
a shape) and shares nothing with the repo's tests.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.lib.manifest import load_module

_voc = load_module("datagen", "voc_like")


def class_parts(cls: int, p: dict) -> tuple:
    """``(direction index, period index, tint row, tint column)`` of a class."""
    textures = p["orientations"] * len(p["periods_px"])
    tint, texture = divmod(int(cls), textures)
    period, direction = divmod(texture, p["orientations"])
    return (direction, period) + divmod(tint, p["tint_grid"])


def _image(seed, split, i, cls, grid, p, out):
    """Image ``i`` of ``split``, of class ``cls``, into ``out`` (``[H, W, 3]``
    uint8)."""
    rng = np.random.default_rng([seed, 0x1A6E, split, i])
    yy, xx = grid
    tex, tmp, tmp2, img = _voc._buffers(yy.shape)
    direction, period, tint_b, tint_r = class_parts(cls, p)
    lo, hi = p["amp_range"]
    gratings = [
        (direction * np.pi / p["orientations"], p["periods_px"][period], rng.uniform(lo, hi), True),
        (rng.uniform(0, np.pi), rng.choice(p["periods_px"]), p["clutter_amp"], False),
    ]
    tex.fill(0.0)
    for theta, period_px, amp, jittered in gratings:
        if jittered:
            theta += np.deg2rad(p["angle_jitter_deg"]) * rng.standard_normal()
        k = 2.0 * np.pi / period_px
        np.multiply(xx, np.float32(k * np.cos(theta)), out=tmp)
        np.multiply(yy, np.float32(k * np.sin(theta)), out=tmp2)
        tmp += tmp2
        tmp += np.float32(rng.uniform(0, 2.0 * np.pi))
        np.cos(tmp, out=tmp)
        tmp *= np.float32(amp)
        tex += tmp
    # the tint: B and R moved against G on a centred grid, luminance held
    # (0.114 B + 0.587 G + 0.2989 R, the grey SIFT reads)
    half = (p["tint_grid"] - 1) / 2.0
    b, r = (
        p["tint_step"] * (np.asarray([tint_b, tint_r]) - half)
        + p["tint_jitter"] * rng.standard_normal(2)
    )
    tint = np.asarray([b, -(0.114 * b + 0.2989 * r) / 0.587, r])
    # uniform noise of +-noise_amp a pixel and channel around the mean level
    rng.random(out=img, dtype=np.float32)
    img *= np.float32(2.0 * p["noise_amp"])
    img += np.float32(p["mean_level"] - p["noise_amp"])
    for ch, gain in enumerate(rng.uniform(*p["gain_range"], 3)):
        np.multiply(tex, np.float32(gain), out=tmp)
        tmp += np.float32(tint[ch])
        img[..., ch] += tmp
    np.rint(img, out=img)
    np.clip(img, 0.0, 255.0, out=img)
    np.copyto(out, img, casting="unsafe")


def _split(params: dict, n: int, seed: int, split: int, pool) -> dict:
    rng = np.random.default_rng([seed, 0x1A6E, split])
    classes = params["classes"]
    made = params["orientations"] * len(params["periods_px"]) * params["tint_grid"] ** 2
    if made != classes or n % classes:
        raise ValueError(
            f"{classes} classes against {made} texture x tint combinations, {n} rows"
        )
    labels = np.concatenate([rng.permutation(classes) for _ in range(n // classes)]).astype(np.int32)
    shapes = [tuple(s[:2]) for s in params["shapes"]]
    share = np.asarray([s[2] for s in params["shapes"]], np.float64)
    # one image of each shape first, in the shapes' own order, as voc_like
    # has it: every seed meets its buckets in one order
    rest = np.repeat(np.arange(len(shapes)), _voc._shape_counts(share, n) - 1)
    which = np.concatenate([np.arange(len(shapes)), rng.permutation(rest)])
    store = [np.empty((int(np.sum(which == s)),) + shapes[s] + (3,), np.uint8) for s in range(len(shapes))]
    grids = [np.mgrid[0:h, 0:w].astype(np.float32) for h, w in shapes]
    slot = np.zeros(n, np.int64)
    for s in range(len(shapes)):
        slot[which == s] = np.arange(int(np.sum(which == s)))

    def make(i):
        _image(seed, split, i, labels[i], grids[which[i]], params, store[which[i]][slot[i]])

    list(pool.map(make, range(n), chunksize=16))
    return {"x": [store[which[i]][slot[i]] for i in range(n)], "y": labels}


def generate(params: dict, rows: dict, seed: int) -> dict:
    """``rows``: ``{"train": n, "test": m}``, each a multiple of the classes.
    ``x`` is a list of ``[H, W, 3]`` uint8 images of mixed shapes, ``y`` an
    ``[n]`` int32 array of class ids.  The same seed gives the same data."""
    with ThreadPoolExecutor(max(1, min(16, (os.cpu_count() or 2) - 1))) as pool:
        return {
            name: _split(params, rows[name], seed, split, pool)
            for split, name in enumerate(("train", "test"))
        }
