"""Plain reference of ImageNetSiftLcsFV for the cell across chips
(``imagenet_sift_lcs_fv_16_mesh4``).  The mathematics and the comparison are
``reference/imagenet_fv.py``'s, loaded from that file and not copied: the
same stages on the same data give the same answers however many chips made
them.  What this file adds is which training images are compared: the
featurizing pass hands each chip ``chunk / shards`` images of every chunk,
and the compared images are drawn from every chip's share, ``images /
shards`` from each, so that a stage that went wrong on one chip shows.  The
weighted solve's numbers (``model_gap``, ``scores_rms_gap``,
``scores_max_gap``, ``top5_gap``) are taken over all the classes, whichever
chip solved them."""

from __future__ import annotations

import numpy as np

from benchmark.lib.manifest import load_module

_one_chip = load_module("reference", "imagenet_fv")
_voc = _one_chip._voc

fit = _one_chip.fit
compare = _one_chip.compare
control = _one_chip.control
weighted_least_squares = _one_chip.weighted_least_squares


def shards_of(index: dict, chunks: dict, shards: int) -> np.ndarray:
    """The chip each image is featurized on: a bucket's images (``index``,
    in the order the chunks take them) go through in chunks of
    ``chunks[shape]`` images, each chunk split into ``shards`` equal runs, one
    a chip; the last chunk of a bucket is padded to the chunk."""
    out = np.zeros(sum(len(i) for i in index.values()), np.int64)
    for shape, members in index.items():
        per_chip = chunks[shape] // shards
        out[members] = (np.arange(len(members)) % chunks[shape]) // per_chip
    return out


def compare_rows(conf: dict, images: list, seed: int, index: dict, chunks: dict, shards: int) -> np.ndarray:
    """The training images whose descriptors and Fisher vectors are compared:
    ``conf["compare"]["images"] / shards`` from each chip's share, drawn from
    the seed as ``reference/voc_fv.compare_rows`` draws them (each shape
    bucket its share and at least one)."""
    rng = np.random.default_rng([seed, 0x51F7])
    chip = shards_of(index, chunks, shards)
    want = conf["compare"]["images"] // shards
    rows = []
    for k in range(shards):
        mine = np.flatnonzero(chip == k)
        for _shape, idx in _voc.buckets_of(images).items():
            pool = np.intersect1d(idx, mine)
            if not len(pool):
                continue
            take = min(len(pool), max(1, round(want * len(pool) / len(mine))))
            rows.append(np.sort(rng.permutation(pool)[:take]))
    return np.unique(np.concatenate(rows))
