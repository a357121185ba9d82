"""Operations and bytes that VOCSIFTFisher's mathematics needs, from the
cell's shapes and, for EM alone, the iterations the program says it ran.

The work, not the implementation: dense SIFT is counted by its definition
(a separable triangular window evaluated where a descriptor bin is read, not
the banded matrix products the program forms), every image passes through
SIFT once (the program's fit passes its training images twice), and EM is
counted a sample and iteration.  So no share passes 100% and none moves when
the program does.
"""

from __future__ import annotations

import math

from benchmark.lib.manifest import load_module

_shared = load_module("counts", "cifar_rp")
bcd, predict, block_widths = _shared.bcd, _shared.predict, _shared.block_widths

SIFT_DIM, ORIENTATIONS, BINS, MAGNIF = 128, 8, 4, 6.0
#: a pixel's gradients (4), magnitude (4), angle (1) and its share of each of
#: the 8 orientations (4 each)
GRADIENT_FLOPS = 41.0
#: a descriptor entry: two normalizations, the clamp, the quantization
NORMALIZE_FLOPS = 8.0


def _bins(length: int, conf: dict, scale: int):
    """(frames, distinct bin centres) along an axis of ``length`` pixels."""
    b = conf["sift_bin"] + 2 * scale
    step = conf["sift_step"] + scale * conf["scale_step"]
    off = (1 + 2 * conf["sift_scales"]) - 3 * scale
    origins = range(off, length - 1 - (BINS - 1) * b + 1, step)
    return len(origins), len({o + j * b for o in origins for j in range(BINS)})


def descriptors(conf: dict, h: int, w: int) -> int:
    return sum(
        _bins(h, conf, s)[0] * _bins(w, conf, s)[0] for s in range(conf["sift_scales"])
    )


def sift(conf: dict, h: int, w: int) -> float:
    """Operations of one image's dense SIFT, by its definition."""
    flops = 0.0
    for s in range(conf["sift_scales"]):
        b = conf["sift_bin"] + 2 * s
        (fy, rows), (fx, cols) = _bins(h, conf, s), _bins(w, conf, s)
        if not fy or not fx:
            continue
        taps = 2 * max(1, math.ceil(4.0 * b / MAGNIF)) + 1
        flops += 2.0 * (2 * taps) * h * w  # Gaussian, rows then columns
        flops += GRADIENT_FLOPS * h * w
        # the window along rows where a bin row is read, then along columns
        # where a bin is read, an orientation
        flops += ORIENTATIONS * 2.0 * (2 * b - 1) * (rows * w + rows * cols)
        flops += NORMALIZE_FLOPS * SIFT_DIM * fy * fx
    return flops


def encode(conf: dict, cols: int) -> float:
    """One image's projection and Fisher vector from ``cols`` descriptors:
    the projection, two products for the densities, two for the first and
    second moments, and the posteriors' exponentials."""
    d, k = conf["desc_dim"], conf["vocab_size"]
    return cols * (2.0 * SIFT_DIM * d + 4 * 2.0 * d * k + 4.0 * k) + 10.0 * d * k


def shape_mix(conf: dict) -> list:
    total = sum(s[2] for s in conf["data"]["shapes"])
    return [(s[0], s[1], s[2] / total) for s in conf["data"]["shapes"]]


def chain(conf: dict, images: int) -> dict:
    """SIFT -> PCA -> Fisher features of ``images`` images, each once; bytes
    are the byte images in and the float32 features out."""
    flops = nbytes = 0.0
    for h, w, share in shape_mix(conf):
        n = images * share
        flops += n * (sift(conf, h, w) + encode(conf, descriptors(conf, h, w)))
        nbytes += n * (3.0 * h * w + 4.0 * 2 * conf["desc_dim"] * conf["vocab_size"])
    return {"flops": flops, "bytes": nbytes}


def em_iterations():
    """EM iterations a fit, as the program's registry counted them (counter
    ``gmm.iterations`` over the fits that entered the ``gmm`` stage); None
    where the program counts none."""
    try:
        from keystone_tpu.core.trace import metrics
    except ImportError:
        return None
    fits = metrics.hist_windows().get("stage_ms.gmm", {}).get("count", 0)
    total = metrics.counters().get("gmm.iterations")
    return total / fits if total and fits else None


def dictionary(conf: dict, iterations: float) -> dict:
    """PCA of the sampled descriptors (the covariance, 2 n d^2, and its
    eigenvectors, ~ 9 d^3) and EM: an iteration is four products of the
    samples with ``[d, k]`` and the posteriors.  Bytes: the samples read
    once for PCA and once an iteration."""
    n_p, n_g = conf["num_pca_samples"], conf["num_gmm_samples"]
    d, k = conf["desc_dim"], conf["vocab_size"]
    pca = 2.0 * n_p * SIFT_DIM**2 + 9.0 * SIFT_DIM**3 + 2.0 * n_g * SIFT_DIM * d
    em = iterations * n_g * (4 * 2.0 * d * k + 4.0 * k)
    return {
        "pca": {"flops": pca, "bytes": 4.0 * SIFT_DIM * (n_p + n_g)},
        "em": {"flops": em, "bytes": iterations * 4.0 * n_g * d},
    }


def fit(conf: dict, rows: dict) -> dict:
    """One whole fit: every image through the chain once, PCA and EM at the
    iterations run (none counted where the program says none), the solve,
    the test rows' scores."""
    d = 2 * conf["desc_dim"] * conf["vocab_size"]
    learned = dictionary(conf, em_iterations() or 0.0)
    parts = {
        "chain": chain(conf, rows["train"] + rows["test"]),
        "pca": learned["pca"],
        "em": learned["em"],
        "bcd": bcd(rows["train"], block_widths(d, conf["solver_block"]), conf["num_classes"], conf["num_epochs"]),
        "predict": predict(rows["test"], d, conf["num_classes"]),
    }
    parts["total_flops"] = sum(p["flops"] for p in parts.values())
    return parts


def kernels(conf: dict, rows: dict) -> dict:
    """The kernels a metric of this cell reads.  The block solve runs here
    too, but ``metrics/bcd_roofline.json`` lists the two older cells and is not a
    file this PR may edit, so no metric would read an entry for it."""
    parts = fit(conf, rows)
    return {
        "fv_chain": dict(parts["chain"], layer="featurizers"),
        # the EM program alone, not the dictionary's SVD beside it
        "gmm": dict(parts["em"], layer="em"),
    }
