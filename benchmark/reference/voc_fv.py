"""Plain reference of VOCSIFTFisher (reference
src/main/scala/pipelines/images/voc/VOCSIFTFisher.scala:18-165): grayscale ->
dense SIFT -> sampled descriptors -> PCA -> GMM by EM -> Fisher vectors and
their normalizations -> block least squares -> 11-point MAP.

``jax.numpy`` in float32 with full-precision products (or, for a control,
with every product's operands rounded lower: ``benchmark/lib/precision``;
SIFT has no matrix product, so there the control rounds each stage's output
and the window's taps with ``lax.reduce_precision``, which no compiler may
fold away, where it does remove an ``f32 -> fp8 -> f32`` pair of casts
inside an elementwise chain).
Nothing is imported from ``keystone_tpu``; what is shared with the program
is the mathematics and the configuration's ``sampling`` recipe.

Dense SIFT is written by its definition (VLFeat.cxx:68-263 and vl_dsift):
per scale Gaussian smoothing, gradients, the magnitude split bilinearly
between the two nearest of 8 orientations, each orientation plane passed
through a triangular window along both axes and read at the 4 x 4 bin
centres of every frame, then normalize -> clamp 0.2 -> renormalize, the
contrast threshold on the norm before normalization, and
``min(floor(512 v), 255)``.  The window is a sum of shifted copies, tap by
tap: no product with a banded matrix.

Departures from the Scala and its native code, each shared with the program:

* descriptors are ordered [bin y, bin x, orientation] on a (row, column)
  image, a fixed permutation of VLFeat's 128 entries; PCA, the GMM and the
  Fisher vector do not see a permutation;
* the triangular window has unit peak (``[1..b..1] / b``) an axis; the
  flat window of VLFeat.cxx:98-102 weights a descriptor uniformly, which
  cancels under the normalization, so the scale only sets where the contrast
  threshold 0.005 bites;
* the column sampler draws without replacement, each shape bucket its
  proportional share, from ``numpy.random.default_rng`` (the Scala draws
  ``numSamples / numImages`` columns an image from ``scala.util.Random``);
* EM starts from ``vocab`` sampled rows as means, the global variance and
  equal weights (EncEval.cxx:146-148 with its own generator), stops on a
  relative change of the mean log-likelihood under 1e-4 or after 100
  iterations, and floors variances at 1e-3 of the mean global variance;
* a centre whose weight EM drove to exactly 0 has gradients 0 / 0 by the
  formula; they are zeros (EM's own log of a zero weight keeps such a
  centre without posterior mass for good);
* PCA by SVD of the centred samples with MATLAB's sign rule, projection
  without centring (PCA.scala:35-40, 63-106);
* a fit cannot hold every descriptor either (189 GB at VOC's sizes), so it
  runs SIFT twice over the training images, as the program does: once for
  the sampled columns, once for the Fisher vectors.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import PRECISIONS, mm

_linear = load_module("reference", "linear")

MAGNIF = 6.0
CONTRAST_THRESHOLD = 0.005
ORIENTATIONS = 8
BINS = 4
SIFT_DIM = ORIENTATIONS * BINS * BINS
EM_TOL = 1e-4
EM_MAX_ITER = 100
EM_SEED = 42
EM_VAR_FLOOR = 1e-3
EM_CHUNK = 1 << 17


# -- dense SIFT ------------------------------------------------------------------


def grayscale(images) -> jnp.ndarray:
    """``[n, H, W, 3]`` bytes, channels B, G, R as the loader yields them ->
    ``[n, H, W]`` NTSC grey in [0, 1]."""
    x = jnp.asarray(images).astype(jnp.float32) / 255.0
    return 0.1140 * x[..., 0] + 0.5870 * x[..., 1] + 0.2989 * x[..., 2]


def frame_origins(h: int, w: int, sift: dict, scale: int):
    """Rows and columns of the frames' first bin centres at ``scale``
    (VLFeat.cxx:93-95): from ``(1 + 2 scales) - 3 scale`` in steps while the
    fourth bin centre stays inside the image."""
    b = sift["bin"] + 2 * scale
    step = sift["step"] + scale * sift["scale_step"]
    off = (1 + 2 * sift["scales"]) - 3 * scale
    ys = np.arange(off, h - 1 - (BINS - 1) * b + 1, step)
    xs = np.arange(off, w - 1 - (BINS - 1) * b + 1, step)
    return ys, xs


def num_descriptors(h: int, w: int, sift: dict) -> int:
    total = 0
    for s in range(sift["scales"]):
        ys, xs = frame_origins(h, w, sift, s)
        total += len(ys) * len(xs)
    return total


#: (exponent bits, mantissa bits) of the formats a control rounds to; fp8 is
#: e4m3, whose largest finite value under IEEE's rules is 240
_FORMATS = {"bf16": (8, 7), "fp8": (4, 3)}
_FP8_TOP = 224.0


def stage_rounded(a, precision: str):
    """``a`` as a stage computing in ``precision`` would hand it on: for fp8
    under one scale a tensor, as ``benchmark/lib/precision.rounded`` does it,
    but by ``reduce_precision``, which stays in the compiled program."""
    if precision == "highest":
        return a
    if precision not in _FORMATS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    bits = _FORMATS[precision]
    if precision == "bf16":
        return jax.lax.reduce_precision(a, *bits)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_TOP
    return jax.lax.reduce_precision(a / scale, *bits) * scale


def _window(x, taps: np.ndarray, axis: int, precision: str):
    """``x`` passed through ``taps`` along ``axis``, edges continued: the sum
    of shifted copies, each tap a product in ``precision``."""
    r = (len(taps) - 1) // 2
    pad = [(0, 0)] * x.ndim
    pad[axis] = (r, len(taps) - 1 - r)
    padded = stage_rounded(jnp.pad(x, pad, mode="edge"), precision)
    k = stage_rounded(jnp.asarray(taps, jnp.float32), precision)
    n = x.shape[axis]
    out = jnp.zeros_like(x)
    for t in range(len(taps)):
        out = out + k[t] * jax.lax.slice_in_dim(padded, t, t + n, axis=axis)
    return out


def _gaussian(sigma: float) -> np.ndarray:
    radius = max(1, int(math.ceil(4.0 * sigma)))
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _triangle(b: int) -> np.ndarray:
    return np.concatenate([np.arange(1, b + 1), np.arange(b - 1, 0, -1)]).astype(np.float32) / b


def _gradients(g):
    """Central differences inside, one-sided at the edges, along rows and
    columns of ``[n, H, W]``."""
    gy = jnp.concatenate(
        [g[:, 1:2] - g[:, 0:1], (g[:, 2:] - g[:, :-2]) * 0.5, g[:, -1:] - g[:, -2:-1]], axis=1
    )
    gx = jnp.concatenate(
        [g[:, :, 1:2] - g[:, :, 0:1], (g[:, :, 2:] - g[:, :, :-2]) * 0.5,
         g[:, :, -1:] - g[:, :, -2:-1]], axis=2
    )
    return gy, gx


def _orientation_planes(gy, gx):
    """``[n, 8, H, W]``: the gradient's magnitude shared between the two
    orientations nearest its angle, by distance."""
    mag = jnp.sqrt(gx * gx + gy * gy)
    a = jnp.arctan2(gy, gx) * (ORIENTATIONS / (2.0 * jnp.pi))
    t = jnp.arange(ORIENTATIONS, dtype=jnp.float32)[:, None, None]
    d = jnp.abs(jnp.mod(a[:, None] - t + ORIENTATIONS / 2, ORIENTATIONS) - ORIENTATIONS / 2)
    return mag[:, None] * jnp.maximum(0.0, 1.0 - d)


@functools.partial(jax.jit, static_argnames=("sift", "precision"))
def _dense_sift(images, *, sift: tuple, precision: str):
    sift = dict(sift)
    gray = grayscale(images)
    n, h, w = gray.shape
    per_scale = []
    for s in range(sift["scales"]):
        b = sift["bin"] + 2 * s
        ys, xs = frame_origins(h, w, sift, s)
        if len(ys) == 0 or len(xs) == 0:
            continue
        g = _window(_window(gray, _gaussian(b / MAGNIF), 1, precision), _gaussian(b / MAGNIF), 2, precision)
        planes = _orientation_planes(*_gradients(g))
        planes = _window(_window(planes, _triangle(b), 2, precision), _triangle(b), 3, precision)
        centres = np.arange(BINS) * b
        yy = (ys[:, None] + centres[None, :]).ravel()
        xx = (xs[:, None] + centres[None, :]).ravel()
        read = planes[:, :, yy][:, :, :, xx]  # [n, 8, Fy*4, Fx*4]
        read = read.reshape(n, ORIENTATIONS, len(ys), BINS, len(xs), BINS)
        # frames row by row; a descriptor's entries [bin y, bin x, orientation]
        per_scale.append(
            jnp.transpose(read, (0, 2, 4, 3, 5, 1)).reshape(n, len(ys) * len(xs), SIFT_DIM)
        )
    descs = jnp.concatenate(per_scale, axis=1)
    norms = jnp.sqrt(jnp.sum(descs * descs, axis=-1, keepdims=True))
    clamped = jnp.minimum(descs / jnp.maximum(norms, 1e-12), 0.2)
    again = jnp.sqrt(jnp.sum(clamped * clamped, axis=-1, keepdims=True))
    final = jnp.where(norms > CONTRAST_THRESHOLD, clamped / jnp.maximum(again, 1e-12), 0.0)
    return jnp.swapaxes(jnp.minimum(jnp.floor(512.0 * final), 255.0), 1, 2)


def dense_sift(images, sift: dict, precision: str = "highest"):
    """``[n, H, W, 3]`` byte images -> ``[n, 128, descriptors]`` quantized
    descriptors as float32."""
    return _dense_sift(jnp.asarray(images), sift=tuple(sorted(sift.items())), precision=precision)


# -- sampling, PCA, GMM ------------------------------------------------------------


def buckets_of(images: list) -> dict:
    """``{(H, W): ordinals}`` in first-occurrence order."""
    out: dict = {}
    for i, img in enumerate(images):
        out.setdefault(tuple(img.shape[:2]), []).append(i)
    return {s: np.asarray(i) for s, i in out.items()}


def draw_columns(totals: dict, num_samples: int, seed: int) -> dict:
    """The configuration's ``sampling`` recipe: ``totals`` is ``{shape:
    (images, descriptors an image)}``; each bucket's sorted draw from its
    ``images * descriptors`` columns."""
    rng = np.random.default_rng(seed)
    grand = sum(n * c for n, c in totals.values())
    draws = {}
    for shape, (n, c) in totals.items():
        if grand <= num_samples:
            draws[shape] = np.arange(n * c)
        else:
            quota = min(n * c, max(1, int(num_samples * n * c / grand)))
            draws[shape] = np.sort(rng.choice(n * c, quota, replace=False))
    return draws


def pca_fit(samples, dims: int):
    """First ``dims`` right singular vectors of the centred ``[n, d]`` samples,
    each signed so that its largest entry by magnitude is positive."""
    x = jnp.asarray(samples, jnp.float32)
    x = x - jnp.mean(x, axis=0)
    _, _, vt = jnp.linalg.svd(x, full_matrices=x.shape[0] < x.shape[1])
    v = vt.T
    flip = jnp.where(jnp.max(v, axis=0) == jnp.max(jnp.abs(v), axis=0), 1.0, -1.0)
    return (v * flip)[:, :dims]


def _log_joint(x, means, variances, weights, precision):
    """``[n, k]``: log weight + log density of each row under each centre."""
    inv = 1.0 / variances
    quad = (
        mm(x * x, inv, precision)
        - 2.0 * mm(x, means * inv, precision)
        + jnp.sum(means * means * inv, axis=0)
    )
    log_det = jnp.sum(jnp.log(variances), axis=0)
    return -0.5 * (quad + log_det + x.shape[1] * jnp.log(2.0 * jnp.pi)) + jnp.log(weights)


@functools.partial(jax.jit, static_argnames=("precision",))
def _e_sums(x, means, variances, weights, *, precision):
    lj = _log_joint(x, means, variances, weights, precision)
    norm = jax.scipy.special.logsumexp(lj, axis=1, keepdims=True)
    q = jnp.exp(lj - norm)
    return jnp.sum(q, axis=0), mm(x.T, q, precision), mm((x * x).T, q, precision), jnp.sum(norm)


def em_step(x, means, variances, weights, floor, precision: str = "highest"):
    """One EM iteration over ``[n, d]`` rows; returns the new parameters and
    the mean log-likelihood under the old ones."""
    n = x.shape[0]
    s0 = s1 = s2 = llh = 0.0
    for i in range(0, n, EM_CHUNK):
        c0, c1, c2, cl = _e_sums(x[i : i + EM_CHUNK], means, variances, weights, precision=precision)
        s0, s1, s2, llh = s0 + c0, s1 + c1, s2 + c2, llh + cl
    safe = jnp.maximum(s0, 1e-10)
    new_means = s1 / safe
    new_vars = jnp.maximum(s2 / safe - new_means * new_means, floor)
    return new_means, new_vars, s0 / n, llh / n


def em_start(x, k: int):
    """The seeded start and the variance floor."""
    x = jnp.asarray(x, jnp.float32)
    rows = np.random.default_rng(EM_SEED).choice(x.shape[0], k, replace=False)
    spread = jnp.var(x, axis=0)[:, None]
    return (
        x[jnp.asarray(rows)].T,
        jnp.broadcast_to(spread, (x.shape[1], k)),
        jnp.full((k,), 1.0 / k, jnp.float32),
        EM_VAR_FLOOR * jnp.mean(spread),
    )


def em_fit(x, k: int, precision: str = "highest"):
    """``(means [d, k], variances [d, k], weights [k], iterations run)``."""
    x = jnp.asarray(x, jnp.float32)
    means, variances, weights, floor = em_start(x, k)
    llhs: list = []
    iters = 0
    while iters < EM_MAX_ITER:
        # the first comparison is after the second iteration
        if len(llhs) >= 2 and abs(llhs[-1] - llhs[-2]) < EM_TOL * max(1.0, abs(llhs[-1])):
            break
        means, variances, weights, now = em_step(x, means, variances, weights, floor, precision)
        llhs.append(float(now))
        iters += 1
    return means, variances, weights, iters


@functools.partial(jax.jit, static_argnames=("precision",))
def _llh_sum(x, means, variances, weights, *, precision):
    lj = _log_joint(x, means, variances, weights, precision)
    return jnp.sum(jax.scipy.special.logsumexp(lj, axis=1))


def mean_log_likelihood(x, gmm, precision: str = "highest") -> float:
    means, variances, weights = (jnp.asarray(a, jnp.float32) for a in gmm)
    parts = [
        _llh_sum(jnp.asarray(x[i : i + EM_CHUNK]), means, variances, weights, precision=precision)
        for i in range(0, x.shape[0], EM_CHUNK)
    ]
    return float(sum(float(part) for part in parts)) / x.shape[0]


# -- Fisher vectors ----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("precision",))
def fisher_features(descs, pca_mat, means, variances, weights, *, precision="highest"):
    """``[n, 128, cols]`` descriptors -> ``[n, 2 d k]``: projection, the mean
    and variance gradients of the improved Fisher vector (FisherVector.scala
    :14-35, EncEval.cxx:41-97 with alpha 1, pnorm 0), vectorized column by
    column, then L2 -> signed square root -> L2 (VOCSIFTFisher.scala:73-80)."""

    def one(desc):
        x = mm(desc.T, pca_mat, precision)  # [cols, d]
        q = jax.nn.softmax(_log_joint(x, means, variances, weights, precision), axis=-1)
        s0 = jnp.sum(q, axis=0)
        s1 = mm(x.T, q, precision)
        s2 = mm((x * x).T, q, precision)
        n = x.shape[0]
        sigma = jnp.sqrt(variances)
        alive = weights > 0
        w = jnp.where(alive, weights, 1.0)
        g_mean = jnp.where(alive, (s1 - means * s0) / (sigma * jnp.sqrt(w) * n), 0.0)
        g_var = jnp.where(
            alive,
            (s2 - 2.0 * means * s1 + (means * means - variances) * s0)
            / (variances * jnp.sqrt(2.0 * w) * n),
            0.0,
        )
        return jnp.concatenate([g_mean, g_var], axis=1).T.reshape(-1)  # column-major

    def unit(v):
        return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 2.2e-16)

    fv = unit(jax.lax.map(one, descs))
    return unit(jnp.sign(fv) * jnp.sqrt(jnp.abs(fv)))


# -- evaluation --------------------------------------------------------------------


def multi_hot(labels: np.ndarray, classes: int) -> np.ndarray:
    """``[n, max labels]`` class ids padded with -1 -> ``[n, classes]`` 0/1."""
    hot = np.zeros((len(labels), classes), np.float32)
    for i, row in enumerate(np.asarray(labels)):
        hot[i, row[row >= 0]] = 1.0
    return hot


def average_precisions(hot: np.ndarray, scores: np.ndarray) -> np.ndarray:
    """11-point interpolated AP a class (MeanAveragePrecisionEvaluator.scala
    :23-85); a class with no positive reads 0."""
    aps = np.zeros(hot.shape[1])
    for c in range(hot.shape[1]):
        g = hot[np.argsort(-scores[:, c].astype(np.float64), kind="stable"), c].astype(np.float64)
        if g.sum() == 0:
            continue
        tp = np.cumsum(g)
        recall, precision = tp / g.sum(), tp / np.arange(1, len(g) + 1)
        aps[c] = np.mean(
            [precision[recall >= t].max() if (recall >= t).any() else 0.0 for t in np.arange(11) / 10.0]
        )
    return aps


# -- the whole pipeline ------------------------------------------------------------


def _sift_conf(conf: dict) -> dict:
    return {
        "step": conf["sift_step"], "bin": conf["sift_bin"],
        "scales": conf["sift_scales"], "scale_step": conf["scale_step"],
    }


def _chunks(images: list, rows: np.ndarray, chunk: int):
    for i in range(0, len(rows), chunk):
        sel = rows[i : i + chunk]
        yield sel, np.stack([images[j] for j in sel])


def sample_pass(conf: dict, images: list, draws: list, precision: str) -> list:
    """Each draw's ``[samples, 128]`` descriptor rows, bucket by bucket."""
    sift = _sift_conf(conf)
    picks = [[] for _ in draws]
    for shape, rows in buckets_of(images).items():
        cols = num_descriptors(*shape, sift)
        for start in range(0, len(rows), conf["reference_chunk"]):
            block = np.stack([images[j] for j in rows[start : start + conf["reference_chunk"]]])
            descs = dense_sift(block, sift, precision)
            for s, draw in enumerate(draws):
                lo, hi = np.searchsorted(draw[shape], [start * cols, (start + len(block)) * cols])
                im, col = np.divmod(draw[shape][lo:hi] - start * cols, cols)
                picks[s].append(descs[jnp.asarray(im), :, jnp.asarray(col)])
    return [jnp.concatenate(p, axis=0) for p in picks]


def feature_pass(conf: dict, images: list, pca_mat, gmm, precision: str) -> np.ndarray:
    """``[n, 2 d k]`` features in image order, on the host."""
    sift = _sift_conf(conf)
    out = np.zeros((len(images), 2 * conf["desc_dim"] * conf["vocab_size"]), np.float32)
    for _shape, rows in buckets_of(images).items():
        for sel, block in _chunks(images, rows, conf["reference_chunk"]):
            out[sel] = np.asarray(
                fisher_features(dense_sift(block, sift, precision), pca_mat, *gmm, precision=precision)
            )
    return out


def compare_rows(conf: dict, images: list, seed: int) -> np.ndarray:
    """The training images whose descriptors and Fisher vectors are compared:
    drawn from the seed, each shape bucket its share and at least one."""
    rng = np.random.default_rng([seed, 0x51F7])
    want, n = conf["compare"]["images"], len(images)
    rows = []
    for _shape, idx in buckets_of(images).items():
        take = min(len(idx), max(1, round(want * len(idx) / n)))
        rows.append(np.sort(rng.permutation(idx)[:take]))
    return np.concatenate(rows)


def _totals(conf: dict, images: list) -> dict:
    sift = _sift_conf(conf)
    return {
        shape: (len(rows), num_descriptors(*shape, sift))
        for shape, rows in buckets_of(images).items()
    }


def _draws(conf: dict, images: list, sample_seed: int) -> list:
    """The PCA sample's and the GMM sample's draws (``sampling`` in the
    configuration's file)."""
    totals = _totals(conf, images)
    return [
        draw_columns(totals, conf["num_pca_samples"], sample_seed),
        draw_columns(totals, conf["num_gmm_samples"], sample_seed + 1),
    ]


def compared_chunks(conf: dict, images: list, rows: np.ndarray, sample_seed: int):
    """The images ``rows`` in blocks of one shape and at most
    ``reference_chunk``, each with where its sampled descriptors lie: yields
    ``(images' ordinals, [(rows of the sample, image in the block, column)
    for the PCA sample and for the GMM sample])``.  A sample's rows run
    bucket by bucket, a bucket's in the order of its sorted draw."""
    totals = _totals(conf, images)
    draws = _draws(conf, images, sample_seed)
    base = [0] * len(draws)
    for shape, members in buckets_of(images).items():
        cols = totals[shape][1]
        where = np.flatnonzero(np.isin(members, rows))  # the compared images' places in the bucket
        for i in range(0, len(where), conf["reference_chunk"]):
            pos = where[i : i + conf["reference_chunk"]]
            picks = []
            for s, draw in enumerate(draws):
                lo = np.searchsorted(draw[shape], pos * cols)
                hi = np.searchsorted(draw[shape], (pos + 1) * cols)
                at = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)]).astype(np.int64)
                im = np.repeat(np.arange(len(pos)), hi - lo)
                picks.append((base[s] + at, im, draw[shape][at] - pos[im] * cols))
            yield members[pos], picks
        for s, draw in enumerate(draws):
            base[s] += len(draw[shape])


def solve_and_score(conf: dict, train_features, hot_labels, test_features, precision: str):
    """Block coordinate descent on the training features (centred blocks of
    ``solver_block`` columns, +-1 labels), and the test rows' scores."""
    width, d = conf["solver_block"], train_features.shape[1]
    cuts = list(range(0, d, width))
    blocks = [jnp.asarray(train_features[:, c : c + width]) for c in cuts]
    models, mus, intercept = _linear.block_least_squares(
        blocks, jnp.asarray(2.0 * hot_labels - 1.0), conf["lam"], conf["num_epochs"], precision
    )
    del blocks
    scores = intercept
    for c, mu, m in zip(cuts, mus, models):
        scores = scores + mm(jnp.asarray(test_features[:, c : c + width]) - mu, m, precision)
    return np.asarray(scores)


def whole_fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The whole pipeline on its own trajectory, in the shape of what the
    program's ``produced`` hands over."""
    train, test = data["train"]["x"], data["test"]["x"]
    pca_samples, gmm_raw = sample_pass(conf, train, _draws(conf, train, seed), precision)
    pca_mat = pca_fit(pca_samples, conf["desc_dim"])
    gmm_samples = mm(gmm_raw, pca_mat, precision)
    *gmm, iterations = em_fit(gmm_samples, conf["vocab_size"], precision)
    floor = em_start(gmm_samples, conf["vocab_size"])[3]
    stepped = em_step(gmm_samples, *gmm, floor, precision)[:3]
    train_features = feature_pass(conf, train, pca_mat, gmm, precision)
    test_features = feature_pass(conf, test, pca_mat, gmm, precision)
    hot = multi_hot(data["train"]["y"], conf["num_classes"])
    scores = solve_and_score(conf, train_features, hot, test_features, precision)
    aps = average_precisions(multi_hot(data["test"]["y"], conf["num_classes"]), scores)
    return {
        "compare_rows": compare_rows(conf, train, seed),
        "sample_seed": seed,
        "pca_samples": np.asarray(pca_samples),
        "gmm_raw": np.asarray(gmm_raw),
        "pca_mat": np.asarray(pca_mat),
        "gmm_samples": np.asarray(gmm_samples),
        "gmm": tuple(np.asarray(a) for a in gmm),
        "gmm_iterations": iterations,
        "em_step": tuple(np.asarray(a) for a in stepped),
        "train_features": train_features,
        "test_features": test_features,
        "test_scores": scores,
        "aps": aps,
        "map": float(np.mean(aps)),
        "test_error": 100.0 * (1.0 - float(np.mean(aps))),
    }


class _WhenAskedFor(dict):
    """A mapping that is made the first time a key of it is read."""

    def __init__(self, make):
        super().__init__()
        self._make = make

    def __missing__(self, key):
        self.update(self._make())
        return dict.__getitem__(self, key)


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The reference's own whole fit (:func:`whole_fit`), made when first
    read.  The control and the tests put it in the program's place and read
    all of it.  A benchmark run never does: :func:`compare` feeds each stage
    of the reference what the program produced upstream, and the two passes
    of plain SIFT over every image that a trajectory of its own costs (minutes
    at the cell's sizes) would measure nothing."""
    return _WhenAskedFor(lambda: whole_fit(conf, data, seed, precision))


def _rel(a, b) -> float:
    return _linear.rel_gap(a, b)


#: a block's sampled rows are padded to a multiple of this, so that the
#: blocks of a run share one compiled comparison and not one a length
_PICK_PAD = 4096


def _padded(pick: tuple) -> tuple:
    """``(rows of the sample, image, column)`` padded with zeros to a multiple
    of ``_PICK_PAD``, and which entries are real."""
    n = len(pick[0])
    size = max(_PICK_PAD, -(-n // _PICK_PAD) * _PICK_PAD)
    out = [np.zeros(size, np.int32) for _ in pick]
    for dst, src in zip(out, pick):
        dst[:n] = src
    return (*out, np.arange(size) < n)


@jax.jit
def _sampled_gaps(descs, sample, at, im, col, real):
    """Of the block's descriptors ``descs[im, :, col]`` against the sample's
    rows ``at``: entries more than 1 apart, and descriptors that one side
    zeroed under the contrast threshold and the other did not."""
    mine, theirs = descs[im, :, col], sample[at]
    apart = jnp.sum((jnp.abs(mine - theirs) > 1) & real[:, None])
    flipped = jnp.sum((jnp.any(mine != 0, axis=1) != jnp.any(theirs != 0, axis=1)) & real)
    return apart, flipped


def compare(conf: dict, data: dict, seed: int, produced: dict, ref: dict) -> dict:
    """Each stage of the reference fed what ``produced`` holds upstream of it,
    so that no number rests on two EM trajectories staying together.  SIFT is
    the exception that has nothing upstream but the images: the reference's
    descriptors of the compared images are set against the rows that the
    program's sampling pass drew from them (``pca_samples``, ``gmm_raw``: what
    the timed chunk program returned), and the reference's Fisher vectors of
    its own descriptors against the rows of the program's featurizing pass,
    so ``fv_gap`` spans the program's SIFT too.  ``ref`` (the reference's own
    whole fit) is read only where it has been made."""
    p = "highest"
    train = data["train"]["x"]
    sift = _sift_conf(conf)

    pca_mat = jnp.asarray(produced["pca_mat"])
    mine = pca_fit(produced["pca_samples"], conf["desc_dim"])
    # sines of the angles between the two subspaces, root mean square
    outside = pca_mat - mm(mine, mm(mine.T, pca_mat, p), p)
    subspace = float(jnp.linalg.norm(outside)) / math.sqrt(conf["desc_dim"])

    gmm = tuple(jnp.asarray(a) for a in produced["gmm"])
    x = jnp.asarray(produced["gmm_samples"])
    *own, own_iterations = em_fit(x, conf["vocab_size"], p)
    llh_own = mean_log_likelihood(x, own, p)
    llh_theirs = mean_log_likelihood(x, gmm, p)
    floor = em_start(x, conf["vocab_size"])[3]
    stepped = em_step(x, *gmm, floor, p)[:3]
    step_gap = max(_rel(a, b) for a, b in zip(produced["em_step"], stepped))
    del x

    off = flips = jnp.zeros((), jnp.int32)
    entries = images = 0
    sampled = [jnp.asarray(produced["pca_samples"]), jnp.asarray(produced["gmm_raw"])]
    features = produced["train_features"]
    fv_mine, fv_theirs = [], []
    chunks = compared_chunks(conf, train, produced["compare_rows"], produced["sample_seed"])
    for sel, picks in chunks:
        descs = dense_sift(np.stack([train[j] for j in sel]), sift, p)
        for pick, theirs in zip(picks, sampled):
            more = _sampled_gaps(descs, theirs, *_padded(pick))
            off, flips = off + more[0], flips + more[1]
            entries += SIFT_DIM * len(pick[0])
        images += len(sel)
        fv_mine.append(fisher_features(descs, pca_mat, *gmm, precision=p))
        fv_theirs.append(features[sel])
    fv_gap = _rel(np.asarray(jnp.concatenate(fv_theirs)), np.asarray(jnp.concatenate(fv_mine)))
    off, flips = int(off), int(flips)

    hot = multi_hot(data["train"]["y"], conf["num_classes"])
    scores = solve_and_score(conf, features, hot, produced["test_features"], p)
    diff = np.asarray(produced["test_scores"]).astype(np.float64) - scores
    rms = float(np.sqrt(np.mean(scores.astype(np.float64) ** 2)))
    aps = average_precisions(multi_hot(data["test"]["y"], conf["num_classes"]), scores)
    return {
        "sift_off_share": off / max(entries, 1),
        "pca_subspace_gap": subspace,
        "gmm_llh_gap": abs(llh_theirs - llh_own) / max(abs(llh_own), 1e-30),
        "em_step_gap": step_gap,
        "fv_gap": fv_gap,
        "scores_rms_gap": float(np.sqrt(np.mean(diff**2))) / rms,
        "scores_max_gap": float(np.max(np.abs(diff))) / rms,
        "map_gap": abs(float(produced["map"]) - float(np.mean(aps))),
        # observed, no limit
        "sift_images": images,
        "sift_entries": entries,
        "sift_zeroing_flips": flips,
        "gmm_llh": llh_theirs,
        "gmm_llh_reference": llh_own,
        "gmm_iterations": int(produced["gmm_iterations"]),
        "gmm_iterations_reference": int(own_iterations),
        "map": float(produced["map"]),
        "map_reference_on_program_features": float(np.mean(aps)),
        "map_reference_alone": ref.get("map"),
    }


def control(conf: dict, data: dict, produced: dict, precision: str) -> dict:
    """The control, stage by stage: ``produced`` with each stage's output
    replaced by what the reference computing in ``precision`` makes of the
    same upstream, so that :func:`compare` reads how far a lower precision
    moves each number at the cell's own sizes.  (The reference's whole fit in
    fp8, two passes of plain SIFT over every image, did not end in half an
    hour on the chip.)  SIFT and the Fisher vectors are replaced on the
    compared images only: their sampled rows and their feature rows.  PCA has
    no product that a precision rounds, so its matrix stays."""
    train = data["train"]["x"]
    sift = _sift_conf(conf)
    out = dict(produced)
    x = jnp.asarray(produced["gmm_samples"])
    *gmm, iterations = em_fit(x, conf["vocab_size"], precision)
    floor = em_start(x, conf["vocab_size"])[3]
    out["gmm"], out["gmm_iterations"] = tuple(gmm), iterations
    out["em_step"] = em_step(x, *gmm, floor, precision)[:3]
    del x

    pca_mat = jnp.asarray(produced["pca_mat"])
    placed = [([], []), ([], [])]  # a sample's (rows, descriptors)
    at_rows, fv_rows = [], []
    chunks = compared_chunks(conf, train, produced["compare_rows"], produced["sample_seed"])
    for sel, picks in chunks:
        descs = dense_sift(np.stack([train[j] for j in sel]), sift, precision)
        for (at, im, col), (rows, values) in zip(picks, placed):
            rows.append(at)
            values.append(descs[jnp.asarray(im), :, jnp.asarray(col)])
        at_rows.append(sel)
        fv_rows.append(fisher_features(descs, pca_mat, *gmm, precision=precision))
    for name, (rows, values) in zip(("pca_samples", "gmm_raw"), placed):
        out[name] = (
            jnp.asarray(produced[name])
            .at[jnp.asarray(np.concatenate(rows))]
            .set(jnp.concatenate(values))
        )
    features = (
        jnp.asarray(produced["train_features"])
        .at[jnp.asarray(np.concatenate(at_rows))]
        .set(jnp.concatenate(fv_rows))
    )
    hot = multi_hot(data["train"]["y"], conf["num_classes"])
    scores = solve_and_score(conf, features, hot, produced["test_features"], precision)
    aps = average_precisions(multi_hot(data["test"]["y"], conf["num_classes"]), scores)
    out.update(
        train_features=features, test_scores=scores, aps=aps, map=float(np.mean(aps)),
        test_error=100.0 * (1.0 - float(np.mean(aps))),
    )
    return out
