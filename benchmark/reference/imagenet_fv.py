"""Plain reference of ImageNetSiftLcsFV (reference
src/main/scala/pipelines/images/imagenet/ImageNetSiftLcsFV.scala:25-268): two
descriptor branches (dense SIFT behind the signed square root; Local Colour
Statistics), each sampled -> PCA -> GMM by EM -> Fisher vectors and their
normalizations, the branches' rows side by side, the class-weighted block
least squares of BlockWeightedLeastSquares.scala:35-362, top-5 error.

``jax.numpy`` in float32 with full-precision products (or, for a control,
rounded lower: ``benchmark/lib/precision`` for the products, and
``reduce_precision`` on the stages' outputs where a stage has no product, as
``reference/voc_fv.py`` does it).  Nothing is imported from ``keystone_tpu``.
Dense SIFT, the column sampler, PCA, EM and the Fisher vector are
``reference/voc_fv.py``'s, loaded from that file; its list of departures
holds here too.  What this file adds:

* **LCS from the definition** (LCSExtractor.scala:25-130): a keypoint grid
  ``border until dim - border by stride`` along x and y, keypoints x-major; at
  each keypoint a 4 x 4 neighbourhood of offsets ``-2 s + s/2 - 1 .. s + s/2 -
  1 by s`` (``s`` the sub-patch); at each of those places the mean and the
  deviation of the ``s x s`` window that the Scala's zero-padded box
  convolution puts there (rows and columns ``place - (s - 1) / 2 ..`` for
  ``s``), the deviation ``sqrt(max(E[x^2] - E[x]^2, 0))`` as the Scala has it;
  a descriptor's entries channel-major, then x-offset, then y-offset, (mean,
  deviation) interleaved.  Written as direct sums over each window's pixels,
  read by index: no convolution, no running sum.
* **the weighted solve, class by class** from the Scala's equations: see
  :func:`weighted_least_squares`.
* the LCS branch's descriptors are centred on the mean of the branch's PCA
  sample before they are projected, a departure shared with the program: PCA
  is fitted on centred samples and projects without centring (PCA.scala:35-40),
  and a Fisher vector does not see a translation of its descriptors and its
  mixture together, so the features are the Scala's; the mixture's means are
  the Scala's less the projected centre.
* the EM sample is capped at 1e6 rows (ImageNetSiftLcsFV.scala:85-86); the
  configuration's ``sampling`` recipe draws the capped count outright.

Each stage of :func:`compare` is fed what the timed fit produced upstream, so
that no number rests on two EM trajectories staying together.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np

from benchmark.lib.manifest import load_module
from benchmark.lib.precision import mm

_voc = load_module("reference", "voc_fv")
_linear = load_module("reference", "linear")

BRANCHES = ("sift", "lcs")
GMM_FIT_CAP = 1_000_000
#: the LCS sample's seeds lie this far behind SIFT's (the configuration's ``sampling``)
LCS_SEED_OFFSET = 100


def _sift_conf(conf: dict) -> dict:
    return {
        "step": conf["sift_step"], "bin": conf["sift_bin"],
        "scales": conf["sift_scales"], "scale_step": conf["sift_scale_step"],
    }


def signed_sqrt(x):
    return jnp.sign(x) * jnp.sqrt(jnp.abs(x))


# -- Local Colour Statistics, from the definition ------------------------------------


def lcs_grid(length: int, conf: dict) -> np.ndarray:
    return np.arange(conf["lcs_border"], length - conf["lcs_border"], conf["lcs_stride"])


def lcs_keypoints(h: int, w: int, conf: dict) -> int:
    return len(lcs_grid(h, conf)) * len(lcs_grid(w, conf))


def lcs_offsets(conf: dict) -> np.ndarray:
    s = conf["lcs_patch"]
    return np.arange(-2 * s + s // 2 - 1, s + s // 2 - 1 + 1, s)


@functools.partial(jax.jit, static_argnames=("lcs", "precision"))
def _lcs(images, *, lcs: tuple, precision: str):
    conf = dict(lcs)
    s = conf["lcs_patch"]
    x = jnp.asarray(images).astype(jnp.float32)  # [n, H, W, C], levels 0..255
    n, h, w, c = x.shape
    low = (s - 1) // 2
    x = _voc.stage_rounded(x, precision)
    # zeros around the image, as the Scala's convolution pads it
    padded = jnp.pad(x, ((0, 0), (low, s - 1 - low), (low, s - 1 - low), (0, 0)))
    ys, xs, off = lcs_grid(h, conf), lcs_grid(w, conf), lcs_offsets(conf)
    # the pixels of every window: place + offset + 0 .. s - 1 in padded
    # coordinates (a window starts (s - 1) / 2 before its place)
    rows = (ys[:, None, None] + off[None, :, None] + np.arange(s)[None, None, :]).reshape(-1)
    cols = (xs[:, None, None] + off[None, :, None] + np.arange(s)[None, None, :]).reshape(-1)
    win = padded[:, rows][:, :, cols]  # [n, Ky*4*s, Kx*4*s, C]
    win = win.reshape(n, len(ys), off.size, s, len(xs), off.size, s, c)
    mean = jnp.sum(win, axis=(3, 6)) / (s * s)  # [n, Ky, ny, Kx, nx, C]
    square = jnp.sum(_voc.stage_rounded(win * win, precision), axis=(3, 6)) / (s * s)
    dev = jnp.sqrt(jnp.maximum(square - mean * mean, 0.0))
    both = jnp.stack([mean, dev], axis=-1)  # [n, Ky, ny, Kx, nx, C, 2]
    # keypoints x-major; entries channel, x-offset, y-offset, (mean, deviation)
    both = jnp.transpose(both, (0, 3, 1, 5, 4, 2, 6))  # [n, Kx, Ky, C, nx, ny, 2]
    out = both.reshape(n, len(xs) * len(ys), c * off.size * off.size * 2)
    return _voc.stage_rounded(jnp.swapaxes(out, 1, 2), precision)


def lcs(images, conf: dict, precision: str = "highest"):
    """``[n, H, W, C]`` images of levels 0..255 -> ``[n, 32 C, keypoints]``."""
    keys = ("lcs_stride", "lcs_border", "lcs_patch")
    return _lcs(jnp.asarray(images), lcs=tuple((k, conf[k]) for k in keys), precision=precision)


# -- the branches ----------------------------------------------------------------------


def describe(branch: str, images, conf: dict, precision: str = "highest"):
    """A block of byte images -> the branch's raw descriptors ``[n, dim, cols]``
    as they are sampled: SIFT's quantized entries, LCS's statistics."""
    if branch == "sift":
        return _voc.dense_sift(images, _sift_conf(conf), precision)
    return lcs(images, conf, precision)


def prepared(branch: str, descs, centre=None):
    """Descriptors (``[n, dim, cols]``) or sampled rows (``[n, dim]``) as the
    branch's PCA, EM and Fisher vector see them: SIFT's behind the signed
    square root; LCS's less ``centre``, the mean of the branch's PCA sample."""
    if branch == "sift":
        return signed_sqrt(descs)
    if centre is None:
        return descs
    centre = jnp.asarray(centre, jnp.float32)
    return descs - (centre if descs.ndim == 2 else centre[:, None])


def columns(branch: str, h: int, w: int, conf: dict) -> int:
    if branch == "sift":
        return _voc.num_descriptors(h, w, _sift_conf(conf))
    return lcs_keypoints(h, w, conf)


def totals(branch: str, conf: dict, images: list) -> dict:
    return {
        shape: (len(rows), columns(branch, *shape, conf))
        for shape, rows in _voc.buckets_of(images).items()
    }


def draws(branch: str, conf: dict, images: list, sample_seed: int) -> list:
    """The branch's PCA draw and GMM draw (``sampling`` in the configuration)."""
    seed = sample_seed + (LCS_SEED_OFFSET if branch == "lcs" else 0)
    t = totals(branch, conf, images)
    return [
        _voc.draw_columns(t, conf["num_pca_samples"], seed),
        _voc.draw_columns(t, min(conf["num_gmm_samples"], GMM_FIT_CAP), seed + 1),
    ]


def compared_chunks(branch: str, conf: dict, images: list, rows: np.ndarray, sample_seed: int):
    """``reference/voc_fv.compared_chunks`` for a branch: the images ``rows``
    in blocks of one shape and at most ``reference_chunk``, each with where
    its sampled descriptors lie: ``(images' ordinals, [(rows of the sample,
    image in the block, column) for the PCA and the GMM sample])``."""
    t = totals(branch, conf, images)
    drawn = draws(branch, conf, images, sample_seed)
    base = [0] * len(drawn)
    for shape, members in _voc.buckets_of(images).items():
        cols = t[shape][1]
        where = np.flatnonzero(np.isin(members, rows))
        for i in range(0, len(where), conf["reference_chunk"]):
            pos = where[i : i + conf["reference_chunk"]]
            picks = []
            for s, draw in enumerate(drawn):
                lo = np.searchsorted(draw[shape], pos * cols)
                hi = np.searchsorted(draw[shape], (pos + 1) * cols)
                at = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)]).astype(np.int64)
                im = np.repeat(np.arange(len(pos)), hi - lo)
                picks.append((base[s] + at, im, draw[shape][at] - pos[im] * cols))
            yield members[pos], picks
        for s, draw in enumerate(drawn):
            base[s] += len(draw[shape])


def sample_pass(conf: dict, images: list, precision: str, sample_seed: int) -> dict:
    """``{branch: [PCA sample, GMM sample]}`` of raw descriptor rows."""
    out = {}
    for branch in BRANCHES:
        drawn = draws(branch, conf, images, sample_seed)
        picks = [[] for _ in drawn]
        for shape, rows in _voc.buckets_of(images).items():
            cols = columns(branch, *shape, conf)
            for start in range(0, len(rows), conf["reference_chunk"]):
                block = np.stack([images[j] for j in rows[start : start + conf["reference_chunk"]]])
                descs = describe(branch, block, conf, precision)
                for s, draw in enumerate(drawn):
                    lo, hi = np.searchsorted(draw[shape], [start * cols, (start + len(block)) * cols])
                    im, col = np.divmod(draw[shape][lo:hi] - start * cols, cols)
                    picks[s].append(descs[jnp.asarray(im), :, jnp.asarray(col)])
        out[branch] = [jnp.concatenate(p, axis=0) for p in picks]
    return out


def branch_features(branch: str, descs, fitted: dict, precision: str):
    """A block's raw descriptors -> the branch's ``[n, 2 d k]`` rows."""
    return _voc.fisher_features(
        prepared(branch, descs, fitted.get("centre")), jnp.asarray(fitted["pca_mat"]),
        *(jnp.asarray(a) for a in fitted["gmm"]), precision=precision,
    )


def feature_pass(conf: dict, images: list, fitted: dict, precision: str) -> np.ndarray:
    """``[n, 2 * 2 d k]`` rows in image order, SIFT's half first, on the host."""
    half = 2 * conf["desc_dim"] * conf["vocab_size"]
    out = np.zeros((len(images), 2 * half), np.float32)
    for _shape, rows in _voc.buckets_of(images).items():
        for sel, block in _voc._chunks(images, rows, conf["reference_chunk"]):
            for b, branch in enumerate(BRANCHES):
                out[sel, b * half : (b + 1) * half] = np.asarray(
                    branch_features(branch, describe(branch, block, conf, precision), fitted[branch], precision)
                )
    return out


def fit_dictionary(branch: str, conf: dict, pca_samples, gmm_raw, precision: str) -> dict:
    """PCA on the branch's prepared PCA sample, EM on its projected GMM
    sample, and one more EM step from the fitted mixture."""
    centre = None if branch == "sift" else np.asarray(jnp.mean(jnp.asarray(pca_samples), axis=0))
    pca_mat = _voc.pca_fit(prepared(branch, jnp.asarray(pca_samples), centre), conf["desc_dim"])
    gmm_samples = mm(prepared(branch, jnp.asarray(gmm_raw), centre), pca_mat, precision)
    *gmm, iterations = _voc.em_fit(gmm_samples, conf["vocab_size"], precision)
    floor = _voc.em_start(gmm_samples, conf["vocab_size"])[3]
    stepped = _voc.em_step(gmm_samples, *gmm, floor, precision)[:3]
    return {
        "pca_samples": pca_samples, "gmm_raw": gmm_raw, "pca_mat": np.asarray(pca_mat), "centre": centre,
        "gmm_samples": gmm_samples, "gmm": tuple(np.asarray(a) for a in gmm),
        "gmm_iterations": iterations, "em_step": tuple(np.asarray(a) for a in stepped),
    }


# -- the class-weighted solve ------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("precision",))
def _population(x, residual, *, precision):
    n = x.shape[0]
    mean = jnp.sum(x, axis=0) / n
    return mean, mm(x.T, x, precision) / n - jnp.outer(mean, mean), mm(x.T, residual, precision) / n


@functools.partial(jax.jit, static_argnames=("precision",))
def _one_class(x, residual_c, rows, real, pop_mean, pop_cov, pop_xtr_c, res_mean_c, w_c, lam, w, *, precision):
    """One class's system (BlockWeightedLeastSquares.scala:228-263).  ``rows``:
    the class's row numbers padded to the largest class, ``real`` which of
    them count.  Returns ``(delta W_c, joint mean)``."""
    xc = x[rows] * real[:, None]
    rc = residual_c[rows] * real
    n_c = jnp.sum(real)
    class_mean = jnp.sum(xc, axis=0) / n_c
    centred = (xc - class_mean) * real[:, None]
    class_cov = mm(centred.T, centred, precision) / n_c
    class_xtr = mm(xc.T, rc[:, None], precision)[:, 0] / n_c
    diff = class_mean - pop_mean
    joint_xtx = (1.0 - w) * pop_cov + w * class_cov + w * (1.0 - w) * jnp.outer(diff, diff)
    joint_mean = w * class_mean + (1.0 - w) * pop_mean
    mean_mixture = (1.0 - w) * res_mean_c + w * jnp.sum(rc) / n_c
    joint_xtr = (1.0 - w) * pop_xtr_c + w * class_xtr - joint_mean * mean_mixture
    factor = jsl.cho_factor(joint_xtx + lam * jnp.eye(x.shape[1], dtype=x.dtype), lower=True)
    return jsl.cho_solve(factor, joint_xtr - lam * w_c), joint_mean


def _residual_mean(residual, labels: np.ndarray, classes: int):
    """The class means of the residual's columns, averaged over the classes
    with equal weight (:165-167, :283-287)."""
    sums = jax.ops.segment_sum(residual, jnp.asarray(labels), num_segments=classes)
    return jnp.mean(sums / jnp.asarray(np.bincount(labels, minlength=classes), jnp.float32)[:, None], axis=0)


def weighted_least_squares(
    features, labels: np.ndarray, classes: int, lam: float, w: float, block: int, epochs: int,
    precision: str = "highest",
):
    """BlockWeightedLeastSquares.scala:35-362 on ``[n, d]`` features and
    ``[n]`` class ids, a class at a time.  With +-1 indicator labels:
    ``jointLabelMean_c = 2 w + 2 (1 - w) n_c / n - 1``; the residual starts as
    labels less that; a block and pass: population mean, covariance and
    ``X^T R / n`` over all rows; a class its mean, covariance and ``X_c^T r_c /
    n_c`` over its own rows and its own residual column; ``joint X^T X = (1 -
    w) pop + w class + w (1 - w) dmu dmu^T``; ``joint X^T R = (1 - w) pop + w
    class - jointMean ((1 - w) residualMean_c + w mean(r_c))``; ``(joint X^T X
    + lam I) dW_c = joint X^T R - lam W_c`` by a float32 Cholesky; then ``W +=
    dW``, ``R -= X dW``; at the end ``b_c = jointLabelMean_c - sum over blocks
    of jointMean_c . W_c``.  Returns ``(W [d, classes], b [classes])``."""
    x = jnp.asarray(features, jnp.float32)
    labels = np.asarray(labels)
    n, d = x.shape
    counts = np.bincount(labels, minlength=classes)
    joint_label_mean = jnp.asarray(2.0 * w + 2.0 * (1.0 - w) * counts / n - 1.0, jnp.float32)
    residual = (2.0 * jnp.eye(classes, dtype=jnp.float32)[jnp.asarray(labels)] - 1.0) - joint_label_mean
    n_max = int(counts.max())
    members = np.zeros((classes, n_max), np.int32)
    real = np.zeros((classes, n_max), np.float32)
    for c in range(classes):
        idx = np.flatnonzero(labels == c)
        members[c, : len(idx)], real[c, : len(idx)] = idx, 1.0
    cuts = list(range(0, d, block))
    weights = [jnp.zeros((min(block, d - c0), classes), jnp.float32) for c0 in cuts]
    joint_means = [None] * len(cuts)
    lam32, w32 = jnp.float32(lam), jnp.float32(w)
    for _ in range(epochs):
        for i, c0 in enumerate(cuts):
            xb = x[:, c0 : c0 + block]
            pop_mean, pop_cov, pop_xtr = _population(xb, residual, precision=precision)
            res_mean = _residual_mean(residual, labels, classes)
            deltas, means = [], []
            for c in range(classes):
                dw, jm = _one_class(
                    xb, residual[:, c], jnp.asarray(members[c]), jnp.asarray(real[c]),
                    pop_mean, pop_cov, pop_xtr[:, c], res_mean[c], weights[i][:, c],
                    lam32, w32, precision=precision,
                )
                deltas.append(dw)
                means.append(jm)
            delta = jnp.stack(deltas, axis=1)
            joint_means[i] = jnp.stack(means, axis=0)  # [classes, width]
            weights[i] = weights[i] + delta
            residual = residual - mm(xb, delta, precision)
    intercept = joint_label_mean - sum(
        jnp.sum(jm * wt.T, axis=1) for jm, wt in zip(joint_means, weights)
    )
    return jnp.concatenate(weights, axis=0), intercept


def solve_and_score(conf: dict, train_features, labels, test_features, precision: str):
    """``(W, b, the test rows' scores)`` on the host."""
    weights, intercept = weighted_least_squares(
        train_features, labels, conf["num_classes"], conf["lam"], conf["mixture_weight"],
        conf["solver_block"], conf["num_epochs"], precision,
    )
    scores = mm(jnp.asarray(test_features, jnp.float32), weights, precision) + intercept
    return np.asarray(weights), np.asarray(intercept), np.asarray(scores)


def top_k_error(scores: np.ndarray, labels: np.ndarray, k: int) -> float:
    """Share of rows whose label is not among the ``k`` largest scores."""
    k = min(k, scores.shape[1])
    top = np.argsort(-scores.astype(np.float64), axis=1, kind="stable")[:, :k]
    return float(np.mean(~np.any(top == np.asarray(labels)[:, None], axis=1)))


# -- the whole pipeline ------------------------------------------------------------------


def whole_fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The whole pipeline on its own trajectory, in the shape of what the
    program's ``produced`` hands over."""
    train, test = data["train"]["x"], data["test"]["x"]
    sampled = sample_pass(conf, train, precision, seed)
    fitted = {
        branch: fit_dictionary(branch, conf, *sampled[branch], precision) for branch in BRANCHES
    }
    train_features = feature_pass(conf, train, fitted, precision)
    test_features = feature_pass(conf, test, fitted, precision)
    weights, intercept, scores = solve_and_score(
        conf, train_features, data["train"]["y"], test_features, precision
    )
    top5 = top_k_error(scores, data["test"]["y"], 5)
    return {
        "compare_rows": _voc.compare_rows(conf, train, seed),
        "sample_seed": seed,
        "branches": fitted,
        "train_features": train_features,
        "test_features": test_features,
        "weights": weights,
        "intercept": intercept,
        "test_scores": scores,
        "top5_error": top5,
        "test_error": 100.0 * top5,
    }


def fit(conf: dict, data: dict, seed: int, precision: str = "highest") -> dict:
    """The reference's own whole fit, made when first read (the control and
    the tests read it; a benchmark run never does: see ``reference/voc_fv.fit``)."""
    return _voc._WhenAskedFor(lambda: whole_fit(conf, data, seed, precision))


def _rel(a, b) -> float:
    return _linear.rel_gap(a, b)


@jax.jit
def _sampled_rel(descs, sample, at, im, col, real):
    """Squared distance and squared norm of the block's descriptors
    ``descs[im, :, col]`` against the sample's rows ``at``."""
    mine, theirs = descs[im, :, col], sample[at]
    keep = real[:, None]
    return jnp.sum(jnp.where(keep, (mine - theirs) ** 2, 0.0)), jnp.sum(jnp.where(keep, mine**2, 0.0))


def compare(conf: dict, data: dict, seed: int, produced: dict, ref: dict) -> dict:
    """Each stage of the reference fed what ``produced`` holds upstream of it.
    The descriptor nodes have only the images upstream: the reference's SIFT
    and LCS of the compared images are set against the rows that the program's
    sampling pass drew from them, and the reference's Fisher vectors *of its
    own descriptors* against the rows of the program's featurizing pass, so a
    branch's ``fv_gap`` spans the program's descriptor node too."""
    p = "highest"
    train = data["train"]["x"]
    half = 2 * conf["desc_dim"] * conf["vocab_size"]
    features = produced["train_features"]
    out = {}
    for b, branch in enumerate(BRANCHES):
        got = produced["branches"][branch]
        pca_mat = jnp.asarray(got["pca_mat"])
        mine = _voc.pca_fit(
            prepared(branch, jnp.asarray(got["pca_samples"]), got.get("centre")), conf["desc_dim"]
        )
        outside = pca_mat - mm(mine, mm(mine.T, pca_mat, p), p)
        out[f"{branch}_pca_subspace_gap"] = float(jnp.linalg.norm(outside)) / math.sqrt(conf["desc_dim"])

        gmm = tuple(jnp.asarray(a) for a in got["gmm"])
        x = jnp.asarray(got["gmm_samples"])
        *own, own_iterations = _voc.em_fit(x, conf["vocab_size"], p)
        llh_own = _voc.mean_log_likelihood(x, own, p)
        llh_theirs = _voc.mean_log_likelihood(x, gmm, p)
        floor = _voc.em_start(x, conf["vocab_size"])[3]
        stepped = _voc.em_step(x, *gmm, floor, p)[:3]
        out[f"{branch}_gmm_llh_gap"] = abs(llh_theirs - llh_own) / max(abs(llh_own), 1e-30)
        out[f"{branch}_em_step_gap"] = max(_rel(a, s) for a, s in zip(got["em_step"], stepped))
        del x

        off = flips = jnp.zeros((), jnp.int32)
        gap2 = norm2 = jnp.zeros((), jnp.float32)
        entries = images = 0
        sampled = [jnp.asarray(got["pca_samples"]), jnp.asarray(got["gmm_raw"])]
        fv_mine, fv_theirs = [], []
        for sel, picks in compared_chunks(branch, conf, train, produced["compare_rows"], produced["sample_seed"]):
            descs = describe(branch, np.stack([train[j] for j in sel]), conf, p)
            for pick, theirs in zip(picks, sampled):
                padded = _voc._padded(pick)
                if branch == "sift":
                    more = _voc._sampled_gaps(descs, theirs, *padded)
                    off, flips = off + more[0], flips + more[1]
                else:
                    more = _sampled_rel(descs, theirs, *padded)
                    gap2, norm2 = gap2 + more[0], norm2 + more[1]
                entries += descs.shape[1] * len(pick[0])
            images += len(sel)
            fv_mine.append(branch_features(branch, descs, got, p))
            fv_theirs.append(features[sel, b * half : (b + 1) * half])
        if branch == "sift":
            out["sift_off_share"] = int(off) / max(entries, 1)
            out["sift_zeroing_flips"] = int(flips)
        else:
            out["lcs_gap"] = math.sqrt(float(gap2) / max(float(norm2), 1e-30))
        out[f"{branch}_fv_gap"] = _rel(
            np.asarray(jnp.concatenate(fv_theirs)), np.asarray(jnp.concatenate(fv_mine))
        )
        out.update({
            f"{branch}_images": images, f"{branch}_entries": entries,
            f"{branch}_gmm_llh": llh_theirs, f"{branch}_gmm_llh_reference": llh_own,
            f"{branch}_gmm_iterations": int(got["gmm_iterations"]),
            f"{branch}_gmm_iterations_reference": int(own_iterations),
        })

    weights, intercept, scores = solve_and_score(
        conf, features, data["train"]["y"], produced["test_features"], p
    )
    theirs = np.concatenate([np.asarray(produced["weights"]), np.asarray(produced["intercept"])[None]])
    diff = np.asarray(produced["test_scores"]).astype(np.float64) - scores
    rms = float(np.sqrt(np.mean(scores.astype(np.float64) ** 2)))
    top5 = top_k_error(scores, data["test"]["y"], 5)
    out.update({
        "model_gap": _rel(theirs, np.concatenate([weights, intercept[None]])),
        "scores_rms_gap": float(np.sqrt(np.mean(diff**2))) / rms,
        "scores_max_gap": float(np.max(np.abs(diff))) / rms,
        "top5_gap": abs(float(produced["top5_error"]) - top5),
        # observed, no limit
        "top5_error": float(produced["top5_error"]),
        "top5_error_reference_on_program_features": top5,
        "top1_error_reference_on_program_features": top_k_error(scores, data["test"]["y"], 1),
        "top5_error_reference_alone": ref.get("top5_error"),
    })
    return out


def control(conf: dict, data: dict, produced: dict, precision: str) -> dict:
    """The control, stage by stage (see ``reference/voc_fv.control``):
    ``produced`` with each stage's output replaced by what the reference
    computing in ``precision`` makes of the same upstream.  The descriptor
    nodes and the Fisher vectors are replaced on the compared images only:
    their sampled rows and their feature rows.  PCA has no product that a
    precision rounds, so its matrix stays."""
    train = data["train"]["x"]
    half = 2 * conf["desc_dim"] * conf["vocab_size"]
    out = dict(produced)
    out["branches"] = {}
    features = jnp.asarray(produced["train_features"])
    for b, branch in enumerate(BRANCHES):
        got = dict(produced["branches"][branch])
        x = jnp.asarray(got["gmm_samples"])
        *gmm, iterations = _voc.em_fit(x, conf["vocab_size"], precision)
        floor = _voc.em_start(x, conf["vocab_size"])[3]
        got["gmm"], got["gmm_iterations"] = tuple(gmm), iterations
        got["em_step"] = _voc.em_step(x, *gmm, floor, precision)[:3]
        del x
        placed = [([], []), ([], [])]
        at_rows, fv_rows = [], []
        for sel, picks in compared_chunks(branch, conf, train, produced["compare_rows"], produced["sample_seed"]):
            descs = describe(branch, np.stack([train[j] for j in sel]), conf, precision)
            for (at, im, col), (rows, values) in zip(picks, placed):
                rows.append(at)
                values.append(descs[jnp.asarray(im), :, jnp.asarray(col)])
            at_rows.append(sel)
            fv_rows.append(branch_features(branch, descs, got, precision))
        for name, (rows, values) in zip(("pca_samples", "gmm_raw"), placed):
            got[name] = (
                jnp.asarray(got[name]).at[jnp.asarray(np.concatenate(rows))].set(jnp.concatenate(values))
            )
        features = features.at[
            jnp.asarray(np.concatenate(at_rows))[:, None], jnp.arange(b * half, (b + 1) * half)[None, :]
        ].set(jnp.concatenate(fv_rows))
        out["branches"][branch] = got
    weights, intercept, scores = solve_and_score(
        conf, features, data["train"]["y"], produced["test_features"], precision
    )
    top5 = top_k_error(scores, data["test"]["y"], 5)
    out.update(
        train_features=features, weights=weights, intercept=intercept, test_scores=scores,
        top5_error=top5, test_error=100.0 * top5,
    )
    return out
