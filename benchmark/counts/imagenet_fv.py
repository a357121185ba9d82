"""Operations and bytes that ImageNetSiftLcsFV's mathematics needs, from the
cell's shapes and, for EM alone, the iterations the program says it ran.

The work, not the implementation, as ``counts/voc_fv.py`` has it (dense SIFT
by its separable window, every image through each descriptor node once a
fit, EM a sample and iteration); SIFT's count is that file's.  LCS is counted
by its box sums: a pixel and channel its square and a separable window of
``lcs_patch`` taps for the levels and for the squares, a sampled place its
deviation.  The weighted solve is one population gram and ``X^T R``, a class
its covariance over its own rows, its system put together, one Cholesky at
``d^3 / 3`` and two triangular solves, at the block's full width whatever the
rows a class has.  So no share passes 100% and none moves when the program
does.
"""

from __future__ import annotations

from benchmark.lib.manifest import load_module

_voc = load_module("counts", "voc_fv")
block_widths, predict = _voc.block_widths, _voc.predict

BRANCHES = ("sift", "lcs")
SIFT_DIM = _voc.SIFT_DIM
GMM_FIT_CAP = 1_000_000
CHANNELS = 3


def _sift_conf(conf: dict) -> dict:
    return dict(conf, scale_step=conf["sift_scale_step"])


def lcs_dim() -> int:
    return 2 * 16 * CHANNELS


def lcs_keypoints(conf: dict, h: int, w: int) -> int:
    along = lambda n: len(range(conf["lcs_border"], n - conf["lcs_border"], conf["lcs_stride"]))  # noqa: E731
    return along(h) * along(w)


def lcs(conf: dict, h: int, w: int) -> float:
    """Operations of one image's LCS: the squares, two separable windows a
    channel, and at each sampled place the deviation (a product, a
    difference, a clamp, a root)."""
    s = conf["lcs_patch"]
    return CHANNELS * (h * w * (1.0 + 2 * 2 * 2.0 * s) + 16.0 * lcs_keypoints(conf, h, w) * 4.0)


def columns(conf: dict, branch: str, h: int, w: int) -> int:
    if branch == "sift":
        return _voc.descriptors(_sift_conf(conf), h, w)
    return lcs_keypoints(conf, h, w)


def dim(branch: str) -> int:
    return SIFT_DIM if branch == "sift" else lcs_dim()


def encode(conf: dict, branch: str, cols: int) -> float:
    """One image's projection and Fisher vector from ``cols`` descriptors of
    the branch (``counts/voc_fv.encode`` at the branch's dimension; SIFT's
    signed square root an entry more)."""
    d, k = conf["desc_dim"], conf["vocab_size"]
    extra = 2.0 * SIFT_DIM if branch == "sift" else 0.0
    return cols * (extra + 2.0 * dim(branch) * d + 4 * 2.0 * d * k + 4.0 * k) + 10.0 * d * k


def chain(conf: dict, images: int) -> dict:
    """Both branches' descriptors -> PCA -> Fisher features of ``images``
    images, each once; bytes are the byte images in and the float32 rows out."""
    flops = nbytes = 0.0
    for h, w, share in _voc.shape_mix(conf):
        n = images * share
        flops += n * (_voc.sift(_sift_conf(conf), h, w) + lcs(conf, h, w))
        flops += n * sum(encode(conf, b, columns(conf, b, h, w)) for b in BRANCHES)
        nbytes += n * (3.0 * h * w + 4.0 * 2 * 2 * conf["desc_dim"] * conf["vocab_size"])
    return {"flops": flops, "bytes": nbytes}


def em_iterations() -> dict:
    """EM iterations a fit and branch, as the program's registry counted them
    (``gmm.iterations.<branch>`` over the fits that entered the ``gmm``
    stage); 0 where the program counts none."""
    try:
        from keystone_tpu.core.trace import metrics
    except ImportError:
        return {b: 0.0 for b in BRANCHES}
    fits = metrics.hist_windows().get("stage_ms.gmm", {}).get("count", 0)
    seen = metrics.counters()
    return {b: (seen.get(f"gmm.iterations.{b}", 0) / fits if fits else 0.0) for b in BRANCHES}


def dictionary(conf: dict, iterations: dict) -> dict:
    """A branch's PCA (the covariance of its sample and its eigenvectors, the
    projection of its EM sample) and EM, as ``counts/voc_fv.dictionary``."""
    n_p, n_g = conf["num_pca_samples"], min(conf["num_gmm_samples"], GMM_FIT_CAP)
    d, k = conf["desc_dim"], conf["vocab_size"]
    pca = em = pca_bytes = em_bytes = 0.0
    for b in BRANCHES:
        m = dim(b)
        pca += 2.0 * n_p * m * m + 9.0 * m**3 + 2.0 * n_g * m * d
        pca_bytes += 4.0 * m * (n_p + n_g)
        em += iterations[b] * n_g * (4 * 2.0 * d * k + 4.0 * k)
        em_bytes += iterations[b] * 4.0 * n_g * d
    return {"pca": {"flops": pca, "bytes": pca_bytes}, "em": {"flops": em, "bytes": em_bytes}}


def weighted_solve(rows: int, widths: list, classes: int, epochs: int) -> dict:
    """BlockWeightedLeastSquares: a block its population gram (2 N w^2) and a
    block and pass ``X^T R`` and the residual's update (4 N w C); the classes'
    covariances over their own rows (2 N w^2 in all) and cross terms; a class,
    block and pass its system put together (5 w^2), a Cholesky (w^3 / 3) and
    two triangular solves (2 w^2).  Bytes: the block read for the gram and
    twice a pass, the residual read and written a pass, and a class the
    population covariance read and its factor written, in float32."""
    flops = nbytes = 0.0
    for w in widths:
        flops += 2.0 * rows * w * w
        nbytes += 4.0 * rows * w * (1 + 2 * epochs)
        flops += epochs * (4.0 * rows * w * classes + 2.0 * rows * w * w + 2.0 * rows * w)
        flops += epochs * classes * (w**3 / 3.0 + 7.0 * w * w)
        nbytes += epochs * (4.0 * 2 * rows * classes + classes * 4.0 * 2 * w * w)
    return {"flops": flops, "bytes": nbytes}


def fit(conf: dict, rows: dict) -> dict:
    """One whole fit: every image through both branches once, PCA and EM at
    the iterations run, the weighted solve, the test rows' scores."""
    d = 2 * 2 * conf["desc_dim"] * conf["vocab_size"]
    learned = dictionary(conf, em_iterations())
    parts = {
        "chain": chain(conf, rows["train"] + rows["test"]),
        "pca": learned["pca"],
        "em": learned["em"],
        "wsolve": weighted_solve(
            rows["train"], block_widths(d, conf["solver_block"]), conf["num_classes"], conf["num_epochs"]
        ),
        "predict": predict(rows["test"], d, conf["num_classes"]),
    }
    parts["total_flops"] = sum(p["flops"] for p in parts.values())
    return parts


def kernels(conf: dict, rows: dict) -> dict:
    parts = fit(conf, rows)
    return {
        "wsolve": dict(parts["wsolve"], layer="solvers"),
        "fv2_chain": dict(parts["chain"], layer="featurizers"),
    }
