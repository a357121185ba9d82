"""Multi-scale dense SIFT — TPU-native replacement for the reference's
VLFeat JNI kernel (src/main/cpp/VLFeat.cxx:37-292, wrapping vlfeat-0.9.20
``vl_dsift``; Scala surface src/main/scala/nodes/images/external/SIFTExtractor.scala:16-40).

Per scale ``s`` (reference VLFeat.cxx:68-123):
  * bin size ``b = bin + 2s``; sampling step ``step + s*scaleStep``;
  * Gaussian smooth with σ = b/magnif, magnif = 6.0 (:85-90);
  * bounds offset ``off = (1+2S) - 3s`` so scale grids share their origin
    when steps coincide (:93-95);
  * flat-window mode, windowSize 1.5 (:98-102) — uniform descriptor
    weighting, which cancels under L2 normalization;
  * descriptors: 4x4 spatial bins × 8 orientations; gradient magnitudes
    split bilinearly between adjacent orientation bins; each orientation
    plane convolved with a triangular kernel of half-width ``b`` (the
    bilinear spatial interpolation, vl_imconvcoltri) and sampled at bin
    centers ``origin + bin_idx*b``;
  * L2 normalize → clamp 0.2 → renormalize; descriptors with pre-norm
    below contrastthreshold=0.005 are zeroed (:62,167-169);
  * quantize ``min(floor(512·v), 255)`` (:249-263).

Everything is batched ``[N, H, W]`` XLA ops — conv, gather, vmap — so whole
image batches stay in HBM (the reference pays a JVM→C JNI crossing per
image).  Descriptor count per image is static given (H, W, params), which
keeps shapes XLA-friendly; variable-size image sets bucket by shape upstream.

Descriptor layout note: the reference transposes each descriptor
(vl_dsift_transpose_descriptor, VLFeat.cxx:256) to undo its x/y-swapped
image layout; we compute directly in (row=y, col=x) convention so no
transpose is needed — the 128 dims are a fixed permutation of the
reference's, which is irrelevant to downstream PCA/GMM/FV learning.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import Transformer, node

MAGNIF = 6.0
CONTRAST_THRESHOLD = 0.005
NUM_BIN_T = 8
NUM_BIN_XY = 4
DESC_DIM = NUM_BIN_T * NUM_BIN_XY * NUM_BIN_XY  # 128


def _gaussian_kernel(sigma: float) -> np.ndarray:
    radius = max(1, int(math.ceil(4.0 * sigma)))
    x = np.arange(-radius, radius + 1, dtype=np.float64)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _triangular_kernel(bin_size: int) -> np.ndarray:
    # vl_imconvcoltri: triangle of half-width bin_size, unit integral
    t = np.concatenate(
        [np.arange(1, bin_size + 1), np.arange(bin_size - 1, 0, -1)]
    ).astype(np.float32)
    return t / bin_size  # peak 1, integral bin_size (scale cancels in L2)


def _binned_sampling_matrix(
    length: int, positions: np.ndarray, kernel: np.ndarray
) -> np.ndarray:
    """[P, length] matrix S with S @ x == (edge-padded conv of x with
    ``kernel``) evaluated at ``positions``.

    The spatial binning of dsift is a triangular convolution sampled only at
    the 4 bin centers per frame — a tiny fraction of the plane.  Expressing
    "convolve then sample" as one banded matmul turns VPU-bound depthwise
    convs plus TPU-hostile gathers into MXU gemms (the einsums in
    ``__call__``); numerics are identical up to f32 summation order."""
    klen = len(kernel)
    r = (klen - 1) // 2
    s = np.zeros((len(positions), length), np.float32)
    for i, p in enumerate(positions):
        for t, kv in enumerate(kernel):
            h = min(max(p + t - r, 0), length - 1)  # edge padding
            s[i, h] += kv
    return s


def _conv1d_axis(batch, kernel, axis):
    """Convolve [N, H, W] along ``axis`` (1=rows/y, 2=cols/x) with edge pad."""
    k = jnp.asarray(kernel, batch.dtype)
    klen = k.shape[0]
    r = (klen - 1) // 2
    pad = [(0, 0), (0, 0), (0, 0)]
    pad[axis] = (r, klen - 1 - r)
    x = jnp.pad(batch, pad, mode="edge")
    # depthwise conv via conv_general_dilated on a singleton channel
    x4 = x[:, None, :, :]  # [N, 1, H, W]
    if axis == 1:
        kern = k[::-1].reshape(1, 1, klen, 1)
    else:
        kern = k[::-1].reshape(1, 1, 1, klen)
    out = jax.lax.conv_general_dilated(
        x4, kern, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW")
    )
    return out[:, 0]


def _smooth(batch, sigma: float):
    k = _gaussian_kernel(sigma)
    return _conv1d_axis(_conv1d_axis(batch, k, 1), k, 2)


def _gradients(batch):
    """np.gradient-style derivatives on [N, H, W]: central differences in the
    interior, one-sided at the edges (vlfeat dsift gradient convention)."""
    gy = (jnp.roll(batch, -1, 1) - jnp.roll(batch, 1, 1)) * 0.5
    gy = gy.at[:, 0, :].set(batch[:, 1, :] - batch[:, 0, :])
    gy = gy.at[:, -1, :].set(batch[:, -1, :] - batch[:, -2, :])
    gx = (jnp.roll(batch, -1, 2) - jnp.roll(batch, 1, 2)) * 0.5
    gx = gx.at[:, :, 0].set(batch[:, :, 1] - batch[:, :, 0])
    gx = gx.at[:, :, -1].set(batch[:, :, -1] - batch[:, :, -2])
    return gy, gx


def _orientation_planes(gy, gx):
    """[N, H, W] -> [N, 8, H, W]: magnitude split bilinearly between the two
    adjacent orientation bins.  Angle math runs f32 regardless of input
    dtype (a low-precision arctan2 would shift bin-split weights); the
    caller chooses the storage dtype of the result and XLA fuses the casts
    into this elementwise chain."""
    gy = gy.astype(jnp.float32)
    gx = gx.astype(jnp.float32)
    mag = jnp.sqrt(gx * gx + gy * gy)
    angle = jnp.arctan2(gy, gx)  # [-pi, pi]
    a = angle * (NUM_BIN_T / (2.0 * jnp.pi))  # bin units
    t = jnp.arange(NUM_BIN_T, dtype=a.dtype)
    # circular distance in bin units; tent weight
    d = jnp.abs(((a[..., None] - t + NUM_BIN_T / 2) % NUM_BIN_T) - NUM_BIN_T / 2)
    w = jnp.maximum(0.0, 1.0 - d)  # [N, H, W, 8]
    return jnp.moveaxis(mag[..., None] * w, -1, 1)


def _scale_geometry(h: int, w: int, step: int, bin_size: int, num_scales: int, scale: int):
    """Frame-origin grids per reference VLFeat.cxx:93-95 and vl_dsift bounds:
    origins from ``off`` while origin + 3b <= dim-1."""
    off = (1 + 2 * num_scales) - 3 * scale
    if off < 0:
        # vl_dsift never starts before the frame; a negative origin would
        # silently wrap under JAX indexing — fail loudly for scale counts
        # outside the reference envelope (VLFeat.cxx:93-95).
        raise ValueError(
            f"scale={scale} with num_scales={num_scales} yields negative "
            f"grid origin {off}; use scales <= {(1 + 2 * num_scales) // 3}"
        )
    span = NUM_BIN_XY - 1  # bin centers at origin + {0,1,2,3}*b
    xs = np.arange(off, w - 1 - span * bin_size + 1, step)
    ys = np.arange(off, h - 1 - span * bin_size + 1, step)
    return ys, xs


@node(meta_fields=("step_size", "bin_size", "scales", "scale_step", "compute_dtype"))
class SIFTExtractor(Transformer):
    """Batched dense SIFT: ``[N, H, W]`` (or [N,H,W,1]) grayscale in [0,1]
    -> ``[N, 128, num_desc]`` quantized descriptors as float32
    (reference SIFTExtractor.scala:27-34 returns DenseMatrix(128, numCols)).

    ``compute_dtype`` (default f32): storage dtype of the large per-scale
    intermediates — the [N, 8, H, W] orientation planes and the banded-gemm
    sampling tensors, the dominant HBM streams of this op (measured ~197
    MB/image of traffic in f32 at 256x256x4-scales; the op is memory-bound
    at ~11 FLOP/byte, bench round r04 roofline, 2026-07-30, record removed
    in PR 21).  Passing ``jnp.bfloat16`` (the
    throughput workloads do — imagenet_sift_lcs_fv, voc_sift_fisher)
    halves that traffic: gemms accumulate f32 and the
    normalize/clamp/quantize tail runs f32, so the only effect is one
    rounding of intermediate values.  MEASURED vs the f32 chain (v5e,
    random-noise 256x256 images — the worst case for near-threshold bins):
    99.5% of quantized entries within +/-1 — the reference's own MATLAB
    acceptance envelope (VLFeatSuite.scala:48-51) — with rare tail
    outliers up to ~13/255; throughput 4.3k -> 5.9k img/s (+35%) on the
    SIFT->PCA->FV chain, traffic 197 -> 126 MB/image.  One known whole-
    descriptor failure mode under bf16: a descriptor whose
    pre-normalization norm lands within bf16 rounding (~0.4%) of
    CONTRAST_THRESHOLD can flip the zeroing decision vs the f32 chain,
    changing its entire 128-dim column — such near-threshold (i.e.
    near-contrastless) descriptors carry negligible signal, which is why
    the throughput workloads opt in; the OP default stays f32 so
    parity-critical callers get bit-level agreement without asking.
    """

    def __init__(
        self,
        step_size: int = 3,
        bin_size: int = 4,
        scales: int = 4,
        scale_step: int = 1,
        compute_dtype=jnp.float32,
    ):
        self.step_size = step_size
        self.bin_size = bin_size
        self.scales = scales
        self.scale_step = scale_step
        self.compute_dtype = compute_dtype

    def num_descriptors(self, h: int, w: int) -> int:
        total = 0
        for s in range(self.scales):
            b = self.bin_size + 2 * s
            step = self.step_size + s * self.scale_step
            ys, xs = _scale_geometry(h, w, step, b, self.scales, s)
            total += len(ys) * len(xs)
        return total

    def __call__(self, batch):
        if batch.ndim == 4:
            batch = batch[..., 0]
        n, h, w = batch.shape
        cdt = self.compute_dtype
        batch = batch.astype(cdt)
        per_scale = []
        for s in range(self.scales):
            b = self.bin_size + 2 * s
            step = self.step_size + s * self.scale_step
            ys, xs = _scale_geometry(h, w, step, b, self.scales, s)
            if len(ys) == 0 or len(xs) == 0:
                continue
            sigma = b / MAGNIF
            smoothed = _smooth(batch, sigma)
            gy, gx = _gradients(smoothed)
            planes = _orientation_planes(gy, gx).astype(cdt)  # [N, 8, H, W]
            tri = _triangular_kernel(b)

            # spatial binning as banded matmuls: triangular conv + bin-center
            # sampling in one MXU gemm per axis (see _binned_sampling_matrix)
            bin_off = np.arange(NUM_BIN_XY) * b
            yy = (ys[:, None] + bin_off[None, :]).ravel()  # [Fy*4]
            xx = (xs[:, None] + bin_off[None, :]).ravel()  # [Fx*4]
            s_y = jnp.asarray(_binned_sampling_matrix(h, yy, tri), cdt)
            s_x = jnp.asarray(_binned_sampling_matrix(w, xx, tri), cdt)
            # Two explicit gemms (not one opt-einsum) so the [N, 8, P, W]
            # intermediate is stored in compute_dtype — at the production
            # shape it is the single largest tensor of the whole op.
            part = jnp.einsum(
                "ph,nthw->ntpw", s_y, planes,
                preferred_element_type=jnp.float32,
            ).astype(cdt)
            sampled = jnp.einsum(
                "ntpw,qw->ntpq", part, s_x,
                preferred_element_type=jnp.float32,
            ).astype(cdt)  # [N, 8, Fy*4, Fx*4]
            fy, fx = len(ys), len(xs)
            sampled = sampled.reshape(n, NUM_BIN_T, fy, NUM_BIN_XY, fx, NUM_BIN_XY)
            # descriptor dims ordered [by, bx, t]; frames ordered y-major
            desc = jnp.einsum("ntybxc->nyxbct", sampled).reshape(
                n, fy * fx, NUM_BIN_XY * NUM_BIN_XY * NUM_BIN_T
            )
            per_scale.append(desc)

        descs = jnp.concatenate(per_scale, axis=1)  # [N, D, 128]
        # Normalization tail in f32: reductions/divisions read the compact
        # descriptors and accumulate full-precision (XLA fuses the upcast).
        norms = jnp.sqrt(
            jnp.sum(jnp.square(descs.astype(jnp.float32)), axis=-1, keepdims=True)
        )
        normed = descs.astype(jnp.float32) / jnp.maximum(norms, 1e-12)
        clamped = jnp.minimum(normed, 0.2)
        norms2 = jnp.linalg.norm(clamped, axis=-1, keepdims=True)
        final = clamped / jnp.maximum(norms2, 1e-12)
        # contrast threshold on the pre-normalization norm (:167-169)
        final = jnp.where(norms > CONTRAST_THRESHOLD, final, 0.0)
        quant = jnp.minimum(jnp.floor(512.0 * final), 255.0)
        return jnp.swapaxes(quant, 1, 2)  # [N, 128, D]
