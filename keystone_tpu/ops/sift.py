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

Everything is batched ``[N, H, W]``: the Gaussian smoothing and the spatial
binning are each two banded MXU products a scale with the edge padding in
the matrices (``_binned_sampling_matrix``; ``_smooth``), gradients and
orientation planes elementwise chains, so whole image batches stay in HBM
(the reference pays a JVM->C JNI crossing per image) and the compiled
program holds no one-channel convolution and no padded copy of a plane.
Descriptor count per image is static given (H, W, params), which keeps
shapes XLA-friendly; variable-size image sets bucket by shape upstream.

**The assembly** (binned planes ``[N, 8, 4*Fy, 4*Fx]`` a scale -> normalized
bytes ``[N, 128, D]``) is a transposition of every value: the planes hold a
frame's rows and columns innermost, the result its 128 dimensions.  It has
two forms with one result (``sift_form`` picks; ROOFLINE.md, "At the
published sizes", has the measurements):

* the *kernel form* (``ops/sift_pallas.py``) reads each scale's planes once
  and writes each descriptor once, as the bytes the chunk programs hand on:
  the sampling matrices are built bins-major (rows ``(by, y)``, columns
  ``(bx, x)``), so a frame row of all 128 dimensions is 32 plane rows, the
  MXU transposes it by a permutation product, and the tail runs in VMEM;
* the *XLA form* stages the scales' descriptors as ``[N, D, 128]`` in
  ``compute_dtype`` and runs the tail on that.  On a TPU the compiler lays
  the staged array out with the frames along the lanes and pays for it five
  times over (42.8 of a 61.8 ms chunk of 64 images of 375x500 where the
  kernel form takes 7.9; ROOFLINE.md); it is what runs on a CPU, under a
  ``model`` split, in float32 and on batches whose chip's share is no
  multiple of 32 images.

The kernel form is a custom call, which the compiler does not partition:
left to GSPMD under a mesh it would run whole on every chip.  A program's
input shows which mesh axes split it (``parallel.mesh.split_axes``).  Where
only the ``data`` axis does, the kernel form runs under ``shard_map`` over
that axis (``_sharded_kernel_form``: each chip its own images of the chunk,
nothing exchanged), as ``ops/conv_fused`` runs its kernel.

Descriptor layout note: the reference transposes each descriptor
(vl_dsift_transpose_descriptor, VLFeat.cxx:256) to undo its x/y-swapped
image layout; we compute directly in (row=y, col=x) convention so no
transpose is needed — the 128 dims, ordered ``(by, bx, t)``, are a fixed
permutation of the reference's, which is irrelevant to downstream
PCA/GMM/FV learning.  Frames are ordered y-major inside a scale, scales in
order.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import trace
from ..core.pipeline import Transformer, node
from ..parallel.mesh import DATA_AXIS
from ..parallel.mesh import input_mesh as _input_mesh
from ..parallel.mesh import split_axes as _split_axes

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


def _smooth(batch, sigma: float):
    """Gaussian smoothing of ``[N, H, W]`` with edge padding as two banded
    MXU products, rows then columns: ``_binned_sampling_matrix`` at every
    pixel *is* the edge-padded convolution matrix (the clamp folds the taps
    that leave the image onto its edge row), so no padded copy of the planes
    exists.  Weights and both results are rounded to the batch's dtype, the
    sums run in float32: what a one-channel convolution of the padded planes
    gives, up to the order of a 7-15 term float32 sum."""
    _n, h, w = batch.shape
    k = _gaussian_kernel(sigma)
    g_y = jnp.asarray(_binned_sampling_matrix(h, np.arange(h), k), batch.dtype)
    g_x = jnp.asarray(_binned_sampling_matrix(w, np.arange(w), k), batch.dtype)
    rows = jnp.einsum(
        "ph,nhw->npw", g_y, batch, preferred_element_type=jnp.float32
    ).astype(batch.dtype)
    return jnp.einsum(
        "npw,qw->npq", rows, g_x, preferred_element_type=jnp.float32
    ).astype(batch.dtype)


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


def sift_form(
    backend: str, split_axes: tuple, compute_dtype, images: int, fx: int, shards: int = 1
) -> str:
    """``"kernel"`` or ``"xla"``: which form of the descriptor assembly runs
    for ``images`` images of ``fx`` frames a row, split over the mesh axes
    ``split_axes`` (``()`` on one device) into ``shards`` equal parts
    (module docstring).  The kernel form is a Mosaic custom call: it
    compiles for the TPU only, and under a mesh it runs a chip's images
    under ``shard_map``, which a split over ``data`` alone allows; it reads
    bfloat16 planes two frame rows a word and writes whole byte tiles of 32
    images, so a chip's share is a multiple of 32; its blocks hold a frame
    row of all planes, so a very wide image would not fit VMEM.  Everything
    else takes the XLA form."""
    from .sift_pallas import fits

    kernel = (
        backend == "tpu" and set(split_axes) <= {DATA_AXIS}
        and jnp.dtype(compute_dtype) == jnp.bfloat16
        and images % shards == 0 and fits(images // shards, fx)
    )
    return "kernel" if kernel else "xla"


@node(meta_fields=("step_size", "bin_size", "scales", "scale_step", "compute_dtype"))
class SIFTExtractor(Transformer):
    """Batched dense SIFT: ``[N, H, W]`` (or [N,H,W,1]) grayscale in [0,1]
    -> ``[N, 128, num_desc]`` quantized descriptors as float32
    (reference SIFTExtractor.scala:27-34 returns DenseMatrix(128, numCols)).

    ``compute_dtype`` (default f32): storage dtype of the large per-scale
    intermediates — the [N, 8, H, W] orientation planes and both banded
    products' results, the dominant HBM streams of the op ahead of the
    assembly.  Passing ``jnp.bfloat16`` (the throughput workloads do —
    imagenet_sift_lcs_fv, voc_sift_fisher) halves that traffic: the products
    accumulate f32 and the angle mathematics and the whole
    normalize/clamp/quantize tail run f32 in both forms of the assembly, so
    the only effect is one rounding of intermediate values.  Against the f32
    chain on random-noise 256x256 images (the worst case for near-threshold
    bins; measured on v5e in round 4, before this repo kept its records:
    ROOFLINE.md, "The SIFT -> PCA -> FV chain") 99.5% of quantized entries
    lie within +/-1 — the reference's own MATLAB acceptance envelope
    (VLFeatSuite.scala:48-51) — with rare tail outliers up to ~13/255; the
    benchmark's `sift_off_share` holds the bf16 program to the plain f32
    reference on every run (PERF.md section 4).  bfloat16 is also what the
    kernel form of the assembly reads (two frame rows a 32-bit word).  One
    known whole-descriptor failure mode under bf16: a descriptor whose
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
        return sum(len(ys) * len(xs) for _b, ys, xs in self._grids(h, w))

    def __call__(self, batch):
        if batch.ndim == 4:
            batch = batch[..., 0]
        n, h, w = batch.shape
        grids = list(self._grids(h, w))
        axes = _split_axes(batch)
        mesh = _input_mesh(batch) if axes else None
        shards = 1 if mesh is None else dict(mesh.shape).get(DATA_AXIS, 1)
        form = sift_form(
            jax.default_backend(), axes, self.compute_dtype,
            n, max((len(xs) for _b, _ys, xs in grids), default=0), shards,
        )
        # Counted where the program is traced: once a jitted shape.  The
        # instant's ``shards`` are the chips the kernel form runs on, each its
        # own images (1 for the XLA form); ``split`` the axes that split the
        # input.
        trace.metrics.inc(f"sift_form.{form}")
        trace.instant(
            "sift_form", form=form, images=n, scales=len(grids),
            frames=self.num_descriptors(h, w),
            shards=shards if form == "kernel" else 1, split=",".join(axes),
            smooth="banded", smooth_rows=f"{h}x{h}", smooth_cols=f"{w}x{w}",
        )
        if form == "xla":
            return self._xla_form(batch)
        if mesh is None:
            return self._kernel_form(batch)
        return self._sharded_kernel_form(batch, mesh)

    def _grids(self, h: int, w: int):
        """``(bin size, frame rows, frame columns)`` of each scale that has
        frames."""
        for s in range(self.scales):
            b = self.bin_size + 2 * s
            step = self.step_size + s * self.scale_step
            ys, xs = _scale_geometry(h, w, step, b, self.scales, s)
            if len(ys) and len(xs):
                yield b, ys, xs

    def _sampled(self, batch, tile=None):
        """Per scale with frames: the binned planes ``[N, 8, P, Q]`` in
        ``compute_dtype`` and the frame grid ``(fy, fx)``.  Rows and columns
        are ``(frame, bin)`` (``P = 4*fy``, ``Q = 4*fx``); with ``tile =
        (rows, columns)`` they are ``(bin, frame)``, a bin's frame rows padded
        to a multiple of ``rows`` and the columns to one of ``columns`` by
        rows of zeros in the sampling matrices."""
        _n, h, w = batch.shape
        cdt = self.compute_dtype
        batch = batch.astype(cdt)
        for b, ys, xs in self._grids(h, w):
            fy, fx = len(ys), len(xs)
            smoothed = _smooth(batch, b / MAGNIF)
            gy, gx = _gradients(smoothed)
            planes = _orientation_planes(gy, gx).astype(cdt)  # [N, 8, H, W]
            tri = _triangular_kernel(b)

            # spatial binning as banded matmuls: triangular conv + bin-center
            # sampling in one MXU gemm per axis (see _binned_sampling_matrix)
            bin_off = np.arange(NUM_BIN_XY) * b
            if tile is None:
                yy = (ys[:, None] + bin_off[None, :]).ravel()  # [Fy*4]
                m_y = _binned_sampling_matrix(h, yy, tri)
                xx = (xs[:, None] + bin_off[None, :]).ravel()  # [Fx*4]
                m_x = _binned_sampling_matrix(w, xx, tri)
            else:
                yy = (bin_off[:, None] + ys[None, :]).ravel()  # [4*Fy]
                m_y = _binned_sampling_matrix(h, yy, tri).reshape(NUM_BIN_XY, fy, h)
                m_y = np.pad(m_y, ((0, 0), (0, -fy % tile[0]), (0, 0))).reshape(-1, h)
                xx = (bin_off[:, None] + xs[None, :]).ravel()  # [4*Fx]
                m_x = _binned_sampling_matrix(w, xx, tri)
                m_x = np.pad(m_x, ((0, -len(xx) % tile[1]), (0, 0)))
            s_y = jnp.asarray(m_y, cdt)
            s_x = jnp.asarray(m_x, cdt)
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
            ).astype(cdt)
            yield sampled, fy, fx

    def _kernel_form(self, batch, interpret: bool = False):
        """The assembly by ``ops/sift_pallas.assemble_scale``: each scale's
        call writes its frames of the one ``u8[D, N, 128]``, frames outermost,
        and the transposition to ``[N, 128, D]`` is one the TPU's layout of
        that shape makes free."""
        from .sift_pallas import LANES, TY, assemble_scale

        frames = self.num_descriptors(*batch.shape[1:])
        descs, offset = None, 0
        for sampled, fy, fx in self._sampled(batch, tile=(TY, LANES)):
            descs = assemble_scale(
                sampled, descs, fy=fy, fx=fx, offset=offset, frames=frames,
                interpret=interpret,
            )
            offset += fy * fx
        return jnp.transpose(descs, (1, 2, 0)).astype(jnp.float32)  # [N, 128, D]

    def _sharded_kernel_form(self, batch, mesh, interpret: bool = False):
        """The kernel form on images split over ``mesh``'s data axis: every
        chip runs :meth:`_kernel_form` on its own images, and the
        descriptors stay split as the images were.  No collective."""
        fn = jax.shard_map(
            lambda rows: self._kernel_form(rows, interpret=interpret),
            mesh=mesh,
            in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS),
            check_vma=False,
        )
        return fn(batch)

    def _xla_form(self, batch):
        n = batch.shape[0]
        per_scale = []
        for sampled, fy, fx in self._sampled(batch):
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
