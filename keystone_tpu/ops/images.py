"""Image operator nodes (reference src/main/scala/nodes/images/).

Representation: a batch of images is a dense ``f32[N, H, W, C]`` array
(H = yDim rows, W = xDim cols).  The reference's
``ChannelMajorArrayVectorizedImage`` stores pixel (x, y, c) at index
``c + x*numChannels + y*numChannels*xDim`` (utils/images/Image.scala:19-317),
i.e. exactly the row-major flattening of ``[H, W, C]`` — so
:class:`ImageVectorizer` here is a plain reshape and produces bit-identical
vector layouts.

The big design change is :class:`Convolver`: the reference materializes an
im2col patch matrix per image and does one gemm
(nodes/images/Convolver.scala:93-136, :62).  On TPU the convolution maps
straight onto the MXU via ``lax.conv_general_dilated`` and the per-patch
normalization is recovered *algebraically* from box-filter sums (see
Convolver docstring) — no patch matrix ever exists in HBM.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..core.pipeline import FunctionNode, Transformer, node


# ---------------------------------------------------------------------------
# Simple per-pixel nodes
# ---------------------------------------------------------------------------


@node(data_fields=(), meta_fields=())
class PixelScaler(Transformer):
    """Rescale [0..255] -> [0..1] (reference nodes/images/PixelScaler.scala:10-14)."""

    def __call__(self, batch):
        return batch / 255.0


@node(data_fields=(), meta_fields=())
class GrayScaler(Transformer):
    """NTSC grayscale (reference nodes/images/GrayScaler.scala:9-11,
    utils/images/ImageUtils.scala:55-87).  3-channel input is assumed BGR
    (as the reference assumes): ``0.2989*R + 0.5870*G + 0.1140*B``; any other
    channel count uses sqrt of the mean of squares.  Output keeps a trailing
    singleton channel axis."""

    def __call__(self, batch):
        c = batch.shape[-1]
        if c == 3:
            w = jnp.array([0.1140, 0.5870, 0.2989], batch.dtype)  # B, G, R
            out = jnp.einsum("...c,c->...", batch, w)
        else:
            out = jnp.sqrt(jnp.mean(batch * batch, axis=-1))
        return out[..., None]


@node(data_fields=(), meta_fields=())
class ImageVectorizer(Transformer):
    """Flatten [N,H,W,C] -> [N, H*W*C]; identical element order to the
    reference's channel-major ``Image.toArray``
    (nodes/images/ImageVectorizer.scala:11-15)."""

    def __call__(self, batch):
        return batch.reshape(batch.shape[0], -1)


@node(data_fields=(), meta_fields=("max_val", "alpha"))
class SymmetricRectifier(Transformer):
    """Two-sided ReLU; channels double: ``[max(v, x-a), max(v, -x-a)]``
    (reference nodes/images/SymmetricRectifier.scala:6-32).  Positive parts
    occupy channels [0, C), negative parts [C, 2C), as in the reference."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def __call__(self, batch):
        pos = jnp.maximum(self.max_val, batch - self.alpha)
        neg = jnp.maximum(self.max_val, -batch - self.alpha)
        return jnp.concatenate([pos, neg], axis=-1)


# ---------------------------------------------------------------------------
# Windower — strided patch extraction
# ---------------------------------------------------------------------------


class Windower(FunctionNode):
    """All strided square patches of each image
    (reference nodes/images/Windower.scala:13-58).

    [N,H,W,C] -> [N * nWin, ws, ws, C].  Patch order matches the reference's
    flatMap order: x (column) outer, y (row) inner.
    """

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def __call__(self, batch):
        n, h, w, c = batch.shape
        ws, st = self.window_size, self.stride
        xs = jnp.arange(0, w - ws + 1, st)
        ys = jnp.arange(0, h - ws + 1, st)
        # grid ordered x-outer, y-inner (reference :27-28)
        gx = jnp.repeat(xs, ys.shape[0])
        gy = jnp.tile(ys, xs.shape[0])

        def one_window(img, x, y):
            return lax.dynamic_slice(img, (y, x, 0), (ws, ws, c))

        per_image = jax.vmap(one_window, in_axes=(None, 0, 0))
        wins = jax.vmap(lambda img: per_image(img, gx, gy))(batch)
        return wins.reshape(n * gx.shape[0], ws, ws, c)


# ---------------------------------------------------------------------------
# Pooler
# ---------------------------------------------------------------------------


@node(data_fields=(), meta_fields=("stride", "pool_size", "pixel_function", "pool_function"))
class Pooler(Transformer):
    """Strided pooling over square regions
    (reference nodes/images/Pooler.scala:20-68).

    Pool centers start at ``strideStart = poolSize/2`` and step by ``stride``;
    each pool covers ``[x - ps//2, min(x + ps//2, dim))`` — edge pools are
    truncated, and (as in the reference, where the pool buffer is a fixed
    ``poolSize²`` zero-filled vector) truncated regions contribute zeros.

    ``pixel_function`` maps each pixel first (e.g. ``jnp.abs``);
    ``pool_function`` is ``'sum'``, ``'mean'`` or ``'max'`` — mean divides by
    the fixed ``poolSize²`` and max sees the pad zeros in truncated edge
    pools, exactly like the reference's zero-filled pool vector.
    """

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_function: Callable | None = None,
        pool_function: str = "sum",
    ):
        if pool_function not in ("sum", "mean", "max"):
            raise ValueError("pool_function must be 'sum', 'mean' or 'max'")
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_function = pixel_function
        self.pool_function = pool_function

    def _num_pools(self, dim: int) -> int:
        stride_start = self.pool_size // 2
        return math.ceil((dim - stride_start) / self.stride)

    def windows(self, dim: int) -> tuple:
        """``(start, length)`` of every pool along an axis of ``dim``
        pixels — the coverage ``__call__`` gives (truncated at the high
        edge), for kernels that take the pools themselves."""
        half = self.pool_size // 2
        span = 2 * half if self.pool_size % 2 == 1 else self.pool_size
        return tuple(
            (p * self.stride, min(p * self.stride + span, dim) - p * self.stride)
            for p in range(self._num_pools(dim))
        )

    def __call__(self, batch):
        n, h, w, c = batch.shape
        ps, st = self.pool_size, self.stride
        half = ps // 2
        stride_start = half
        np_x = self._num_pools(w)
        np_y = self._num_pools(h)

        x = batch if self.pixel_function is None else self.pixel_function(batch)

        # Window origins: strideStart + i*stride - ps//2 = i*stride; windows
        # span ps pixels (even ps) or 2*(ps//2) pixels (odd ps, matching the
        # reference's [x-ps/2, x+ps/2) bound), truncated at the high edge.
        span = 2 * half if ps % 2 == 1 else ps
        # Pad the high edge with zeros so every window is full-size.
        pad_h = max(0, (np_y - 1) * st + span - h)
        pad_w = max(0, (np_x - 1) * st + span - w)
        x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))

        if self.pool_function == "max":
            init, op = -jnp.inf, lax.max
        else:
            init, op = 0.0, lax.add
        pooled = lax.reduce_window(
            x,
            jnp.asarray(init, x.dtype),
            op,
            window_dimensions=(1, span, span, 1),
            window_strides=(1, st, st, 1),
            padding="VALID",
        )
        pooled = pooled[:, :np_y, :np_x, :]
        if self.pool_function == "mean":
            pooled = pooled / float(ps * ps)
        elif self.pool_function == "max" and span < ps:
            # Odd pool_size: the reference's fixed poolSize² zero-filled pool
            # buffer (Pooler.scala:43) is never fully overwritten (the window
            # spans only (ps-1)² pixels), so its max always sees zeros.
            pooled = jnp.maximum(pooled, 0.0)
        return pooled


# ---------------------------------------------------------------------------
# Convolver
# ---------------------------------------------------------------------------


@node(
    data_fields=("filters", "whitener_means", "filter_means_dot"),
    meta_fields=("normalize_patches", "var_constant"),
)
class Convolver(Transformer):
    """Convolve a filter bank over images with optional per-patch
    normalization (reference nodes/images/Convolver.scala:19-154).

    The reference builds an explicit im2col patch matrix, normalizes each
    patch row (``Stats.normalizeRows`` with additive ``varConstant``,
    Convolver.scala:128), subtracts ZCA means, and gemms with the filter bank
    (:62).  TPU-native formulation: for a patch ``p`` (d = ws·ws·C elements),
    normalized ``p' = (p - μ·1)/σ  - m`` with ``μ = Σp/d``,
    ``σ = sqrt((Σp² - d μ²)/(d-1) + varConstant)``, so for filter ``f``:

        f·p' = (f·p − μ·Σf) / σ − f·m

    ``f·p`` is one conv with the filter bank; ``Σp`` and ``Σp²`` come from a
    channel-summed box filter over the image and its square — three
    MXU convolutions replace the patch matrix entirely.

    ``filters``: [F, ws, ws, C] (HWC patch layout, matching the reference's
    ``c + x*C + y*C*ws`` row-major order) or [F, ws*ws*C] flat.
    """

    def __init__(
        self,
        filters,
        whitener_means=None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
        img_channels: int | None = None,
    ):
        filters = jnp.asarray(filters)
        if filters.ndim == 2:
            if img_channels is None:
                raise ValueError("img_channels required for flat filters")
            ws = int(math.isqrt(filters.shape[1] // img_channels))
            filters = filters.reshape(filters.shape[0], ws, ws, img_channels)
        self.filters = filters
        self.normalize_patches = normalize_patches
        self.var_constant = var_constant
        self.whitener_means = (
            None if whitener_means is None else jnp.asarray(whitener_means)
        )
        # f·m per filter, folded into the output as a bias (reference
        # subtracts means from every patch row; dotting with filters is
        # equivalent and free).
        if self.whitener_means is not None:
            flat = self.filters.reshape(self.filters.shape[0], -1)
            self.filter_means_dot = flat @ self.whitener_means
        else:
            self.filter_means_dot = None

    @property
    def conv_size(self) -> int:
        return self.filters.shape[1]

    def __call__(self, batch):
        f, ws, _, c = self.filters.shape
        if batch.shape[-1] != c:
            raise ValueError(
                f"image channels {batch.shape[-1]} != filter channels {c}"
            )
        dn = lax.conv_dimension_numbers(
            batch.shape, (ws, ws, c, f), ("NHWC", "HWIO", "NHWC")
        )
        kernel = jnp.moveaxis(self.filters, 0, -1)  # [ws, ws, C, F]
        conv_fp = lax.conv_general_dilated(
            batch, kernel, (1, 1), "VALID", dimension_numbers=dn
        )

        if self.normalize_patches:
            d = ws * ws * c
            ones = jnp.ones((ws, ws, c, 1), batch.dtype)
            dn1 = lax.conv_dimension_numbers(
                batch.shape, (ws, ws, c, 1), ("NHWC", "HWIO", "NHWC")
            )
            psum = lax.conv_general_dilated(
                batch, ones, (1, 1), "VALID", dimension_numbers=dn1
            )
            psumsq = lax.conv_general_dilated(
                batch * batch, ones, (1, 1), "VALID", dimension_numbers=dn1
            )
            mu = psum / d
            var = (psumsq - d * mu * mu) / (d - 1.0)
            sigma = jnp.sqrt(var + self.var_constant)
            fsum = jnp.sum(self.filters, axis=(1, 2, 3))  # Σf per filter
            out = (conv_fp - mu * fsum) / sigma
        else:
            out = conv_fp

        if self.filter_means_dot is not None:
            out = out - self.filter_means_dot
        return out


# ---------------------------------------------------------------------------
# Label extractors (reference nodes/images/LabeledImageExtractors.scala:8-32)
# ---------------------------------------------------------------------------


@node(data_fields=(), meta_fields=())
class ImageExtractor(Transformer):
    """LabeledImage batch -> images (field extractor)."""

    def __call__(self, labeled):
        return labeled.images


@node(data_fields=(), meta_fields=())
class LabelExtractor(Transformer):
    """LabeledImage batch -> labels."""

    def __call__(self, labeled):
        return labeled.labels


MultiLabelExtractor = LabelExtractor
MultiLabeledImageExtractor = ImageExtractor
