"""Local Color Statistics extractor
(reference src/main/scala/nodes/images/LCSExtractor.scala:25-130).

Per channel: box-filter means and standard deviations (via E[x²]−E[x]²) over
``subPatchSize`` windows, sampled at a 4×4 neighborhood around each keypoint
of a regular grid — 96-dim descriptors for RGB (4·4·3·2).

The reference runs per-image Scala while-loops over a conv2D helper
(utils/images/ImageUtils.scala:162-274: zero-padded 'same' separable
convolution); here the box windows are sums of shifted copies of a whole
batch, channels leading, taken only on the grid of places that some keypoint
samples, and the neighborhood sampling is static strided slices — whole
batches stay in HBM.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import Transformer, node


def _same_conv2d_zero(batch, xfilt, yfilt):
    """The reference conv2D: zero padding of filter_len−1 split
    floor/ceil (low/high), true convolution (filter reversed), output same
    size.  ``batch`` [N, H, W, C]; filters 1-D."""
    xk = jnp.asarray(xfilt[::-1].copy())
    yk = jnp.asarray(yfilt[::-1].copy())
    n, h, w, c = batch.shape
    xlen, ylen = xk.shape[0], yk.shape[0]
    # reference pads (len-1) total: low = floor((len-1)/2), high = rest
    pads = {
        1: ((ylen - 1) // 2, (ylen - 1) - (ylen - 1) // 2),
        2: ((xlen - 1) // 2, (xlen - 1) - (xlen - 1) // 2),
    }
    x = jnp.pad(
        batch, ((0, 0), pads[1], pads[2], (0, 0)), mode="constant"
    )
    x = jnp.moveaxis(x, -1, 1).reshape(n * c, 1, h + ylen - 1, w + xlen - 1)
    out = jax.lax.conv_general_dilated(
        x,
        yk.reshape(1, 1, ylen, 1),
        (1, 1),
        "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    out = jax.lax.conv_general_dilated(
        out,
        xk.reshape(1, 1, 1, xlen),
        (1, 1),
        "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return jnp.moveaxis(out.reshape(n, c, h, w), 1, -1)


def _box_mean_at(x, size: int, axis: int, first: int, pitch: int, count: int):
    """The reference conv2D with a box of ``size`` along ``axis`` (zero
    padding of size-1 split floor/ceil, low/high; output same size), read at
    the ``count`` places ``first, first + pitch, ...`` only: the sum of the
    window's shifted, strided copies."""
    low = (size - 1) // 2
    pad = [(0, 0)] * x.ndim
    pad[axis] = (low, size - 1 - low)
    padded = jnp.pad(x, pad)
    # place q's window is padded[q : q + size]
    last = first + pitch * (count - 1)
    out = jax.lax.slice_in_dim(padded, first, last + 1, pitch, axis=axis)
    for t in range(1, size):
        out = out + jax.lax.slice_in_dim(padded, first + t, last + t + 1, pitch, axis=axis)
    return out * (1.0 / size)


@node(meta_fields=("stride", "stride_start", "sub_patch_size"))
class LCSExtractor(Transformer):
    """Batched LCS: ``[N, H, W, C]`` -> ``[N, descDim, numKeypoints]``
    (descriptors as columns, the SIFT/BatchPCA convention).

    Keypoints: ``strideStart until dim−strideStart by stride`` in x and y,
    columns ordered x-major (reference :99-125); descriptor entries ordered
    channel-major, then (nx, ny) neighborhood, interleaving (mean, std)
    (reference :108-122).
    """

    def __init__(self, stride: int, stride_start: int, sub_patch_size: int):
        self.stride = stride
        self.stride_start = stride_start
        self.sub_patch_size = sub_patch_size

    def _keypoints(self, dim: int) -> np.ndarray:
        return np.arange(self.stride_start, dim - self.stride_start, self.stride)

    def _neighborhood(self) -> np.ndarray:
        s = self.sub_patch_size
        # reference :66-71: -2s + s/2 - 1  to  s + s/2 - 1  by s
        nbr = np.arange(-2 * s + s // 2 - 1, s + s // 2 - 1 + 1, s)
        # JAX would silently wrap negative sample coordinates to the far
        # edge (the Scala reference throws); fail loudly instead.
        if self.stride_start + nbr.min() < 0:
            raise ValueError(
                f"stride_start={self.stride_start} too small for "
                f"sub_patch_size={s}: sample offset {nbr.min()} would index "
                "before the image edge"
            )
        return nbr

    def num_keypoints(self, h: int, w: int) -> int:
        return len(self._keypoints(w)) * len(self._keypoints(h))

    def __call__(self, batch):
        n, h, w, c = batch.shape
        s = self.sub_patch_size
        xs = self._keypoints(w)
        ys = self._keypoints(h)
        nbr = self._neighborhood()
        dim = c * nbr.size * nbr.size * 2
        if len(xs) == 0 or len(ys) == 0:
            return jnp.zeros((n, dim, 0), batch.dtype)
        # Every sampled place is keypoint + offset = first + stride i + s j,
        # so the statistics are needed on a grid of pitch gcd(stride, s) only
        # (every second row and column at the reference's stride 4 and patch
        # 6): the windows are summed there and nowhere else.
        pitch = math.gcd(self.stride, s)
        ky, kx = self.stride // pitch, s // pitch  # a keypoint's, an offset's step on the grid

        def grid(keys):
            first = int(keys[0] + nbr[0])
            return first, (int(keys[-1] + nbr[-1]) - first) // pitch + 1

        (y0, rows), (x0, cols) = grid(ys), grid(xs)
        # channels lead, so the image plane is the tiled pair of axes (with
        # three channels innermost an accelerator pads them to a full tile)
        x = jnp.moveaxis(batch, -1, 1)

        def window_means(img):
            return _box_mean_at(_box_mean_at(img, s, 2, y0, pitch, rows), s, 3, x0, pitch, cols)

        means = window_means(x)
        stds = jnp.sqrt(jnp.maximum(window_means(x * x) - means * means, 0.0))

        def sample(img):  # [N, C, rows, cols] -> [N, C, nx * ny, Kx, Ky]
            # a neighbour's places are a strided run of the grid: static
            # slices, no gather.  nx outer, ny inner (reference :108-113)
            by_row = [
                jax.lax.slice_in_dim(img, j * kx, j * kx + ky * (len(ys) - 1) + 1, ky, axis=2)
                for j in range(nbr.size)
            ]
            planes = [
                jnp.swapaxes(
                    jax.lax.slice_in_dim(by_row[jy], jx * kx, jx * kx + ky * (len(xs) - 1) + 1, ky, axis=3),
                    2, 3,
                )
                for jx in range(nbr.size)
                for jy in range(nbr.size)
            ]
            return jnp.stack(planes, axis=2)

        # interleave mean/std behind the neighbour -> [N, C, nx*ny, 2, Kx, Ky]
        pairs = jnp.stack([sample(means), sample(stds)], axis=3)
        return pairs.reshape(n, dim, len(xs) * len(ys))  # [N, descDim, K], K x-major
