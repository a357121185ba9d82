"""Fused conv -> rectify -> pool featurizer: one node, two forms of the
same mathematics, chosen by what the code can observe.

TPU-native re-design of the RandomPatchCifar featurization chain
(reference src/main/scala/pipelines/images/cifar/RandomPatchCifar.scala:53-56:
Convolver -> SymmetricRectifier -> Pooler -> ImageVectorizer, with
Convolver's im2col+gemm at nodes/images/Convolver.scala:93-136).

The chain is bandwidth-limited (ROOFLINE.md): its arithmetic is small and
the only lever is HBM traffic.  The stream that dominates is the
normalized conv activations ``[N, oh, ow, F]``; which form is cheaper
depends on how wide that stream is against a patch tensor's.

**The XLA form** (``_xla_form``) lets XLA's conv emitter stream implicit
patches through the MXU (no im2col exists in HBM) and cuts the activation
stream instead: activations stored ``activation_dtype`` (bf16), pos/neg
pooled by two reduce_windows so the rectifier fuses into each pool read
and the ``[oh, ow, 2F]`` concat never exists, per-patch normalization by
Convolver's algebraic identity in the conv epilogue.  Three crossings of
the activations remain: one write, two reads.  At 100 filters that is
0.59 MB an image and the form runs at ~85% of HBM peak; an HBM patch
tensor costs as much as it removes there, and every hand-written kernel
tried at that width lost (ROOFLINE.md, "Why the hand-written kernels
lost").

**The kernel form** (``_kernel_form``) keeps the activations off HBM
altogether.  XLA builds the patch tensor inside the same program
(``_patch_rows``: images innermost, the patch depth padded to one MXU
pass, normalized in f32 and stored bf16), one Pallas kernel owns filter
product -> rectify -> sum-pool with the activations alive only in VMEM
(``_pool_kernel``), and XLA reorders the pooled ``[2*npools, N, F]`` into
the node's element order.  The kernel's only HBM operands are the patch
tensor and the pooled features, so it has no boundary on the activation
tensor (the relayout copies that sank ops/rect_pool_pallas.py cannot
occur), and the patch tensor is laid out as XLA's conv emitter writes it
(``[oh, ow, N, kp]``), so nothing is copied at the boundary it does have.
Activations stay f32 in VMEM: nothing is stored at lower precision than
the XLA form stores it, and the patches are normalized before their one
rounding, so this form lies closer to an f32 reference than the XLA form
does (rms 0.0016 against 0.0057 on v5e).

**Which form runs** (``conv_form``) follows from the streams.  An image's
activations cross HBM three times in the XLA form, ``3 * 2 B * oh*ow * F``
bytes; the kernel form writes and reads the patch tensor instead,
``2 * 2 B * oh*ow * kp`` bytes (``kp`` the padded patch depth).  The
kernel form runs when the first is at least ``KERNEL_STREAM_RATIO`` times
the second; the multiple covers what the byte count leaves out (the patch
tensor is gathered by a conv of its own, filters are padded to a lane
tile).  Measured on v5e (tools/conv_form_probe.py; ROOFLINE.md, "1,250
filters"), 6x6x3 patches on 32x32 images, kernel against XLA form:

    filters   ratio   kernel form   XLA form   (ms a 2,048-image chunk)
      100      1.17      1.38         0.91     (1,024 images, ROOFLINE.md's)
      256      3.0       2.73         4.67
      384      4.5       3.35         5.99
      640      7.5       3.95         9.10
    1,250     14.6       5.12        17.15     (the benchmark's)

so the rule's multiple, 2, lies between the widest shape the XLA form
won and the narrowest the kernel form did.  The kernel form is a custom
call, which the compiler does not partition: left to GSPMD under a mesh it
would run replicated on every chip.  A program's input shows which mesh
axes split it (``parallel.mesh.split_axes``).  Where only the ``data`` axis does, the
kernel form runs under ``shard_map`` over that axis (``_sharded_kernel_form``:
each chip its own rows of the chunk against the whole filter bank, filters
and whitener replicated, nothing exchanged); under a model-axis split, as
on a CPU (Mosaic compiles for the TPU only), the XLA form runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core import trace
from ..core.pipeline import Transformer, node
from ..parallel.mesh import DATA_AXIS
from ..parallel.mesh import input_mesh as _input_mesh
from ..parallel.mesh import on_one_device as _on_one_device
from ..parallel.mesh import split_axes as _split_axes
from .images import Convolver, Pooler

#: The activation stream of the XLA form over the patch stream of the
#: kernel form (bytes an image, see the module docstring) from which the
#: kernel form runs.
KERNEL_STREAM_RATIO = 2.0

#: Images a grid step: one bf16 sublane tile, so a position's patches are
#: one [16, kp] tile and its products whole f32 vregs.
_IMAGES_PER_STEP = 16
#: The widest filter tile, and the outer positions whose products are
#: taken in one matrix product ([9 * 27 * 16, 256] f32 is 4 MB of VMEM;
#: 1, 3 and 9 rows measured the same, and fewer products trace faster).
_FILTER_TILE = 256
_ROWS_PER_PRODUCT = 9
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _patch_depth(d: int) -> int:
    """Patch values plus the two bias columns, padded to a lane tile."""
    return _round_up(d + 2, _LANES)


def conv_form(
    backend: str, positions: int, d: int, num_filters: int, split_axes: tuple
) -> str:
    """``"kernel"`` or ``"xla"`` for ``positions`` patches an image of
    ``d`` values each and ``num_filters`` filters, on an input split over
    the mesh axes ``split_axes`` (``()`` on one device): the rule of the
    module docstring, in one place."""
    if backend != "tpu" or not set(split_axes) <= {DATA_AXIS}:
        return "xla"
    activation_stream = 3 * 2 * positions * num_filters
    patch_stream = 2 * 2 * positions * _patch_depth(d)
    return "kernel" if activation_stream >= KERNEL_STREAM_RATIO * patch_stream else "xla"


def _pool_kernel(p_ref, w_ref, o_ref, *, wy, wx, alpha, max_val):
    """One grid step: the patches of ``b`` images ``[oh, ow, b, kp]``
    against one filter tile ``[kp, ft]``; out ``[2*npools, b, ft]``,
    sign-major.

    Images lie along the sublanes, so a position's products are whole
    vregs ``[b, 128]`` and every pool is a sum over leading axes: plain
    vector adds, no mask, no shuffle.  The products of a few rows of
    positions land in VMEM as f32 and are consumed there.  Rectifying as
    ``max(z, t)`` and ``min(z, -t)`` with ``t = max_val + alpha`` costs
    one operation a sign: ``max(max_val, z - alpha) = max(z, t) - alpha``
    and ``max(max_val, -z - alpha) = -min(z, -t) - alpha``, the ``alpha``s
    taken off the pooled sums.
    """
    oh, ow, b, kp = p_ref.shape
    ft = w_ref.shape[1]
    t = max_val + alpha
    tiles = [slice(j * _LANES, (j + 1) * _LANES) for j in range(ft // _LANES)]
    hi = [[[None] * len(tiles) for _ in wx] for _ in wy]
    lo = [[[None] * len(tiles) for _ in wx] for _ in wy]
    for y0 in range(0, oh, _ROWS_PER_PRODUCT):
        g = min(_ROWS_PER_PRODUCT, oh - y0)
        z = jnp.dot(
            p_ref[y0 : y0 + g].reshape(g * ow * b, kp), w_ref[...],
            preferred_element_type=jnp.float32,
        ).reshape(g, ow, b, ft)
        for k, (ys, ylen) in enumerate(wy):
            rows = slice(max(ys, y0) - y0, min(ys + ylen, y0 + g) - y0)
            if rows.start >= rows.stop:
                continue
            for m, (x0, xlen) in enumerate(wx):
                for j, lanes in enumerate(tiles):
                    zs = z[rows, x0 : x0 + xlen, :, lanes]
                    zh = jnp.sum(jnp.maximum(zs, t), axis=(0, 1))
                    zl = jnp.sum(jnp.minimum(zs, -t), axis=(0, 1))
                    hi[k][m][j] = zh if hi[k][m][j] is None else hi[k][m][j] + zh
                    lo[k][m][j] = zl if lo[k][m][j] is None else lo[k][m][j] + zl
    npools = len(wy) * len(wx)
    for k, (_, ylen) in enumerate(wy):
        for m, (_, xlen) in enumerate(wx):
            off = alpha * (ylen * xlen)
            for j, lanes in enumerate(tiles):
                o_ref[k * len(wx) + m, :, lanes] = hi[k][m][j] - off
                o_ref[npools + k * len(wx) + m, :, lanes] = -lo[k][m][j] - off


def _pooled_products(patches, weights, *, wy, wx, alpha, max_val, interpret):
    """``patches`` [oh, ow, n, kp] bf16 (n a multiple of the image block),
    ``weights`` [kp, fp] bf16 -> [2*npools, n, fp] f32."""
    oh, ow, n, kp = patches.shape
    fp = weights.shape[1]
    b = _IMAGES_PER_STEP
    ft = min(_FILTER_TILE, fp)
    npools = len(wy) * len(wx)
    kern = functools.partial(
        _pool_kernel, wy=wy, wx=wx, alpha=alpha, max_val=max_val
    )
    return pl.pallas_call(
        kern,
        # the filter tile is the inner axis: an image block's patches are
        # fetched once and stay while the tiles pass
        grid=(n // b, fp // ft),
        in_specs=[
            pl.BlockSpec((oh, ow, b, kp), lambda i, j: (0, 0, i, 0)),
            pl.BlockSpec((kp, ft), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((2 * npools, b, ft), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((2 * npools, n, fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="conv_rect_pool",
        interpret=interpret,
    )(patches, weights)


@node(
    data_fields=("conv",),
    meta_fields=(
        "alpha", "max_val", "pool_stride", "pool_size", "activation_dtype"
    ),
)
class FusedConvFeaturizer(Transformer):
    """Convolver -> SymmetricRectifier -> Pooler('sum') -> ImageVectorizer
    as one program; the form it takes follows :func:`conv_form`.

    Construction mirrors :class:`~keystone_tpu.ops.images.Convolver`
    (filters [F, ws, ws, C] or flat, optional whitener means, per-patch
    normalization) plus the rectifier/pooler parameters; ``__call__`` maps
    [N, H, W, C] images to the [N, npy*npx*2F] vectorized features of the
    unfused chain, element order identical.  ``activation_dtype`` is what
    the XLA form stores its activations in; the kernel form stores none.
    """

    def __init__(
        self,
        filters,
        whitener_means=None,
        *,
        pool_stride: int,
        pool_size: int,
        alpha: float = 0.0,
        max_val: float = 0.0,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
        img_channels: int | None = None,
        activation_dtype=jnp.bfloat16,
    ):
        # Reuse Convolver's filter canonicalization + normalization terms.
        self.conv = Convolver(
            filters,
            whitener_means=whitener_means,
            normalize_patches=normalize_patches,
            var_constant=var_constant,
            img_channels=img_channels,
        )
        self.alpha = alpha
        self.max_val = max_val
        self.pool_stride = pool_stride
        self.pool_size = pool_size
        self.activation_dtype = activation_dtype

    def __call__(self, batch):
        f, ws, _, c = self.conv.filters.shape
        n, h, w, _ = batch.shape
        oh, ow = h - ws + 1, w - ws + 1
        axes = _split_axes(batch)
        form = conv_form(jax.default_backend(), oh * ow, ws * ws * c, f, axes)
        mesh = _input_mesh(batch) if form == "kernel" and axes else None
        shards = 1 if mesh is None else mesh.shape[DATA_AXIS]
        # Counted where the program is traced: once a jitted fit.
        trace.metrics.inc(f"conv_form.{form}")
        trace.instant(
            "conv_form", form=form, images=n, positions=oh * ow, filters=f,
            shards=shards,
        )
        if form == "xla":
            return self._xla_form(batch)
        if mesh is None:
            return self._kernel_form(batch)
        return self._sharded_kernel_form(batch, mesh)

    def _xla_form(self, batch):
        # Normalized conv activations, stored compact.  The cast fuses into
        # the conv epilogue; everything downstream reads half the bytes.
        zf = self.conv(batch).astype(self.activation_dtype).astype(jnp.float32)
        pooler = Pooler(self.pool_stride, self.pool_size, None, "sum")
        a = jnp.asarray(self.alpha, jnp.float32)
        mv = jnp.asarray(self.max_val, jnp.float32)
        # Two reduce_windows instead of pool(concat(pos, neg)): the
        # rectifier fuses into each pool's read and the [oh, ow, 2F] concat
        # never materializes.  Pool accumulation stays f32.
        pos = pooler(jnp.maximum(mv, zf - a))
        neg = pooler(jnp.maximum(mv, -zf - a))
        out = jnp.concatenate([pos, neg], axis=-1)  # [N, npy, npx, 2F]
        return out.reshape(out.shape[0], -1)

    def _patch_rows(self, batch, kp: int):
        """[N, H, W, C] f32 -> [oh, ow, N, kp] bf16: row (y, x, n) holds
        image n's patch at (y, x) in the filters' (dy, dx, c) order,
        normalized, then two columns of ones (they carry the filters' bias
        through the product), then zeros.  Images innermost is the order
        the conv emitter writes in, so the transpose moves nothing.

        The MXU gathers the patches: a conv with a one-hot kernel copies
        pixel (y+dy, x+dx, c) to column (dy, dx, c).  Pixels go in as a
        bf16 head and a bf16 remainder on twice the channels, which the
        f32 accumulation adds back, so the copy is good to 2^-17 and the
        normalization, in f32, sees what the reference sees."""
        conv = self.conv
        _, ws, _, c = conv.filters.shape
        d = ws * ws * c
        x = batch.astype(jnp.float32)
        if conv.normalize_patches:
            # Centred by image, which a normalized patch does not see: the
            # variance below no longer cancels against the square of a
            # mean of hundreds.
            x = x - jnp.mean(x, axis=(1, 2, 3), keepdims=True)
        head = x.astype(jnp.bfloat16)
        rest = (x - head.astype(jnp.float32)).astype(jnp.bfloat16)
        eye = np.eye(d, kp, dtype=np.float32).reshape(ws, ws, c, kp)
        onehot = np.concatenate([eye, eye], axis=2)  # head and rest alike
        x2 = jnp.concatenate([head, rest], axis=-1)
        dn = lax.conv_dimension_numbers(
            x2.shape, onehot.shape, ("NHWC", "HWIO", "NHWC")
        )
        p = lax.conv_general_dilated(
            x2, jnp.asarray(onehot, jnp.bfloat16), (1, 1), "VALID",
            dimension_numbers=dn, preferred_element_type=jnp.float32,
        )  # [N, oh, ow, kp]
        if conv.normalize_patches:
            def box(img):
                return lax.reduce_window(
                    jnp.sum(img, axis=-1), 0.0, lax.add,
                    (1, ws, ws), (1, 1, 1), "VALID",
                )

            mu = box(x) / d
            var = (box(x * x) - d * mu * mu) / (d - 1.0)
            p = (p - mu[..., None]) * lax.rsqrt(var + conv.var_constant)[..., None]
        col = lax.broadcasted_iota(jnp.int32, (1, 1, 1, kp), 3)
        p = jnp.where(col < d, p, jnp.where(col < d + 2, 1.0, 0.0))
        return p.astype(jnp.bfloat16).transpose(1, 2, 0, 3)

    def _filter_columns(self, kp: int, fp: int):
        """[kp, fp] bf16: a filter a column, under it ``-f.m`` (the
        whitener means' term) as a bf16 head and remainder against the
        patch rows' two columns of ones; pad filters are zero."""
        conv = self.conv
        f = conv.filters.shape[0]
        flat = conv.filters.reshape(f, -1).astype(jnp.float32)
        if conv.filter_means_dot is None:
            bias = jnp.zeros((f,), jnp.float32)
        else:
            bias = -conv.filter_means_dot.astype(jnp.float32)
        head = bias.astype(jnp.bfloat16)
        rest = (bias - head.astype(jnp.float32)).astype(jnp.bfloat16)
        cols = jnp.concatenate(
            [flat.astype(jnp.bfloat16), head[:, None], rest[:, None]], axis=1
        )
        return jnp.pad(cols, ((0, fp - f), (0, kp - cols.shape[1]))).T

    def _kernel_form(self, batch, interpret: bool = False):
        f, ws, _, c = self.conv.filters.shape
        n, h, w, _ = batch.shape
        oh, ow = h - ws + 1, w - ws + 1
        kp = _patch_depth(ws * ws * c)
        # filters padded to whole tiles: one lane tile or the widest tile
        fp = _round_up(f, _LANES if f <= _LANES else _FILTER_TILE)
        pooler = Pooler(self.pool_stride, self.pool_size, None, "sum")
        wy, wx = pooler.windows(oh), pooler.windows(ow)
        npools = len(wy) * len(wx)

        pad = (-n) % _IMAGES_PER_STEP
        if pad:
            batch = jnp.pad(batch, ((0, pad), (0, 0), (0, 0), (0, 0)))
        out = _pooled_products(
            self._patch_rows(batch, kp), self._filter_columns(kp, fp),
            wy=wy, wx=wx, alpha=float(self.alpha), max_val=float(self.max_val),
            interpret=interpret,
        )
        # [2, npools, N, F] -> [N, npools, 2, F] -> [N, npools*2F]: the
        # node's order, position-major, positive block then negative.
        out = out[:, :n, :f].reshape(2, npools, n, f).transpose(2, 1, 0, 3)
        return out.reshape(n, npools * 2 * f)

    def _sharded_kernel_form(self, batch, mesh, interpret: bool = False):
        """The kernel form on an input whose rows are split over ``mesh``'s
        data axis: every chip runs :meth:`_kernel_form` on its own rows
        against the whole filter bank (the node is replicated), and the
        features stay split as the images were.  No collective."""
        fn = jax.shard_map(
            lambda node_, rows: node_._kernel_form(rows, interpret=interpret),
            mesh=mesh,
            in_specs=(P(), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
            check_vma=False,
        )
        return fn(self, batch)
