"""Pallas TPU kernel: fused symmetric-rectify + sum-pool, single read.

The shipped fused featurizer (ops/conv_fused.py) stores the normalized conv
activations ``z`` once (bf16) and pools pos/neg with two reduce_windows —
each fusing its rectifier but each READING z: ~0.44 MB/image of the
0.59 MB/image total is that one write + two reads.  This kernel computes
BOTH pooled signs from one pass over z: read once, write [2*npools, F]
per image — projected ~0.41 MB/image total for the featurizer.

Why this kernel avoids the traps that sank the im2col kernels (ROOFLINE.md):
it contains NO matmuls and NO reshapes — rectification is elementwise on
the native [b, oh, ow, F] conv layout, row-pooling sums over an OUTER dim
(plain tile adds), and column-pooling sums a sublane range.  All VPU work
on tiles the conv already emits.

MEASURED VERDICT (v5e, 1024 CIFAR images, production shape): the XLA
two-reduce_window form runs 1.16M img/s at 594 KB/img; this kernel runs
311k img/s at 1,896 KB/img — 3.7x SLOWER with 3x MORE traffic.  The
projection failed at the program boundary, not in the kernel: a Pallas
call is an XLA custom call with operand layout constraints, so (a) the
conv can no longer fuse its bf16 epilogue cast into the consumer, and (b)
XLA inserts relayout copies of the full [N, oh, ow, F] activation tensor
to satisfy the constrained tiled layout — the copies cost more than the
saved second read.  Same boundary economics as the im2col kernels in
ROOFLINE.md: beating XLA's fusion pipeline requires removing streams it
is FORCED to keep, and a custom-call boundary adds streams instead.
Kept as the measured proof (its test and chip_smoke.py's leg C call it
directly; no node selects it, and ROADMAP design item D5 decides its
future).  The kernel that did win owns the whole chain and so has no
boundary on the activation tensor at all: ops/conv_fused.py's kernel form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .images import Pooler



def _kernel(z_ref, o_ref, *, wy, wx, alpha: float, max_val: float):
    z = z_ref[...].astype(jnp.float32)  # [b, oh, ow, F]
    pos = jnp.maximum(max_val, z - alpha)
    neg = jnp.maximum(max_val, -z - alpha)
    outs = []
    for t in (pos, neg):
        for y0, ylen in wy:
            # row pool: sum over the outer spatial dim — tile adds
            u = jnp.sum(t[:, y0 : y0 + ylen], axis=1)  # [b, ow, F]
            for x0, xlen in wx:
                # col pool: sublane-range sum
                outs.append(jnp.sum(u[:, x0 : x0 + xlen], axis=1))  # [b, F]
    # [b, 2*npools, F]: sign-major, then (py, px) — epilogue reorders
    o_ref[...] = jnp.stack(outs, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "pool_stride", "pool_size", "alpha", "max_val", "images_per_step",
        "interpret",
    ),
)
def rect_pool_pallas(
    z,
    *,
    pool_stride: int,
    pool_size: int,
    alpha: float = 0.0,
    max_val: float = 0.0,
    images_per_step: int = 8,
    interpret: bool = False,
):
    """[N, oh, ow, F] activations -> [N, npools*2F] pooled features in the
    unfused element order (position-major, pos block then neg block)."""
    n, oh, ow, f = z.shape
    pooler = Pooler(pool_stride, pool_size, None, "sum")
    wy, wx = pooler.windows(oh), pooler.windows(ow)
    npools = len(wy) * len(wx)

    b = images_per_step
    n_pad = (-n) % b
    if n_pad:
        z = jnp.pad(z, ((0, n_pad), (0, 0), (0, 0), (0, 0)))

    kern = functools.partial(
        _kernel, wy=wy, wx=wx, alpha=alpha, max_val=max_val
    )
    out = pl.pallas_call(
        kern,
        grid=((n + n_pad) // b,),
        in_specs=[pl.BlockSpec((b, oh, ow, f), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((b, 2 * npools, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, 2 * npools, f), jnp.float32),
        interpret=interpret,
    )(z)

    # [N, 2, npools, F] -> [N, npools, 2, F] -> [N, npools*2F]
    out = out[:n].reshape(n, 2, npools, f).transpose(0, 2, 1, 3)
    return out.reshape(n, npools * 2 * f)
