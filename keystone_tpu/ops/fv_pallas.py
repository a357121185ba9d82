"""Pallas TPU kernel: the Fisher vector's sufficient statistics in one pass,
the posteriors alive only in VMEM.  It is the *kernel form* of
``ops/fisher.FisherVector``; ``ops/fisher.fv_form`` says when it runs, and
that module's docstring holds the measured table behind the rule.

The XLA form (``ops/fisher.fisher_vector``) writes the ``[cols, k]``
posteriors to HBM and crosses them seven times (two log-density products,
the softmax's max, sum and normalization, two moment products).  Here a grid
step loads one ``[d, C]`` block of projected descriptors, forms the
``[k, C]`` logits, the softmax and the validity mask in VMEM, and adds the
block's share to ``s0 [k]``, ``s1 [d, k]``, ``s2 [d, k]``, which stay
resident across the inner grid axis (their block index is constant in it).
The kernel's only HBM operands are the descriptors, read once, and the three
statistics.  Descriptors are COLUMNS (``[d, C]`` blocks), so the long axis
is the lane axis whatever ``k`` is.

This is the TPU-native re-own of the enceval FV accumulation loop the
reference calls through JNI (src/main/cpp/EncEval.cxx:19-120, whose
fisher<float> encoder likewise accumulates statistics descriptor by
descriptor in cache).

Parameterization: with inv_var = 1/variances,

    logit = A^T x - B^T (x*x) + c                           [k, C]
    A = means * inv_var [d, k];  B = 0.5 * inv_var [d, k]
    c = log w - 0.5*(sum_d means^2*inv_var + sum_d log var + d*log 2pi) [k]

then q = softmax_k(logit) masked to the first ``counts[i]`` descriptors,
s0 = sum_n q, s1 = x q^T, s2 = (x*x) q^T: the mathematics of
``fisher_vector``, reassociated only.

**Precision, product by product, is the XLA form's** (what
``benchmark/configs/voc_sift_fv_256.json`` states): the two log-density
products at full float32 (``Precision.HIGHEST``, as ``solvers/gmm._log_resp``:
their expanded square cancels, PERF.md PR 28); the two moment products in one
bfloat16 pass with float32 accumulation, which is what a TPU makes of the XLA
form's default-precision ``x.T @ q`` (operands rounded to bfloat16 once,
``x*x`` squared in float32 first); ``s0``, max, exp and sum in float32.
Asking ``HIGHEST`` for all four, as this file did until PR 29, is 12 MXU
passes of moment products where the XLA form pays 2.

The descriptor count need be no multiple of the block: the grid covers it
with a ragged last block, whose lanes past the array are unspecified, so
the descriptors pass a ``where`` there (a product with a 0/1 mask would keep
a NaN), and the posteriors take their zeros from the masked reciprocal of the
softmax's sum.  No padded copy of the descriptors is
made.  Ragged *images* enter as per-image COUNTS (an SMEM operand read by
program id), not a dense ``[N, cols]`` mask: a mask row violates Mosaic's
(8, 128) block rule, and an in-kernel ``iota < count`` is free.  Arbitrary
(non-prefix) masks take the XLA form.  A centre of weight 0 has ``c = -inf``,
so its logits are ``-inf`` and its posteriors exactly 0 (never NaN: the
``-inf`` is added, not multiplied), as ``fisher._fv_from_stats`` assumes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# s0 is [k] per image, but a (1, k) output block violates Mosaic's
# (sublane, lane) divisibility; the accumulator is padded to 8 sublanes and
# row 0 sliced out at the end.
_S0_PAD = 8
_LANES = 128
#: Descriptors a grid step (tools/fv_form_probe.py --blocks, PR 29): the
#: [k, BLOCK] f32 logits are 2 MB at k 256.
BLOCK = 2048


def _fv_stats_kernel(
    cnt_ref, x_ref, at_ref, bt_ref, c_ref, s0_ref, s1_ref, s2_ref, *, block: int
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # validity: global column index < count for this image (scalar SMEM
    # read); the count is at most the array's width, so this also covers the
    # lanes of a ragged last block that lie past the array
    col = j * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    valid = col < cnt_ref[0, i]

    x = jnp.where(valid, x_ref[0], 0.0)  # [d, C] — descriptors as columns
    x2 = x * x
    # Full float32: Mosaic's default f32 matmul rounds its operands to
    # bf16, and the expanded square cancels (solvers/gmm._log_resp).
    exact = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    logit = exact(at_ref[...], x) - exact(bt_ref[...], x2) + c_ref[...]  # [k, C]
    e = jnp.exp(logit - jnp.max(logit, axis=0, keepdims=True))
    # x is 0 where not valid, so e is finite there and the product an exact
    # 0: the mask rides the [1, C] reciprocal, not the [k, C] posteriors
    q = e * jnp.where(valid, 1.0 / jnp.sum(e, axis=0, keepdims=True), 0.0)

    s0_ref[0, 0, :] += jnp.sum(q, axis=1)
    # One bf16 pass, f32 accumulation, contracting the block axis:
    # [d, C] x [k, C] -> [d, k].
    qb = q.astype(jnp.bfloat16)
    moment = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s1_ref[0] += moment(x.astype(jnp.bfloat16), qb)
    s2_ref[0] += moment(x2.astype(jnp.bfloat16), qb)


@functools.partial(jax.jit, static_argnames=("moment_dtype",))
def fv_stats_jnp(x, counts, means, variances, weights, *, moment_dtype=jnp.bfloat16):
    """The kernel's statistics in plain jnp, operands as ``fv_stats_pallas``
    takes them: what the tests, chip_smoke.py leg C and
    tools/fv_form_probe.py hold the kernel to.  The moment products'
    operands are rounded to ``moment_dtype`` explicitly, so bfloat16 gives on
    any backend what a TPU's default precision makes of the XLA form, and
    float32 the form with no rounding."""
    from ..solvers.gmm import _log_resp

    cols = x.shape[2]
    if counts is None:
        counts = jnp.full((x.shape[0],), cols, jnp.int32)

    def one(xi, count):  # xi [d, cols]
        q = jax.nn.softmax(_log_resp(xi.T, means, variances, weights), axis=-1)
        q = jnp.where(jnp.arange(cols)[:, None] < count, q, 0.0)
        moment = functools.partial(
            jnp.dot,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        qr = q.astype(moment_dtype)
        return (
            jnp.sum(q, axis=0),
            moment(xi.astype(moment_dtype), qr),
            moment((xi * xi).astype(moment_dtype), qr),
        )

    return jax.vmap(one)(x.astype(jnp.float32), counts)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fv_stats_pallas(
    x, counts, means, variances, weights, *, block: int = BLOCK, interpret: bool = False
):
    """Batched FV sufficient statistics in one fused pass.

    x: [N, d, cols] descriptor matrices (descriptors as columns — the
    FisherVector node's native layout); counts: [N] int32 valid-descriptor
    counts (prefix-valid ragged batches) or None for all-valid;
    means/variances: [d, k]; weights: [k].
    Returns (s0 [N, k], s1 [N, d, k], s2 [N, d, k]).
    """
    n, d, cols = x.shape
    k = means.shape[1]
    # short descriptor batches: no 2048-lane block for a ~700-column image
    block = min(block, -(-cols // _LANES) * _LANES)
    if counts is None:
        counts = jnp.full((n,), cols, jnp.int32)
    counts = jnp.minimum(counts.astype(jnp.int32), cols).reshape(1, n)  # one SMEM block

    inv_var = 1.0 / variances
    at = (means * inv_var).T.astype(jnp.float32)  # [k, d]
    bt = (0.5 * inv_var).T.astype(jnp.float32)  # [k, d]: the half is exact
    c = (
        jnp.log(weights)
        - 0.5
        * (
            jnp.sum(means * means * inv_var, axis=0)
            + jnp.sum(jnp.log(variances), axis=0)
            + d * jnp.log(2.0 * jnp.pi)
        )
    ).astype(jnp.float32)[:, None]  # [k, 1]

    s0, s1, s2 = pl.pallas_call(
        functools.partial(_fv_stats_kernel, block=block),
        grid=(n, pl.cdiv(cols, block)),
        in_specs=[
            pl.BlockSpec((1, n), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, block), lambda i, j: (i, 0, j)),
            pl.BlockSpec((k, d), lambda i, j: (0, 0)),
            pl.BlockSpec((k, d), lambda i, j: (0, 0)),
            pl.BlockSpec((k, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _S0_PAD, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, d, k), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, _S0_PAD, k), jnp.float32),
            jax.ShapeDtypeStruct((n, d, k), jnp.float32),
            jax.ShapeDtypeStruct((n, d, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="fv_stats",
        interpret=interpret,
    )(counts, x.astype(jnp.float32), at, bt, c)
    return s0[:, 0, :], s1, s2
