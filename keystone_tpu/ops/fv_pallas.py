"""Pallas TPU kernel: fused Fisher-vector sufficient statistics.

The XLA formulation of FV encoding (ops/fisher.py) materializes the [n, k]
responsibilities to HBM and then runs three separate contractions (s0, s1,
s2) over the descriptors.  This kernel makes ONE pass: each descriptor chunk
is loaded to VMEM once; posterior logits, the softmax, the validity mask and
all three statistics accumulate before the next chunk streams in.  The
per-image [d, k] accumulators stay VMEM-resident across the chunk loop
(their output block index is constant in the inner grid axis).  Descriptors
are processed as COLUMNS ([d, chunk] blocks) so the long chunk axis is the
lane axis — the row-major variant wastes 7/8 of the lanes on the [*, k]
tensors and measured 2.3x slower.

This is the TPU-native re-own of the enceval FV accumulation loop the
reference calls through JNI (src/main/cpp/EncEval.cxx:19-120, whose
fisher<float> encoder likewise accumulates statistics descriptor-by-
descriptor in cache) — SURVEY §2.8's "native-quality kernel" for the FV op.

MEASURED VERDICT (v5e, 64 images x 13165 descriptors, d=64, K=16, serial
in-graph chain timing): XLA fused path 0.95 ms/batch, this kernel (best
chunk=2048) 1.61 ms/batch.  XLA's own fusion of the softmax + three gemms
beats the hand-written kernel by 1.7x on the production shape, so the
XLA path is the DEFAULT and this kernel is opt-in (KEYSTONE_PALLAS=1) —
kept as the measured proof behind that design choice and as the template
for shapes where the balance tips (e.g. much larger K, where the [n, k]
posterior spill that XLA materializes grows linearly).  That timing is from
round 4 (2026-07-30) with default-precision dots; the dots have since
moved to ``Precision.HIGHEST`` (see the kernel body), which Mosaic compiles
and which matches the f32 reference to 3.7e-6 at this shape (chip run,
PR 21) — and can only have made the kernel slower.  Not re-timed.

Parameterization: with inv_var = 1/variances,

    logit^T = A^T x^T - 0.5 * B^T (x*x)^T + c         [k, C]
    A = means * inv_var [d, k];  B = inv_var [d, k]
    c = log w - 0.5*(sum_d means^2*inv_var + sum_d log var + d*log 2pi) [k]

then q = softmax_k(logit) masked to the first ``counts[i]`` descriptors,
s0 = sum_n q, s1 = x^T q, s2 = (x*x)^T q — identical math to
ops/fisher.fisher_vector, reassociated only.

Ragged descriptor counts enter as per-image COUNTS (an SMEM operand read
scalar-wise by program id), not a dense [N, D] mask: Mosaic requires block
last-two-dims of (8k, 128m), which a mask row violates, and an in-kernel
``iota < count`` compare is free.  Arbitrary (non-prefix) masks take the
XLA path in FisherVector.
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


def _fv_stats_kernel(
    cnt_ref, x_ref, at_ref, bt_ref, c_ref, s0_ref, s1_ref, s2_ref, *, chunk: int
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # HIGHEST: Mosaic's default f32 matmul rounds its operands to bf16,
    # which puts these statistics ~1e-2 (relative) off the f32 reference
    # (chip run, PR 21) — two orders outside the kernel's test tolerance.
    dot = functools.partial(
        jax.lax.dot_general,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    mat = (((1,), (0,)), ((), ()))
    x = x_ref[0]  # [d, C] — descriptors as columns
    x2 = x * x
    logit = (
        dot(at_ref[...], x, mat) - 0.5 * dot(bt_ref[...], x2, mat) + c_ref[...]
    )  # [k, C]
    m = jnp.max(logit, axis=0, keepdims=True)
    e = jnp.exp(logit - m)
    q = e / jnp.sum(e, axis=0, keepdims=True)  # [k, C]

    # validity: global column index < count for this image (scalar SMEM read)
    col = j * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    q = q * (col < cnt_ref[0, i]).astype(jnp.float32)

    s0_ref[0, 0, :] += jnp.sum(q, axis=1)
    # contract over the chunk axis: [d, C] x [k, C] -> [d, k]
    over_chunk = (((1,), (1,)), ((), ()))
    s1_ref[0] += dot(x, q, over_chunk)
    s2_ref[0] += dot(x2, q, over_chunk)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fv_stats_pallas(
    x, counts, means, variances, weights, *, chunk: int = 2048, interpret: bool = False
):
    """Batched FV sufficient statistics in one fused pass.

    x: [N, d, D] descriptor matrices (descriptors as columns — the
    FisherVector node's native layout); counts: [N] int32 valid-descriptor
    counts (prefix-valid ragged batches) or None for all-valid;
    means/variances: [d, k]; weights: [k].
    Returns (s0 [N, k], s1 [N, d, k], s2 [N, d, k]).
    """
    n, d, d_count = x.shape
    k = means.shape[1]
    # short descriptor batches: don't pad a ~700-column image up to a 2048
    # chunk of mostly-zero gemm work — clamp to the lane-aligned column count
    chunk = min(chunk, max(128, -(-d_count // 128) * 128))
    if counts is None:
        counts = jnp.full((n,), d_count, jnp.int32)
    counts = counts.astype(jnp.int32).reshape(1, n)  # one full SMEM block

    inv_var = 1.0 / variances
    at = (means * inv_var).T.astype(jnp.float32)  # [k, d]
    bt = inv_var.T.astype(jnp.float32)  # [k, d]
    c = (
        jnp.log(weights)
        - 0.5
        * (
            jnp.sum(means * means * inv_var, axis=0)
            + jnp.sum(jnp.log(variances), axis=0)
            + d * jnp.log(2.0 * jnp.pi)
        )
    ).astype(jnp.float32)[:, None]  # [k, 1]

    # pad the descriptor axis to a chunk multiple; counts exclude pad columns
    pad = (-d_count) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    n_chunks = (d_count + pad) // chunk

    kernel = functools.partial(_fv_stats_kernel, chunk=chunk)
    s0, s1, s2 = pl.pallas_call(
        kernel,
        grid=(n, n_chunks),
        in_specs=[
            pl.BlockSpec((1, n), lambda i, j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, chunk), lambda i, j: (i, 0, j)),
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
        interpret=interpret,
    )(counts, x.astype(jnp.float32), at, bt, c)
    return s0[:, 0, :], s1, s2
