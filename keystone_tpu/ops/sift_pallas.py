"""Pallas TPU kernel: the assembly of dense SIFT's descriptors, binned planes
-> normalized bytes, each descriptor read once and written once.  It is the
*kernel form* of ``ops/sift.SIFTExtractor``'s tail; ``ops/sift.sift_form``
says when it runs, and ROOFLINE.md ("At the published sizes") holds the
measurements behind it.

What the chip wants of the result decides the layout.  A chunk's descriptors
``u8[N, 128, D]`` live on the device with the 128 descriptor dimensions along
the lanes and the images along the sublanes, the ``D`` frames outermost
(layout ``{1,0,2}``: what the TPU compiler gives that shape at the program's
boundary, and what the gather of sampled columns and the PCA product behind
it read).  The second banded product leaves ``[N, 8, 4*Fy, 4*Fx]``: frame
rows along the sublanes, frame columns along the lanes, the descriptor
dimensions ``(t, by, bx)`` outermost.  Between the two lies a transposition
of every value, which XLA made in five passes over a staged
``bf16[N, D, 128]`` buffer (two relayout copies and a write at an unaligned
lane offset a scale, two norm reductions, the tail, a relayout of the bytes).

Here one grid step takes ``QUAD`` images' block of ``TY`` frame rows, all 32
``(t, by)`` planes of each, and for every frame row:

* reads the row of all planes as one ``[32, 4*Fx]`` matrix.  The planes are
  bfloat16, two frame rows to a 32-bit sublane word, so the block is read as
  ``uint32`` and a word's halves are the two rows, exactly;
* cuts the four ``bx`` column groups and stacks them: ``[128, Fx]``, rows
  ``(bx, t, by)``, a descriptor a column;
* does the tail there in float32: L2 norm, clamp 0.2, L2 norm, contrast
  threshold, ``min(floor(512 v), 255)``.  A descriptor's 128 values lie along
  the sublanes, so each norm is a sum of vregs and no lane is shuffled;
* transposes on the MXU: one product with a 128x128 permutation matrix,
  contracting the rows, gives ``[Fx, 128]`` with the lanes in descriptor
  order ``(by, bx, t)``.  The quantized values are whole numbers to 255,
  which bfloat16 holds, and a one-hot product accumulated in float32 moves
  them exactly;
* packs the four images' bytes into one 32-bit word (an image a byte: the
  u8 tiling's four rows a sublane word) and stores it at its frames' rows of
  a VMEM accumulator ``[TY*Fx, 8, 128]``.

After the 8 quads of a 32-image half the accumulator, reinterpreted as bytes,
is a block ``u8[TY*Fx, 32, 128]`` of the output: whole (32, 128) byte tiles,
frames outermost, so a scale's frame rows and the four scales join along the
outermost axis, where every offset is aligned.  The kernel copies the block
to its frames of the chunk's one ``u8[D, N, 128]`` itself (a scale's call
takes the array the scale before returned and writes in place), so no join
of the scales exists either.  Nothing of shape ``[N, D, 128]`` exists wider
than a byte.

The sampling matrices are built bins-major for this (``ops/sift.py``): rows
``(by, y)`` with ``y`` padded to ``TY`` by rows of zeros, columns
``(bx, x)``.  The products' contractions are the XLA form's, so the planes
hold the same values; the only licence is the order of the float32 sums over
a descriptor's 128 values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sift import CONTRAST_THRESHOLD, NUM_BIN_T, NUM_BIN_XY

LANES = 128
#: frame rows a grid step: one bfloat16 tile of sublanes
TY = 16
#: images a 32-bit word of the byte output holds (u8 tiles pack 4 rows a sublane)
QUAD = 4
#: images an output block: one u8 tile of sublanes
HALF = 32
_PLANES = NUM_BIN_T * NUM_BIN_XY  # (t, by) planes an image: 32
_DIMS = _PLANES * NUM_BIN_XY  # 128
#: the most VMEM a call may ask (v5e holds 128 MiB)
VMEM_CAP = 100 * 1024 * 1024


def vmem_bytes(fx: int) -> int:
    """What a call asks of VMEM at ``fx`` frames a row: the input block twice
    (the pipeline's two buffers) and as words, the accumulator, two staging
    slots."""
    block_in = QUAD * _PLANES * TY * (NUM_BIN_XY * fx + LANES) * 2
    block_out = TY * fx * HALF * _DIMS
    return 3 * block_in + 3 * block_out + (8 << 20)


def fits(images: int, fx: int) -> bool:
    """Whether a batch can take the kernel: whole byte tiles of images, and
    a block of ``fx`` frames a row inside VMEM."""
    return images % HALF == 0 and fx > 0 and vmem_bytes(fx) <= VMEM_CAP


def _permutation() -> np.ndarray:
    """[128, 128] one-hot: stacked row ``(bx, t, by)`` -> lane ``(by, bx, t)``."""
    p = np.zeros((_DIMS, _DIMS), np.float32)
    for bx in range(NUM_BIN_XY):
        for t in range(NUM_BIN_T):
            for by in range(NUM_BIN_XY):
                row = (bx * NUM_BIN_T + t) * NUM_BIN_XY + by
                p[row, (by * NUM_BIN_XY + bx) * NUM_BIN_T + t] = 1.0
    return p


def _tail(d):
    """``[128, frames]`` float32 descriptors, one a column -> quantized
    float32 whole numbers to 255: ``SIFTExtractor._xla_form``'s tail,
    operation for operation.  A descriptor's values lie along the sublanes,
    so both norms are sums of vregs: no lane leaves its place."""
    norms = jnp.sqrt(jnp.sum(d * d, axis=0, keepdims=True))
    clamped = jnp.minimum(d / jnp.maximum(norms, 1e-12), 0.2)
    norms2 = jnp.sqrt(jnp.sum(clamped * clamped, axis=0, keepdims=True))
    final = clamped / jnp.maximum(norms2, 1e-12)
    final = jnp.where(norms > CONTRAST_THRESHOLD, final, 0.0)
    return jnp.minimum(jnp.floor(512.0 * final), 255.0)


def _assemble_kernel(x_ref, p_ref, *rest, fy: int, fx: int, offset: int):
    """x_ref ``bf16[QUAD*32, TY, Q]``: rows (image, t, by); o_ref
    ``u8[frames, N, 128]`` in HBM (``rest`` starts with the same buffer as an
    aliased operand when an earlier scale has written to it); words
    ``u32[QUAD*32, TY/2, Q]``; acc_ref ``i32[TY*fx, HALF/QUAD, 128]``; stage_ref
    ``u8[2, TY*fx, HALF, 128]``."""
    o_ref, words, acc_ref, stage_ref, sem = rest[-5:]
    yb, h, j = (pl.program_id(a) for a in range(3))
    halves_n = pl.num_programs(1)
    perm = p_ref[...]

    # frame rows 2k and 2k+1 are the halves of word row k (a copy, not
    # ``x_ref.bitcast``: the TPU interpreter reads no reinterpreted ref)
    def as_words(i, carry):
        words[i] = pltpu.bitcast(x_ref[i], jnp.uint32)
        return carry

    jax.lax.fori_loop(0, QUAD * _PLANES, as_words, 0)

    def row_pair(k, carry):
        w = words[:, k, :]
        packed = [None, None]  # a frame row's bytes, four images a word
        for i in range(QUAD):
            planes = w[i * _PLANES : (i + 1) * _PLANES]  # [(t, by), (bx, x)]
            stacked = jnp.concatenate(
                [planes[:, b * fx : (b + 1) * fx] for b in range(NUM_BIN_XY)], axis=0
            )  # [(bx, t, by), x]
            # a bfloat16 is the high half of its float32
            halves = (
                pltpu.bitcast(stacked << 16, jnp.float32),
                pltpu.bitcast(stacked & jnp.uint32(0xFFFF0000), jnp.float32),
            )
            for r, d in enumerate(halves):
                # whole numbers to 255 are bfloat16's, and a one-hot product
                # accumulated in float32 moves them exactly
                q = jax.lax.dot_general(
                    _tail(d).astype(jnp.bfloat16), perm, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.int32)  # [x, (by, bx, t)]
                packed[r] = q if i == 0 else packed[r] | (q << (8 * i))
        for r, word in enumerate(packed):
            acc_ref[pl.ds((2 * k + r) * fx, fx), j, :] = word
        return carry

    # the last block of frame rows holds fewer than TY: the rows past ``fy``
    # (zeros of the padded sampling matrix) are not worked on, and what the
    # accumulator holds there lies past the output array
    rows = jnp.minimum(TY, fy - pl.program_id(0) * TY)
    jax.lax.fori_loop(0, (rows + 1) // 2, row_pair, 0)

    # A finished block of 32 images leaves as bytes, by a copy of its own:
    # the scales share one output, and a scale's frames start where the
    # one before ended, which no block index reaches.  Two staging slots, so
    # the copy of one block runs under the work on the next.
    whole = pl.cdiv(fy, TY) - 1  # blocks of TY frame rows
    short = fy - whole * TY  # frame rows of the last block

    def copy(slot, block, half, frame_rows):
        return pltpu.make_async_copy(
            stage_ref.at[slot, pl.ds(0, frame_rows * fx)],
            o_ref.at[
                pl.ds(offset + block * TY * fx, frame_rows * fx), pl.ds(half * HALF, HALF)
            ],
            sem.at[slot],
        )

    def wait(emitted):
        """For the copy of the ``emitted``-th block (its size is what counts)."""
        slot = emitted % 2
        if whole:
            pl.when(emitted // halves_n < whole)(lambda: copy(slot, 0, 0, TY).wait())
        pl.when(emitted // halves_n == whole)(lambda: copy(slot, 0, 0, short).wait())

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        emitted = yb * halves_n + h
        slot = emitted % 2
        pl.when(emitted >= 2)(lambda: wait(emitted - 2))  # the slot's last copy

        def frame_row(r, carry):
            at = pl.ds(r * fx, fx)
            stage_ref[slot, at] = pltpu.bitcast(acc_ref[at], jnp.uint8)
            return carry

        jax.lax.fori_loop(0, rows, frame_row, 0)
        if whole:
            pl.when(yb < whole)(lambda: copy(slot, yb, h, TY).start())
        pl.when(yb == whole)(lambda: copy(slot, yb, h, short).start())

        @pl.when(emitted == (whole + 1) * halves_n - 1)
        def _drain():
            pl.when(emitted >= 1)(lambda: wait(emitted - 1))
            wait(emitted)


def assemble_scale(
    sampled, into=None, *, fy: int, fx: int, offset: int, frames: int, interpret: bool = False
):
    """One scale's descriptors as bytes, frames outermost, written at frame
    ``offset`` of the chunk's ``u8[frames, N, 128]``, which is returned.

    sampled: ``bf16[N, 8, 4*fyp, Q]``, the second banded product's result
    with rows ``(by, y)`` (``fyp``: ``fy`` rounded up to ``TY``, the rows past
    ``fy`` anything) and columns ``(bx, x)`` in the first ``4*fx`` of ``Q``, a
    multiple of 128; ``N`` a multiple of ``HALF``.  into: the array an earlier
    scale's call returned (written in place), or None for a new one, whose
    other frames are then unspecified.  The scale's ``fy*fx`` frames are
    y-major, a descriptor's dimensions ``(by, bx, t)``.
    """
    n, t, p, q = sampled.shape
    fyp = p // NUM_BIN_XY
    if (
        sampled.dtype != jnp.bfloat16 or t != NUM_BIN_T or n % HALF or fyp % TY
        or fyp < fy or q % LANES or q < NUM_BIN_XY * fx or offset + fy * fx > frames
    ):
        raise ValueError(
            f"assemble_scale: {sampled.dtype}{sampled.shape} for {fy}x{fx} frames "
            f"at {offset} of {frames}"
        )
    out = jax.ShapeDtypeStruct((frames, n, _DIMS), jnp.uint8)
    if into is not None and (into.shape, into.dtype) != (out.shape, out.dtype):
        raise ValueError(f"assemble_scale: into {into.dtype}{into.shape}, not {out}")
    joined = () if into is None else (into,)
    return pl.pallas_call(
        functools.partial(_assemble_kernel, fy=fy, fx=fx, offset=offset),
        grid=(pl.cdiv(fy, TY), n // HALF, HALF // QUAD),
        in_specs=[
            pl.BlockSpec(
                (QUAD * _PLANES, TY, q),
                lambda yb, h, j: (h * (HALF // QUAD) + j, yb, 0),
            ),
            pl.BlockSpec((_DIMS, _DIMS), lambda yb, h, j: (0, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in joined],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=out,
        input_output_aliases={2: 0} if joined else {},
        scratch_shapes=[
            pltpu.VMEM((QUAD * _PLANES, TY // 2, q), jnp.uint32),
            pltpu.VMEM((TY * fx, HALF // QUAD, _DIMS), jnp.int32),
            pltpu.VMEM((2, TY * fx, HALF, _DIMS), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            # in order: the staging slots alternate from block to block
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP, vmem_bytes(fx)),
        ),
        name="sift_assemble",
        interpret=interpret,
    )(sampled.reshape(n * _PLANES, fyp, q), jnp.asarray(_permutation(), jnp.bfloat16), *joined)
