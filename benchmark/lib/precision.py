"""Matrix products of the plain references, in a stated precision.

``highest``: float32 with full-precision passes, what a reference is.
``fp8``: both operands rounded to float8_e4m3 under one scale a tensor
(the nearest precision below the bf16 pass the configurations state) and
multiplied exactly: the control of "How correct is decided".
``bf16``: operands rounded to bfloat16, for looking at the program's own
rounding; no cell's control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "bf16", "fp8")
_FP8_MAX = 448.0


def rounded(a, precision: str):
    if precision == "highest":
        return a
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def mm(a, b, precision: str):
    return jnp.matmul(
        rounded(a, precision),
        rounded(b, precision),
        precision=jax.lax.Precision.HIGHEST,
    )
