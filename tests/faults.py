"""Fault-injection harness for the resilience suite (NOT a test module —
imported by tests/test_resilience.py and usable from the REPL to shake any
pipeline).

Spark gave the reference a substrate that was *constantly* injected with
faults in production (task preemption, straggler kills, bad input records);
our JAX port has to earn that hardness on purpose.  Three fault families:

* **corrupt data**: ``corrupt_jpeg`` mangles a valid JPEG stream (keeps the
  SOI marker so the native decoder engages and must fail cleanly);
  ``make_image_tar`` builds tar archives with chosen members corrupted or
  truncated — the loader must skip-and-count, never crash.
* **transient IO**: ``flaky`` / ``transient_faults`` wrap a callable (or
  patch a module attribute) to raise ``OSError`` for the first N calls and
  then behave — exercising core.resilience.retry's backoff path.
* **poisoned numerics**: ``inject_nan`` sprinkles NaN into a batch;
  ``rank_deficient_gram`` builds a gram whose unregularized Cholesky is
  guaranteed to fail — exercising the solver jitter-retry and the
  ``assert_all_finite`` fit guards.
* **device memory exhaustion**: ``resource_exhausted_error`` builds the
  exact exception XLA raises on HBM OOM (``XlaRuntimeError`` carrying
  RESOURCE_EXHAUSTED); ``oom_faults`` patches a callable to die with it
  for the first N calls — exercising the solvers' degradation-ladder
  step-down (core.memory.run_ladder) without needing a real OOM.
"""

from __future__ import annotations

import contextlib
import io
import tarfile

import numpy as np


def make_jpeg_bytes(rng, h: int = 48, w: int = 48, quality: int = 90) -> bytes:
    """A valid random-texture JPEG."""
    from PIL import Image as PILImage

    arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    buf = io.BytesIO()
    PILImage.fromarray(arr).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def corrupt_jpeg(data: bytes, rng) -> bytes:
    """Mangle a JPEG stream: keep the SOI marker (so decoders engage rather
    than reject on sniffing), truncate the tail, and scramble a slice of
    the entropy-coded body."""
    n = len(data)
    keep = max(8, n // 3)
    body = bytearray(data[:keep])
    lo = min(6, len(body) - 1)
    scramble = rng.integers(0, 256, max(0, keep - lo), dtype=np.uint8)
    body[lo:] = scramble.tobytes()
    return bytes(body[:2] + body[2:])  # SOI preserved at [:2]


def corrupt_jpeg_entropy(data: bytes, mode: str = "truncate") -> bytes:
    """Damage ONLY the entropy-coded scan of a baseline JPEG — every
    header (SOF/DQT/DHT/SOS) stays intact, so a decoder that validates
    headers engages the scan and must fail there, typed.  Two
    deterministic modes: ``truncate`` chops the scan mid-stream (bits run
    out inside an MCU), ``marker`` splices an early EOI into the scan
    (the MCU count comes up short).  Both are guaranteed-detectable, so
    the ``jpeg_corrupt_entropy`` chaos family never depends on random
    bytes happening to form an invalid Huffman sequence."""
    sos = data.find(b"\xff\xda")
    if sos < 0:
        raise ValueError("not a JPEG with an SOS marker")
    seg_len = (data[sos + 2] << 8) | data[sos + 3]
    scan = sos + 2 + seg_len
    keep = scan + max(4, (len(data) - scan) // 3)
    if mode == "truncate":
        return data[:keep]
    if mode == "marker":
        return data[:keep] + b"\xff\xd9"
    raise ValueError(f"unknown entropy corruption mode {mode!r}")


def make_image_tar(
    path: str,
    n_images: int,
    rng,
    corrupt: tuple[int, ...] = (),
    h: int = 48,
    w: int = 48,
    name_fmt: str = "img_{:04d}.jpg",
    corrupt_fn=None,
) -> list[str]:
    """Write a tar of JPEGs; members whose index is in ``corrupt`` carry
    mangled JPEG bytes (decode must fail, mid-archive, without breaking
    the members after them).  ``corrupt_fn(data)`` overrides HOW a member
    is mangled (default: :func:`corrupt_jpeg`; the ``jpeg_corrupt_entropy``
    chaos family passes :func:`corrupt_jpeg_entropy` to damage only the
    scan).  Returns the member names."""
    names = []
    with tarfile.open(path, "w") as tf:
        for i in range(n_images):
            data = make_jpeg_bytes(rng, h, w)
            if i in corrupt:
                data = (
                    corrupt_fn(data)
                    if corrupt_fn is not None
                    else corrupt_jpeg(data, rng)
                )
            info = tarfile.TarInfo(name_fmt.format(i))
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
            names.append(info.name)
    return names


def truncate_tail(path: str, nbytes: int = 1024) -> None:
    """Chop the last ``nbytes`` off an archive — a partially-transferred
    tar whose final member (and end-of-archive blocks) are gone."""
    import os

    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(max(0, size - nbytes))


def flaky(fn, failures: int, exc: type[BaseException] = OSError, message: str = "injected transient fault"):
    """Wrap ``fn`` to raise ``exc`` for its first ``failures`` calls, then
    delegate.  The wrapper exposes ``.calls`` and ``.failures_left``."""
    state = {"calls": 0, "left": failures}

    def wrapped(*args, **kwargs):
        state["calls"] += 1
        if state["left"] > 0:
            state["left"] -= 1
            raise exc(f"{message} (call {state['calls']})")
        return fn(*args, **kwargs)

    wrapped.state = state
    return wrapped


@contextlib.contextmanager
def transient_faults(
    obj,
    attr: str,
    failures: int,
    exc: type[BaseException] = OSError,
    message: str = "injected transient fault",
):
    """Patch ``obj.attr`` with a :func:`flaky` wrapper for the duration of
    the block — e.g. ``transient_faults(image_loaders.tarfile, "open", 2)``
    makes the next two tar opens fail with OSError."""
    original = getattr(obj, attr)
    wrapper = flaky(original, failures, exc, message)
    setattr(obj, attr, wrapper)
    try:
        yield wrapper
    finally:
        setattr(obj, attr, original)


def xla_runtime_error_type() -> type[BaseException]:
    """The exception type XLA raises at dispatch/execution time."""
    import jax

    return jax.errors.JaxRuntimeError


def resource_exhausted_error(nbytes: int = 1 << 33) -> BaseException:
    """An exception indistinguishable from XLA's device-memory exhaustion:
    same type, same RESOURCE_EXHAUSTED grammar as a real TPU allocator
    failure — what ``core.memory.is_oom_error`` must recognize."""
    return xla_runtime_error_type()(
        f"RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        f"{nbytes} bytes. (injected fault)"
    )


@contextlib.contextmanager
def oom_faults(obj, attr: str, failures: int = 1):
    """Patch ``obj.attr`` to raise RESOURCE_EXHAUSTED for its first
    ``failures`` calls — e.g. ``oom_faults(block, "_execute_fused_bcd", 1)``
    makes the next fused BCD dispatch die exactly the way a too-small HBM
    does, driving the fit ladder's one-tier step-down."""
    with transient_faults(
        obj,
        attr,
        failures,
        exc=xla_runtime_error_type(),
        message="RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "8589934592 bytes. (injected fault)",
    ) as wrapper:
        yield wrapper


def inject_nan(batch, rng, frac: float = 0.01):
    """Copy of ``batch`` with ~``frac`` of entries replaced by NaN.

    ``order="C"`` matters: the default ``np.array`` copy preserves the
    source's memory layout, and on a transposed input (e.g. the CIFAR
    loader's NHWC images) ``reshape(-1)`` of that layout is a COPY — the
    NaN writes would be silently discarded and the injection a no-op."""
    out = np.array(batch, copy=True, order="C")
    flat = out.reshape(-1)
    k = max(1, int(frac * flat.size))
    idx = rng.choice(flat.size, k, replace=False)
    flat[idx] = np.nan
    return out


def rank_deficient_gram(rng, n: int = 32, d: int = 8, k: int = 2):
    """(AᵀA, AᵀB) from a design matrix with duplicated columns — the
    unregularized gram is singular, so ``cho_factor`` yields non-finite
    values and only jitter recovery can solve it."""
    a = rng.normal(size=(n, d)).astype(np.float32)
    a[:, d // 2 :] = a[:, : d - d // 2]
    b = rng.normal(size=(n, k)).astype(np.float32)
    return a.T @ a, a.T @ b
