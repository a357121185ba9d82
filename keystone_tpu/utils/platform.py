"""Platform/runtime gates shared across ops and entry points: which device
the process runs on, where its compile cache lives, and how the native
libraries are built.

No module-level jax import: the native loaders call
:func:`build_native_library` from spawned decode workers, which must never
import jax (tests/test_lazy_import.py)."""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess

_logger = logging.getLogger("keystone_tpu.platform")

#: The checkout this package was imported from.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: JAX's own variable.  When the machine sets it the cache is placed from
#: outside and this module touches nothing.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache for this process:
    ``JAX_COMPILATION_CACHE_DIR`` when the machine sets it, otherwise one
    fixed path inside the checkout — never a temporary or per-process
    directory, which the next run could not find again."""
    return os.environ.get(COMPILE_CACHE_ENV, "").strip() or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def describe_device() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX selected — the
    form every record names its device in."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def init_device() -> dict:
    """Call once per entry point, BEFORE the first compile (JAX binds the
    cache directory at its first compilation).  Returns
    :func:`describe_device` after logging it, so a run that fell back to
    the CPU says so in its first line, and places the compile cache
    (:func:`compile_cache_dir`).

    With ``JAX_COMPILATION_CACHE_DIR`` unset, an accelerator run caches
    every program, however quick its compile, so a second run of the same
    command compiles nothing.  A CPU run (tests, rehearsals) caches
    nothing: its compiles take seconds, and XLA:CPU reloads each cached
    executable under a machine-feature warning of several KB."""
    import jax

    device = describe_device()
    cache = os.environ.get(COMPILE_CACHE_ENV, "").strip()
    if not cache and device["platform"] != "cpu":
        cache = compile_cache_dir()
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _logger.info(
        "device: %d x %s (%s); jax %s; compile cache %s",
        device["count"], device["kind"], device["platform"],
        jax.__version__, cache or "off",
    )
    return device


def build_native_library(src: str, stem: str, link: tuple = ()) -> str | None:
    """Path of the shared library compiled from ``src`` with the system
    g++, building it when absent; ``None`` when the build fails.

    The file is named by a hash of the source and the build command and
    lives in ``<src dir>/build/`` (ignored by git), so a binary that was
    copied along with the tree can never be loaded for a source it was not
    built from.  Fork failures and filesystem hiccups retry with backoff
    (``core.resilience.retry``); a compile error or a blown 120 s timeout
    is not transient and fails at once.  The output is renamed into place,
    so concurrent builders (decode workers) never load a half-written
    file."""
    from ..core.resilience import retry

    flags = ("-O2", "-shared", "-fPIC")
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + repr((flags, tuple(link))).encode()
        ).hexdigest()
    build_dir = os.path.join(os.path.dirname(src), "build")
    lib = os.path.join(build_dir, f"lib{stem}-{digest[:16]}.so")
    if os.path.exists(lib):
        return lib
    os.makedirs(build_dir, exist_ok=True)
    tmp = f"{lib}.{os.getpid()}.tmp"
    # libraries come after the source: ld resolves left to right
    cmd = ["g++", *flags, src, "-o", tmp, *link]

    @retry(retry_on=(OSError,), name=f"native_build_{stem}")
    def _run():
        return subprocess.run(cmd, capture_output=True, timeout=120)

    try:
        res = _run()
        if res.returncode != 0:
            _logger.warning(
                "native build of %s failed: %s",
                src, res.stderr.decode(errors="replace")[-400:],
            )
            return None
        os.replace(tmp, lib)
    except (OSError, subprocess.TimeoutExpired) as e:
        _logger.warning("native build of %s failed: %s", src, e)
        return None
    return lib
