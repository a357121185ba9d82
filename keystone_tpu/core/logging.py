"""Logging trait analog (reference src/main/scala/pipelines/Logging.scala:8-67).

Python stdlib logging with the same convenience surface, plus a wall-clock
stage timer (the reference's ``"Pipeline took N s"`` lines,
MnistRandomFFT.scala:34,86-87) and ``jax.named_scope`` tagging so stages show
up in the JAX profiler — the Spark-UI ``RDD.setName`` analog.  The stage
timer is built ON the trace subsystem (core.trace): every timed stage is
a ``trace.Stage``, the layer boundary of a fit's timeline.

As a library we never touch the root logger; workload entry points call
:func:`configure_logging` to get console output (level from the
``KEYSTONE_LOG_LEVEL`` env knob unless passed explicitly).
"""

from __future__ import annotations

import contextlib
import logging
import os
import time

import jax

from . import trace

_ROOT = logging.getLogger("keystone_tpu")
_ROOT.addHandler(logging.NullHandler())

#: env var: log level name ("DEBUG", "INFO", ...) or numeric level for
#: :func:`configure_logging` when the caller does not pass one.
LOG_LEVEL_ENV = "KEYSTONE_LOG_LEVEL"


def _env_level(default: int = logging.INFO) -> int:
    raw = os.environ.get(LOG_LEVEL_ENV, "").strip()
    if not raw:
        return default
    if raw.lstrip("-").isdigit():
        return int(raw)
    level = logging.getLevelName(raw.upper())
    if isinstance(level, int):
        return level
    raise ValueError(
        f"{LOG_LEVEL_ENV}={raw!r} is neither a level name "
        "(DEBUG/INFO/WARNING/ERROR/CRITICAL) nor a number"
    )


def configure_logging(level: int | None = None) -> None:
    """Attach a console handler to the keystone_tpu logger tree.
    Called by workload CLIs (never on import).  ``level`` defaults to the
    ``KEYSTONE_LOG_LEVEL`` env knob, then INFO."""
    if level is None:
        level = _env_level()
    if any(not isinstance(h, logging.NullHandler) for h in _ROOT.handlers):
        _ROOT.setLevel(level)
        return
    handler = logging.StreamHandler()
    handler.setFormatter(
        logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
    )
    _ROOT.addHandler(handler)
    _ROOT.setLevel(level)


class Logging:
    """Mixin giving ``log_info`` etc. on a per-class logger under the
    keystone_tpu hierarchy."""

    @property
    def logger(self) -> logging.Logger:
        return logging.getLogger(f"keystone_tpu.{type(self).__name__}")

    def log_debug(self, msg, *args):
        self.logger.debug(msg, *args)

    def log_info(self, msg, *args):
        self.logger.info(msg, *args)

    def log_warning(self, msg, *args):
        self.logger.warning(msg, *args)

    def log_error(self, msg, *args):
        self.logger.error(msg, *args)


@contextlib.contextmanager
def stage_timer(name: str, logger: logging.Logger | None = None):
    """Time a pipeline stage: same ``"<name> took N s"`` log line and
    signature as ever, a ``trace.Stage`` (a span of cat ``stage`` in the
    ``KEYSTONE_TRACE`` timeline whose exit records ``stage_ms.<name>`` and
    the wait and copy sums beneath it into ``trace.metrics``, tracing on or
    off), plus the ``jax.named_scope`` tag for the JAX profiler."""
    logger = logger or _ROOT
    t0 = time.perf_counter()
    with trace.Stage(name):
        with jax.named_scope(name):
            yield
    logger.info("%s took %.3f s", name, time.perf_counter() - t0)
