"""Streaming ingest: decode/featurize overlap with a host ring buffer and
double-buffered H2D transfers.

The reference hides decode latency behind per-executor parallelism
(ImageLoaderUtils.scala decodes per executor while other executors
featurize); the eager port decoded every tar member into host RAM before
the first device batch ran, leaving the accelerator idle for the whole
decode phase.  This module turns tar -> decode -> featurize into a
bounded-capacity pipeline (the tf.data "prefetch to device" pattern):

* **producer thread** — reads the tar serially (tar is a sequential
  format; opens retry via ``core.resilience.retry``), decodes JPEGs on a
  thread pool (``loaders.image_loaders.decode_threads()`` wide, with a
  bounded in-order window of ``decode_threads() + decode_ahead()``
  in-flight decodes), assembles decoded images into **shape buckets**
  (XLA wants static shapes), and pushes batch-assembled ``np.ndarray``
  chunks into a host **ring buffer**.  A full ring blocks the producer —
  backpressure, so decode never runs unboundedly ahead of the device.
* **transfer stage** — the consumer generator starts each chunk's H2D
  (``jax.device_put``, dispatched asynchronously) as soon as it leaves the
  ring and keeps **two** device-resident batches in flight: batch *i+1*
  transfers while the consumer featurizes batch *i*.  The consumer
  synchronizes (``np.asarray`` / ``block_until_ready``) only on the batch
  it is consuming.
* **consumer API** — ``stream_batches(path, batch_size, ...)`` yields
  :class:`StreamBatch` in assembly order; each carries the global image
  ordinals (``indices``) and member ``names`` so features scatter back to
  decode-survival order exactly like the eager path.

Resilience invariants preserved from the eager loaders:

* tar opens retry transient IO (``io_retry`` counted); corrupt members
  are counted skips (``corrupt_image``/``tar_member_error``) — never
  silent, never fatal.
* every ring wait is a short poll, so a ``resilience.deadline`` armed
  around the consumer interrupts a hung decoder thread as a typed
  ``DeadlineExceeded`` instead of deadlocking the pipeline.
* consumer exceptions (or early exit) stop the producer and release the
  decode pool; producer exceptions surface on the consumer's next
  ``__next__``.  ``join()`` lets tests assert every thread exited.

Two attacks on the decode wall itself (bench round r05, 2026-07-30, record
removed in PR 21: threaded decode speedup
1.04x — the pool is GIL-bound — while device featurize runs 15-17k
images/sec):

* **process decode backend** (``KEYSTONE_DECODE_BACKEND=process``) — a
  pool of SPAWNED worker processes decodes members truly in parallel; raw
  tar member bytes go in over per-worker queues, decoded pixels come back
  in ``multiprocessing.shared_memory`` blocks the chunk assembly stacks
  straight out of.  Worker crashes respawn (counted
  ``decode_worker_respawn``; a task that keeps killing workers becomes a
  counted ``decode_worker_lost`` skip), hangs fall to the same
  ``resilience.deadline`` contract as a hung decode thread, and every
  worker is joined — and every shm block released — on stream exit.
* **snapshot cache** (``KEYSTONE_SNAPSHOT_DIR``, core.snapshot) — the
  first pass over a tar tees its decoded chunks to disk; later passes
  stream the shards through the same ring at IO speed.  Staleness and
  shard corruption are counted fallbacks to live decode
  (``snapshot_stale`` / ``snapshot_fallback``), never silently wrong
  pixels — the fallback re-decode cross-checks the chunk prefix the
  consumer already received and dies typed
  (:class:`SnapshotFallbackDivergence`) if the survivor sequences
  diverged rather than scramble ordinals.

The THIRD decode-wall attack (ISSUE 13) moves the pixel math off the host
entirely: ``decode_mode="device"`` (``KEYSTONE_DEVICE_DECODE=1``) has the
producer threads run an ENTROPY-ONLY pass (ops.jpeg_device: markers +
Huffman -> quantized DCT coefficients), the ring carries
:class:`CoeffChunk` coefficient chunks bucketed by JPEG geometry, the
transfer stage double-buffers H2D of coefficients (~1/4 of pixel bytes),
and ``StreamBatch.apply`` fuses dequant/IDCT/upsample/colorspace INTO the
featurize program — pixels are born on device.  JPEGs outside the
baseline subset fall back to the host decode path counted per reason
(``device_decode_fallback_<reason>``); damaged scans are typed counted
skips (``jpeg_corrupt_entropy``, chaos family of the same name).  The
decoded-pixel snapshot cache does not compose with device decode
(different IDCT rounding — disabled counted); the DEVICE-FORMAT snapshot
tier (``snapshot_mode="device"``) stores dtype-final padded shards on the
(host-decoded) cold pass so warm epochs are pure DMA with zero host
transform.

Every sizing knob lives in a mutable :class:`StreamConfig` (env-seeded:
the ``KEYSTONE_DECODE_THREADS`` / ``KEYSTONE_DECODE_AHEAD`` /
``KEYSTONE_RING_CAPACITY`` values are INITIAL settings, no longer frozen
at construction) consulted at every decision point, so the closed-loop
autotuner (core.optimize.IngestAutotuner, ``KEYSTONE_AUTOTUNE=1``) can
retune decode width, ring depth, decode-ahead — and now the decode
BACKEND (promoted to ``process`` when it observes threaded scaling
flatline) — mid-stream.  Knobs change concurrency and buffering only —
never ordering or content.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import multiprocessing
import os
import queue as _queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Callable

import numpy as np

from ..loaders import image_loaders
from . import numerics as knum
from . import snapshot as ksnap
from . import trace
from .resilience import counters

# NO module-level jax import: every spawned decode worker re-imports THIS
# module (its target function _decode_worker_main lives here), and the only
# jax consumer is the consumer-side H2D transfer — which a worker never
# runs.  jax loads lazily at the first device_put instead of costing every
# worker spawn multi-second interpreter startup (the bench_decode
# total-vs-steady gap).  tests/test_lazy_import.py enforces this.


def _device_put(host):
    import jax

    return jax.device_put(host)

_logger = logging.getLogger("keystone_tpu.ingest")

#: Process-unique sequence for /statusz stream-provider names.
_stream_seq = itertools.count()

#: Assembled chunks the host ring holds before the producer blocks.  Each
#: slot is a decoded f32 batch (batch_size * H * W * 3 * 4 bytes), so the
#: default bounds host RAM at ~4 batches beyond the decode window.
DEFAULT_RING_CAPACITY = 4

#: Device batches the transfer stage keeps in flight: the consumed batch
#: plus the next one whose H2D overlaps the consumer's featurize.
DEVICE_BUFFERS = 2

#: Every blocking wait in the pipeline is a poll at this period so signals
#: (the resilience.deadline SIGALRM) and stop flags are always observed.
_POLL_SECONDS = 0.05


def ring_capacity() -> int:
    """Ring depth: ``KEYSTONE_RING_CAPACITY`` env or the default."""
    raw = os.environ.get("KEYSTONE_RING_CAPACITY", "").strip()
    if raw:
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(
                f"KEYSTONE_RING_CAPACITY={raw!r} is not an integer"
            ) from None
        if val < 1:
            raise ValueError(f"KEYSTONE_RING_CAPACITY={raw!r} must be >= 1")
        return val
    return DEFAULT_RING_CAPACITY


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip() in ("1", "true", "on", "yes")


def _host_cores() -> int:
    """Physical decode ceiling: the host's schedulable cores — deliberately
    NOT ``image_loaders.decode_threads()``, whose env override sets the
    INITIAL width; capping at the override too would pin the autotuner to
    it and make widening impossible."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # platforms without sched_getaffinity
        return os.cpu_count() or 1


def _env_int(name: str, default: int, minimum: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val < minimum:
        raise ValueError(f"{name}={raw!r} must be >= {minimum}")
    return val


#: Decode backends a stream can run: GIL-bound thread pool (PIL/native
#: decode release the GIL, but entropy decode + colorspace still serialize
#: badly — bench round r05 (2026-07-30; record removed in PR 21) measured
#: 1.04x threaded "speedup") or true parallel
#: spawned worker processes returning pixels via shared memory.
DECODE_BACKENDS = ("thread", "process")

#: Where pixels are born: ``host`` (full decode on the host, the classic
#: path) or ``device`` (host does the entropy pass only, the ring carries
#: quantized DCT coefficient chunks, and dequant/IDCT/upsample/colorspace
#: run batched on the accelerator — ops.jpeg_device).
DECODE_MODES = ("host", "device")


def decode_backend_env() -> str:
    """``KEYSTONE_DECODE_BACKEND``: ``thread`` (default) or ``process``."""
    raw = os.environ.get("KEYSTONE_DECODE_BACKEND", "").strip() or "thread"
    if raw not in DECODE_BACKENDS:
        raise ValueError(
            f"KEYSTONE_DECODE_BACKEND={raw!r} must be one of {DECODE_BACKENDS}"
        )
    return raw


def decode_mode_env() -> str:
    """``KEYSTONE_DEVICE_DECODE``: ``1`` (or ``device``) turns on
    device-resident decode; default ``host``."""
    raw = os.environ.get("KEYSTONE_DEVICE_DECODE", "").strip().lower()
    if raw in ("", "0", "off", "false", "host"):
        return "host"
    if raw in ("1", "on", "true", "device", "yes"):
        return "device"
    raise ValueError(
        f"KEYSTONE_DEVICE_DECODE={raw!r} must be 0/1 (or host/device)"
    )


@dataclasses.dataclass
class StreamConfig:
    """The LIVE knob set of one ingest stream.

    The env knobs (``KEYSTONE_DECODE_THREADS`` / ``KEYSTONE_DECODE_AHEAD`` /
    ``KEYSTONE_RING_CAPACITY``) used to be read once at stream construction
    and frozen; they are now only the INITIAL values of this mutable config
    (:meth:`from_env`).  The stream consults the config at every decision
    point — each tar member for the decode window, each ring put for the
    capacity — so mutating a field retunes the stream mid-run.  That is the
    closed-loop autotuner's mutation surface (core.optimize.IngestAutotuner),
    and a programmatic configuration API in its own right.

    The knobs control CONCURRENCY AND BUFFERING only — never ordering or
    content: decodes complete through an in-order FIFO window and chunks
    assemble identically at any width/depth, so retuning may change speed,
    never results (the ``autotune_thrash`` chaos family holds it to that).

    ``decode_threads`` is the number of decodes kept in flight (the
    effective pool width); ``max_decode_threads`` caps how far a tuner may
    raise it — the thread pool is created at the cap, width is governed by
    the in-flight window.
    """

    decode_threads: int
    decode_ahead: int
    ring_capacity: int
    max_decode_threads: int = 0  # 0 -> resolved to >= decode_threads in __post_init__
    autotune: bool = False  #: create an IngestAutotuner for this stream
    autotune_interval: int = 4  #: chunks between controller evaluations
    #: Decode backend: "thread" (GIL-bound pool) or "process" (spawned
    #: workers + shared-memory return path).  Consulted PER MEMBER, so the
    #: autotuner can promote a running stream to process decode when it
    #: observes threaded scaling flatline (core.optimize.IngestAutotuner).
    decode_backend: str = "thread"
    #: Process-backend worker count; 0 -> resolved to decode_threads.
    decode_procs: int = 0
    #: ``host`` = full pixel decode on the host (thread/process backend);
    #: ``device`` = entropy-only host pass, coefficient chunks in the
    #: ring, batched dequant+IDCT+upsample+colorspace on the accelerator
    #: (ops.jpeg_device).  JPEGs outside the device path's baseline
    #: subset fall back to host decode COUNTED per reason
    #: (``device_decode_fallback_<reason>``); the entropy pass runs on
    #: the thread pool regardless of ``decode_backend`` (it is the light
    #: pass — the heavy math moved on-device).
    decode_mode: str = "host"
    #: Snapshot cache root (None = off): first pass over the tar writes
    #: decoded chunks here, later passes stream them at IO speed
    #: (core.snapshot).  ``snapshot_mode="featurized"`` is handled ABOVE
    #: the ring by the workload helpers (fv_common) — the ingest stream
    #: itself only materializes decoded chunks.
    snapshot_dir: str | None = None
    snapshot_mode: str = "decoded"
    #: Extra key material for the snapshot content hash — REQUIRED when the
    #: stream uses a ``keep`` member filter (the filter selects the member
    #: set, so an unkeyed filter would alias different survivor sets).
    snapshot_extra: str | None = None

    def __post_init__(self):
        if self.decode_threads < 1:
            raise ValueError(f"decode_threads must be >= 1, got {self.decode_threads}")
        if self.decode_ahead < 0:
            raise ValueError(f"decode_ahead must be >= 0, got {self.decode_ahead}")
        if self.ring_capacity < 1:
            raise ValueError(f"ring_capacity must be >= 1, got {self.ring_capacity}")
        if self.autotune_interval < 1:
            raise ValueError(
                f"autotune_interval must be >= 1, got {self.autotune_interval}"
            )
        if self.decode_backend not in DECODE_BACKENDS:
            raise ValueError(
                f"decode_backend={self.decode_backend!r} must be one of "
                f"{DECODE_BACKENDS}"
            )
        if self.decode_procs < 0:
            raise ValueError(
                f"decode_procs must be >= 0, got {self.decode_procs}"
            )
        if self.decode_procs == 0:
            self.decode_procs = self.decode_threads
        if self.decode_mode not in DECODE_MODES:
            raise ValueError(
                f"decode_mode={self.decode_mode!r} must be one of "
                f"{DECODE_MODES}"
            )
        if self.snapshot_mode not in ksnap.MODES:
            raise ValueError(
                f"snapshot_mode={self.snapshot_mode!r} must be one of "
                f"{ksnap.MODES}"
            )
        if self.max_decode_threads == 0:
            self.max_decode_threads = max(self.decode_threads, _host_cores())
        elif self.max_decode_threads < self.decode_threads:
            # An EXPLICIT cap below the width is a contradiction, not a
            # sentinel — silently widening it would let the tuner exceed a
            # bound the caller set to protect host CPU.
            raise ValueError(
                f"max_decode_threads={self.max_decode_threads} is below "
                f"decode_threads={self.decode_threads}"
            )

    @classmethod
    def from_env(cls, **overrides) -> "StreamConfig":
        """Env-seeded defaults (``KEYSTONE_DECODE_THREADS`` /
        ``KEYSTONE_DECODE_AHEAD`` / ``KEYSTONE_RING_CAPACITY`` /
        ``KEYSTONE_AUTOTUNE`` / ``KEYSTONE_AUTOTUNE_INTERVAL`` /
        ``KEYSTONE_DECODE_BACKEND`` / ``KEYSTONE_DECODE_PROCS`` /
        ``KEYSTONE_SNAPSHOT_DIR`` / ``KEYSTONE_SNAPSHOT_MODE``), any field
        overridable by keyword."""
        cfg = {
            "decode_threads": image_loaders.decode_threads(),
            "decode_ahead": image_loaders.decode_ahead(),
            "ring_capacity": ring_capacity(),
            "autotune": _env_flag("KEYSTONE_AUTOTUNE"),
            "autotune_interval": _env_int("KEYSTONE_AUTOTUNE_INTERVAL", 4, 1),
            "decode_backend": decode_backend_env(),
            "decode_mode": decode_mode_env(),
            "decode_procs": _env_int("KEYSTONE_DECODE_PROCS", 0, 0),
            "snapshot_dir": ksnap.snapshot_dir_env(),
            "snapshot_mode": ksnap.snapshot_mode_env(),
        }
        cfg.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**cfg)

    def window(self) -> int:
        """In-flight decode window: effective pool width + decode-ahead."""
        return max(1, self.decode_threads) + max(0, self.decode_ahead)

    def record(self) -> dict:
        return dataclasses.asdict(self)


class _Cancelled(Exception):
    """Internal: the consumer stopped the stream — unwind the producer."""


class _FallbackPixels:
    """Device-decode task result: the JPEG is outside the device path's
    baseline subset (``reason``) and was decoded on the host instead —
    the producer counts the fallback per reason."""

    __slots__ = ("reason", "img")

    def __init__(self, reason: str, img):
        self.reason = reason
        self.img = img


class _CorruptEntropy:
    """Device-decode task result: the entropy-coded scan is damaged — a
    typed, counted skip (``jpeg_corrupt_entropy``), never silent wrong
    pixels."""

    __slots__ = ("detail",)

    def __init__(self, detail: str):
        self.detail = detail


def _entropy_decode_task(data: bytes):
    """One member's DEVICE-mode decode task (thread pool): entropy-only
    decode into a ``CoeffImage``; JPEGs the device path cannot claim fall
    back to the full host decode TYPED (``_FallbackPixels``), damaged
    scans come back as ``_CorruptEntropy``.  The device path reproduces
    ``decode_image``'s reject rules (min dimension) so host and device
    streams keep identical survivor sets."""
    from ..ops import jpeg_device as jdev

    try:
        ci = jdev.entropy_decode(data)
    except jdev.JpegEntropyCorrupt as e:
        return _CorruptEntropy(str(e))
    except jdev.JpegDecodeUnsupported as e:
        return _FallbackPixels(e.reason, image_loaders.decode_image(data))
    if (
        ci.geom.height < image_loaders.MIN_DIM
        or ci.geom.width < image_loaders.MIN_DIM
    ):
        return None  # the decode_image reject floor, same counted skip
    return ci


class SnapshotFallbackDivergence(RuntimeError):
    """The live re-decode behind a corrupt-shard snapshot fallback stopped
    matching the chunk prefix the consumer already received from the
    snapshot (a transient counted skip — e.g. ``decode_worker_lost`` —
    shifted the survivor sequence between the two passes).  The served
    prefix is valid original data, but continuing would assign the same
    stream ordinals to different images, silently scrambling the
    consumer's scatter — so the stream dies TYPED (and counted,
    ``snapshot_fallback_divergence``) instead."""


# -- the multiprocess decode backend ------------------------------------------


def _decode_worker_main(task_q, result_q):
    """Entry point of one SPAWNED decode worker process.

    Receives ``(task_id, raw_member_bytes)``, decodes with the same
    ``image_loaders.decode_image`` the thread path runs (bit-identity by
    construction), and publishes the pixels through a
    ``multiprocessing.shared_memory`` block sized to the decoded array —
    the parent maps the block and stacks STRAIGHT from it into the chunk
    assembly, so no pickled array ever crosses the result queue.  A
    ``None`` task is the shutdown sentinel; a corrupt member answers
    ``(task_id, None, None, None)`` (the parent counts the skip)."""
    from multiprocessing import shared_memory

    from ..loaders import image_loaders as _loaders
    from ..loaders.native_decode import available as _native_available

    _native_available()  # one-time build/load before the decode loop
    while True:
        item = task_q.get()
        if item is None:
            break
        tid, data = item
        try:
            img = _loaders.decode_image(data)
        except Exception:  # noqa: BLE001 — a crash here is a counted skip
            img = None
        if img is None:
            result_q.put((tid, None, None, None))
            continue
        shm = shared_memory.SharedMemory(create=True, size=img.nbytes)
        np.ndarray(img.shape, img.dtype, buffer=shm.buf)[:] = img
        # The block stays REGISTERED with the resource tracker (shared
        # with the parent via the spawn tracker_fd): the tracker reaps
        # only when main + every worker have exited, so worker exit or
        # respawn can never unlink a block the parent is assembling from,
        # and a SIGKILL landing anywhere around this put — even before
        # the queue's feeder thread flushes the name to the pipe — leaves
        # the block tracker-known and reclaimed at interpreter exit.  The
        # parent's unlink() unregisters on the normal path.
        result_q.put((tid, shm.name, img.shape, img.dtype.str))
        shm.close()


class _ShmArray:
    """Parent-side view of one worker-decoded image living in shared
    memory.  ``arr`` is a zero-copy ndarray over the block; ``release()``
    (after chunk assembly copies the pixels out) closes and unlinks it."""

    __slots__ = ("_pool", "shm", "arr")

    def __init__(self, pool, shm, shape, dtype):
        self._pool = pool
        self.shm = shm
        self.arr = np.ndarray(shape, dtype, buffer=shm.buf)

    @property
    def shape(self):
        return self.arr.shape

    def release(self) -> None:
        self._pool._release(self.shm)


class _ProcTask:
    """Future-like handle for one member's process decode (same
    ``result(timeout)`` surface as a thread-pool future, so the in-order
    FIFO window holds either kind)."""

    __slots__ = (
        "id", "name", "data", "worker", "img", "done", "skip_reason",
        "resubmits", "_pool",
    )

    def __init__(self, pool, tid: int, name: str, data: bytes):
        self._pool = pool
        self.id = tid
        self.name = name
        self.data = data  # retained until done: a dead worker's tasks resubmit
        self.worker = None
        self.img = None
        self.done = False
        self.skip_reason: str | None = None
        self.resubmits = 0

    def result(self, timeout: float):
        return self._pool._wait(self, timeout)


class _PoolWorker:
    __slots__ = ("proc", "task_q", "pending")

    def __init__(self, proc, task_q):
        self.proc = proc
        self.task_q = task_q
        self.pending: dict = {}  # task_id -> _ProcTask


class _ProcessDecodePool:
    """True parallel decode: ``procs`` SPAWNED worker processes (no fork —
    jax-unsafe), raw tar member bytes in over per-worker task queues,
    decoded pixels back via shared memory.

    Crash containment: a worker that dies (OOM-killed, SIGKILL chaos) is
    detected on the next result wait — its pending tasks are resubmitted to
    a freshly spawned replacement (counted ``decode_worker_respawn``); a
    task that kills workers repeatedly becomes a counted skip
    (``decode_worker_lost``) instead of a respawn storm.  A HUNG worker is
    the consumer deadline's problem, exactly like a hung decode thread:
    ``result()`` keeps timing out, the armed ``resilience.deadline`` fires
    typed, and :meth:`shutdown` terminates the stragglers — the ring never
    deadlocks and workers are always joined on stream exit.

    Every live shared-memory block is registered in ``_live_shm`` until the
    chunk assembly releases it, and :meth:`shutdown` force-releases the
    registry — no ``/dev/shm`` segment outlives the stream (asserted by the
    tier-1 suite)."""

    MAX_RESUBMITS = 2

    def __init__(self, procs: int, stats: StreamStats | None = None):
        self._ctx = multiprocessing.get_context("spawn")
        self._result_q = self._ctx.Queue()
        self._workers: list[_PoolWorker] = []
        self._inflight: dict = {}  # task_id -> _ProcTask
        self._live_shm: dict = {}  # shm name -> SharedMemory
        self._ids = itertools.count()
        self._stats = stats
        self._down = False
        for _ in range(max(1, procs)):
            self._spawn_worker()

    # -- worker lifecycle ------------------------------------------------------

    def _spawn_worker(self) -> _PoolWorker:
        task_q = self._ctx.Queue()
        proc = self._ctx.Process(
            target=_decode_worker_main,
            args=(task_q, self._result_q),
            name="keystone-decode-proc",
            daemon=True,
        )
        proc.start()
        w = _PoolWorker(proc, task_q)
        self._workers.append(w)
        return w

    def _reap_dead_workers(self) -> None:
        for w in list(self._workers):
            if w.proc.is_alive():
                continue
            self._workers.remove(w)
            lost = list(w.pending.values())
            w.pending.clear()
            w.task_q.cancel_join_thread()
            w.task_q.close()
            counters.record(
                "decode_worker_respawn",
                f"pid {w.proc.pid} exited {w.proc.exitcode} with "
                f"{len(lost)} task(s) pending — respawned",
            )
            trace.instant(
                "decode_worker_respawn",
                pid=w.proc.pid, exitcode=w.proc.exitcode, lost=len(lost),
            )
            if self._stats is not None:
                self._stats.worker_respawns += 1
            self._spawn_worker()
            # Blame the crash on the worker's OLDEST pending task only —
            # the FIFO worker was decoding it when it died (pending is
            # insertion-ordered; later entries were still queued).
            # Charging every co-pending task would let one poison member
            # exhaust healthy members' resubmit budgets, skipping images
            # the thread path keeps (breaking process-vs-thread
            # bit-identity).
            if lost:
                lost[0].resubmits += 1
            for t in lost:
                if t.resubmits > self.MAX_RESUBMITS:
                    # The task itself keeps killing workers: a counted
                    # skip, never an infinite respawn loop.
                    self._inflight.pop(t.id, None)
                    t.img = None
                    t.skip_reason = "decode_worker_lost"
                    t.done = True
                    t.data = None
                else:
                    self._dispatch(t)

    # -- task flow -------------------------------------------------------------

    def submit(self, name: str, data: bytes) -> _ProcTask:
        if self._down:
            raise RuntimeError("decode pool is shut down")
        t = _ProcTask(self, next(self._ids), name, data)
        self._inflight[t.id] = t
        self._dispatch(t)
        return t

    def _dispatch(self, task: _ProcTask) -> None:
        w = min(self._workers, key=lambda w: len(w.pending))
        w.pending[task.id] = task
        task.worker = w
        w.task_q.put((task.id, task.data))

    def _handle(self, item) -> None:
        tid, shm_name, shape, dtype = item
        task = self._inflight.pop(tid, None)
        if shm_name is None:
            if task is not None:
                task.img = None
                task.skip_reason = task.skip_reason or "corrupt_image"
                task.done = True
                task.data = None
                if task.worker is not None:
                    task.worker.pending.pop(tid, None)
            return
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(name=shm_name)
        if task is None or task.done:
            # A resubmit raced the original worker's queued result: the
            # duplicate block is surplus — release it immediately.
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
            return
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        # An instant, not an io_span: attaching the block is a zero-copy
        # mmap (the pixels move later, in _emit's np.stack), so a derived
        # mb_per_s here would report dict-insert latency as IPC bandwidth.
        trace.instant(
            "ingest.shm_recv", bytes=nbytes, member=task.name
        )
        self._live_shm[shm.name] = shm
        task.img = _ShmArray(self, shm, shape, np.dtype(dtype))
        task.done = True
        task.data = None
        if task.worker is not None:
            task.worker.pending.pop(tid, None)

    def _wait(self, task: _ProcTask, timeout: float):
        end = time.monotonic() + timeout
        while True:
            drained = False
            try:
                item = self._result_q.get(timeout=_POLL_SECONDS / 5)
                drained = True
            except _queue.Empty:
                item = None
            while item is not None:
                self._handle(item)
                try:
                    item = self._result_q.get_nowait()
                except _queue.Empty:
                    item = None
            if task.done:
                return task.img
            if not drained:
                self._reap_dead_workers()
            if task.done:
                return task.img
            if time.monotonic() >= end:
                raise _FutureTimeout()

    def _release(self, shm) -> None:
        self._live_shm.pop(shm.name, None)
        shm.close()
        try:
            shm.unlink()
        except FileNotFoundError:
            pass

    # -- shutdown --------------------------------------------------------------

    def shutdown(self, clean: bool) -> None:
        """Stop every worker (sentinel, then terminate/kill stragglers),
        drain undelivered results, and force-release every live
        shared-memory block.  Idempotent."""
        if self._down:
            return
        self._down = True
        for w in self._workers:
            try:
                w.task_q.put_nowait(None)
            except (ValueError, OSError):
                pass
        end = time.monotonic() + (5.0 if clean else 1.0)
        for w in self._workers:
            w.proc.join(max(0.0, end - time.monotonic()))
        for w in self._workers:
            if w.proc.is_alive():
                w.proc.terminate()
                w.proc.join(1.0)
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(1.0)
            w.task_q.cancel_join_thread()
            w.task_q.close()
        # Undelivered results hold blocks the parent never attached: attach
        # and unlink each so nothing leaks in /dev/shm.
        while True:
            try:
                item = self._result_q.get_nowait()
            except (_queue.Empty, OSError, ValueError):
                break
            if item[1] is not None:
                from multiprocessing import shared_memory

                try:
                    s = shared_memory.SharedMemory(name=item[1])
                    s.close()
                    s.unlink()
                except FileNotFoundError:
                    pass
        self._result_q.cancel_join_thread()
        self._result_q.close()
        for shm in list(self._live_shm.values()):
            self._release(shm)
        self._inflight.clear()

    def joined(self) -> bool:
        return self._down and not any(
            w.proc.is_alive() for w in self._workers
        )


@dataclasses.dataclass
class CoeffChunk:
    """Device-decode payload of one chunk: quantized DCT coefficients for
    a batch of same-geometry JPEGs (what the ring carries instead of
    pixels under ``decode_mode="device"``)."""

    geom: object  #: ops.jpeg_device.JpegGeometry (hashable, shape-static)
    coeffs: tuple  #: per-component [b, by, bx, 8, 8] int16 host arrays
    qt: np.ndarray  #: [b, ncomp, 8, 8] f32 per-image dequant tables
    #: (coeffs_on_device, qt_on_device) once the transfer stage ran —
    #: the double-buffered H2D moves COEFFICIENTS, not pixels
    device: tuple | None = None

    def arrays(self) -> tuple:
        return self.device if self.device is not None else (
            self.coeffs, self.qt
        )

    def nbytes(self) -> int:
        return sum(int(c.nbytes) for c in self.coeffs) + int(self.qt.nbytes)


@dataclasses.dataclass
class StreamBatch:
    """One shape-bucketed, batch-assembled chunk of decoded images.

    Under ``decode_mode="device"`` a chunk may carry COEFFICIENTS instead
    of pixels (``coeff`` set, ``host`` None): ``dev()`` then runs the
    batched device decode, and :meth:`apply` fuses decode+featurize into
    one jitted dispatch (ops.jpeg_device.fused_apply)."""

    index: int  #: chunk ordinal (FIFO yield order)
    indices: np.ndarray  #: [b] global image ordinals in decode-survival order
    names: list  #: [b] tar member names
    host: np.ndarray | None  #: [b, H, W, C] f32 host batch (None for coeff)
    device: object | None = None  #: jax.Array once the transfer stage ran
    coeff: CoeffChunk | None = None  #: device-decode payload (host is None)

    @property
    def shape(self) -> tuple:
        """The bucket key: per-image (H, W)."""
        if self.coeff is not None:
            return (self.coeff.geom.height, self.coeff.geom.width)
        return tuple(self.host.shape[1:3])

    def __len__(self) -> int:
        return len(self.names)

    def dev(self):
        """The device-resident PIXEL batch (transferring — and for
        coefficient chunks, device-decoding — on demand when the stream
        ran with ``transfer=False``)."""
        if self.device is None:
            self.device = (
                _decode_coeffs(self.coeff)
                if self.coeff is not None
                else _device_put(self.host)
            )
        return self.device

    def apply(self, transform):
        """``transform(pixels)`` for this chunk — FUSED with the device
        decode into one jitted program for coefficient chunks (pixels are
        never materialized between two dispatches), a plain call on the
        device pixel batch otherwise."""
        if self.coeff is None:
            from . import profiler as kprof

            if not kprof.enabled():
                return self._probed(transform(self.dev()))
            # Per-program MFU attribution of the featurize dispatch
            # (ISSUE 14).  Values unchanged; pipelining traded for
            # measurement only while the profiler is ON.
            dev = self.dev()
            return self._probed(kprof.attributed_call(
                f"featurize:{self.shape[0]}x{self.shape[1]}",
                tuple(np.shape(dev)), transform, dev,
            ))
        from ..ops import jpeg_device as jdev

        coeffs, qt = self.coeff.arrays()
        return self._probed(
            jdev.fused_apply(transform, self.coeff.geom, coeffs, qt)
        )

    def _probed(self, out):
        """Numerics observatory hook (KEYSTONE_NUMERICS=1): the featurize
        output of every streamed chunk is a tensor-stat probe site, with
        this chunk's tar member ``names`` as the NaN-provenance map — a
        non-finite featurize row is counted naming the member that
        produced it, not just the chunk that carried it.  One flag check
        when off; the value passes through bit-unchanged either way."""
        if knum.active():
            knum.probe(
                f"stream.featurize.{self.shape[0]}x{self.shape[1]}",
                out, names=self.names,
            )
        return out


def _decode_coeffs(chunk: CoeffChunk):
    from ..ops import jpeg_device as jdev

    coeffs, qt = chunk.arrays()
    return jdev.decode_batch(chunk.geom, coeffs, qt)


@dataclasses.dataclass
class StreamStats:
    """Per-stream ingest counters (ring depth/stall accounting for the
    bench ``e2e`` section and the backpressure tests)."""

    decoded: int = 0  #: images decoded successfully
    skipped: int = 0  #: corrupt members skipped (also counted globally)
    batches: int = 0  #: chunks emitted into the ring
    ring_capacity: int = 0
    ring_max_depth: int = 0  #: high-water mark of assembled chunks queued
    producer_stalls: int = 0  #: puts that blocked on a full ring (backpressure)
    consumer_stalls: int = 0  #: gets that found the ring empty (decode-bound)
    snapshot_chunks_read: int = 0  #: chunks served from the snapshot cache
    snapshot_chunks_written: int = 0  #: chunks teed into a snapshot writer
    worker_respawns: int = 0  #: process-backend decode workers respawned
    entropy_decoded: int = 0  #: images entropy-decoded (device decode mode)
    entropy_backend: str = ""  #: scan hot-loop backend ("native"/"python")
    entropy_corrupt: int = 0  #: typed+counted corrupt-scan skips
    device_fallbacks: int = 0  #: JPEGs routed to host decode (counted per reason)
    coeff_bytes: int = 0  #: coefficient payload bytes carried by the ring
    snapshot_dma_bytes: int = 0  #: device-format shard bytes served straight to H2D

    def record(self) -> dict:
        return dataclasses.asdict(self)


class _Ring:
    """Bounded FIFO between the producer thread and the consumer.

    All waits poll at ``_POLL_SECONDS`` so the main thread stays
    interruptible (resilience.deadline's SIGALRM) and the producer always
    observes ``stop()``.  A producer error is stored and re-raised on the
    consumer side; ``close()`` marks end-of-stream."""

    _END = object()

    def __init__(self, config: StreamConfig, stats: StreamStats):
        self._q: collections.deque = collections.deque()
        self._cond = threading.Condition()
        # Capacity is read from the LIVE config on every put: a mid-stream
        # retune takes effect at the next enqueue (shrinking below the
        # current depth just blocks the producer until the consumer drains).
        self._config = config
        self._stats = stats
        self._closed = False
        self._stopped = False
        self._error: BaseException | None = None

    @property
    def stopped(self) -> bool:
        return self._stopped

    def depth(self) -> int:
        with self._cond:
            return len(self._q)

    def put(self, item) -> bool:
        """Producer side; blocks while full (backpressure).  Returns False
        when the consumer stopped the stream."""
        with self._cond:
            stalled = False
            while len(self._q) >= max(1, self._config.ring_capacity) and not self._stopped:
                if not stalled:
                    self._stats.producer_stalls += 1
                    stalled = True
                self._cond.wait(_POLL_SECONDS)
            if self._stopped:
                return False
            self._q.append(item)
            self._stats.ring_max_depth = max(
                self._stats.ring_max_depth, len(self._q)
            )
            self._cond.notify_all()
            return True

    def get(self):
        """Consumer side; blocks while empty.  Returns ``_Ring._END`` at
        end-of-stream, re-raises a producer failure."""
        with self._cond:
            stalled = False
            while True:
                if self._q:
                    item = self._q.popleft()
                    self._cond.notify_all()
                    return item
                if self._error is not None:
                    err, self._error = self._error, None
                    raise err
                if self._closed or self._stopped:
                    return self._END
                if not stalled:
                    self._stats.consumer_stalls += 1
                    stalled = True
                self._cond.wait(_POLL_SECONDS)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def fail(self, error: BaseException) -> None:
        with self._cond:
            self._error = error
            self._closed = True
            self._cond.notify_all()

    def stop(self) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()


class IngestStream:
    """The streaming pipeline: iterate to consume, ``with`` (or ``close``)
    to guarantee shutdown, ``join()`` to assert no thread leaked."""

    def __init__(
        self,
        path: str,
        batch_size: int,
        *,
        keep: Callable[[str], bool] | None = None,
        num_threads: int | None = None,
        decode_ahead_slots: int | None = None,
        capacity: int | None = None,
        transfer: bool = True,
        config: StreamConfig | None = None,
        tuner=None,
    ):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self._path = path
        self._batch_size = batch_size
        self._keep = keep
        # The stream's live knob set: an explicit StreamConfig, or an
        # env-seeded one; the legacy per-stream kwargs override its initial
        # values.  The config object is SHARED with the caller/tuner —
        # mutations retune the running stream.
        if config is None:
            config = StreamConfig.from_env(
                decode_threads=num_threads,
                decode_ahead=decode_ahead_slots,
                ring_capacity=capacity,
            )
        else:
            if num_threads is not None:
                config.decode_threads = num_threads
                config.max_decode_threads = max(
                    config.max_decode_threads, num_threads
                )
            if decode_ahead_slots is not None:
                config.decode_ahead = decode_ahead_slots
            if capacity is not None:
                config.ring_capacity = capacity
            if num_threads is not None or decode_ahead_slots is not None or capacity is not None:
                # Legacy overrides must pass the same validation the
                # constructor enforces (num_threads=0 etc. raise, never
                # silently configure a dead stream).
                config.__post_init__()
        self.config = config
        self._transfer = transfer
        self.stats = StreamStats(ring_capacity=config.ring_capacity)
        self._ring = _Ring(config, self.stats)
        self._workers: list[threading.Thread] = []
        self._pool: ThreadPoolExecutor | None = None
        self._proc_pool: _ProcessDecodePool | None = None
        #: resolved per produce pass (_produce_live): device decode is
        #: forced OFF while a snapshot writer needs host pixels
        self._device_decode = config.decode_mode == "device"
        self._writer = None  #: core.snapshot.SnapshotWriter while teeing
        self._skip_chunks = 0
        #: (names, indices) per chunk already served from a snapshot when a
        #: corrupt shard forced the live fallback — the oracle the
        #: suppressed re-decode prefix must reproduce exactly.
        self._served_prefix: list = []
        self._chunk_counter = 0
        self.tuner = tuner
        if self.tuner is None and config.autotune:
            # Lazy import: optimize imports ingest at module level; the
            # reverse edge resolves only when a stream actually autotunes.
            from .optimize import IngestAutotuner

            self.tuner = IngestAutotuner()
        if self.tuner is not None:
            self.tuner.attach(self)
        # One line per stream so operators can see the effective ingest
        # configuration (the env knobs resolved) without env spelunking.
        _logger.info(
            "streaming ingest %s: batch=%d threads=%d ahead=%d ring=%d "
            "transfer=%s autotune=%s",
            path,
            batch_size,
            config.decode_threads,
            config.decode_ahead,
            config.ring_capacity,
            transfer,
            bool(self.tuner),
        )
        # Live ring/stream state on the /statusz debug page (ISSUE 15) —
        # jax-free: telemetry is already on the resilience import path.
        # The name carries a process-unique sequence so two concurrent
        # streams over the SAME tar each get their own row (and the
        # identity-guarded unregister means an old stream's close can
        # never evict a newer one's entry).
        from . import telemetry as _telemetry

        self._statusz_name = (
            f"stream:{os.path.basename(path)}#{next(_stream_seq)}"
        )
        self._statusz_provider = lambda: {
            "path": path,
            "batch_size": batch_size,
            "decode_threads": self.config.decode_threads,
            "decode_ahead": self.config.decode_ahead,
            "ring_capacity": self.config.ring_capacity,
            "decode_backend": self.config.decode_backend,
            **self.stats.record(),
        }
        _telemetry.register_statusz(
            self._statusz_name, self._statusz_provider
        )
        self._iter = self._drain()
        self._thread = threading.Thread(
            target=self._produce, name="keystone-ingest-producer", daemon=True
        )
        self._thread.start()

    # -- producer side --------------------------------------------------------

    def _register_worker(self):
        self._workers.append(threading.current_thread())

    def _await_decode(self, fut):
        """Poll a decode future so a stopped stream abandons a hung decoder
        instead of joining it forever."""
        while True:
            if self._ring.stopped:
                raise _Cancelled()
            try:
                return fut.result(timeout=_POLL_SECONDS)
            except _FutureTimeout:
                continue

    def _ensure_thread_pool(self) -> ThreadPoolExecutor:
        # The pool is sized at the retune CEILING; the effective width is
        # the in-flight window (config.decode_threads), consulted per
        # member — so the tuner can widen/narrow decode mid-stream without
        # rebuilding the pool.
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.config.max_decode_threads,
                thread_name_prefix="keystone-decode",
                initializer=self._register_worker,
            )
        return self._pool

    def _ensure_proc_pool(self) -> _ProcessDecodePool:
        if self._proc_pool is None:
            with trace.span(
                "ingest.spawn_decode_procs", cat="ingest",
                procs=self.config.decode_procs,
            ):
                self._proc_pool = _ProcessDecodePool(
                    self.config.decode_procs, self.stats
                )
            _logger.info(
                "process decode backend: %d spawned worker(s)",
                self.config.decode_procs,
            )
        return self._proc_pool

    def _submit_decode(self, name: str, data: bytes):
        """Submit one member's decode on the CURRENTLY configured backend
        (consulted per member: the autotuner may promote a running stream
        from thread to process decode; mixed futures drain through the same
        in-order FIFO window).  On the thread backend, when tracing is
        enabled each decode becomes an ``ingest.decode`` span on ITS worker
        thread's timeline — the parallel decode lanes are visible next to
        the consumer lane, so decode/featurize overlap is a picture, not an
        inference.  The module attribute is resolved at call time (the
        chaos harness patches ``image_loaders.decode_image``)."""
        if self._device_decode:
            # Entropy-only pass: always the thread pool (the native scan
            # loop releases the GIL per call so the pool scales across
            # cores; the pure-Python fallback stays the LIGHT half of the
            # decode — the heavy math runs on-device).  A process backend
            # setting governs the host-pixel path only.
            pool = self._ensure_thread_pool()
            if not trace.enabled():
                return pool.submit(_entropy_decode_task, data)

            def traced_entropy(data=data, name=name):
                with trace.span(
                    "ingest.entropy_decode", cat="ingest", member=name
                ):
                    return _entropy_decode_task(data)

            return pool.submit(traced_entropy)
        if self.config.decode_backend == "process":
            return self._ensure_proc_pool().submit(name, data)
        pool = self._ensure_thread_pool()
        if not trace.enabled():
            return pool.submit(image_loaders.decode_image, data)

        def traced(data=data, name=name):
            with trace.span("ingest.decode", cat="ingest", member=name):
                return image_loaders.decode_image(data)

        return pool.submit(traced)

    def _produce(self):
        clean = False
        try:
            clean = self._run_producer()
        except BaseException as e:  # noqa: BLE001 — surfaces on the consumer
            self._ring.fail(e)
        finally:
            self._ring.close()
            if self._writer is not None:
                # No-op after a successful commit; a cancelled/failed pass
                # must never leave a partial snapshot behind.
                self._writer.abort()
            # A stopped stream may hold a hung decode future: abandon it
            # (workers are daemon threads) instead of blocking shutdown.
            if self._pool is not None:
                self._pool.shutdown(wait=clean, cancel_futures=not clean)
            if self._proc_pool is not None:
                self._proc_pool.shutdown(clean)

    def _snapshot_plan(self):
        """``(root, key, mode)`` when an ingest-level snapshot tier applies
        to this stream — ``decoded`` (f32 pixel chunks, exactly what the
        ring carried) or ``device`` (pre-laid-out device-format shards:
        padded/bucketed, dtype-final, read back as pure DMA).
        ``snapshot_mode="featurized"`` is the workload helpers' business —
        the ring never carries feature rows.

        ``decode_mode="device"`` + a DECODED snapshot is a contradiction
        (device streams decode pixels on the accelerator, host-decoded
        cached pixels differ within IDCT rounding — serving them would
        silently change the stream's bits): the cache is disabled COUNTED
        rather than silently served."""
        cfg = self.config
        if not cfg.snapshot_dir or cfg.snapshot_mode not in (
            "decoded", "device",
        ):
            return None
        if cfg.decode_mode == "device" and cfg.snapshot_mode == "decoded":
            counters.record(
                "snapshot_mode_unsupported",
                f"{self._path}: decoded-pixel snapshots do not compose "
                "with device decode (different IDCT rounding) — use "
                "snapshot_mode='device' for a DMA-format cache",
            )
            return None
        if self._keep is not None and cfg.snapshot_extra is None:
            _logger.warning(
                "snapshot cache disabled for %s: the stream has a keep "
                "filter but no snapshot_extra key material — an unkeyed "
                "filter would alias different member subsets",
                self._path,
            )
            return None
        key = ksnap.snapshot_key(
            self._path,
            batch_size=self._batch_size,
            mode=cfg.snapshot_mode,
            extra=cfg.snapshot_extra,
        )
        return cfg.snapshot_dir, key, cfg.snapshot_mode

    def _run_producer(self) -> bool:
        """Produce chunks — from the snapshot cache when a valid one
        exists, else by live decode (teeing a fresh snapshot when caching
        is on).  Returns True on clean end-of-stream, False when the
        consumer cancelled."""
        plan = self._snapshot_plan()
        skip = 0
        if plan is not None:
            root, key, snap_mode = plan
            snap, reason = ksnap.lookup(
                root, key, tar_path=self._path, mode=snap_mode
            )
            if reason == "stale":
                counters.record(
                    "snapshot_stale",
                    f"{self._path}: committed snapshot exists under a "
                    "different key (input or decode config moved) — live "
                    "decode, fresh snapshot written",
                )
            if snap is not None:
                try:
                    emitted = self._emit_from_snapshot(snap)
                except _Cancelled:
                    return False
                if emitted is True:
                    return True
                # Corrupt shard mid-read: the chunks already emitted were
                # hash-validated (bit-equal to live decode by construction);
                # re-decode from the top, suppressing re-emission of that
                # prefix, and REWRITE the snapshot (self-healing).
                skip = emitted
                counters.record(
                    "snapshot_fallback",
                    f"{snap.path}: corrupt shard after {skip} chunk(s) — "
                    "falling back to live decode (bit-equal), rewriting",
                )
                trace.instant(
                    "snapshot_fallback", path=snap.path, emitted=skip
                )
            try:
                self._writer = ksnap.SnapshotWriter(
                    root,
                    key,
                    mode=snap_mode,
                    meta={
                        "tar": ksnap.tar_identity(self._path),
                        "path": self._path,
                        "batch_size": self._batch_size,
                        "extra": self.config.snapshot_extra,
                    },
                )
            except (OSError, ksnap.SnapshotError) as e:
                # Same contract as the add_chunk tee: an unusable snapshot
                # root (unwritable, component is a file) must never kill a
                # healthy live-decode stream — counted, cache skipped.
                counters.record(
                    "snapshot_write_failed",
                    f"{self._path}: cannot open snapshot writer: {e}",
                )
        try:
            self._produce_live(skip)
        except _Cancelled:
            return False
        if self._writer is not None:
            try:
                self._writer.commit()
            except (OSError, ksnap.SnapshotError) as e:
                # Every chunk already reached the consumer — a failed
                # commit (ENOSPC, a concurrent writer racing os.replace)
                # loses only the CACHE, never the stream.
                counters.record(
                    "snapshot_write_failed",
                    f"{self._path}: commit failed: {e}",
                )
                self._writer.abort()
        return True

    def _emit_from_snapshot(self, snap) -> bool | int:
        """Stream a committed snapshot's chunks into the ring.  Returns
        True when the whole snapshot streamed, or the count of chunks
        already emitted when a corrupt shard forces the live-decode
        fallback."""
        emitted = 0
        images = 0
        served: list = []
        with trace.span(
            "ingest.snapshot_read", cat="ingest",
            path=snap.path, chunks=len(snap.manifest["chunks"]),
        ) as sp:
            try:
                for _entry, arrays in snap.iter_chunks():
                    if self._ring.stopped:
                        raise _Cancelled()
                    payload = arrays["payload"]
                    if snap.mode == "device":
                        # Pre-laid-out shard: dtype-final f32, batch dim
                        # padded to a sharding quantum.  The slice to
                        # the valid rows is a zero-copy view — the shard
                        # bytes flow straight into the consumer's
                        # device_put with NO host transform (the warm
                        # "pure DMA" epoch the tier exists for).
                        self.stats.snapshot_dma_bytes += int(
                            payload.nbytes
                        )
                        valid = int(arrays.get("valid", len(payload)))
                        if valid < len(payload):
                            payload = payload[:valid]
                    chunk = StreamBatch(
                        index=self._chunk_counter,
                        indices=np.asarray(arrays["indices"], np.int64),
                        names=[str(n) for n in arrays["names"].tolist()],
                        host=payload,
                    )
                    self._chunk_counter += 1
                    with trace.span(
                        "ingest.ring_put", cat="ingest",
                        index=chunk.index, images=len(chunk),
                    ):
                        ok = self._ring.put(chunk)
                    if not ok:
                        raise _Cancelled()
                    self.stats.batches += 1
                    self.stats.decoded += len(chunk)
                    self.stats.snapshot_chunks_read += 1
                    emitted += 1
                    images += len(chunk)
                    served.append((chunk.names, chunk.indices))
            except ksnap.SnapshotCorrupt as e:
                sp.set(fallback_after=emitted, corrupt=str(e)[:200])
                # The live fallback re-decodes (and re-counts) everything
                # from the top; un-count the snapshot prefix so stats stay
                # one-pass truthful.  Chunk numbering restarts with it.
                self.stats.decoded -= images
                self._chunk_counter = 0
                self._served_prefix = served
                return emitted
            sp.set(chunks_read=emitted, images=images)
        return True

    def _produce_live(self, skip_chunks: int = 0):
        self._skip_chunks = skip_chunks
        # Build/load the native decoder before any pool spins up (the
        # one-time g++ build runs under native_decode's module lock and
        # would otherwise stall every worker behind the first decode).
        from ..loaders.native_decode import available as _native_available

        _native_available()
        # Frozen per pass: a snapshot tee needs host pixels (the writer
        # materializes what the ring carried), and a corrupt-shard
        # FALLBACK re-decode (skip_chunks > 0) must reproduce the pixel
        # chunks the consumer already received — _emit's prefix
        # suppression and divergence guard only exist on the pixel path,
        # so the fallback pins host decode even when the rewrite writer
        # failed to open.  Mid-stream decode_mode mutation would mix
        # chunk kinds inconsistently, so the mode is not a live retune
        # surface.
        self._device_decode = (
            self.config.decode_mode == "device"
            and self._writer is None
            and skip_chunks == 0
        )
        if self._device_decode:
            # Same prewarm contract for the entropy hot loop: build/load
            # the native scan decoder (ops/native_entropy) before the
            # entropy pool spins up, and record which backend this pass
            # will run.  Unavailability degrades to the pure-Python pass
            # counted native_entropy_unavailable — bit-equal stream,
            # lower throughput, never a crash.
            from ..ops import jpeg_device as _jd

            self.stats.entropy_backend = _jd.entropy_backend()
        # shape -> (ordinals, names, images); insertion-ordered so the
        # end-of-stream flush of partial buckets is deterministic.
        buckets: dict = {}
        # geometry -> (ordinals, names, CoeffImages) for device decode
        coeff_buckets: dict = {}
        window: collections.deque = collections.deque()
        ordinal = 0

        def keep_image(name, img):
            nonlocal ordinal
            self.stats.decoded += 1
            key = img.shape[:2]
            idx, names, imgs = buckets.setdefault(key, ([], [], []))
            idx.append(ordinal)
            names.append(name)
            imgs.append(img)
            ordinal += 1
            if len(imgs) >= self._batch_size:
                self._emit(buckets.pop(key))

        def keep_coeff(name, ci):
            nonlocal ordinal
            self.stats.decoded += 1
            self.stats.entropy_decoded += 1
            self.stats.coeff_bytes += ci.geom.coeff_bytes()
            idx, names, imgs = coeff_buckets.setdefault(
                ci.geom, ([], [], [])
            )
            idx.append(ordinal)
            names.append(name)
            imgs.append(ci)
            ordinal += 1
            if len(imgs) >= self._batch_size:
                self._emit_coeff(ci.geom, coeff_buckets.pop(ci.geom))

        def drain_one():
            name, fut = window.popleft()
            img = self._await_decode(fut)
            if isinstance(img, _CorruptEntropy):
                # Damaged entropy-coded scan under device decode: a TYPED,
                # COUNTED skip — the rest of the batch survives, and the
                # member never becomes silent wrong pixels.
                counters.record(
                    "jpeg_corrupt_entropy", f"{name}: {img.detail}"
                )
                self.stats.skipped += 1
                self.stats.entropy_corrupt += 1
                return
            if isinstance(img, _FallbackPixels):
                # Outside the device path's baseline subset: decoded on
                # the host instead, counted PER REASON so a tar full of
                # (say) progressive JPEGs is visible as exactly that.
                counters.record(
                    "device_decode_fallback", f"{name}: {img.reason}"
                )
                counters.record(
                    f"device_decode_fallback_{img.reason}", name
                )
                self.stats.device_fallbacks += 1
                img = img.img
            if img is None:
                # "corrupt_image" for an undecodable member; the process
                # backend may instead report "decode_worker_lost" (a task
                # that kept killing its workers) — either way a COUNTED
                # skip, never a silent drop.
                counters.record(
                    getattr(fut, "skip_reason", None) or "corrupt_image",
                    name,
                )
                self.stats.skipped += 1
                return
            from ..ops.jpeg_device import CoeffImage

            if isinstance(img, CoeffImage):
                keep_coeff(name, img)
            else:
                keep_image(name, img)

        with trace.span(
            "ingest.produce", cat="ingest", path=self._path
        ) as prod_sp:
            try:
                for name, data in image_loaders._iter_tar_members(
                    self._path
                ):
                    if self._ring.stopped:
                        raise _Cancelled()
                    if self._keep is not None and not self._keep(name):
                        continue
                    window.append((name, self._submit_decode(name, data)))
                    # Live window limit: a retune takes effect at the
                    # next member ("while" drains DOWN to a narrowed
                    # window; completion order through the FIFO window
                    # is unchanged by any width).
                    while len(window) >= self.config.window():
                        drain_one()
                while window:
                    drain_one()
                # Flush the batch-size remainders (partial last batch
                # per shape/geometry), oldest bucket first for a
                # deterministic tail order across BOTH bucket kinds.
                tails = [
                    (b[0][0], None, b) for b in buckets.values()
                ] + [
                    (b[0][0], geom, b)
                    for geom, b in coeff_buckets.items()
                ]
                for _first, geom, bucket in sorted(
                    tails, key=lambda t: t[0]
                ):
                    if geom is None:
                        self._emit(bucket)
                    else:
                        self._emit_coeff(geom, bucket)
            except _Cancelled:
                # Consumer stopped the stream early — routine shutdown
                # (a supported path), not a producer failure: the span
                # marks it aborted rather than errored.
                prod_sp.set(aborted=True)
                raise
            finally:
                prod_sp.set(
                    decoded=self.stats.decoded,
                    skipped=self.stats.skipped,
                    batches=self.stats.batches,
                )

    def _emit(self, bucket):
        idx, names, imgs = bucket
        # np.stack copies straight out of any shared-memory views (the
        # process backend's zero-extra-copy path into chunk assembly);
        # the blocks are released the moment the chunk owns the pixels.
        host = np.stack(
            [i.arr if isinstance(i, _ShmArray) else i for i in imgs]
        )
        for i in imgs:
            if isinstance(i, _ShmArray):
                i.release()
        chunk = StreamBatch(
            index=self._chunk_counter,
            indices=np.asarray(idx, np.int64),
            names=names,
            host=host,
        )
        self._chunk_counter += 1
        if self._writer is not None:
            try:
                # pad_to only applies to device-format shards (the writer
                # pads the batch dim so warm epochs stream fixed-shape,
                # sharding-ready buffers); decoded shards store exactly
                # the chunk.
                self._writer.add_chunk(
                    chunk.index, chunk.indices, chunk.names, chunk.host,
                    pad_to=self._batch_size,
                )
                self.stats.snapshot_chunks_written += 1
            except (OSError, ksnap.SnapshotError) as e:
                # The cache is an optimization: a full disk (or any shard
                # write failure) must never kill a healthy live-decode
                # stream — counted, writer dropped, pass continues.
                counters.record(
                    "snapshot_write_failed", f"{self._path}: {e}"
                )
                self._writer.abort()
                self._writer = None
        if chunk.index < self._skip_chunks:
            # Fallback re-decode: this prefix already streamed from the
            # snapshot (hash-validated) — rewritten above, not re-emitted.
            # Suppression is only sound while the re-decode reproduces the
            # served chunks EXACTLY; a transient counted skip in either
            # pass shifts every later chunk boundary, so verify before
            # dropping (the consumer scatters rows by these ordinals —
            # a divergence here would silently scramble them).
            names, indices = self._served_prefix[chunk.index]
            if chunk.names != names or not np.array_equal(
                chunk.indices, indices
            ):
                counters.record(
                    "snapshot_fallback_divergence",
                    f"{self._path}: live re-decode chunk {chunk.index} != "
                    "snapshot prefix already served",
                )
                raise SnapshotFallbackDivergence(
                    f"{self._path}: chunk {chunk.index} of the fallback "
                    "re-decode does not match the snapshot prefix the "
                    "consumer already received (survivor sequences "
                    "diverged — see the counted skip that shifted them)"
                )
            return
        # The put span's duration IS the backpressure stall: a full ring
        # blocks here, and the trace shows the producer lane waiting.
        with trace.span(
            "ingest.ring_put", cat="ingest",
            index=chunk.index, images=len(chunk),
        ):
            ok = self._ring.put(chunk)
        if not ok:
            raise _Cancelled()
        self.stats.batches += 1

    def _emit_coeff(self, geom, bucket):
        """Assemble one same-geometry coefficient bucket into a
        :class:`CoeffChunk`-carrying :class:`StreamBatch` (device decode
        mode: the ring carries coefficients, never pixels).  Device-mode
        passes never tee a snapshot (``_device_decode`` is forced off
        while a writer is live), so no shard/suppression path exists
        here."""
        from ..ops.jpeg_device import stack_coeff_images

        idx, names, imgs = bucket
        coeffs, qt = stack_coeff_images(imgs)
        chunk = StreamBatch(
            index=self._chunk_counter,
            indices=np.asarray(idx, np.int64),
            names=names,
            host=None,
            coeff=CoeffChunk(geom=geom, coeffs=coeffs, qt=qt),
        )
        self._chunk_counter += 1
        with trace.span(
            "ingest.ring_put", cat="ingest",
            index=chunk.index, images=len(chunk),
            coeff_bytes=chunk.coeff.nbytes(),
        ):
            ok = self._ring.put(chunk)
        if not ok:
            raise _Cancelled()
        self.stats.batches += 1

    # -- consumer side --------------------------------------------------------

    def _yield_consumed(self, item):
        """Yield one chunk under an ``ingest.consume`` span: the span runs
        from the moment the consumer receives the chunk until it asks for
        the next one — i.e. the consumer's featurize time for THAT chunk,
        on the consumer thread's lane.  Decode spans on the worker lanes
        running inside a consume span's interval ARE the overlap."""
        with trace.span(
            "ingest.consume", cat="ingest",
            index=item.index, images=len(item),
        ):
            yield item

    def _publish_metrics(self) -> None:
        """Chunk-boundary gauges: the live trace-metrics the autotuner (and
        any operator dashboard) reads — ring depth plus the current knob
        values, alongside the stats counters."""
        m = trace.metrics
        # A retune may have moved the capacity: keep the stats record (the
        # bench/chaos artifact) consistent with the ring's live bound.
        self.stats.ring_capacity = self.config.ring_capacity
        m.gauge("ingest_ring_depth", self._ring.depth())
        m.gauge("ingest_decode_threads", self.config.decode_threads)
        m.gauge("ingest_decode_ahead", self.config.decode_ahead)
        m.gauge("ingest_ring_capacity", self.config.ring_capacity)
        m.gauge("ingest_producer_stalls", self.stats.producer_stalls)
        m.gauge("ingest_consumer_stalls", self.stats.consumer_stalls)
        m.gauge("ingest_decoded", self.stats.decoded)
        m.gauge("ingest_snapshot_chunks_read", self.stats.snapshot_chunks_read)
        m.gauge("ingest_worker_respawns", self.stats.worker_respawns)
        # Device-decode surface: entropy-decode progress, coefficient
        # bytes the ring carried, fallbacks to host decode, and
        # device-format shard bytes served straight to H2D — the warm
        # device-snapshot acceptance check reads these (all zero on a
        # pure-DMA epoch except the dma gauge).
        m.gauge("ingest_entropy_decoded", self.stats.entropy_decoded)
        m.gauge(
            "ingest_entropy_native",
            1 if self.stats.entropy_backend == "native" else 0,
        )
        m.gauge("ingest_coeff_bytes", self.stats.coeff_bytes)
        m.gauge("ingest_device_fallbacks", self.stats.device_fallbacks)
        m.gauge("ingest_snapshot_dma_bytes", self.stats.snapshot_dma_bytes)

    def _drain(self):
        pending: collections.deque = collections.deque()
        try:
            while True:
                with trace.span("ingest.ring_get", cat="ingest"):
                    item = self._ring.get()
                if item is _Ring._END:
                    break
                if self._transfer:
                    # Async dispatch: the H2D for this chunk starts now and
                    # overlaps the consumer's work on the PREVIOUS chunk
                    # still being featurized.  Coefficient chunks transfer
                    # their (much lighter) coefficient arrays — the pixel
                    # batch is only ever born on device.
                    if item.coeff is not None:
                        item.coeff.device = (
                            tuple(
                                _device_put(c) for c in item.coeff.coeffs
                            ),
                            _device_put(item.coeff.qt),
                        )
                    else:
                        item.device = _device_put(item.host)
                self._publish_metrics()
                if self.tuner is not None:
                    # Chunk boundary: the closed-loop controller reads the
                    # stall counters/gauges and may retune the config.
                    self.tuner.on_chunk(self)
                pending.append(item)
                if len(pending) >= DEVICE_BUFFERS:
                    yield from self._yield_consumed(pending.popleft())
            while pending:
                yield from self._yield_consumed(pending.popleft())
        finally:
            self.close()

    def __iter__(self):
        return self

    def __next__(self) -> StreamBatch:
        return next(self._iter)

    def __enter__(self) -> "IngestStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Stop the producer and release the ring.  Idempotent; called
        automatically on stream exhaustion, consumer exception, or context
        exit."""
        from . import telemetry as _telemetry

        _telemetry.unregister_statusz(
            self._statusz_name, self._statusz_provider
        )
        self._ring.stop()
        # Close the drain generator too: a consumer that stopped early
        # leaves it SUSPENDED at the yield inside an open ingest.consume
        # span, and a suspended span sits on this thread's span stack
        # corrupting every later span's depth/parent (and the flight
        # recorder's view) until the generator is garbage-collected.
        # Closing delivers GeneratorExit at the yield — the span exits as
        # aborted and pops.  ValueError = close() reached from INSIDE the
        # running generator (the exhaustion path's own finally); it is
        # already unwinding, nothing to do.
        try:
            self._iter.close()
        except ValueError:
            pass

    def join(self, timeout: float = 10.0) -> bool:
        """Wait for the producer, every decoder thread, AND every decode
        worker process to exit; returns True when no ingest thread or
        process remains alive (the no-leak assertion the tier-1 suite runs
        under pytest)."""
        end = time.monotonic() + timeout
        self._thread.join(max(0.0, end - time.monotonic()))
        for t in list(self._workers):
            t.join(max(0.0, end - time.monotonic()))
        procs_ok = True
        if self._proc_pool is not None:
            while (
                not self._proc_pool.joined() and time.monotonic() < end
            ):
                time.sleep(_POLL_SECONDS / 5)
            procs_ok = self._proc_pool.joined()
        return procs_ok and not (
            self._thread.is_alive()
            or any(t.is_alive() for t in self._workers)
        )


def stream_batches(
    path: str,
    batch_size: int,
    *,
    keep: Callable[[str], bool] | None = None,
    num_threads: int | None = None,
    decode_ahead_slots: int | None = None,
    capacity: int | None = None,
    transfer: bool = True,
    config: StreamConfig | None = None,
    tuner=None,
) -> IngestStream:
    """Stream shape-bucketed device batches from a tar (or directory of
    tars) of images.

    ``keep``: member-name predicate (label filtering before decode).
    ``config``: a :class:`StreamConfig` — the stream's LIVE knob set
    (env-seeded via :meth:`StreamConfig.from_env` when omitted); mutate it
    mid-stream to retune, or set ``config.autotune`` (env
    ``KEYSTONE_AUTOTUNE=1``) for the closed-loop controller.
    ``num_threads`` / ``decode_ahead_slots`` / ``capacity``: legacy
    per-stream overrides of the config's initial values.
    ``transfer=False`` skips the H2D stage (host-only consumers, decode
    benchmarking).  ``tuner``: an explicit controller (anything with
    ``attach(stream)`` / ``on_chunk(stream)``) instead of the default.

    Yields :class:`StreamBatch` in assembly order; use as a context
    manager (or iterate to exhaustion) so the decode threads are released,
    and ``stream.join()`` to assert they exited."""
    return IngestStream(
        path,
        batch_size,
        keep=keep,
        num_threads=num_threads,
        decode_ahead_slots=decode_ahead_slots,
        capacity=capacity,
        transfer=transfer,
        config=config,
        tuner=tuner,
    )


def host_shards(paths, rank: int | None = None, world: int | None = None):
    """This host's slice of a tar-shard list: deterministic round-robin
    (``paths[rank::world]``) over the SORTED names, so every member of a
    process group derives a disjoint cover of the dataset from the same
    listing with no coordination.  ``rank``/``world`` default from the
    live process group (``parallel.distributed``) and collapse to
    "everything" single-process — the multi-host data axis costs the
    single-process path nothing.  Each host then streams its own shards
    through :func:`stream_batches`; no bytes cross hosts at ingest."""
    paths = sorted(str(p) for p in paths)
    if rank is None or world is None:
        from ..parallel import distributed as kdist

        rank = kdist.process_index() if rank is None else rank
        world = kdist.process_count() if world is None else world
    if world <= 1:
        return paths
    if not (0 <= rank < world):
        raise ValueError(f"rank {rank} outside world {world}")
    return paths[rank::world]
