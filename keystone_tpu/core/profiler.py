"""Device cost attribution: the per-program MFU ledger, the HBM watermark
sampler, and triggered XLA profiler capture.

The host side of this system is observable (spans, SLO surface, flight
recorder); the DEVICE was a black box: XLA's ``cost_analysis`` was called
ad-hoc in two bench-only sites, and ``plan_program``'s charged bytes were
never compared to what the device actually allocated.  This module is the
measured substrate that closes those gaps:

* **Program ledger** — every compiled-program execution path
  (``run_ladder`` tiers, ``ServingEngine`` buckets, the fused
  device-decode+featurize dispatch) calls :func:`record_program` with its
  compiled executable and device-synced measured wall; the ledger joins
  ``cost_analysis()`` FLOPs/bytes with the wall into live per-program MFU
  and roofline position (``optimize.DEVICE_RATES``),
  exported as ``profiler_*`` gauges in ``trace.metrics`` (Prometheus rides
  for free) and ``profiler.program`` trace instants, and aggregated by
  :func:`ledger_record`.
* **HBM watermark sampler** — a background thread polls
  ``device.memory_stats()`` every ``KEYSTONE_HBM_SAMPLE_MS`` and keeps
  per-:func:`phase` high-water marks; :func:`audit_plan` compares a
  phase's watermark against the ``plan_program`` charge — drift beyond
  ``KEYSTONE_PLAN_DRIFT_TOL`` is counted (``plan_drift``).  A sampler
  crash is a counted degradation (``profiler_sampler_crash``),
  never a failed run — the chaos family ``profiler_crash`` enforces it.
* **Triggered XLA capture** — :func:`maybe_capture` opens a bounded
  ``jax.profiler`` trace window under ``KEYSTONE_XPROF_DIR`` (at most
  :data:`MAX_CAPTURES_PER_KIND` per kind per process, one window at a
  time, ``KEYSTONE_XPROF_WINDOW_S`` long), fired by an SLO burn-rate
  breach (``telemetry.SLOTracker``) or any postmortem-family fault;
  capture paths are linked from the flight-recorder dump.

Overhead discipline: :func:`enabled` is one module-flag/env check; with
the profiler OFF every hook in the execution paths is that single check
(the tier-1 suite pins an empty ledger and no sampler thread after a
profiled-shape run).  ON, the per-run cost is one cached cost-analysis
lookup + a dict update under a lock (the serve-path p99 overhead: not
measured on the chip).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
import os
import re
import threading
import time
import weakref

from . import trace
from .resilience import counters

_logger = logging.getLogger("keystone_tpu.profiler")

#: env var: ``1`` turns the cost-attribution layer on (ledger + sampler).
PROFILER_ENV = "KEYSTONE_PROFILER"
#: env var: HBM watermark sampling period in milliseconds.
HBM_SAMPLE_ENV = "KEYSTONE_HBM_SAMPLE_MS"
#: env var: directory for triggered ``jax.profiler`` capture windows
#: (unset = capture disabled).
XPROF_DIR_ENV = "KEYSTONE_XPROF_DIR"
#: env var: seconds one triggered capture window stays open.
XPROF_WINDOW_ENV = "KEYSTONE_XPROF_WINDOW_S"
#: env var: relative tolerance before watermark-vs-charge drift is counted.
DRIFT_TOL_ENV = "KEYSTONE_PLAN_DRIFT_TOL"

DEFAULT_HBM_SAMPLE_MS = 50.0
DEFAULT_XPROF_WINDOW_S = 0.5
DEFAULT_DRIFT_TOL = 0.25

#: Per-kind capture cap per process: the first windows around a breach
#: carry the information; a fault storm must not fill a disk with xprof.
MAX_CAPTURES_PER_KIND = 2

_NAME_RE = re.compile(r"[^a-zA-Z0-9_.-]")

_override: bool | None = None


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() in ("1", "true", "on", "yes")


def enabled() -> bool:
    """Is the cost-attribution layer on?  ``KEYSTONE_PROFILER=1`` or the
    programmatic :func:`profiled` override.  This is THE hot-path check —
    every hook in the execution paths is gated on it."""
    if _override is not None:
        return _override
    return _env_flag(PROFILER_ENV)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        _logger.error("%s=%r is not a number — using %g", name, raw, default)
        return default


def drift_tol() -> float:
    return max(0.0, _env_float(DRIFT_TOL_ENV, DEFAULT_DRIFT_TOL))


# -- cost analysis (the ONE cost_analysis call site) ---------------------------

#: id(obj) -> (weakref(obj), cost dict).  Bounded (probe-style callers
#: walk many throwaway executables; the ledger must not pin them), and
#: identity-validated through the weakref: a recycled id after GC must
#: never serve another program's flops.
_cost_cache: dict[int, tuple] = {}
_COST_CACHE_MAX = 256


def _keep_ref(obj):
    try:
        return weakref.ref(obj)
    except TypeError:  # unweakreferenceable executables: hold it strong
        return lambda o=obj: o


def _cache_cost(key_obj, cost) -> None:
    if len(_cost_cache) >= _COST_CACHE_MAX:
        _cost_cache.pop(next(iter(_cost_cache)))
    _cost_cache[id(key_obj)] = (_keep_ref(key_obj), cost)


def _cached_cost(key_obj):
    cached = _cost_cache.get(id(key_obj))
    if cached is not None and cached[0]() is key_obj:
        return cached[1]
    return None


def program_cost(compiled) -> dict:
    """``cost_analysis()`` of one compiled executable as a plain dict:
    ``{"flops": float|None, "bytes_accessed": float|None}``.  The single
    place the raw XLA cost-analysis quirks live (list-wrapped analyses,
    missing keys, backends without the API) — bench and every profiler
    hook read through here instead of re-implementing the unwrap."""
    cached = _cached_cost(compiled)
    if cached is not None:
        return cached
    out: dict = {"flops": None, "bytes_accessed": None}
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        out["flops"] = float(analysis.get("flops", 0.0)) or None
        out["bytes_accessed"] = (
            float(analysis.get("bytes accessed", 0.0)) or None
        )
    except Exception:  # noqa: BLE001 — cost analysis is advisory
        pass
    _cache_cost(compiled, out)
    return out


def cost_pair(compiled) -> tuple[float | None, float | None]:
    """``(flops, bytes_accessed)`` — the tuple shape bench always wanted."""
    c = program_cost(compiled)
    return c["flops"], c["bytes_accessed"]


def jit_cost(jitted_fn, *args, **kwargs) -> tuple[float | None, float | None]:
    """``(flops, bytes_accessed)`` of a jitted callable on ``args`` —
    lowering hits the jit cache, so a warm function is never traced or
    compiled a second time (the former ``bench.compiled_cost``)."""
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001 — advisory
        return None, None
    return cost_pair(compiled)


#: (id(key_obj), shape_key) -> (weakref(key_obj), (flops, bytes)).  The
#: streaming hot paths (StreamBatch.apply, fused_apply) attribute the
#: SAME program once per chunk — re-lowering per chunk just to re-derive
#: identical numbers would be real per-chunk overhead, so the pair is
#: memoized on a stable live object + shape key (identity-validated, like
#: the executable cache above).
_keyed_cost_cache: dict[tuple, tuple] = {}


def jit_cost_keyed(
    key_obj, shape_key, jitted_fn, *args, **kwargs
) -> tuple[float | None, float | None]:
    """:func:`jit_cost` memoized under ``(key_obj identity, shape_key)``
    — one lower per (program, shape), not one per dispatch."""
    key = (id(key_obj), shape_key)
    cached = _keyed_cost_cache.get(key)
    if cached is not None and cached[0]() is key_obj:
        return cached[1]
    cost = jit_cost(jitted_fn, *args, **kwargs)
    if len(_keyed_cost_cache) >= _COST_CACHE_MAX:
        _keyed_cost_cache.pop(next(iter(_keyed_cost_cache)))
    _keyed_cost_cache[key] = (_keep_ref(key_obj), cost)
    return cost


def attributed_call(label: str, shape_key, fn, *args):
    """``fn(*args)`` with ledger attribution: device-synced wall, the
    memoized per-(fn, shape) cost pair (when ``fn`` is a lowerable jit),
    one :func:`record_program` row under ``label``.  THE profiled-dispatch
    idiom for the streaming hot paths (``StreamBatch.apply``,
    ``jpeg_device.fused_apply``) — callers gate on :func:`enabled`, so
    this is never on the off path.  Syncing trades the caller's
    pipelining for measurement; values are unchanged."""
    t0 = time.perf_counter()
    out = fn(*args)
    wall = synced_wall(out, t0)
    fl, ba = (
        jit_cost_keyed(fn, shape_key, fn, *args)
        if hasattr(fn, "lower")
        else (None, None)
    )
    record_program(label, None, wall, flops=fl, bytes_accessed=ba)
    return out


# -- device rates --------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def device_rates() -> dict | None:
    """``{"peak_flops", "hbm_gbps"}`` of the live device from
    ``optimize.DEVICE_RATES`` (read once per process), or ``None`` when its
    ``device_kind`` is not in the table — MFU and roofline position are
    then reported as ``None``, never against a made-up peak."""
    import jax

    from . import optimize as kopt

    row = kopt.DEVICE_RATES.get(jax.devices()[0].device_kind)
    return row and {
        "peak_flops": row["peak_flops"], "hbm_gbps": row["hbm_gbps"],
    }


# -- the program ledger --------------------------------------------------------


class _ProgramRow:
    """Aggregated cost attribution for one program label."""

    __slots__ = (
        "label", "runs", "wall_seconds", "flops", "bytes_accessed",
        "last_wall_seconds", "last_mfu", "last_hbm_gbps",
    )

    def __init__(self, label: str):
        self.label = label
        self.runs = 0
        self.wall_seconds = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.last_wall_seconds = 0.0
        self.last_mfu: float | None = None
        self.last_hbm_gbps: float | None = None

    def record(self) -> dict:
        rates = device_rates()
        wall = self.wall_seconds
        flops_rate = self.flops / wall if wall > 0 and self.flops else None
        gbps = (
            self.bytes_accessed / wall / 1e9
            if wall > 0 and self.bytes_accessed
            else None
        )
        intensity = (
            self.flops / self.bytes_accessed if self.bytes_accessed else None
        )
        ridge = (
            rates["peak_flops"] / (rates["hbm_gbps"] * 1e9) if rates else None
        )
        out = {
            "runs": self.runs,
            "wall_seconds": round(wall, 6),
            "flops": self.flops or None,
            "bytes_accessed": self.bytes_accessed or None,
            "mfu": (
                round(flops_rate / rates["peak_flops"], 6)
                if flops_rate and rates
                else None
            ),
            "achieved_hbm_gbps": round(gbps, 3) if gbps else None,
            "intensity_flop_per_byte": (
                round(intensity, 3) if intensity else None
            ),
            "ridge_flop_per_byte": round(ridge, 3) if ridge else None,
            # Roofline position: below the ridge intensity the program's
            # ceiling is HBM bandwidth, above it the MXU peak.
            "bound": (
                ("memory" if intensity < ridge else "compute")
                if intensity and ridge
                else None
            ),
            "last_wall_seconds": round(self.last_wall_seconds, 6),
        }
        return out


_ledger_lock = threading.Lock()
_ledger: dict[str, _ProgramRow] = {}
_LEDGER_MAX = 512


def record_program(
    label: str,
    compiled,
    wall_seconds: float,
    *,
    flops: float | None = None,
    bytes_accessed: float | None = None,
) -> dict | None:
    """Attribute one device-synced execution of ``compiled`` to the
    ledger: joins the program's ``cost_analysis()`` FLOPs/bytes (cached
    per executable; explicit overrides win) with ``wall_seconds`` into
    per-run MFU and achieved HBM bandwidth.  Returns the per-run numbers
    (None when the profiler is off).  Exported live as
    ``profiler_<label>_mfu`` / ``profiler_<label>_gbps`` gauges and a
    ``profiler.program`` trace instant."""
    if not enabled():
        return None
    if flops is None or bytes_accessed is None:
        cost = (
            program_cost(compiled)
            if compiled is not None
            else {"flops": None, "bytes_accessed": None}
        )
        flops = flops if flops is not None else cost["flops"]
        bytes_accessed = (
            bytes_accessed
            if bytes_accessed is not None
            else cost["bytes_accessed"]
        )
    rates = device_rates()
    wall = max(float(wall_seconds), 0.0)
    mfu = (
        flops / wall / rates["peak_flops"]
        if flops and wall > 0 and rates
        else None
    )
    gbps = (
        bytes_accessed / wall / 1e9 if bytes_accessed and wall > 0 else None
    )
    with _ledger_lock:
        row = _ledger.get(label)
        if row is None:
            if len(_ledger) >= _LEDGER_MAX:
                _ledger.pop(next(iter(_ledger)))
            row = _ledger[label] = _ProgramRow(label)
        row.runs += 1
        row.wall_seconds += wall
        row.last_wall_seconds = wall
        if flops:
            row.flops += flops
        if bytes_accessed:
            row.bytes_accessed += bytes_accessed
        row.last_mfu = mfu
        row.last_hbm_gbps = gbps
    metric = _NAME_RE.sub("_", label)
    if mfu is not None:
        trace.metrics.gauge(f"profiler_{metric}_mfu", round(mfu, 6))
    if gbps is not None:
        trace.metrics.gauge(f"profiler_{metric}_gbps", round(gbps, 3))
    trace.metrics.inc("profiler_programs_recorded")
    out = {
        "label": label,
        "wall_seconds": round(wall, 6),
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "mfu": round(mfu, 6) if mfu is not None else None,
        "achieved_hbm_gbps": round(gbps, 3) if gbps is not None else None,
    }
    trace.instant("profiler.program", **out)
    return out


def ledger() -> dict:
    """Snapshot of the per-program rows (label -> aggregate record)."""
    with _ledger_lock:
        rows = list(_ledger.values())
    return {r.label: r.record() for r in rows}


def ledger_record() -> dict:
    """The bench ``profiler`` section: the ledger plus the device rates
    the MFU figures were computed against."""
    return {
        "rates": device_rates(),
        "programs": ledger(),
        "captures": capture_paths(),
    }


def synced_wall(out, t0: float) -> float:
    """Honest wall seconds for a possibly-async result: block until the
    result pytree is ready, then measure from ``t0``.  A wall that omits
    the device-side completion would train the MFU ledger toward
    dispatch-time fantasy numbers."""
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:  # noqa: BLE001 — an unsyncable result is not an error
        pass
    return time.perf_counter() - t0


# -- the HBM watermark sampler -------------------------------------------------


class HbmSampler:
    """Background thread polling device ``memory_stats()`` bytes-in-use.

    Keeps a process-lifetime high-water mark plus one per live
    :func:`phase`; phase exit takes one synchronous sample so a phase
    shorter than the polling period still gets a watermark.  A backend
    that cannot report (CPU without allocator stats) disables the sampler
    after its first poll — watermarks are then ``None`` and every audit
    skips, never guesses.  A CRASH of the sampling thread is a counted
    degradation (``profiler_sampler_crash``): the run it was watching
    completes unprofiled, bit-equal to an unprofiled run (the
    ``profiler_crash`` chaos family's invariant)."""

    def __init__(
        self,
        interval_ms: float | None = None,
        stats_fn=None,
    ):
        self.interval_s = (
            interval_ms
            if interval_ms is not None
            else _env_float(HBM_SAMPLE_ENV, DEFAULT_HBM_SAMPLE_MS)
        ) / 1e3
        self.interval_s = max(self.interval_s, 1e-4)
        self._stats_fn = stats_fn or self._device_stats
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._global_peak = 0
        self._phase_peaks: dict[str, int] = {}
        self._active: dict[str, int] = {}  # phase -> refcount
        self.samples = 0
        self.crashed = False
        self.unavailable = False
        self._thread = threading.Thread(
            target=self._loop, name="keystone-hbm-sampler", daemon=True
        )

    @staticmethod
    def _device_stats() -> int | None:
        import jax

        try:
            stats = jax.devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — backends without stats
            return None
        if not stats:
            return None
        used = stats.get("bytes_in_use")
        return int(used) if used else None

    def start(self) -> "HbmSampler":
        self._thread.start()
        return self

    def _loop(self) -> None:
        try:
            while not self._stop.wait(self.interval_s):
                if not self.sample():
                    return
        except Exception as e:  # noqa: BLE001 — counted, never a failed run
            self.crashed = True
            counters.record(
                "profiler_sampler_crash",
                f"HBM watermark sampler died ({type(e).__name__}: {e}) — "
                "run continues unprofiled",
            )

    def sample(self) -> bool:
        """Take one sample.  Returns False when the backend cannot report
        (the sampler retires itself — polling an API that will never
        answer is pure overhead)."""
        used = self._stats_fn()
        if used is None:
            self.unavailable = True
            self._stop.set()
            return False
        with self._lock:
            self.samples += 1
            self._global_peak = max(self._global_peak, used)
            for name in self._active:
                self._phase_peaks[name] = max(
                    self._phase_peaks.get(name, 0), used
                )
        trace.metrics.gauge("profiler_hbm_bytes_in_use", used)
        trace.metrics.gauge("profiler_hbm_watermark_bytes", self._global_peak)
        return True

    def phase_enter(self, name: str) -> None:
        with self._lock:
            n = self._active.get(name, 0)
            if n == 0:
                # Fresh entry: the phase's watermark must describe THIS
                # occupancy, not a bigger run that used the same phase
                # name earlier in the process — a stale peak would read
                # as spurious drift against the current plan's charge
                # (and poison the hbm_drift calibration rows).
                self._phase_peaks.pop(name, None)
            self._active[name] = n + 1

    def phase_exit(self, name: str) -> None:
        # One synchronous sample on the way out: a phase shorter than the
        # polling period still records the bytes it was holding.
        if not (self._stop.is_set() or self.crashed):
            with contextlib.suppress(Exception):
                self.sample()
        with self._lock:
            n = self._active.get(name, 0) - 1
            if n <= 0:
                self._active.pop(name, None)
            else:
                self._active[name] = n

    def watermark(self, phase: str | None = None) -> int | None:
        """High-water mark bytes: a phase's (None until it was sampled at
        least once) or the process-lifetime peak."""
        with self._lock:
            if phase is not None:
                return self._phase_peaks.get(phase)
            return self._global_peak or None

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        self._thread.join(timeout)

    def record(self) -> dict:
        with self._lock:
            return {
                "samples": self.samples,
                "interval_ms": round(self.interval_s * 1e3, 3),
                "global_watermark_bytes": self._global_peak or None,
                "phase_watermark_bytes": dict(self._phase_peaks),
                "crashed": self.crashed,
                "unavailable": self.unavailable,
            }


_sampler_lock = threading.Lock()
_sampler: HbmSampler | None = None


def ensure_sampler(
    interval_ms: float | None = None, stats_fn=None
) -> HbmSampler | None:
    """The process sampler, started on first use (None when the profiler
    is off).  ``stats_fn`` is the test/chaos seam — an injected stats
    source replaces the device poll."""
    if not enabled():
        return None
    global _sampler
    with _sampler_lock:
        if _sampler is None or (
            stats_fn is not None and _sampler._stats_fn is not stats_fn
        ):
            if _sampler is not None:
                _sampler.stop(0.5)
            _sampler = HbmSampler(
                interval_ms=interval_ms, stats_fn=stats_fn
            ).start()
        return _sampler


def sampler() -> HbmSampler | None:
    return _sampler


def stop_sampler() -> None:
    global _sampler
    with _sampler_lock:
        if _sampler is not None:
            _sampler.stop()
            _sampler = None


@contextlib.contextmanager
def phase(name: str):
    """Attribute HBM watermarks inside this block to ``name`` (the solver
    fits and serve batches declare themselves; nested phases each get
    their own watermark).  A no-op when the profiler is off."""
    s = ensure_sampler()
    if s is None:
        yield
        return
    s.phase_enter(name)
    try:
        yield
    finally:
        s.phase_exit(name)


def watermark(phase_name: str | None = None) -> int | None:
    s = _sampler
    return s.watermark(phase_name) if s is not None else None


def audit_plan(
    label: str,
    plan,
    *,
    phase_name: str | None = None,
) -> dict | None:
    """Compare the watermark the sampler actually saw against what
    ``plan_program`` charged for the program that ran.  Drift beyond
    ``KEYSTONE_PLAN_DRIFT_TOL`` (relative, either direction) is counted
    (``plan_drift``).  Returns the audit row, or None when either side is unknown (no sampler, backend
    without stats, unanalyzed plan) — skipped, never guessed."""
    if not enabled():
        return None
    charged = int(getattr(plan, "total_bytes", 0) or 0)
    if charged <= 0:
        return None
    # PHASE watermark only — the process-lifetime global peak describes
    # whatever ran biggest since import, and auditing a small plan
    # against it would manufacture drift.  No phase sample (sampler dead
    # or phase never entered) -> skipped, never guessed.
    wm = watermark(phase_name or label)
    if not wm:
        return None
    drift = wm / charged
    tol = drift_tol()
    drifted = abs(math.log(drift)) > math.log1p(tol)
    audit = {
        "label": label,
        "charged_bytes": charged,
        "watermark_bytes": int(wm),
        "drift_ratio": round(drift, 4),
        "tolerance": tol,
        "drifted": drifted,
    }
    if drifted:
        from . import memory as kmem

        counters.record(
            "plan_drift",
            f"{label}: device watermark {kmem.fmt_bytes(wm)} vs plan charge "
            f"{kmem.fmt_bytes(charged)} (x{drift:.3g}, tol ±{tol:.0%}) — "
            "the admission model drifted from the device",
        )
    trace.instant("plan_drift", **audit)
    trace.metrics.gauge(
        f"profiler_{_NAME_RE.sub('_', label)}_plan_drift", round(drift, 4)
    )
    return audit


# -- triggered XLA capture -----------------------------------------------------

_capture_lock = threading.Lock()
_capture_counts: dict[str, int] = {}
_capture_paths: list[str] = []
_capture_active = False
_capture_timer: threading.Timer | None = None
#: monotonically increasing window id: a close callback only stops the
#: window it OPENED (cancel() cannot stop an already-running timer, so
#: without ownership a stale closer could truncate a newer window).
_capture_gen = 0


def _xprof_dir() -> str | None:
    raw = os.environ.get(XPROF_DIR_ENV, "").strip()
    return raw or None


def _start_trace(logdir: str) -> None:  # seam: tests patch this
    import jax

    jax.profiler.start_trace(logdir)


def _stop_trace() -> None:  # seam: tests patch this
    import jax

    jax.profiler.stop_trace()


def capture_paths() -> list[str]:
    """Directories of every capture window this process opened (linked
    from flight-recorder postmortem dumps)."""
    with _capture_lock:
        return list(_capture_paths)


def maybe_capture(kind: str, reason: str = "") -> str | None:
    """Open one bounded ``jax.profiler`` trace window for trigger
    ``kind`` if ``KEYSTONE_XPROF_DIR`` is set, no window is already open,
    and the per-kind cap (:data:`MAX_CAPTURES_PER_KIND`) has room.  The
    window closes itself after ``KEYSTONE_XPROF_WINDOW_S`` on a daemon
    timer.  Returns the capture directory or None.  Never raises and
    never counts through the fault ledger — a capture fired FROM the
    fault path must not re-enter it."""
    dump_dir = _xprof_dir()
    if dump_dir is None:
        return None
    global _capture_active, _capture_gen
    with _capture_lock:
        n = _capture_counts.get(kind, 0)
        if n >= MAX_CAPTURES_PER_KIND or _capture_active:
            return None
        _capture_counts[kind] = n + 1
        _capture_active = True
        _capture_gen += 1
        gen = _capture_gen
    path = os.path.join(
        dump_dir, f"xprof_{_NAME_RE.sub('_', kind)}_{os.getpid()}_{n}"
    )
    try:
        os.makedirs(path, exist_ok=True)
        _start_trace(path)
    except Exception:  # noqa: BLE001 — capture is advisory
        _logger.exception("xprof capture for %r failed to start", kind)
        with _capture_lock:
            _capture_active = False
            # Refund the budget: no window opened, so a transient start
            # failure must not burn the kind's cap for the process.
            _capture_counts[kind] = max(0, _capture_counts.get(kind, 1) - 1)
        return None

    def _close(gen: int = gen) -> None:
        global _capture_active, _capture_timer
        with _capture_lock:
            if gen != _capture_gen or not _capture_active:
                # A reset (or a newer window) took over since this timer
                # was armed — the window it owned is already closed, and
                # stopping here would truncate someone else's capture.
                return
            _capture_active = False
            _capture_timer = None
        try:
            _stop_trace()
        except Exception:  # noqa: BLE001
            _logger.exception("xprof capture stop failed")

    timer = threading.Timer(
        _env_float(XPROF_WINDOW_ENV, DEFAULT_XPROF_WINDOW_S), _close
    )
    timer.daemon = True
    timer.start()
    with _capture_lock:
        _capture_paths.append(path)
        _capture_timer = timer
    trace.metrics.inc("profiler_captures")
    trace.instant("xprof_capture", kind=kind, path=path, reason=reason)
    _logger.warning(
        "xprof capture window opened -> %s (trigger %s%s)",
        path, kind, f": {reason}" if reason else "",
    )
    return path


# -- lifecycle / test seams ----------------------------------------------------


def reset_state() -> None:
    """Test isolation: empty ledger, forget capture caps, stop and
    drop the sampler, cancel any open capture window's timer (a stale
    timer firing later would stop a NEW window early — or call
    ``stop_trace`` with nothing open)."""
    stop_sampler()
    global _capture_active, _capture_timer, _capture_gen
    with _capture_lock:
        _capture_counts.clear()
        _capture_paths.clear()
        was_open = _capture_active
        _capture_active = False
        # Invalidate every armed closer: cancel() cannot stop one that
        # already started running, but the generation check makes a
        # stale closer a no-op instead of a truncation of whatever
        # window opens next.
        _capture_gen += 1
        timer, _capture_timer = _capture_timer, None
    if timer is not None:
        timer.cancel()
    if was_open:
        # The reset owns the open window now — close it (best effort) so
        # no trace session outlives the reset.
        with contextlib.suppress(Exception):
            _stop_trace()
    with _ledger_lock:
        _ledger.clear()
    _cost_cache.clear()
    _keyed_cost_cache.clear()


@contextlib.contextmanager
def profiled(
    on: bool = True,
    *,
    interval_ms: float | None = None,
    stats_fn=None,
):
    """Programmatic enable/disable for benches and tests: overrides the
    env gate for the block, starts the sampler (with an optional injected
    stats source — the chaos harness's crash seam), and restores the
    previous state (sampler stopped) on exit."""
    global _override
    prev = _override
    _override = on
    try:
        if on:
            # Pre-warm the lazies the first attribution would otherwise
            # pay ON the hot path (rate-table import, jax.devices): the
            # steady-state overhead is what a serving cell would bound.
            device_rates()
            ensure_sampler(interval_ms=interval_ms, stats_fn=stats_fn)
        yield
    finally:
        _override = prev
        if on:
            stop_sampler()
