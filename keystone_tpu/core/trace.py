"""Unified tracing & metrics: structured spans, a process-wide metrics
registry, and Chrome-trace/JSONL exporters.

KeystoneML's cost-based optimizer decides caching/materialization from
*measured per-node profiles* (time + output size, PipelineRuntimeEstimator);
tf.data lives on built-in per-stage metrics feeding autotuning.  Neither is
possible while timing/counters are scattered across ``stage_timer``,
``resilience.counters``, ``FitReport``, and ad-hoc ring stats with no shared
schema.  This module is that shared substrate:

* :func:`span` — a thread-safe context manager producing nested structured
  spans: wall time, thread id, nesting depth/parent, arbitrary JSON-able
  attributes (bytes/shape/dtype), optional device-sync time
  (``sp.sync(value)`` runs ``jax.block_until_ready`` and records the
  synced duration).  A span names what caused it: ``args`` carry a
  process-unique ``id``, the ``parent_id`` and ``root``, the id of its
  outermost ancestor on the thread (the spans of one fit share it).  When
  tracing is disabled ``span()`` returns a shared no-op singleton — no
  allocation, no lock, one attribute check.
* **The fit timeline.**  A workload's ``run`` is one root span ``fit`` (cat
  ``fit``).  :class:`Stage` (through ``core.logging.stage_timer``) is the
  layer boundary: spans of cat ``stage`` that tile the root but for glue,
  each name once a fit.  Beneath a stage the host code says whether it
  works or waits, by cat: ``wait`` (:func:`wait`: blocked on the device),
  ``d2h`` (:func:`d2h`: a read to the host, counted as waiting), ``h2d``
  (:func:`h2d`: a copy to the device, with bytes) and ``host``
  (:func:`host`: a named section of host work, one of :data:`SECTIONS`:
  ``dispatch`` the call of a jitted program, ``stack``, ``draw``,
  ``concat``, the solvers' ``search`` / ``plan`` / ``place`` / ``sort`` /
  ``finish``, the checkpoint's ``write``).  At exit a stage records
  ``stage_ms.<name>`` (self time), ``stage_wait_ms.<name>``,
  ``stage_h2d_ms.<name>`` and ``stage_h2d_mb.<name>`` into :data:`metrics`
  and, for every section charged beneath it,
  ``stage_host_ms.<name>.<section>`` (summed self time),
  ``stage_host_n.<name>.<section>`` (occurrences) and
  ``stage_max_ms.<name>.<part>`` (the longest single occurrence; a part is
  a section, ``wait`` or ``h2d``) — always: the sums are kept on the
  stage, not read back from spans, so they hold with tracing off and with
  the flight ring off.  Every microsecond of a stage's self time lands in
  exactly one of ``wait``, ``h2d``, a section or ``other``
  (``stage_host_ms.<name>.other``: what no helper covers).
* **One clock with the device trace.**  While tracing is enabled a span is
  also a ``jax.profiler.TraceAnnotation`` named ``ks/<cat>/<name>`` with
  its ``id`` and ``root``: under ``jax.profiler.start_trace`` the program's
  spans sit in the xplane's host plane on the profiler's clock, beside the
  device's, with no offset to estimate.  With tracing off none is made.
* :data:`metrics` — the process-wide registry unifying **counters**,
  **gauges**, and **histograms** behind one API, with an atomic
  :meth:`Metrics.snapshot`.  ``resilience.counters`` (the fault ledger)
  rides along as an adopted group, so one snapshot captures both.
* :func:`instant` — point events (admission decisions, fault counts) that
  land in the same timeline as spans.
* Exporters: **Chrome trace_event JSON** (loads in Perfetto / chrome://
  tracing; the default for ``*.json`` paths) and a **JSONL event log**
  (``*.jsonl``).  Enable with ``KEYSTONE_TRACE=out.json`` (checked once at
  import; the file is written at process exit) or programmatically with
  :func:`enable` / a workload's ``--trace`` flag.
* **Flight recorder** — a bounded ring of the most recent events that runs
  even with tracing DISABLED (``KEYSTONE_FLIGHT_DEPTH``, 0 disables): a
  fault that fires in an untraced production process still has its last
  moments on record, and ``core.telemetry`` dumps the ring as a postmortem
  JSON when a typed fault is counted.  The ring is a fixed-capacity deque
  — old events fall off the back, retained memory is bounded and constant
  once warm.

Overhead discipline: with tracing AND the flight ring off the path is a
module-state check returning a cached null object; with only the ring on,
each finished span is one small dict append into a bounded deque (the
tier-1 suite asserts no retained allocation growth once the ring is warm,
and a span's enter + exit under 20 us), a charge (:func:`wait`,
:func:`h2d`, :func:`host`) adds two clock reads and a few float adds to its
span, and a stage adds one registry lock.
Enabled, each finished span is one dict append under a lock (bounded at
:data:`MAX_EVENTS`; overflow is counted, never unbounded).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import itertools
import json
import logging
import os
import sys
import tempfile
import threading
import time

_logger = logging.getLogger("keystone_tpu.trace")

#: env var: path of the trace file to write at process exit ("out.json" for
#: Chrome trace_event JSON viewable in Perfetto, "out.jsonl" for JSONL).
TRACE_ENV = "KEYSTONE_TRACE"

#: env var: flight-recorder ring depth (events retained with tracing off);
#: ``0`` disables the ring entirely.
FLIGHT_ENV = "KEYSTONE_FLIGHT_DEPTH"

#: Default flight-ring depth: enough to hold the last few micro-batches of
#: serving lifecycle events around a fault, small enough that the retained
#: footprint (~a few hundred KB of dicts) is production-invisible.
DEFAULT_FLIGHT_DEPTH = 512

#: Hard cap on buffered events — a runaway span loop degrades to a counted
#: drop (``metrics`` counter ``trace_events_dropped``, plus a drop field in
#: both export formats), never unbounded RAM.
MAX_EVENTS = 1_000_000

#: The trace clock's source.  A module attribute so that a test can stand a
#: clock of its own in for it (``monkeypatch.setattr(trace, "_clock", ...)``).
_clock = time.perf_counter
_EPOCH = _clock()  # ts origin: microseconds since module import

# getpid() is a real syscall on every call (Python does not cache it), and
# on sandboxed kernels it measures ~10us — per EVENT that would dwarf the
# event itself.  Cached once; refreshed after fork so a forked child's
# events carry ITS pid.
_PID = os.getpid()


def _refresh_pid() -> None:
    global _PID
    _PID = os.getpid()


if hasattr(os, "register_at_fork"):  # pragma: no branch
    os.register_at_fork(after_in_child=_refresh_pid)

_lock = threading.Lock()
_events: list = []
_dropped = 0
#: Bumped by reset(): a span that outlives the buffer it was opened in
#: (e.g. an abandoned decoder thread finishing after a per-schedule
#: chaos reset) must not leak into the NEXT buffer with a stale tid.
_epoch = 0
_enabled = False
_path: str | None = None
_tids: dict[int, int] = {}  # threading.get_ident() -> small sequential tid
_tid_metas: dict[int, dict] = {}  # tid -> its thread_name metadata event
_tids_in_buffer: set = set()  # tids whose metadata reached _events
_tls = threading.local()  # per-thread span stack (nesting/parents), open stages
#: Process-unique span ids (``next`` on a count is atomic under the GIL).
_next_id = itertools.count(1).__next__
_atexit_registered = False

# -- the always-on flight recorder ring.  Deliberately separate from the
# trace buffer: it records even when tracing is disabled, it is bounded by
# construction (deque maxlen — old events fall off), and it is never
# exported unless a postmortem asks for it (core.telemetry).
_flight_lock = threading.Lock()
_flight: collections.deque | None = None


def _parse_flight_depth() -> int:
    raw = os.environ.get(FLIGHT_ENV, "").strip()
    if not raw:
        return DEFAULT_FLIGHT_DEPTH
    try:
        depth = int(raw)
    except ValueError:
        _logger.error(
            "%s=%r is not an integer — flight recorder at default depth %d",
            FLIGHT_ENV, raw, DEFAULT_FLIGHT_DEPTH,
        )
        return DEFAULT_FLIGHT_DEPTH
    return max(0, depth)


def _now_us() -> float:
    return (_clock() - _EPOCH) * 1e6


def now_us() -> float:
    """The trace clock: microseconds since this module's import — the
    ``ts`` origin every span/instant uses.  Public for the wire protocol's
    clock-offset handshake (core.wire ``T_CLOCK``): two processes exchange
    their trace clocks so ``tools/trace_view.py --stitch`` can align a
    client's timeline with the server's."""
    return _now_us()


def _tid() -> int:
    """Small sequential id for the calling thread; first sight also emits
    the Chrome ``thread_name`` metadata event so Perfetto labels lanes."""
    ident = threading.get_ident()
    tid = _tids.get(ident)
    if tid is None:
        meta = None
        with _lock:
            tid = _tids.get(ident)
            if tid is None:
                tid = len(_tids)
                _tids[ident] = tid
                meta = {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": _PID,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name},
                }
                # Cached even when tracing is off: a thread first seen in
                # flight-only mode must still get its Perfetto lane label
                # if tracing is enabled later (enable() re-emits these).
                _tid_metas[tid] = meta
                if _enabled:
                    _events.append(meta)
                    _tids_in_buffer.add(tid)
        if meta is not None and _flight is not None:
            with _flight_lock:
                if _flight is not None:
                    _flight.append(meta)
    return tid


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _record(event: dict) -> None:
    global _dropped
    with _lock:
        if len(_events) >= MAX_EVENTS:
            _dropped += 1
            overflow = True
        else:
            _events.append(event)
            overflow = False
    if overflow:
        # Counted OUTSIDE the trace lock (metrics has its own) so the
        # truncation shows up in every metrics snapshot, not just the
        # exporters' drop fields.
        metrics.inc("trace_events_dropped")


def _emit(event: dict) -> None:
    """Route one finished event: into the flight ring (always, when the
    ring is on) and into the trace buffer (only when tracing is enabled)."""
    if _flight is not None:
        with _flight_lock:
            if _flight is not None:
                _flight.append(event)
    if _enabled:
        _record(event)


class _NullSpan:
    """The disabled-mode span: a shared, allocation-free no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def sync(self, value):
        return value


_NULL = _NullSpan()

def _annotate(sp: "Span"):
    """While tracing is enabled a span is also a
    ``jax.profiler.TraceAnnotation`` named ``ks/<cat>/<name>`` that carries
    the span's ``id`` and ``root``: under ``jax.profiler.start_trace`` the
    program's spans then sit in the xplane's host plane on the profiler's
    own clock, beside the device's.  A process that has not imported jax
    (the decode workers) has no profiler to annotate for and makes none."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    note = profiler.TraceAnnotation(
        f"ks/{sp.cat}/{sp.name}", id=sp.id, root=sp._root
    )
    note.__enter__()
    return note


class Span:
    """One live span (use via ``with trace.span(...) as sp``)."""

    __slots__ = (
        "name", "cat", "attrs", "t0", "id", "_tid", "_depth", "_parent",
        "_parent_id", "_root", "_note", "_epoch",
    )

    def __init__(self, name: str, cat: str, attrs: dict):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.t0 = 0.0
        self.id = 0
        self._tid = 0
        self._depth = 0
        self._parent = None
        self._parent_id = None
        self._root = 0
        self._note = None
        self._epoch = 0

    def __enter__(self):
        stack = _stack()
        self.id = _next_id()
        self._depth = len(stack)
        if stack:
            parent = stack[-1]
            self._parent = parent.name
            self._parent_id = parent.id
            self._root = parent._root
        else:
            self._root = self.id
        stack.append(self)
        self._tid = _tid()
        self._epoch = _epoch
        if _enabled:
            self._note = _annotate(self)
        self.t0 = _now_us()
        return self

    def __exit__(self, etype, exc, tb):
        t1 = _now_us()
        if self._note is not None:
            self._note.__exit__(etype, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # exited out of order (generator close) — heal
            stack.remove(self)
        if self._epoch != _epoch:
            # The buffer this span was opened in was reset (per-schedule
            # chaos traces): a straggler from an abandoned thread must not
            # land in the NEXT trace with a stale tid.
            return False
        args = dict(self.attrs)
        args["depth"] = self._depth
        args["id"] = self.id
        args["root"] = self._root
        if self._parent is not None:
            args["parent"] = self._parent
            args["parent_id"] = self._parent_id
        if etype is not None:
            if issubclass(etype, GeneratorExit):
                # A generator-hosted span (ingest.consume) is closed — not
                # failed — when the consumer stops early or raises outside
                # the generator frame; naming GeneratorExit as the error
                # would mask the consumer's real failure, which lands on
                # whatever span wraps the consumer code.
                args["aborted"] = True
            else:
                # Typed-error spans are never silent: the failure rides in
                # the span itself, matchable against the fault counters.
                args["error"] = etype.__name__
        _emit(
            {
                "ph": "X",
                "name": self.name,
                "cat": self.cat,
                "ts": self.t0,
                "dur": max(t1 - self.t0, 0.0),
                "pid": _PID,
                "tid": self._tid,
                "args": args,
            }
        )
        return False

    def set(self, **attrs) -> "Span":
        """Attach attributes (bytes, shapes, reports) to the span."""
        self.attrs.update(attrs)
        return self

    def sync(self, value):
        """``jax.block_until_ready(value)`` and record the device-sync
        time (span start -> sync completion) as ``sync_us``.  Returns
        ``value`` so call sites stay expression-shaped."""
        import jax

        value = jax.block_until_ready(value)
        self.attrs["sync_us"] = round(_now_us() - self.t0, 1)
        return value


def span(name: str, cat: str = "span", **attrs):
    """Open a structured span.  With tracing AND the flight ring both off
    this returns a shared no-op — the hot-path cost is two module-state
    checks; with only the flight ring on, the finished span lands in the
    bounded ring and nowhere else."""
    if not _enabled and _flight is None:
        return _NULL
    return Span(name, cat, attrs)


class _IOSpan(Span):
    """A span over a byte-moving operation (snapshot shard IO, shared-memory
    IPC): records ``bytes`` up front and derives ``mb_per_s`` at exit, so
    the trace answers "was this transfer bandwidth-bound?" without
    cross-referencing durations by hand."""

    __slots__ = ()

    def __exit__(self, etype, exc, tb):
        dur_s = (_now_us() - self.t0) / 1e6
        nbytes = self.attrs.get("bytes", 0)
        if dur_s > 0 and nbytes:
            self.attrs["mb_per_s"] = round(nbytes / dur_s / 1e6, 1)
        return super().__exit__(etype, exc, tb)


def io_span(name: str, nbytes: int, cat: str = "io", **attrs):
    """Span for an IO/IPC transfer of ``nbytes`` — like :func:`span`, plus
    achieved-bandwidth accounting (``bytes`` + ``mb_per_s`` attrs)."""
    if not _enabled and _flight is None:
        return _NULL
    attrs["bytes"] = int(nbytes)
    return _IOSpan(name, cat, attrs)


# -- stages: the layer boundary of a fit ---------------------------------------
#
# A stage is a span of cat ``stage`` that also keeps sums: its own duration,
# the part nested stages cover, and what the helpers below (``wait``,
# ``d2h``, ``h2d``, ``host``) charge to it.  The sums live on the stage
# object, found through a per-thread list of open stages, so they hold with
# tracing off and with the flight ring off (``KEYSTONE_FLIGHT_DEPTH=0``),
# when the spans themselves are no-ops.  Stages and charges of a thread also
# share one list of open frames: a frame that ends adds its duration to the
# frame it lies in, so a charge knows what part of it other frames cover and
# charges its self time only.

#: The named sections of host work (:func:`host`): a closed vocabulary, so
#: that a histogram's name says what it holds (``PERF.md`` section 3 lists
#: where each is charged).
SECTIONS = frozenset({
    "dispatch",  # the call of compiled or eager programs (no sync)
    "stack",  # a chunk's images gathered, padded and flattened on the host
    "draw",  # the sampling pass's positions inside a chunk
    "concat",  # the chunks' results joined
    "search",  # the solvers' candidate enumeration and placement search
    "plan",  # a tier's admission preflight
    "place",  # operands padded, sorted and placed ahead of a tier's program
    "sort",  # the weighted solver's class sort
    "finish",  # after a solve's program: the plan's bookkeeping and log, the model cut into blocks
    "write",  # a checkpoint serialized and written
})

#: A stage name's parts seen so far in the process: a part a stage instance
#: does not charge is recorded as 0, so after its first sample a part has
#: one sample a stage instance and lines up with ``stage_ms.<name>``.
_parts_seen: dict[str, set] = {}


def _open(kind: str) -> list:
    """The calling thread's open ``stages`` or ``frames``, innermost last."""
    found = getattr(_tls, kind, None)
    if found is None:
        found = []
        setattr(_tls, kind, found)
    return found


def _close(kind: str, item) -> list:
    """Take ``item`` off its thread's open ``kind``; returns what stays open."""
    found = _open(kind)
    if found and found[-1] is item:
        found.pop()
    elif item in found:  # exited out of order — heal, as Span does
        found.remove(item)
    return found


def _close_frame(frame, dur: float) -> None:
    """Take ``frame`` off its thread's open frames and add its duration to
    the frame it lay in."""
    frames = _close("frames", frame)
    if frames:
        frames[-1].covered_us += dur


class Stage:
    """One stage of a fit (use via ``core.logging.stage_timer``).  At exit
    it records into :data:`metrics`, always, under one lock:

    * ``stage_ms.<name>`` — its *self* time: duration less the stages
      nested in it;
    * ``stage_wait_ms.<name>`` — time beneath it blocked on the device
      (:func:`wait` and :func:`d2h`);
    * ``stage_h2d_ms.<name>`` / ``stage_h2d_mb.<name>`` — time and bytes of
      the host-to-device copies beneath it (:func:`h2d`);
    * ``stage_host_ms.<name>.<section>`` / ``stage_host_n.<name>.<section>``
      — self time and occurrences of each named section of host work
      beneath it (:func:`host`), and ``stage_host_ms.<name>.other``: the
      self time nothing above covers;
    * ``stage_max_ms.<name>.<part>`` — the longest single occurrence of a
      part (a section, ``wait`` or ``h2d``): one long wait and many slow
      ones have the same sum and not the same maximum.

    A charge is its self time (what a charge or a stage nested in it covers
    is taken out), so self = wait + h2d + the sections + ``other``.  Charges
    go to the innermost open stage of their thread only, like self time.  A
    stage's name occurs once a fit, so the last *n* samples of a name are
    the last *n* fits."""

    __slots__ = ("name", "_span", "_t0", "nested_us", "covered_us", "h2d_bytes", "parts")

    def __init__(self, name: str):
        self.name = name
        self._span = _NULL
        self._t0 = 0.0
        self.nested_us = 0.0
        self.covered_us = 0.0  # a frame's slot; a stage's ``other`` needs none
        self.h2d_bytes = 0
        self.parts: dict = {}  # part -> [summed self us, occurrences, longest us]

    def __enter__(self):
        self._span = span(self.name, cat="stage")
        self._span.__enter__()
        _open("stages").append(self)
        _open("frames").append(self)
        self._t0 = _now_us()
        return self

    def __exit__(self, etype, exc, tb):
        dur = max(_now_us() - self._t0, 0.0)
        stages = _close("stages", self)
        if stages:
            stages[-1].nested_us += dur
        _close_frame(self, dur)
        self_us = dur - self.nested_us
        parts = self.parts
        seen = _parts_seen.get(self.name)
        if seen is None:
            seen = _parts_seen.setdefault(self.name, {"wait", "h2d"})
        seen.update(parts)
        values = {
            f"stage_ms.{self.name}": self_us / 1e3,
            f"stage_h2d_mb.{self.name}": self.h2d_bytes / 1e6,
        }
        host_ms = {}
        other_us = self_us
        for part in list(seen):
            total_us, n, longest_us = parts.get(part, (0.0, 0, 0.0))
            other_us -= total_us
            values[f"stage_max_ms.{self.name}.{part}"] = longest_us / 1e3
            if part in ("wait", "h2d"):
                values[f"stage_{part}_ms.{self.name}"] = total_us / 1e3
            else:
                values[f"stage_host_ms.{self.name}.{part}"] = total_us / 1e3
                values[f"stage_host_n.{self.name}.{part}"] = n
                if n:
                    host_ms[part] = round(total_us / 1e3, 3)
        values[f"stage_host_ms.{self.name}.other"] = other_us / 1e3
        self._span.set(
            stage_ms=round(self_us / 1e3, 3),
            stage_wait_ms=round(values[f"stage_wait_ms.{self.name}"], 3),
            stage_h2d_ms=round(values[f"stage_h2d_ms.{self.name}"], 3),
            stage_h2d_mb=round(self.h2d_bytes / 1e6, 3),
            host_ms=host_ms,
        )
        self._span.__exit__(etype, exc, tb)
        metrics.observe_all(values)
        return False


class _Charged:
    """A span whose self time (and bytes) is also charged to the innermost
    open stage of the thread, as ``part``: ``wait``, ``h2d`` or one of
    :data:`SECTIONS`.  The clock is read here, not taken from the span,
    which is a no-op when tracing and the flight ring are off."""

    __slots__ = ("_span", "_part", "_bytes", "_t0", "covered_us")

    def __init__(self, sp, part: str = "wait", nbytes: int = 0):
        self._span = sp
        self._part = part
        self._bytes = nbytes
        self._t0 = 0.0
        self.covered_us = 0.0  # what the frames nested in this one took

    def __enter__(self):
        _open("frames").append(self)
        self._t0 = _now_us()
        return self._span.__enter__()

    def __exit__(self, etype, exc, tb):
        self._span.__exit__(etype, exc, tb)
        dur = max(_now_us() - self._t0, 0.0)
        _close_frame(self, dur)
        stages = getattr(_tls, "stages", None)
        if stages:
            stage = stages[-1]
            own = max(dur - self.covered_us, 0.0)
            got = stage.parts.get(self._part)
            if got is None:
                stage.parts[self._part] = [own, 1, own]
            else:
                got[0] += own
                got[1] += 1
                if own > got[2]:
                    got[2] = own
            stage.h2d_bytes += self._bytes
        return False


def wait(value, name: str = "device"):
    """``jax.block_until_ready(value)`` inside a span of cat ``wait``, the
    time charged to the open stage's ``stage_wait_ms``: the host is blocked
    on the device, not working.  Wraps a sync the fit path already has (put
    it in front of a ``np.asarray`` or ``float()`` of a device value); unlike
    :meth:`Span.sync`, which the serving path uses and which only syncs
    while spans are recorded, it blocks always.  Returns ``value``."""
    import jax

    with _Charged(span(name, cat="wait")):
        return jax.block_until_ready(value)


def d2h(name: str, nbytes: int, **attrs):
    """Span (cat ``d2h``, an :func:`io_span`) around a read of ``nbytes``
    from the device to the host; the time counts as waiting."""
    return _Charged(io_span(name, nbytes, cat="d2h", **attrs))


def h2d(name: str, nbytes: int, **attrs):
    """Span (cat ``h2d``, an :func:`io_span`) around a copy of ``nbytes``
    from the host to the device; time and bytes are added to the open
    stage's ``stage_h2d_ms`` / ``stage_h2d_mb``."""
    return _Charged(io_span(name, nbytes, cat="h2d", **attrs), "h2d", int(nbytes))


def host(section: str, name: str | None = None, **attrs):
    """Span (cat ``host``, named by its ``section``, one of :data:`SECTIONS`)
    around host work that is neither a wait nor a copy; its self time goes
    to the open stage's ``stage_host_ms.<stage>.<section>``.  ``name`` says
    which site of the section this is (the span's ``site``)."""
    if section not in SECTIONS:
        raise ValueError(f"{section!r} is no host section: {sorted(SECTIONS)}")
    if name is not None:
        attrs["site"] = name
    return _Charged(span(section, cat="host", **attrs), section)


def instant(name: str, **attrs) -> None:
    """Point event (admission decision, fault count) on the current
    thread's timeline.

    No epoch guard, deliberately (unlike spans): an instant is wholly
    inside the CURRENT buffer's lifetime — a straggler thread firing one
    after a reset() records an event that really happened now, and the
    matching counter increment lands in the same window's delta, so the
    chaos verifier's counted-fault -> trace-event pairing stays
    consistent.  A span, by contrast, opened before the reset would carry
    a stale tid/interval, which is why Span.__exit__ drops it."""
    if not _enabled and _flight is None:
        return
    _emit(
        {
            "ph": "i",
            "s": "t",
            "name": name,
            "cat": "instant",
            "ts": _now_us(),
            "pid": _PID,
            "tid": _tid(),
            "args": attrs,
        }
    )


def enabled() -> bool:
    return _enabled


def enable(path: str) -> None:
    """Turn tracing on, writing to ``path`` at :func:`flush` / process
    exit.  ``*.jsonl`` selects the JSONL event log; anything else writes
    Chrome trace_event JSON (Perfetto-loadable)."""
    global _enabled, _path, _atexit_registered
    # Fail fast on an unwritable destination: flush() runs at the END of a
    # (possibly hours-long) run — discovering a missing directory there
    # would lose the whole trace.
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    if not os.access(parent, os.W_OK):
        raise PermissionError(f"trace path directory {parent!r} not writable")
    with _lock:
        _path = path
        _enabled = True
        # Threads first registered while tracing was off (flight-only
        # mode) have cached thread_name metas — emit them now so their
        # lanes are labeled in the flushed trace.
        for tid, meta in _tid_metas.items():
            if tid not in _tids_in_buffer:
                _events.append(meta)
                _tids_in_buffer.add(tid)
        if not _atexit_registered:
            atexit.register(_flush_at_exit)
            _atexit_registered = True
    _logger.info("tracing enabled -> %s", path)


def disable() -> None:
    """Stop recording (buffered events are kept until :func:`reset`)."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop every buffered event AND the flight ring (test isolation;
    per-schedule traces).  Spans still open when reset is called belong to
    the OLD buffer and are discarded at their exit (epoch check), never
    recorded into the new one."""
    global _dropped, _epoch
    with _lock:
        _events.clear()
        _tids.clear()
        _tid_metas.clear()
        _tids_in_buffer.clear()
        _dropped = 0
        _epoch += 1
    flight_reset()


def events() -> list:
    """Snapshot (copy) of the buffered events."""
    with _lock:
        return list(_events)


# -- flight recorder ----------------------------------------------------------


def flight_depth() -> int:
    """Current flight-ring capacity (0 = disabled)."""
    with _flight_lock:
        return _flight.maxlen if _flight is not None else 0


def set_flight_depth(depth: int) -> None:
    """Resize the flight ring to ``depth`` events (0 disables it).  The
    most recent events that still fit are kept."""
    global _flight
    with _flight_lock:
        if depth <= 0:
            _flight = None
            return
        kept = list(_flight)[-depth:] if _flight is not None else []
        _flight = collections.deque(kept, maxlen=int(depth))


def flight_events() -> list:
    """Snapshot (copy) of the flight ring, oldest first."""
    with _flight_lock:
        return list(_flight) if _flight is not None else []


def flight_reset() -> None:
    """Drop the flight ring's contents (capacity unchanged)."""
    with _flight_lock:
        if _flight is not None:
            _flight.clear()


def atomic_write(path: str, write) -> None:
    """Crash-safe text-file write (the ``core.checkpoint`` idiom, shared
    by the trace flush and the telemetry exporters): ``write(f)`` runs on
    a same-directory temp file which is fsynced and atomically renamed
    into place — a crash mid-write leaves the previous file intact; a
    failed write unlinks its temp.  The result gets world-readable 0644
    perms (mkstemp's private 0600 would hide exported metrics/traces from
    scraper users)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            write(f)
            f.flush()
            os.fsync(f.fileno())
        os.chmod(tmp, 0o644)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def flush(path: str | None = None) -> str | None:
    """Write the buffered events to ``path`` (default: the enabled path).
    Chrome format for ``*.json``, JSONL for ``*.jsonl``.  Returns the
    path written, or None when there is nowhere to write.  Crash-safe via
    :func:`atomic_write` — never a truncated Perfetto JSON."""
    path = path or _path
    if path is None:
        return None
    with _lock:
        evs = list(_events)
        dropped = _dropped

    def write(f) -> None:
        if path.endswith(".jsonl"):
            for ev in evs:
                f.write(json.dumps(ev) + "\n")
            if dropped:
                # Truncation must be visible in THIS format too, not
                # just the Chrome JSON's otherData field.
                f.write(
                    json.dumps(
                        {"ph": "M", "name": "dropped_events",
                         "pid": _PID, "tid": 0,
                         "args": {"count": dropped}}
                    ) + "\n"
                )
        else:
            json.dump(
                {
                    "traceEvents": evs,
                    "displayTimeUnit": "ms",
                    "otherData": {
                        "producer": "keystone_tpu.core.trace",
                        "dropped_events": dropped,
                    },
                },
                f,
            )

    atomic_write(path, write)
    return path


def _flush_at_exit() -> None:
    try:
        if _path is not None and (_events or _enabled):
            flush()
    except Exception:  # noqa: BLE001 — never break interpreter shutdown
        _logger.exception("trace flush at exit failed")


# -- metrics registry ---------------------------------------------------------


class _Hist:
    """Streaming histogram: count/sum/min/max plus a bounded sample window
    for percentiles (last :data:`_HIST_WINDOW` observations)."""

    _WINDOW = 1024
    __slots__ = ("count", "total", "min", "max", "samples")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.samples: collections.deque = collections.deque(maxlen=self._WINDOW)

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        self.samples.append(value)

    def summary(self) -> dict:
        if not self.count:
            return {"count": 0}
        s = sorted(self.samples)
        pick = lambda q: s[min(len(s) - 1, int(q * len(s)))]  # noqa: E731
        return {
            "count": self.count,
            "mean": self.total / self.count,
            "min": self.min,
            "max": self.max,
            "p50": pick(0.50),
            "p90": pick(0.90),
            "p99": pick(0.99),
        }


class Metrics:
    """Thread-safe registry of counters, gauges, and histograms.

    External counter groups with their own lock (``resilience.counters``)
    are *adopted*: they keep their API and storage, and ride along in
    every :meth:`snapshot` under their group name — one snapshot captures
    the whole process's metrics surface atomically per group.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, _Hist] = {}
        self._groups: dict[str, object] = {}

    # counters ---------------------------------------------------------------
    def inc(self, name: str, n: int = 1) -> int:
        with self._lock:
            self._counters[name] = total = self._counters.get(name, 0) + n
        return total

    def get(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    # gauges -----------------------------------------------------------------
    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def gauge_value(self, name: str, default: float | None = None) -> float | None:
        """Read one gauge back (controllers — the ingest autotuner — consume
        the same live registry the exporters snapshot)."""
        with self._lock:
            return self._gauges.get(name, default)

    # histograms -------------------------------------------------------------
    def _observe_locked(self, name: str, value: float) -> None:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = _Hist()
        h.observe(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._observe_locked(name, value)

    def observe_all(self, values: dict) -> None:
        """:meth:`observe` each ``name: value`` under one lock (a stage's
        sums land together or not at all)."""
        with self._lock:
            for name, value in values.items():
                self._observe_locked(name, value)

    def hist_windows(self) -> dict:
        """Raw per-histogram sample windows (count/total/min/max plus the
        bounded sample deque as a list) — the wire payload the fleet
        observability plane ships so FLEET percentiles come from pooled
        samples, not averaged per-host percentiles (core.fleetobs)."""
        with self._lock:
            return {
                k: {
                    "count": h.count,
                    "total": h.total,
                    "min": h.min,
                    "max": h.max,
                    "samples": list(h.samples),
                }
                for k, h in self._hists.items()
                if h.count
            }

    # groups -----------------------------------------------------------------
    def adopt(self, name: str, group) -> None:
        """Register an external counter group (must expose
        ``snapshot(reset=False) -> dict``) under ``name``."""
        with self._lock:
            self._groups[name] = group

    # snapshot ---------------------------------------------------------------
    def snapshot(self, reset: bool = False) -> dict:
        """Atomic copy of every counter/gauge/histogram (and each adopted
        group via ITS own atomic snapshot).  ``reset=True`` clears the
        registry under the same lock — read-then-reset can never lose a
        concurrent increment."""
        with self._lock:
            out = {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.summary() for k, h in self._hists.items()},
            }
            groups = dict(self._groups)
            if reset:
                self._counters.clear()
                self._gauges.clear()
                self._hists.clear()
        for name, group in groups.items():
            out[name] = group.snapshot(reset=reset)
        return out

    def reset(self) -> None:
        self.snapshot(reset=True)


#: Process-wide registry.  ``resilience.counters`` adopts itself in as the
#: "faults" group, so ``metrics.snapshot()`` captures perf metrics and the
#: fault ledger in one record (bench embeds exactly this).
metrics = Metrics()


# -- env activation -----------------------------------------------------------

# The flight recorder is ON by default (the whole point is postmortems for
# faults nobody predicted); KEYSTONE_FLIGHT_DEPTH=0 turns it off.
set_flight_depth(_parse_flight_depth())

_env_path = os.environ.get(TRACE_ENV, "").strip()
if _env_path:
    try:
        enable(_env_path)
    except OSError as e:
        # A bad env var must not make the whole package unimportable for
        # tools that never asked to trace — but the user who DID ask gets
        # told on stderr (the logger tree has no handler this early).
        import sys as _sys

        _sys.stderr.write(
            f"keystone_tpu: {TRACE_ENV}={_env_path!r} is unusable ({e}) — "
            "tracing disabled\n"
        )
        _logger.error(
            "%s=%r unusable (%s) — tracing disabled", TRACE_ENV, _env_path, e
        )
