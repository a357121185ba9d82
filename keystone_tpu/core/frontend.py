"""Shape-routed serving front-end: one warm engine per request shape,
closed-loop engine add/retire, and cross-engine HBM admission.

``core.serve`` serves ONE request shape through one ``ServingEngine`` +
``Server`` pair — the static-shape discipline XLA wants.  A production
endpoint sees a *mix* of request shapes (several image geometries, several
feature widths), and the mix drifts.  This module is the front-end tier
that turns the single-shape engines into one multi-shape service:

* **ShapeRouter** — holds one ``(ServingEngine, Server)`` pair per request
  shape (each engine is a whole batch-bucket family: per-bucket AOT
  executables, dynamic batcher, its own SLO tracker) and routes every
  request to the engine whose example shape it matches.  Each engine's
  label is per-shape (``<label>:<d0>x<d1>``), so ``KEYSTONE_SERVE_SLO_MS``'s
  ``label=ms`` syntax sets PER-SHAPE SLO targets and the telemetry
  registry's adopted ``slo`` group carries one tracker per live shape.
* **Warm add / retire from the observed mix** — the dynamic-batching
  analogue of the ingest autotuner's closed loop: requests for an unserved
  shape are counted in a rolling window and answered with a typed
  :class:`RetryLater` (explicit backpressure, never unbounded queueing);
  when a shape goes HOT (``warm_threshold`` requests inside
  ``mix_window_s``) the router warms a new engine from its
  ``engine_factory`` and serves the triggering request through it.  An
  engine that stops earning traffic (``retire_after_s`` idle) is retired:
  unrouted first, then DRAINED (every outstanding future resolves), then
  closed — an engine swap never drops a request.
* **Cross-engine admission** — every bucket of every engine is already
  admission-checked against the HBM budget by ``core.memory.plan_program``
  at compile time, but each engine plans in isolation; the router adds the
  missing cross-engine sum: a warm add is denied (counted
  ``router_admission_denied``, answered :class:`RetryLater`) when the new
  engine's peak-bucket bytes plus every live engine's would overrun the
  shared budget.  Denial is backpressure, not death — a later retire frees
  the headroom and the retry succeeds.  On a mesh-anchored router the
  budget is the anchor mesh's ``min_chip_budget`` — after a re-anchor the
  sum re-runs against the SURVIVING mesh's smallest chip, never the dead
  topology's.
* **Surviving-mesh re-anchor** (ISSUE 16) — :class:`MeshEngineFactory`
  walks the solvers' degradation ladder (full mesh → ``reduced_mesh`` →
  single device) when a tier's build fails, and
  :meth:`ShapeRouter.reanchor` hot-swaps every live engine onto a new
  (typically smaller, surviving) mesh through the same warm-add/
  drained-retire loop a mix shift uses: each replacement is built and
  registered BEFORE its predecessor is unrouted, the predecessor then
  drains (every outstanding future resolves) and closes — zero request
  loss across the reshard, counted ``mesh_reanchor`` (postmortem-linked).

Router state exports into ``trace.metrics`` (``router_engines`` gauge,
``router_routes``/``router_misses``/``router_warm_adds``/
``router_engine_retired`` counters, ``router_route_overhead_us``
histogram — the routing decision's own cost, the number the serving bench
regresses on), and every add/retire/denial lands on the trace timeline as
an instant event.

Env knobs (README ``KEYSTONE_*`` table):

* ``KEYSTONE_ROUTER_WARM_THRESHOLD`` — unserved-shape requests inside the
  mix window that trigger a warm engine add (default ``3``).
* ``KEYSTONE_ROUTER_MIX_WINDOW_S`` — rolling request-shape-mix window
  seconds (default ``5``).
* ``KEYSTONE_ROUTER_RETIRE_AFTER_S`` — idle seconds before an engine is
  retired (default ``30``).
* ``KEYSTONE_ROUTER_MAX_ENGINES`` — engine-count ceiling; at the ceiling a
  hot new shape can only warm by retiring the idlest engine (default ``8``).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from collections import deque
from typing import Callable

import numpy as np

from . import memory as kmem
from . import numerics as knum
from . import telemetry
from . import trace
from ..parallel import mesh as kmesh
from .resilience import counters
from .serve import (
    ServeConfig,
    ServeError,
    ServeFuture,
    Server,
    ServingEngine,
    ServingUnavailable,
)

_logger = logging.getLogger("keystone_tpu.frontend")

WARM_THRESHOLD_ENV = "KEYSTONE_ROUTER_WARM_THRESHOLD"
MIX_WINDOW_ENV = "KEYSTONE_ROUTER_MIX_WINDOW_S"
RETIRE_AFTER_ENV = "KEYSTONE_ROUTER_RETIRE_AFTER_S"
MAX_ENGINES_ENV = "KEYSTONE_ROUTER_MAX_ENGINES"


class NoRouteForShape(ServeError):
    """No live engine serves the request's shape and the router has no
    engine factory to warm one — a permanently unroutable request (the
    client should not retry the same shape)."""


class RetryLater(ServeError):
    """Typed backpressure: the request was NOT accepted (unserved shape
    still below the warm threshold, an engine mid-warm, or admission out
    of headroom) and the client should retry after ``retry_after_s``.
    The wire tier maps this 1:1 onto a RETRY_AFTER frame — explicit
    push-back instead of unbounded queueing."""

    def __init__(self, message: str, retry_after_s: float = 0.05):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


def _env_pos_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val < 1:
        raise ValueError(f"{name}={raw!r} must be >= 1")
    return val


def _env_pos_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not a number") from None
    if val <= 0:
        raise ValueError(f"{name}={raw!r} must be > 0")
    return val


def shape_label(label: str, shape) -> str:
    """Per-shape engine label: ``<label>:<d0>x<d1>x...`` (``scalar`` for a
    rank-0 example) — the key ``KEYSTONE_SERVE_SLO_MS``'s per-label SLO
    override syntax targets."""
    dims = "x".join(str(int(d)) for d in shape)
    return f"{label}:{dims or 'scalar'}"


@dataclasses.dataclass
class RouterConfig:
    """Knob set of one shape router (env-seeded via :meth:`from_env`)."""

    #: unserved-shape requests inside the mix window that make the shape
    #: HOT (worth the compile cost of a warm engine add).
    warm_threshold: int = 3
    #: rolling window over which the request-shape mix is observed.
    mix_window_s: float = 5.0
    #: an engine idle this long stops earning its HBM and is retired.
    retire_after_s: float = 30.0
    #: never retire below this many engines.
    min_engines: int = 1
    #: engine-count ceiling; a hot shape at the ceiling can only warm by
    #: retiring the idlest engine.
    max_engines: int = 8
    #: the retry hint carried by :class:`RetryLater` rejections.
    retry_after_s: float = 0.05
    #: opportunistic adapt cadence on the submit path (a background thread
    #: runs the retire sweep; the hot path only reads a clock).
    adapt_interval_s: float = 2.0
    #: graceful-retire drain budget: outstanding futures get this long to
    #: resolve before the server is closed anyway (typed, never hung).
    drain_timeout_s: float = 30.0

    def __post_init__(self):
        if self.warm_threshold < 1:
            raise ValueError(
                f"warm_threshold must be >= 1, got {self.warm_threshold}"
            )
        if self.mix_window_s <= 0 or self.retire_after_s < 0:
            raise ValueError(
                "mix_window_s must be > 0 and retire_after_s >= 0"
            )
        if self.min_engines < 0 or self.max_engines < 1:
            raise ValueError(
                "min_engines must be >= 0 and max_engines >= 1"
            )

    @classmethod
    def from_env(cls, **overrides) -> "RouterConfig":
        cfg = {
            "warm_threshold": _env_pos_int(WARM_THRESHOLD_ENV, 3),
            "mix_window_s": _env_pos_float(MIX_WINDOW_ENV, 5.0),
            "retire_after_s": _env_pos_float(RETIRE_AFTER_ENV, 30.0),
            "max_engines": _env_pos_int(MAX_ENGINES_ENV, 8),
        }
        cfg.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**cfg)

    def record(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RouterStats:
    """Counters of one router's lifetime (bench/chaos artifact)."""

    routes: int = 0  #: requests routed to a live engine
    misses: int = 0  #: requests whose shape had no live engine
    warm_adds: int = 0  #: engines warmed from the observed mix
    retires: int = 0  #: engines retired (drained, closed, unregistered)
    rejected: int = 0  #: RetryLater answers (backpressure, retryable)
    replaces: int = 0  #: atomic per-shape engine swaps (lifecycle refits)
    admission_denied: int = 0  #: warm adds denied by the shared HBM budget
    no_route: int = 0  #: NoRouteForShape answers (no factory — permanent)

    def record(self) -> dict:
        return dataclasses.asdict(self)


class _Entry:
    """One live shape family: engine + its batcher, plus mix accounting."""

    __slots__ = ("key", "engine", "server", "added_at", "last_routed", "routes")

    def __init__(self, key: tuple, engine: ServingEngine, server: Server, now: float):
        self.key = key
        self.engine = engine
        self.server = server
        self.added_at = now
        self.last_routed = now
        self.routes = 0


class MeshEngineFactory:
    """Mesh-aware engine factory (ISSUE 16): builds engines anchored on a
    target mesh, walking the solvers' ``_fit_mesh_ladder`` degradation
    tiers — anchor mesh → ``reduced_mesh`` (same devices, model axis
    collapsed) → single-device floor — when a tier's build raises a typed
    :class:`~.serve.ServeError` (per-chip admission denial, no surviving
    bucket).  Each step down is counted ``router_mesh_stepdown``; only
    when the single-device floor also fails does the factory raise.

    ``build(shape, dtype, mesh_or_none) -> ServingEngine`` constructs one
    engine on one tier (``None`` = meshless single-device engine).  The
    anchor moves with the substrate: :meth:`ShapeRouter.reanchor` calls
    :meth:`set_mesh` with the surviving mesh, and every later build walks
    the NEW ladder.
    """

    def __init__(self, build, mesh=None):
        self._build = build
        self._mesh_lock = threading.Lock()
        self._mesh = mesh

    @property
    def mesh(self):
        with self._mesh_lock:
            return self._mesh

    def set_mesh(self, mesh) -> None:
        """Move the anchor (the surviving mesh after device loss)."""
        with self._mesh_lock:
            self._mesh = mesh

    def _ladder(self) -> list:
        mesh = self.mesh
        tiers = []
        if mesh is not None:
            tiers.append(mesh)
            reduced = kmesh.reduced_mesh(mesh)
            if reduced is not None:
                tiers.append(reduced)
        tiers.append(None)  # single-device floor: a meshless engine
        return tiers

    @staticmethod
    def _tier_desc(tier) -> str:
        return kmesh.mesh_desc(tier) if tier is not None else "single-device"

    @staticmethod
    def _denied_bucket(engine: ServingEngine) -> int | None:
        """A live bucket that only survived as the engine's denied floor
        (``ServingEngine`` keeps the floor bucket when preflight denies it
        rather than dying) — on a mesh tier that is per-chip admission
        failure, and a lower tier should be tried instead."""
        live = set(engine.buckets())
        for bucket, plan in engine.memory_plans.items():
            if bucket in live and not plan.admitted:
                return bucket
        return None

    def __call__(self, shape, dtype) -> ServingEngine:
        key = tuple(int(d) for d in shape)
        tiers = self._ladder()
        last_err: ServeError | None = None
        for i, tier in enumerate(tiers):
            try:
                engine = self._build(key, np.dtype(dtype), tier)
                denied = (
                    self._denied_bucket(engine) if tier is not None else None
                )
                if denied is None or i + 1 >= len(tiers):
                    return engine
                counters.record(
                    "router_mesh_stepdown",
                    f"engine for shape {key} on mesh "
                    f"{self._tier_desc(tier)} only serves through its "
                    f"DENIED floor bucket {denied} (per-chip admission) — "
                    f"stepping down to {self._tier_desc(tiers[i + 1])}",
                )
            except ServeError as e:
                last_err = e
                if i + 1 < len(tiers):
                    counters.record(
                        "router_mesh_stepdown",
                        f"engine for shape {key} failed to build on mesh "
                        f"{self._tier_desc(tier)} ({e}) — stepping down to "
                        f"{self._tier_desc(tiers[i + 1])}",
                    )
        raise ServingUnavailable(
            f"engine for shape {key} failed on every mesh tier "
            f"({', '.join(self._tier_desc(t) for t in tiers)}): {last_err}"
        ) from last_err


class ShapeRouter:
    """The multi-shape serving front-end: submit any supported-shape
    request, get a :class:`~.serve.ServeFuture` from the matching engine's
    batcher.

    ``engine_factory(shape, dtype) -> ServingEngine`` (optional) warms
    engines for hot unserved shapes; without it, unserved shapes answer
    :class:`NoRouteForShape`.  Engines added up front via
    :meth:`add_engine` serve immediately.  Use as a context manager (or
    call :meth:`close`).
    """

    def __init__(
        self,
        engine_factory: Callable[[tuple, np.dtype], ServingEngine] | None = None,
        *,
        label: str = "router",
        config: RouterConfig | None = None,
        server_config: ServeConfig | None = None,
        clock=time.monotonic,
        mesh=None,
    ):
        self._factory = engine_factory
        # The router's anchor mesh: cross-engine admission budgets against
        # ITS smallest chip (not the global hbm_budget), and reanchor()
        # moves it.  A MeshEngineFactory and the router share one anchor.
        if isinstance(engine_factory, MeshEngineFactory):
            if mesh is not None:
                engine_factory.set_mesh(mesh)
            else:
                mesh = engine_factory.mesh
        self._mesh = mesh
        self._last_reanchor: dict | None = None
        self.label = label
        self.config = config or RouterConfig.from_env()
        self._server_config = server_config
        self._clock = clock
        self._lock = threading.Lock()
        self._engines: dict[tuple, _Entry] = {}
        self._misses: dict[tuple, deque] = {}
        self._warming: set = set()
        #: shape -> peak bytes of an admitted-but-not-yet-registered warm
        #: add: concurrent warms for DIFFERENT shapes must see each
        #: other's claim, or two individually-fitting engines could
        #: jointly overrun the shared budget.
        self._warm_reserved: dict[tuple, int] = {}
        self.stats = RouterStats()
        #: JSON-able ledger of cross-engine admission verdicts (bench
        #: artifact — WHY a warm add was allowed/denied, with the bytes).
        self.admissions: list[dict] = []
        self._closed = False
        self._adapting = False
        self._last_adapt = self._clock()
        # The router's live state is a /statusz section (ISSUE 15): one
        # GET on the metrics port shows the engine table, per-engine drift
        # verdicts, and the admission ledger.  Unregistered at close(),
        # identity-guarded: a newer same-label router replaces this entry,
        # and this router's close must then NOT evict the newer one.
        self._statusz_provider = self.record
        telemetry.register_statusz(f"router:{label}", self._statusz_provider)

    # -- engine lifecycle -----------------------------------------------------

    def add_engine(self, engine: ServingEngine) -> tuple:
        """Register a pre-built engine (and its batcher) for its example
        shape.  Returns the routing key (the shape tuple)."""
        key = tuple(int(d) for d in engine.example_shape)
        server = Server(engine, config=self._server_config)
        now = self._clock()
        with self._lock:
            if self._closed:
                server.close()
                server.join()
                raise ServingUnavailable("router is closed")
            if key in self._engines:
                server.close()
                server.join()
                raise ValueError(f"shape {key} already has a live engine")
            self._engines[key] = _Entry(key, engine, server, now)
            n = len(self._engines)
        trace.metrics.gauge("router_engines", n)
        trace.instant(
            "router_engine_added", shape=list(key), label=engine.label,
            engines=n,
        )
        _logger.info(
            "router %s: engine %s live for shape %s (%d engine(s))",
            self.label, engine.label, key, n,
        )
        return key

    def replace_engine(self, engine: ServingEngine, *, why: str = "engine swap") -> tuple:
        """ATOMICALLY swap the engine serving ``engine.example_shape``:
        the replacement registers under ONE routing-table update
        (add-then-retire), so a request arriving at any instant routes to
        the incumbent or the successor — a retire-then-add sequence would
        open a window where a continuously-servable shape answers a
        transient ``RetryLater``.  The incumbent (when present) drains
        AFTER it is unrouted (:meth:`_retire_entry`: every in-flight
        future resolves, zero request loss); with no incumbent this
        degrades to :meth:`add_engine`.  Mix accounting (``routes``,
        ``last_routed``) carries over so the idle-retire clock does not
        restart on a swap.  Returns the routing key."""
        key = tuple(int(d) for d in engine.example_shape)
        with self._lock:
            old = self._engines.get(key)
            # SLO trackers and drift monitors unregister BY LABEL: a
            # same-label successor would be unregistered by the
            # incumbent's retirement.  Rename BEFORE the Server below
            # registers the SLO tracker.
            if old is not None and engine.label == old.engine.label:
                engine.label = f"{old.engine.label}@swap"
        server = Server(engine, config=self._server_config)
        now = self._clock()
        with self._lock:
            if self._closed:
                server.close()
                server.join()
                raise ServingUnavailable("router is closed")
            old = self._engines.get(key)
            entry = _Entry(key, engine, server, now)
            if old is not None:
                entry.routes = old.routes
                entry.last_routed = old.last_routed
                self.stats.replaces += 1
            self._engines[key] = entry
            n = len(self._engines)
        trace.metrics.gauge("router_engines", n)
        trace.instant(
            "router_engine_added", shape=list(key), label=engine.label,
            engines=n, replaced=old.engine.label if old is not None else None,
        )
        if old is not None:
            self._retire_entry(old, why=why)
        _logger.info(
            "router %s: engine %s %s for shape %s (%s)",
            self.label, engine.label,
            "replaced " + old.engine.label if old is not None else "live",
            key, why,
        )
        return key

    def engines(self) -> dict:
        """shape -> engine label of every live engine (routing table
        snapshot)."""
        with self._lock:
            return {k: e.engine.label for k, e in self._engines.items()}

    def server_for(self, shape) -> Server:
        """The live :class:`~.serve.Server` batching ``shape``'s requests
        (stats/SLO introspection; raises :class:`NoRouteForShape` when the
        shape has no engine)."""
        key = tuple(int(d) for d in shape)
        with self._lock:
            entry = self._engines.get(key)
        if entry is None:
            raise NoRouteForShape(
                f"router {self.label}: no engine serves shape {key}"
            )
        return entry.server

    # -- the request path -----------------------------------------------------

    def submit(self, x) -> ServeFuture:
        """Route one request to the engine serving its shape.  Raises the
        shape family's typed errors: ``MalformedRequest`` (bad payload),
        :class:`RetryLater` (backpressure: shape not warm yet / admission
        out of headroom), :class:`NoRouteForShape` (no factory)."""
        t0 = time.perf_counter()
        arr = np.asarray(x)
        key = tuple(int(d) for d in arr.shape)
        now = self._clock()
        with self._lock:
            if self._closed:
                raise ServingUnavailable("router is closed")
            entry = self._engines.get(key)
            if entry is not None:
                entry.last_routed = now
                entry.routes += 1
                self.stats.routes += 1
        if entry is not None:
            # The router's OWN cost on the hot path: table lookup + mix
            # bookkeeping, measured before the engine's batcher takes over.
            trace.metrics.observe(
                "router_route_overhead_us", (time.perf_counter() - t0) * 1e6
            )
            trace.metrics.inc("router_routes")
            try:
                fut = entry.server.submit(arr)
            except ServingUnavailable:
                # Retired under our feet (the entry was grabbed just before
                # the sweep unrouted it): degrade to the miss path — typed
                # backpressure or a fresh warm, never a dead-engine error
                # for a shape the router still claims to serve.
                return self._miss(arr, key, self._clock())
            self._maybe_adapt(now)
            return fut
        fut = self._miss(arr, key, now)
        self._maybe_adapt(now)
        return fut

    def predict(self, x, timeout: float = 30.0):
        """Blocking convenience: ``submit`` + ``result``, absorbing
        :class:`RetryLater` backpressure by honoring the retry hint until
        ``timeout`` — what a well-behaved wire client does."""
        end = time.monotonic() + timeout
        while True:
            try:
                return self.submit(x).result(max(0.0, end - time.monotonic()))
            except RetryLater as e:
                if time.monotonic() + e.retry_after_s >= end:
                    raise
                time.sleep(e.retry_after_s)

    def _miss(self, arr: np.ndarray, key: tuple, now: float):
        warm_me = False
        with self._lock:
            if self._closed:
                raise ServingUnavailable("router is closed")
            entry = self._engines.get(key)
            if entry is not None:  # lost a warm race — the engine is there
                entry.last_routed = now
                entry.routes += 1
                self.stats.routes += 1
            else:
                self.stats.misses += 1
                trace.metrics.inc("router_misses")
                if self._factory is None:
                    self.stats.no_route += 1
                    raise NoRouteForShape(
                        f"router {self.label}: no engine serves shape {key} "
                        "and no engine factory is configured"
                    )
                dq = self._misses.setdefault(key, deque())
                dq.append(now)
                cutoff = now - self.config.mix_window_s
                while dq and dq[0] < cutoff:
                    dq.popleft()
                hot = len(dq) >= self.config.warm_threshold
                if hot and key not in self._warming:
                    self._warming.add(key)
                    warm_me = True
                elif not hot:
                    self.stats.rejected += 1
                    trace.metrics.inc("router_retry_later")
                    raise RetryLater(
                        f"router {self.label}: shape {key} has no warm "
                        f"engine yet ({len(dq)}/{self.config.warm_threshold} "
                        "recent requests) — retry",
                        self.config.retry_after_s,
                    )
                else:  # another thread is mid-warm for this shape
                    self.stats.rejected += 1
                    trace.metrics.inc("router_retry_later")
                    raise RetryLater(
                        f"router {self.label}: an engine for shape {key} "
                        "is warming — retry",
                        self.config.retry_after_s,
                    )
        if entry is not None:
            return entry.server.submit(arr)
        try:
            return self._warm_and_submit(arr, key, now)
        finally:
            with self._lock:
                self._warming.discard(key)
                self._warm_reserved.pop(key, None)

    # -- warm add (the closed loop's grow side) -------------------------------

    def _warm_and_submit(self, arr: np.ndarray, key: tuple, now: float):
        # At the engine ceiling the only way to warm is to free a slot:
        # retire the idlest engine IF it has stopped earning traffic —
        # the shape mix genuinely shifted, so the slot follows it.
        evict = None
        with self._lock:
            if len(self._engines) >= self.config.max_engines:
                idlest = min(
                    self._engines.values(), key=lambda e: e.last_routed
                )
                if (
                    now - idlest.last_routed >= self.config.mix_window_s
                    and len(self._engines) > self.config.min_engines
                ):
                    evict = self._engines.pop(idlest.key)
                else:
                    self.stats.rejected += 1
                    trace.metrics.inc("router_retry_later")
                    raise RetryLater(
                        f"router {self.label}: at the engine ceiling "
                        f"({self.config.max_engines}) with every engine "
                        "still earning traffic — retry",
                        self.config.retry_after_s,
                    )
        if evict is not None:
            self._retire_entry(evict, why="evicted for a hotter shape")
        with trace.span(
            "router.warm", cat="serve", shape=list(key), label=self.label
        ):
            engine = self._factory(key, arr.dtype)
        admitted, verdict = self._cross_admission(key, engine)
        with self._lock:
            self.admissions.append(verdict)
            del self.admissions[:-16]  # bounded ledger
        if not admitted:
            with self._lock:
                self.stats.admission_denied += 1
                self.stats.rejected += 1
            counters.record(
                "router_admission_denied",
                f"router {self.label}: warm add for shape {key} denied — "
                f"{verdict['reason']}",
            )
            raise RetryLater(
                f"router {self.label}: no HBM headroom to warm an engine "
                f"for shape {key} ({verdict['reason']}) — retry",
                self.config.retry_after_s,
            )
        self.add_engine(engine)
        with self._lock:
            self.stats.warm_adds += 1
            self._misses.pop(key, None)
            entry = self._engines.get(key)
            if entry is not None:
                entry.last_routed = self._clock()
                entry.routes += 1
                self.stats.routes += 1
        trace.metrics.inc("router_warm_adds")
        trace.instant(
            "router_engine_warmed", shape=list(key), label=engine.label
        )
        if entry is None:  # pragma: no cover — add_engine just inserted it
            raise ServingUnavailable("router closed during warm add")
        return entry.server.submit(arr)

    def _engine_peak_bytes(self, engine: ServingEngine) -> int:
        """The engine's steady-state HBM claim: the largest LIVE bucket's
        planned total (argument+temp+output−alias), from the very
        ``plan_program`` preflight that admitted it.  Unanalyzed plans (no
        budget known at build) fall back to an analytic floor: padded
        batch in + out bytes of the largest bucket."""
        peak = 0
        live = set(engine.buckets())
        for bucket, plan in engine.memory_plans.items():
            if bucket not in live:
                continue
            if plan.analyzed and plan.total_bytes:
                peak = max(peak, int(plan.total_bytes))
            else:
                row = int(
                    np.prod(engine.example_shape, dtype=np.int64)
                    * engine.example_dtype.itemsize
                ) if engine.example_shape else engine.example_dtype.itemsize
                peak = max(peak, 2 * bucket * row)
        return peak

    def _cross_admission(
        self, key: tuple, new_engine: ServingEngine
    ) -> tuple[bool, dict]:
        """The missing cross-engine sum over the per-engine preflights:
        live engines' peak-bucket bytes, OTHER in-flight warm adds'
        reserved bytes, and the candidate's must together fit the shared
        HBM budget (``core.memory.hbm_budget``; unknown budget admits with
        the reason recorded, exactly like ``plan_program``).  An admitted
        candidate RESERVES its bytes under the same lock acquisition, so
        two concurrent warms for different shapes cannot both pass against
        the same headroom; the reservation clears once the engine is in
        the routing table (the ``_miss`` finally).

        A mesh-anchored router budgets against the CURRENT anchor mesh's
        smallest chip (``min_chip_budget``): after a re-anchor the sum
        re-runs against the surviving topology — a budget computed on the
        dead mesh would over-admit (ISSUE 16)."""
        mesh = self._mesh
        if mesh is not None:
            budget, _ = kmem.min_chip_budget(mesh)
        else:
            budget = kmem.hbm_budget()
        candidate = self._engine_peak_bytes(new_engine)
        with self._lock:
            resident = sum(
                self._engine_peak_bytes(e.engine)
                for e in self._engines.values()
            )
            reserved = sum(
                v for k, v in self._warm_reserved.items() if k != key
            )
            verdict = {
                "label": new_engine.label,
                "resident_bytes": int(resident),
                "reserved_bytes": int(reserved),
                "candidate_bytes": int(candidate),
                "budget_bytes": int(budget) if budget is not None else None,
            }
            if budget is None:
                verdict.update(
                    admitted=True,
                    reason=(
                        "no HBM budget known — cross-engine admission "
                        "skipped"
                    ),
                )
                return True, verdict
            admitted = resident + reserved + candidate <= budget
            if admitted:
                self._warm_reserved[key] = candidate
            verdict.update(
                admitted=admitted,
                reason=(
                    f"{resident + reserved + candidate} bytes across "
                    f"engines vs budget {budget}"
                ),
            )
        trace.instant(
            "router_admission",
            admitted=admitted,
            resident_bytes=int(resident),
            reserved_bytes=int(reserved),
            candidate_bytes=int(candidate),
            budget_bytes=int(budget),
        )
        return admitted, verdict

    # -- retire (the closed loop's shrink side) -------------------------------

    def _maybe_adapt(self, now: float) -> None:
        if now - self._last_adapt < self.config.adapt_interval_s:
            return
        with self._lock:
            if self._adapting or self._closed:
                return
            if now - self._last_adapt < self.config.adapt_interval_s:
                return
            self._adapting = True
            self._last_adapt = now
        threading.Thread(
            target=self._adapt_bg, name="keystone-router-adapt", daemon=True
        ).start()

    def _adapt_bg(self) -> None:
        try:
            self.adapt()
        except Exception:  # noqa: BLE001 — the sweep must not die silently
            _logger.exception("router adapt sweep failed")
        finally:
            self._adapting = False

    def adapt(self) -> dict:
        """One retire sweep: unroute every engine idle past
        ``retire_after_s`` (down to ``min_engines``), drain it, close it,
        unregister its SLO tracker.  Returns the actions taken (tests
        call this directly; the submit path runs it on a
        background thread every ``adapt_interval_s``)."""
        now = self._clock()
        retired: list[_Entry] = []
        with self._lock:
            if self._closed:
                return {"retired": []}
            idle_first = sorted(
                self._engines.values(), key=lambda e: e.last_routed
            )
            for entry in idle_first:
                if len(self._engines) <= self.config.min_engines:
                    break
                if now - entry.last_routed >= self.config.retire_after_s:
                    del self._engines[entry.key]
                    retired.append(entry)
        for entry in retired:
            self._retire_entry(entry, why="stopped earning traffic")
        return {"retired": [list(e.key) for e in retired]}

    # -- surviving-mesh re-anchor (ISSUE 16) ----------------------------------

    def reanchor(self, mesh, *, why: str = "device loss") -> dict:
        """Hot-swap every live engine onto ``mesh`` — the surviving-mesh
        re-anchor after device loss or per-chip admission denial.

        Zero request loss, the PR-12 swap invariant: each replacement
        engine is built and REGISTERED before its predecessor is unrouted,
        so requests route to one or the other at every instant; the
        predecessor then drains (every outstanding future resolves) and
        closes through the same :meth:`_retire_entry` path a mix-driven
        retire uses.  A shape whose rebuild fails on every tier keeps its
        OLD engine serving (degraded, not dead) and lands in the record's
        ``failed`` list.  The whole event is counted ``mesh_reanchor``
        (trace fault instant + flight-recorder postmortem) and the record
        is surfaced as ``last_reanchor`` in :meth:`record`.
        """
        t0 = time.perf_counter()
        if self._factory is None:
            raise ServingUnavailable(
                f"router {self.label}: cannot re-anchor without an engine "
                "factory"
            )
        if isinstance(self._factory, MeshEngineFactory):
            self._factory.set_mesh(mesh)
        with self._lock:
            if self._closed:
                raise ServingUnavailable("router is closed")
            self._mesh = mesh
            old_entries = list(self._engines.values())
        desc = kmesh.mesh_desc(mesh) if mesh is not None else "single-device"
        swapped: list[dict] = []
        failed: list[dict] = []
        for old in old_entries:
            try:
                with trace.span(
                    "router.reanchor", cat="serve", shape=list(old.key),
                    label=self.label, mesh=desc,
                ):
                    engine = self._factory(old.key, old.engine.example_dtype)
            except ServeError as e:
                failed.append({
                    "shape": list(old.key),
                    "error": f"{type(e).__name__}: {e}",
                })
                _logger.warning(
                    "router %s: re-anchor of shape %s onto mesh %s failed "
                    "(%s) — old engine keeps serving",
                    self.label, old.key, desc, e,
                )
                continue
            if engine.label == old.engine.label:
                # SLO trackers and drift monitors unregister BY LABEL when
                # the predecessor retires — the replacement must not share
                # its name or it gets unregistered with the corpse.
                engine.label = f"{old.engine.label}@{desc}"
            server = Server(engine, config=self._server_config)
            now = self._clock()
            with self._lock:
                stale = self._closed or self._engines.get(old.key) is not old
                if not stale:
                    entry = _Entry(old.key, engine, server, now)
                    entry.routes = old.routes
                    entry.last_routed = old.last_routed
                    self._engines[old.key] = entry
            if stale:
                # Retired/replaced mid-build (or the router closed) — do
                # not resurrect the shape; discard the fresh server.
                server.close()
                server.join()
                telemetry.unregister_slo(engine.label)
                knum.unregister_drift(engine.label)
                continue
            trace.instant(
                "router_engine_added", shape=list(old.key),
                label=engine.label, mesh=desc,
            )
            self._retire_entry(
                old, why=f"re-anchored onto mesh {desc} ({why})"
            )
            swapped.append({"shape": list(old.key), "label": engine.label})
        wall = time.perf_counter() - t0
        rec = {
            "mesh": desc,
            "why": why,
            "swapped": swapped,
            "failed": failed,
            "reshard_wall_s": round(wall, 6),
        }
        with self._lock:
            self._last_reanchor = rec
        counters.record(
            "mesh_reanchor",
            f"router {self.label}: {len(swapped)} engine(s) re-anchored "
            f"onto mesh {desc} in {wall:.3f}s ({why}; "
            f"{len(failed)} failed)",
        )
        trace.instant(
            "router_reanchor", mesh=desc, swapped=len(swapped),
            failed=len(failed), wall_s=round(wall, 6), why=why,
        )
        _logger.info(
            "router %s: re-anchored %d engine(s) onto mesh %s in %.3fs "
            "(%s; %d failed)",
            self.label, len(swapped), desc, wall, why, len(failed),
        )
        return rec

    def _retire_entry(self, entry: _Entry, why: str) -> None:
        """Graceful engine retirement: the entry is ALREADY unrouted (new
        requests for its shape go down the miss path), so draining resolves
        every outstanding future before the server closes — zero request
        loss across the swap."""
        drained = entry.server.drain(self.config.drain_timeout_s)
        if not drained:
            _logger.warning(
                "router %s: engine %s did not drain in %.1fs — closing "
                "anyway (stragglers answer ServingUnavailable, typed)",
                self.label, entry.engine.label, self.config.drain_timeout_s,
            )
        entry.server.close()
        entry.server.join()
        telemetry.unregister_slo(entry.engine.label)
        # A retired engine's drift monitor must leave the live numerics
        # surface with it (its history belongs to the records that
        # captured it, not to every future /statusz snapshot).
        knum.unregister_drift(entry.engine.label)
        with self._lock:
            self.stats.retires += 1
            n = len(self._engines)
        trace.metrics.inc("router_engine_retired")
        trace.metrics.gauge("router_engines", n)
        trace.instant(
            "router_engine_retired", shape=list(entry.key),
            label=entry.engine.label, why=why, drained=drained,
            routes=entry.routes, engines=n,
        )
        _logger.info(
            "router %s: retired engine %s (%s; %d requests routed, "
            "drained=%s)",
            self.label, entry.engine.label, why, entry.routes, drained,
        )

    # -- lifecycle / records --------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Drain every live engine (all outstanding futures resolve)."""
        end = time.monotonic() + timeout
        with self._lock:
            entries = list(self._engines.values())
        ok = True
        for entry in entries:
            ok &= entry.server.drain(max(0.0, end - time.monotonic()))
        return ok

    def close(self) -> None:
        """Close every engine's server (pending requests answer
        ``ServingUnavailable``) and stop routing.  Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            entries = list(self._engines.values())
            self._engines.clear()
        for entry in entries:
            entry.server.close()
            entry.server.join()
            telemetry.unregister_slo(entry.engine.label)
            knum.unregister_drift(entry.engine.label)
        telemetry.unregister_statusz(
            f"router:{self.label}", self._statusz_provider
        )
        trace.metrics.gauge("router_engines", 0)

    def __enter__(self) -> "ShapeRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def record(self) -> dict:
        """JSON-able router summary for bench/serving records: the live
        routing table, lifetime stats (routes/misses/warm_adds/retires),
        and the admission ledger."""
        now = self._clock()
        with self._lock:
            engines = {
                "x".join(map(str, k)) or "scalar": {
                    "label": e.engine.label,
                    "live_buckets": list(e.engine.buckets()),
                    "routes": e.routes,
                    "idle_seconds": round(now - e.last_routed, 3),
                    # Output-drift verdict (ISSUE 15): the engine's live
                    # divergence vs its fit-time baseline, None when no
                    # baseline was armed.
                    "drift": (
                        e.engine.drift.record()
                        if e.engine.drift is not None
                        else None
                    ),
                }
                for k, e in self._engines.items()
            }
            stats = self.stats.record()
            admissions = list(self.admissions)
            last_reanchor = self._last_reanchor
            mesh = self._mesh
        out = {
            "label": self.label,
            "mesh": kmesh.mesh_desc(mesh) if mesh is not None else None,
            "config": self.config.record(),
            "engines": engines,
            "stats": stats,
            "admissions": admissions,
            "last_reanchor": last_reanchor,
        }
        from . import profiler as kprof

        if kprof.enabled():
            # Device cost attribution (ISSUE 14): with the profiler on,
            # the router record carries the per-program MFU ledger — the
            # per-shape serve buckets' roofline positions land in every
            # serving artifact that embeds the router.
            out["profiler"] = kprof.ledger_record()
        return out


# -- multi-host fleet front-end (ISSUE 17) ------------------------------------


class HostFleet:
    """The wire front-end over N HOST-LOCAL routers: one
    :class:`~.wire.WireClient` per fleet member, requests spread
    round-robin, and a member whose socket dies is declared lost (counted
    ``fleet_host_lost``, postmortem-linked) with the request REISSUED to a
    survivor — a host loss costs the fleet capacity, never an answer.

    This is the serving half of the multi-host story: engines never span
    hosts (``ServingEngine`` refuses a process-spanning mesh), so scale-out
    is N independent ``ShapeRouter`` + ``WireServer`` pairs — one per host,
    each anchored on its :func:`~..parallel.mesh.host_local_mesh` — fronted
    by this class.  Predictions are pure, so reissuing an in-flight request
    to a survivor is exact, not at-least-once-with-drift; a request only
    fails when NO host is left (typed :class:`ServingUnavailable`).

    Thread-safe: each member's client socket is guarded by its own lock, so
    concurrent callers fan out across members instead of serializing."""

    def __init__(self, endpoints, *, label: str = "fleet", timeout: float = 30.0):
        if not endpoints:
            raise ValueError("HostFleet needs at least one endpoint")
        self.label = label
        self.timeout = float(timeout)
        self._hosts = []
        for ep in endpoints:
            if isinstance(ep, str):
                host, _, port = ep.rpartition(":")
                ep = (host or "127.0.0.1", int(port))
            self._hosts.append(
                {
                    "endpoint": (str(ep[0]), int(ep[1])),
                    "client": None,
                    "lock": threading.Lock(),
                    "alive": True,
                    "requests": 0,
                    "reissued": 0,
                }
            )
        self._rr = 0
        self._rr_lock = threading.Lock()
        self.lost_hosts = 0
        self._collector = None
        trace.instant(
            "fleet.up",
            label=label,
            hosts=[list(h["endpoint"]) for h in self._hosts],
        )

    def _client(self, h):
        from . import wire

        if h["client"] is None:
            h["client"] = wire.WireClient(
                h["endpoint"][0], h["endpoint"][1], timeout=self.timeout
            )
        return h["client"]

    def _mark_lost(self, h, why: str) -> None:
        if not h["alive"]:
            return
        h["alive"] = False
        self.lost_hosts += 1
        try:
            if h["client"] is not None:
                h["client"].close()
        finally:
            h["client"] = None
        counters.record(
            "fleet_host_lost", f"{self.label}: {h['endpoint']}: {why}"
        )

    def alive_hosts(self) -> list:
        return [h["endpoint"] for h in self._hosts if h["alive"]]

    def attach_collector(self, collector) -> None:
        """Self-register the whole fleet with a
        :class:`~.fleetobs.FleetCollector`: every current member becomes
        an observed obs agent (the serving socket doubles as the obs
        endpoint), and members re-admitted later via :meth:`reattach`
        register too."""
        self._collector = collector
        for rank, h in enumerate(self._hosts):
            collector.register(h["endpoint"], rank=rank)

    def predict(self, arr, timeout: float | None = None):
        """Answer one request through some live host.  A member that dies
        mid-request (reset, closed socket, silence past the deadline) is
        declared lost and the SAME request is reissued to the next member;
        typed remote errors (the server answering "no") propagate — they
        are answers, not host deaths."""
        from . import wire

        budget = timeout if timeout is not None else self.timeout
        tried = 0
        n = len(self._hosts)
        while True:
            live = [h for h in self._hosts if h["alive"]]
            if not live:
                raise ServingUnavailable(
                    f"fleet {self.label!r}: all {n} host(s) lost"
                )
            with self._rr_lock:
                h = live[self._rr % len(live)]
                self._rr += 1
            try:
                with h["lock"]:
                    client = self._client(h)
                    h["requests"] += 1
                    return client.predict(arr, timeout=budget)
            except wire.WireRemoteError:
                raise  # a typed answer from a live host
            except (OSError, TimeoutError, wire.WireProtocolError) as e:
                self._mark_lost(h, f"{type(e).__name__}: {e}")
                tried += 1
                if tried > n:  # pragma: no cover - every host died
                    raise ServingUnavailable(
                        f"fleet {self.label!r}: no host answered: {e}"
                    ) from e
                h["reissued"] += 1  # this member's loss forced a reissue

    def reattach(self, endpoint) -> None:
        """Re-admit a (restarted) member at ``endpoint`` — the scale-back-up
        half of elasticity.  New endpoint, new member; known endpoint,
        revived in place."""
        if isinstance(endpoint, str):
            host, _, port = endpoint.rpartition(":")
            endpoint = (host or "127.0.0.1", int(port))
        endpoint = (str(endpoint[0]), int(endpoint[1]))
        for h in self._hosts:
            if h["endpoint"] == endpoint:
                h["alive"] = True
                h["client"] = None
                trace.instant("fleet.reattach", endpoint=list(endpoint))
                if self._collector is not None:
                    self._collector.register(endpoint)
                return
        self._hosts.append(
            {
                "endpoint": endpoint,
                "client": None,
                "lock": threading.Lock(),
                "alive": True,
                "requests": 0,
                "reissued": 0,
            }
        )
        trace.instant("fleet.reattach", endpoint=list(endpoint))
        if self._collector is not None:
            self._collector.register(endpoint, rank=len(self._hosts) - 1)

    def record(self) -> dict:
        return {
            "label": self.label,
            "hosts": [
                {
                    "endpoint": list(h["endpoint"]),
                    "alive": h["alive"],
                    "requests": h["requests"],
                    "reissued": h["reissued"],
                }
                for h in self._hosts
            ],
            "lost_hosts": self.lost_hosts,
        }

    def close(self) -> None:
        for h in self._hosts:
            with h["lock"]:
                if h["client"] is not None:
                    try:
                        h["client"].close()
                    except OSError:  # pragma: no cover
                        pass
                    h["client"] = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
