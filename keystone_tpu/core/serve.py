"""Low-latency serving: fused AOT inference, dynamic request batching, and
online SLO observability.

Every other subsystem in this repo optimizes the throughput of *fit*; this
module serves a *fitted* pipeline under request traffic — the
"millions of users" half of the ROADMAP north star.  KeystoneML pipelines
were deploy-once/apply-many artifacts (fit on the cluster, apply forever);
the TensorFlow paper (PAPERS.md: 1605.08695) shows what the apply-forever
half needs to be fast: ONE compiled program, parameters warm-loaded once,
requests batched.  tf.data (PAPERS.md: 2101.12127) supplies the
deadline-aware pipelined feeding idiom the batcher mirrors.

Three pieces:

* **Fused AOT inference** (:class:`ServingEngine`) — the whole fitted
  apply-chain compiles into one donated-input AOT executable per **batch
  bucket** via the existing ``core.memory.plan_program`` preflight, so
  every bucket is admission-checked against the HBM budget before it can
  ever OOM a live endpoint, and its ``memory_analysis`` breakdown is
  recorded (``engine.memory_plans``).  Fitted state warm-loads from a
  ``core.checkpoint`` artifact (:func:`load_engine` measures the
  fresh-process cold start: restore seconds, per-bucket compile seconds,
  first-inference warmup).  Buckets are BATCH-size buckets over one fixed
  request shape — the static-shape discipline XLA wants; a workload with
  several request shapes runs one engine per shape, exactly like the
  ingest stream's shape buckets.
* **Dynamic request batcher** (:class:`Server`) — a thread-safe request
  queue feeding bucket-sized micro-batches with deadline-aware flush:
  a batch goes out when it reaches the largest bucket OR when the OLDEST
  pending request has waited ``max_wait_ms``, whichever first.  Remainder
  batches pad up to the nearest bucket (pad rows are sliced off before
  answering — row-wise programs never mix rows, so padding changes
  latency, not results).  H2D is double-buffered with the ``core.ingest``
  two-in-flight idiom: the assembler thread dispatches ``device_put`` for
  micro-batch *i+1* while the executor thread runs batch *i*, and only
  the executor ever blocks on device work.  Each request is answered with
  its own output slice, in arrival order.
* **Observability + typed failure** — every request gets an id at
  ``submit`` that rides through its whole lifecycle (a ``serve.submit``
  instant, request-id ranges on the per-micro-batch ``serve.h2d`` /
  ``serve.execute`` / ``serve.d2h`` spans, and a per-request
  ``serve.request`` span), and a per-phase latency decomposition —
  queue-wait / H2D / device-wait / execute / D2H / answer / pad overhead
  — lands on ``ServeFuture.phases`` and aggregates in ``serve_bench``'s
  ``phase_breakdown``.  Each ``Server`` registers a live SLO tracker
  (``core.telemetry``: rolling p50/p99/QPS + error-budget burn rate
  against ``KEYSTONE_SERVE_SLO_MS``), batcher state exports into the
  ``trace.metrics`` registry (flush-reason counters, bucket retirements,
  occupancy), and the typed-or-equal invariant extends online: a
  malformed request dies at ``submit`` with a counted
  :class:`MalformedRequest` and NEVER enters a batch (no poisoned
  batchmates); a burst OOM degrades to a smaller bucket (counted
  ``serve_burst_oom``) and re-answers the same requests — never a silent
  wrong answer; a dead endpoint answers :class:`ServingUnavailable`, not
  a bare traceback.

Env knobs (documented in README's ``KEYSTONE_*`` table):

* ``KEYSTONE_SERVE_BUCKETS`` — comma-separated batch buckets (default
  ``1,4,16,64``).
* ``KEYSTONE_SERVE_MAX_BATCH`` — cap/extend the largest bucket.
* ``KEYSTONE_SERVE_MAX_WAIT_MS`` — deadline-aware flush budget (default
  ``5``).
* ``KEYSTONE_SERVE_EAGER_FLUSH`` — ``0`` disables the opportunistic idle
  flush (a micro-batch dispatches as soon as the device pipeline is idle,
  without waiting out ``max_wait_ms``; the TF-Serving batch-scheduler
  discipline — the deadline only governs waiting while the device is busy).

Bucket parity: XLA may emit a DIFFERENT reduction order for the same
row-wise program at different batch sizes (measured here: the batch-1
matmul takes a gemv path whose rounding differs from the gemm the larger
buckets and the eager oracle share).  A bucket whose rows are not
bit-identical to the offline apply would silently break the "served answer
== pipeline(x)" contract, so :meth:`ServingEngine.warmup` doubles as a
PARITY CHECK: every bucket executes a deterministic probe batch and any
bucket whose rows differ from the eager oracle is dropped (counted
``serve_bucket_parity_dropped``) — unless NO bucket passes, in which case
the engine serves but says so (``parity_ok=False``, counted once) rather
than refusing service.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from . import memory as kmem
from . import numerics as knum
from . import profiler as kprof
from . import telemetry
from . import trace
from .resilience import counters

_logger = logging.getLogger("keystone_tpu.serve")

BUCKETS_ENV = "KEYSTONE_SERVE_BUCKETS"
MAX_BATCH_ENV = "KEYSTONE_SERVE_MAX_BATCH"
MAX_WAIT_ENV = "KEYSTONE_SERVE_MAX_WAIT_MS"
EAGER_FLUSH_ENV = "KEYSTONE_SERVE_EAGER_FLUSH"

DEFAULT_BUCKETS = (1, 4, 16, 64)
DEFAULT_MAX_WAIT_MS = 5.0

#: Micro-batches in flight between the assembler and the executor — the
#: consumed batch plus the one whose H2D overlaps it (the core.ingest
#: DEVICE_BUFFERS idiom, applied to the request path).
INFLIGHT_BATCHES = 2

#: Every blocking wait polls at this period so stop flags and the
#: resilience.deadline SIGALRM are always observed (same discipline as the
#: ingest ring).
_POLL_SECONDS = 0.05


class ServeError(RuntimeError):
    """Base of the serving subsystem's typed failures."""


class MalformedRequest(ServeError, ValueError):
    """A request that cannot enter a batch: wrong shape, uncastable dtype,
    or non-finite payload.  Raised at ``submit`` time — the request is
    REJECTED (counted ``serve_malformed_request``) before it can poison
    the micro-batch its batchmates ride in."""


class ServingUnavailable(ServeError):
    """The endpoint cannot answer: every batch bucket OOMed away, or the
    server was closed with requests still pending.  Typed — a dead
    endpoint is an operable condition, never a bare traceback."""


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not a number") from None
    if val < 0:
        raise ValueError(f"{name}={raw!r} must be >= 0")
    return val


def _parse_buckets(raw: str) -> tuple[int, ...]:
    try:
        vals = tuple(sorted({int(tok) for tok in raw.split(",") if tok.strip()}))
    except ValueError:
        raise ValueError(
            f"{BUCKETS_ENV}={raw!r}: expected comma-separated integers"
        ) from None
    if not vals or any(v < 1 for v in vals):
        raise ValueError(f"{BUCKETS_ENV}={raw!r}: buckets must be >= 1")
    return vals


@dataclasses.dataclass
class ServeConfig:
    """Knob set of one serving endpoint (env-seeded via :meth:`from_env`)."""

    #: ascending batch-size buckets; one AOT executable compiles per bucket.
    buckets: tuple = DEFAULT_BUCKETS
    #: deadline-aware flush: a micro-batch goes out when the OLDEST pending
    #: request has waited this long, even if the largest bucket isn't full.
    max_wait_ms: float = DEFAULT_MAX_WAIT_MS
    #: donate the request batch buffer into the compiled program (the
    #: engine owns the freshly-transferred micro-batch, so donation is
    #: always safe and halves the inference working set).
    donate: bool = True
    #: opportunistic idle flush: when the device pipeline is idle a pending
    #: micro-batch dispatches IMMEDIATELY instead of aging toward
    #: ``max_wait_ms`` — the deadline then only governs waiting while the
    #: device is busy (where waiting buys occupancy).  Disable for strict
    #: two-trigger (full-or-deadline) flushing.
    eager_flush: bool = True

    def __post_init__(self):
        buckets = tuple(sorted({int(b) for b in self.buckets}))
        if not buckets or any(b < 1 for b in buckets):
            raise ValueError(f"buckets must all be >= 1, got {self.buckets}")
        self.buckets = buckets
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}"
            )

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    @classmethod
    def from_env(cls, **overrides) -> "ServeConfig":
        """``KEYSTONE_SERVE_BUCKETS`` / ``KEYSTONE_SERVE_MAX_BATCH`` /
        ``KEYSTONE_SERVE_MAX_WAIT_MS``, any field overridable by keyword."""
        cfg: dict = {}
        raw = os.environ.get(BUCKETS_ENV, "").strip()
        buckets = _parse_buckets(raw) if raw else DEFAULT_BUCKETS
        mb = os.environ.get(MAX_BATCH_ENV, "").strip()
        if mb:
            cap = int(mb)
            if cap < 1:
                raise ValueError(f"{MAX_BATCH_ENV}={mb!r} must be >= 1")
            buckets = tuple(b for b in buckets if b < cap) + (cap,)
        cfg["buckets"] = buckets
        cfg["max_wait_ms"] = _env_float(MAX_WAIT_ENV, DEFAULT_MAX_WAIT_MS)
        cfg["eager_flush"] = (
            os.environ.get(EAGER_FLUSH_ENV, "").strip() != "0"
        )
        cfg.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**cfg)

    def record(self) -> dict:
        return {
            "buckets": list(self.buckets),
            "max_wait_ms": self.max_wait_ms,
            "donate": self.donate,
            "eager_flush": self.eager_flush,
        }


# -- fused AOT inference ------------------------------------------------------


class ServingEngine:
    """One fitted pipeline compiled into per-bucket AOT inference programs.

    ``pipe`` is any fitted Transformer/Pipeline over batches (a registered
    pytree: its fitted arrays become real program ARGUMENTS, not baked
    constants, so the same weights buffer feeds every bucket executable).
    ``example`` fixes one request's shape/dtype — a host array or
    ``jax.ShapeDtypeStruct`` WITHOUT the batch axis.

    Every bucket preflights through ``core.memory.plan_program`` (the same
    admission control the solvers use): the request batch argument is
    DONATED, the breakdown is recorded in ``memory_plans``, and a bucket
    denied by admission never compiles into the endpoint — it is dropped
    with a counted ``serve_bucket_denied`` (the smallest bucket is the
    floor and is kept even when denied, exactly like ``run_ladder``'s
    floor tier).  A bucket that still hits RESOURCE_EXHAUSTED under burst
    traffic at runtime is retired (counted ``serve_burst_oom``) and its
    requests re-run through smaller buckets — degradation, never a silent
    wrong answer.
    """

    def __init__(
        self,
        pipe,
        example,
        *,
        config: ServeConfig | None = None,
        label: str = "pipeline",
        warmup: bool = True,
        mesh=None,
    ):
        import jax

        self._jax = jax
        #: the eager parity/offline oracle always applies the ORIGINAL
        #: object — mixing mesh-committed params into the eager apply
        #: would let placement errors masquerade as parity failures.
        self._oracle_pipe = pipe
        if mesh is not None:
            from ..parallel.mesh import host_local_mesh, mesh_spans_processes

            if mesh_spans_processes(mesh):
                # Serving never spans hosts: a request answered through a
                # cross-process mesh would need every host's cooperation
                # per request (one slow peer stalls the whole fleet, one
                # dead peer aborts it).  Typed refusal with the fix named
                # — anchor each host's engines on ITS sub-mesh and let the
                # front-end fan requests across hosts.
                raise ServeError(
                    f"serving mesh spans processes — anchor on "
                    f"host_local_mesh(mesh) instead "
                    f"(this host owns {host_local_mesh(mesh).devices.size} "
                    f"of the mesh's devices)"
                )
        self.mesh = mesh
        self._pipe = self._mesh_place(pipe, mesh) if mesh is not None else pipe
        self.label = label
        self.config = config or ServeConfig.from_env()
        self.example_shape = tuple(int(d) for d in example.shape)
        self.example_dtype = np.dtype(example.dtype)
        if self.config.donate:
            self._fn = jax.jit(
                lambda pipe, batch: pipe(batch), donate_argnums=(1,)
            )
        else:
            self._fn = jax.jit(lambda pipe, batch: pipe(batch))
        #: bucket -> MemoryPlan (admission verdict + memory_analysis
        #: breakdown) for EVERY configured bucket, dropped ones included.
        self.memory_plans: dict[int, kmem.MemoryPlan] = {}
        #: bucket -> seconds of the warmup inference (compile+first run
        #: cost a live request never pays).
        self.warmup_seconds: dict[int, float] = {}
        #: bucket -> did its probe rows come back bit-identical to the
        #: eager offline apply (filled by :meth:`warmup`)?
        self.parity: dict[int, bool] = {}
        #: False only when NO bucket passed the parity probe (the engine
        #: serves, but its answers are per-bucket-consistent rather than
        #: verified eager-equal — counted, never silent).
        self.parity_ok: bool = True
        self._exec: dict[int, Any] = {}
        self._lock = threading.Lock()
        #: numerics observatory (ISSUE 15): the output-drift monitor, armed
        #: by :meth:`arm_drift_baseline` when a fit-time reference baseline
        #: exists (load_engine reads it from the checkpoint manifest).
        self.drift: knum.DriftMonitor | None = None
        self._build()
        if warmup:
            self.warmup()

    # -- construction ---------------------------------------------------------

    def _mesh_place(self, pipe, mesh):
        """Pin the fitted state onto the serving mesh.  A ``jax.Array``
        leaf already resident on exactly this mesh's devices keeps its
        SOLVE placement (a mesh fit serves from where it solved — no host
        pull); every other array leaf is placed replicated
        (``parallel.mesh.spec_sharding``) so each bucket program sees
        committed, mesh-consistent parameters."""
        from ..parallel.mesh import spec_sharding

        jax = self._jax
        mesh_devs = set(mesh.devices.flat)

        def place(leaf):
            if isinstance(leaf, jax.Array):
                try:
                    if set(leaf.sharding.device_set) == mesh_devs:
                        return leaf
                except Exception:  # noqa: BLE001 — unknown sharding: re-place
                    pass
            elif not isinstance(leaf, (np.ndarray, np.generic)):
                return leaf
            arr = np.asarray(jax.device_get(leaf))
            return jax.device_put(
                arr, spec_sharding("replicated", mesh, arr.ndim)
            )

        return jax.tree_util.tree_map(place, pipe)

    def _batch_sharding(self, bucket: int):
        """Layout of one request micro-batch on the serving mesh:
        row-sharded over the data axis when the bucket divides evenly,
        replicated otherwise (small buckets under a wide mesh).  ``None``
        when the engine is meshless."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS

        d = int(self.mesh.shape[DATA_AXIS])
        if d > 1 and bucket % d == 0:
            return NamedSharding(self.mesh, P(DATA_AXIS))
        return NamedSharding(self.mesh, P())

    def _batch_struct(self, bucket: int):
        sharding = self._batch_sharding(bucket)
        if sharding is not None:
            return self._jax.ShapeDtypeStruct(
                (bucket, *self.example_shape), self.example_dtype,
                sharding=sharding,
            )
        return self._jax.ShapeDtypeStruct(
            (bucket, *self.example_shape), self.example_dtype
        )

    def _h2d(self, padded: np.ndarray, bucket: int):
        """One micro-batch host->device, onto the serving mesh when one is
        set — the layout the bucket's AOT executable was lowered for."""
        sharding = self._batch_sharding(bucket)
        if sharding is None:
            return self._jax.device_put(padded)
        return self._jax.device_put(padded, sharding)

    def _build(self) -> None:
        for i, bucket in enumerate(self.config.buckets):
            floor = i == 0
            with trace.span(
                "serve.compile", cat="serve", bucket=bucket, label=self.label
            ):
                plan = kmem.plan_program(
                    self._fn,
                    self._pipe,
                    self._batch_struct(bucket),
                    label=f"serve:{self.label}:b{bucket}",
                    require_analysis=True,
                    mesh=self.mesh,
                )
            self.memory_plans[bucket] = plan
            if plan.compiled is None:
                raise ServeError(
                    f"serve:{self.label}: bucket {bucket} failed to "
                    f"compile — {plan.reason}"
                )
            if not plan.admitted and not floor:
                counters.record(
                    "serve_bucket_denied",
                    f"serve:{self.label}: bucket {bucket} denied by HBM "
                    f"admission ({plan.reason}) — endpoint serves without it",
                )
                continue
            if not plan.admitted and floor:
                _logger.warning(
                    "serve:%s: floor bucket %d denied by preflight (%s) but "
                    "nothing is below it — serving anyway",
                    self.label, bucket, plan.reason,
                )
            self._exec[bucket] = plan.compiled
        if not self._exec:  # pragma: no cover — floor is always kept
            raise ServeError(f"serve:{self.label}: no bucket survived admission")

    def _probe_batch(self, rows: int) -> np.ndarray:
        """Deterministic nonzero probe data for the parity check (zeros
        would let a broken program pass by accident)."""
        rng = np.random.default_rng(20260803)
        shape = (rows, *self.example_shape)
        if self.example_dtype.kind in "fc":
            return rng.standard_normal(shape).astype(self.example_dtype)
        info = np.iinfo(self.example_dtype)
        return rng.integers(
            info.min, min(info.max, 255), shape, endpoint=True
        ).astype(self.example_dtype)

    def warmup(self) -> float:
        """Run each live bucket once on a deterministic probe batch — no
        live request ever pays a first-dispatch cost — and VERIFY PARITY:
        each bucket's probe rows must be bit-identical to the eager offline
        apply of the same rows.  A bucket that fails (XLA's batch-1 gemv
        path rounds differently than the shared gemm path, for instance) is
        dropped with a counted ``serve_bucket_parity_dropped``.  If NO
        bucket passes (XLA fuses the whole chain differently than the
        op-by-op eager apply — the Fisher chains measure ~1e-3 relative),
        the engine records ``parity_ok=False`` (counted
        ``serve_parity_unverified``) and RE-ANCHORS parity on the largest
        bucket's own AOT rows: buckets that disagree with *that* are still
        dropped, so every served answer remains deterministic and
        bucket-independent — degraded from "verified eager-equal" to
        "self-consistent", never to "depends which batch you rode in".
        Served but saying so beats refusing service.  Returns total warmup
        seconds."""
        live = self.buckets()
        if not live:
            return 0.0
        probe = self._probe_batch(live[-1])
        oracle = self.offline(probe)
        total = 0.0
        outs: dict[int, np.ndarray] = {}
        for bucket in live:
            t0 = time.perf_counter()
            with trace.span(
                "serve.warmup", cat="serve", bucket=bucket, label=self.label
            ):
                outs[bucket] = np.asarray(
                    self._execute(
                        bucket, self._h2d(probe[:bucket], bucket)
                    )
                )
            dt = time.perf_counter() - t0
            self.warmup_seconds[bucket] = dt
            total += dt
            self.parity[bucket] = bool(
                np.array_equal(
                    outs[bucket][:bucket], np.asarray(oracle)[:bucket]
                )
            )
        passing = [b for b in live if self.parity.get(b)]
        reason = "rows differ from the eager apply"
        if not passing:
            self.parity_ok = False
            counters.record(
                "serve_parity_unverified",
                f"serve:{self.label}: no bucket reproduced the eager "
                "oracle bit-for-bit — re-anchoring on the largest bucket "
                "(served answers stay self-consistent, not eager-verified)",
            )
            # Self-consistency floor: the largest bucket's AOT rows become
            # the anchor; its own parity flag stays False (it is NOT
            # eager-verified) but it always survives the drop pass.
            anchor = outs[live[-1]]
            passing = [
                b
                for b in live
                if np.array_equal(outs[b][:b], anchor[:b])
            ]
            reason = "rows differ from the largest bucket's AOT apply"
        for bucket in live:
            if bucket in passing:
                continue
            with self._lock:
                self._exec.pop(bucket, None)
            counters.record(
                "serve_bucket_parity_dropped",
                f"serve:{self.label}: bucket {bucket} {reason} "
                "(batch-size-dependent XLA rounding) — dropped so every "
                f"served answer stays deterministic; live {passing}",
            )
        return total

    # -- the inference path ---------------------------------------------------

    def buckets(self) -> tuple[int, ...]:
        """Currently-live buckets, ascending (admission-dropped and
        OOM-retired buckets excluded)."""
        with self._lock:
            return tuple(sorted(self._exec))

    def bucket_for(self, n: int) -> int:
        """Smallest live bucket holding ``n`` requests (the largest bucket
        when ``n`` exceeds it — the caller chunks)."""
        live = self.buckets()
        if not live:
            raise ServingUnavailable(
                f"serve:{self.label}: every bucket OOMed away — the "
                "endpoint has no executable left"
            )
        for b in live:
            if n <= b:
                return b
        return live[-1]

    def _execute(self, bucket: int, dev_batch):
        """Run one bucket's AOT executable (the very program the preflight
        planned — ``plan.compiled``).  Separated out so the chaos harness
        can inject RESOURCE_EXHAUSTED here."""
        with self._lock:
            ex = self._exec.get(bucket)
        if ex is None:
            raise ServingUnavailable(
                f"serve:{self.label}: bucket {bucket} was retired"
            )
        return ex(self._pipe, dev_batch)

    def _profile_bucket(self, bucket: int, wall_seconds: float) -> None:
        """Ledger hook (core.profiler): one synced bucket execution's MFU
        attribution, keyed ``serve:<label>:b<bucket>``.  Caller gates on
        ``profiler.enabled()`` — this is never on the off path."""
        plan = self.memory_plans.get(bucket)
        kprof.record_program(
            f"serve:{self.label}:b{bucket}",
            plan.compiled if plan is not None else None,
            wall_seconds,
        )

    def _retire_bucket(self, bucket: int, why: str) -> None:
        with self._lock:
            self._exec.pop(bucket, None)
            remaining = sorted(self._exec)
        # Retirements land in the metrics registry too (ISSUE 11): one
        # snapshot() shows the endpoint's degradation state alongside the
        # fault ledger's serve_burst_oom count.
        trace.metrics.inc("serve_bucket_retired")
        trace.metrics.gauge("serve_live_buckets", len(remaining))
        counters.record(
            "serve_burst_oom",
            f"serve:{self.label}: bucket {bucket} {why} — degraded to "
            f"buckets {remaining}",
        )
        trace.instant(
            "serve_bucket_retired", bucket=bucket, label=self.label,
            remaining=remaining,
        )

    def arm_drift_baseline(self, baseline: dict | None) -> None:
        """Arm output-drift detection against a fit-time reference sketch
        (the ``numerics_baseline`` entry ``core.checkpoint.save_pipeline``
        persists in the manifest).  None is a no-op — an engine without a
        baseline serves exactly as before."""
        if baseline:
            self.drift = knum.DriftMonitor(self.label, baseline)

    def rearm_drift_baseline(self, baseline: dict | None) -> None:
        """Re-arm drift detection on a NEW fit-time baseline (counted
        ``drift_rearmed``) — the lifecycle hot-swap path.  Unlike
        :meth:`arm_drift_baseline` this resets the live window and the
        latch through :meth:`numerics.DriftMonitor.rearm`, so answers the
        candidate produced during validation/warmup never contaminate the
        post-swap judgment.  None is a no-op; an engine with no monitor
        yet arms one."""
        if not baseline:
            return
        if self.drift is None:
            self.arm_drift_baseline(baseline)
        else:
            self.drift.rearm(baseline)

    def observe_output(self, host_rows, request_ids=None, bucket=None) -> None:
        """Numerics observatory hook on one bucket's ANSWERED rows: a
        tensor-stat probe (request ids as the NaN-provenance map) plus the
        output-drift sketch.  Observation only — the rows are already on
        their way to the callers, bit-unchanged.  One flag check when the
        observatory is off."""
        if not knum.active():
            return
        site = f"serve.{self.label}" + (f".b{bucket}" if bucket else "")
        knum.probe(site, host_rows, request_ids=request_ids)
        if self.drift is not None:
            self.drift.observe(host_rows)

    def _pad(self, host: np.ndarray, bucket: int) -> np.ndarray:
        pad = bucket - host.shape[0]
        if pad <= 0:
            return host
        return np.concatenate(
            [host, np.zeros((pad, *host.shape[1:]), host.dtype)], axis=0
        )

    def infer(self, host_batch: np.ndarray) -> np.ndarray:
        """Answer ``[n, *example_shape]`` host rows through the bucketed
        AOT programs: chunked to the largest live bucket, each chunk
        padded to its bucket, transferred, executed, sliced back to the
        true rows.  A runtime RESOURCE_EXHAUSTED retires the failing
        bucket and re-runs the SAME rows through smaller buckets — the
        caller sees correct answers or a typed error, never neither."""
        host_batch = np.asarray(host_batch)
        n = host_batch.shape[0]
        outs = []
        i = 0
        while i < n:
            bucket = self.bucket_for(n - i)
            chunk = host_batch[i : i + min(bucket, n - i)]
            outs.append(self._infer_chunk(chunk, bucket))
            i += chunk.shape[0]
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _infer_chunk(self, chunk: np.ndarray, bucket: int) -> np.ndarray:
        k = chunk.shape[0]
        padded = self._pad(chunk, bucket)
        with trace.io_span(
            "serve.h2d", padded.nbytes, cat="serve", bucket=bucket
        ):
            dev = self._h2d(padded, bucket)
        try:
            t_exec = time.perf_counter()
            with trace.span(
                "serve.execute", cat="serve", bucket=bucket, rows=k
            ) as sp:
                out = sp.sync(self._execute(bucket, dev))
            if kprof.enabled():
                # Per-bucket MFU ledger entry (ISSUE 14): the synced
                # execute wall against the very executable the preflight
                # planned.  One enabled() check when the profiler is off.
                self._profile_bucket(bucket, time.perf_counter() - t_exec)
        except Exception as e:  # noqa: BLE001 — only OOM degrades
            # A concurrent caller can retire this bucket between
            # bucket_for() and _execute(); rows re-route below exactly
            # like an own-OOM (no live bucket left -> typed raise).
            retired_race = (
                isinstance(e, ServingUnavailable)
                and bucket not in self.buckets()
            )
            if not kmem.is_oom_error(e) and not retired_race:
                raise
            if not retired_race:
                self._retire_bucket(
                    bucket, "hit RESOURCE_EXHAUSTED at runtime"
                )
            kmem.free_buffers(dev)
            if not self.buckets():
                raise ServingUnavailable(
                    f"serve:{self.label}: burst OOM on the last "
                    f"bucket ({bucket}) — nothing to degrade to"
                ) from e
            # Re-run the same rows through the surviving buckets (several
            # micro-batches when the chunk no longer fits one).
            return self.infer(chunk)
        with trace.io_span(
            "serve.d2h",
            int(getattr(out, "nbytes", 0)), cat="serve", bucket=bucket,
        ):
            host = np.asarray(out)
        self.observe_output(host[:k], bucket=bucket)
        return host[:k]

    def offline(self, host_batch: np.ndarray) -> np.ndarray:
        """The offline oracle: the fitted pipeline applied directly (no
        bucketing, no padding, no AOT path) — what served answers are
        asserted bit-equal against."""
        import jax.numpy as jnp

        return np.asarray(self._oracle_pipe(jnp.asarray(host_batch)))

    def record(self) -> dict:
        """JSON-able engine summary for bench records."""
        from ..parallel.mesh import mesh_desc

        return {
            "label": self.label,
            "config": self.config.record(),
            "example_shape": list(self.example_shape),
            "example_dtype": str(self.example_dtype),
            "mesh": mesh_desc(self.mesh) if self.mesh is not None else None,
            "live_buckets": list(self.buckets()),
            "parity_ok": self.parity_ok,
            "parity": {str(k): v for k, v in self.parity.items()},
            "warmup_seconds": {
                str(k): round(v, 4) for k, v in self.warmup_seconds.items()
            },
            "memory_plans": {
                str(k): p.breakdown() for k, p in self.memory_plans.items()
            },
            # Output-drift verdict (ISSUE 15): None when no fit-time
            # baseline was armed.
            "drift": self.drift.record() if self.drift is not None else None,
        }


def load_engine(
    path: str,
    example,
    *,
    config: ServeConfig | None = None,
    label: str = "pipeline",
    wrap: Callable[[Any], Any] | None = None,
    mesh=None,
) -> tuple[ServingEngine, dict]:
    """Warm-load a fitted pipeline from a ``core.checkpoint`` artifact and
    stand up its serving engine, measuring the fresh-process COLD START:
    restore seconds, per-bucket AOT compile (inside engine build), and the
    warmup inference.  ``wrap`` post-processes the loaded object into the
    servable Transformer (e.g. a workload assembling a checkpointed dict
    of fitted nodes into its apply chain).  ``mesh`` makes the whole round
    trip topology-portable: the checkpoint restores THROUGH
    ``load_pipeline(mesh=)`` (resharded onto the target, even when it was
    recorded under a different topology) and the engine AOT-compiles
    mesh-native on it.  Returns ``(engine, cold_start_record)``."""
    from .checkpoint import load_numerics_baseline, load_pipeline

    t0 = time.perf_counter()
    with trace.span("serve.cold_load", cat="serve", path=path):
        pipe = load_pipeline(path, mesh=mesh)
    t_load = time.perf_counter()
    if wrap is not None:
        pipe = wrap(pipe)
    engine = ServingEngine(
        pipe, example, config=config, label=label, warmup=False, mesh=mesh
    )
    # Output-drift detection (ISSUE 15): arm the monitor from the
    # fit-time reference sketch the checkpoint manifest carries (absent
    # on pre-observatory artifacts — the engine just serves unmonitored).
    engine.arm_drift_baseline(load_numerics_baseline(path))
    t_build = time.perf_counter()
    engine.warmup()
    t_warm = time.perf_counter()
    cold = {
        "checkpoint_load_seconds": round(t_load - t0, 4),
        "compile_seconds": round(t_build - t_load, 4),
        "warmup_seconds": round(t_warm - t_build, 4),
        "cold_start_seconds": round(t_warm - t0, 4),
    }
    if mesh is not None:
        from ..parallel.mesh import mesh_desc

        cold["mesh"] = mesh_desc(mesh)
    trace.instant("serve_cold_start", label=label, **cold)
    return engine, cold


# -- the dynamic request batcher ----------------------------------------------


class ServeFuture:
    """Handle for one submitted request.  ``result()`` blocks until the
    batcher answers (the request's own output slice) or fails it typed.

    Lifecycle telemetry (ISSUE 11): ``request_id`` is minted at
    ``Server.submit`` and rides through every span the request touches
    (queue -> batch assembly -> H2D -> execute -> slice -> answer), and
    ``phases`` holds the per-phase latency decomposition — queue-wait,
    H2D, device-wait (time parked in the in-flight handoff), execute,
    D2H, answer, and the estimated pad overhead — filled when the
    request resolves."""

    __slots__ = (
        "_event", "_value", "_error", "t_submit", "t_answer",
        "request_id", "phases",
    )

    def __init__(self, request_id: int = 0):
        self._event = threading.Event()
        self._value = None
        self._error: BaseException | None = None
        self.t_submit = time.perf_counter()
        self.t_answer = 0.0
        self.request_id = request_id
        self.phases: dict | None = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        if not self._event.wait(timeout):
            raise TimeoutError("request not answered within timeout")
        if self._error is not None:
            raise self._error
        return self._value

    def latency_seconds(self) -> float:
        """Submit-to-answer wall time (valid once done)."""
        return self.t_answer - self.t_submit

    def _resolve(self, value=None, error: BaseException | None = None):
        self._value = value
        self._error = error
        self.t_answer = time.perf_counter()
        self._event.set()


@dataclasses.dataclass
class ServerStats:
    """Counters of one server's lifetime (bench/chaos artifact)."""

    requests: int = 0
    answered: int = 0
    failed: int = 0  #: futures resolved with a typed error (close, OOM floor)
    malformed: int = 0
    batches: int = 0
    flush_full: int = 0  #: flushes triggered by a full largest bucket
    flush_deadline: int = 0  #: flushes triggered by max_wait_ms
    flush_idle: int = 0  #: opportunistic flushes (device pipeline idle)
    padded_rows: int = 0  #: zero rows added to reach bucket sizes
    occupancy_sum: float = 0.0  #: Σ rows/bucket per batch (mean = /batches)
    queue_peak: int = 0

    def occupancy(self) -> float:
        return self.occupancy_sum / self.batches if self.batches else 0.0

    def record(self) -> dict:
        out = dataclasses.asdict(self)
        out["mean_occupancy"] = round(self.occupancy(), 4)
        # Flight-recorder postmortems written this process (core.telemetry)
        # — the serving stats record links straight to the evidence files.
        out["postmortems"] = telemetry.postmortem_paths()
        return out


class Server:
    """The warm online endpoint: submit single requests, get futures.

    A background ASSEMBLER thread collects queued requests into
    bucket-sized micro-batches (flush on full-largest-bucket OR
    ``max_wait_ms`` from the oldest request, whichever first), pads the
    remainder to the nearest bucket, and dispatches the H2D transfer; a
    background EXECUTOR thread runs the bucket's AOT program and answers
    each request with its own output slice in arrival order.  The two
    threads keep :data:`INFLIGHT_BATCHES` micro-batches in flight — batch
    *i+1* transfers while batch *i* executes, the ``core.ingest``
    double-buffer idiom on the request path.

    Use as a context manager (or call :meth:`close`); pending requests at
    close answer :class:`ServingUnavailable`, never hang.
    """

    def __init__(self, engine: ServingEngine, config: ServeConfig | None = None):
        self.engine = engine
        self.config = config or engine.config
        self.stats = ServerStats()
        #: live SLO surface for this endpoint (core.telemetry): rolling
        #: p50/p99/QPS and error-budget burn rate against the
        #: KEYSTONE_SERVE_SLO_MS target; registered so metrics.snapshot()
        #: carries it under the "slo" group.
        self.slo = telemetry.register_slo(engine.label)
        self._next_id = 0
        self._queue: list = []  # pending _Request entries, arrival order
        self._cond = threading.Condition()
        self._stopped = False
        # Futures minted but not yet resolved (answered OR failed): the
        # accounting drain()/outstanding() wait on.  Every resolution path
        # decrements exactly once (the success loop in _run_batch, and
        # _fail_futs for every typed-failure path).
        self._outstanding = 0
        # assembler -> executor handoff (bounded: backpressure keeps at
        # most INFLIGHT_BATCHES transfers ahead of the executor).
        self._inflight: list = []
        self._inflight_cond = threading.Condition()
        # True while the executor thread is inside a batch — read (without
        # the lock, deliberately: a stale read only shifts WHICH trigger
        # flushes, never correctness) by the assembler's idle-flush check.
        self._executing = False
        self._assembler = threading.Thread(
            target=self._assemble_loop, name="keystone-serve-assembler",
            daemon=True,
        )
        self._executor = threading.Thread(
            target=self._execute_loop, name="keystone-serve-executor",
            daemon=True,
        )
        self._assembler.start()
        self._executor.start()

    # -- client surface -------------------------------------------------------

    def submit(self, x) -> ServeFuture:
        """Enqueue one request (shape ``example_shape``).  Malformed
        requests — wrong shape, uncastable dtype, non-finite payload —
        raise :class:`MalformedRequest` HERE, counted, without ever
        entering a batch."""
        arr = self._validate(x)
        with self._cond:
            if self._stopped:
                raise ServingUnavailable("server is closed")
            self._next_id += 1
            fut = ServeFuture(request_id=self._next_id)
            self._queue.append((arr, fut))
            self._outstanding += 1
            self.stats.requests += 1
            self.stats.queue_peak = max(self.stats.queue_peak, len(self._queue))
            trace.metrics.gauge("serve_queue_depth", len(self._queue))
            trace.metrics.gauge("serve_queue_peak", self.stats.queue_peak)
            self._cond.notify_all()
        # The request's birth on the timeline (and in the flight ring):
        # the id minted here is the key every later lifecycle span carries.
        trace.instant(
            "serve.submit", request_id=fut.request_id, label=self.engine.label
        )
        return fut

    def predict(self, x, timeout: float | None = 30.0):
        """Blocking convenience: ``submit`` + ``result``."""
        return self.submit(x).result(timeout)

    def _reject(self, detail: str, message: str, cause=None):
        # stats mutations happen under the same condition lock as every
        # other ServerStats field — a bare += from concurrent submitters
        # would drop increments and let stats.malformed silently disagree
        # with the (lock-protected) counters ledger.
        with self._cond:
            self.stats.malformed += 1
        counters.record("serve_malformed_request", detail)
        raise MalformedRequest(message) from cause

    def _validate(self, x) -> np.ndarray:
        eng = self.engine
        try:
            arr = np.asarray(x)
        except Exception as e:  # noqa: BLE001 — unarrayable payload
            self._reject(
                f"unarrayable payload: {e}",
                f"request is not array-like: {e}",
                cause=e,
            )
        if tuple(arr.shape) != eng.example_shape:
            self._reject(
                f"shape {tuple(arr.shape)} != {eng.example_shape}",
                f"request shape {tuple(arr.shape)} does not match the "
                f"endpoint's example shape {eng.example_shape}",
            )
        try:
            arr = arr.astype(eng.example_dtype, casting="same_kind", copy=False)
        except TypeError:
            self._reject(
                f"dtype {arr.dtype} not castable to {eng.example_dtype}",
                f"request dtype {arr.dtype} is not same-kind castable to "
                f"{eng.example_dtype}",
            )
        if arr.dtype.kind in "fc" and not np.isfinite(arr).all():
            self._reject(
                "non-finite payload",
                "request payload contains NaN/Inf — refusing to serve a "
                "prediction from a poisoned input",
            )
        return arr

    # -- assembler thread -----------------------------------------------------

    def _pipeline_idle(self) -> bool:
        """No batch in the H2D handoff and none executing — waiting longer
        buys zero occupancy, so a pending batch should go NOW."""
        return not self._inflight and not self._executing

    def _collect(self) -> list | None:
        """Block until a micro-batch is due: full largest bucket, the
        oldest request aged past ``max_wait_ms``, or (``eager_flush``) the
        device pipeline went idle with requests pending.  None at
        shutdown."""
        max_batch = self.config.max_batch
        max_wait = self.config.max_wait_ms / 1e3
        with self._cond:
            while True:
                if self._queue:
                    oldest = self._queue[0][1].t_submit
                    if len(self._queue) >= max_batch:
                        self.stats.flush_full += 1
                        reason = "full"
                    elif time.perf_counter() - oldest >= max_wait:
                        self.stats.flush_deadline += 1
                        reason = "deadline"
                    elif self.config.eager_flush and self._pipeline_idle():
                        self.stats.flush_idle += 1
                        reason = "idle"
                    else:
                        reason = None
                    if reason is None:
                        remaining = max_wait - (time.perf_counter() - oldest)
                        self._cond.wait(min(remaining, _POLL_SECONDS))
                        continue
                    batch = self._queue[:max_batch]
                    del self._queue[:max_batch]
                    trace.metrics.gauge("serve_queue_depth", len(self._queue))
                    # Flush reasons are registry counters too, so one
                    # snapshot() shows the batcher's trigger mix.
                    trace.metrics.inc(f"serve_flush_{reason}")
                    trace.instant(
                        "serve_flush", reason=reason, rows=len(batch),
                        queued=len(self._queue),
                    )
                    return batch
                if self._stopped:
                    return None
                self._cond.wait(_POLL_SECONDS)

    def _assemble_loop(self) -> None:
        try:
            while True:
                batch = self._collect()
                if batch is None:
                    break
                all_rows = np.stack([arr for arr, _ in batch])
                all_futs = [fut for _, fut in batch]
                # Chunk by the CURRENT largest live bucket: after a burst-OOM
                # retirement the collected batch can exceed every surviving
                # bucket, and an oversized batch must become several
                # micro-batches, never a wrong-shaped AOT argument.
                stop = False
                i = 0
                while i < all_rows.shape[0] and not stop:
                    bucket = self.engine.bucket_for(all_rows.shape[0] - i)
                    take = min(bucket, all_rows.shape[0] - i)
                    rows = all_rows[i : i + take]
                    futs = all_futs[i : i + take]
                    i += take
                    n = rows.shape[0]
                    t_assembled = time.perf_counter()
                    padded = self.engine._pad(rows, bucket)
                    pad = padded.shape[0] - n
                    self.stats.padded_rows += pad
                    if pad:
                        trace.metrics.inc("serve_padded_rows", pad)
                    # Dispatch the H2D NOW (async) — it overlaps the
                    # executor's work on the previous micro-batch.  The
                    # span carries the micro-batch's request-id range so a
                    # postmortem can tie a transfer to its victims.
                    with trace.io_span(
                        "serve.h2d", padded.nbytes, cat="serve", bucket=bucket,
                        req_first=futs[0].request_id,
                        req_last=futs[-1].request_id,
                    ):
                        dev = self.engine._h2d(padded, bucket)
                    t_h2d_done = time.perf_counter()
                    entry = (futs, rows, dev, bucket, t_assembled, t_h2d_done)
                    with self._inflight_cond:
                        while (
                            len(self._inflight) >= INFLIGHT_BATCHES
                            and not self._stopped
                        ):
                            self._inflight_cond.wait(_POLL_SECONDS)
                        if self._stopped:
                            # Close raced a collected batch: fail the chunk
                            # in hand AND every not-yet-chunked future of
                            # this batch — all_futs[i:] would otherwise
                            # never be resolved by anyone (the queue no
                            # longer holds them), leaving their callers
                            # blocked forever.
                            self._fail_futs(
                                futs + all_futs[i:],
                                ServingUnavailable("server closed mid-batch"),
                            )
                            stop = True
                        else:
                            self._inflight.append(entry)
                            self._inflight_cond.notify_all()
                if stop:
                    break
        except BaseException as e:  # noqa: BLE001 — never die silently
            _logger.exception("serve assembler thread failed")
            self._shutdown(error=e)
        finally:
            with self._inflight_cond:
                self._inflight.append(None)  # end-of-stream for the executor
                self._inflight_cond.notify_all()

    # -- executor thread ------------------------------------------------------

    def _execute_loop(self) -> None:
        while True:
            with self._inflight_cond:
                while not self._inflight:
                    self._inflight_cond.wait(_POLL_SECONDS)
                entry = self._inflight.pop(0)
                self._executing = entry is not None
                self._inflight_cond.notify_all()
            if entry is None:
                break
            try:
                self._run_batch(entry)
            finally:
                self._executing = False
                # Wake the assembler promptly: the pipeline just went idle,
                # which is itself a flush trigger under eager_flush.
                with self._cond:
                    self._cond.notify_all()

    def _run_batch(self, entry) -> None:
        futs, rows, dev, bucket, t_assembled, t_h2d_done = entry
        n = len(futs)
        degraded = False
        t_exec_start = time.perf_counter()
        try:
            try:
                with trace.span(
                    "serve.execute", cat="serve", bucket=bucket, rows=n,
                    req_first=futs[0].request_id,
                    req_last=futs[-1].request_id,
                ) as sp:
                    out = sp.sync(self.engine._execute(bucket, dev))
                t_exec = time.perf_counter()
                if kprof.enabled():
                    self.engine._profile_bucket(bucket, t_exec - t_exec_start)
                with trace.io_span(
                    "serve.d2h",
                    int(getattr(out, "nbytes", 0)), cat="serve", bucket=bucket,
                ):
                    host = np.asarray(out)
                t_d2h = time.perf_counter()
            except Exception as e:  # noqa: BLE001 — OOM degrades, in-line
                # Retirement race: the previous batch's OOM retired this
                # bucket while THIS batch was already assembled/in flight
                # (the double buffer keeps INFLIGHT_BATCHES ahead) — its
                # rows re-route like the OOM batch's own, they are not
                # failures.  A ServingUnavailable with live buckets
                # remaining is exactly that race; with none left, infer()
                # below re-raises it and the futures fail typed.
                retired_race = (
                    isinstance(e, ServingUnavailable)
                    and bucket not in self.engine.buckets()
                )
                if not kmem.is_oom_error(e) and not retired_race:
                    raise
                if not retired_race:
                    self.engine._retire_bucket(
                        bucket, "hit RESOURCE_EXHAUSTED under burst traffic"
                    )
                kmem.free_buffers(dev)
                # Same rows, smaller buckets — answers stay correct, the
                # endpoint stays up (the tf-serving degradation ladder).
                host = self.engine.infer(rows)
                t_exec = t_d2h = time.perf_counter()
                degraded = True
        except BaseException as e:  # noqa: BLE001 — typed delivery
            counters.record(
                "serve_batch_failed", f"{type(e).__name__}: {e}"
            )
            self._fail_futs(futs, e)
            return
        if not degraded:
            # Numerics observatory: probe + drift-sketch this bucket's
            # answered rows with their request ids as provenance.  The
            # degraded path already observed through infer()'s own chunks
            # — observing again would double-count the sketch.
            self.engine.observe_output(
                host[:n],
                request_ids=[f.request_id for f in futs],
                bucket=bucket,
            )
        self.stats.batches += 1
        self.stats.answered += n
        self.stats.occupancy_sum += n / bucket
        trace.metrics.inc("serve_batches")
        trace.metrics.observe("serve_batch_occupancy", n / bucket)
        trace.metrics.gauge("serve_mean_occupancy", self.stats.occupancy())
        pad = bucket - n if bucket > n else 0
        execute_ms = (t_exec - t_exec_start) * 1e3
        now = time.perf_counter()
        for i, fut in enumerate(futs):
            # Per-phase latency decomposition (ISSUE 11), recorded on the
            # future itself: where did this request's latency go?
            # queue-wait (submit -> batch assembly), H2D, device-wait
            # (parked in the in-flight handoff behind the previous
            # micro-batch), execute, D2H, answer (slice + resolve), plus
            # the pad overhead estimate (execute time bought for zero
            # rows: execute_ms * pad/bucket).
            queue_ms = (t_assembled - fut.t_submit) * 1e3
            latency_ms = (now - fut.t_submit) * 1e3
            fut.phases = {
                "request_id": fut.request_id,
                "bucket": bucket,
                "rows": n,
                "pad_rows": pad,
                "queue_wait_ms": round(queue_ms, 3),
                "h2d_ms": round((t_h2d_done - t_assembled) * 1e3, 3),
                "device_wait_ms": round(
                    (t_exec_start - t_h2d_done) * 1e3, 3
                ),
                "execute_ms": round(execute_ms, 3),
                "d2h_ms": round((t_d2h - t_exec) * 1e3, 3),
                "answer_ms": round((now - t_d2h) * 1e3, 3),
                "pad_overhead_ms": round(execute_ms * pad / bucket, 3),
                "latency_ms": round(latency_ms, 3),
            }
            if degraded:
                fut.phases["degraded"] = True
            fut._resolve(value=host[i])
            self.slo.observe(latency_ms, ok=True)
            trace.metrics.observe("serve_latency_ms", latency_ms)
            trace.metrics.observe("serve_queue_wait_ms", queue_ms)
            trace.metrics.observe("serve_device_wait_ms",
                                  fut.phases["device_wait_ms"])
            trace.metrics.observe("serve_execute_ms", execute_ms)
            trace.metrics.inc("serve_requests")
            # One span per REQUEST carrying its phase breakdown — the
            # span itself is point-like on the executor lane; the real
            # intervals live on the serve.h2d/execute/d2h spans above.
            with trace.span("serve.request", cat="serve") as sp:
                sp.set(**fut.phases)
        with self._cond:
            self._outstanding -= n
            self._cond.notify_all()

    def _fail_futs(self, futs, error: BaseException) -> None:
        now = time.perf_counter()
        resolved = 0
        for fut in futs:
            if not fut.done():
                fut._resolve(error=error)
                resolved += 1
                # A typed failure burns error budget like an SLO miss.
                self.slo.observe((now - fut.t_submit) * 1e3, ok=False)
        if resolved:
            with self._cond:
                self.stats.failed += resolved
                self._outstanding -= resolved
                self._cond.notify_all()

    # -- lifecycle ------------------------------------------------------------

    def _shutdown(self, error: BaseException | None = None) -> None:
        with self._cond:
            self._stopped = True
            pending = list(self._queue)
            self._queue.clear()
            self._cond.notify_all()
        with self._inflight_cond:
            self._inflight_cond.notify_all()
        err = error or ServingUnavailable(
            "server closed with requests still pending"
        )
        self._fail_futs([fut for _, fut in pending], err)

    def outstanding(self) -> int:
        """Futures minted by :meth:`submit` and not yet resolved (answered
        or typed-failed)."""
        with self._cond:
            return self._outstanding

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every submitted request has been RESOLVED (answered
        or typed-failed) — the graceful-retire primitive: a router stops
        routing to this server, drains it, then closes it, so an engine
        swap never drops a request.  Returns False on timeout."""
        end = time.monotonic() + timeout
        with self._cond:
            while self._outstanding > 0:
                remaining = end - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, _POLL_SECONDS))
        return True

    def close(self) -> None:
        """Stop accepting requests; pending/in-flight requests answer
        :class:`ServingUnavailable`.  Idempotent."""
        self._shutdown()

    def join(self, timeout: float = 10.0) -> bool:
        """Wait for both serving threads to exit (the no-leak assertion
        the tier-1 suite runs).  Call after :meth:`close`."""
        end = time.monotonic() + timeout
        self._assembler.join(max(0.0, end - time.monotonic()))
        self._executor.join(max(0.0, end - time.monotonic()))
        return not (self._assembler.is_alive() or self._executor.is_alive())

    def __enter__(self) -> "Server":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
        self.join()


# -- the SLO bench ------------------------------------------------------------


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    return float(sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))])


#: The request-lifecycle phases aggregated by :func:`phase_breakdown`.
PHASE_KEYS = (
    "queue_wait_ms", "h2d_ms", "device_wait_ms", "execute_ms", "d2h_ms",
    "answer_ms", "pad_overhead_ms",
)


def phase_breakdown(phases: Sequence[dict]) -> dict:
    """Aggregate per-request phase decompositions (``ServeFuture.phases``)
    into mean/p99 per phase — the tf.data-style bottleneck attribution for
    the request path (which phase to fix to move p99)."""
    out: dict = {"requests": len(phases)}
    for key in PHASE_KEYS:
        vals = sorted(p[key] for p in phases if key in p)
        if not vals:
            continue
        out[key] = {
            "mean": round(sum(vals) / len(vals), 3),
            "p99": round(_percentile(vals, 0.99), 3),
        }
    if phases:
        out["degraded_requests"] = sum(1 for p in phases if p.get("degraded"))
    return out


def serve_bench(
    engine: ServingEngine,
    requests: np.ndarray,
    *,
    clients: int = 4,
    depth: int = 4,
    unbatched_baseline: bool = True,
    timeout: float = 120.0,
) -> dict:
    """Drive ``clients`` concurrent synthetic clients over ``requests``
    (``[N, *example_shape]`` rows, split round-robin; each client keeps
    ``depth`` requests outstanding — the pipelined open-loop pressure a
    real request population puts on an endpoint, and what lets the batcher
    actually fill buckets) and record the online SLOs: p50/p99 latency,
    sustained QPS, batcher occupancy — plus the batched-vs-unbatched QPS
    ratio (the SAME engine behind a flush-per-request server) and
    bit-equality of every served answer against the offline
    ``pipeline(x)`` oracle."""
    requests = np.asarray(requests)
    offline = engine.offline(requests)
    # When the chain failed eager-parity verification (parity_ok=False,
    # counted at warmup) the honest equality bar is the engine's own
    # bucketed AOT apply: answers must be DETERMINISTIC (identical to a
    # fresh offline pass through the same executables), even though the
    # eager oracle rounds differently.
    aot_oracle = None if engine.parity_ok else engine.infer(requests)

    def drive(server: Server) -> tuple[float, list, np.ndarray, list]:
        lat: list = []
        phases: list = []
        answers: list = [None] * requests.shape[0]
        errors: list = []

        def client(cid: int):
            pending: list = []

            def resolve(fut, i):
                answers[i] = fut.result(timeout)
                lat.append(fut.latency_seconds())
                if fut.phases is not None:
                    phases.append(fut.phases)

            try:
                for i in range(cid, requests.shape[0], clients):
                    pending.append((server.submit(requests[i]), i))
                    if len(pending) >= max(1, depth):
                        resolve(*pending.pop(0))
                for fut, i in pending:
                    resolve(fut, i)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout)
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        return wall, lat, np.stack(answers), phases

    with Server(engine) as server:
        wall, lat, answers, phases = drive(server)
        stats = server.stats
        slo = server.slo.summary()
    lat_ms = sorted(v * 1e3 for v in lat)
    record = {
        "engine": engine.record(),
        "clients": clients,
        "requests": int(requests.shape[0]),
        "qps": round(requests.shape[0] / wall, 2),
        "p50_latency_ms": round(_percentile(lat_ms, 0.50), 3),
        "p99_latency_ms": round(_percentile(lat_ms, 0.99), 3),
        "max_latency_ms": round(lat_ms[-1], 3) if lat_ms else 0.0,
        "batcher": stats.record(),
        # Where the latency went (ISSUE 11): mean/p99 of every request's
        # per-phase decomposition — queue-wait vs device-wait vs pad
        # overhead separable at a glance.
        "phase_breakdown": phase_breakdown(phases),
        # The live SLO surface at bench end: rolling p50/p99/QPS and the
        # error-budget burn rate against KEYSTONE_SERVE_SLO_MS.
        "slo": slo,
        "predictions_bit_identical": bool(np.array_equal(answers, offline)),
    }
    if engine.drift is not None:
        # Output-drift verdict over the benched traffic (ISSUE 15) —
        # per-engine divergence vs the fit-time baseline, the row
        # tools/health_view.py renders.
        record["output_drift"] = engine.drift.record()
    if aot_oracle is not None:
        record["parity_unverified"] = True
        record["predictions_deterministic"] = bool(
            np.array_equal(answers, aot_oracle)
        )
    if unbatched_baseline:
        # Batching OFF, everything else identical: the SAME parity-verified
        # engine behind a server whose flush threshold is one request
        # (max_batch=1, zero wait) — each request rides its own padded
        # micro-batch through the same executables, so the QPS ratio
        # isolates batching amortization, not a recompile.
        un_cfg = ServeConfig(
            buckets=(1,),
            max_wait_ms=0.0,
            donate=engine.config.donate,
            eager_flush=engine.config.eager_flush,
        )
        with Server(engine, config=un_cfg) as server:
            u_wall, _u_lat, u_answers, _u_phases = drive(server)
        record["unbatched_qps"] = round(requests.shape[0] / u_wall, 2)
        record["batched_vs_unbatched_qps"] = round(
            record["qps"] / max(record["unbatched_qps"], 1e-9), 2
        )
        record["unbatched_bit_identical"] = bool(
            np.array_equal(u_answers, offline)
        )
        if aot_oracle is not None:
            record["unbatched_deterministic"] = bool(
                np.array_equal(u_answers, aot_oracle)
            )
    return record
