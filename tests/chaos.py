"""Deterministic end-to-end chaos harness (NOT a test module — driven by
tests/test_chaos.py in-suite and tools/chaos_run.py from the CLI).

Spark subjected the reference to production chaos for free: task
preemption, stragglers, flaky DFS reads, bad records.  This harness earns
that hardness on purpose — a SEED maps to a fault schedule drawn from the
injector families in tests/faults.py, the schedule is applied to a real
workload pipeline (MnistRandomFFT or RandomPatchCifar on synthetic data),
and the outcome is judged against one invariant:

    every run either COMPLETES with predictions equal to the fault-free
    run, or fails with a TYPED, COUNTED, LOGGED error — never a silent
    wrong model, never a bare traceback.

Fault families (``seed % len(FAMILIES)`` picks the family, the seeded rng
draws its parameters — fully deterministic):

* ``solver_oom`` / ``oom_cascade`` — injected RESOURCE_EXHAUSTED at fused
  (and stepwise) dispatch: the degradation ladder must step down and the
  degraded tiers must reproduce the fault-free predictions exactly.
* ``io_transient`` — tar opens fail transiently during an image-tar ingest
  phase: core.resilience.retry must absorb them (counted ``io_retry``).
* ``corrupt_members`` — mangled JPEG members mid-archive: the loader must
  skip-and-count each (``corrupt_image``), decode every survivor.
* ``nan_input`` — NaN poisoning of the training batch: the workload's
  finite-model guard must fail TYPED (FloatingPointError), counted.
* ``preempt_resume`` — a simulated preemption mid-BCD (after a completed
  block checkpoint) followed by a ``resume_from=`` restart that must land
  on the fault-free predictions.
* ``deadline`` — an injected hang in the solve, bounded by
  ``resilience.deadline``: the run must die with a typed
  ``DeadlineExceeded`` naming the phase (counted ``deadline_exceeded``).
* ``stream_corrupt`` — a corrupt member MID-STREAM on the streaming
  ingest path (core.ingest): the stream must skip-and-count it and the
  streamed features must equal a fault-free stream over the surviving
  images bit-for-bit.
* ``stream_hang`` — an injected decoder-thread hang under the streaming
  path, bounded by ``resilience.deadline``: typed ``DeadlineExceeded``,
  never a deadlocked ring.
* ``autotune_thrash`` — forced OSCILLATING retunes of every ingest knob
  (decode width, ring depth, decode-ahead) at every chunk boundary
  mid-stream: the typed-or-equal invariant must hold under retuning —
  streamed features bit-equal to a static-knob stream, every thread
  joined.
* ``snapshot_corrupt`` — a truncated/bit-flipped snapshot shard under the
  materialized decode cache (core.snapshot): the stream must fall back to
  live decode with a counted ``snapshot_fallback`` and features
  BIT-EQUAL to the fault-free pass — never silently stale pixels.
* ``decode_worker_kill`` — SIGKILL of a process-backend decode worker
  mid-stream: the pool must respawn it (counted
  ``decode_worker_respawn``) and finish with features bit-equal to the
  thread-path oracle — never a hung ring, never a lost image.
* ``slow_client`` — one client trickles requests with long think times
  while another hammers the SAME endpoint (core.serve): the batcher's
  deadline/idle flush must keep answering the fast client (never wait for
  a full bucket that the slow client will not fill), every answer
  bit-equal to the offline apply.
* ``malformed_request`` — wrong-shape / NaN / uncastable payloads
  interleaved with good requests: each dies at ``submit`` with a typed,
  counted :class:`~keystone_tpu.core.serve.MalformedRequest` and NEVER
  enters a batch — the good batchmates' answers stay bit-equal.
* ``serve_burst_oom`` — injected RESOURCE_EXHAUSTED on the largest batch
  bucket under a request burst: the engine retires the bucket (counted
  ``serve_burst_oom``), re-answers the same requests through smaller
  buckets, and every answer stays bit-equal — degradation, never a
  silent wrong answer and never a dead endpoint.
* ``plan_mispredict`` — a cost-model misprediction made real: the
  placement search's TOP-RANKED plan dies RESOURCE_EXHAUSTED at runtime
  (injected at dispatch).  The fit must step down to the NEXT plan in the
  searched ranking (``results["placement"]`` proves the order), count an
  ``autoshard_stepdown``, and land predictions bit-equal to the
  fault-free fit — a wrong cost model degrades loudly, never silently.
* ``spec_mispredict`` — the SPEC-sharded analog (ISSUE 10): the workload
  runs under a mesh, so the search's top-ranked plan is a real
  ``NamedSharding``-layout (spec-executing) mesh plan; injected
  RESOURCE_EXHAUSTED at its GSPMD dispatch forces a counted
  ``autoshard_stepdown`` to the next-ranked plan, and predictions must
  stay bit-equal to the fault-free MESH run — a mispredicted sharded
  layout degrades loudly, never silently.
* ``wire_disconnect`` — a wire client vanishes MID-BATCH (socket closed
  with requests in flight, core.wire + core.frontend): the disconnect is
  counted (``wire_client_disconnect``), the micro-batches its requests
  ride in still COMPLETE (every serve future resolves — batchmates are
  never poisoned, answers for the dead client are discarded), and a
  second client on the same endpoint gets every answer bit-equal.
* ``slow_loris`` — clients trickle PARTIAL frames and stall (half a
  length prefix; a declared payload with one byte sent): each parks only
  its own connection's reader — the accept loop keeps accepting, and
  concurrent well-behaved clients get every answer bit-equal and timely,
  never starved behind the stalled parser.
* ``jpeg_corrupt_entropy`` — truncated scan data / an early marker in the
  entropy-coded stream MID-BATCH under device decode
  (``decode_mode="device"``, ops.jpeg_device): the damaged member becomes
  a typed, counted skip (``jpeg_corrupt_entropy``) with the rest of the
  batch surviving, and the streamed features equal a fault-free
  device-decode stream over the survivors bit-for-bit — never silent
  wrong pixels.
* ``native_entropy`` — the NATIVE entropy-decode backend
  (ops.native_entropy, the C port of the scan hot loop) under the same
  damage and under its own failure: corrupt-scan members through the
  native-preferred device stream are the SAME typed counted skips
  (``jpeg_corrupt_entropy``) with survivor features bit-equal to a
  fault-free FORCED-PYTHON stream (the portable baseline every backend
  must bit-match), and a mid-stream UNEXPECTED native failure degrades
  that one image to the Python pass counted
  (``native_entropy_fallback``) with the stream still bit-equal — never
  a crash, never a silent difference between backends.
* ``profiler_crash`` — the device cost-attribution layer's HBM watermark
  sampler thread (core.profiler) is killed MID-RUN by an injected stats
  failure: the crash is a counted degradation (``profiler_sampler_crash``),
  the profiled run COMPLETES, and its outputs are bit-equal to an
  unprofiled run — observability may die, the workload may not, and a
  dead profiler must never change a single bit of the answer.
* ``output_drift`` — a deterministically SHIFTED request mix replayed
  against a served classifier engine whose output-drift monitor
  (core.numerics, KEYSTONE_NUMERICS) is armed with a fit-time baseline:
  the divergence must be counted (``serve_output_drift``) with a
  flight-recorder postmortem dumped, and every answer must stay
  bit-equal to an UNMONITORED engine serving the same mix — detection
  fires loudly, the answers never change.
* ``mesh_shrink`` — device loss mid-serve (ISSUE 16): a mesh-anchored
  router's engines are re-anchored onto the SURVIVING mesh while requests
  are in flight — every one answered bit-equal to the offline apply
  (zero request loss across the hot swap), the event counted
  ``mesh_reanchor`` — and a fit checkpointed SHARDED under the full mesh
  must refuse a naive load (typed ``CheckpointMismatch`` naming the
  ``mesh=`` reshard path) then resume onto the survivors via
  ``load_pipeline(mesh=)`` with predictions bit-equal to the fault-free
  full-mesh run.
* ``host_loss`` — a serving HOST dies mid-flight (ISSUE 17): a fleet of
  wire-served host routers (REAL subprocesses where spawn is available,
  in-process wire servers otherwise) loses one member under live
  traffic — the front-end counts the loss (``fleet_host_lost``) and
  reissues the dead host's in-flight requests to survivors, the
  survivors re-form the reduced group (``dist_reform``), reshard the
  checkpointed state host-locally and hot-swap their engines (counted
  ``host_reanchor``, postmortem-linked); every request is answered
  bit-equal to the offline oracle — zero dropped, never a silent wrong
  answer.
* ``drift_refit`` — the closed lifecycle loop (ISSUE 18): a served
  model's request mix shifts mid-serve and the drift monitor trips
  (``serve_output_drift``); the :class:`~.core.lifecycle.
  LifecycleController` must warm-refit the model over fresh-mix data,
  validate it (finite + parity + holdout-quality gates), and hot-swap
  the router atomically (counted ``lifecycle_refit``, postmortem-linked,
  drift re-armed on the candidate's baseline) with requests IN FLIGHT
  across the swap — zero dropped, every pre-swap answer bit-equal to the
  incumbent's offline apply and every post-swap answer bit-equal to an
  OFFLINE refit on the same data.  Injected refit OOM, validation
  rejection (a candidate WORSE than the incumbent), and a mid-swap kill
  must each degrade typed + counted (``refit_failed`` /
  ``refit_rejected``) to the incumbent model — never a silent wrong
  answer, never a gap in service — and a trip inside the cooldown is a
  counted suppression (``refit_suppressed``), not a refit storm.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tarfile
import tempfile
import time

import numpy as np

import faults

from keystone_tpu.core import checkpoint as ckpt_mod
from keystone_tpu.core import ingest
from keystone_tpu.core import memory as kmem
from keystone_tpu.core import trace
from keystone_tpu.core.resilience import (
    DeadlineExceeded,
    counters,
    deadline,
)
from keystone_tpu.loaders import image_loaders
from keystone_tpu.loaders.cifar import cifar_loader
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.solvers import block as block_mod
from keystone_tpu.solvers.block import bcd_checkpoint_writer

#: Exception types that count as a STRUCTURED failure — anything else
#: escaping a chaos run is a bare traceback, i.e. a harness violation.
TYPED_ERRORS = (
    FloatingPointError,
    DeadlineExceeded,
    ckpt_mod.CheckpointError,  # includes CheckpointMismatch
    kmem.LadderSourceLost,
)

FAMILIES = (
    "solver_oom",
    "oom_cascade",
    "io_transient",
    "corrupt_members",
    "nan_input",
    "preempt_resume",
    "deadline",
    "stream_corrupt",
    "stream_hang",
    "autotune_thrash",
    "snapshot_corrupt",
    "decode_worker_kill",
    "slow_client",
    "malformed_request",
    "serve_burst_oom",
    "plan_mispredict",
    "spec_mispredict",
    "wire_disconnect",
    "slow_loris",
    "jpeg_corrupt_entropy",
    "profiler_crash",
    "output_drift",
    "mesh_shrink",
    "host_loss",
    "drift_refit",
    "native_entropy",
    "obs_capture",
)

#: The serving-path families (core.serve / core.frontend / core.wire),
#: selectable via ``tools/chaos_run.py --serve``.
SERVE_FAMILIES = (
    "slow_client",
    "malformed_request",
    "serve_burst_oom",
    "wire_disconnect",
    "slow_loris",
    "output_drift",
)

#: Seeds the tier-1 suite runs (small schedule, covers every family);
#: ``-m chaos`` / ``tools/chaos_run.py --full`` runs the full schedule.
TIER1_SEEDS = tuple(range(27))
FULL_SEEDS = tuple(range(54))

_DATA_SEED = 20260803  # fixed: the fault-free baseline is schedule-invariant
_N_TAR_IMAGES = 6
_N_STREAM_IMAGES = 10  # streaming-path tars (corrupt picked mid-stream)


class SimulatedPreemption(RuntimeError):
    """Injected mid-fit preemption (the chaos analog of a TPU VM being
    reclaimed between BCD blocks) — expected and consumed by the
    ``preempt_resume`` schedule, never a final outcome."""


class ChaosOracleError(AssertionError):
    """The resilience contract itself broke (wrong skip count, missing
    expected failure, survivors lost) — surfaces as a failed outcome."""


@dataclasses.dataclass
class Fault:
    kind: str
    params: dict

    def record(self) -> dict:
        return {"kind": self.kind, **self.params}


@dataclasses.dataclass
class ChaosResult:
    seed: int
    workload: str
    fault: Fault
    outcome: str  # completed_equal | typed_error | SILENT_WRONG_MODEL |
    #             UNTYPED_ERROR | ORACLE_FAILED
    error_type: str | None = None
    error: str | None = None
    phase: str | None = None
    counters_delta: dict = dataclasses.field(default_factory=dict)
    seconds: float = 0.0
    #: where this schedule's trace landed (run_schedule(trace_path=)) —
    #: the ONE place the per-schedule filename lives; verifiers read it
    #: from here instead of re-deriving the naming convention.
    trace_path: str | None = None

    def ok(self) -> bool:
        return self.outcome in ("completed_equal", "typed_error")

    def record(self) -> dict:
        return {
            "seed": self.seed,
            "workload": self.workload,
            "fault": self.fault.record(),
            "outcome": self.outcome,
            "error_type": self.error_type,
            "error": self.error[:200] if self.error else None,
            "phase": self.phase,
            "counters_delta": dict(self.counters_delta),
            "seconds": round(self.seconds, 3),
            "trace_path": self.trace_path,
        }


def make_schedule(seed: int) -> Fault:
    """seed -> fault schedule, deterministically: the family cycles so any
    contiguous seed range covers all of them, the parameters are drawn
    from ``default_rng(seed)``."""
    rng = np.random.default_rng(seed)
    kind = FAMILIES[seed % len(FAMILIES)]
    if kind == "solver_oom":
        return Fault(kind, {"failures": 1})
    if kind == "oom_cascade":
        return Fault(kind, {"failures": 2})
    if kind == "io_transient":
        return Fault(kind, {"io_failures": int(rng.integers(1, 3))})
    if kind == "corrupt_members":
        k = int(rng.integers(1, 4))
        corrupt = tuple(
            sorted(int(i) for i in rng.choice(_N_TAR_IMAGES, k, replace=False))
        )
        return Fault(kind, {"corrupt": corrupt})
    if kind == "nan_input":
        return Fault(kind, {"frac": float(rng.uniform(0.002, 0.02))})
    if kind == "preempt_resume":
        return Fault(kind, {"preempt_after_blocks": 1})
    if kind == "stream_corrupt":
        k = int(rng.integers(1, 3))
        corrupt = tuple(  # strictly mid-stream members
            sorted(
                int(i)
                for i in rng.choice(
                    np.arange(1, _N_STREAM_IMAGES - 1), k, replace=False
                )
            )
        )
        return Fault(kind, {"corrupt": corrupt, "batch": 4})
    if kind == "stream_hang":
        return Fault(
            kind,
            {"hang_at": int(rng.integers(1, 6)), "seconds": 0.8},
        )
    if kind == "autotune_thrash":
        return Fault(
            kind,
            {"batch": int(rng.integers(2, 5)), "period": int(rng.integers(1, 3))},
        )
    if kind == "snapshot_corrupt":
        return Fault(
            kind,
            {
                "batch": int(rng.integers(2, 5)),
                "shard": int(rng.integers(0, 4)),
                "corruption": ("truncate", "bitflip")[int(rng.integers(0, 2))],
            },
        )
    if kind == "decode_worker_kill":
        return Fault(kind, {"batch": 4, "procs": 2})
    if kind == "slow_client":
        return Fault(
            kind,
            {
                "slow_requests": int(rng.integers(2, 5)),
                "think_seconds": 0.05,
                "fast_requests": int(rng.integers(12, 25)),
            },
        )
    if kind == "malformed_request":
        return Fault(
            kind,
            {"bad": int(rng.integers(2, 5)), "good": int(rng.integers(8, 17))},
        )
    if kind == "serve_burst_oom":
        return Fault(
            kind,
            {"burst": int(rng.integers(9, 17)), "failures": 1},
        )
    if kind == "plan_mispredict":
        return Fault(kind, {"failures": 1})
    if kind == "spec_mispredict":
        return Fault(kind, {"failures": 1})
    if kind == "wire_disconnect":
        return Fault(
            kind,
            {"requests": int(rng.integers(6, 13)), "hold_seconds": 0.25},
        )
    if kind == "slow_loris":
        return Fault(
            kind,
            {"requests": int(rng.integers(6, 13)),
             "lorises": int(rng.integers(1, 3))},
        )
    if kind == "jpeg_corrupt_entropy":
        k = int(rng.integers(1, 3))
        corrupt = tuple(  # strictly mid-stream members
            sorted(
                int(i)
                for i in rng.choice(
                    np.arange(1, _N_STREAM_IMAGES - 1), k, replace=False
                )
            )
        )
        return Fault(
            kind,
            {
                "corrupt": corrupt,
                "batch": 4,
                "mode": ("truncate", "marker")[int(rng.integers(0, 2))],
            },
        )
    if kind == "native_entropy":
        k = int(rng.integers(1, 3))
        corrupt = tuple(  # strictly mid-stream members
            sorted(
                int(i)
                for i in rng.choice(
                    np.arange(1, _N_STREAM_IMAGES - 1), k, replace=False
                )
            )
        )
        return Fault(
            kind,
            {
                "corrupt": corrupt,
                "batch": 4,
                "mode": ("truncate", "marker")[int(rng.integers(0, 2))],
                # which decode_scan call the injected native failure hits —
                # <= 8 so it always lands inside the survivor stream
                # (>= _N_STREAM_IMAGES - 2 survivors)
                "fail_at": int(rng.integers(1, 9)),
            },
        )
    if kind == "profiler_crash":
        return Fault(
            kind,
            {"batch": 4, "crash_after": int(rng.integers(1, 5))},
        )
    if kind == "output_drift":
        return Fault(
            kind,
            {
                "reference": int(rng.integers(48, 81)),
                # Must clear numerics.DRIFT_MIN_COUNT with margin so the
                # monitor is allowed to judge the shifted mix.
                "shifted": int(rng.integers(48, 81)),
                "shift_scale": float(rng.uniform(4.0, 8.0)),
            },
        )
    if kind == "mesh_shrink":
        return Fault(
            kind,
            {
                "requests": int(rng.integers(6, 13)),
                # how much of the 4-device full mesh survives the loss
                "survivors": int(rng.integers(1, 3)),
                "hold_seconds": 0.25,
            },
        )
    if kind == "host_loss":
        return Fault(
            kind,
            {
                "hosts": 2,  # tools/chaos_run.py --hosts N overrides via env
                "requests": int(rng.integers(14, 25)),
            },
        )
    if kind == "obs_capture":
        return Fault(
            kind,
            {
                "hosts": 2,
                "requests": int(rng.integers(12, 21)),
            },
        )
    if kind == "drift_refit":
        return Fault(
            kind,
            {
                # fit-time reference + shifted-mix sizes both clear
                # numerics.DRIFT_MIN_COUNT with margin
                "reference": int(rng.integers(48, 81)),
                "shifted": int(rng.integers(48, 81)),
                "shift_scale": float(rng.uniform(4.0, 8.0)),
                # refit training rows (fresh post-shift world)
                "rows": int(rng.integers(96, 161)),
                # requests in flight across the hot-swap
                "requests": int(rng.integers(6, 13)),
                "hold_seconds": 0.2,
            },
        )
    return Fault("deadline", {"seconds": 1.0})


# -- workload cases -----------------------------------------------------------


def _mnist_case():
    rng = np.random.default_rng(_DATA_SEED)
    d, k = 64, 5
    centers = rng.normal(size=(k, d))

    def split(n):
        labels = rng.integers(0, k, n)
        data = (centers[labels] + 0.3 * rng.normal(size=(n, d))).astype(
            np.float32
        )
        return LabeledData(data=data, labels=labels.astype(np.int32))

    return split(160), split(80)


_mnist_data_cache: list = []


def _run_mnist(train_override=None, mesh=None, **conf_kw):
    from keystone_tpu.workloads.mnist_random_fft import (
        MnistRandomFFTConfig,
        run,
    )

    if not _mnist_data_cache:
        _mnist_data_cache.append(_mnist_case())
    train, test = _mnist_data_cache[0]
    if train_override is not None:
        train = train_override(train)
    conf = MnistRandomFFTConfig(
        num_ffts=2,
        block_size=512,
        lam=1e-2,
        mnist_image_size=64,
        num_classes=5,
        **conf_kw,
    )
    return run(conf, train, test, mesh=mesh)


_cifar_paths_cache: list = []


def _write_synthetic_cifar(path, n, rng, num_classes=4, base=None):
    """Class-colored blobs + noise in CIFAR binary record format."""
    from keystone_tpu.loaders.cifar import RECORD_BYTES

    labels = rng.integers(0, num_classes, n).astype(np.uint8)
    if base is None:
        base = rng.uniform(40, 215, (num_classes, 3))
    recs = np.zeros((n, RECORD_BYTES), np.uint8)
    yy, xx = np.mgrid[0:32, 0:32]
    del yy
    for i in range(n):
        img = base[labels[i]][:, None, None] + rng.normal(0, 25, (3, 32, 32))
        img[labels[i] % 3] += 30 * np.sin(xx / (2.0 + labels[i]))
        recs[i, 0] = labels[i]
        recs[i, 1:] = np.clip(img, 0, 255).astype(np.uint8).reshape(-1)
    recs.tofile(path)


def _run_cifar(train_override=None, mesh=None, **conf_kw):
    from keystone_tpu.workloads.cifar_random_patch import (
        RandomCifarConfig,
        run,
    )

    if not _cifar_paths_cache:
        d = tempfile.mkdtemp(prefix="chaos_cifar_")
        rng = np.random.default_rng(_DATA_SEED)
        palette = rng.uniform(40, 215, (4, 3))
        tr, te = os.path.join(d, "train.bin"), os.path.join(d, "test.bin")
        _write_synthetic_cifar(tr, 72, rng, base=palette)
        _write_synthetic_cifar(te, 36, rng, base=palette)
        _cifar_paths_cache.append((tr, te))
    tr, te = _cifar_paths_cache[0]
    conf = RandomCifarConfig(
        num_filters=8,
        patch_size=6,
        patch_steps=4,
        lam=10.0,
        whitener_size=300,
        featurize_chunk=36,
        num_classes=4,
        **conf_kw,
    )
    train, test = cifar_loader(tr), cifar_loader(te)
    if train_override is not None:
        train = train_override(train)
    return run(conf, train, test, mesh=mesh)


def _run_workload(workload: str, train_override=None, mesh=None, **conf_kw):
    if workload == "mnist":
        return _run_mnist(train_override=train_override, mesh=mesh, **conf_kw)
    if workload == "cifar":
        return _run_cifar(train_override=train_override, mesh=mesh, **conf_kw)
    raise ValueError(f"unknown chaos workload {workload!r}")


_spec_mesh_cache: list = []


def _spec_mesh():
    """The mesh the ``spec_mispredict`` family runs under: all live
    devices, (data, model=2) when the count divides — so the search's
    top-ranked plan is a real spec-executing GSPMD layout.  Cached: the
    baseline and every faulted run must fit on the SAME mesh for the
    bit-equality judgement to mean anything."""
    if not _spec_mesh_cache:
        import jax

        from keystone_tpu.parallel.mesh import make_mesh

        n = len(jax.devices())
        model = 2 if n >= 2 and n % 2 == 0 else 1
        _spec_mesh_cache.append(make_mesh(data=n // model, model=model))
    return _spec_mesh_cache[0]


_baselines: dict[tuple, dict] = {}


def baseline(workload: str, mesh: bool = False) -> dict:
    """The fault-free run every schedule is judged against (cached — one
    per workload per process; also pre-warms every jit cache so faulted
    runs measure fault handling, not compilation).  ``mesh=True``: the
    fault-free MESH run (the ``spec_mispredict`` oracle — a sharded
    faulted run must be judged against a sharded baseline)."""
    key = (workload, bool(mesh))
    if key not in _baselines:
        _baselines[key] = _run_workload(
            workload, mesh=_spec_mesh() if mesh else None
        )
    return _baselines[key]


def _preds_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(np.array_equal(a, b))


@contextlib.contextmanager
def _patched(obj, attr, replacement):
    original = getattr(obj, attr)
    setattr(obj, attr, replacement)
    try:
        yield
    finally:
        setattr(obj, attr, original)


@contextlib.contextmanager
def _clean_env():
    """Chaos runs start from the default resilience posture: no HBM budget
    override (ladders start at the fused tier), the numerics guard on, and
    the profiler OFF (profiler_crash enables it itself, scoped)."""
    saved = {
        k: os.environ.pop(k, None)
        for k in (
            kmem.HBM_BUDGET_ENV, "KEYSTONE_NUMERICS_GUARD",
            "KEYSTONE_PROFILER", "KEYSTONE_NUMERICS",
            "KEYSTONE_DRIFT_TOL", "KEYSTONE_POSTMORTEM_DIR",
        )
    }
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# -- the per-family drivers ---------------------------------------------------


def _ingest_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """The tar-ingest chaos phase (io_transient / corrupt_members): build a
    seeded JPEG tar (optionally with mangled members), stream-decode it
    under the injected faults, and hold the loader to its contract —
    every survivor decoded in order, every corrupt member a COUNTED skip."""
    rng = np.random.default_rng(seed)
    tar_path = os.path.join(tmpdir, f"chaos_ingest_{seed}.tar")
    corrupt = tuple(fault.params.get("corrupt", ()))
    names = faults.make_image_tar(
        tar_path, _N_TAR_IMAGES, rng, corrupt=corrupt
    )
    before_skip = counters.get("corrupt_image")
    before_retry = counters.get("io_retry")
    io_failures = int(fault.params.get("io_failures", 0))
    ctx = (
        faults.transient_faults(image_loaders.tarfile, "open", io_failures)
        if io_failures
        else contextlib.nullcontext()
    )
    with ctx:
        decoded = [
            name
            for name, _img in image_loaders._iter_tar_images(
                tar_path, num_threads=1
            )
        ]
    survivors = [n for i, n in enumerate(names) if i not in corrupt]
    if decoded != survivors:
        raise ChaosOracleError(
            f"ingest lost data: decoded {decoded} != survivors {survivors}"
        )
    skipped = counters.get("corrupt_image") - before_skip
    if skipped != len(corrupt):
        raise ChaosOracleError(
            f"{len(corrupt)} corrupt member(s) but {skipped} counted skips — "
            "a corrupt member was swallowed uncounted"
        )
    if io_failures and counters.get("io_retry") - before_retry < io_failures:
        raise ChaosOracleError(
            f"{io_failures} injected open failure(s) but fewer io_retry "
            "counts — a transient fault was absorbed invisibly"
        )


def _stream_featurize(tar_path: str, batch: int, config=None, tuner=None):
    """The streaming-path probe pipeline: core.ingest stream -> per-image
    device featurize -> scatter back to stream order (the real consumer
    API, fv_common.scatter_features_streaming)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.workloads.fv_common import scatter_features_streaming

    feat = jax.jit(
        lambda x: jnp.stack(
            [jnp.mean(x, axis=(1, 2, 3)), jnp.max(x, axis=(1, 2, 3))], axis=1
        )
    )
    with ingest.stream_batches(
        tar_path, batch, config=config, tuner=tuner
    ) as st:
        feats, names = scatter_features_streaming(st, feat, 2)
    if not st.join(10.0):
        raise ChaosOracleError(
            "streaming ingest left decoder/producer threads alive"
        )
    return feats, names


def _stream_corrupt_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Corrupt member mid-stream: the streaming path must count the skip
    and produce features BIT-IDENTICAL to a fault-free stream over the
    surviving images (tar rebuilt from the same member bytes)."""
    rng = np.random.default_rng(seed)
    corrupt = tuple(fault.params["corrupt"])
    batch = int(fault.params["batch"])
    tar_bad = os.path.join(tmpdir, f"chaos_stream_{seed}.tar")
    names = faults.make_image_tar(
        tar_bad, _N_STREAM_IMAGES, rng, corrupt=corrupt
    )
    survivors = {n for i, n in enumerate(names) if i not in corrupt}
    # The fault-free oracle tar: the SAME member bytes minus the corrupt
    # ones, so decoded survivors are identical by construction.
    tar_ok = os.path.join(tmpdir, f"chaos_stream_{seed}_ok.tar")
    with tarfile.open(tar_bad) as src, tarfile.open(tar_ok, "w") as dst:
        for m in src:
            if m.name in survivors:
                dst.addfile(m, src.extractfile(m))

    before = counters.get("corrupt_image")
    faulted_feats, faulted_names = _stream_featurize(tar_bad, batch)
    skipped = counters.get("corrupt_image") - before
    if skipped != len(corrupt):
        raise ChaosOracleError(
            f"{len(corrupt)} corrupt member(s) but {skipped} counted skips "
            "on the streaming path — a corrupt member was swallowed "
            "uncounted"
        )
    clean_feats, clean_names = _stream_featurize(tar_ok, batch)
    if faulted_names != clean_names:
        raise ChaosOracleError(
            f"streaming ingest lost data: {faulted_names} != {clean_names}"
        )
    if not np.array_equal(faulted_feats, clean_feats):
        raise ChaosOracleError(
            "streamed features under a corrupt member differ from the "
            "fault-free stream on the surviving images"
        )


def _jpeg_corrupt_entropy_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Damaged entropy-coded scan mid-batch under DEVICE decode
    (ops.jpeg_device): headers parse, so the member reaches the entropy
    decoder and must die there as a typed, COUNTED skip
    (``jpeg_corrupt_entropy``) — the rest of the batch survives and the
    streamed features equal a fault-free device-decode stream over the
    surviving members bit-for-bit (both passes decode on-device, so
    bit-equality is exact, not tolerance)."""
    rng = np.random.default_rng(seed)
    corrupt = tuple(fault.params["corrupt"])
    batch = int(fault.params["batch"])
    mode = fault.params["mode"]
    tar_bad = os.path.join(tmpdir, f"chaos_jpeg_{seed}.tar")
    names = faults.make_image_tar(
        tar_bad, _N_STREAM_IMAGES, rng, corrupt=corrupt,
        corrupt_fn=lambda data: faults.corrupt_jpeg_entropy(data, mode),
    )
    survivors = {n for i, n in enumerate(names) if i not in corrupt}
    tar_ok = os.path.join(tmpdir, f"chaos_jpeg_{seed}_ok.tar")
    with tarfile.open(tar_bad) as src, tarfile.open(tar_ok, "w") as dst:
        for m in src:
            if m.name in survivors:
                dst.addfile(m, src.extractfile(m))

    def device_cfg():
        # snapshot pinned OFF: an ambient KEYSTONE_SNAPSHOT_DIR would turn
        # the device-decode probe into a shard-read pass with no entropy
        # decode to corrupt.
        return ingest.StreamConfig.from_env(
            decode_mode="device", snapshot_dir=""
        )

    before = counters.get("jpeg_corrupt_entropy")
    faulted_feats, faulted_names = _stream_featurize(
        tar_bad, batch, config=device_cfg()
    )
    skipped = counters.get("jpeg_corrupt_entropy") - before
    if skipped != len(corrupt):
        raise ChaosOracleError(
            f"{len(corrupt)} entropy-corrupt member(s) but {skipped} "
            "counted jpeg_corrupt_entropy skips — a damaged scan was "
            "swallowed uncounted (or decoded into silent wrong pixels)"
        )
    clean_feats, clean_names = _stream_featurize(
        tar_ok, batch, config=device_cfg()
    )
    if faulted_names != clean_names:
        raise ChaosOracleError(
            "device-decode stream lost data under entropy corruption: "
            f"{faulted_names} != {clean_names}"
        )
    if not np.array_equal(faulted_feats, clean_feats):
        raise ChaosOracleError(
            "device-decoded features under entropy corruption differ "
            "from the fault-free device stream on the surviving images"
        )


def _native_entropy_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """The native entropy backend (ops.native_entropy) held to the
    backend-indistinguishability bar, in two legs:

    1. a corrupt-scan member through the NATIVE-preferred device stream
       is the same typed counted skip (``jpeg_corrupt_entropy``) and the
       survivors are BIT-equal to a fault-free FORCED-PYTHON stream —
       the portable baseline every backend must bit-match;
    2. an UNEXPECTED native failure mid-stream (decode_scan raises on
       call ``fail_at``) degrades that one image to the Python pass
       counted ``native_entropy_fallback`` with the stream still
       bit-equal — never a crash.

    Both legs inject at the ``native_entropy.decode_scan`` boundary the
    dispatch resolves at call time, so the family exercises the
    degradation contract even on hosts where the library cannot build
    (there decode_scan returns False and leg 1 runs the Python pass —
    still bit-equal by definition)."""
    from keystone_tpu.ops import native_entropy as ne

    rng = np.random.default_rng(seed)
    corrupt = tuple(fault.params["corrupt"])
    batch = int(fault.params["batch"])
    mode = fault.params["mode"]
    fail_at = int(fault.params["fail_at"])
    tar_bad = os.path.join(tmpdir, f"chaos_native_{seed}.tar")
    names = faults.make_image_tar(
        tar_bad, _N_STREAM_IMAGES, rng, corrupt=corrupt,
        corrupt_fn=lambda data: faults.corrupt_jpeg_entropy(data, mode),
    )
    survivors = {n for i, n in enumerate(names) if i not in corrupt}
    tar_ok = os.path.join(tmpdir, f"chaos_native_{seed}_ok.tar")
    with tarfile.open(tar_bad) as src, tarfile.open(tar_ok, "w") as dst:
        for m in src:
            if m.name in survivors:
                dst.addfile(m, src.extractfile(m))

    def device_cfg():
        # snapshot pinned OFF (see _jpeg_corrupt_entropy_phase)
        return ingest.StreamConfig.from_env(
            decode_mode="device", snapshot_dir=""
        )

    # KEYSTONE_NATIVE_ENTROPY is managed per leg (not in _clean_env's
    # fixed key list): "0" pins the Python oracle, unset prefers native.
    saved_env = os.environ.pop(ne.NATIVE_ENTROPY_ENV, None)
    try:
        os.environ[ne.NATIVE_ENTROPY_ENV] = "0"
        clean_feats, clean_names = _stream_featurize(
            tar_ok, batch, config=device_cfg()
        )
        del os.environ[ne.NATIVE_ENTROPY_ENV]

        # -- leg 1: corrupt scan through the native-preferred stream ----
        before = counters.get("jpeg_corrupt_entropy")
        faulted_feats, faulted_names = _stream_featurize(
            tar_bad, batch, config=device_cfg()
        )
        skipped = counters.get("jpeg_corrupt_entropy") - before
        if skipped != len(corrupt):
            raise ChaosOracleError(
                f"{len(corrupt)} entropy-corrupt member(s) but {skipped} "
                "counted jpeg_corrupt_entropy skips through the native "
                "backend — a damaged scan was swallowed uncounted (or "
                "classified differently than the Python pass)"
            )
        if faulted_names != clean_names:
            raise ChaosOracleError(
                "native-backend stream lost data under entropy "
                f"corruption: {faulted_names} != {clean_names}"
            )
        if not np.array_equal(faulted_feats, clean_feats):
            raise ChaosOracleError(
                "native-backend features differ from the forced-Python "
                "stream on the surviving images — the backends are "
                "distinguishable"
            )

        # -- leg 2: forced native failure mid-stream --------------------
        calls = [0]
        orig = ne.decode_scan

        def flaky(*args, **kwargs):
            calls[0] += 1
            if calls[0] == fail_at:
                raise RuntimeError("chaos: injected native entropy failure")
            return orig(*args, **kwargs)

        before_fb = counters.get("native_entropy_fallback")
        with _patched(ne, "decode_scan", flaky):
            leg2_feats, leg2_names = _stream_featurize(
                tar_ok, batch, config=device_cfg()
            )
        fell_back = counters.get("native_entropy_fallback") - before_fb
        if fell_back < 1:
            raise ChaosOracleError(
                "injected native entropy failure was not counted "
                "native_entropy_fallback — it was swallowed silently "
                f"(decode_scan called {calls[0]} time(s), fail_at "
                f"{fail_at})"
            )
        if leg2_names != clean_names:
            raise ChaosOracleError(
                "stream lost data across a per-image native->Python "
                f"degradation: {leg2_names} != {clean_names}"
            )
        if not np.array_equal(leg2_feats, clean_feats):
            raise ChaosOracleError(
                "features differ after a per-image native->Python "
                "degradation — the fallback image was not re-decoded "
                "cleanly"
            )
    finally:
        if saved_env is None:
            os.environ.pop(ne.NATIVE_ENTROPY_ENV, None)
        else:
            os.environ[ne.NATIVE_ENTROPY_ENV] = saved_env


def _profiler_crash_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """The HBM watermark sampler thread (core.profiler) dies MID-RUN from
    an injected stats failure: the crash must be a counted degradation
    (``profiler_sampler_crash``), the profiled run must COMPLETE, and its
    streamed features must be bit-equal to an unprofiled run — a dead
    observability thread may cost telemetry, never correctness."""
    from keystone_tpu.core import profiler as kprof

    rng = np.random.default_rng(seed)
    batch = int(fault.params["batch"])
    crash_after = int(fault.params["crash_after"])
    tar_path = os.path.join(tmpdir, f"chaos_prof_{seed}.tar")
    faults.make_image_tar(tar_path, _N_STREAM_IMAGES, rng)

    # The unprofiled oracle (the default posture: profiler off).
    base_feats, base_names = _stream_featurize(tar_path, batch)

    calls = {"n": 0}

    def crashing_stats():
        calls["n"] += 1
        if calls["n"] > crash_after:
            raise RuntimeError("injected HBM sampler crash")
        return 123 * 2**20  # a plausible bytes-in-use figure until then

    before = counters.get("profiler_sampler_crash")
    kprof.reset_state()
    try:
        with kprof.profiled(
            True, interval_ms=1.0, stats_fn=crashing_stats
        ):
            feats, names = _stream_featurize(tar_path, batch)
            # The thread polls every 1ms — wait (bounded) for the injected
            # crash to land so the count below is deterministic.
            s = kprof.sampler()
            end = time.monotonic() + 5.0
            while (
                s is not None and not s.crashed and time.monotonic() < end
            ):
                time.sleep(0.01)
    finally:
        kprof.reset_state()
    crashed = counters.get("profiler_sampler_crash") - before
    if crashed != 1:
        raise ChaosOracleError(
            f"sampler crash injected but {crashed} counted "
            "profiler_sampler_crash — a dead profiler thread went "
            "unnoticed (or died more than once)"
        )
    if names != base_names:
        raise ChaosOracleError(
            "profiled stream lost data under a sampler crash: "
            f"{names} != {base_names}"
        )
    if not np.array_equal(feats, base_feats):
        raise ChaosOracleError(
            "profiled features differ from the unprofiled run — the "
            "cost-attribution layer changed the answer"
        )


def _stream_hang_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Injected decoder-thread hang: the consumer's resilience.deadline
    must convert it into a typed DeadlineExceeded — the ring must never
    deadlock.  Raises (the schedule's expected outcome is typed_error)."""
    rng = np.random.default_rng(seed)
    tar_path = os.path.join(tmpdir, f"chaos_hang_{seed}.tar")
    faults.make_image_tar(tar_path, _N_STREAM_IMAGES, rng)
    budget = float(fault.params["seconds"])
    hang_at = int(fault.params["hang_at"])
    calls = {"n": 0}
    real = image_loaders.decode_image

    def hanging(data):
        calls["n"] += 1
        if calls["n"] == hang_at:
            time.sleep(4.0 * budget)  # outlives the watchdog budget
        return real(data)

    # The patch must be live BEFORE the stream constructs: the producer
    # thread starts submitting decode_image calls immediately, and a
    # late patch could race past the hang_at'th decode entirely.
    st = None
    try:
        with _patched(image_loaders, "decode_image", hanging):
            st = ingest.stream_batches(tar_path, 4, num_threads=2)
            with deadline(budget, phase="ingest"):
                for batch in st:
                    np.asarray(batch.host)
    finally:
        if st is not None:
            st.close()
    raise ChaosOracleError(
        "hung decoder thread did not trip the ingest deadline — the "
        "stream completed (or deadlocked silently)"
    )


class _ThrashTuner:
    """Adversarial autotuner: flip EVERY ingest knob between its extremes
    every ``period`` chunks — the worst-case retune schedule a closed-loop
    controller could emit.  The typed-or-equal invariant says knob motion
    may change speed, never results."""

    def __init__(self, period: int):
        self._period = max(1, period)
        self._chunks = 0
        self._cfg = None
        self.retunes = 0

    def attach(self, stream) -> None:
        self._cfg = stream.config

    def on_chunk(self, stream) -> None:
        self._chunks += 1
        if self._chunks % self._period:
            return
        cfg = self._cfg
        wide = cfg.decode_threads == 1
        cfg.decode_threads = cfg.max_decode_threads if wide else 1
        cfg.decode_ahead = 8 if wide else 0
        cfg.ring_capacity = 8 if wide else 1
        self.retunes += 1


def _autotune_thrash_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Oscillating mid-stream retunes: features must stay BIT-IDENTICAL to
    a static-knob stream over the same tar, with every retune observed and
    every thread joined."""
    rng = np.random.default_rng(seed)
    tar_path = os.path.join(tmpdir, f"chaos_thrash_{seed}.tar")
    faults.make_image_tar(tar_path, _N_STREAM_IMAGES, rng)
    batch = int(fault.params["batch"])
    static_feats, static_names = _stream_featurize(tar_path, batch)

    tuner = _ThrashTuner(int(fault.params["period"]))
    cfg = ingest.StreamConfig(
        decode_threads=1, decode_ahead=0, ring_capacity=1,
        max_decode_threads=4,
    )
    thrash_feats, thrash_names = _stream_featurize(
        tar_path, batch, config=cfg, tuner=tuner
    )
    if tuner.retunes < 1:
        raise ChaosOracleError(
            "thrash tuner never retuned — the oscillation schedule did not "
            "exercise mid-stream reconfiguration"
        )
    if thrash_names != static_names:
        raise ChaosOracleError(
            "retuned stream lost/reordered data: "
            f"{thrash_names} != {static_names}"
        )
    if not np.array_equal(thrash_feats, static_feats):
        raise ChaosOracleError(
            "streamed features under knob thrash differ from the "
            "static-knob stream — retuning changed RESULTS, not just speed"
        )
    counters.record(
        "chaos_autotune_thrash",
        f"seed {seed}: {tuner.retunes} oscillating retune(s), output "
        "bit-equal",
    )


def _snapshot_corrupt_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Corrupt snapshot shard (core.snapshot): a cold pass materializes the
    decoded chunks, one shard is truncated/bit-flipped, and the warm pass
    must fall back to live decode COUNTED (``snapshot_fallback``) with
    features bit-equal to the fault-free pass — never silently stale
    pixels."""
    import glob as _glob

    from keystone_tpu.core import snapshot as ksnap

    rng = np.random.default_rng(seed)
    tar_path = os.path.join(tmpdir, f"chaos_snap_{seed}.tar")
    faults.make_image_tar(tar_path, _N_STREAM_IMAGES, rng)
    snap_root = os.path.join(tmpdir, f"chaos_snap_{seed}_cache")
    batch = int(fault.params["batch"])

    def cfg():
        # snapshot_mode pinned: an ambient KEYSTONE_SNAPSHOT_MODE=featurized
        # would stop the ingest tee from committing a decoded snapshot and
        # fail the family with nothing to corrupt.
        return ingest.StreamConfig.from_env(
            snapshot_dir=snap_root, snapshot_mode="decoded"
        )

    clean_feats, clean_names = _stream_featurize(tar_path, batch, config=cfg())
    committed = [
        s for s in ksnap.list_snapshots(snap_root) if s.get("valid")
    ]
    if not committed:
        raise ChaosOracleError(
            "cold snapshot pass committed no snapshot — the corruption "
            "schedule has nothing to corrupt"
        )
    shards = sorted(
        _glob.glob(
            os.path.join(snap_root, committed[0]["dir"], "chunk_*.npz")
        )
    )
    if not shards:
        raise ChaosOracleError("committed snapshot holds no shards")
    target = shards[int(fault.params["shard"]) % len(shards)]
    with open(target, "rb") as fh:
        data = bytearray(fh.read())
    if fault.params["corruption"] == "truncate":
        data = data[: max(1, len(data) // 2)]
    else:
        data[len(data) // 3] ^= 0xFF
    with open(target, "wb") as fh:
        fh.write(bytes(data))

    before = counters.get("snapshot_fallback")
    faulted_feats, faulted_names = _stream_featurize(
        tar_path, batch, config=cfg()
    )
    if counters.get("snapshot_fallback") - before < 1:
        raise ChaosOracleError(
            "corrupt snapshot shard produced no counted snapshot_fallback "
            "— the reader either served corrupt bytes or fell back "
            "invisibly"
        )
    if faulted_names != clean_names:
        raise ChaosOracleError(
            "snapshot fallback lost/reordered data: "
            f"{faulted_names} != {clean_names}"
        )
    if not np.array_equal(faulted_feats, clean_feats):
        raise ChaosOracleError(
            "features under a corrupt snapshot shard differ from live "
            "decode — the fallback is not bit-equal"
        )


def _decode_worker_kill_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """SIGKILL a process-backend decode worker mid-stream: the pool must
    respawn it (counted ``decode_worker_respawn``), resubmit its pending
    members, and finish with features bit-equal to the thread-path oracle
    — never a hung ring."""
    import signal

    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    tar_path = os.path.join(tmpdir, f"chaos_kill_{seed}.tar")
    faults.make_image_tar(tar_path, _N_STREAM_IMAGES + 6, rng)
    batch = int(fault.params["batch"])
    clean_feats, clean_names = _stream_featurize(tar_path, batch)

    feat = jax.jit(
        lambda x: jnp.stack(
            [jnp.mean(x, axis=(1, 2, 3)), jnp.max(x, axis=(1, 2, 3))], axis=1
        )
    )
    cfg = ingest.StreamConfig(
        decode_threads=2, decode_ahead=2, ring_capacity=1,
        decode_backend="process", decode_procs=int(fault.params["procs"]),
    )
    before = counters.get("decode_worker_respawn")
    parts, name_pairs, n = [], [], 0
    killed = False
    st = ingest.stream_batches(tar_path, batch, config=cfg)
    try:
        for b in st:
            if not killed:
                pool = st._proc_pool
                if pool is None:
                    raise ChaosOracleError(
                        "process backend configured but no decode pool "
                        "spun up — the kill schedule has no target"
                    )
                live = [w for w in pool._workers if w.proc.is_alive()]
                if live:
                    os.kill(live[0].proc.pid, signal.SIGKILL)
                    killed = True
            parts.append((b.indices, np.asarray(feat(b.dev()))))
            name_pairs.extend(zip(b.indices.tolist(), b.names))
            n += len(b)
    finally:
        st.close()
    if not st.join(20.0):
        raise ChaosOracleError(
            "worker-kill stream left decode threads/processes alive"
        )
    if not killed:
        raise ChaosOracleError(
            "no live decode worker to kill — the schedule never exercised "
            "the crash path"
        )
    if counters.get("decode_worker_respawn") - before < 1:
        raise ChaosOracleError(
            "killed decode worker was never respawned-and-counted"
        )
    from keystone_tpu.workloads.fv_common import _scatter_parts

    feats, names = _scatter_parts(parts, name_pairs, n)
    if names != clean_names:
        raise ChaosOracleError(
            f"worker kill lost/reordered data: {names} != {clean_names}"
        )
    if not np.array_equal(feats, clean_feats):
        raise ChaosOracleError(
            "features under a worker kill differ from the thread-path "
            "oracle — process decode is not bit-equal after respawn"
        )
    counters.record(
        "chaos_decode_worker_kill",
        f"seed {seed}: worker killed, respawned, stream bit-equal",
    )


# -- the serving-path phases (core.serve) -------------------------------------


def _serve_engine(buckets=(1, 2, 4)):
    """A tiny deterministic warm endpoint: fixed-weight row-wise pipeline,
    parity-verified per-bucket AOT executables.  Weights are seeded from
    the schedule-invariant data seed so the offline oracle is stable."""
    import jax.numpy as jnp

    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.pipeline import FunctionTransformer

    rng = np.random.default_rng(_DATA_SEED)
    # Fusion-invariant arithmetic (one exactly-rounded multiply + max, no
    # fma/gemv rounding variance): eager == jit == every bucket on every
    # backend, so the phases' offline-oracle equality checks test the
    # BATCHER's behavior, not XLA's rounding moods.
    w = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))

    pipe = FunctionTransformer(
        lambda x: jnp.maximum(x * w, b), name="chaos_serve"
    )
    cfg = kserve.ServeConfig(buckets=tuple(buckets), max_wait_ms=2.0)
    return kserve.ServingEngine(
        pipe, np.zeros(16, np.float32), config=cfg, label="chaos"
    )


def _serve_requests(rng, n: int) -> np.ndarray:
    return rng.normal(size=(n, 16)).astype(np.float32)


def _slow_client_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """One trickling client + one hammering client on the same endpoint:
    the deadline/idle flush must answer the fast client without waiting
    for buckets the slow client never fills — every answer bit-equal."""
    import threading

    from keystone_tpu.core import serve as kserve

    rng = np.random.default_rng(seed)
    engine = _serve_engine()
    n_slow = int(fault.params["slow_requests"])
    n_fast = int(fault.params["fast_requests"])
    think = float(fault.params["think_seconds"])
    slow_reqs = _serve_requests(rng, n_slow)
    fast_reqs = _serve_requests(rng, n_fast)
    slow_ans = [None] * n_slow
    fast_ans = [None] * n_fast
    errors: list = []

    with kserve.Server(engine) as server:

        def slow():
            try:
                for i, r in enumerate(slow_reqs):
                    slow_ans[i] = server.submit(r).result(30.0)
                    time.sleep(think)  # the think time: a slow client
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        def fast():
            try:
                futs = [server.submit(r) for r in fast_reqs]
                for i, f in enumerate(futs):
                    fast_ans[i] = f.result(30.0)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        ts = [threading.Thread(target=slow), threading.Thread(target=fast)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60.0)
        stats = server.stats
    if errors:
        raise errors[0]
    if not np.array_equal(np.stack(slow_ans), engine.offline(slow_reqs)):
        raise ChaosOracleError(
            "slow client's answers differ from the offline apply"
        )
    if not np.array_equal(np.stack(fast_ans), engine.offline(fast_reqs)):
        raise ChaosOracleError(
            "fast client's answers differ from the offline apply — a slow "
            "batchmate changed RESULTS, not just latency"
        )
    if stats.answered != n_slow + n_fast:
        raise ChaosOracleError(
            f"{stats.answered} answered != {n_slow + n_fast} submitted"
        )
    # The trickle must have been answered by deadline/idle flushes (a
    # strict full-bucket batcher would stall the slow client forever).
    if stats.flush_deadline + stats.flush_idle < 1:
        raise ChaosOracleError(
            "no deadline/idle flush fired — the slow client was only "
            "answered because the fast client happened to fill buckets"
        )
    counters.record(
        "chaos_slow_client",
        f"seed {seed}: {n_slow} trickled + {n_fast} hammered requests "
        "answered bit-equal",
    )


def _malformed_request_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Malformed payloads interleaved with good requests: each dies TYPED
    at submit (counted serve_malformed_request), no batchmate poisoned."""
    from keystone_tpu.core import serve as kserve

    rng = np.random.default_rng(seed)
    engine = _serve_engine()
    n_bad = int(fault.params["bad"])
    n_good = int(fault.params["good"])
    good = _serve_requests(rng, n_good)
    bad_payloads = []
    for i in range(n_bad):
        kind = i % 3
        if kind == 0:  # wrong shape
            bad_payloads.append(np.zeros(7, np.float32))
        elif kind == 1:  # NaN-poisoned
            r = _serve_requests(rng, 1)[0]
            r[int(rng.integers(0, 16))] = np.nan
            bad_payloads.append(r)
        else:  # uncastable dtype
            bad_payloads.append(np.array(["x"] * 16, dtype=object))

    before = counters.get("serve_malformed_request")
    rejected = 0
    with kserve.Server(engine) as server:
        futs = []
        for j in range(n_good + n_bad):
            if j % 2 == 0 and j // 2 < n_bad:
                try:
                    server.submit(bad_payloads[j // 2])
                except kserve.MalformedRequest:
                    rejected += 1
                else:
                    raise ChaosOracleError(
                        "malformed request was ACCEPTED into the queue"
                    )
            if j < n_good:
                futs.append(server.submit(good[j]))
        answers = np.stack([f.result(30.0) for f in futs])
    if rejected != n_bad:
        raise ChaosOracleError(
            f"{n_bad} malformed payloads but {rejected} typed rejections"
        )
    if counters.get("serve_malformed_request") - before != n_bad:
        raise ChaosOracleError(
            "malformed rejections were not all counted "
            "(serve_malformed_request delta != injected)"
        )
    if not np.array_equal(answers, engine.offline(good)):
        raise ChaosOracleError(
            "good requests' answers differ from the offline apply — a "
            "malformed batchmate poisoned the batch"
        )


def _serve_burst_oom_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """RESOURCE_EXHAUSTED on the largest bucket under a burst: the engine
    must retire the bucket (counted serve_burst_oom), re-answer the same
    requests through smaller buckets, and stay bit-equal — the endpoint
    degrades, it never dies and never serves a wrong answer."""
    from keystone_tpu.core import serve as kserve

    rng = np.random.default_rng(seed)
    engine = _serve_engine(buckets=(1, 2, 4))
    burst = int(fault.params["burst"])
    failures = int(fault.params["failures"])
    top = engine.buckets()[-1]
    real_execute = engine._execute
    state = {"n": 0}

    def failing_execute(bucket, dev_batch):
        if bucket == top and state["n"] < failures:
            state["n"] += 1
            raise faults.resource_exhausted_error()
        return real_execute(bucket, dev_batch)

    requests = _serve_requests(rng, burst)
    before = counters.get("serve_burst_oom")
    engine._execute = failing_execute
    try:
        with kserve.Server(engine) as server:
            futs = [server.submit(r) for r in requests]
            answers = np.stack([f.result(30.0) for f in futs])
    finally:
        engine._execute = real_execute
    if state["n"] < failures:
        raise ChaosOracleError(
            "the burst never dispatched the largest bucket — the OOM "
            "schedule did not exercise the degradation path"
        )
    if counters.get("serve_burst_oom") - before < 1:
        raise ChaosOracleError(
            "bucket OOM was not counted under serve_burst_oom"
        )
    if top in engine.buckets():
        raise ChaosOracleError(
            f"bucket {top} survived its RESOURCE_EXHAUSTED — it must be "
            "retired, not retried in place"
        )
    if not np.array_equal(answers, engine.offline(requests)):
        raise ChaosOracleError(
            "answers under burst OOM differ from the offline apply — "
            "degradation changed RESULTS, not just batch shape"
        )


def _wire_disconnect_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """A wire client vanishes mid-batch: the disconnect must be COUNTED
    (``wire_client_disconnect``), every request it submitted must still
    ride its micro-batch to completion (futures resolve; batchmates are
    never poisoned), and a concurrent surviving client must get every
    answer bit-equal to the offline apply."""
    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import wire as kwire

    rng = np.random.default_rng(seed)
    engine = _serve_engine()
    n = int(fault.params["requests"])
    hold = float(fault.params["hold_seconds"])
    reqs_a = _serve_requests(rng, n)
    reqs_b = _serve_requests(rng, n)
    real_execute = engine._execute

    def slow_execute(bucket, dev_batch):
        # Stretch the batch so the disconnect demonstrably lands while
        # requests are IN FLIGHT (EOF with a full window, not after it).
        time.sleep(hold)
        return real_execute(bucket, dev_batch)

    before = counters.get("wire_client_disconnect")
    router = kfrontend.ShapeRouter(label=f"chaos_wire_{seed}")
    server_ref = None
    try:
        key = router.add_engine(engine)
        server_ref = router.server_for(key)
        engine._execute = slow_execute
        with kwire.WireServer(router, port=0, label="chaos") as ws:
            victim = kwire.WireClient(port=ws.port)
            for r in reqs_a:
                victim.submit(r)
            victim.close()  # mid-batch: the first micro-batch is still held
            with kwire.WireClient(port=ws.port) as survivor:
                answers = np.stack(
                    survivor.predict_many(list(reqs_b), window=8, timeout=60.0)
                )
        engine._execute = real_execute
        if not server_ref.drain(30.0):
            raise ChaosOracleError(
                "serve futures did not drain after the disconnect — the "
                "victim's batch never completed"
            )
    finally:
        engine._execute = real_execute
        router.close()
    if counters.get("wire_client_disconnect") - before < 1:
        raise ChaosOracleError(
            "a client vanished with requests in flight but no "
            "wire_client_disconnect was counted"
        )
    if not np.array_equal(answers, engine.offline(reqs_b)):
        raise ChaosOracleError(
            "the surviving client's answers differ from the offline apply "
            "— a dead batchmate changed RESULTS, not just who gets bytes"
        )
    st = server_ref.stats
    if st.answered != 2 * n or st.failed != 0:
        raise ChaosOracleError(
            f"batch completion broke under the disconnect: answered "
            f"{st.answered} / failed {st.failed}, expected {2 * n} / 0"
        )


def _slow_loris_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Slow-loris clients trickle partial frames and stall: each must park
    only its OWN connection's reader — the accept loop keeps accepting and
    concurrent honest clients are answered bit-equal and timely."""
    import socket as _socket
    import threading

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import wire as kwire

    rng = np.random.default_rng(seed)
    engine = _serve_engine()
    n = int(fault.params["requests"])
    lorises = int(fault.params["lorises"])
    reqs = [_serve_requests(rng, n), _serve_requests(rng, n)]
    answers: dict = {}
    errors: list = []

    router = kfrontend.ShapeRouter(label=f"chaos_loris_{seed}")
    try:
        router.add_engine(engine)
        with kwire.WireServer(router, port=0, label="chaos") as ws:
            stuck = []
            for i in range(lorises):
                s = _socket.create_connection(("127.0.0.1", ws.port), 5.0)
                if i % 2 == 0:
                    s.sendall(b"\x00\x00")  # half a length prefix
                else:
                    # a declared 64-byte payload with ONE byte delivered
                    s.sendall(kwire._LEN.pack(64) + b"\x01")
                stuck.append(s)
            time.sleep(0.1)  # the loris frames reach the readers first

            def good_client(cid):
                try:
                    with kwire.WireClient(port=ws.port) as c:
                        answers[cid] = np.stack(
                            c.predict_many(
                                list(reqs[cid]), window=8, timeout=30.0
                            )
                        )
                except BaseException as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)

            t0 = time.monotonic()
            ts = [
                threading.Thread(target=good_client, args=(c,))
                for c in range(2)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60.0)
            elapsed = time.monotonic() - t0
            # The accept loop must still be accepting WHILE the lorises
            # hold their sockets open mid-frame.
            with kwire.WireClient(port=ws.port) as probe:
                probe.ping()
            for s in stuck:
                s.close()
    finally:
        router.close()
    if errors:
        raise errors[0]
    if elapsed > 30.0:
        raise ChaosOracleError(
            f"honest clients took {elapsed:.1f}s behind {lorises} "
            "slow-loris connection(s) — partial frames starved the service"
        )
    for cid in range(2):
        if not np.array_equal(answers[cid], engine.offline(reqs[cid])):
            raise ChaosOracleError(
                f"client {cid}'s answers differ from the offline apply "
                "under slow-loris load"
            )
    counters.record(
        "chaos_slow_loris",
        f"seed {seed}: {lorises} stalled partial-frame connection(s), "
        f"2x{n} honest requests answered bit-equal in {elapsed:.2f}s",
    )


def _output_drift_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """A deterministically shifted request mix against a served classifier
    engine whose output-drift monitor (core.numerics) is armed with a
    fit-time baseline: the divergence must be COUNTED
    (``serve_output_drift``) with a flight-recorder postmortem dumped, and
    every answer must stay bit-equal to an UNMONITORED engine serving the
    same mix — the observatory detects, it never alters an answer."""
    import glob as _glob

    import jax.numpy as jnp

    from keystone_tpu.core import numerics as knum
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core import telemetry as ktelemetry
    from keystone_tpu.core.pipeline import FunctionTransformer

    rng = np.random.default_rng(seed)
    n_ref = int(fault.params["reference"])
    n_shift = int(fault.params["shifted"])
    scale = float(fault.params["shift_scale"])

    # A classifier head built from fusion-invariant arithmetic (exactly-
    # rounded multiply + max, like _serve_engine) so eager == jit == every
    # bucket and the bit-equality oracle tests the MONITOR, not XLA's
    # rounding moods.  Weights are schedule-invariant.
    wrng = np.random.default_rng(_DATA_SEED)
    w_np = wrng.normal(size=(16,)).astype(np.float32)
    b_np = wrng.normal(size=(16,)).astype(np.float32)
    w, b = jnp.asarray(w_np), jnp.asarray(b_np)
    pipe = FunctionTransformer(
        lambda x: jnp.argmax(jnp.maximum(x * w, b), axis=-1),
        name="chaos_drift_head",
    )
    cfg = kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0)
    engine = kserve.ServingEngine(
        pipe, np.zeros(16, np.float32), config=cfg, label="chaos_drift"
    )

    # The fit-time reference: the engine's own offline answers over an
    # unshifted request population.
    ref = _serve_requests(rng, n_ref)
    baseline = knum.OutputSketch.for_outputs(engine.offline(ref)).record()

    # The deterministic shift: push the feature with the LARGEST positive
    # weight, so the shifted mix's argmax collapses onto that class and
    # the answer distribution demonstrably leaves the baseline.
    shift = np.zeros(16, np.float32)
    shift[int(np.argmax(w_np))] = scale
    shifted = _serve_requests(rng, n_shift) + shift

    # The unmonitored oracle: the SAME engine, observatory off.
    with kserve.Server(engine) as server:
        unmon = np.stack(
            [f.result(30.0) for f in [server.submit(r) for r in shifted]]
        )

    pm_dir = os.path.join(tmpdir, f"chaos_drift_{seed}_pm")
    # Re-open the per-kind postmortem budget for THIS schedule (earlier
    # suite activity may have spent the process cap).
    with ktelemetry._pm_lock:
        ktelemetry._pm_counts.pop("serve_output_drift", None)
    before = counters.get("serve_output_drift")
    os.environ["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
    try:
        with knum.monitored(True):
            engine.arm_drift_baseline(baseline)
            with kserve.Server(engine) as server:
                mon = np.stack(
                    [
                        f.result(30.0)
                        for f in [server.submit(r) for r in shifted]
                    ]
                )
            drift_rec = engine.drift.record()
    finally:
        os.environ.pop("KEYSTONE_POSTMORTEM_DIR", None)
        knum.reset_state()
    if counters.get("serve_output_drift") - before < 1:
        raise ChaosOracleError(
            f"shifted request mix (divergence {drift_rec['divergence']}, "
            f"tol {drift_rec['tol']}) produced no counted "
            "serve_output_drift — the monitor missed a real distribution "
            "shift"
        )
    dumps = _glob.glob(
        os.path.join(pm_dir, "postmortem_serve_output_drift_*.json")
    )
    if not dumps:
        raise ChaosOracleError(
            "serve_output_drift was counted but no flight-recorder "
            "postmortem was dumped — the drift fired without evidence"
        )
    if not np.array_equal(mon, unmon):
        raise ChaosOracleError(
            "monitored engine's answers differ from the unmonitored "
            "engine's — the observatory changed RESULTS, not just what "
            "is observed"
        )
    if not np.array_equal(mon, engine.offline(shifted)):
        raise ChaosOracleError(
            "served answers under drift detection differ from the "
            "offline apply"
        )


def _mesh_shrink_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """Device loss mid-serve (ISSUE 16), both halves of the elastic story.

    Leg 1 (live re-anchor): a router anchored on a 4-device mesh has
    requests IN FLIGHT (the engine's execute is stretched so the loss
    demonstrably straddles live batches) when the mesh shrinks to the
    schedule's survivor count; ``reanchor`` must hot-swap every engine
    onto the surviving mesh with every future resolving bit-equal to the
    offline apply — zero request loss — and the event counted
    ``mesh_reanchor``.

    Leg 2 (reshard-resume): fitted state saved SHARDED under the full
    mesh must refuse a naive load with a typed ``CheckpointMismatch``
    that names the ``mesh=`` escape hatch, then resume onto the surviving
    mesh via ``load_pipeline(mesh=)`` (counted ``ckpt_reshard``) with
    predictions bit-equal to the fault-free full-mesh run.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.checkpoint import (
        CheckpointMismatch,
        load_pipeline,
        save_pipeline,
    )
    from keystone_tpu.core.pipeline import FunctionTransformer
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.parallel.mesh import DATA_AXIS, make_mesh, use_mesh

    rng = np.random.default_rng(seed)
    n = int(fault.params["requests"])
    survivors = int(fault.params["survivors"])
    hold = float(fault.params["hold_seconds"])
    devs = jax.devices()
    # The tier-1 substrate has 8 virtual devices (full = 4x1); a
    # standalone single-device chaos_run still exercises the swap
    # machinery on whatever mesh the host actually has.
    n_full = min(4, len(devs))
    survivors = min(survivors, n_full)
    full = make_mesh(data=n_full, model=1, devices=devs[:n_full])
    surviving = make_mesh(data=survivors, model=1, devices=devs[:survivors])

    # -- leg 1: live re-anchor with requests in flight ------------------------
    wrng = np.random.default_rng(_DATA_SEED)
    w = jnp.asarray(wrng.normal(size=(16,)).astype(np.float32))
    b = jnp.asarray(wrng.normal(size=(16,)).astype(np.float32))
    # Fusion-invariant arithmetic (see _serve_engine): eager == jit ==
    # every bucket on every mesh tier, so the bit-equality oracle tests
    # the SWAP, not XLA's rounding moods.
    pipe = FunctionTransformer(
        lambda x: jnp.maximum(x * w, b), name="chaos_mesh_shrink"
    )

    def build(shape, dtype, mesh):
        cfg = kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0)
        return kserve.ServingEngine(
            pipe, np.zeros(shape, dtype), config=cfg,
            label=f"chaos_shrink_{seed}", mesh=mesh,
        )

    reqs = _serve_requests(rng, 2 * n)
    factory = kfrontend.MeshEngineFactory(build, mesh=full)
    router = kfrontend.ShapeRouter(
        factory, label=f"chaos_shrink_{seed}",
        config=kfrontend.RouterConfig(warm_threshold=1, retire_after_s=300.0),
    )
    before = counters.get("mesh_reanchor")
    try:
        engine = factory((16,), np.float32)
        router.add_engine(engine)
        offline = np.asarray(engine.offline(reqs))
        real_execute = engine._execute

        def slow_execute(bucket, dev_batch):
            # Stretch the doomed mesh's batches so the loss demonstrably
            # lands while requests are IN FLIGHT, not between them.
            time.sleep(hold)
            return real_execute(bucket, dev_batch)

        engine._execute = slow_execute
        try:
            futs = [router.submit(r) for r in reqs[:n]]
            rec = router.reanchor(
                surviving, why=f"chaos seed {seed}: device loss"
            )
        finally:
            engine._execute = real_execute
        futs += [router.submit(r) for r in reqs[n:]]
        answers = np.stack([np.asarray(f.result(60.0)) for f in futs])
    finally:
        router.close()
    if rec["failed"]:
        raise ChaosOracleError(
            f"re-anchor left shapes on the dead mesh: {rec['failed']}"
        )
    if counters.get("mesh_reanchor") - before < 1:
        raise ChaosOracleError(
            "engines re-anchored onto the surviving mesh but no "
            "mesh_reanchor was counted"
        )
    if not np.array_equal(answers, offline):
        raise ChaosOracleError(
            "answers across the re-anchor differ from the offline apply — "
            "the surviving mesh changed RESULTS, not just placement"
        )

    # -- leg 2: checkpoint on mesh A, resume on surviving mesh B --------------
    mean = jax.device_put(
        jnp.asarray(wrng.normal(size=(16,)).astype(np.float32)),
        NamedSharding(full, PartitionSpec(DATA_AXIS)),
    )
    std = jnp.abs(jnp.asarray(wrng.normal(size=(16,)).astype(np.float32))) + 1.0
    scaler = StandardScalerModel(mean, std)
    test_rows = _serve_requests(rng, n)
    fault_free = np.asarray(
        StandardScalerModel(np.asarray(jax.device_get(mean)), np.asarray(std))(
            test_rows
        )
    )
    stem = os.path.join(tmpdir, f"chaos_shrink_{seed}_ckpt")
    with use_mesh(full):
        stem = save_pipeline(stem, scaler)
    if n_full >= 2:
        # Arrays sharded over >1 device: the naive load must REFUSE typed.
        # (On a 1-device host the state is effectively replicated and the
        # strict load legitimately succeeds — nothing to refuse.)
        try:
            load_pipeline(stem)
        except CheckpointMismatch as e:
            if "mesh=" not in str(e):
                raise ChaosOracleError(
                    f"the topology refusal does not name the mesh= reshard "
                    f"path: {e}"
                )
        else:
            raise ChaosOracleError(
                "a checkpoint holding full-mesh-sharded arrays loaded "
                "silently onto a different topology"
            )
    before_rs = counters.get("ckpt_reshard")
    resumed = load_pipeline(stem, mesh=surviving)
    if counters.get("ckpt_reshard") - before_rs < 1:
        raise ChaosOracleError(
            "the checkpoint resumed on the surviving mesh but no "
            "ckpt_reshard was counted"
        )
    got = np.asarray(resumed(jnp.asarray(test_rows)))
    if not np.array_equal(got, fault_free):
        raise ChaosOracleError(
            "predictions resumed on the surviving mesh differ from the "
            "fault-free full-mesh run"
        )


def _host_loss_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """A serving host dies mid-flight (ISSUE 17): drive the multi-host
    drill (real subprocesses where spawn is available, the in-process
    wire fleet otherwise) and hold it to the never-silent bar — every
    request answered bit-equal to the offline oracle, zero dropped, the
    loss counted ``fleet_host_lost``, the survivors re-formed
    (``dist_reform``) and re-anchored (``host_reanchor``,
    postmortem-linked)."""
    from keystone_tpu.workloads.multihost import run_host_loss_drill

    hosts = int(
        os.environ.get("KEYSTONE_CHAOS_HOSTS", fault.params["hosts"])
    )
    lost_before = counters.get("fleet_host_lost")
    reanchor_before = counters.get("host_reanchor")
    rec = run_host_loss_drill(
        tmpdir,
        hosts=hosts,
        requests=int(fault.params["requests"]),
        seed=seed,
        timeout_s=180.0,
    )
    if rec["dropped_requests"] != 0:
        raise ChaosOracleError(
            f"host loss dropped {rec['dropped_requests']} request(s) "
            f"({rec['answered']}/{rec['requests']} answered; "
            f"errors: {rec['errors']})"
        )
    if rec["mismatches"] != 0:
        raise ChaosOracleError(
            f"{rec['mismatches']} answer(s) differ from the offline "
            "oracle after the host loss — silent wrong answers"
        )
    if rec["errors"]:
        raise ChaosOracleError(
            f"fleet clients saw errors across the loss: {rec['errors']}"
        )
    for r, sc in rec["survivor_counters"].items():
        if sc.get("dist_reform", 0) < 1:
            raise ChaosOracleError(
                f"survivor {r} never re-formed the group: {sc}"
            )
        if sc.get("host_reanchor", 0) < 1:
            raise ChaosOracleError(
                f"survivor {r} never re-anchored its engines: {sc}"
            )
    if counters.get("fleet_host_lost") - lost_before < 1:
        raise ChaosOracleError(
            "the front-end never counted the host loss (fleet_host_lost)"
        )
    if counters.get("host_reanchor") - reanchor_before < 1:
        raise ChaosOracleError(
            "the re-anchor was never counted controller-side "
            "(host_reanchor)"
        )
    pm = [p for p in rec["postmortems"] if "host_reanchor" in p]
    if not pm:
        raise ChaosOracleError(
            f"no host_reanchor postmortem dumped (got {rec['postmortems']})"
        )


def _obs_capture_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """A fleet member is SIGKILLed mid-scrape (ISSUE 20): drive the
    fleet-observability drill (real subprocess members where spawn is
    available, in-process wire fleet otherwise) and hold the collector to
    its bar — fleet counters equal the sum of per-member snapshots, fleet
    p99 comes from the pooled sample windows, the loss is counted
    ``obs_member_lost`` (postmortem-linked) with the fleet view monotone
    for the survivors, ONE clock-aligned incident bundle holds every
    surviving member's flight ring, and every request still answers
    bit-equal to the offline oracle — collection never touches serving."""
    from keystone_tpu.workloads.multihost import run_obs_capture_drill

    hosts = int(
        os.environ.get("KEYSTONE_CHAOS_HOSTS", fault.params["hosts"])
    )
    lost_before = counters.get("obs_member_lost")
    rec = run_obs_capture_drill(
        tmpdir,
        hosts=hosts,
        requests=int(fault.params["requests"]),
        seed=seed,
        timeout_s=180.0,
    )
    if rec["dropped_requests"] != 0:
        raise ChaosOracleError(
            f"obs drill dropped {rec['dropped_requests']} request(s) "
            f"({rec['answered']}/{rec['requests']} answered; "
            f"errors: {rec['errors']})"
        )
    if rec["mismatches"] != 0:
        raise ChaosOracleError(
            f"{rec['mismatches']} answer(s) differ from the offline "
            "oracle with the collector attached — collection touched "
            "the serving answers"
        )
    if not rec.get("counter_sum_ok"):
        raise ChaosOracleError(
            "fleet counters != sum of per-member snapshots: "
            f"{rec.get('counter_sum_mismatch')}"
        )
    if not rec.get("p99_match"):
        raise ChaosOracleError(
            f"fleet p99 {rec.get('p99_fleet')} does not come from the "
            f"pooled windows (pick oracle {rec.get('p99_oracle_pick')}, "
            f"numpy oracle {rec.get('p99_oracle_np')}, "
            f"pool n={rec.get('p99_pool_n')})"
        )
    if not rec.get("monotone_ok"):
        raise ChaosOracleError(
            "fleet counters stepped BACKWARDS across the member loss: "
            f"{rec.get('monotone_violations')}"
        )
    if counters.get("obs_member_lost") - lost_before < 1:
        raise ChaosOracleError(
            "the collector never counted the member loss "
            "(obs_member_lost)"
        )
    incident = rec.get("incident") or {}
    if incident.get("error"):
        raise ChaosOracleError(
            f"incident capture wrote {incident['error']} for one member "
            "loss — expected exactly one bundle"
        )
    if incident.get("schema") != "keystone.incident/1":
        raise ChaosOracleError(
            f"incident bundle is not schema-tagged: {incident}"
        )
    if not incident.get("survivor_rings_ok"):
        raise ChaosOracleError(
            "the incident bundle is missing a surviving member's flight "
            f"ring: {incident}"
        )
    if not incident.get("events_monotone"):
        raise ChaosOracleError(
            "incident bundle events are not on one monotone clock-aligned "
            "timeline"
        )
    pm = [p for p in rec["postmortems"] if "obs_member_lost" in p]
    if not pm:
        raise ChaosOracleError(
            f"no obs_member_lost postmortem dumped (got {rec['postmortems']})"
        )


def _stepdown_oracle(
    res: dict,
    stepdown_delta: int,
    *,
    require_specs: bool = False,
    require_mesh: bool = False,
) -> None:
    """Shared oracle of the plan/spec-mispredict families: the searched
    placement record must prove the top-ranked plan died and the fit
    chose the NEXT-ranked one, with the step-down counted.
    ``require_mesh``/``require_specs`` additionally pin that the killed
    plan was a mesh plan / a non-default spec-assignment layout."""
    placement = res.get("placement")
    if placement is None:
        raise ChaosOracleError(
            "no searched placement in results — the mispredict families "
            "require the placement search to be active"
        )
    ranking, chosen = placement["ranking"], placement["chosen"]
    top_rec = next(
        (
            c for c in placement["candidates"]
            if ranking and c["name"] == ranking[0]
        ),
        {},
    )
    if require_mesh and not top_rec.get("mesh"):
        raise ChaosOracleError(
            f"top-ranked plan {ranking[0] if ranking else None!r} is not "
            "a mesh plan — the schedule did not exercise a sharded layout"
        )
    if require_specs and not top_rec.get("specs"):
        raise ChaosOracleError(
            f"top-ranked plan {ranking[0] if ranking else None!r} carries "
            "no spec assignment — the schedule killed the default layout, "
            "not a searched spec layout"
        )
    if len(ranking) < 2 or chosen != ranking[1]:
        raise ChaosOracleError(
            f"top-ranked plan {ranking[0] if ranking else None!r} died "
            f"but the fit chose {chosen!r}, not the next-ranked "
            f"{ranking[1] if len(ranking) > 1 else None!r}"
        )
    if stepdown_delta < 1:
        raise ChaosOracleError(
            "the top-ranked plan died RESOURCE_EXHAUSTED but no "
            f"autoshard_stepdown was counted (top candidate: {top_rec})"
        )


def _drift_refit_phase(fault: Fault, tmpdir: str, seed: int) -> None:
    """The closed lifecycle loop end-to-end (ISSUE 18) plus its fault
    legs — see the module docstring's ``drift_refit`` bullet.

    One deployment, five legs in sequence: (0) a shifted request mix
    trips the armed drift monitor and the controller SEES the trip;
    (A) a refit that OOMs materializing fresh features degrades typed +
    counted ``refit_failed`` to the incumbent; (B) a candidate refit
    over garbage labels is REJECTED by the holdout gate (counted
    ``refit_rejected``) — never swapped; (C) a mid-swap kill (the router
    dying under the replace) degrades typed + counted to the incumbent;
    (D) the clean cycle lands: warm refit, validation, atomic hot-swap
    with requests in flight (counted ``lifecycle_refit``, postmortem
    dumped, drift re-armed on the candidate's baseline, zero dropped,
    post-swap answers bit-equal to an OFFLINE refit); and (E) a trip
    inside the fresh cooldown is a counted suppression
    (``refit_suppressed``), not a refit storm."""
    import glob as _glob

    import jax.numpy as jnp

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import numerics as knum
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core import telemetry as ktelemetry
    from keystone_tpu.core.lifecycle import LifecycleConfig, LifecycleController
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.solvers.block import BlockLeastSquaresEstimator

    rng = np.random.default_rng(seed)
    n_ref = int(fault.params["reference"])
    n_shift = int(fault.params["shifted"])
    scale = float(fault.params["shift_scale"])
    n_rows = int(fault.params["rows"])
    n_req = int(fault.params["requests"])
    hold = float(fault.params["hold_seconds"])

    # Two worlds, one deployment: before the drift the truth is
    # ``(x - mean0) @ T1``; after the mix shifts the truth is
    # ``(x - mean0) @ T2`` — so the incumbent is genuinely WRONG on the
    # new mix and a refit on fresh data genuinely fixes it (the quality
    # gate has something real to judge).  Featurizer (mean-subtract) is
    # exactly-rounded elementwise arithmetic, weights schedule-invariant.
    wrng = np.random.default_rng(_DATA_SEED)
    mean0 = wrng.normal(size=(16,)).astype(np.float32)
    t1 = wrng.normal(size=(16, 4)).astype(np.float32)
    t2 = wrng.normal(size=(16, 4)).astype(np.float32)
    featurizer = StandardScalerModel(jnp.asarray(mean0), None)
    shift = np.zeros(16, np.float32)
    shift[int(np.argmax(np.abs(t1).sum(axis=1)))] = scale

    def fit_model(feats, labels, checkpoint=None):
        est = BlockLeastSquaresEstimator(block_size=16, num_iter=1, lam=0.0)
        return est.fit(
            jnp.asarray(feats), jnp.asarray(labels), checkpoint=checkpoint
        )

    # Incumbent: fit on the pre-drift world, served behind a router.
    xa = _serve_requests(rng, n_rows)
    feats_a = xa - mean0
    pipe_inc = featurizer.then(fit_model(feats_a, feats_a @ t1))
    cfg = kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0)
    engine_inc = kserve.ServingEngine(
        pipe_inc, np.zeros(16, np.float32), config=cfg,
        label=f"chaos_refit_inc_{seed}",
    )
    ref = _serve_requests(rng, n_ref)
    baseline = knum.OutputSketch.for_outputs(engine_inc.offline(ref)).record()

    # Post-drift world: shifted requests, new truth, fresh training data.
    xb = _serve_requests(rng, n_rows) + shift
    feats_b = xb - mean0
    labels_b = feats_b @ t2
    # Big enough that the noise-fit candidate's holdout MSE dwarfs even a
    # badly-wrong incumbent's — the rejection leg must be unambiguous.
    labels_noise = rng.normal(size=labels_b.shape).astype(np.float32) * 50.0
    hx = _serve_requests(rng, 64) + shift
    hy = (hx - mean0) @ t2
    shifted = _serve_requests(rng, n_shift) + shift
    reqs_mid = _serve_requests(rng, n_req) + shift
    reqs_post = _serve_requests(rng, n_req) + shift

    # The OFFLINE refit oracle: same fresh data, fit outside the
    # controller — post-swap served answers must be bit-equal to it.
    pipe_offline = featurizer.then(fit_model(feats_b, labels_b))
    offline_refit = np.asarray(pipe_offline(jnp.asarray(reqs_post)))

    mode = {"fetch": "good"}

    def fetch(digest):
        if mode["fetch"] == "oom":
            raise faults.resource_exhausted_error()
        if mode["fetch"] == "noise":
            return feats_b, labels_noise
        return feats_b, labels_b

    def quality(predict, x, y):
        return -float(np.mean((np.asarray(predict(x)) - y) ** 2))

    pm_dir = os.path.join(tmpdir, f"chaos_refit_{seed}_pm")
    with ktelemetry._pm_lock:
        ktelemetry._pm_counts.pop("serve_output_drift", None)
        ktelemetry._pm_counts.pop("lifecycle_refit", None)
    before = {
        k: counters.get(k)
        for k in (
            "serve_output_drift", "refit_failed", "refit_rejected",
            "lifecycle_refit", "drift_rearmed", "refit_suppressed",
        )
    }

    def delta(kind):
        return counters.get(kind) - before[kind]

    router = kfrontend.ShapeRouter(
        label=f"chaos_refit_{seed}",
        config=kfrontend.RouterConfig(warm_threshold=1, retire_after_s=300.0),
    )
    os.environ["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
    ctl = None
    try:
        router.add_engine(engine_inc)
        ctl = LifecycleController(
            router,
            workdir=os.path.join(tmpdir, f"chaos_refit_{seed}_wd"),
            featurizer=featurizer,
            fetch=fetch,
            estimator=lambda: BlockLeastSquaresEstimator(
                block_size=16, num_iter=1, lam=0.0
            ),
            assemble=lambda model: featurizer.then(model),
            holdout=lambda: (hx, hy),
            quality=quality,
            example=np.zeros(16, np.float32),
            label=f"chaos_refit_{seed}",
            serve_config=cfg,
            config=LifecycleConfig(cooldown_s=0.0, poll_interval_s=0.05),
        )
        with knum.monitored(True):
            engine_inc.arm_drift_baseline(baseline)
            # -- leg 0: the shifted mix trips the armed monitor ---------------
            futs = [router.submit(r) for r in shifted]
            mon = np.stack([np.asarray(f.result(30.0)) for f in futs])
            if not np.array_equal(mon, engine_inc.offline(shifted)):
                raise ChaosOracleError(
                    "served answers under drift detection differ from the "
                    "incumbent's offline apply"
                )
            if delta("serve_output_drift") < 1:
                raise ChaosOracleError(
                    "shifted request mix produced no counted "
                    "serve_output_drift — the monitor missed the shift"
                )
            reason = ctl.check_signals()
            if reason != "serve_output_drift":
                raise ChaosOracleError(
                    f"the lifecycle watcher did not see the drift trip "
                    f"(check_signals -> {reason!r})"
                )

            def incumbent_still_serving(leg):
                table = router.engines()
                if table.get((16,)) != engine_inc.label:
                    raise ChaosOracleError(
                        f"{leg}: the failed cycle touched the routing table "
                        f"({table}) — a half-swapped model is serving"
                    )
                probe = _serve_requests(rng, 3) + shift
                got = np.stack(
                    [
                        np.asarray(f.result(30.0))
                        for f in [router.submit(r) for r in probe]
                    ]
                )
                if not np.array_equal(got, engine_inc.offline(probe)):
                    raise ChaosOracleError(
                        f"{leg}: post-fault answers differ from the "
                        "incumbent's offline apply — silent wrong answers"
                    )

            # -- leg A: refit OOM degrades typed + counted --------------------
            mode["fetch"] = "oom"
            rec = ctl.run_refit(reason=reason)
            if rec["outcome"] != "refit_failed" or delta("refit_failed") < 1:
                raise ChaosOracleError(
                    f"injected refit OOM was not a counted typed "
                    f"degradation: {rec}"
                )
            incumbent_still_serving("refit OOM")

            # -- leg B: a WORSE candidate is rejected, never swapped ----------
            mode["fetch"] = "noise"
            rec = ctl.run_refit(reason="operator")
            if rec["outcome"] != "rejected" or delta("refit_rejected") < 1:
                raise ChaosOracleError(
                    f"a candidate refit worse than the incumbent was not "
                    f"rejected+counted: {rec}"
                )
            incumbent_still_serving("validation rejection")

            # -- leg C: a mid-swap kill degrades typed + counted --------------
            mode["fetch"] = "good"
            real_replace = router.replace_engine
            failed_before = delta("refit_failed")
            try:
                def killed_replace(engine, **kw):
                    raise kserve.ServingUnavailable("injected mid-swap kill")

                router.replace_engine = killed_replace
                rec = ctl.run_refit(reason="operator")
            finally:
                router.replace_engine = real_replace
            if (
                rec["outcome"] != "refit_failed"
                or rec.get("phase") != "swap"
                or delta("refit_failed") <= failed_before
            ):
                raise ChaosOracleError(
                    f"a mid-swap kill was not a counted typed degradation "
                    f"to the incumbent: {rec}"
                )
            incumbent_still_serving("mid-swap kill")

            # -- leg D: the clean cycle lands, requests in flight -------------
            ctl.config.cooldown_s = 300.0  # leg E exercises the storm guard
            inflight_mid = []
            real_execute = engine_inc._execute

            def slow_execute(bucket, dev_batch):
                # Stretch the incumbent's batches so the swap demonstrably
                # straddles live requests (drain-after-unroute resolves
                # them on the OLD engine — zero loss).
                time.sleep(hold)
                return real_execute(bucket, dev_batch)

            def replace_with_traffic(engine, **kw):
                inflight_mid.extend(router.submit(r) for r in reqs_mid)
                return real_replace(engine, **kw)

            try:
                engine_inc._execute = slow_execute
                router.replace_engine = replace_with_traffic
                rec = ctl.run_refit(reason=reason)
            finally:
                engine_inc._execute = real_execute
                router.replace_engine = real_replace
            if rec["outcome"] != "swapped" or delta("lifecycle_refit") < 1:
                raise ChaosOracleError(
                    f"the clean drift->refit->swap cycle did not land "
                    f"counted: {rec}"
                )
            if delta("drift_rearmed") < 1:
                raise ChaosOracleError(
                    "the swap landed but the drift monitor was not "
                    "re-armed on the candidate's baseline"
                )
            dropped = 0
            mid_answers = []
            for f in inflight_mid:
                try:
                    mid_answers.append(np.asarray(f.result(60.0)))
                except Exception:  # noqa: BLE001 — counted as a drop
                    dropped += 1
            if dropped:
                raise ChaosOracleError(
                    f"{dropped} request(s) in flight across the hot-swap "
                    "were dropped — the swap opened a service gap"
                )
            if not np.array_equal(
                np.stack(mid_answers), engine_inc.offline(reqs_mid)
            ):
                raise ChaosOracleError(
                    "in-flight answers across the swap differ from the "
                    "incumbent's offline apply"
                )
            engine_new = router.server_for((16,)).engine
            if engine_new is engine_inc:
                raise ChaosOracleError("the swap left the incumbent routed")
            post = np.stack(
                [
                    np.asarray(f.result(30.0))
                    for f in [router.submit(r) for r in reqs_post]
                ]
            )
            if not np.array_equal(post, offline_refit):
                raise ChaosOracleError(
                    "post-swap answers differ from the offline refit — "
                    "the lifecycle served a model that is not the refit"
                )
            dumps = _glob.glob(
                os.path.join(pm_dir, "postmortem_lifecycle_refit_*.json")
            )
            if not dumps:
                raise ChaosOracleError(
                    "lifecycle_refit was counted but no flight-recorder "
                    "postmortem was dumped — the swap left no evidence"
                )

            # -- leg E: the cooldown storm guard ------------------------------
            rec = ctl.run_refit(reason="operator")
            if (
                rec["outcome"] != "suppressed"
                or delta("refit_suppressed") < 1
            ):
                raise ChaosOracleError(
                    f"a trip inside the cooldown was not a counted "
                    f"suppression: {rec}"
                )
    finally:
        if ctl is not None:
            ctl.close()
        router.close()
        os.environ.pop("KEYSTONE_POSTMORTEM_DIR", None)
        knum.reset_state()


def _run_faulted(fault: Fault, workload: str, tmpdir: str, seed: int):
    """Apply one schedule to the workload; returns the results dict (or
    raises).  Each branch is the minimal faithful injection for its
    family — all patches restored on exit."""
    if fault.kind == "solver_oom":
        with faults.oom_faults(
            block_mod, "_execute_fused_bcd", failures=fault.params["failures"]
        ):
            return _run_workload(workload)

    if fault.kind == "oom_cascade":
        # Fused dies, then the stepwise per-block solve dies too: the
        # ladder must walk fused -> stepwise -> host_staged.
        with faults.oom_faults(block_mod, "_execute_fused_bcd", failures=1):
            with faults.oom_faults(block_mod, "_bcd_block_solve", failures=1):
                return _run_workload(workload)

    if fault.kind in ("io_transient", "corrupt_members"):
        _ingest_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "stream_corrupt":
        _stream_corrupt_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "jpeg_corrupt_entropy":
        _jpeg_corrupt_entropy_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "native_entropy":
        _native_entropy_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "profiler_crash":
        _profiler_crash_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "output_drift":
        _output_drift_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "mesh_shrink":
        _mesh_shrink_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "host_loss":
        _host_loss_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "obs_capture":
        _obs_capture_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "drift_refit":
        _drift_refit_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "stream_hang":
        return _stream_hang_phase(fault, tmpdir, seed)  # always raises

    if fault.kind == "autotune_thrash":
        _autotune_thrash_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "snapshot_corrupt":
        _snapshot_corrupt_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "decode_worker_kill":
        _decode_worker_kill_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "slow_client":
        _slow_client_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "malformed_request":
        _malformed_request_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "serve_burst_oom":
        _serve_burst_oom_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "wire_disconnect":
        _wire_disconnect_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "slow_loris":
        _slow_loris_phase(fault, tmpdir, seed)
        return _run_workload(workload)

    if fault.kind == "plan_mispredict":
        # The cost model's top-ranked plan (fused, on these shapes) is made
        # WRONG at runtime: injected RESOURCE_EXHAUSTED at its dispatch.
        # Oracle: the fit walks to the NEXT plan in the SEARCHED ranking
        # (the placement record proves the order), the step-down is
        # counted, and the judge then holds predictions to bit-equality.
        from keystone_tpu.core.resilience import counters as _counters

        before = _counters.get("autoshard_stepdown")
        with faults.oom_faults(
            block_mod, "_execute_fused_bcd", failures=fault.params["failures"]
        ):
            res = _run_workload(workload)
        _stepdown_oracle(res, _counters.get("autoshard_stepdown") - before)
        return res

    if fault.kind == "spec_mispredict":
        # The spec-ASSIGNMENT analog (ISSUE 10): the fault-free mesh
        # baseline's placement table names the enumerated spec candidates;
        # one on the head mesh shape is FORCED to the top of the faulted
        # run's ranking (conf.solve_plan -> fit(plan=[name])), so the plan
        # that dies at the GSPMD dispatch is a real non-default
        # NamedSharding layout lowered from searched spec strings — not
        # the same default rung plan_mispredict already kills.  The fit
        # must step down the ranking (counted autoshard_stepdown) onto
        # the default plan, and the judge then holds predictions
        # bit-equal to the fault-free MESH baseline.
        from keystone_tpu.core.resilience import counters as _counters

        base_pl = baseline(workload, mesh=True).get("placement")
        forced = None
        if base_pl and base_pl.get("ranking"):
            head = next(
                (
                    c for c in base_pl["candidates"]
                    if c["name"] == base_pl["ranking"][0]
                ),
                {},
            )
            forced = next(
                (
                    [c["name"]] for c in base_pl["candidates"]
                    if c.get("specs") and not c["pruned"]
                    and c["mesh"] == head.get("mesh")
                ),
                None,
            )
        before = _counters.get("autoshard_stepdown")
        with faults.oom_faults(
            block_mod, "_execute_fused_bcd_mesh",
            failures=fault.params["failures"],
        ):
            res = _run_workload(
                workload, mesh=_spec_mesh(), solve_plan=forced
            )
        _stepdown_oracle(
            res,
            _counters.get("autoshard_stepdown") - before,
            # With >= 2 devices a spec candidate always exists; a 1x1 mesh
            # has no non-default layouts, so the oracle degrades to the
            # mesh-plan check there instead of passing vacuously.
            require_specs=forced is not None,
            require_mesh=True,
        )
        return res

    if fault.kind == "nan_input":
        frac = fault.params["frac"]
        rng = np.random.default_rng(seed)

        def poison(train):
            if hasattr(train, "data"):  # LabeledData
                return dataclasses.replace(
                    train, data=faults.inject_nan(train.data, rng, frac)
                )
            return dataclasses.replace(  # LabeledImageBatch
                train, images=faults.inject_nan(train.images, rng, frac)
            )

        return _run_workload(workload, train_override=poison)

    if fault.kind == "preempt_resume":
        ckpt_path = os.path.join(tmpdir, f"chaos_bcd_{workload}_{seed}")
        writer = bcd_checkpoint_writer(ckpt_path)
        after = int(fault.params["preempt_after_blocks"])
        calls = {"n": 0}

        def preempting_cb(state):
            writer(state)
            calls["n"] += 1
            if calls["n"] >= after:
                raise SimulatedPreemption(
                    f"injected preemption after block {state['block']} "
                    f"of epoch {state['epoch']}"
                )

        try:
            _run_workload(workload, solve_checkpoint=preempting_cb)
        except SimulatedPreemption:
            pass
        else:
            raise ChaosOracleError(
                "preemption callback never fired — the checkpointing "
                "stepwise path was not taken"
            )
        counters.record(
            "chaos_preemption", f"{workload} seed {seed}: resuming from "
            f"{ckpt_path}"
        )
        return _run_workload(
            workload,
            solve_checkpoint=ckpt_path,
            solve_resume=ckpt_path,
        )

    if fault.kind == "deadline":
        budget = float(fault.params["seconds"])
        real = block_mod._execute_fused_bcd

        def hanging_execute(*a, **kw):
            time.sleep(600.0)  # interrupted by the deadline watchdog
            return real(*a, **kw)

        with _patched(block_mod, "_execute_fused_bcd", hanging_execute):
            with deadline(budget, phase="solve"):
                return _run_workload(workload)

    raise ValueError(f"unknown fault family {fault.kind!r}")


def expected_outcome(fault: Fault) -> str:
    """What a HEALTHY system does under this schedule."""
    if fault.kind in ("nan_input", "deadline", "stream_hang"):
        return "typed_error"
    return "completed_equal"


def run_schedule(
    seed: int,
    workload: str = "mnist",
    tmpdir: str | None = None,
    trace_path: str | None = None,
) -> ChaosResult:
    """Run ONE seeded fault schedule end-to-end and judge the outcome.

    ``trace_path``: write a per-schedule Chrome-trace JSON of the faulted
    run — every counted fault lands in it as an instant event (kind attr)
    and every failed span carries the error type, so
    :func:`verify_trace` can hold the trace to the never-silent bar."""
    fault = make_schedule(seed)
    own_tmp = tmpdir is None
    if own_tmp:
        tmpdir = tempfile.mkdtemp(prefix="chaos_")
    t0 = time.monotonic()
    result = ChaosResult(seed=seed, workload=workload, fault=fault, outcome="")
    with _clean_env():
        # spec_mispredict runs under a mesh, so it is judged against the
        # fault-free MESH baseline (same devices, same mesh shape).
        base = baseline(workload, mesh=fault.kind == "spec_mispredict")
        if trace_path is not None:
            # Per-schedule timeline: clear the buffer so this trace holds
            # exactly this schedule's events (baseline is pre-cached above).
            trace.reset()
            trace.enable(trace_path)
        before = counters.snapshot()
        try:
            result.outcome = _judge_schedule(
                result, fault, workload, tmpdir, seed, base
            )
        finally:
            after = counters.snapshot()
            result.counters_delta = {
                k: after[k] - before.get(k, 0)
                for k in after
                if after[k] != before.get(k, 0)
            }
            if trace_path is not None:
                # finally: even an unexpected (KeyboardInterrupt-class)
                # escape must not leave tracing globally enabled with
                # _path aimed at this schedule's file.
                trace.flush(trace_path)
                trace.disable()
                result.trace_path = trace_path
    result.seconds = time.monotonic() - t0
    if own_tmp:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return result


def _judge_schedule(result, fault, workload, tmpdir, seed, base) -> str:
    """Run one faulted schedule and return the judged outcome (filling
    ``result``'s error fields as a side effect)."""
    try:
        res = _run_faulted(fault, workload, tmpdir, seed)
    except TYPED_ERRORS as e:
        result.error_type = type(e).__name__
        result.error = str(e)
        result.phase = getattr(e, "phase", None)
        return "typed_error"
    except ChaosOracleError as e:
        result.error_type = type(e).__name__
        result.error = str(e)
        return "ORACLE_FAILED"
    except Exception as e:  # noqa: BLE001 — the contract violation case
        result.error_type = type(e).__name__
        result.error = str(e)
        return "UNTYPED_ERROR"
    got = res.get("test_predictions")
    want = base.get("test_predictions")
    if got is None or want is None:
        # A missing prediction vector must never score as equal — that
        # would be the oracle passing vacuously.
        result.error = (
            "no test_predictions to compare "
            f"(faulted: {got is not None}, baseline: {want is not None})"
        )
        return "ORACLE_FAILED"
    if _preds_equal(got, want):
        return "completed_equal"
    result.error = (
        "run completed but predictions differ from the fault-free baseline"
    )
    return "SILENT_WRONG_MODEL"


def verify_trace(trace_path: str, result: ChaosResult) -> list[str]:
    """Hold one schedule's trace to the never-silent bar.  Returns the
    violations (empty = clean):

    * every fault kind counted during the schedule must appear as a
      ``fault`` instant event with a matching ``kind`` attribute;
    * a typed-error outcome must also be visible as a span that FAILED
      with that error type (spans record ``error`` on exception) or as a
      counted fault event — a typed error that left no trace evidence is
      an observability regression even when the run itself was judged ok.
    """
    import json as _json

    with open(trace_path) as f:
        if trace_path.endswith(".jsonl"):
            events = [_json.loads(line) for line in f if line.strip()]
        else:
            doc = _json.load(f)
            events = (
                doc.get("traceEvents", []) if isinstance(doc, dict) else doc
            )
    fault_kinds = {
        ev.get("args", {}).get("kind")
        for ev in events
        if ev.get("ph") == "i" and ev.get("name") == "fault"
    }
    span_errors = {
        ev.get("args", {}).get("error")
        for ev in events
        if ev.get("ph") == "X" and ev.get("args", {}).get("error")
    }
    missing = [
        f"counted fault {kind!r} has no trace event"
        for kind in sorted(result.counters_delta)
        if kind not in fault_kinds
    ]
    if (
        result.outcome == "typed_error"
        and result.error_type not in span_errors
        and not fault_kinds
    ):
        missing.append(
            f"typed error {result.error_type} appears in no span and no "
            "fault event — a silent typed failure"
        )
    return missing


def run_suite(
    seeds, workload: str = "mnist", trace_dir: str | None = None
) -> list[ChaosResult]:
    tmpdir = tempfile.mkdtemp(prefix="chaos_suite_")
    try:
        results = []
        for s in seeds:
            tp = (
                os.path.join(trace_dir, f"chaos_seed{s}.json")
                if trace_dir is not None
                else None
            )
            results.append(
                run_schedule(s, workload=workload, tmpdir=tmpdir, trace_path=tp)
            )
        return results
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
