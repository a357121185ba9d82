"""Automated sharding/placement search: cost-model-ranked plans replace the
hand-enumerated ladders.

The degradation ladders (core.memory.run_ladder) encode placement as two
hand-written lists: fused -> stepwise -> host-staged on one device, full
mesh -> collapsed mesh -> single device across chips.  That is KeystoneML's
pre-optimizer posture — operator choices written down instead of searched.
This module is the whole-pipeline-optimizer treatment for PLACEMENT
(Automap and the Learned Cost Model placement paper, PAPERS.md): given a
solve's candidate executions — every (data, model) factorization of the
live device set (parallel.mesh.enumerate_mesh_shapes) x sharding spec per
operand (from the program's avals, :func:`spec_candidates`) x execution
strategy (fused / stepwise / host-staged) — the search

1. **prunes** candidates with the zero-cost analytic batch preflight
   (core.memory.plan_bytes / plan_batch — no compile; a denied plan is
   free to reject, and the full compiled admission still guards whatever
   the ladder later selects);
2. **scores** survivors with the shared cost model
   (core.optimize.CostModel): an analytic roofline prior over per-chip
   bytes / FLOPs / dispatches / collective volume, multiplied by a learned
   per-(program, candidate) calibration fitted to MEASURED outcomes from
   the persistent plan-outcome log (``~/.keystone_plans.jsonl``, keyed by
   program fingerprint) — the model improves across runs;
3. **ranks** with a confidence margin: candidates whose predicted costs
   are within one margin FACTOR of the cheapest remaining candidate keep
   their prior (hand-ladder) order (:data:`UNTRAINED_MARGIN` cold,
   :data:`TRAINED_MARGIN` for pairs where BOTH sides carry >=
   :data:`MIN_TRAIN` direct measurements) — an untrained prior never
   deviates from the proven default on noise, so a searched fit is
   bit-identical to the hand ladder until real measurements argue
   otherwise; the resilience floor is pinned last regardless of score;
4. **runs** the ranked list through the SAME ``run_ladder`` contract the
   hand ladders use — per-tier compiled admission at selection, runtime
   RESOURCE_EXHAUSTED steps down the RANKED list one plan at a time
   (counted ``autoshard_stepdown``), typed errors propagate — and lands
   the full candidate table, deny/score rationale, and predicted-vs-actual
   cost of the chosen plan in the :class:`PlacementPlan` attached to the
   solver's ``FitReport``.

**Sharding specs are executable** (ISSUE 10): :func:`spec_candidates`
enumerates per-operand shardings from an aval's own dimensions, and
:func:`spec_pspec` / :func:`spec_sharding` lower a chosen spec string
(``"data@dim0"``, ``"model@dim1"``, ``"replicated"``) into the actual
``PartitionSpec`` / ``NamedSharding`` the mesh programs constrain their
operands with — so a :class:`Candidate` can carry a per-operand spec
assignment (``Candidate.specs``) that the solvers execute as a REAL
layout, not just a byte estimate.  The candidate space is then
(mesh factorization x strategy x spec assignment), still pruned by the
same zero-cost batch preflight (which already charges spec bytes) and
still run through the unchanged ``run_ladder`` contract.
``KEYSTONE_AUTOSHARD_SPECS=0`` restores the PR 9 posture (one hard-coded
layout per strategy; the spec dimension drops out of the enumeration).

**Calibration is cross-program** (ISSUE 10): below :data:`MIN_TRAIN`
direct measurements, a candidate's factor comes from a featurized ratio
regression (core.optimize.CalibrationModel) fitted over EVERY program's
logged outcomes — operand bytes, mesh axes, strategy, arithmetic
intensity from the roofline prior — so learning on one solve shape
transfers to unseen shapes.  The conservative-margin rules are
unchanged: only direct measurements tighten the margin, and an empty log
reproduces the hand ladder bit-for-bit.

``KEYSTONE_AUTOSHARD=0`` restores the hand ladders; ``fit(plan=...)``
overrides per call (``False`` hand, ``True`` force search, a
:class:`PlacementPlan` or name list replays a previous ranking).
``KEYSTONE_PLAN_LOG`` points the outcome log elsewhere (``off`` disables);
``KEYSTONE_PLAN_LOG_MAX`` caps its entry count (oldest-first compaction on
write).  The log is read ONCE per process: outcomes appended during a run
train the NEXT process, so a ranking can never silently change between a
baseline and a comparison fit inside one process (the chaos bit-equality
bar).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import logging
import os
import time
from typing import Any, Callable, Sequence

import numpy as np

from . import memory as kmem
from . import optimize as kopt
from . import profiler as kprof
from . import trace
from .resilience import counters

_logger = logging.getLogger("keystone_tpu.autoshard")

#: env var: "0"/"off"/"false" restores the hand ladders process-wide.
AUTOSHARD_ENV = "KEYSTONE_AUTOSHARD"

#: env var: "0"/"off"/"false" drops the per-operand SPEC dimension from
#: the candidate enumeration (the PR 9 posture: one layout per strategy).
SPECS_ENV = "KEYSTONE_AUTOSHARD_SPECS"

#: env var: plan-outcome log path; default ``~/.keystone_plans.jsonl``;
#: "0"/"off"/"none" disables persistence.
PLAN_LOG_ENV = "KEYSTONE_PLAN_LOG"
_DEFAULT_PLAN_LOG = "~/.keystone_plans.jsonl"

#: env var: plan-outcome log entry cap (oldest-first compaction on write);
#: "0"/"off" disables capping.
PLAN_LOG_MAX_ENV = "KEYSTONE_PLAN_LOG_MAX"
_DEFAULT_PLAN_LOG_MAX = 20_000

#: measurements per (fingerprint, candidate) before its calibration counts.
MIN_TRAIN = 3
#: cold-start ranking margin: an untrained analytic score must beat the
#: cheapest remaining candidate by this FACTOR before reordering past a
#: prior-earlier plan — the guarantee that a searched fit without
#: measurements reproduces the hand ladder's choice bit-for-bit.
UNTRAINED_MARGIN = 4.0
#: margin for a pair of candidates that BOTH carry >= MIN_TRAIN direct
#: measured outcomes — only like-for-like measured comparisons get the
#: tight margin; any pair with an unmeasured side keeps the cold one.
TRAINED_MARGIN = 1.15

#: bound on how much of the log one process will read back (newest wins).
_MAX_LOG_RECORDS = 50_000


def enabled() -> bool:
    """Search is the default; ``KEYSTONE_AUTOSHARD=0`` restores the hand
    ladders."""
    return os.environ.get(AUTOSHARD_ENV, "").strip().lower() not in (
        "0", "off", "false",
    )


def specs_enabled() -> bool:
    """Spec-assignment candidates are enumerated by default when the
    search runs; ``KEYSTONE_AUTOSHARD_SPECS=0`` restores the PR 9
    one-layout-per-strategy candidate space."""
    return os.environ.get(SPECS_ENV, "").strip().lower() not in (
        "0", "off", "false",
    )


# -- program fingerprints ------------------------------------------------------


def fingerprint(label: str, *parts) -> str:
    """Stable 16-hex-char fingerprint of a solve program's cost identity:
    the label plus whatever shapes/dtypes/statics/device description the
    caller folds in.  Same fingerprint => the plan log's measurements are
    comparable => same ranking under a fixed device set (the determinism
    contract the tests pin)."""
    blob = json.dumps([label, *map(str, parts)], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def device_fingerprint(devices=None) -> str:
    """``'cpu x8'``-style description of the device set a plan assumed."""
    try:
        if devices is None:
            import jax

            devices = jax.devices()
        devices = list(devices)
        kind = getattr(devices[0], "device_kind", "unknown")
        return f"{kind} x{len(devices)}"
    except Exception:  # noqa: BLE001 — no backend yet
        return "unknown x0"


# -- sharding-spec enumeration from avals --------------------------------------


def spec_candidates(aval, mesh_shape: dict) -> list[dict]:
    """Candidate shardings for ONE operand aval under a (data, model) mesh
    shape — generated from the aval's dimensions, not a hand list: the data
    axis over any evenly-divisible dim, the model axis over any other
    evenly-divisible dim, and replicated (always legal).  Each entry
    carries the spec's per-chip bytes, the quantity the cost model charges.
    """
    shape = tuple(int(d) for d in aval.shape)
    itemsize = np.dtype(aval.dtype).itemsize
    total = int(np.prod(shape)) * itemsize if shape else itemsize
    out = [{"spec": "replicated", "per_chip_bytes": total}]
    d_sz = int(mesh_shape.get("data", 1))
    m_sz = int(mesh_shape.get("model", 1))
    for dim, n in enumerate(shape):
        if d_sz > 1 and n % d_sz == 0:
            out.append({
                "spec": f"data@dim{dim}",
                "per_chip_bytes": total // d_sz,
            })
        if m_sz > 1 and n % m_sz == 0:
            out.append({
                "spec": f"model@dim{dim}",
                "per_chip_bytes": total // m_sz,
            })
    return out


def best_spec(aval, mesh_shape: dict) -> dict:
    """The minimum-per-chip-bytes legal sharding for one aval — what the
    analytic byte accounting assumes a candidate mesh can achieve for a
    shardable operand (replicated when nothing divides)."""
    cands = spec_candidates(aval, mesh_shape)
    return min(cands, key=lambda c: (c["per_chip_bytes"], c["spec"]))


# -- spec strings -> executable layouts ----------------------------------------
#
# A spec string names ONE mesh axis over ONE operand dimension
# ("data@dim0", "model@dim1") or full replication ("replicated") — the
# exact vocabulary :func:`spec_candidates` enumerates from avals.  The
# lowerers below turn a CHOSEN spec into the jax objects the mesh
# programs execute with, so the byte accounting and the executed layout
# can never drift: both read the same string.


def spec_pspec(spec: str, ndim: int):
    """Lower one spec string to the ``PartitionSpec`` it names."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    if spec == "replicated":
        return P(*([None] * ndim))
    axis, sep, dim = spec.partition("@dim")
    if not sep or axis not in ("data", "model") or not dim.isdigit():
        raise ValueError(
            f"bad sharding spec {spec!r} (want 'replicated', 'data@dimN' "
            "or 'model@dimN')"
        )
    i = int(dim)
    if i >= ndim:
        raise ValueError(f"spec {spec!r} names dim {i} of a {ndim}-d operand")
    parts: list = [None] * ndim
    parts[i] = DATA_AXIS if axis == "data" else MODEL_AXIS
    return P(*parts)


def spec_sharding(spec: str, mesh, ndim: int):
    """Lower one spec string to a ``NamedSharding`` on ``mesh`` — the
    layout the solvers constrain an operand with when a spec-assignment
    candidate executes."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, spec_pspec(spec, ndim))


def spec_chip_bytes(shape, dtype, spec: str, mesh_shape: dict) -> int:
    """Analytic per-chip bytes of one operand under one spec — the figure
    a spec-assignment candidate's hints charge (and the quantity the
    lower-bound regression test pins against the compiled
    ``memory_analysis``).  The named dimension must divide evenly; callers
    enumerate via :func:`spec_candidates`, which only emits legal specs."""
    shape = tuple(int(d) for d in shape)
    total = int(np.prod(shape)) * np.dtype(dtype).itemsize if shape else (
        np.dtype(dtype).itemsize
    )
    if spec == "replicated":
        return total
    axis, _, dim = spec.partition("@dim")
    size = int(mesh_shape.get(axis, 1))
    n = shape[int(dim)]
    if size <= 1:
        return total
    if n % size:
        raise ValueError(
            f"spec {spec!r} does not divide dim of size {n} by {size}"
        )
    return total // size


def spec_tag(specs: dict | None) -> str:
    """Compact human tag for a spec assignment (candidate names, the
    plan_view spec column): ``'labels=model@dim1,models=rep'``."""
    if not specs:
        return "default"
    return ",".join(
        f"{k}={'rep' if v == 'replicated' else v}"
        for k, v in sorted(specs.items())
    )


# -- the plan-outcome log ------------------------------------------------------


def plan_log_path() -> str | None:
    raw = os.environ.get(PLAN_LOG_ENV, "").strip()
    if raw.lower() in ("0", "off", "none"):
        return None
    return os.path.expanduser(raw or _DEFAULT_PLAN_LOG)


def hermetic_plan_log() -> str:
    """Point the plan-outcome log at a fresh throwaway file and forget any
    cached read.  For chaos drivers (tools/chaos_run.py): their fixed-seed
    synthetic fits must neither TRAIN the operator's real log (a few
    rounds would calibrate their fingerprints and start reordering the
    very ranking the driver asserts is hand-identical) nor evict real
    workload records from its bounded tail."""
    import tempfile

    path = os.path.join(
        tempfile.mkdtemp(prefix="keystone_plans_hermetic_"), "plans.jsonl"
    )
    os.environ[PLAN_LOG_ENV] = path
    clear_outcome_cache()
    return path


def plan_log_max() -> int | None:
    """Entry cap on the plan-outcome log (``KEYSTONE_PLAN_LOG_MAX``;
    default 20k, ``0``/``off`` disables).  Raises ``ValueError`` for a
    malformed or negative value (same fail-fast grammar as the other
    ``KEYSTONE_*`` numeric knobs); the append path catches it — telemetry
    never crashes a solve."""
    raw = os.environ.get(PLAN_LOG_MAX_ENV, "").strip()
    if not raw:
        return _DEFAULT_PLAN_LOG_MAX
    if raw.lower() in ("0", "off", "none"):
        return None
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{PLAN_LOG_MAX_ENV}={raw!r} is not an integer"
        ) from None
    if val < 1:
        raise ValueError(f"{PLAN_LOG_MAX_ENV}={raw!r} must be >= 1 (or 'off')")
    return val


#: newest records kept per (fingerprint, candidate) when compaction must
#: drop history: enough for a stable median over MIN_TRAIN-sized tails
#: (an odd count keeps the median an actual sample).
_COMPACT_KEEP_PAIR = 9


@contextlib.contextmanager
def _log_lock(path: str):
    """Advisory exclusive lock (sidecar ``<path>.lock``) serializing log
    appends against compaction's read-rewrite-replace: without it, a
    record another process appends between compaction's read and its
    ``os.replace`` would vanish silently.  Best-effort — platforms
    without ``fcntl`` (or an unwritable sidecar) fall back to unlocked
    appends, the pre-cap behavior."""
    lf = None
    try:
        try:
            import fcntl

            lf = open(path + ".lock", "a")
            fcntl.flock(lf, fcntl.LOCK_EX)
        except (ImportError, OSError):
            lf = None
        yield
    finally:
        if lf is not None:
            try:
                lf.close()  # closing the fd releases the flock
            except OSError:
                pass


def compact_log(path: str, cap: int) -> int:
    """Oldest-first compaction of the outcome log to a watermark BELOW
    ``cap`` (~90%, so the headroom amortizes the next O(entries) recount
    across many appends instead of re-reading per append once the log
    saturates).  Three passes: (1) per (fingerprint, candidate) pair,
    drop all but the newest :data:`_COMPACT_KEEP_PAIR` records — the
    median the calibration reads is computed over a pair's newest ratios,
    so trimming a pair's deep history leaves its factor stable; (2) if
    still over the watermark, evict whole pairs, least-recently-written
    first — but never the last one; (3) a lone surviving pair still over
    the watermark trims to its newest records.  The log is never wiped
    outright, whatever the cap.  Atomic rewrite (tmp + rename); returns
    the surviving record count."""
    with _log_lock(path):
        return _compact_locked(path, cap)


def _compact_locked(path: str, cap: int) -> int:
    try:
        with open(path) as f:
            lines = [ln for ln in (l.strip() for l in f) if ln]
    except OSError:
        return 0
    if len(lines) <= cap:
        return len(lines)
    target = max(1, cap - max(1, cap // 10))
    parsed: list = []
    for i, ln in enumerate(lines):
        try:
            r = json.loads(ln)
        except json.JSONDecodeError:
            continue  # a torn line never survives compaction
        parsed.append((i, (r.get("fingerprint"), r.get("candidate")), ln))
    by_pair: dict = {}
    for i, pair, ln in parsed:
        by_pair.setdefault(pair, []).append((i, ln))
    # pass 1: newest records per pair (file order = age order); a tiny
    # cap bounds the per-pair tail too, so one pair cannot overflow it
    keep = max(1, min(_COMPACT_KEEP_PAIR, target))
    kept_pairs = {p: rows[-keep:] for p, rows in by_pair.items()}
    # pass 2: whole-pair eviction, least-recently-written pair first
    pairs_by_recency = sorted(kept_pairs, key=lambda p: kept_pairs[p][-1][0])
    total = sum(len(rows) for rows in kept_pairs.values())
    for p in pairs_by_recency:
        if total <= target or len(kept_pairs) == 1:
            break
        total -= len(kept_pairs.pop(p))
    if total > target:  # pass 3: one pair left — trim, never wipe
        p = next(iter(kept_pairs))
        kept_pairs[p] = kept_pairs[p][-target:]
    survivors = sorted(
        (row for rows in kept_pairs.values() for row in rows),
        key=lambda row: row[0],
    )
    tmp = f"{path}.compact.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write("".join(ln + "\n" for _i, ln in survivors))
    os.replace(tmp, path)
    return len(survivors)


#: floor on one serialized outcome record's size — the unit converting
#: "entries of headroom" into "bytes of growth" for the cap prechecks.
_MIN_RECORD_BYTES = 64

#: path -> byte size below which the file PROVABLY holds <= cap entries
#: (set after each count: current size + headroom * _MIN_RECORD_BYTES).
#: Bounds the O(entries) recount to once per cap's-worth of growth
#: instead of once per append — the append path is a solve's finish path.
_compact_skip: dict[str, int] = {}


def append_outcome(record: dict) -> None:
    """Best-effort append of one plan outcome to the persistent log,
    compacting first when the log exceeds ``KEYSTONE_PLAN_LOG_MAX``
    entries (oldest records give way; per-pair median tails survive).  A
    broken log path — or a malformed cap env — degrades counted
    (``plan_log_write_failed``): the solve's result never depends on
    telemetry landing."""
    path = plan_log_path()
    if path is None:
        return
    try:
        parent = os.path.dirname(os.path.abspath(path))
        if parent:
            os.makedirs(parent, exist_ok=True)
        cap = plan_log_max()
        with _log_lock(path):
            # The whole cap-check + compact + append sequence holds the
            # log lock, so compaction's read-rewrite-replace can never
            # swallow a record another process appends concurrently.
            if cap is not None and os.path.exists(path):
                size = os.path.getsize(path)
                floor = max(
                    cap * _MIN_RECORD_BYTES, _compact_skip.get(path, 0)
                )
                if size > floor:
                    kept = _compact_locked(path, cap)
                    # Convert the entry headroom the watermark bought
                    # into bytes of growth using the OBSERVED mean record
                    # size (floored at _MIN_RECORD_BYTES) — real records
                    # carry the feature vector and run ~400-600 bytes, so
                    # the 64-byte floor alone would re-trigger the
                    # O(entries) recount within a couple of appends on a
                    # saturated log.
                    size_now = os.path.getsize(path)
                    rec_bytes = max(
                        _MIN_RECORD_BYTES, size_now // max(1, kept)
                    )
                    _compact_skip[path] = size_now + (
                        max(0, cap - kept) * rec_bytes
                    )
            with open(path, "a") as f:
                f.write(json.dumps(record) + "\n")
    except (OSError, ValueError) as e:
        counters.record("plan_log_write_failed", f"{path}: {e}")


#: path -> parsed records, filled once per process (see module docstring:
#: in-process stability is what keeps baseline-vs-faulted comparisons
#: bit-equal; fresh measurements train the NEXT process).
_outcome_cache: dict[str, list] = {}


def load_outcomes(path: str | None = None) -> list[dict]:
    path = path if path is not None else plan_log_path()
    if path is None:
        return []
    cached = _outcome_cache.get(path)
    if cached is not None:
        return cached
    records: list[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # a torn tail line is not an error
    except OSError:
        records = []
    records = records[-_MAX_LOG_RECORDS:]
    _outcome_cache[path] = records
    return records


def clear_outcome_cache() -> None:
    """Test seam: forget the once-per-process log read."""
    _outcome_cache.clear()
    _ratio_cache.clear()
    _model_cache.clear()
    _drift_model_cache.clear()
    _compact_skip.clear()


#: path -> ({(fingerprint, candidate): ratios}, {fingerprint: ratios},
#: model_rows) — one pass over the log per process instead of a rescan per
#: candidate (the search's O(candidates) calibration lookups must stay
#: O(1) against a log grown toward _MAX_LOG_RECORDS, or the scan itself
#: would eat the <5% search-overhead budget).
_ratio_cache: dict[str, tuple[dict, dict, list]] = {}

#: path -> fitted cross-program model (or None when the log cannot
#: support one) — the regression is fit once per process, like the read.
_model_cache: dict[str, object] = {}


def _ratio_index(path: str | None) -> tuple[dict, dict, list]:
    key = path if path is not None else (plan_log_path() or "")
    cached = _ratio_cache.get(key)
    if cached is not None:
        return cached
    by_pair: dict = {}
    by_fp: dict = {}
    rows: list = []
    for r in load_outcomes(path):
        if not (
            r.get("outcome") == "ok"
            and r.get("predicted_seconds")
            and r.get("measured_seconds")
        ):
            continue
        # The regression learns measured vs the RAW analytic prior (the
        # quantity features describe); pre-calibration records fall back
        # to predicted (factor 1.0 at the time, so the two coincide).
        ratio = r["measured_seconds"] / r["predicted_seconds"]
        fp = r.get("fingerprint")
        by_pair.setdefault((fp, r.get("candidate")), []).append(ratio)
        by_fp.setdefault(fp, []).append(ratio)
        feats = r.get("features")
        raw = r.get("raw_seconds")
        if isinstance(feats, dict) and feats:
            rows.append((
                fp,
                feats,
                r["measured_seconds"] / raw if raw else ratio,
            ))
    _ratio_cache[key] = (by_pair, by_fp, rows)
    return by_pair, by_fp, rows


def model_rows(path: str | None = None) -> list:
    """The cross-program training rows the log holds:
    ``[(fingerprint, features, measured/raw_ratio)]`` over successful
    outcomes that carried a feature vector (bench drives the
    trained-on-A-predicted-on-B error from these)."""
    return list(_ratio_index(path)[2])


# -- the HBM watermark drift calibration (ISSUE 14) ----------------------------

#: path -> fitted byte-drift model (or None) — like _model_cache, read and
#: fit once per process; fresh drift rows train the NEXT process.
_drift_model_cache: dict[str, object] = {}


def hbm_features(
    argument_bytes: float,
    temp_bytes: float,
    output_bytes: float,
    mesh_axes: dict | None,
) -> dict:
    """Featurize one program's CHARGED byte composition for the byte-drift
    calibration — the same vector shape whether the row comes from a
    watermark audit (``core.profiler.audit_plan``, the MemoryPlan side) or
    a search candidate's hints (the scoring side), so train and predict
    can never drift apart on feature semantics."""
    charged = float(argument_bytes) + float(temp_bytes) + float(output_bytes)
    return {
        "kind": "hbm",
        "log_charged": float(np.log1p(charged)),
        "log_args": float(np.log1p(float(argument_bytes))),
        "log_temp": float(np.log1p(float(temp_bytes))),
        "log_out": float(np.log1p(float(output_bytes))),
        "data_axis": float((mesh_axes or {}).get("data", 1)),
        "model_axis": float((mesh_axes or {}).get("model", 1)),
    }


def drift_rows(path: str | None = None) -> list:
    """The plan-vs-actual HBM drift evidence the log holds:
    ``[(fingerprint, features, watermark/charged_ratio)]`` over the
    ``outcome:"hbm_drift"`` rows ``core.profiler.audit_plan`` appends —
    the byte-side analog of :func:`model_rows`."""
    rows = []
    for r in load_outcomes(path):
        if r.get("outcome") != "hbm_drift":
            continue
        ratio = r.get("drift_ratio")
        feats = r.get("features")
        if ratio and ratio > 0 and isinstance(feats, dict) and feats:
            rows.append((r.get("fingerprint"), feats, float(ratio)))
    return rows


def _drift_model(path: str | None = None):
    """The fitted byte-drift calibration (optimize.CalibrationModel over
    :func:`drift_rows`), or None when the log holds too little evidence —
    same thresholds as the time model, and the same empty-log guarantee:
    no drift rows means factor 1.0 everywhere, so an untrained search
    still reproduces the hand ladder bit-for-bit."""
    key = path if path is not None else (plan_log_path() or "")
    if key in _drift_model_cache:
        return _drift_model_cache[key]
    rows = drift_rows(path)
    model = None
    if (
        len(rows) >= kopt.MIN_MODEL_ROWS
        and len({fp for fp, _f, _r in rows}) >= 2
    ):
        model = kopt.CalibrationModel.fit_rows(rows)
    _drift_model_cache[key] = model
    return model


def drift_factor(features: dict, path: str | None = None) -> float:
    """Predicted watermark/charged ratio for one byte-composition feature
    vector (1.0 with no trained model)."""
    model = _drift_model(path)
    if model is None:
        return 1.0
    return model.predict_factor(features)


def _cross_program_model(path: str | None):
    """The fitted cross-program calibration (core.optimize
    CalibrationModel), or ``None`` when the log holds too few featurized
    outcomes or only one program — transfer needs >= 2 fingerprints by
    definition, and a single-program fit would just shadow the pooled
    median with extra variance."""
    key = path if path is not None else (plan_log_path() or "")
    if key in _model_cache:
        return _model_cache[key]
    rows = _ratio_index(path)[2]
    model = None
    if (
        len(rows) >= kopt.MIN_MODEL_ROWS
        and len({fp for fp, _f, _r in rows}) >= 2
    ):
        model = kopt.CalibrationModel.fit_rows(rows)
    _model_cache[key] = model
    return model


def plan_features(kind: str, mesh_axes: dict | None, hints: dict) -> dict:
    """Featurize one candidate for the cross-program calibration model:
    log-domain operand bytes / FLOPs / dispatches / transfer volumes, the
    mesh factorization, the arithmetic intensity the roofline prior sees,
    and the strategy kind — the quantities that transfer between solve
    shapes, unlike a (fingerprint, candidate) key."""
    b = lambda k: float(hints.get(k, 0) or 0)  # noqa: E731
    touched = b("arg_bytes") + b("temp_bytes") + b("out_bytes")
    flops = b("flops")
    feats = {
        "kind": kind,
        "log_bytes": float(np.log1p(touched)),
        "log_flops": float(np.log1p(flops)),
        "log_dispatches": float(np.log1p(b("dispatches") or 1.0)),
        "log_h2d": float(np.log1p(b("h2d_bytes"))),
        "log_coll": float(np.log1p(b("coll_bytes"))),
        "log_ai": float(np.log((flops + 1.0) / (touched + 1.0))),
        "data_axis": float((mesh_axes or {}).get("data", 1)),
        "model_axis": float((mesh_axes or {}).get("model", 1)),
    }
    return feats


def calibrate(
    fp: str,
    candidate: str,
    features: dict | None = None,
    path: str | None = None,
) -> tuple[float, int, str]:
    """``(factor, direct_samples, source)`` for one candidate.

    Priority ladder — most specific evidence first, each rung a strict
    superset of what the rung below knows:

    1. **direct** — >= :data:`MIN_TRAIN` measured outcomes of THIS
       (fingerprint, candidate) pair: their median ratio (the PR 9 rule,
       and the only rung that tightens the ranking margin);
    2. **model** — the cross-program regression
       (:func:`_cross_program_model`) evaluated on the candidate's
       features: learning from OTHER programs/shapes transfers here;
    3. **pooled** — the program-level median (every candidate of the
       fingerprint pooled): a CONSTANT factor across uncalibrated
       siblings, shifting absolute predictions toward honesty without
       reordering them;
    4. **none** — factor 1.0 (the raw analytic prior stands).

    Training is one-sided — only plans that actually RAN log outcomes —
    which is why rungs 2-3 exist: without them the measured winner would
    absorb its real slowdown while unmeasured competitors kept optimistic
    raw priors, and the ranking would drift toward whatever never ran.
    The returned sample count is the DIRECT count — it drives the
    per-pair trained margin, which no fallback rung may tighten."""
    by_pair, by_fp, _rows = _ratio_index(path)
    direct = by_pair.get((fp, candidate), ())
    if len(direct) >= MIN_TRAIN:
        return float(np.median(direct)), len(direct), "direct"
    if features is not None:
        model = _cross_program_model(path)
        if model is not None:
            return model.predict_factor(features), len(direct), "model"
    pooled = by_fp.get(fp, ())
    if len(pooled) >= MIN_TRAIN:
        return float(np.median(pooled)), len(direct), "pooled"
    return 1.0, len(direct), "none"


def calibration(fp: str, candidate: str, path: str | None = None) -> tuple[float, int]:
    """Back-compat view of :func:`calibrate` without features (direct ->
    pooled -> 1.0): ``(factor, direct_samples)``."""
    factor, n, _source = calibrate(fp, candidate, path=path)
    return factor, n


# -- candidates and the plan record --------------------------------------------


@dataclasses.dataclass
class Candidate:
    """One executable placement: a mesh shape (or none) x execution
    strategy, with the lazy compiled preflight / run closures the ladder
    consumes and the analytic cost hints the search scores."""

    name: str
    kind: str  #: "fused_mesh" | "fused" | "stepwise" | "host_staged" | ...
    plan: Callable[[], "kmem.MemoryPlan"]
    run: Callable[["kmem.MemoryPlan"], Any]
    #: analytic per-chip cost hints (CostModel.predict_seconds keys) plus
    #: the prune figures plan_bytes charges (arg/temp/out/extra/resident).
    hints: dict = dataclasses.field(default_factory=dict)
    mesh_axes: dict | None = None
    prior_rank: int = 0  #: hand-ladder position (ties resolve to this)
    floor: bool = False  #: the resilience backstop — always ranked last
    hand: bool = True  #: hand-ladder member (its prunes land in FitReport)
    #: per-operand sharding-spec assignment this candidate EXECUTES
    #: (operand name -> spec string, e.g. {"labels": "model@dim1"});
    #: ``None`` = the strategy's default layout.  The solver's run closure
    #: lowers these through :func:`spec_sharding` — the same strings the
    #: hints' byte accounting charged.
    specs: dict | None = None


@dataclasses.dataclass
class CandidateRecord:
    """One row of the plan's candidate table — the deny/score rationale."""

    name: str
    kind: str
    mesh: dict | None
    prior_rank: int
    pruned: bool
    reason: str  #: deny reason when pruned, score rationale otherwise
    predicted_seconds: float | None = None
    raw_seconds: float | None = None  #: analytic prior before calibration
    calibration: float = 1.0
    samples: int = 0  #: DIRECT measured outcomes behind the calibration
    #: which rung produced the factor: "direct" | "model" | "pooled" | "none"
    calibration_source: str = "none"
    #: watermark-drift calibration applied to the scored temp bytes
    #: (1.0 = no trained byte-drift model; see autoshard.drift_factor)
    byte_drift: float = 1.0
    rank: int | None = None  #: position in the execution ranking
    measured_seconds: float | None = None  #: filled when this plan RAN
    outcome: str | None = None  #: "ok" | "oom" | "denied" after the run
    #: the spec assignment this candidate executes (None = default layout)
    specs: dict | None = None
    #: cross-program feature vector (what the calibration model consumed
    #: and the outcome log persists for the NEXT process's training)
    features: dict | None = None

    def record(self) -> dict:
        out = dataclasses.asdict(self)
        for k in ("predicted_seconds", "raw_seconds", "measured_seconds"):
            if out[k] is not None:
                out[k] = round(out[k], 6)
        out["calibration"] = round(self.calibration, 4)
        if out["features"] is not None:
            out["features"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in out["features"].items()
            }
        return out


@dataclasses.dataclass
class PlacementPlan:
    """The search's audit trail (FitReport's placement leg): every
    enumerated candidate with its deny/score rationale, the ranking that
    actually executed, and the chosen plan's predicted-vs-actual cost."""

    label: str
    fingerprint: str
    devices: str
    trained: bool
    margin: float
    candidates: list  #: list[CandidateRecord], prior order
    ranking: list  #: candidate names, execution order (floor last)
    search_seconds: float = 0.0
    chosen: str | None = None
    predicted_seconds: float | None = None
    measured_seconds: float | None = None
    prediction_error: float | None = None  #: predicted / measured
    #: name -> the zero-cost analytic MemoryPlan the batch preflight
    #: produced (pruned candidates hand it straight to the ladder walk —
    #: a pruned plan is denied for free, never re-planned or compiled).
    analytic_plans: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )

    def candidate(self, name: str) -> CandidateRecord | None:
        for c in self.candidates:
            if c.name == name:
                return c
        return None

    def record(self) -> dict:
        return {
            "label": self.label,
            "fingerprint": self.fingerprint,
            "devices": self.devices,
            "trained": self.trained,
            "margin": self.margin,
            "search_seconds": round(self.search_seconds, 6),
            "ranking": list(self.ranking),
            "chosen": self.chosen,
            "predicted_seconds": (
                round(self.predicted_seconds, 6)
                if self.predicted_seconds is not None else None
            ),
            "measured_seconds": (
                round(self.measured_seconds, 6)
                if self.measured_seconds is not None else None
            ),
            "prediction_error": (
                round(self.prediction_error, 4)
                if self.prediction_error is not None else None
            ),
            "candidates": [c.record() for c in self.candidates],
        }

    def to_json(self) -> str:
        return json.dumps(self.record())

    def summary(self) -> str:
        s = (
            f"autoshard {self.label}[{self.fingerprint}]: "
            f"{len(self.ranking)}/{len(self.candidates)} candidates ranked"
            f" ({'trained' if self.trained else 'untrained'} margin "
            f"{self.margin}x), head={self.ranking[0] if self.ranking else None}"
        )
        if self.chosen is not None:
            s += f", chosen={self.chosen}"
        if self.prediction_error is not None:
            s += f", prediction_error={self.prediction_error:.2f}x"
        return s


# -- search + ranked execution -------------------------------------------------


def _margin_order(body: list) -> list:
    """Margin-aware selection order over ``(Candidate, CandidateRecord)``
    pairs: at each step, among the remaining candidates whose predicted
    cost is within the confidence margin of the CHEAPEST remaining one,
    the lowest prior (hand) rank wins.  Relative margins (not absolute
    buckets — two scores a hair apart must never split across a bucket
    edge and reorder) and per-pair trained-ness: the tight
    :data:`TRAINED_MARGIN` applies only when BOTH the candidate and the
    cheapest one carry >= :data:`MIN_TRAIN` direct measurements."""
    ordered: list = []
    remaining = sorted(body, key=lambda sr: sr[1].prior_rank)
    while remaining:
        best = min(remaining, key=lambda sr: (sr[1].predicted_seconds,
                                              sr[1].prior_rank))
        def margin(sr, best=best):
            both_trained = (
                sr[1].samples >= MIN_TRAIN and best[1].samples >= MIN_TRAIN
            )
            return TRAINED_MARGIN if both_trained else UNTRAINED_MARGIN

        pick = min(
            (
                sr for sr in remaining
                if sr[1].predicted_seconds
                <= best[1].predicted_seconds * margin(sr)
            ),
            key=lambda sr: sr[1].prior_rank,
        )
        ordered.append(pick)
        remaining.remove(pick)
    return ordered


def search(
    label: str,
    candidates: Sequence[Candidate],
    *,
    fingerprint: str,
    budget: int | None | object = kmem._UNSET,
    model: "kopt.CostModel | None" = None,
) -> PlacementPlan:
    """Enumerate -> prune -> score -> rank.  Pure decision pass: nothing is
    compiled and nothing runs — see :func:`run_search` for execution."""
    t0 = time.perf_counter()
    with trace.host("search", "autoshard.search", label=label):
        model = model if model is not None else kopt.CostModel.for_devices()
        records: list[CandidateRecord] = []
        survivors: list[tuple[Candidate, CandidateRecord]] = []
        # 1. zero-cost batch preflight: analytic per-chip bytes vs budget.
        analytic = kmem.plan_batch([
            (
                c.name,
                lambda c=c: kmem.plan_bytes(
                    f"autoshard:{c.name}",
                    # LOWER bound of the compiled admission (see
                    # plan_bytes): donated/aliased argument bytes are
                    # credited out so the prune can never deny a plan the
                    # full preflight would admit.
                    argument_bytes=max(
                        0,
                        c.hints.get("arg_bytes", 0)
                        - c.hints.get("alias_bytes", 0),
                    ),
                    temp_bytes=c.hints.get("temp_bytes", 0),
                    extra_bytes=c.hints.get("extra_bytes", 0),
                    resident_bytes=c.hints.get("resident_bytes", 0),
                    budget=budget,
                ),
            )
            for c in candidates
        ])
        trained = True
        for c in candidates:
            mp = analytic[c.name]
            rec = CandidateRecord(
                name=c.name,
                kind=c.kind,
                mesh=dict(c.mesh_axes) if c.mesh_axes else None,
                prior_rank=c.prior_rank,
                pruned=not mp.admitted and not c.floor,
                reason=mp.reason,
                specs=dict(c.specs) if c.specs else None,
            )
            records.append(rec)
            if rec.pruned:
                rec.outcome = "denied"
                continue
            # 2. score: analytic roofline prior x learned calibration
            # (direct median, else the cross-program feature regression,
            # else the program-pooled median — see calibrate()).  The
            # scored TEMP bytes first pass through the byte-drift
            # calibration learned from HBM watermark audits
            # (core.profiler.audit_plan rows): a program family whose
            # transients the analytic floor consistently under-charges
            # scores its real HBM traffic.  Factor 1.0 (exact) with no
            # trained drift model — the empty-log bit-for-bit guarantee.
            hints = c.hints
            dfac = drift_factor(hbm_features(
                hints.get("arg_bytes", 0),
                hints.get("temp_bytes", 0),
                hints.get("out_bytes", 0),
                c.mesh_axes,
            ))
            if dfac != 1.0:
                hints = dict(hints)
                hints["temp_bytes"] = hints.get("temp_bytes", 0) * dfac
            rec.byte_drift = round(dfac, 4)
            raw = model.predict_seconds(hints)
            feats = plan_features(c.kind, c.mesh_axes, c.hints)
            factor, samples, source = calibrate(
                fingerprint, c.name, features=feats
            )
            rec.raw_seconds = raw
            rec.calibration = factor
            rec.samples = samples
            rec.calibration_source = source
            rec.features = feats
            rec.predicted_seconds = raw * factor
            if samples < MIN_TRAIN:
                trained = False
            survivors.append((c, rec))
        # 3. rank: within-margin candidates keep their prior order (the
        # tight margin only for measured-vs-measured pairs), floor pinned
        # last.  ``margin`` on the plan reports the factor the HEAD
        # comparison got.
        margin = TRAINED_MARGIN if trained and survivors else UNTRAINED_MARGIN
        body = [sr for sr in survivors if not sr[0].floor]
        floor = [sr for sr in survivors if sr[0].floor]
        ordered = _margin_order(body) + sorted(
            floor, key=lambda sr: sr[1].prior_rank
        )
        for i, (c, rec) in enumerate(ordered):
            rec.reason = (
                f"rank {i}: predicted {rec.predicted_seconds:.4g}s "
                f"(prior {rec.raw_seconds:.4g}s x calibration "
                f"{rec.calibration:.3g} [{rec.calibration_source}] from "
                f"{rec.samples} direct outcome(s))"
                + (" [floor: pinned last]" if c.floor else "")
            )
        # Pruned HAND candidates stay in the execution order at their hand
        # position (their cached analytic deny is handed to the ladder walk
        # — rejected for free, and the FitReport's denial ORDER matches the
        # hand contract exactly).  Pruned EXTRA candidates are dropped: the
        # search enumerated them, the placement table shows why they lost,
        # and the hand report's shape stays untouched.
        ranking: list[tuple] = list(ordered)
        by_name = {c.name: c for c in candidates}
        pruned_hand = [
            r for r in records if r.pruned and by_name[r.name].hand
        ]
        for rec in sorted(pruned_hand, key=lambda r: r.prior_rank):
            at = len(ranking)
            for i, (rc, _rrec) in enumerate(ranking):
                if rc.floor or (rc.hand and rc.prior_rank > rec.prior_rank):
                    at = i
                    break
            ranking.insert(at, (by_name[rec.name], rec))
        for i, (_c, rec) in enumerate(ranking):
            rec.rank = i
        plan = PlacementPlan(
            label=label,
            fingerprint=fingerprint,
            devices=device_fingerprint(),
            trained=trained,
            margin=margin if survivors else UNTRAINED_MARGIN,
            candidates=records,
            ranking=[rec.name for _, rec in ranking],
            search_seconds=time.perf_counter() - t0,
            analytic_plans={
                rec.name: analytic[rec.name] for rec in records if rec.pruned
            },
        )
        trace.instant(
            "autoshard_plan",
            label=label,
            fingerprint=fingerprint,
            ranking=plan.ranking,
            pruned=[r.name for r in records if r.pruned],
            trained=trained,
        )
        # How many searches a stage ran and how long they took are the open
        # stage's ``stage_host_n.<stage>.search`` / ``stage_host_ms.<stage>.search``
        # (the ``search`` section above); the instant carries ``trained``.
        _logger.info("%s", plan.summary())
    return plan


def will_search(plan_arg) -> bool:
    """Whether ``fit(plan=plan_arg)`` will run the placement search — the
    solvers' guard for skipping candidate-enumeration work (building a
    jax Mesh per device factorization) that a hand-ladder walk would
    discard unused."""
    return _resolve(plan_arg)[0]


def _resolve(plan_arg) -> tuple[bool, list | None]:
    """``fit(plan=...)`` semantics -> (search?, forced ranking names)."""
    if plan_arg is None:
        return enabled(), None
    if plan_arg is False:
        return False, None
    if plan_arg is True:
        return True, None
    if isinstance(plan_arg, PlacementPlan):
        return True, list(plan_arg.ranking)
    if isinstance(plan_arg, (list, tuple)):
        return True, [str(n) for n in plan_arg]
    raise TypeError(
        f"fit(plan=...) wants None/bool/PlacementPlan/name list, got "
        f"{type(plan_arg).__name__}"
    )


def run_search(
    label: str,
    candidates: Sequence[Candidate],
    report: "kmem.FitReport",
    *,
    fingerprint: str,
    plan=None,
    budget: int | None | object = kmem._UNSET,
    model: "kopt.CostModel | None" = None,
):
    """The solvers' one entry point: search (or honor the ``plan``
    override), then drive the RANKED candidate list through
    ``core.memory.run_ladder`` — the same per-tier compiled admission and
    one-plan-at-a-time OOM step-down contract the hand ladders obey, now
    over the searched order.  Attaches the finished :class:`PlacementPlan`
    record to ``report.placement``, appends outcomes to the plan log, and
    counts every step off the top-ranked plan under ``autoshard_stepdown``.
    """
    do_search, forced = _resolve(plan)
    report.fingerprint = fingerprint
    by_prior = sorted(candidates, key=lambda c: c.prior_rank)
    if not do_search:
        tiers = [
            kmem.Tier(c.name, c.plan, c.run)
            for c in by_prior
            if c.hand  # the hand ladder is exactly the hand candidates
        ]
        return kmem.run_ladder(label, tiers, report)

    placement = search(
        label, candidates, fingerprint=fingerprint, budget=budget, model=model
    )
    if forced is not None:
        known = {c.name for c in candidates}
        ranking = [n for n in forced if n in known]
        # anything the override did not name keeps its searched order
        ranking += [n for n in placement.ranking if n not in ranking]
        # the floor stays the backstop even under a forced ranking
        floors = [c.name for c in by_prior if c.floor and c.name in ranking]
        ranking = [n for n in ranking if n not in floors] + floors
        placement.ranking = ranking
        # Re-stamp the audit table to the order that will EXECUTE — the
        # searched rank/reason would otherwise contradict the replay.
        for rec in placement.candidates:
            rec.rank = None
        for i, name in enumerate(ranking):
            rec = placement.candidate(name)
            if rec is None:
                continue
            rec.rank = i
            if rec.predicted_seconds is not None:
                rec.reason = (
                    f"rank {i} (forced replay): predicted "
                    f"{rec.predicted_seconds:.4g}s (prior "
                    f"{rec.raw_seconds:.4g}s x calibration "
                    f"{rec.calibration:.3g} from {rec.samples} outcome(s))"
                )

    by_name = {c.name: c for c in candidates}
    measured: dict[str, float] = {}

    def wrap(c: Candidate) -> kmem.Tier:
        cached_deny = placement.analytic_plans.get(c.name)
        # A pruned candidate's walk "plan" IS the search's analytic deny —
        # denied for free, never compiled; the ladder records the denial
        # at its hand position like any preflight-denied tier.
        plan_fn = (
            (lambda: cached_deny) if cached_deny is not None else c.plan
        )

        def run(mplan):
            rec = placement.candidate(c.name)
            t0 = time.perf_counter()
            with trace.host(
                "dispatch",
                f"plan:{c.name}",
                predicted_s=rec.predicted_seconds if rec else None,
                label=label,
                rank=rec.rank if rec else None,
                specs=spec_tag(rec.specs if rec else None),
            ):
                try:
                    out = c.run(mplan)
                    # Sync before reading the clock: a fused program's run
                    # returns async-dispatched arrays, so an unsynced
                    # measurement records ~0s dispatch time — garbage that
                    # would train the calibration model toward "free".
                    # The sync also surfaces an ASYNC runtime
                    # RESOURCE_EXHAUSTED here, inside the ladder's try,
                    # so it steps down counted instead of escaping at the
                    # caller's first use of the result.
                    _block_until_ready(out)
                except Exception:
                    measured[c.name] = time.perf_counter() - t0
                    raise
            measured[c.name] = time.perf_counter() - t0
            if kprof.enabled() and mplan is not None:
                # Audit the hand-derived flops hint against the compiled
                # program's own cost_analysis (ISSUE 14): single-device
                # candidates only — SPMD modules report per-device numbers
                # whose hint mapping is mesh-dependent, and a misleading
                # audit would be worse than none.  Mismatch beyond the
                # tolerance factor is counted, never silent.
                chips = 1
                for v in (c.mesh_axes or {}).values():
                    chips *= int(v)
                if chips == 1:
                    kprof.audit_flops(
                        f"{label}:{c.name}",
                        c.hints.get("flops"),
                        getattr(mplan, "compiled", None),
                    )
            return out

        return kmem.Tier(c.name, plan_fn, run)

    tiers = [wrap(by_name[n]) for n in placement.ranking if n in by_name]
    try:
        out = kmem.run_ladder(label, tiers, report)
    finally:
        with trace.host("finish", "autoshard.finish", label=label):
            _finish(placement, report, measured, fingerprint, label)
    return out


def _block_until_ready(out) -> None:
    """Best-effort sync on a tier run's result pytree (measurement
    honesty + async-OOM surfacing; a result that cannot sync — or no
    live backend — is not an error)."""
    try:
        trace.wait(out, "solve")
    except Exception as e:  # noqa: BLE001 — only OOM matters here
        if kmem.is_oom_error(e):
            raise


def _finish(placement, report, measured, fp, label) -> None:
    """Post-run bookkeeping: predicted-vs-actual on the plan, outcome rows
    to the log, step-downs counted."""
    placement.chosen = report.chosen
    for name, secs in measured.items():
        rec = placement.candidate(name)
        if rec is None:
            continue
        rec.measured_seconds = secs
        # Only a genuine RESOURCE_EXHAUSTED step-down (run_ladder's
        # oom_retries) is a memory misprediction; a typed non-OOM failure
        # that propagated must not masquerade as one in the audit trail
        # or the plan log.
        if name == report.chosen:
            rec.outcome = "ok"
        elif name in report.oom_retries:
            rec.outcome = "oom"
        else:
            rec.outcome = "error"
        append_outcome({
            "fingerprint": fp,
            "label": label,
            "candidate": name,
            "predicted_seconds": rec.predicted_seconds,
            "raw_seconds": rec.raw_seconds,
            "measured_seconds": secs,
            "outcome": rec.outcome,
            "devices": placement.devices,
            "specs": rec.specs,
            # the cross-program training row: the NEXT process's
            # CalibrationModel regresses measured/raw on these.
            "features": rec.features,
            "ts": time.time(),
        })
    chosen_rec = (
        placement.candidate(report.chosen) if report.chosen else None
    )
    if chosen_rec is not None:
        placement.predicted_seconds = chosen_rec.predicted_seconds
        placement.measured_seconds = chosen_rec.measured_seconds
        if chosen_rec.predicted_seconds and chosen_rec.measured_seconds:
            placement.prediction_error = (
                chosen_rec.predicted_seconds / chosen_rec.measured_seconds
            )
    for name in report.oom_retries:
        if placement.candidate(name) is not None:
            counters.record(
                "autoshard_stepdown",
                f"{label}: ranked plan {name!r} died RESOURCE_EXHAUSTED at "
                "runtime — stepping down the searched ranking "
                f"(cost-model misprediction logged for {fp})",
            )
    report.placement = placement.record()
