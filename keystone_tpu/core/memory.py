"""HBM admission control: preflight memory planning + graceful degradation.

VERDICT r5's top finding was that the flagship fused solvers discovered OOM
as a bare ``RESOURCE_EXHAUSTED`` at execution time — a 4 GB design matrix
failing on a 16 GB chip with nothing saying *whose* memory died.  KeystoneML
never had this failure mode because Spark's block manager admitted or
spilled every cached partition against a known executor budget; this module
is that admission-control discipline rebuilt for a single-controller JAX
stack:

* :func:`hbm_budget` — the byte budget a fit may plan against:
  ``KEYSTONE_HBM_BUDGET`` (testing / policy override) or the live device's
  ``memory_stats()`` free bytes; ``None`` when neither is known (CPU
  backends), in which case admission is skipped, never guessed.
* :class:`MemoryPlan` / :func:`plan_program` — AOT-lower a candidate
  program on ``jax.ShapeDtypeStruct``s (NO data is allocated to plan),
  read ``compiled.memory_analysis()`` (argument/temp/output/alias bytes),
  add the caller's accounting of persistent buffers the program's argument
  list does not see (``extra_bytes``), and return admit/deny with the full
  breakdown.  An OOM is thereby diagnosed *before* execution, with numbers.
* :func:`run_ladder` — the graceful-degradation driver: an ordered list of
  :class:`Tier`\\ s (e.g. fused one-program → stepwise per-block →
  host-staged streaming) is walked with per-tier preflight; a denied tier
  is skipped with its reason counted, an admitted tier that still dies with
  ``RESOURCE_EXHAUSTED`` at runtime steps down exactly one tier instead of
  failing the fit.  The last tier is the floor — it runs even if its own
  preflight is pessimistic, because there is nothing below it.
* :class:`FitReport` — the audit trail (per-tier plans, chosen tier,
  denials, OOM retries) estimators expose as ``last_fit_report`` and the
  bench emits verbatim, so the OOM boundary is measured, not guessed.
* **Mesh mode** — ``plan_program(mesh=...)`` models a GSPMD program
  per chip: ``NamedSharding``-annotated avals charge their SHARD's bytes
  (replicated operands charge whole, conservatively), admission runs
  against the MINIMUM per-chip free HBM across ``mesh.devices``
  (:func:`min_chip_budget`), and the compiled SPMD module's own per-device
  ``memory_analysis()`` rides along as ground truth (``plan.reported``).
  The solvers' mesh ladders use it to step full mesh → reduced-model mesh
  → the single-device ladder instead of dying on one tight chip.

Temp-size caveat: CPU backends report ``temp_size_in_bytes == 0``, which
would make a fused program look cheaper than its own stepwise decomposition.
Callers that know a program's true transient floor pass it as
``min_temp_bytes``; the plan uses ``max(reported, analytic)``.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import re
import time
from typing import Any, Callable, Sequence

import jax

from . import profiler
from . import trace
from .resilience import counters

_logger = logging.getLogger("keystone_tpu.memory")

#: env var: byte budget override ("2G", "512M", "1.5T", or plain bytes).
HBM_BUDGET_ENV = "KEYSTONE_HBM_BUDGET"

_SUFFIX = {"": 1, "K": 2**10, "M": 2**20, "G": 2**30, "T": 2**40}


def parse_bytes(spec: str | int | float) -> int:
    """``"16G"`` / ``"512M"`` / ``"1.5GB"`` / ``4096`` -> bytes."""
    if isinstance(spec, (int, float)):
        return int(spec)
    m = re.fullmatch(
        r"\s*([0-9]+(?:\.[0-9]+)?)\s*([KMGT]?)I?B?\s*", str(spec).upper()
    )
    if not m:
        raise ValueError(
            f"cannot parse byte size {spec!r} (expected e.g. '16G', '512M', "
            "'1.5GB', or a plain byte count)"
        )
    return int(float(m.group(1)) * _SUFFIX[m.group(2)])


def fmt_bytes(b: int | float) -> str:
    """Human-scaled byte count for log/reason strings ('3.25GB', '514KB')."""
    b = float(b)
    for unit in ("B", "KB", "MB", "GB"):
        if abs(b) < 1024 or unit == "GB":
            return f"{b:.2f}{unit}" if unit != "B" else f"{int(b)}B"
        b /= 1024
    return f"{b:.2f}TB"  # pragma: no cover


def budget_is_live() -> bool:
    """True when :func:`hbm_budget` reads LIVE free bytes (device
    ``memory_stats``) rather than the ``KEYSTONE_HBM_BUDGET`` capacity
    override.  The distinction matters for admission: a live free-bytes
    budget already excludes device-resident inputs, so their bytes must be
    credited back out of a plan's total (``plan_program(resident_bytes=)``)
    or a fit whose matrix is already on-chip double-counts it and degrades
    needlessly; a capacity-style env budget must charge them."""
    return not os.environ.get(HBM_BUDGET_ENV, "").strip()


def min_chip_budget(mesh) -> tuple[int | None, Any]:
    """``(budget_bytes, device)``: the SMALLEST per-chip byte budget across
    ``mesh.devices`` and the chip it came from — what a GSPMD program must
    be admitted against, because XLA allocates the sharded program on every
    participating chip and the tightest one is the one that OOMs.

    ``KEYSTONE_HBM_BUDGET`` keeps its override role with PER-CHIP capacity
    semantics (a mesh of 16 GB chips is ``16G``, not ``256G``).  Without the
    env, every device's live ``memory_stats()`` free bytes are read; if ANY
    participating chip cannot report (CPU backends), the answer is
    ``(None, None)`` — admission is skipped, never guessed from a subset of
    the mesh.  On a mesh spanning PROCESSES only the chips addressable
    from this host are consulted — a remote chip's ``memory_stats()``
    cannot be read here, and in a symmetric fleet the local minimum IS the
    per-chip answer; a mesh with no local chips at all answers
    ``(None, None)``."""
    raw = os.environ.get(HBM_BUDGET_ENV, "").strip()
    if raw:
        return parse_bytes(raw), None
    me = jax.process_index()
    local = [d for d in mesh.devices.flat if d.process_index == me]
    if not local:
        return None, None
    worst: int | None = None
    worst_dev = None
    for dev in local:
        free = hbm_budget(dev)
        if free is None:
            return None, None
        if worst is None or free < worst:
            worst, worst_dev = free, dev
    return worst, worst_dev


def shard_bytes(aval, mesh=None) -> int:
    """Per-chip bytes of one array/ShapeDtypeStruct under its sharding.

    A ``NamedSharding``-annotated aval contributes its SHARD's bytes (the
    sharding's per-device ``shard_shape``); anything un-annotated — or
    annotated replicated — contributes its full bytes, the conservative
    fallback (a replicated operand really does occupy full size on every
    chip).  This is the per-axis division the mesh admission model is built
    on: a ``(data=4, model=2)``-sharded design matrix charges 1/4 of its
    global bytes to each chip, its replicated gram factors charge whole."""
    import numpy as np

    n = 1
    for dim in aval.shape:
        n *= int(dim)
    total = n * np.dtype(aval.dtype).itemsize
    sharding = getattr(aval, "sharding", None)
    if sharding is None or not hasattr(sharding, "shard_shape"):
        return total
    try:
        shard = sharding.shard_shape(tuple(aval.shape))
    except Exception:  # noqa: BLE001 — unshardable spec: charge whole
        return total
    m = 1
    for dim in shard:
        m *= int(dim)
    return m * np.dtype(aval.dtype).itemsize


def _device_stats(device) -> tuple[int | None, int]:
    """``(limit, in_use)`` from a device's ``memory_stats()``; the limit is
    ``None`` on backends without stats."""
    try:
        stats = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — backends without stats
        return None, 0
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    return (int(limit) if limit else None), int(stats.get("bytes_in_use", 0))


def hbm_budget(device=None) -> int | None:
    """Bytes a program may plan against, or ``None`` when unknowable.

    Priority: ``KEYSTONE_HBM_BUDGET`` env (tests force degradation tiers
    with it; capacity semantics — resident inputs charge against it) > the
    device's live ``memory_stats()`` free bytes (limit minus in-use — the
    same numbers Spark's block manager admitted against; already-resident
    inputs are credited via ``plan_program(resident_bytes=)``) > ``None``
    (CPU and other backends without stats: admission is skipped, the
    solver runs its first tier exactly as before this module existed).
    """
    raw = os.environ.get(HBM_BUDGET_ENV, "").strip()
    if raw:
        return parse_bytes(raw)
    limit, in_use = _device_stats(device if device is not None else jax.devices()[0])
    return None if limit is None else limit - in_use


def hbm_capacity(device=None) -> int | None:
    """The device's whole byte budget, whatever is in use: the
    ``KEYSTONE_HBM_BUDGET`` override (capacity semantics) > the device's
    ``memory_stats()`` limit > ``None``.  Unlike :func:`hbm_budget`'s free
    bytes it reads the same before every fit of a process, so a rule that
    shapes a compiled program from it picks the same program each time."""
    raw = os.environ.get(HBM_BUDGET_ENV, "").strip()
    if raw:
        return parse_bytes(raw)
    return _device_stats(device if device is not None else jax.devices()[0])[0]


@dataclasses.dataclass
class MemoryPlan:
    """Admit/deny verdict for one candidate program, with the evidence."""

    label: str
    admitted: bool
    reason: str
    budget_bytes: int | None = None
    argument_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0
    alias_bytes: int = 0
    extra_bytes: int = 0  # persistent buffers outside the program's args
    resident_bytes: int = 0  # of total, already allocated on device
    total_bytes: int = 0
    analyzed: bool = False  # False: no compile happened (no budget known)
    #: mesh mode: the (data, model) axis sizes the per-chip numbers assume.
    #: When set, argument/temp/output/total_bytes above are PER-CHIP.
    mesh_axes: dict | None = None
    #: mesh mode: the raw ``memory_analysis()`` numbers of the compiled
    #: SPMD module (XLA's own per-device accounting) kept alongside the
    #: analytic per-axis division, so the admission model is auditable
    #: against ground truth in every record.
    reported: dict | None = None
    error: str | None = None
    compiled: Any = dataclasses.field(default=None, repr=False, compare=False)

    def breakdown(self) -> dict:
        """JSON-able record for bench artifacts (GB, 3 decimals)."""
        gb = lambda b: round(b / 2**30, 3)  # noqa: E731
        out = {
            "admitted": self.admitted,
            "analyzed": self.analyzed,
            "argument_gb": gb(self.argument_bytes),
            "temp_gb": gb(self.temp_bytes),
            "output_gb": gb(self.output_bytes),
            "alias_gb": gb(self.alias_bytes),
            "extra_gb": gb(self.extra_bytes),
            "resident_gb": gb(self.resident_bytes),
            "total_gb": gb(self.total_bytes),
            "budget_gb": gb(self.budget_bytes) if self.budget_bytes else None,
            "reason": self.reason,
        }
        if self.mesh_axes is not None:
            out["per_chip"] = True
            out["mesh"] = dict(self.mesh_axes)
            if self.reported is not None:
                out["xla_reported_gb"] = {
                    k: gb(v) for k, v in self.reported.items()
                }
        if self.error:
            out["error"] = self.error[:200]
        return out


def _admission_event(plan: "MemoryPlan") -> "MemoryPlan":
    """Every admission decision is a point event on the trace timeline:
    charged bytes vs budget, per-chip mesh axes when in mesh mode — the
    trace shows WHY a tier was denied next to the tier spans that ran.
    The event args ARE ``plan.breakdown()`` (the same record bench emits),
    so the two can never drift apart."""
    trace.instant(
        "hbm_admission",
        **{
            "label": plan.label,
            "per_chip": plan.mesh_axes is not None,
            **plan.breakdown(),
        },
    )
    return plan


_UNSET = object()
# (fn, arg signature) -> dict of analysis numbers + compiled object;
# admission is re-evaluated against the CURRENT budget on every call, but the
# AOT lower+compile (the expensive part) happens once per program signature.
# Entries hold the compiled EXECUTABLE (so an admitted plan executes the very
# program that was planned) — callers probing many throwaway shapes (the
# at-scale bench) call clear_plan_cache() afterwards to release them.
_plan_cache: dict = {}


#: label -> number of REAL AOT lower+compiles plan_program performed (cache
#: misses only).  The AOT-reuse contract — "the per-block program compiles
#: exactly once: at preflight" — is asserted against this in the tests.
_compile_counts: dict[str, int] = {}


def compile_count(label: str) -> int:
    """How many times a plan labeled ``label`` actually compiled (plan-cache
    hits don't count — they reuse the executable)."""
    return _compile_counts.get(label, 0)


def clear_plan_cache() -> None:
    """Drop every cached plan analysis AND its compiled executable.  Loaded
    executables can reserve device program memory; probe-style callers
    (bench_solve_at_scale walks five multi-GB shapes) clear the cache once
    the boundary is measured so the reservations don't outlive the probe."""
    _plan_cache.clear()


def _is_array_like(a) -> bool:
    return hasattr(a, "shape") and hasattr(a, "dtype")


def _cache_key(fn, args, kwargs):
    sig = []
    for a in (*args, *sorted(kwargs.items())):
        leaves, treedef = jax.tree_util.tree_flatten(a)
        if leaves and all(_is_array_like(leaf) for leaf in leaves):
            # The sharding is part of the compiled program's identity: the
            # same shapes planned for a (4, 2) mesh and an (8, 1) mesh are
            # different SPMD modules with different per-chip footprints.
            # An argument that is a pytree of arrays (the block solver's
            # ``BlockSource``) is its structure and its leaves' signatures.
            sig.append(("arr", str(treedef)) + tuple(
                (tuple(leaf.shape), str(leaf.dtype), str(getattr(leaf, "sharding", None)))
                for leaf in leaves
            ))
        else:
            sig.append(("static", a))
    return (id(fn), tuple(sig))


def _per_chip_output_bytes(fn, args, kwargs, compiled) -> int | None:
    """Analytic per-chip output bytes of a planned SPMD program: the out
    avals (``eval_shape`` — abstract, allocates nothing) divided by the
    compiled executable's actual output shardings.  ``None`` when either
    side is unavailable (old jaxlib without ``output_shardings``, or a
    tree-shape mismatch) — the caller falls back to XLA's reported number."""
    try:
        out_avals = jax.tree_util.tree_leaves(jax.eval_shape(fn, *args, **kwargs))
        out_shardings = jax.tree_util.tree_leaves(compiled.output_shardings)
        if len(out_avals) != len(out_shardings):
            return None
        total = 0
        for aval, sh in zip(out_avals, out_shardings):
            total += shard_bytes(
                jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sh)
            )
        return total
    except Exception:  # noqa: BLE001 — advisory refinement only
        return None


def plan_program(
    fn,
    *args,
    label: str = "program",
    budget: int | None | object = _UNSET,
    extra_bytes: int = 0,
    min_temp_bytes: int = 0,
    resident_bytes: int = 0,
    require_analysis: bool = False,
    mesh=None,
    **kwargs,
) -> MemoryPlan:
    """Preflight ``fn`` (a ``jax.jit``-wrapped callable) on ``args``.

    ``args`` may be real arrays OR ``jax.ShapeDtypeStruct``s — planning
    allocates nothing.  When a budget is known (or ``require_analysis``),
    the program is AOT lowered+compiled (cached per signature; the returned
    plan carries ``compiled`` so an admitted fused program executes the very
    executable that was planned, not a recompile) and admission compares

        argument + max(temp, min_temp_bytes) + output − alias + extra

    against the budget.  ``resident_bytes`` declares how much of that total
    is ALREADY allocated on device (e.g. a device-resident design matrix
    among the arguments): a live free-bytes budget (:func:`budget_is_live`)
    excludes those bytes from free, so they are credited back before the
    comparison; a capacity-style ``KEYSTONE_HBM_BUDGET`` charges them.
    With no budget and no ``require_analysis`` the plan is a zero-cost
    pass-through: admitted, unanalyzed, reason recorded.  Denials are
    counted under ``hbm_preflight_denied``.

    **Mesh mode** (``mesh=`` a ``jax.sharding.Mesh``): the program is a
    GSPMD solve and every byte figure becomes PER-CHIP.  Arguments and
    outputs are divided by the per-axis sharding of each
    ``NamedSharding``-annotated aval (:func:`shard_bytes`; replicated or
    un-annotated operands conservatively charge full size — they really do
    live whole on every chip), and the default budget is the MINIMUM
    per-chip free HBM across ``mesh.devices`` (:func:`min_chip_budget`;
    ``KEYSTONE_HBM_BUDGET`` overrides with per-chip capacity semantics).
    The compiled SPMD module's own ``memory_analysis()`` — which XLA also
    reports per device — is kept in ``plan.reported`` as the ground truth
    the analytic division is audited against; admission charges the LARGER
    of the two for each category, so a spec the analytic model cannot see
    through (e.g. a resharded intermediate) still cannot under-admit.
    ``resident_bytes`` credit is not modeled per chip; mesh callers pass 0.
    """
    if mesh is not None and budget is _UNSET:
        budget, _worst = min_chip_budget(mesh)
    if budget is _UNSET:
        budget = hbm_budget()
    # With the profiler ON the zero-cost skip still compiles (ISSUE 14):
    # the cost-attribution ledger and the flops audit need the compiled
    # executable's cost_analysis, and the compile is work the admitted
    # tier was about to do anyway (plan.compiled is what executes).
    # Admission itself stays skipped — budget None never denies.
    if budget is None and not require_analysis and not profiler.enabled():
        return _admission_event(MemoryPlan(
            label=label,
            admitted=True,
            reason=(
                "no HBM budget known (no device memory_stats and "
                f"{HBM_BUDGET_ENV} unset) — admission skipped"
            ),
            mesh_axes=dict(mesh.shape) if mesh is not None else None,
        ))

    key = _cache_key(fn, args, kwargs)
    cached = _plan_cache.get(key)
    if cached is None:
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            ma = compiled.memory_analysis()
            cached = {
                "argument": int(ma.argument_size_in_bytes),
                "temp": int(ma.temp_size_in_bytes),
                "output": int(ma.output_size_in_bytes),
                "alias": int(ma.alias_size_in_bytes),
                "compiled": compiled,
                "error": None,
            }
            if mesh is not None:
                cached["sharded_out"] = _per_chip_output_bytes(
                    fn, args, kwargs, compiled
                )
            # Only SUCCESSFUL analyses are cached: a compile failure can be
            # transient (program-memory pressure from live buffers), and
            # caching it would deny this tier for the rest of the process.
            _plan_cache[key] = cached
            _compile_counts[label] = _compile_counts.get(label, 0) + 1
        except Exception as e:  # noqa: BLE001 — a compile OOM IS an answer
            cached = {"error": f"{type(e).__name__}: {e}"[:300]}

    if cached["error"] is not None:
        if budget is None and not require_analysis:
            # The compile only happened because the PROFILER asked for
            # attribution (the budget-less skip above) — attribution is
            # advisory, so its failure must admit exactly like the
            # unprofiled skip would: enabling the profiler can never
            # deny a tier an unprofiled run would have executed.
            return _admission_event(MemoryPlan(
                label=label,
                admitted=True,
                reason=(
                    "no HBM budget known — admission skipped (profiler "
                    f"attribution compile failed: {cached['error'][:120]})"
                ),
                mesh_axes=dict(mesh.shape) if mesh is not None else None,
                error=cached["error"],
            ))
        plan = MemoryPlan(
            label=label,
            admitted=False,
            reason=f"lower/compile failed: {cached['error'][:120]}",
            budget_bytes=budget,
            analyzed=False,
            mesh_axes=dict(mesh.shape) if mesh is not None else None,
            error=cached["error"],
        )
        counters.record("hbm_preflight_denied", f"{label}: {plan.reason}")
        return _admission_event(plan)

    reported = None
    if mesh is None:
        arg_bytes = cached["argument"]
        out_bytes = cached["output"]
    else:
        reported = {
            k: cached[k] for k in ("argument", "temp", "output", "alias")
        }
        # Analytic per-axis division of the argument avals; XLA's own
        # per-device module accounting is the floor (max of the two), so a
        # replicated-in-practice operand the annotations promised sharded
        # still charges what the compiled module will really hold.
        analytic_args = sum(
            shard_bytes(a)
            for a in (*args, *(v for _, v in sorted(kwargs.items())))
            if _is_array_like(a)
        )
        arg_bytes = max(analytic_args, cached["argument"])
        sharded_out = cached.get("sharded_out")
        out_bytes = (
            max(sharded_out, cached["output"])
            if sharded_out is not None
            else cached["output"]
        )

    temp = max(cached["temp"], min_temp_bytes)
    total = arg_bytes + temp + out_bytes - cached["alias"] + extra_bytes
    credit = resident_bytes if budget_is_live() else 0
    admitted = budget is None or total - credit <= budget
    h = fmt_bytes
    reason = (
        ("per-chip " if mesh is not None else "")
        + f"args {h(arg_bytes)} + temp {h(temp)} + "
        f"out {h(out_bytes)} - alias {h(cached['alias'])} "
        f"+ extra {h(extra_bytes)} = {h(total)}"
        + (f" (- {h(credit)} already resident)" if credit else "")
        + " vs "
        + (
            f"min-free-chip budget {h(budget)} on mesh {dict(mesh.shape)}"
            if mesh is not None and budget is not None
            else f"budget {h(budget)}" if budget is not None else "no budget"
        )
    )
    plan = MemoryPlan(
        label=label,
        admitted=admitted,
        reason=("fits: " if admitted else "DENIED: ") + reason,
        budget_bytes=budget,
        argument_bytes=arg_bytes,
        temp_bytes=temp,
        output_bytes=out_bytes,
        alias_bytes=cached["alias"],
        extra_bytes=extra_bytes,
        resident_bytes=resident_bytes,
        total_bytes=total,
        analyzed=True,
        mesh_axes=dict(mesh.shape) if mesh is not None else None,
        reported=reported,
        compiled=cached["compiled"],
    )
    if not admitted:
        counters.record("hbm_preflight_denied", f"{label}: {reason}")
    return _admission_event(plan)


def plan_bytes(
    label: str,
    *,
    argument_bytes: int = 0,
    temp_bytes: int = 0,
    output_bytes: int = 0,
    extra_bytes: int = 0,
    resident_bytes: int = 0,
    mesh=None,
    budget: int | None | object = _UNSET,
) -> MemoryPlan:
    """ANALYTIC-ONLY admission of a candidate program from caller-supplied
    per-chip byte figures — no lower, no compile, no cache entry: the
    zero-cost half of the placement search's candidate-batch preflight
    (core.autoshard prunes enumerated candidates with this before any of
    them is worth an AOT compile).

    Deliberately a LOWER BOUND on what :func:`plan_program` would charge
    (no alias credit is modeled, and callers pass only the transient floors
    they can prove): a plan denied here is denied a fortiori by the
    compiled preflight, while an admitted one still faces the full
    admission when the ladder actually selects it — pruning can skip work,
    never under-admit.  Same budget/credit semantics as ``plan_program``
    (min per-chip free HBM under a ``mesh``; resident credit only against a
    live free-bytes budget); denials are counted under
    ``hbm_preflight_denied`` like any other admission decision."""
    if mesh is not None and budget is _UNSET:
        budget, _worst = min_chip_budget(mesh)
    if budget is _UNSET:
        budget = hbm_budget()
    mesh_axes = dict(mesh.shape) if mesh is not None else None
    if budget is None:
        return _admission_event(MemoryPlan(
            label=label,
            admitted=True,
            reason=(
                "no HBM budget known (no device memory_stats and "
                f"{HBM_BUDGET_ENV} unset) — analytic admission skipped"
            ),
            argument_bytes=int(argument_bytes),
            temp_bytes=int(temp_bytes),
            output_bytes=int(output_bytes),
            extra_bytes=int(extra_bytes),
            resident_bytes=int(resident_bytes),
            total_bytes=int(
                argument_bytes + temp_bytes + output_bytes + extra_bytes
            ),
            mesh_axes=mesh_axes,
        ))
    total = int(argument_bytes + temp_bytes + output_bytes + extra_bytes)
    credit = int(resident_bytes) if budget_is_live() else 0
    admitted = total - credit <= budget
    h = fmt_bytes
    reason = (
        ("fits: " if admitted else "DENIED: ")
        + ("per-chip " if mesh is not None else "")
        + f"analytic args {h(argument_bytes)} + temp {h(temp_bytes)} + "
        f"out {h(output_bytes)} + extra {h(extra_bytes)} = {h(total)}"
        + (f" (- {h(credit)} already resident)" if credit else "")
        + f" vs budget {h(budget)} (no compile)"
    )
    plan = MemoryPlan(
        label=label,
        admitted=admitted,
        reason=reason,
        budget_bytes=budget,
        argument_bytes=int(argument_bytes),
        temp_bytes=int(temp_bytes),
        output_bytes=int(output_bytes),
        extra_bytes=int(extra_bytes),
        resident_bytes=int(resident_bytes),
        total_bytes=total,
        analyzed=False,  # no compile happened — analytic numbers only
        mesh_axes=mesh_axes,
    )
    if not admitted:
        counters.record("hbm_preflight_denied", f"{label}: {reason}")
    return _admission_event(plan)


def plan_batch(
    planners: Sequence[tuple[str, Callable[[], MemoryPlan]]],
) -> dict[str, MemoryPlan]:
    """Candidate-batch preflight: evaluate every ``(label, planner)`` pair
    and return ``{label: MemoryPlan}``.  A planner that RAISES becomes a
    denied plan carrying the error (one broken candidate must not kill the
    search over the others) — the batch analog of ``plan_program``'s
    compile-failure-is-an-answer rule."""
    out: dict[str, MemoryPlan] = {}
    for label, planner in planners:
        try:
            out[label] = planner()
        except Exception as e:  # noqa: BLE001 — a failed plan IS a deny
            out[label] = _admission_event(MemoryPlan(
                label=label,
                admitted=False,
                reason=f"planner failed: {type(e).__name__}: {e}"[:200],
                error=f"{type(e).__name__}: {e}"[:300],
            ))
    return out


def plan_cache_bytes(
    label: str,
    nbytes: int,
    *,
    mesh=None,
    budget: int | None | object = _UNSET,
    headroom: float = 0.5,
) -> MemoryPlan:
    """Admit or deny holding ``nbytes`` of materialized intermediates
    resident — the auto-Cacher's admission gate (core.optimize).  Data-only:
    no program to compile, so admission is a straight byte comparison
    against the HBM budget (the minimum per-chip free HBM under a ``mesh``,
    exactly like :func:`plan_program`'s mesh mode; callers divide sharded
    cache bytes per chip before calling).

    ``headroom``: fraction of the budget caches may claim — a cache that
    fills ALL free HBM starves the very solve it was meant to speed up, so
    the default admits at most half.  No budget known -> admitted
    unanalyzed (CPU backends without stats), same skip-never-guess rule as
    every other admission path.  Denials are counted under
    ``cache_admission_denied`` and land on the trace timeline as
    ``hbm_admission`` events like any program plan."""
    if mesh is not None and budget is _UNSET:
        budget, _worst = min_chip_budget(mesh)
    if budget is _UNSET:
        budget = hbm_budget()
    mesh_axes = dict(mesh.shape) if mesh is not None else None
    if budget is None:
        return _admission_event(MemoryPlan(
            label=label,
            admitted=True,
            reason=(
                "no HBM budget known (no device memory_stats and "
                f"{HBM_BUDGET_ENV} unset) — cache admission skipped"
            ),
            output_bytes=int(nbytes),
            total_bytes=int(nbytes),
            mesh_axes=mesh_axes,
        ))
    allowed = int(budget * headroom)
    admitted = int(nbytes) <= allowed
    h = fmt_bytes
    reason = (
        ("fits: " if admitted else "DENIED: ")
        + ("per-chip " if mesh is not None else "")
        + f"cached {h(nbytes)} vs {h(allowed)} "
        f"(budget {h(budget)} x headroom {headroom})"
    )
    plan = MemoryPlan(
        label=label,
        admitted=admitted,
        reason=reason,
        budget_bytes=allowed,
        output_bytes=int(nbytes),
        total_bytes=int(nbytes),
        analyzed=True,
        mesh_axes=mesh_axes,
    )
    if not admitted:
        counters.record("cache_admission_denied", f"{label}: {reason}")
    return _admission_event(plan)


# -- OOM detection / recovery -------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


class LadderSourceLost(RuntimeError):
    """A ladder tier cannot run because its data source was donated away
    (``fit(donate=True)`` consumed the caller's buffers and a later tier
    has nothing to rebuild from).  Deliberately NOT an OOM: the ladder must
    surface it, not retry through it."""


def is_oom_error(e: BaseException) -> bool:
    """True for XLA's device-memory exhaustion (``XlaRuntimeError`` carrying
    RESOURCE_EXHAUSTED / out-of-memory text) — the ONLY failure the
    degradation ladder retries; everything else — including the ladder's
    own :class:`LadderSourceLost` guard — propagates unchanged."""
    if isinstance(e, LadderSourceLost):
        return False
    if not isinstance(e, (RuntimeError, MemoryError)):
        return False
    return isinstance(e, MemoryError) or is_oom_text(str(e))


def is_oom_text(msg: str) -> bool:
    """True when ``msg`` (an exception's text, or ``MemoryPlan.error``)
    carries XLA's memory-exhaustion grammar."""
    return any(m in msg for m in _OOM_MARKERS)


def free_buffers(*arrays) -> None:
    """Best-effort immediate release of device buffers (OOM recovery frees
    the failed tier's live arrays before retrying a cheaper tier, rather
    than waiting on the GC)."""
    for a in arrays:
        if isinstance(a, jax.Array):
            try:
                if not a.is_deleted():
                    a.delete()
            except Exception:  # noqa: BLE001 — freeing is advisory
                pass


def array_bytes(*shaped) -> int:
    """Σ nbytes of arrays/ShapeDtypeStructs (resident-set accounting for
    ``plan_program(extra_bytes=...)``)."""
    import numpy as np

    total = 0
    for s in shaped:
        if s is None:
            continue
        n = 1
        for dim in s.shape:
            n *= int(dim)
        total += n * np.dtype(s.dtype).itemsize
    return total


# -- the degradation ladder ---------------------------------------------------


@dataclasses.dataclass
class Tier:
    """One rung: ``plan`` is lazy (called at selection time), ``run`` gets
    the plan back so an admitted fused tier can execute ``plan.compiled``."""

    name: str
    plan: Callable[[], MemoryPlan]
    run: Callable[[MemoryPlan], Any]


@dataclasses.dataclass
class FitReport:
    """Audit trail of one laddered fit (``estimator.last_fit_report``)."""

    label: str = ""
    budget_bytes: int | None = None
    plans: dict = dataclasses.field(default_factory=dict)
    chosen: str | None = None
    denials: list = dataclasses.field(default_factory=list)
    oom_retries: list = dataclasses.field(default_factory=list)
    #: the placement search's program fingerprint (set by
    #: autoshard.run_search) — the grouping key the profiler's HBM
    #: watermark drift rows use, so byte-drift evidence joins the same
    #: program family as the time outcomes.
    fingerprint: str | None = None
    #: mesh ladders: the (data, model) axis sizes of the mesh that actually
    #: RAN the solve; ``None`` after a step-down to the single-device floor
    #: (and for plain single-device fits).
    mesh_shape: dict | None = None
    #: placement search (core.autoshard): the PlacementPlan record of the
    #: searched ranking this fit ran through — the full candidate table
    #: with deny/score rationale and the chosen plan's predicted-vs-actual
    #: cost.  ``None`` when the fit walked the hand ladder.
    placement: dict | None = None
    #: numerics observatory (core.numerics, KEYSTONE_NUMERICS=1): per-block
    #: κ estimates of this solve's gram blocks — the ACCURACY.md §6 offline
    #: sweep as a live per-fit monitor.  ``None`` when the observatory was
    #: off for the fit.
    conditioning: list | None = None
    #: the block solver's plan of the fit (``solvers.block._plan_bcd``):
    #: rows, blocks, block width, whether the blocks were ``held`` as one
    #: matrix or ``made`` inside the solver's programs (``block_source``),
    #: and the bytes on both sides of that rule.  A rule from bytes, not an
    #: admission denial: ``denials`` does not see it.
    bcd_plan: dict | None = None

    @property
    def block_source(self) -> str | None:
        return self.bcd_plan["block_source"] if self.bcd_plan else None

    def record(self) -> dict:
        """JSON-able form for bench artifacts."""
        from . import telemetry

        return {
            "chosen_tier": self.chosen,
            "conditioning": (
                list(self.conditioning) if self.conditioning else None
            ),
            "mesh_shape": dict(self.mesh_shape) if self.mesh_shape else None,
            "budget_gb": (
                round(self.budget_bytes / 2**30, 3) if self.budget_bytes else None
            ),
            "denials": list(self.denials),
            "oom_retries": list(self.oom_retries),
            "tiers": {k: p.breakdown() for k, p in self.plans.items()},
            "placement": self.placement,
            "block_source": self.block_source,
            "bcd_plan": dict(self.bcd_plan) if self.bcd_plan else None,
            # Flight-recorder postmortems this process has dumped
            # (core.telemetry) — a degraded fit links to its evidence.
            "postmortems": telemetry.postmortem_paths(),
        }

    def summary(self) -> str:
        s = f"{self.label}: tier={self.chosen}"
        if self.mesh_shape:
            s += f", mesh={self.mesh_shape}"
        if self.denials:
            s += f", denied={self.denials}"
        if self.oom_retries:
            s += f", oom_retries={self.oom_retries}"
        return s

    def degraded(self) -> bool:
        return bool(self.denials or self.oom_retries)


def run_ladder(label: str, tiers: Sequence[Tier], report: FitReport):
    """Walk ``tiers`` best-first: preflight each LAZILY (a tier is only
    planned — and its program only compiled — once every better tier has
    been denied or OOMed, so the common fused-admitted fit pays for exactly
    one plan), run the first admitted one, and on a runtime
    ``RESOURCE_EXHAUSTED`` step down exactly one tier (the tier's ``run``
    frees its own buffers on the way out; anything it leaked is
    best-effort-freed by the next tier's builder).  The final tier is the
    floor: it runs even when its preflight is a deny — with a warning —
    because failing is the only thing below it.  Every CONSIDERED tier's
    plan lands in ``report`` so the decision is auditable afterwards.
    """
    report.label = label
    last_oom: BaseException | None = None
    # The whole laddered solve is one span; each considered tier's plan and
    # run are child spans, and the FitReport is linked into the solve span
    # at exit — a trace shows which tiers were tried, denied, OOMed, and
    # chosen, with the admission numbers alongside.
    with trace.span(f"solve:{label}", cat="solve") as solve_sp:
        for i, tier in enumerate(tiers):
            floor = i == len(tiers) - 1
            with trace.host("plan", f"plan:{tier.name}", solve=label):
                plan = tier.plan()
            report.plans[tier.name] = plan
            if plan.budget_bytes is not None:
                report.budget_bytes = plan.budget_bytes
            if not plan.admitted and not floor:
                report.denials.append(tier.name)
                _logger.info("%s: %s denied by preflight — %s", label, tier.name, plan.reason)
                continue
            if not plan.admitted and floor:
                _logger.warning(
                    "%s: floor tier %s denied by preflight (%s) but nothing is "
                    "below it — attempting anyway",
                    label, tier.name, plan.reason,
                )
            try:
                with trace.span(
                    f"tier:{tier.name}", cat="solve",
                    solve=label, admitted=plan.admitted,
                ), profiler.phase(f"solve:{label}"):
                    t_run = time.perf_counter()
                    out = tier.run(plan)
            except Exception as e:  # noqa: BLE001 — only OOM is retried
                if not is_oom_error(e) or floor:
                    raise
                report.oom_retries.append(tier.name)
                counters.record(
                    "solver_oom_retry",
                    f"{label}/{tier.name}: RESOURCE_EXHAUSTED at runtime "
                    f"(preflight said: {plan.reason}) — stepping down one tier",
                )
                last_oom = e
                continue
            with trace.host("finish", "fit_report", solve=label):
                report.chosen = tier.name
                if report.degraded() or tier.name != tiers[0].name:
                    counters.record("solver_tier_degraded", report.summary())
                _logger.info("%s: running tier=%s (%s)", label, tier.name, plan.reason)
                solve_sp.set(report=report.record())
            if profiler.enabled():
                # Device cost attribution (ISSUE 14): the chosen tier's
                # compiled program lands in the per-program MFU ledger
                # with its device-synced wall, and the HBM watermark the
                # sampler saw during the solve is audited against what
                # this plan CHARGED — drift is counted and logged as
                # calibration evidence.  One enabled() check when off.
                wall = profiler.synced_wall(out, t_run)
                if plan.compiled is not None:
                    profiler.record_program(
                        f"{label}:{tier.name}", plan.compiled, wall
                    )
                profiler.audit_plan(
                    f"{label}:{tier.name}", plan,
                    phase_name=f"solve:{label}",
                    fingerprint=report.fingerprint,
                )
            return out
        # Unreachable in practice (the floor either returns or raises), but
        # be explicit if a caller builds a ladder whose floor denied AND
        # raised.
        raise RuntimeError(
            f"{label}: every ladder tier failed"
        ) from last_oom


def log_fit_report(est, logger=None, label: str = "") -> None:
    """Workload fit-path hook: surface which tier a solve actually ran on
    (one INFO line; degradations are already counted by the ladder)."""
    rep = getattr(est, "last_fit_report", None)
    if rep is None:
        return
    lg = logger or _logger
    lg.info("%s%s", f"{label}: " if label else "", rep.summary())
