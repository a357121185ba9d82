#!/usr/bin/env python
"""Seeded end-to-end chaos runner (CLI face of tests/chaos.py).

Runs deterministic fault schedules — injected solver OOMs, transient tar
IO, corrupt archive members, NaN-poisoned batches, mid-BCD preemption with
``resume_from=`` restart, and watchdog-bounded hangs — against a real
workload pipeline, and holds every run to the chaos invariant: complete
with predictions equal to the fault-free run, or fail with a typed,
counted, logged error.  Never a silent wrong model.

Usage:
    python tools/chaos_run.py --seed 3              # one schedule
    python tools/chaos_run.py                       # the tier-1 seed set
    python tools/chaos_run.py --full                # the full seed set
    python tools/chaos_run.py --workload cifar      # RandomPatchCifar
    python tools/chaos_run.py --stream              # streaming-ingest families
    python tools/chaos_run.py --trace DIR           # one trace per schedule

``--trace DIR`` writes a Chrome-trace JSON per schedule (Perfetto-loadable)
and ADDS an observability invariant to the suite: every injected fault must
appear in its schedule's trace as a counted ``fault`` instant event with a
matching ``kind`` attribute, and a typed-error outcome must be visible as a
failed span carrying the error type — typed-error spans are never silent.
A schedule whose trace misses either fails the run like any other
violation.  The assertion covers ALL 25 fault families (the streaming,
snapshot, decode-worker, serving, wire-protocol, elastic-mesh,
multi-host, and native-entropy families included) and the tier-1 suite runs every schedule
traced
(tests/test_chaos.py), so the invariant is continuously enforced, not just
on demand.

Exit status is nonzero if ANY schedule violates the invariant.  The first
stdout line is the machine-readable JSON record (truncation-proof); a
short human summary follows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("chaos_run")
    p.add_argument("--seed", type=int, default=None, help="run ONE schedule")
    p.add_argument(
        "--full",
        action="store_true",
        help="run the full seed set instead of the tier-1 subset",
    )
    p.add_argument(
        "--stream",
        action="store_true",
        help="run only the streaming-ingest fault schedules "
        "(stream_corrupt / stream_hang / autotune_thrash / "
        "snapshot_corrupt / decode_worker_kill / jpeg_corrupt_entropy / "
        "native_entropy families, core.ingest + core.snapshot paths)",
    )
    p.add_argument(
        "--serve",
        action="store_true",
        help="run only the serving fault schedules (slow_client / "
        "malformed_request / serve_burst_oom / wire_disconnect / "
        "slow_loris families — the core.serve, core.frontend, and "
        "core.wire online paths)",
    )
    p.add_argument("--workload", default="mnist", choices=("mnist", "cifar"))
    p.add_argument(
        "--hosts",
        type=int,
        default=None,
        metavar="N",
        help="size of the serving fleet the host_loss family spawns "
        "(default 2; real subprocesses where spawn is available) — sets "
        "KEYSTONE_CHAOS_HOSTS for the drill",
    )
    p.add_argument(
        "--trace",
        default=None,
        metavar="DIR",
        help="write a Chrome-trace JSON per schedule into DIR and assert "
        "every injected fault appears in it as a counted event "
        "(typed-error spans never silent)",
    )
    a = p.parse_args(argv)

    if a.hosts is not None:
        if a.hosts < 2:
            print("--hosts must be >= 2 (one host must die)", file=sys.stderr)
            return 2
        os.environ["KEYSTONE_CHAOS_HOSTS"] = str(a.hosts)

    import chaos

    if a.seed is not None:
        seeds = (a.seed,)
    else:
        seeds = chaos.FULL_SEEDS if a.full else chaos.TIER1_SEEDS
    if a.stream or a.serve:

        def selected(seed: int) -> bool:
            kind = chaos.make_schedule(seed).kind
            if a.stream and (
                kind.startswith("stream_")
                or kind
                in (
                    "autotune_thrash", "snapshot_corrupt",
                    "decode_worker_kill", "jpeg_corrupt_entropy",
                    "native_entropy",
                )
            ):
                return True
            return a.serve and kind in chaos.SERVE_FAMILIES

        seeds = tuple(
            s
            for s in (chaos.FULL_SEEDS if a.seed is None else seeds)
            if selected(s)
        )
        if not seeds:
            print("no matching schedules in the selected seed set")
            return 1

    if a.trace is not None:
        os.makedirs(a.trace, exist_ok=True)
        if os.environ.get("KEYSTONE_TRACE", "").strip():
            # Per-schedule tracing resets the global buffer and retargets
            # the trace path every schedule — an ambient session trace
            # cannot coexist with it.
            print(
                "# WARNING: --trace overrides KEYSTONE_TRACE: per-schedule "
                "traces reset the buffer, so the env-configured session "
                "trace will not be written",
                file=sys.stderr,
            )
    results = chaos.run_suite(seeds, workload=a.workload, trace_dir=a.trace)
    trace_violations: dict[int, list] = {}
    if a.trace is not None:
        for r in results:
            # r.trace_path is the one source of truth for the filename
            # (set by run_schedule) — never re-derived here.
            missing = (
                chaos.verify_trace(r.trace_path, r)
                if r.trace_path is not None
                else ["schedule produced no trace file"]
            )
            if missing:
                trace_violations[r.seed] = missing
    violations = [
        r
        for r in results
        if not r.ok()
        or r.outcome != chaos.expected_outcome(r.fault)
        or r.seed in trace_violations
    ]
    record = {
        "metric": "chaos",
        "workload": a.workload,
        "seeds": list(seeds),
        "ok": not violations,
        "outcomes": {r.outcome: sum(1 for x in results if x.outcome == r.outcome) for r in results},
        "results": [r.record() for r in results],
    }
    if a.trace is not None:
        record["trace"] = {
            "dir": a.trace,
            "violations": {
                str(s): v for s, v in sorted(trace_violations.items())
            },
        }
    print(json.dumps(record), flush=True)
    for r in results:
        bad = (
            not r.ok()
            or r.outcome != chaos.expected_outcome(r.fault)
            or r.seed in trace_violations
        )
        flag = "BAD" if bad else "ok "
        print(
            f"# {flag} seed={r.seed} {r.fault.kind}: {r.outcome}"
            + (f" ({r.error_type})" if r.error_type else "")
            + f" [{r.seconds:.2f}s]"
            + (
                f" TRACE: {'; '.join(trace_violations[r.seed])}"
                if r.seed in trace_violations
                else ""
            )
        )
    print(f"# chaos: {len(results) - len(violations)}/{len(results)} schedules honored the invariant")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
