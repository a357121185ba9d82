"""Seeded chaos suite: every fault schedule must end in either
predictions-equal-to-fault-free or a typed, counted error — never a
silent wrong model, never a bare traceback (see tests/chaos.py).

Tier-1 runs ``chaos.TIER1_SEEDS`` on the MNIST pipeline (plus two
schedules on the conv CIFAR pipeline); the full seed set runs only under
``-m chaos`` (it is also marked slow so the tier-1 ``-m 'not slow'``
filter keeps excluding it).
"""

import pytest

import chaos

#: The counter each family must bump — faults are COUNTED, not just
#: survived, so operators can see them (the "structured, counted, logged"
#: leg of the chaos invariant).
EXPECTED_COUNTER = {
    "solver_oom": "solver_oom_retry",
    "oom_cascade": "solver_oom_retry",
    "io_transient": "io_retry",
    "corrupt_members": "corrupt_image",
    "nan_input": "nonfinite_model",
    "preempt_resume": "chaos_preemption",
    "deadline": "deadline_exceeded",
    "stream_corrupt": "corrupt_image",
    "stream_hang": "deadline_exceeded",
    "autotune_thrash": "chaos_autotune_thrash",
    "snapshot_corrupt": "snapshot_fallback",
    "decode_worker_kill": "decode_worker_respawn",
    "slow_client": "chaos_slow_client",
    "malformed_request": "serve_malformed_request",
    "serve_burst_oom": "serve_burst_oom",
    "wire_disconnect": "wire_client_disconnect",
    "slow_loris": "chaos_slow_loris",
    "jpeg_corrupt_entropy": "jpeg_corrupt_entropy",
    "profiler_crash": "profiler_sampler_crash",
    "output_drift": "serve_output_drift",
    "mesh_shrink": "mesh_reanchor",
    "host_loss": "host_reanchor",
    "drift_refit": "lifecycle_refit",
    "native_entropy": "jpeg_corrupt_entropy",
    "obs_capture": "obs_member_lost",
}


def _check(r):
    assert r.ok(), r.record()
    assert r.outcome == chaos.expected_outcome(r.fault), r.record()
    counter = EXPECTED_COUNTER[r.fault.kind]
    assert r.counters_delta.get(counter, 0) >= 1, (
        f"schedule survived but its fault went uncounted "
        f"({counter} delta 0): {r.record()}"
    )


@pytest.mark.parametrize("seed", chaos.TIER1_SEEDS)
def test_chaos_schedule_mnist(seed, tmp_path):
    """Every tier-1 schedule runs TRACED and its trace is held to the
    never-silent bar (the ``chaos_run.py --trace`` invariant, extended
    from the original 10 families to all 25): every counted fault appears
    as a kind-tagged ``fault`` instant, every typed error as a failed
    span or fault event."""
    trace_path = str(tmp_path / f"chaos_seed{seed}.json")
    r = chaos.run_schedule(
        seed, "mnist", tmpdir=str(tmp_path), trace_path=trace_path
    )
    _check(r)
    violations = chaos.verify_trace(trace_path, r)
    assert violations == [], {
        "seed": seed, "family": r.fault.kind, "violations": violations,
    }


@pytest.mark.parametrize("seed", (0, 4))  # OOM step-down + NaN guard
def test_chaos_schedule_cifar(seed, tmp_path):
    _check(chaos.run_schedule(seed, "cifar", tmpdir=str(tmp_path)))


def test_tier1_seed_set_meets_the_chaos_bar():
    """The in-tier-1 schedule set is the acceptance floor: >= 10 seeded
    schedules covering EVERY fault family, including one
    preempt-then-resume and one deadline/watchdog trip."""
    assert len(chaos.TIER1_SEEDS) >= 10
    kinds = {chaos.make_schedule(s).kind for s in chaos.TIER1_SEEDS}
    assert kinds == set(chaos.FAMILIES)
    assert {"preempt_resume", "deadline"} <= kinds
    # Streaming-ingest coverage (ISSUE 4): >= 2 streaming schedules in tier-1
    assert {"stream_corrupt", "stream_hang"} <= kinds
    # Mid-stream retune coverage (ISSUE 6): the typed-or-equal invariant
    # must be exercised under oscillating autotuner knob motion
    assert "autotune_thrash" in kinds
    # Decode-wall coverage (ISSUE 7): corrupt snapshot shards must fall
    # back counted-and-bit-equal, and a SIGKILLed decode worker must
    # respawn counted — never a hung ring
    assert {"snapshot_corrupt", "decode_worker_kill"} <= kinds
    # Serving coverage (ISSUE 8): the typed-or-equal invariant extends to
    # the online path — slow clients, malformed requests, burst OOM
    assert set(chaos.SERVE_FAMILIES) <= kinds
    # Wire-protocol coverage (ISSUE 12): a client disconnect mid-batch
    # must be counted with the batch still completing, and slow-loris
    # partial frames must never stall the accept loop or starve honest
    # connections
    assert {"wire_disconnect", "slow_loris"} <= kinds
    # Device-decode coverage (ISSUE 13): a damaged entropy-coded scan
    # under decode_mode="device" must become a typed, counted skip with
    # the rest of the batch surviving bit-equal — never silent wrong
    # pixels
    assert "jpeg_corrupt_entropy" in kinds
    # Profiler coverage (ISSUE 14): the HBM watermark sampler thread
    # dying mid-run must be a counted degradation with the run completing
    # bit-equal to an unprofiled run — observability may die, the
    # workload may not
    assert "profiler_crash" in kinds
    # Numerics-observatory coverage (ISSUE 15): a shifted request mix
    # against a drift-armed served engine must be counted
    # serve_output_drift with a postmortem, every answer bit-equal to an
    # unmonitored engine
    assert "output_drift" in kinds
    # Elastic-serving coverage (ISSUE 16): device loss mid-serve must
    # re-anchor every engine onto the surviving mesh with zero request
    # loss (counted mesh_reanchor), and a full-mesh-sharded checkpoint
    # must resume onto the survivors predictions-equal — never a silent
    # divergence, never a crash for a mesh the process still has
    assert "mesh_shrink" in kinds
    # Multi-host coverage (ISSUE 17): a serving HOST dying mid-flight
    # must be counted fleet_host_lost with its in-flight requests
    # reissued to survivors, the reduced group re-formed (dist_reform)
    # and every survivor re-anchored (host_reanchor, postmortem-linked)
    # — zero dropped requests, every answer bit-equal to the offline
    # oracle
    assert "host_loss" in kinds
    # Lifecycle coverage (ISSUE 18): a drifted served model must be
    # detected, warm-refit, validated, and hot-swapped with zero dropped
    # requests and post-swap answers bit-equal to an offline refit;
    # injected refit OOM / validation rejection / mid-swap kill must each
    # degrade typed+counted to the incumbent — never a silent wrong or
    # missing answer
    assert "drift_refit" in kinds
    # Native-entropy coverage (ISSUE 19): the C scan loop must be
    # indistinguishable from the Python pass — corrupt scans through the
    # native backend are the same typed counted skips with survivors
    # bit-equal to a forced-Python stream, and an unexpected native
    # failure degrades per-image counted, never a crash
    assert "native_entropy" in kinds
    # Fleet-observability coverage (ISSUE 20): a member SIGKILLed
    # mid-scrape must degrade the collector (obs_member_lost,
    # postmortem-linked), keep the fleet view monotone for survivors
    # with counters summed and p99 pooled from raw windows, and produce
    # ONE clock-aligned incident bundle holding every surviving member's
    # flight ring — with serving answers bit-equal to an uncollected
    # fleet
    assert "obs_capture" in kinds


def test_schedules_are_deterministic():
    for seed in chaos.TIER1_SEEDS:
        a, b = chaos.make_schedule(seed), chaos.make_schedule(seed)
        assert a.kind == b.kind and a.params == b.params


def test_deadline_names_the_phase(tmp_path):
    """The watchdog schedule's error must carry the phase name — a hang
    report that cannot say WHAT hung is barely better than the hang."""
    seed = next(
        s for s in chaos.TIER1_SEEDS
        if chaos.make_schedule(s).kind == "deadline"
    )
    r = chaos.run_schedule(seed, "mnist", tmpdir=str(tmp_path))
    assert r.error_type == "DeadlineExceeded"
    assert r.phase == "solve"


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_full_schedule_mnist():
    results = chaos.run_suite(chaos.FULL_SEEDS, workload="mnist")
    bad = [
        r.record()
        for r in results
        if not r.ok() or r.outcome != chaos.expected_outcome(r.fault)
    ]
    assert not bad, bad


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_full_schedule_cifar():
    results = chaos.run_suite(chaos.FULL_SEEDS[: len(chaos.FAMILIES)], workload="cifar")
    bad = [
        r.record()
        for r in results
        if not r.ok() or r.outcome != chaos.expected_outcome(r.fault)
    ]
    assert not bad, bad
