"""Device cost attribution tests (core.profiler, ISSUE 14): the
per-program MFU ledger, the HBM watermark sampler + plan-drift accounting, triggered XLA capture rate limiting, the
disabled-mode zero-overhead bound, and the cross-process stitched request
waterfall (a REAL two-process serve over sockets)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.core import memory as kmem
from keystone_tpu.core import profiler as kprof
from keystone_tpu.core import serve as kserve
from keystone_tpu.core import telemetry as ktelemetry
from keystone_tpu.core import trace as ktrace
from keystone_tpu.core import wire as kwire
from keystone_tpu.core.pipeline import FunctionTransformer
from keystone_tpu.core.resilience import counters
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_profiler():
    kprof.reset_state()
    yield
    kprof.reset_state()


def _matmul_compiled(n=64):
    f = jax.jit(lambda x: x @ x)
    x = jnp.asarray(np.ones((n, n), np.float32))
    return f, x, f.lower(x).compile()


# -- the cost-analysis reader and the ledger ----------------------------------


class TestLedger:
    def test_cost_pair_and_jit_cost(self):
        f, x, compiled = _matmul_compiled(64)
        flops, ba = kprof.cost_pair(compiled)
        assert flops and flops >= 2 * 64**3 * 0.9  # ~2n^3 matmul flops
        assert ba and ba > 0
        assert kprof.jit_cost(f, x) == (flops, ba)

    def test_record_program_mfu_math(self, monkeypatch):
        _f, _x, compiled = _matmul_compiled(64)
        rates = {"peak_flops": 2e12, "hbm_gbps": 100.0}
        monkeypatch.setattr(kprof, "device_rates", lambda: rates)
        with kprof.profiled(True):
            row = kprof.record_program("t", compiled, 0.01)
            assert row["mfu"] == pytest.approx(
                row["flops"] / 0.01 / rates["peak_flops"], abs=1e-6
            )
            led = kprof.ledger()["t"]
            assert led["runs"] == 1
            assert led["mfu"] == pytest.approx(row["mfu"], rel=1e-3)
            assert led["bound"] in ("compute", "memory")

    def test_unknown_device_kind_reports_no_utilization(self):
        """The CPU test platform is not in optimize.DEVICE_RATES: its
        programs are timed and counted, but never given an MFU."""
        _f, _x, compiled = _matmul_compiled(64)
        assert kprof.device_rates() is None
        with kprof.profiled(True):
            row = kprof.record_program("nomfu", compiled, 0.01)
            led = kprof.ledger()["nomfu"]
        assert row["mfu"] is None and row["flops"]
        assert led["mfu"] is None and led["bound"] is None
        assert led["achieved_hbm_gbps"] is not None

    def test_ledger_aggregates_runs(self):
        _f, _x, compiled = _matmul_compiled(32)
        with kprof.profiled(True):
            kprof.record_program("agg", compiled, 0.01)
            kprof.record_program("agg", compiled, 0.03)
            led = kprof.ledger()["agg"]
        assert led["runs"] == 2
        assert led["wall_seconds"] == pytest.approx(0.04, rel=1e-6)

    def test_run_ladder_feeds_ledger_and_solver_hint_audited(self, rng):
        """A profiled BCD fit lands its chosen tier in the ledger with the
        compiled program's own cost analysis: the FLOPs the ledger reports
        are the compiler's, not a hand-derived hint."""
        x = jnp.asarray(rng.normal(size=(512, 128)).astype(np.float32))
        y = jnp.asarray(
            2.0 * np.eye(4)[rng.integers(0, 4, 512)] - 1.0, jnp.float32
        )
        with kprof.profiled(True):
            est = BlockLeastSquaresEstimator(128, 2, 1e-2)
            est.fit(x, y)
            led = kprof.ledger()
        assert est.last_fit_report.chosen == "fused"
        chosen = led.get("bcd_fit:fused")
        assert chosen is not None, f"no fused ledger row in {sorted(led)}"
        assert chosen["runs"] >= 1 and chosen["wall_seconds"] > 0
        plan = est.last_fit_report.plans["fused"]
        assert chosen["flops"] == kprof.program_cost(plan.compiled)["flops"] > 0


# -- disabled-mode zero overhead ----------------------------------------------


class TestDisabledMode:
    def test_disabled_hooks_are_inert(self, rng):
        """With the profiler OFF (the default), every hook is one flag
        check: nothing lands in the ledger, no sampler thread exists, no
        registry metric moves — the zero-overhead bound the serving and
        solve paths rely on."""
        assert not kprof.enabled()
        _f, _x, compiled = _matmul_compiled(32)
        assert kprof.record_program("off", compiled, 0.01) is None
        assert kprof.audit_plan("off", _plan(1000)) is None
        x = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
        y = jnp.asarray(
            2.0 * np.eye(4)[rng.integers(0, 4, 128)] - 1.0, jnp.float32
        )
        before = ktrace.metrics.get("profiler_programs_recorded")
        BlockLeastSquaresEstimator(64, 1, 1e-2).fit(x, y)
        assert kprof.ledger() == {}
        assert kprof.sampler() is None
        assert ktrace.metrics.get("profiler_programs_recorded") == before

    def test_phase_is_noop_when_disabled(self):
        with kprof.phase("anything"):
            pass
        assert kprof.sampler() is None

    def test_profiled_restores_disabled(self):
        with kprof.profiled(True, stats_fn=lambda: 1024):
            assert kprof.enabled()
            assert kprof.sampler() is not None
        assert not kprof.enabled()
        assert kprof.sampler() is None


# -- the HBM watermark sampler + drift accounting -----------------------------


def _plan(total_bytes, label="p") -> kmem.MemoryPlan:
    return kmem.MemoryPlan(
        label=label, admitted=True, reason="test",
        argument_bytes=total_bytes // 2, temp_bytes=total_bytes // 4,
        output_bytes=total_bytes - total_bytes // 2 - total_bytes // 4,
        total_bytes=total_bytes, analyzed=True,
    )


class TestWatermark:
    def test_phase_watermarks(self):
        seq = iter([100, 500, 300, 200])
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: next(seq)
        ):
            s = kprof.sampler()
            s.sample()  # 100, no phase
            with kprof.phase("solve"):
                s.sample()  # 500 attributed to "solve"
            # phase exit samples once more (300)
            assert s.watermark("solve") == 500
            assert s.watermark() == 500  # global peak
            with kprof.phase("serve"):
                pass  # exit sample: 200
            assert s.watermark("serve") == 200

    def test_phase_reentry_clears_stale_peak(self):
        """A phase name reused for a SMALLER run must not inherit the
        bigger run's watermark — stale peaks would read as spurious
        drift and poison the hbm_drift calibration rows."""
        seq = iter([5000, 100, 100])
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: next(seq)
        ):
            s = kprof.sampler()
            with kprof.phase("solve"):
                s.sample()  # 5000: the big run
            with kprof.phase("solve"):
                s.sample()  # 100: the small run — fresh watermark
            assert s.watermark("solve") == 100

    def test_audit_skips_without_a_phase_sample(self):
        """No phase watermark -> skipped, never guessed from the
        process-lifetime global peak (which describes whatever ran
        biggest since import, not this plan)."""
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: 9999
        ):
            kprof.sampler().sample()  # global peak only, no phase
            assert kprof.audit_plan("never-entered", _plan(10)) is None

    def test_backendless_sampler_retires_itself(self):
        with kprof.profiled(True, interval_ms=10_000.0, stats_fn=lambda: None):
            s = kprof.sampler()
            assert s.sample() is False
            assert s.unavailable
            assert kprof.watermark() is None

    def test_drift_within_tolerance_not_counted_but_logged(self):
        before = counters.get("plan_drift")
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: 1000
        ):
            with kprof.phase("fit:tier"):
                kprof.sampler().sample()
            audit = kprof.audit_plan("fit:tier", _plan(1100))
        assert audit is not None and not audit["drifted"]
        assert audit["watermark_bytes"] == 1000
        assert audit["charged_bytes"] == 1100
        assert counters.get("plan_drift") == before
        # the ratio lands as a gauge either way
        gauges = ktrace.metrics.snapshot()["gauges"]
        assert gauges["profiler_fit_tier_plan_drift"] == pytest.approx(1000 / 1100, rel=1e-3)

    def test_drift_beyond_tolerance_counted_and_logged(self):
        before = counters.get("plan_drift")
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: 4000
        ):
            with kprof.phase("fit:tier"):
                kprof.sampler().sample()
            audit = kprof.audit_plan("fit:tier", _plan(1000))
        assert audit["drifted"] and audit["drift_ratio"] == pytest.approx(4.0)
        assert counters.get("plan_drift") == before + 1
        gauges = ktrace.metrics.snapshot()["gauges"]
        assert gauges["profiler_fit_tier_plan_drift"] == pytest.approx(4.0)

    def test_run_ladder_audits_watermark(self):
        """The generic ladder hook: a profiled fit with a live (injected)
        stats source audits its chosen tier's plan against the phase
        watermark and records the ratio under the tier's label."""
        x = np.random.default_rng(0).normal(size=(128, 64)).astype(np.float32)
        y = (2.0 * np.eye(4)[np.random.default_rng(1).integers(0, 4, 128)]
             - 1.0).astype(np.float32)
        with kprof.profiled(
            True, interval_ms=10_000.0, stats_fn=lambda: 10 * 2**20
        ):
            est = BlockLeastSquaresEstimator(64, 1, 1e-2)
            est.fit(jnp.asarray(x), jnp.asarray(y))
        assert est.last_fit_report.chosen == "fused"
        charged = est.last_fit_report.plans["fused"].total_bytes
        gauges = ktrace.metrics.snapshot()["gauges"]
        assert gauges["profiler_bcd_fit_fused_plan_drift"] == pytest.approx(
            10 * 2**20 / charged, rel=1e-3
        )

    def test_sampler_crash_is_counted_and_run_survives(self):
        calls = {"n": 0}

        def crashing():
            calls["n"] += 1
            if calls["n"] > 2:
                raise RuntimeError("boom")
            return 512

        before = counters.get("profiler_sampler_crash")
        with kprof.profiled(True, interval_ms=1.0, stats_fn=crashing):
            s = kprof.sampler()
            end = time.monotonic() + 5.0
            while not s.crashed and time.monotonic() < end:
                time.sleep(0.005)
            assert s.crashed
        assert counters.get("profiler_sampler_crash") == before + 1


# -- triggered XLA capture -----------------------------------------------------


@pytest.fixture
def capture_seams(monkeypatch, tmp_path):
    started, stopped = [], []
    monkeypatch.setattr(kprof, "_start_trace", started.append)
    monkeypatch.setattr(kprof, "_stop_trace", lambda: stopped.append(1))
    monkeypatch.setenv(kprof.XPROF_DIR_ENV, str(tmp_path / "xprof"))
    monkeypatch.setenv(kprof.XPROF_WINDOW_ENV, "0.02")
    return started, stopped


class TestCapture:
    def test_rate_limited_per_kind(self, capture_seams):
        started, stopped = capture_seams
        paths = []
        for _ in range(5):
            p = kprof.maybe_capture("slo_burn")
            if p:
                paths.append(p)
            time.sleep(0.05)  # let the window close between attempts
        assert len(paths) == kprof.MAX_CAPTURES_PER_KIND
        # another kind gets its own budget
        assert kprof.maybe_capture("serve_burst_oom") is not None
        time.sleep(0.05)
        assert len(kprof.capture_paths()) == kprof.MAX_CAPTURES_PER_KIND + 1
        assert len(started) == len(kprof.capture_paths())

    def test_single_window_at_a_time(self, capture_seams, monkeypatch):
        monkeypatch.setenv(kprof.XPROF_WINDOW_ENV, "5.0")
        assert kprof.maybe_capture("slo_burn") is not None
        # the window is still open — a second trigger (any kind) is a no-op
        assert kprof.maybe_capture("slo_burn") is None
        assert kprof.maybe_capture("deadline_exceeded") is None

    def test_no_dir_no_capture(self, monkeypatch):
        monkeypatch.delenv(kprof.XPROF_DIR_ENV, raising=False)
        assert kprof.maybe_capture("slo_burn") is None

    def test_start_failure_refunds_the_budget(self, capture_seams, monkeypatch):
        """A transient start_trace failure must not burn the kind's cap:
        no window opened means no budget spent."""
        calls = {"n": 0}

        def flaky_start(path):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("profiler session busy")

        monkeypatch.setattr(kprof, "_start_trace", flaky_start)
        assert kprof.maybe_capture("slo_burn") is None
        assert kprof.maybe_capture("slo_burn") is None
        # two failures later, the full budget is still available
        assert kprof.maybe_capture("slo_burn") is not None
        time.sleep(0.05)
        assert kprof.maybe_capture("slo_burn") is not None
        time.sleep(0.05)

    def test_postmortem_fault_triggers_capture(self, capture_seams):
        before = len(kprof.capture_paths())
        counters.record("serve_burst_oom", "chaos probe: capture trigger")
        assert len(kprof.capture_paths()) == before + 1
        time.sleep(0.05)

    def test_slo_burn_breach_triggers_capture(self, capture_seams):
        tracker = ktelemetry.SLOTracker(
            "probe", slo_ms=1.0, budget=0.01, window_s=60.0
        )
        for _ in range(tracker.BURN_CAPTURE_MIN_COUNT + 5):
            tracker.observe(50.0, ok=True)  # every one violates the SLO
        assert any(
            "slo_burn" in p for p in kprof.capture_paths()
        ), kprof.capture_paths()
        time.sleep(0.05)


# -- the wire clock handshake + stitched waterfall ----------------------------


class _Echo:
    """Minimal wire target: answers the request array itself."""

    def submit(self, arr):
        fut = kserve.ServeFuture(request_id=1)
        fut._resolve(value=np.asarray(arr))
        return fut


class TestClockSync:
    def test_clock_sync_offset(self):
        with kwire.WireServer(_Echo(), port=0, label="clk") as ws:
            with kwire.WireClient(port=ws.port, timeout=10.0) as client:
                est = client.clock_sync()
        assert est is not None
        assert est["rtt_us"] >= 0
        # Same process, same trace epoch: the two clocks read the same
        # counter, so the estimated offset is ~the rtt scale, not huge.
        assert abs(est["offset_us"]) < 1e6

    def test_traced_request_carries_client_span(self):
        ktrace.reset()
        with kwire.WireServer(_Echo(), port=0, label="span") as ws:
            ktrace.enable(os.devnull)
            try:
                with kwire.WireClient(port=ws.port, timeout=10.0) as client:
                    rid = client.submit(
                        np.zeros(4, np.float32), client_span=77
                    )
                    reply = client.read()
            finally:
                events = ktrace.events()
                ktrace.disable()
                ktrace.reset()
        assert reply.type == kwire.T_RESPONSE and reply.request_id == rid
        req = [
            e for e in events
            if e.get("ph") == "i" and e.get("name") == "wire.request"
        ]
        assert req and req[-1]["args"].get("client_span") == 77


def _stitch_pipe(rng):
    w = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
    return FunctionTransformer(
        lambda x: jnp.maximum(x * w, 0.0), name="stitch"
    )


class TestStitchedWaterfall:
    def test_two_process_stitch_over_real_sockets(self, rng, tmp_path):
        """The acceptance path: a REAL client process
        (tools/serve_client.py --trace) drives a wire server whose own
        trace is enabled; trace_view --stitch joins the two files by wire
        rid into one waterfall decomposing network vs queue vs device
        time for every request."""
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        import trace_view

        server_trace = str(tmp_path / "server.json")
        client_trace = str(tmp_path / "client.jsonl")
        n_req = 8
        eng = kserve.ServingEngine(
            _stitch_pipe(rng), np.zeros(4, np.float32),
            config=kserve.ServeConfig(buckets=(1, 2), max_wait_ms=1.0),
            label="stitch",
        )
        ktrace.reset()
        ktrace.enable(server_trace)
        try:
            with kserve.Server(eng) as server:
                with kwire.WireServer(server, port=0, label="stitch") as ws:
                    out = subprocess.run(
                        [
                            sys.executable,
                            os.path.join(_REPO, "tools", "serve_client.py"),
                            "--port", str(ws.port), "--shape", "4",
                            "--requests", str(n_req),
                            "--trace", client_trace,
                        ],
                        capture_output=True, text=True, timeout=120,
                        cwd=str(tmp_path),
                    )
            assert out.returncode == 0, out.stderr[-2000:]
            ktrace.flush(server_trace)
        finally:
            ktrace.disable()
            ktrace.reset()

        client_rec = json.loads(out.stdout.splitlines()[0])
        assert client_rec["clock_offset_us"] is not None

        merged = trace_view.stitch(
            trace_view.load_events(server_trace),
            trace_view.load_events(client_trace),
        )
        assert merged["requests"] == n_req
        assert merged["clock"] and "offset_us" in merged["clock"]
        for row in merged["rows"]:
            # every request decomposes: client total = network + server,
            # and the server side carries the serve-phase split
            assert row["client_ms"] > 0 and row["server_ms"] > 0
            assert row["client_ms"] == pytest.approx(
                row["network_ms"] + row["server_ms"], abs=0.01
            )
            assert "queue_wait_ms" in row and "execute_ms" in row
            assert row["client_span"] is not None
        # the CLI face renders the same merge without crashing
        summary = trace_view.stitch_summary(server_trace, client_trace, 3)
        assert "stitched waterfall" in summary

    def test_stitch_pure_function(self):
        """Unit-level join: synthetic client/server events reconstruct
        the expected decomposition exactly."""
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        import trace_view

        client = [
            {"ph": "i", "name": "client.submit", "args": {"rid": 1, "span": 0}},
            {"ph": "i", "name": "client.answer",
             "args": {"rid": 1, "span": 0, "ms": 10.0}},
            {"ph": "i", "name": "client.clock",
             "args": {"offset_us": 5.0, "rtt_us": 2.0}},
        ]
        server = [
            # A SECOND connection with a colliding wire rid (per-conn
            # counters both start at 1): the join must pick conn 2 — the
            # one whose recorded client_span matches this client's span —
            # not whichever connection logged last.
            {"ph": "i", "name": "wire.request",
             "args": {"conn": 1, "wire_rid": 1, "request_id": 4,
                      "client_span": 9}},
            {"ph": "i", "name": "wire.response",
             "args": {"conn": 1, "wire_rid": 1, "ms": 99.0}},
            {"ph": "i", "name": "wire.request",
             "args": {"conn": 2, "wire_rid": 1, "request_id": 9,
                      "client_span": 0}},
            {"ph": "i", "name": "wire.response",
             "args": {"conn": 2, "wire_rid": 1, "ms": 7.5}},
            {"ph": "X", "name": "serve.request", "ts": 0, "dur": 0,
             "args": {"request_id": 9, "queue_wait_ms": 3.0,
                      "execute_ms": 2.0, "h2d_ms": 0.5}},
        ]
        merged = trace_view.stitch(server, client)
        assert merged["requests"] == 1
        assert merged["server_connections"] == 2
        assert merged["connection"] == 2
        row = merged["rows"][0]
        assert row["request_id"] == 9
        assert row["network_ms"] == pytest.approx(2.5)
        assert row["queue_wait_ms"] == 3.0
        assert row["execute_ms"] == 2.0
        assert merged["clock"]["offset_us"] == 5.0
        assert merged["client_submits"] == 1


# -- profiled serving ----------------------------------------------------------


class TestProfiledServe:
    def test_serve_buckets_land_in_ledger_bit_equal(self, rng):
        eng = kserve.ServingEngine(
            _stitch_pipe(rng), np.zeros(4, np.float32),
            config=kserve.ServeConfig(buckets=(1, 2), max_wait_ms=1.0),
            label="prof",
        )
        reqs = rng.normal(size=(6, 4)).astype(np.float32)
        plain = eng.infer(reqs)
        with kprof.profiled(True):
            profiled = eng.infer(reqs)
            led = kprof.ledger()
        assert np.array_equal(plain, profiled)  # profiling changes no bits
        serve_rows = {k: v for k, v in led.items() if k.startswith("serve:prof")}
        assert serve_rows, f"no serve rows in {sorted(led)}"
        assert all(v["runs"] >= 1 for v in serve_rows.values())
