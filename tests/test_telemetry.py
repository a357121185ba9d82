"""Production telemetry tier (core.telemetry + the serve/trace wiring):
the live SLO surface, Prometheus exposition (golden-file exact), the
metrics exporters, the flight-recorder postmortem path — including the
ISSUE 11 acceptance test that an injected runtime OOM inside a running
``Server`` in a FRESH process (tracing disabled) produces a schema-valid
postmortem dump containing the fault instant and the victim requests'
lifecycle spans."""

import glob
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

from keystone_tpu.core import telemetry, trace
from keystone_tpu.core.resilience import counters

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Postmortem caps/paths and SLO trackers are process-global."""
    telemetry._reset_state()
    trace.flight_reset()
    yield
    telemetry._reset_state()


# -- SLO tracker --------------------------------------------------------------


class TestSLOTracker:
    def test_window_percentiles_and_burn_rate(self):
        clock = {"t": 100.0}
        t = telemetry.SLOTracker(
            "eng", slo_ms=10.0, budget=0.1, window_s=60.0,
            clock=lambda: clock["t"],
        )
        for i, v in enumerate((1.0, 2.0, 3.0, 50.0, 4.0)):
            clock["t"] = 100.0 + i  # 1s apart -> QPS computable
            t.observe(v)
        s = t.summary()
        assert s["slo_ms"] == 10.0 and s["budget"] == 0.1
        w = s["window"]
        assert w["count"] == 5
        assert w["violations"] == 1  # the 50ms outlier
        # violation rate 0.2 against a 0.1 budget -> burning 2x budget
        assert w["burn_rate"] == pytest.approx(2.0)
        assert w["p99_ms"] == 50.0 and w["max_ms"] == 50.0
        assert w["qps"] == pytest.approx(5 / 4, rel=0.01)
        assert s["total"]["requests"] == 5 and s["total"]["errors"] == 0
        json.dumps(s)

    def test_errors_burn_budget_and_window_rolls(self):
        clock = {"t": 0.0}
        t = telemetry.SLOTracker(
            "eng", slo_ms=100.0, budget=0.5, window_s=10.0,
            clock=lambda: clock["t"],
        )
        t.observe(1.0, ok=False)  # an error inside SLO latency still burns
        assert t.summary()["window"]["violations"] == 1
        assert t.summary()["total"]["errors"] == 1
        clock["t"] = 100.0  # far past the window
        t.observe(1.0, ok=True)
        w = t.summary()["window"]
        assert w["count"] == 1 and w["violations"] == 0  # old error rolled off
        assert t.summary()["total"]["violations"] == 1  # totals never forget

    def test_env_targets_per_label(self, monkeypatch):
        monkeypatch.setenv(telemetry.SLO_MS_ENV, "25")
        assert telemetry.slo_target_ms("anything") == 25.0
        monkeypatch.setenv(
            telemetry.SLO_MS_ENV, "mnist_fft=20,default=75,cifar_conv=150"
        )
        assert telemetry.slo_target_ms("mnist_fft") == 20.0
        assert telemetry.slo_target_ms("cifar_conv") == 150.0
        assert telemetry.slo_target_ms("unknown") == 75.0
        monkeypatch.delenv(telemetry.SLO_MS_ENV)
        assert telemetry.slo_target_ms("x") == telemetry.DEFAULT_SLO_MS

    def test_registered_trackers_ride_in_metrics_snapshot(self):
        t = telemetry.register_slo("snap_probe", slo_ms=5.0)
        t.observe(1.0)
        snap = trace.metrics.snapshot()
        assert snap["slo"]["snap_probe"]["window"]["count"] == 1
        json.dumps(snap)  # bench embeds this verbatim


# -- Prometheus exposition ----------------------------------------------------


def test_prometheus_text_golden():
    """Exact exposition-format output for a fixed snapshot — counters,
    gauges, histogram summaries with quantile labels, and an adopted
    group flattened as counters."""
    m = trace.Metrics()
    m.inc("alpha_total", 3)
    m.gauge("queue_depth", 2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat_ms", v)

    class Group:
        def snapshot(self, reset=False):
            return {"corrupt_image": 2}

    m.adopt("faults", Group())
    text = telemetry.prometheus_text(m.snapshot())
    assert text == textwrap.dedent(
        """\
        # TYPE keystone_alpha_total counter
        keystone_alpha_total 3
        # TYPE keystone_queue_depth gauge
        keystone_queue_depth 2.5
        # TYPE keystone_lat_ms summary
        keystone_lat_ms{quantile="0.50"} 3.0
        keystone_lat_ms{quantile="0.90"} 4.0
        keystone_lat_ms{quantile="0.99"} 4.0
        keystone_lat_ms_sum 10.0
        keystone_lat_ms_count 4
        # TYPE keystone_faults_corrupt_image counter
        keystone_faults_corrupt_image 2
        """
    )


def test_prometheus_text_labeled_golden():
    """ISSUE 20: the same fixed snapshot rendered with ``host``/``rank``
    labels — every sample line carries the sorted label block, the
    histogram quantile label composes AFTER the member labels, and the
    TYPE lines stay label-free (exposition-format exact)."""
    m = trace.Metrics()
    m.inc("alpha_total", 3)
    m.gauge("queue_depth", 2.5)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat_ms", v)

    class Group:
        def snapshot(self, reset=False):
            return {"corrupt_image": 2}

    m.adopt("faults", Group())
    text = telemetry.prometheus_text(
        m.snapshot(), labels={"host": "h0", "rank": 0}
    )
    assert text == textwrap.dedent(
        """\
        # TYPE keystone_alpha_total counter
        keystone_alpha_total{host="h0",rank="0"} 3
        # TYPE keystone_queue_depth gauge
        keystone_queue_depth{host="h0",rank="0"} 2.5
        # TYPE keystone_lat_ms summary
        keystone_lat_ms{host="h0",rank="0",quantile="0.50"} 3.0
        keystone_lat_ms{host="h0",rank="0",quantile="0.90"} 4.0
        keystone_lat_ms{host="h0",rank="0",quantile="0.99"} 4.0
        keystone_lat_ms_sum{host="h0",rank="0"} 10.0
        keystone_lat_ms_count{host="h0",rank="0"} 4
        # TYPE keystone_faults_corrupt_image counter
        keystone_faults_corrupt_image{host="h0",rank="0"} 2
        """
    )


def test_render_labels_sorts_escapes_and_skips_none():
    assert telemetry.render_labels(None) == ""
    assert telemetry.render_labels({}) == ""
    assert telemetry.render_labels({"rank": None}) == ""
    assert (
        telemetry.render_labels({"b": 'say "hi"\n', "a": "x\\y"})
        == '{a="x\\\\y",b="say \\"hi\\"\\n"}'
    )
    assert (
        telemetry.render_labels({"host": "h0"}, extra='quantile="0.99"')
        == '{host="h0",quantile="0.99"}'
    )
    assert telemetry.render_labels({}, extra='quantile="0.99"') == (
        '{quantile="0.99"}'
    )


def test_prometheus_text_without_labels_is_byte_identical():
    """labels=None must not perturb the un-labeled exposition the
    original golden test pins (single-process scrapes keep their bytes)."""
    m = trace.Metrics()
    m.inc("alpha_total", 3)
    assert telemetry.prometheus_text(m.snapshot()) == telemetry.prometheus_text(
        m.snapshot(), labels=None
    )
    assert telemetry.prometheus_text(m.snapshot(), labels={}) == (
        telemetry.prometheus_text(m.snapshot())
    )


def test_prometheus_text_sanitizes_names_and_skips_non_numeric():
    m = trace.Metrics()
    m.inc("weird.name-with/chars")

    class Group:
        def snapshot(self, reset=False):
            return {"nested": {"ok": 1, "label": "not-a-number"}}

    m.adopt("grp", Group())
    text = telemetry.prometheus_text(m.snapshot())
    assert "keystone_weird_name_with_chars 1" in text
    assert "keystone_grp_nested_ok 1" in text
    assert "not-a-number" not in text


def test_metrics_file_writer_atomic_and_periodic(tmp_path):
    path = str(tmp_path / "metrics.prom")
    trace.metrics.inc("writer_probe_total")
    w = telemetry.MetricsWriter(path, interval_s=0.05)
    w.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if os.path.exists(path):
                break
            time.sleep(0.01)
        body = open(path).read()
        assert "keystone_writer_probe_total" in body
    finally:
        w.stop()
    # no temp litter from the atomic writes
    assert [p for p in os.listdir(tmp_path) if ".tmp" in p] == []


def test_metrics_http_endpoint(tmp_path):
    trace.metrics.inc("http_probe_total")
    server = telemetry.start_metrics_server(0)  # ephemeral port
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "keystone_http_probe_total" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10
            )
    finally:
        server.shutdown()


def test_statusz_and_healthz_endpoints():
    """The /statusz debug page (ISSUE 15): one JSON snapshot of provider
    state (router engines, ring/stream), SLO windows, and the numerics
    observatory — golden-pinned schema; /healthz answers liveness."""
    from keystone_tpu.core import numerics as knum

    telemetry.register_statusz("probe_provider", lambda: {"engines": 2})
    telemetry.register_statusz(
        "sick_provider", lambda: (_ for _ in ()).throw(RuntimeError("down"))
    )
    trace.metrics.gauge("statusz_probe_gauge", 7)
    server = telemetry.start_metrics_server(0)
    try:
        port = server.server_address[1]
        health = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10
            ).read()
        )
        assert health == {"ok": True}
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/statusz", timeout=10
        )
        assert resp.headers["Content-Type"] == "application/json"
        doc = json.loads(resp.read())
        # Golden schema: the keys operators script against.
        assert doc["schema"] == "keystone.statusz/1"
        assert set(doc) >= {
            "schema", "time_unix", "pid", "providers", "slo", "numerics",
            "faults", "counters", "gauges",
        }
        assert doc["providers"]["probe_provider"] == {"engines": 2}
        # One sick provider reports its error without blanking the page.
        assert "RuntimeError" in doc["providers"]["sick_provider"]["error"]
        assert doc["gauges"]["statusz_probe_gauge"] == 7
        assert set(doc["numerics"]) >= {
            "active", "sites", "conditioning", "provenance", "drift",
        }
        assert doc["pid"] == os.getpid()
    finally:
        server.shutdown()
        telemetry.unregister_statusz("probe_provider")
        telemetry.unregister_statusz("sick_provider")
        del knum


def test_statusz_carries_router_and_stream_state(tmp_path):
    """Routers and ingest streams self-register as /statusz providers and
    unregister on close — the page shows the CURRENT topology."""
    from keystone_tpu.core import frontend as kfrontend

    router = kfrontend.ShapeRouter(label="statusz_router")
    try:
        snap = telemetry.statusz_snapshot()
        assert "router:statusz_router" in snap["providers"]
        assert snap["providers"]["router:statusz_router"]["engines"] == {}
    finally:
        router.close()
    assert "router:statusz_router" not in (
        telemetry.statusz_snapshot()["providers"]
    )


# -- postmortem dumps ---------------------------------------------------------


def test_counted_fault_dumps_schema_valid_postmortem(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.POSTMORTEM_DIR_ENV, str(tmp_path))
    assert not trace.enabled()
    with trace.span("doomed_phase", cat="probe"):
        pass
    counters.record("deadline_exceeded", "probe: watchdog tripped")
    dumps = glob.glob(str(tmp_path / "postmortem_deadline_exceeded_*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["schema"] == telemetry.POSTMORTEM_SCHEMA
    assert doc["fault"]["kind"] == "deadline_exceeded"
    assert doc["trace_enabled"] is False
    # the ring carried the pre-fault span AND the fault instant itself
    names = [e.get("name") for e in doc["flight"]]
    assert "doomed_phase" in names and "fault" in names
    assert doc["metrics"]["faults"]["deadline_exceeded"] >= 1
    assert dumps[0] in telemetry.postmortem_paths()


def test_postmortem_rate_cap_and_kind_filter(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.POSTMORTEM_DIR_ENV, str(tmp_path))
    for _ in range(telemetry.MAX_DUMPS_PER_KIND + 3):
        counters.record("serve_burst_oom", "storm")
    assert (
        len(glob.glob(str(tmp_path / "postmortem_serve_burst_oom_*")))
        == telemetry.MAX_DUMPS_PER_KIND
    )
    # a non-postmortem fault family never dumps
    counters.record("io_retry", "transient")
    assert glob.glob(str(tmp_path / "postmortem_io_retry_*")) == []


def test_no_dump_without_dir(tmp_path, monkeypatch):
    monkeypatch.delenv(telemetry.POSTMORTEM_DIR_ENV, raising=False)
    assert telemetry.maybe_postmortem("serve_burst_oom", "no dir") is None
    assert telemetry.postmortem_paths() == []


def test_postmortems_linked_from_reports(tmp_path, monkeypatch):
    from keystone_tpu.core.memory import FitReport
    from keystone_tpu.core.serve import ServerStats

    monkeypatch.setenv(telemetry.POSTMORTEM_DIR_ENV, str(tmp_path))
    counters.record("nonfinite_model", "probe")
    [path] = telemetry.postmortem_paths()
    assert path in FitReport().record()["postmortems"]
    assert path in ServerStats().record()["postmortems"]


# -- the fresh-process acceptance path (ISSUE 11) -----------------------------


def test_fresh_process_serve_oom_postmortem(tmp_path):
    """A runtime OOM inside a running ``Server`` in a FRESH interpreter
    with tracing DISABLED must produce a schema-valid flight-recorder
    postmortem containing the ``serve_burst_oom`` fault instant and the
    victim requests' lifecycle evidence: their ``serve.submit`` instants
    and the failed ``serve.execute`` span naming their id range — while
    the endpoint degrades and still answers every request bit-equal."""
    dump_dir = str(tmp_path / "dumps")
    script = textwrap.dedent(
        """
        import os
        os.environ['JAX_PLATFORMS'] = 'cpu'
        import sys
        sys.path.insert(0, 'tests')
        import numpy as np
        import jax.numpy as jnp
        import faults
        from keystone_tpu.core import serve as kserve, trace
        from keystone_tpu.core.pipeline import FunctionTransformer

        assert not trace.enabled(), 'tracing must be OFF for this proof'
        assert trace.flight_depth() > 0, 'flight ring must be on'
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
        pipe = FunctionTransformer(lambda x: jnp.maximum(x * w, b), name='pm')
        cfg = kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0)
        engine = kserve.ServingEngine(
            pipe, np.zeros(16, np.float32), config=cfg, label='pm')
        real = engine._execute
        state = {'n': 0}

        def failing(bucket, dev):
            if bucket == 4 and state['n'] < 1:
                state['n'] += 1
                raise faults.resource_exhausted_error()
            return real(bucket, dev)

        engine._execute = failing
        reqs = rng.normal(size=(12, 16)).astype(np.float32)
        with kserve.Server(engine) as server:
            futs = [server.submit(r) for r in reqs]
            answers = np.stack([f.result(30.0) for f in futs])
        engine._execute = real
        assert state['n'] == 1, 'the OOM was never injected'
        np.testing.assert_array_equal(answers, engine.offline(reqs))
        print('PM_SERVE_OK')
        """
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KEYSTONE_POSTMORTEM_DIR=dump_dir,
    )
    env.pop("KEYSTONE_TRACE", None)
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "PM_SERVE_OK" in res.stdout

    dumps = glob.glob(os.path.join(dump_dir, "postmortem_serve_burst_oom_*"))
    assert len(dumps) == 1, dumps
    doc = json.load(open(dumps[0]))
    # schema-valid
    assert doc["schema"] == telemetry.POSTMORTEM_SCHEMA
    assert set(doc) >= {
        "schema", "time_unix", "pid", "fault", "trace_enabled",
        "flight_depth", "flight", "metrics",
    }
    assert doc["trace_enabled"] is False
    assert doc["fault"]["kind"] == "serve_burst_oom"
    flight = doc["flight"]
    # the triggering fault instant is in the ring
    fault_events = [
        e for e in flight
        if e.get("name") == "fault"
        and e.get("args", {}).get("kind") == "serve_burst_oom"
    ]
    assert fault_events, "fault instant missing from the flight ring"
    # the victim micro-batch: a serve.execute span that FAILED with the
    # injected error, naming its request-id range
    failed_exec = [
        e for e in flight
        if e.get("name") == "serve.execute" and e.get("args", {}).get("error")
    ]
    assert failed_exec, "no failed serve.execute span in the ring"
    args = failed_exec[0]["args"]
    assert args["req_first"] <= args["req_last"]
    # ...and the victims' births: serve.submit instants for that id range
    submitted = {
        e["args"]["request_id"]
        for e in flight
        if e.get("name") == "serve.submit"
    }
    victims = set(range(args["req_first"], args["req_last"] + 1))
    assert victims <= submitted, (victims, submitted)
    # the counters snapshot rode along
    assert doc["metrics"]["faults"]["serve_burst_oom"] >= 1


def _child_reports_writer_state(q):
    from keystone_tpu.core import telemetry as t

    q.put(t._env_writer is None and t._env_server is None)


def test_worker_process_does_not_activate_exporters(tmp_path, monkeypatch):
    """Spawned helper processes (decode workers) inherit the parent env;
    they must NOT each start a metrics writer clobbering the shared file
    (or race to bind the metrics port) — only the main process exports."""
    import multiprocessing

    monkeypatch.setenv(telemetry.METRICS_FILE_ENV, str(tmp_path / "w.prom"))
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_child_reports_writer_state, args=(q,))
    p.start()
    try:
        assert q.get(timeout=60) is True, (
            "a spawned child activated the env exporters"
        )
    finally:
        p.join(30)


def test_fresh_process_env_activates_metrics_file(tmp_path):
    """KEYSTONE_METRICS_FILE in the environment must stand up the periodic
    Prometheus writer for ANY process that imports the resilience layer —
    no serving, no explicit telemetry call."""
    path = str(tmp_path / "metrics.prom")
    script = textwrap.dedent(
        """
        import time
        from keystone_tpu.core.resilience import counters
        from keystone_tpu.core import trace
        trace.metrics.inc('env_probe_total', 7)
        time.sleep(0.3)
        print('ENV_METRICS_OK')
        """
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        KEYSTONE_METRICS_FILE=path,
        KEYSTONE_METRICS_INTERVAL_S="0.05",
    )
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "ENV_METRICS_OK" in res.stdout
    body = open(path).read()
    assert "keystone_env_probe_total 7" in body


# -- per-request lifecycle + stats-in-registry (the serve wiring) -------------


def _tiny_engine(rng):
    import jax.numpy as jnp

    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.pipeline import FunctionTransformer

    w = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(16,)).astype(np.float32))
    pipe = FunctionTransformer(lambda x: jnp.maximum(x * w, b), name="ph")
    cfg = kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0)
    return kserve.ServingEngine(
        pipe, np.zeros(16, np.float32), config=cfg, label="phase_probe"
    )


def test_request_phase_decomposition_and_ids(rng):
    from keystone_tpu.core import serve as kserve

    engine = _tiny_engine(rng)
    reqs = rng.normal(size=(10, 16)).astype(np.float32)
    with kserve.Server(engine) as server:
        futs = [server.submit(r) for r in reqs]
        for f in futs:
            f.result(30.0)
    ids = [f.request_id for f in futs]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    assert ids[0] >= 1
    for f in futs:
        p = f.phases
        assert p is not None and p["request_id"] == f.request_id
        for key in kserve.PHASE_KEYS:
            assert key in p, key
        assert p["latency_ms"] > 0
        # the decomposition's parts never exceed the whole (answer slack
        # aside, each phase is a sub-interval of the request's life)
        parts = (
            p["queue_wait_ms"] + p["h2d_ms"] + p["device_wait_ms"]
            + p["execute_ms"] + p["d2h_ms"] + p["answer_ms"]
        )
        assert parts <= p["latency_ms"] * 1.5 + 1.0
        assert p["pad_overhead_ms"] <= p["execute_ms"] + 1e-9
    # aggregation used by serve_bench / results["serving"]
    bd = kserve.phase_breakdown([f.phases for f in futs])
    assert bd["requests"] == len(futs)
    assert bd["queue_wait_ms"]["p99"] >= bd["queue_wait_ms"]["mean"] >= 0


def test_server_stats_exported_into_metrics_registry(rng):
    from keystone_tpu.core import serve as kserve

    engine = _tiny_engine(rng)
    before = trace.metrics.snapshot()["counters"]
    reqs = rng.normal(size=(9, 16)).astype(np.float32)
    with kserve.Server(engine) as server:
        for f in [server.submit(r) for r in reqs]:
            f.result(30.0)
        stats = server.stats
    snap = trace.metrics.snapshot()
    c = snap["counters"]

    def delta(name):
        return c.get(name, 0) - before.get(name, 0)

    assert delta("serve_batches") == stats.batches
    flush_total = sum(
        delta(f"serve_flush_{r}") for r in ("full", "deadline", "idle")
    )
    assert flush_total == (
        stats.flush_full + stats.flush_deadline + stats.flush_idle
    )
    assert delta("serve_padded_rows") == stats.padded_rows
    assert snap["gauges"]["serve_mean_occupancy"] == pytest.approx(
        stats.occupancy(), abs=1e-6
    )
    # one snapshot covers serving: the SLO group is there too
    assert snap["slo"]["phase_probe"]["total"]["requests"] == 9


def test_bucket_retirement_exported(rng):
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    import faults

    engine = _tiny_engine(rng)
    before = trace.metrics.snapshot()["counters"].get(
        "serve_bucket_retired", 0
    )
    engine._retire_bucket(4, "probe retirement")
    snap = trace.metrics.snapshot()
    assert snap["counters"]["serve_bucket_retired"] == before + 1
    assert snap["gauges"]["serve_live_buckets"] == 2
    del faults  # imported only to mirror the suite's path setup


def test_serve_bench_record_gains_phase_and_slo_sections(rng):
    from keystone_tpu.core import serve as kserve

    engine = _tiny_engine(rng)
    reqs = rng.normal(size=(24, 16)).astype(np.float32)
    rec = kserve.serve_bench(
        engine, reqs, clients=3, depth=4, unbatched_baseline=False
    )
    json.dumps(rec)
    bd = rec["phase_breakdown"]
    assert bd["requests"] == 24
    for key in ("queue_wait_ms", "execute_ms", "pad_overhead_ms"):
        assert {"mean", "p99"} <= set(bd[key])
    slo = rec["slo"]
    assert slo["label"] == "phase_probe"
    assert slo["total"]["requests"] == 24
    assert "burn_rate" in slo["window"]
