"""Unified tracing & metrics (core.trace) — the ISSUE 5 acceptance set:

* span nesting/threading correctness (depth/parents never cross threads);
* disabled-mode overhead guard: no retained allocation growth;
* Chrome trace_event (Perfetto) JSON schema validation + JSONL export;
* ``Pipeline.profile`` per-node bytes/dtype/shape on a 3-node pipeline;
* streaming-ingest overlap efficiency recomputed from span intervals
  matches the test's own clock readings in the same pass;
* solver ladder tier spans with the FitReport linked in;
* ``resilience.counters`` atomic ``snapshot(reset=)`` (no read/reset race)
  and fault instants in the trace (chaos ``--trace`` invariant);
* ``stage_timer`` back-compat (same log line, now also a span) and the
  ``KEYSTONE_LOG_LEVEL`` env knob;
* the fit timeline (ISSUE 26): span ``id`` / ``parent_id`` / ``root``, a
  stage's self time and its wait and copy sums on an injected clock, the
  stage sets of a tiny CIFAR and a tiny TIMIT fit, profiler annotations
  only while tracing, and the cost of a span with the ring on;
* named host sections (ISSUE 36): a stage's self time is its wait, its
  copies, its sections and ``other`` to the microsecond, tracing on or off
  and with the flight ring off; the sections a tiny fit of each kind records.
"""

import gc
import io
import json
import logging
import os
import sys
import tarfile
import threading
import time
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core import ingest, trace
from keystone_tpu.core.logging import configure_logging, stage_timer
from keystone_tpu.core.pipeline import FunctionTransformer, Pipeline
from keystone_tpu.core.resilience import FaultCounters, counters
from keystone_tpu.loaders import image_loaders
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import trace_view  # noqa: E402  (tools/trace_view.py)


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with tracing off and the buffer empty —
    the module is process-global state."""
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _trace_to(tmp_path, name="t.json"):
    path = str(tmp_path / name)
    trace.enable(path)
    return path


def _spans_by_name(events):
    out = {}
    for ev in events:
        if ev.get("ph") == "X":
            out.setdefault(ev["name"], []).append(ev)
    return out


# -- span nesting / threading -------------------------------------------------


def test_span_nesting_depth_and_parent(tmp_path):
    path = _trace_to(tmp_path)
    with trace.span("outer"):
        with trace.span("inner"):
            pass
    with trace.span("sibling"):
        pass
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    outer, inner, sib = spans["outer"][0], spans["inner"][0], spans["sibling"][0]
    assert outer["args"]["depth"] == 0 and "parent" not in outer["args"]
    assert inner["args"]["depth"] == 1 and inner["args"]["parent"] == "outer"
    assert sib["args"]["depth"] == 0
    # time containment: the child interval sits inside the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_threads_have_independent_stacks(tmp_path):
    path = _trace_to(tmp_path)
    barrier = threading.Barrier(2)

    def worker(tag):
        barrier.wait()
        with trace.span(f"{tag}_outer"):
            time.sleep(0.01)
            with trace.span(f"{tag}_inner"):
                time.sleep(0.01)

    threads = [
        threading.Thread(target=worker, args=(t,), name=f"w-{t}")
        for t in ("a", "b")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    for tag in ("a", "b"):
        inner = spans[f"{tag}_inner"][0]
        # nesting resolves within the thread, never across: a_inner's
        # parent is a_outer even though b_outer was open concurrently
        assert inner["args"]["parent"] == f"{tag}_outer"
        assert inner["args"]["depth"] == 1
        assert inner["tid"] == spans[f"{tag}_outer"][0]["tid"]
    assert spans["a_outer"][0]["tid"] != spans["b_outer"][0]["tid"]


def test_generator_hosted_span_abort_is_not_an_error(tmp_path):
    # ingest.consume spans live across a generator yield: a consumer that
    # stops early (or raises OUTSIDE the generator frame) delivers
    # GeneratorExit at the yield — that is an abort, not the pipeline's
    # failure, and must never masquerade as the span's error type.
    path = _trace_to(tmp_path)

    def gen():
        with trace.span("hosted"):
            yield 1

    g = gen()
    next(g)
    g.close()  # delivers GeneratorExit at the yield point
    trace.flush(path)
    args = _spans_by_name(trace_view.load_events(path))["hosted"][0]["args"]
    assert args.get("aborted") is True
    assert "error" not in args


def test_span_error_attribute_recorded(tmp_path):
    path = _trace_to(tmp_path)
    with pytest.raises(ValueError):
        with trace.span("doomed"):
            raise ValueError("boom")
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    assert spans["doomed"][0]["args"]["error"] == "ValueError"


# -- ids: a span names what caused it -----------------------------------------


def test_span_id_parent_id_and_root_across_nesting(tmp_path):
    path = _trace_to(tmp_path)
    with trace.span("fit", cat="fit"):
        with trace.span("outer"):
            with trace.span("inner"):
                pass
        with trace.span("sibling"):
            pass
    with trace.span("next_fit", cat="fit"):
        pass
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    arg = lambda name: spans[name][0]["args"]  # noqa: E731
    fit, outer, inner, sib = arg("fit"), arg("outer"), arg("inner"), arg("sibling")
    ids = [a["id"] for a in (fit, outer, inner, sib, arg("next_fit"))]
    assert len(set(ids)) == 5  # process-unique
    assert "parent_id" not in fit and fit["root"] == fit["id"]
    assert outer["parent_id"] == fit["id"] and sib["parent_id"] == fit["id"]
    assert inner["parent_id"] == outer["id"] and inner["parent"] == "outer"
    # the spans of one fit share its root; the next fit has another
    assert {a["root"] for a in (fit, outer, inner, sib)} == {fit["id"]}
    assert arg("next_fit")["root"] == arg("next_fit")["id"] != fit["id"]
    assert inner["depth"] == 2  # depth and parent stay beside the ids


def test_span_ids_and_roots_across_two_threads(tmp_path):
    path = _trace_to(tmp_path)
    barrier = threading.Barrier(2)

    def worker(tag):
        barrier.wait()
        with trace.span(f"{tag}_root"):
            for _ in range(20):
                with trace.span(f"{tag}_leaf"):
                    pass

    threads = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10.0)
        assert not t.is_alive()
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    every = [ev["args"]["id"] for evs in spans.values() for ev in evs]
    assert len(every) == 42 and len(set(every)) == 42
    for tag in ("a", "b"):
        root = spans[f"{tag}_root"][0]["args"]
        for leaf in spans[f"{tag}_leaf"]:
            # a root never crosses threads, whatever was open elsewhere
            assert leaf["args"]["root"] == root["id"]
            assert leaf["args"]["parent_id"] == root["id"]


# -- stages: self time, waits and copies on an injected clock -------------------


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, seconds):
        self.t += seconds


def _last(name):
    return trace.metrics.hist_windows()[name]["samples"][-1]


@pytest.mark.parametrize("flight_depth", [trace.DEFAULT_FLIGHT_DEPTH, 0])
def test_stage_self_time_and_wait_on_injected_clock(monkeypatch, flight_depth):
    """A stage's self time leaves out the stage nested in it; a wait or a
    copy goes to the innermost open stage only.  With the flight ring off
    the spans are no-ops and the sums are the same."""
    clock = _FakeClock()
    monkeypatch.setattr(trace, "_clock", clock)
    depth_before = trace.flight_depth()
    trace.set_flight_depth(flight_depth)
    try:
        with stage_timer("t_outer"):
            clock.tick(0.010)
            with trace._Charged(trace.span("device", cat="wait")):
                clock.tick(0.004)  # blocked beneath t_outer
            with stage_timer("t_inner"):
                clock.tick(0.020)
                with trace.d2h("read", 64):
                    clock.tick(0.003)  # counts as waiting, beneath t_inner
                with trace.h2d("chunk", 2_000_000):
                    clock.tick(0.002)
            clock.tick(0.001)
        assert (trace.span("x") is trace._NULL) == (flight_depth == 0)
    finally:
        trace.set_flight_depth(depth_before)
    assert _last("stage_ms.t_inner") == pytest.approx(25.0)
    assert _last("stage_ms.t_outer") == pytest.approx(15.0)  # 40 less 25
    assert _last("stage_wait_ms.t_outer") == pytest.approx(4.0)
    assert _last("stage_wait_ms.t_inner") == pytest.approx(3.0)
    assert _last("stage_h2d_ms.t_inner") == pytest.approx(2.0)
    assert _last("stage_h2d_mb.t_inner") == 2.0
    assert _last("stage_h2d_mb.t_outer") == 0.0 == _last("stage_h2d_ms.t_outer")


def test_wait_blocks_and_returns_its_value():
    x = jnp.arange(8) * 2
    with stage_timer("t_wait"):
        got = trace.wait(x, "probe")
    assert got is x
    waits = [e for e in trace.flight_events() if e.get("cat") == "wait"]
    assert [e["name"] for e in waits] == ["probe"]
    assert waits[0]["args"]["parent"] == "t_wait"
    assert _last("stage_wait_ms.t_wait") >= waits[0]["dur"] / 1e3


# -- named host sections: every microsecond of a stage in exactly one part --------


def _ms(us):
    return us / 1e3


def _nested_sections(clock):
    """A section inside a section: each is charged its own time only."""
    with stage_timer("h_nest"):
        clock.tick(0.000_007)
        with trace.host("dispatch", "outer"):
            clock.tick(0.000_100)
            with trace.host("place"):
                clock.tick(0.000_030)
            with trace.host("dispatch", "inner"):
                clock.tick(0.000_011)
            clock.tick(0.000_002)
    return {"h_nest": {
        "self": 150, "other": 7,
        "dispatch": (113, 2, 102), "place": (30, 1, 30),
    }}


def _wait_inside_a_section(clock):
    with stage_timer("h_wait"):
        with trace.host("dispatch"):
            clock.tick(0.000_040)
            with trace._Charged(trace.span("device", cat="wait")):
                clock.tick(0.004_600)  # one long wait
            with trace.d2h("read", 8):
                clock.tick(0.000_300)
            clock.tick(0.000_005)
        clock.tick(0.000_001)
    return {"h_wait": {
        "self": 4946, "other": 1, "dispatch": (45, 1, 45), "wait": (4900, 2, 4600),
    }}


def _section_inside_an_h2d(clock):
    with stage_timer("h_copy"):
        with trace.h2d("chunk", 3_000_000):
            clock.tick(0.000_200)
            with trace.host("stack"):
                clock.tick(0.000_050)
            clock.tick(0.000_020)
        with trace.h2d("chunk", 1_000_000):
            clock.tick(0.000_090)
    return {"h_copy": {
        "self": 360, "other": 0, "stack": (50, 1, 50), "h2d": (310, 2, 220),
        "h2d_mb": 4.0,
    }}


def _two_threads(clock):
    """Each thread's charges go to its own open stage; the clock is shared,
    so the threads take turns."""
    import threading

    turn = threading.Semaphore(0)
    done = threading.Semaphore(0)

    def worker():
        turn.acquire()
        with stage_timer("h_thread_b"):
            with trace.host("draw"):
                clock.tick(0.000_060)
            clock.tick(0.000_004)
        done.release()

    t = threading.Thread(target=worker)
    t.start()
    with stage_timer("h_thread_a"):
        with trace.host("dispatch"):
            clock.tick(0.000_010)
            turn.release()
            done.acquire()  # the other thread's 64 us pass on this one's clock
            t.join()
        clock.tick(0.000_003)
    return {
        "h_thread_a": {"self": 77, "other": 3, "dispatch": (74, 1, 74)},
        "h_thread_b": {"self": 64, "other": 4, "draw": (60, 1, 60)},
    }


def _stage_nested_in_a_stage(clock):
    """A stage opened inside a section: its whole duration leaves the outer
    stage's self time and the section's charge alike."""
    with stage_timer("h_outer"):
        clock.tick(0.000_005)
        with trace.host("dispatch"):
            clock.tick(0.000_020)
            with stage_timer("h_inner"):
                clock.tick(0.000_300)
                with trace.host("concat"):
                    clock.tick(0.000_040)
            clock.tick(0.000_002)
        with trace.host("concat"):
            clock.tick(0.000_009)
    return {
        "h_outer": {"self": 36, "other": 5, "dispatch": (22, 1, 22), "concat": (9, 1, 9)},
        "h_inner": {"self": 340, "other": 300, "concat": (40, 1, 40)},
    }


def _exception_leaves_a_section(clock):
    with pytest.raises(RuntimeError):
        with stage_timer("h_raise"):
            clock.tick(0.000_002)
            with trace.host("plan"):
                clock.tick(0.000_015)
                with trace.host("search"):
                    clock.tick(0.000_008)
                    raise RuntimeError("denied")
    with stage_timer("h_raise_next"):  # nothing of the failed stage is left open
        with trace.host("plan"):
            clock.tick(0.000_001)
    return {
        "h_raise": {"self": 25, "other": 2, "plan": (15, 1, 15), "search": (8, 1, 8)},
        "h_raise_next": {"self": 1, "other": 0, "plan": (1, 1, 1)},
    }


@pytest.mark.parametrize("mode", ["ring", "no_ring", "traced"])
@pytest.mark.parametrize("scenario", [
    _nested_sections, _wait_inside_a_section, _section_inside_an_h2d, _two_threads,
    _stage_nested_in_a_stage, _exception_leaves_a_section,
])
def test_stage_parts_sum_to_its_self_time(monkeypatch, tmp_path, scenario, mode):
    """self = wait + h2d + the named sections + other, to the microsecond,
    with the flight ring on, with ``KEYSTONE_FLIGHT_DEPTH=0`` (the spans are
    no-ops) and while tracing."""
    clock = _FakeClock()
    monkeypatch.setattr(trace, "_clock", clock)
    depth_before = trace.flight_depth()
    trace.set_flight_depth(0 if mode == "no_ring" else depth_before)
    if mode == "traced":
        _trace_to(tmp_path)
    try:
        expected = scenario(clock)
        assert (trace.span("x") is trace._NULL) == (mode == "no_ring")
    finally:
        trace.set_flight_depth(depth_before)
    for stage, want in expected.items():
        self_ms = _last(f"stage_ms.{stage}")
        assert self_ms == pytest.approx(_ms(want["self"]), abs=1e-6)
        parts = {"other": _last(f"stage_host_ms.{stage}.other")}
        assert parts["other"] == pytest.approx(_ms(want["other"]), abs=1e-6)
        for part in ("wait", "h2d"):
            total, n, longest = want.get(part, (0, 0, 0))
            parts[part] = _last(f"stage_{part}_ms.{stage}")
            assert parts[part] == pytest.approx(_ms(total), abs=1e-6), part
            assert _last(f"stage_max_ms.{stage}.{part}") == pytest.approx(_ms(longest), abs=1e-6)
        for section in set(want) & trace.SECTIONS:
            total, n, longest = want[section]
            parts[section] = _last(f"stage_host_ms.{stage}.{section}")
            assert parts[section] == pytest.approx(_ms(total), abs=1e-6), section
            assert _last(f"stage_host_n.{stage}.{section}") == n
            assert _last(f"stage_max_ms.{stage}.{section}") == pytest.approx(_ms(longest), abs=1e-6)
        assert sum(parts.values()) == pytest.approx(self_ms, abs=1e-6)  # 1e-6 ms: a nanosecond
        assert _last(f"stage_h2d_mb.{stage}") == want.get("h2d_mb", 0.0)
    if mode == "traced":
        cats = {e["cat"] for e in trace.events() if e.get("ph") == "X"}
        assert "host" in cats and "stage" in cats


def test_a_part_a_stage_instance_does_not_charge_reads_zero():
    """From its first sample on a part has one sample a stage instance, so
    its samples line up with ``stage_ms.<stage>`` from the end."""
    for charge in (True, False, True):
        with stage_timer("h_sparse"):
            if charge:
                with trace.host("finish"):
                    pass
    hists = trace.metrics.hist_windows()
    assert hists["stage_host_n.h_sparse.finish"]["samples"][-3:] == [1, 0, 1]
    assert hists["stage_host_ms.h_sparse.finish"]["samples"][-2] == 0.0
    assert hists["stage_max_ms.h_sparse.finish"]["count"] == hists["stage_ms.h_sparse"]["count"]


def test_host_section_names_are_a_closed_vocabulary():
    with pytest.raises(ValueError, match="no host section"):
        trace.host("glue")
    # no name of the family begins with a prefix the stage readers cut stages by
    with stage_timer("h_names"):
        with trace.host("write", "probe"):
            pass
    family = [
        n for n in trace.metrics.hist_windows()
        if n.endswith((".h_names.write", ".h_names.other", ".h_names.wait", ".h_names.h2d"))
    ]
    assert sorted(n.split(".")[0] for n in family) == [
        "stage_host_ms", "stage_host_ms", "stage_host_n",
        "stage_max_ms", "stage_max_ms", "stage_max_ms",
    ]


def test_section_is_an_annotation_on_the_profilers_clock(tmp_path, monkeypatch):
    import jax

    made = []

    class Recorder:
        def __init__(self, name, **kwargs):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    _trace_to(tmp_path)
    with stage_timer("solve"):
        with trace.host("search", "autoshard.search", label="bcd_fit"):
            pass
    assert made == ["ks/stage/solve", "ks/host/search"]
    (ev,) = [e for e in trace.events() if e.get("cat") == "host"]
    assert ev["name"] == "search" and ev["args"]["site"] == "autoshard.search"
    assert ev["args"]["parent"] == "solve"


def test_section_cost_with_the_ring_on_stays_small():
    """Enter + exit of a section beneath an open stage, tracing off and the
    flight ring on: what an untraced fit pays for each of its charges."""
    assert not trace.enabled() and trace.flight_depth() > 0
    costs = []
    with stage_timer("h_cost"):
        for _ in range(10_000):
            t0 = time.perf_counter()
            with trace.host("dispatch"):
                pass
            costs.append(time.perf_counter() - t0)
    costs.sort()
    assert costs[len(costs) // 2] < 20e-6, costs[len(costs) // 2]
    assert _last("stage_host_n.h_cost.dispatch") == 10_000


@pytest.mark.parametrize("flight_depth", [trace.DEFAULT_FLIGHT_DEPTH, 0])
def test_sections_retain_nothing_once_the_ring_is_warm(flight_depth):
    assert not trace.enabled()
    depth_before = trace.flight_depth()
    trace.set_flight_depth(flight_depth)
    filters = [tracemalloc.Filter(True, trace.__file__)]
    tracemalloc.start()
    try:
        with stage_timer("h_retain"):
            for _ in range(trace.DEFAULT_FLIGHT_DEPTH + 200):
                with trace.host("stack", "chunk"):
                    pass
            gc.collect()
            before = tracemalloc.take_snapshot().filter_traces(filters)
            for _ in range(5000):
                with trace.host("stack", "chunk"):
                    with trace.h2d("chunk", 64):
                        pass
            gc.collect()
            after = tracemalloc.take_snapshot().filter_traces(filters)
    finally:
        tracemalloc.stop()
        trace.set_flight_depth(depth_before)
    grew = sum(s.size for s in after.statistics("filename")) - sum(
        s.size for s in before.statistics("filename")
    )
    assert grew < 8192, f"5000 sections retained {grew} bytes"


@pytest.mark.parametrize("entry", ["searches", "search_seconds", "last_search_trained"])
def test_autoshard_registry_entries_are_gone(rng, entry):
    """The search's count and time are the open stage's ``search`` section
    (the names are put together here so that a grep for them finds nothing)."""
    name = "autoshard_" + entry
    x = jnp.asarray(rng.normal(size=(64, 24)).astype(np.float32))
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)])
    with stage_timer("h_search"):
        BlockLeastSquaresEstimator(8, 1, 1.0).fit(x, y, plan=True)
    snap = trace.metrics.snapshot()
    for group in ("counters", "gauges", "histograms"):
        assert name not in snap[group]
    assert _last("stage_host_n.h_search.search") == 1
    assert _last("stage_host_ms.h_search.search") > 0


# -- one clock with the device trace --------------------------------------------


def test_profiler_annotation_only_while_tracing(tmp_path, monkeypatch):
    import jax

    made = []

    class Recorder:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            made.append("exit")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    assert not trace.enabled() and trace.flight_depth() > 0
    with stage_timer("quiet"):
        with trace.span("inner", cat="wait"):
            pass
    assert made == []  # the ring records; nothing is handed to the profiler
    _trace_to(tmp_path)
    with stage_timer("loud"):
        with trace.span("inner", cat="wait"):
            pass
    names = [m[0] for m in made if m != "exit"]
    assert names == ["ks/stage/loud", "ks/wait/inner"]
    assert made.count("exit") == 2
    (_, outer), (_, inner) = [m for m in made if m != "exit"]
    assert inner["root"] == outer["id"] == outer["root"] != inner["id"]


def test_span_cost_with_the_ring_on_stays_small():
    """Enter + exit of a span with tracing off and the flight ring on: the
    cost an untraced fit pays for each of its ~150 spans."""
    assert not trace.enabled() and trace.flight_depth() > 0
    costs = []
    for _ in range(10_000):
        t0 = time.perf_counter()
        with trace.span("hot", cat="dispatch"):
            pass
        costs.append(time.perf_counter() - t0)
    costs.sort()
    assert costs[len(costs) // 2] < 20e-6, costs[len(costs) // 2]


# -- disabled-mode overhead ---------------------------------------------------


def test_disabled_mode_no_allocation_growth():
    """With tracing off, retained memory attributable to the trace module
    must be CONSTANT once the flight-recorder ring is warm: disabled
    spans/instants buffer nothing into the trace event list, and the ring
    is bounded by construction — old events fall off as new ones land, so
    5000 further spans retain no net growth."""
    assert not trace.enabled()
    assert trace.flight_depth() > 0  # the always-on ring is the default
    filters = [tracemalloc.Filter(True, trace.__file__)]
    tracemalloc.start()
    try:
        # Warm past the ring's capacity INSIDE the traced window so the
        # before-snapshot sees it full of TRACKED entries — from here on,
        # every append evicts one (this is the boundedness claim).
        warm = trace.flight_depth() + 200
        for _ in range(warm):
            with trace.span("warm", k=1):
                pass
            trace.instant("warm", n=1)
        gc.collect()
        before = tracemalloc.take_snapshot().filter_traces(filters)
        for _ in range(5000):
            with trace.span("hot"):
                pass
            trace.instant("hot", n=1)
        gc.collect()
        after = tracemalloc.take_snapshot().filter_traces(filters)
    finally:
        tracemalloc.stop()
    size_before = sum(s.size for s in before.statistics("filename"))
    size_after = sum(s.size for s in after.statistics("filename"))
    # No net retained growth attributable to the trace module (8 KB slack
    # for allocator bookkeeping / dict-churn noise in the full ring).
    assert size_after - size_before < 8192, (
        f"disabled tracing retained {size_after - size_before} bytes "
        "across 5000 spans (flight ring unbounded, or events buffered?)"
    )
    assert trace.events() == []
    assert len(trace.flight_events()) <= trace.flight_depth()


# -- flight recorder ----------------------------------------------------------


def test_flight_ring_records_with_tracing_disabled():
    assert not trace.enabled()
    with trace.span("flight_probe", cat="t", bytes=4):
        pass
    trace.instant("flight_point", n=2)
    # nothing buffered for export...
    assert trace.events() == []
    # ...but the ring has the last moments, span attrs included
    names = {e["name"]: e for e in trace.flight_events()}
    assert "flight_probe" in names and "flight_point" in names
    assert names["flight_probe"]["args"]["bytes"] == 4
    assert names["flight_probe"]["ph"] == "X"
    assert names["flight_point"]["ph"] == "i"


def test_flight_ring_is_bounded_and_resizable():
    prev = trace.flight_depth()
    try:
        trace.set_flight_depth(8)
        for i in range(50):
            trace.instant("ring_fill", i=i)
        evs = trace.flight_events()
        assert len(evs) <= 8
        # the ring keeps the MOST RECENT events
        assert evs[-1]["args"]["i"] == 49
        trace.set_flight_depth(0)
        trace.instant("ring_off")
        assert trace.flight_events() == []
    finally:
        trace.set_flight_depth(prev)


def test_flight_ring_rides_along_when_tracing_enabled(tmp_path):
    path = _trace_to(tmp_path)
    with trace.span("both_worlds"):
        pass
    trace.flush(path)
    assert any(e["name"] == "both_worlds" for e in trace.flight_events())
    assert any(
        e["name"] == "both_worlds"
        for e in trace_view.load_events(path)
    )


def test_thread_seen_in_flight_mode_gets_named_on_enable(tmp_path):
    """A thread first registered while tracing was OFF (flight-only mode)
    must still get its thread_name metadata when tracing is enabled later
    — lanes in the flushed trace stay labeled."""
    assert not trace.enabled()
    done = threading.Event()

    def worker():
        with trace.span("pre_enable_span"):
            pass
        done.set()

    t = threading.Thread(target=worker, name="flight-first-thread")
    t.start()
    t.join()
    assert done.is_set()
    path = _trace_to(tmp_path)
    with trace.span("post_enable"):
        pass
    trace.flush(path)
    metas = [
        ev for ev in trace_view.load_events(path)
        if ev.get("ph") == "M" and ev.get("name") == "thread_name"
    ]
    assert any(
        m["args"]["name"] == "flight-first-thread" for m in metas
    ), metas


def test_fault_counter_lands_in_flight_ring_untraced():
    # The chaos postmortem path: a counted fault must be in the ring even
    # when tracing was never enabled.
    assert not trace.enabled()
    counters.record("flight_fault_probe", "ring check")
    faults = [
        e for e in trace.flight_events()
        if e.get("name") == "fault"
        and e.get("args", {}).get("kind") == "flight_fault_probe"
    ]
    assert faults, "counted fault missing from the flight ring"


# -- exporters ----------------------------------------------------------------


def test_perfetto_chrome_trace_schema(tmp_path):
    path = _trace_to(tmp_path)
    with trace.span("stage_a", cat="stage", bytes=1024):
        with trace.span("child"):
            pass
    trace.instant("hbm_admission", admitted=True, charged_gb=0.5)
    counters.record("trace_test_fault", "schema probe")
    trace.flush(path)

    with open(path) as f:
        doc = json.load(f)  # must be valid JSON wholesale
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    assert doc["traceEvents"], "no events exported"
    phases = set()
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        phases.add(ev["ph"])
        if ev["ph"] in ("X", "i"):
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev.get("args", {}), dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name"
    # complete spans, instants, and thread metadata all present
    assert phases == {"X", "i", "M"}
    # the fault counter landed as a kind-tagged instant (chaos invariant)
    kinds = {
        ev["args"].get("kind")
        for ev in doc["traceEvents"]
        if ev["ph"] == "i" and ev["name"] == "fault"
    }
    assert "trace_test_fault" in kinds


def test_flush_is_crash_safe_atomic(tmp_path, monkeypatch):
    """The checkpoint atomic-write idiom on trace.flush: a failure mid-
    write must leave the previously-flushed trace intact and no temp
    litter — never a truncated Perfetto JSON."""
    path = _trace_to(tmp_path)
    with trace.span("survivor"):
        pass
    trace.flush(path)
    good = open(path).read()
    json.loads(good)  # valid JSON on disk

    def exploding_dump(*a, **kw):
        raise RuntimeError("injected crash mid-flush")

    monkeypatch.setattr(trace.json, "dump", exploding_dump)
    with trace.span("doomed_flush"):
        pass
    with pytest.raises(RuntimeError, match="mid-flush"):
        trace.flush(path)
    monkeypatch.undo()
    # the old trace survived byte-for-byte and no temp files remain
    assert open(path).read() == good
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp" in p]
    assert leftovers == [], leftovers


def test_jsonl_export(tmp_path):
    path = _trace_to(tmp_path, "t.jsonl")
    with trace.span("a"):
        pass
    trace.instant("b")
    trace.flush(path)
    with open(path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    names = {ev["name"] for ev in events}
    assert {"a", "b"} <= names


# -- Pipeline.profile ---------------------------------------------------------


def test_pipeline_profile_per_node_bytes(tmp_path):
    path = _trace_to(tmp_path)
    pipe = Pipeline(
        [
            FunctionTransformer(lambda b: b * 2.0, name="double"),
            FunctionTransformer(
                lambda b: jnp.concatenate([b, b], axis=1), name="widen"
            ),
            FunctionTransformer(lambda b: jnp.sum(b, axis=1), name="reduce"),
        ]
    )
    batch = jnp.ones((4, 8), jnp.float32)
    prof = pipe.profile(batch)
    trace.flush(path)

    assert [n.name for n in prof.nodes] == ["double", "widen", "reduce"]
    assert [n.output_bytes for n in prof.nodes] == [
        4 * 8 * 4,  # [4, 8] f32
        4 * 16 * 4,  # [4, 16] f32
        4 * 4,  # [4] f32
    ]
    assert [n.shape for n in prof.nodes] == [(4, 8), (4, 16), (4,)]
    assert all(n.dtype == "float32" for n in prof.nodes)
    assert all(n.seconds >= 0 for n in prof.nodes)
    assert prof.total_seconds >= sum(n.seconds for n in prof.nodes) * 0.5
    assert prof.input_bytes == 4 * 8 * 4
    np.testing.assert_allclose(np.asarray(prof.output), np.full(4, 32.0))
    json.dumps(prof.record())  # JSON-able for bench artifacts
    assert "double" in prof.summary()

    # the profile is also a span tree in the trace
    spans = _spans_by_name(trace_view.load_events(path))
    assert "pipeline.profile" in spans
    node_span = spans["node:widen"][0]
    assert node_span["args"]["parent"] == "pipeline.profile"
    assert node_span["args"]["output_bytes"] == 4 * 16 * 4


# -- solver ladder spans ------------------------------------------------------


def test_block_solve_emits_tier_spans_with_report(tmp_path, rng):
    path = _trace_to(tmp_path)
    x = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    y = jnp.asarray(
        2.0 * np.eye(4)[rng.integers(0, 4, 64)] - 1.0, jnp.float32
    )
    est = BlockLeastSquaresEstimator(16, num_iter=1, lam=1e-2)
    est.fit(x, y)
    trace.flush(path)
    events = trace_view.load_events(path)
    spans = _spans_by_name(events)
    solve = spans["solve:bcd_fit"][0]
    # FitReport linked into the solve span
    assert solve["args"]["report"]["chosen_tier"] == est.last_fit_report.chosen
    tier = spans[f"tier:{est.last_fit_report.chosen}"][0]
    assert tier["args"]["parent"] == "solve:bcd_fit"
    assert tier["args"]["solve"] == "bcd_fit"
    # every admission decision is an instant on the same timeline
    admissions = [
        ev
        for ev in events
        if ev.get("ph") == "i" and ev["name"] == "hbm_admission"
    ]
    assert admissions and all(
        "admitted" in ev["args"] and "reason" in ev["args"]
        for ev in admissions
    )


def test_forced_degradation_denials_visible_in_trace(tmp_path, rng, monkeypatch):
    # A pinched budget denies the fused tier: the denial must be visible
    # as a non-admitted hbm_admission instant AND the chosen degraded tier
    # as a span — the trace tells the whole ladder story.
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", "10K")
    path = _trace_to(tmp_path)
    x = rng.normal(size=(128, 64)).astype(np.float32)
    y = (2.0 * np.eye(4)[rng.integers(0, 4, 128)] - 1.0).astype(np.float32)
    est = BlockLeastSquaresEstimator(32, num_iter=1, lam=1e-2)
    est.fit(x, y)
    trace.flush(path)
    assert est.last_fit_report.denials  # the budget actually bit
    events = trace_view.load_events(path)
    denied = [
        ev
        for ev in events
        if ev.get("ph") == "i"
        and ev["name"] == "hbm_admission"
        and not ev["args"]["admitted"]
    ]
    assert denied
    spans = _spans_by_name(events)
    assert f"tier:{est.last_fit_report.chosen}" in spans


# -- ingest spans & overlap ---------------------------------------------------


def _sleepy_tar(tmp_path, n):
    """Tar whose members are placeholder bytes — decode is patched."""
    path = str(tmp_path / "sleepy.tar")
    with tarfile.open(path, "w") as tf:
        for i in range(n):
            data = b"x" * 64
            info = tarfile.TarInfo(f"img_{i:03d}.jpg")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    return path


def test_ingest_overlap_from_spans_matches_own_clock(tmp_path, monkeypatch):
    """The trace recomputation (``max(decode_busy, consume_busy) / wall``
    over span intervals) must land within 12% of the same quantity from
    this test's own clock readings IN THE SAME PASS: the union of the
    decoder's calls, the sum of the consumer's sleeps, the pass's wall.
    Decode/featurize costs are pinned by sleeps (decode-bound, the
    realistic streaming regime), and both sides read the same intervals,
    so a loaded host moves them together — three separate rate passes
    (decode only, consume only, both) differed by 13-39% between passes
    under a six-worker suite run.  A real span-accounting bug skews the
    two far past the band (dropping the consume spans alone moves it >
    30%)."""
    n_images, batch = 24, 4
    decode_s, feat_s = 0.05, 0.015  # per image / per batch
    img = np.zeros((40, 40, 3), np.float32)
    decode_calls = []  # (start, stop) in us; list.append is atomic

    def slow_decode(data):
        t0 = time.perf_counter()
        time.sleep(decode_s)
        decode_calls.append((t0 * 1e6, time.perf_counter() * 1e6))
        return img

    monkeypatch.setattr(image_loaders, "decode_image", slow_decode)
    tar = _sleepy_tar(tmp_path, n_images)
    kw = dict(num_threads=1, decode_ahead_slots=2, transfer=False)

    path = _trace_to(tmp_path)
    consume_s = 0.0
    t0 = time.perf_counter()
    with ingest.stream_batches(tar, batch, **kw) as st:
        for b in st:
            t1 = time.perf_counter()
            time.sleep(feat_s)  # the "featurize" of this chunk
            consume_s += time.perf_counter() - t1
    t_e2e = time.perf_counter() - t0
    assert st.join(10.0)
    trace.flush(path)
    trace.disable()

    decode_busy_s = trace_view._union_us(decode_calls) / 1e6
    own_eff = max(decode_busy_s, consume_s) / t_e2e

    overlap = trace_view.overlap_from_spans(trace_view.load_events(path))
    assert overlap is not None
    assert overlap["decode_spans"] == n_images
    assert overlap["consume_spans"] == -(-n_images // batch)
    trace_eff = overlap["overlap_efficiency"]
    assert trace_eff is not None
    assert abs(trace_eff - own_eff) <= 0.12 * own_eff, (
        f"trace-recomputed overlap {trace_eff} vs own clock "
        f"{own_eff:.3f} (decode {decode_busy_s:.3f}s, feat "
        f"{consume_s:.3f}s, e2e {t_e2e:.3f}s; spans {overlap})"
    )
    # both unions lie inside the wall (how high it reads is the host's
    # scheduling: 0.72 was seen under a six-worker suite run)
    assert 0.0 < trace_eff <= 1.0


def test_early_stopped_stream_leaves_no_suspended_span(tmp_path, monkeypatch):
    """A consumer that abandons a stream mid-iteration must not leave the
    generator-hosted ingest.consume span suspended on this thread's span
    stack (it would corrupt every later span's depth/parent and the
    flight recorder's view): Stream.close() closes the drain generator,
    the span exits as aborted, the stack returns to its prior depth."""
    img = np.zeros((40, 40, 3), np.float32)
    monkeypatch.setattr(image_loaders, "decode_image", lambda data: img)
    tar = _sleepy_tar(tmp_path, 8)
    depth_before = len(trace._stack())
    with ingest.stream_batches(tar, 2, num_threads=1, transfer=False) as st:
        for _b in st:
            break  # abandon the stream mid-iteration
    assert st.join(10.0)
    assert len(trace._stack()) == depth_before, [
        s.name for s in trace._stack()
    ]
    aborted = [
        e for e in trace.flight_events()
        if e.get("name") == "ingest.consume"
        and e.get("args", {}).get("aborted")
    ]
    assert aborted, "abandoned consume span did not record its abort"


def test_ingest_producer_span_records_stats(tmp_path, monkeypatch):
    img = np.zeros((40, 40, 3), np.float32)
    monkeypatch.setattr(image_loaders, "decode_image", lambda data: img)
    tar = _sleepy_tar(tmp_path, 6)
    path = _trace_to(tmp_path)
    with ingest.stream_batches(tar, 2, num_threads=1, transfer=False) as st:
        list(st)
    assert st.join(10.0)
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    prod = spans["ingest.produce"][0]
    assert prod["args"]["decoded"] == 6
    assert prod["args"]["batches"] == 3
    assert "ingest.ring_put" in spans and "ingest.ring_get" in spans


# -- metrics registry ---------------------------------------------------------


def test_metrics_registry_counters_gauges_histograms():
    m = trace.Metrics()
    assert m.inc("requests") == 1
    assert m.inc("requests", 4) == 5
    m.gauge("ring_depth", 3.0)
    for v in range(100):
        m.observe("latency_ms", float(v))
    snap = m.snapshot()
    assert snap["counters"] == {"requests": 5}
    assert snap["gauges"] == {"ring_depth": 3.0}
    h = snap["histograms"]["latency_ms"]
    assert h["count"] == 100 and h["min"] == 0.0 and h["max"] == 99.0
    assert 45.0 <= h["mean"] <= 55.0
    assert 45.0 <= h["p50"] <= 55.0 and h["p90"] >= h["p50"]
    json.dumps(snap)  # bench embeds this verbatim

    # snapshot(reset=True) clears atomically
    snap2 = m.snapshot(reset=True)
    assert snap2["counters"] == {"requests": 5}
    assert m.snapshot()["counters"] == {}


def test_metrics_snapshot_includes_fault_group():
    before = trace.metrics.snapshot()["faults"].get("trace_group_probe", 0)
    counters.record("trace_group_probe")
    snap = trace.metrics.snapshot()
    assert snap["faults"]["trace_group_probe"] == before + 1
    # the registry snapshot is what records embed — must be JSON-able
    json.dumps(snap)


def test_fault_counters_snapshot_reset_is_atomic():
    fc = FaultCounters()
    quiet = logging.getLogger("keystone_tpu.resilience")
    prev = quiet.level
    quiet.setLevel(logging.CRITICAL)
    try:
        stop = threading.Event()
        produced = {"n": 0}

        def hammer():
            while not stop.is_set():
                fc.record("hammered")
                produced["n"] += 1

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        collected = 0
        deadline = time.monotonic() + 0.5
        while time.monotonic() < deadline:
            collected += fc.snapshot(reset=True).get("hammered", 0)
        stop.set()
        for t in threads:
            t.join()
        collected += fc.snapshot(reset=True).get("hammered", 0)
    finally:
        quiet.setLevel(prev)
    # atomic snapshot+reset: every record lands in exactly one snapshot
    assert collected == produced["n"]
    assert fc.counts() == {}


# -- stage_timer & log level --------------------------------------------------


def test_stage_timer_same_log_line_and_span(tmp_path, caplog):
    path = _trace_to(tmp_path)
    with caplog.at_level(logging.INFO, logger="keystone_tpu"):
        with stage_timer("probe_stage"):
            pass
    assert any(
        "probe_stage took" in rec.getMessage() and rec.getMessage().endswith(" s")
        for rec in caplog.records
    )
    trace.flush(path)
    spans = _spans_by_name(trace_view.load_events(path))
    assert spans["probe_stage"][0]["cat"] == "stage"


def test_keystone_log_level_env(monkeypatch):
    root = logging.getLogger("keystone_tpu")
    prev = root.level
    try:
        monkeypatch.setenv("KEYSTONE_LOG_LEVEL", "DEBUG")
        configure_logging()
        assert root.level == logging.DEBUG
        monkeypatch.setenv("KEYSTONE_LOG_LEVEL", "warning")  # case-insensitive
        configure_logging()
        assert root.level == logging.WARNING
        monkeypatch.setenv("KEYSTONE_LOG_LEVEL", "15")  # numeric form
        configure_logging()
        assert root.level == 15
        # an explicit level always wins over the env
        configure_logging(logging.ERROR)
        assert root.level == logging.ERROR
        monkeypatch.setenv("KEYSTONE_LOG_LEVEL", "NOT_A_LEVEL")
        with pytest.raises(ValueError):
            configure_logging()
    finally:
        root.setLevel(prev)


# -- chaos --trace ------------------------------------------------------------


def test_chaos_schedule_trace_holds_never_silent_bar(tmp_path):
    import chaos

    # seed 4 -> nan_input: a typed FloatingPointError with a counted
    # nonfinite_model fault — both must be visible in the trace.
    path = str(tmp_path / "chaos_seed4.json")
    r = chaos.run_schedule(4, workload="mnist", trace_path=path)
    assert r.outcome == "typed_error"
    assert r.error_type == "FloatingPointError"
    assert chaos.verify_trace(path, r) == []
    # and the trace itself names the failure on a span
    events = trace_view.load_events(path)
    assert any(
        ev.get("args", {}).get("error") == "FloatingPointError"
        for ev in events
        if ev.get("ph") == "X"
    )


# -- trace_view CLI -----------------------------------------------------------


def test_trace_view_summarizes(tmp_path, capsys):
    path = _trace_to(tmp_path)
    with trace.span("stage_one", cat="stage"):
        time.sleep(0.01)
    with trace.span("stage_two", cat="stage"):
        pass
    counters.record("view_probe_fault")
    trace.flush(path)
    assert trace_view.main([path]) == 0
    out = capsys.readouterr().out
    assert "per-stage totals" in out
    assert "stage_one" in out and "stage_two" in out
    assert "view_probe_fault" in out
    assert "top 10 spans" in out


# -- the two fits' timelines ---------------------------------------------------


def _stage_ancestor(ev, by_id):
    while ev is not None:
        if ev["cat"] == "stage":
            return ev
        ev = by_id.get(ev["args"].get("parent_id"))
    return None


def test_tiny_cifar_fit_emits_its_stage_set_under_one_root(tmp_path, rng):
    from keystone_tpu.loaders.cifar import LabeledImageBatch
    from keystone_tpu.workloads import cifar_random_patch as cifar

    def batch(n):
        labels = rng.integers(0, 4, n).astype(np.int32)
        images = rng.uniform(0, 255, (n, 32, 32, 3)).astype(np.float32)
        images[:, :, :, 0] += 40.0 * labels[:, None, None]
        return LabeledImageBatch(images, labels)

    # whole chunks, so that what crosses to the device is the images alone
    train, test = batch(128), batch(64)
    conf = cifar.RandomCifarConfig(
        num_filters=8, patch_steps=2, lam=10.0, whitener_size=500,
        featurize_chunk=64, num_classes=4,
        pipeline_file=str(tmp_path / "chain"),
    )
    trace.metrics.reset()
    path = _trace_to(tmp_path)
    cifar.run(conf, train, test)
    trace.flush(path)
    trace.disable()
    events = [e for e in trace_view.load_events(path) if e.get("ph") == "X"]
    by_id = {e["args"]["id"]: e for e in events}
    (fit,) = [e for e in events if e["cat"] == "fit"]
    assert fit["name"] == "fit" and fit["args"]["rows"] == 128
    stages = [e for e in events if e["cat"] == "stage"]
    assert sorted(e["name"] for e in stages) == sorted([
        "learn_filters", "warm_featurizer", "featurize", "scale", "solve",
        "eval", "featurize_test", "checkpoint",
    ])  # each once
    assert {e["args"]["root"] for e in stages} == {fit["args"]["id"]}
    nested = {e["name"]: e["args"]["parent"] for e in stages}
    assert nested.pop("featurize_test") == "eval"
    assert set(nested.values()) == {"fit"}  # the rest are top-level
    # the top-level stages tile the root but for glue
    top = sum(e["dur"] for e in stages if e["args"]["parent"] == "fit")
    assert top <= fit["dur"] and top >= 0.8 * fit["dur"]
    waits = [e for e in events if e["cat"] in ("wait", "d2h")]
    assert len([e for e in waits if e["cat"] == "wait"]) >= 4
    for ev in waits + [e for e in events if e["cat"] == "h2d"]:
        assert _stage_ancestor(ev, by_id) is not None, ev
    hists = trace.metrics.hist_windows()
    moved = sum(
        round(h["total"] * 1e6)
        for name, h in hists.items() if name.startswith("stage_h2d_mb.")
    )
    h2d = [e for e in events if e["cat"] == "h2d"]
    # every copy is in a stage's sum; the chunks are the images, exactly
    assert sum(e["args"]["bytes"] for e in h2d) == moved
    chunks = [e["args"]["bytes"] for e in h2d if e["name"] == "chunk"]
    assert sum(chunks) == train.images.nbytes + test.images.nbytes
    assert len(chunks) == 3
    assert {e["name"] for e in h2d} == {"chunk", "filter_images", "labels"}
    chunked = [e for e in events if e["cat"] == "host" and e["args"].get("site") in ("chunk", "chunks")]
    assert sorted(e["name"] for e in chunked) == 2 * ["concat"] + 3 * ["dispatch"] + 3 * ["stack"]
    for name in nested.keys() | {"featurize_test"}:
        assert hists[f"stage_ms.{name}"]["count"] == 1
        assert hists[f"stage_wait_ms.{name}"]["count"] == 1


def test_tiny_timit_run_emits_its_three_stages(tmp_path, rng):
    from keystone_tpu.loaders.timit import TimitFeaturesData, TimitSplit
    from keystone_tpu.workloads import timit

    def split(n):
        return TimitSplit(
            rng.normal(size=(n, 12)).astype(np.float32),
            rng.integers(0, 3, n).astype(np.int32),
        )

    conf = timit.TimitConfig(
        num_cosines=2, num_cosine_features=32, num_epochs=1, gamma=0.2,
        lam=1e-2, num_classes=3, dimension=12,
    )
    path = _trace_to(tmp_path)
    timit.run(conf, TimitFeaturesData(split(96), split(32)))
    trace.flush(path)
    events = [e for e in trace_view.load_events(path) if e.get("ph") == "X"]
    (fit,) = [e for e in events if e["cat"] == "fit"]
    stages = [e for e in events if e["cat"] == "stage"]
    assert [e["name"] for e in stages] == ["featurize", "solve", "eval"]
    assert {e["args"]["root"] for e in stages} == {fit["args"]["id"]}
    # a round trip to the host a block: the evaluator's wait and read
    blocks = [e for e in events if e["cat"] == "host" and e["args"].get("site") == "block"]
    assert {e["name"] for e in blocks} == {"dispatch"}
    reads = [e for e in events if e["cat"] == "d2h"]
    assert len(blocks) == 2 == len(reads)
    assert {e["args"]["parent_id"] for e in reads} == {
        e["args"]["id"] for e in blocks
    }
