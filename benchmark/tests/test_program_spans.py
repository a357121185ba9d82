"""The per-layer metrics that read the program's own timeline (PR 26), on
the CPU (``python3 -m pytest benchmark/tests -q``).

* a rehearsal ``--trace 1`` run of each cell yields a number for every
  ``program_span`` / ``program_counter`` metric the cell lists: they read the
  program's registry and need no device plane;
* nothing that runs after the window (``produced``, the reference's fit)
  enters a stage, which is what lets the readers take the last samples of
  a stage as the window's fits;
* ``idle_host_bound_pct``'s reader on a small recorded plain trace with the
  program's ``ks/`` annotations added by hand;
* on a program that records no stage (the parent of the PR that brought
  them) every reader returns None and says why; none raises.
"""

import json
import os

import pytest

from benchmark import run as bench
from benchmark.lib import manifest, xplane

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
SEED = 2_147_483_777
NEW = ["featurize_wall_ms", "solve_wall_ms", "eval_wall_ms", "host_wait_pct", "h2d_mb"]


def counts_names(counts):
    return [n[len("stage_ms."):] for n in counts]


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_rehearsal_reads_every_program_metric(cell_name):
    from keystone_tpu.core import trace

    trace.metrics.reset()  # a run is a process of its own; the tests share one
    result = bench.run_cell(cell_name, SEED, 2.0, True, rehearsal=True, chip_check=False)
    assert result["failed"] == 0, result["observed"]["failures"]
    listed = manifest.metrics_for(cell_name, manifest.benchmark_json(), "per_layer")
    for name in NEW:
        if name in listed:
            value = result["metrics"][name]["value"]
            assert value > 0, (name, value)
        else:
            assert name not in result["metrics"]
    observed = result["observed"]
    # set-up's one fit and the window's, and nothing after the window: the
    # comparison with the reference has run by the time the readers do
    counts = {
        name: h["count"]
        for name, h in trace.metrics.hist_windows().items()
        if name.startswith("stage_ms.")
    }
    assert set(counts.values()) == {result["attempted"] + 1}, counts
    assert set(observed["stage_self_ms"]) == set(counts_names(counts))
    assert {"featurize", "solve", "eval"} <= set(observed["stage_self_ms"])
    assert 0 <= result["metrics"]["host_wait_pct"]["value"] <= 100
    if "stage_samples" not in observed:  # the window held untraced fits too
        assert observed["untiled_pct"] < 50
        assert set(observed["stage_traced_over_untraced"]) == set(counts_names(counts))
    # off the chip there is no device plane, so the share of idle has nothing
    # to read and leaves the line without raising
    assert "idle_host_bound_pct" not in result["metrics"]


def test_idle_host_bound_on_the_recorded_trace():
    reader = manifest.load_module("readers", "idle_host_bound")
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", "small_trace_ks.json")) as f:
        plain = json.load(f)
    spans = reader.program_spans(plain)
    assert len(spans) == 10 and all(name.count("#") == 0 for name, _, _ in spans)
    assert ("ks/wait/solve", 1_350_000, 1_470_000) in spans
    gaps = xplane.reduce_trace(plain)["devices"][0]["gaps"]
    got = reader.split_idle(gaps, spans, {"wait", "d2h"})
    assert got["idle_s"] == pytest.approx(380_000e-9)
    # 80,000 ns in ks/h2d/chunk and 100,000 in ks/eval/block are the host's;
    # 200,000 in ks/wait/solve are not
    assert got["host_s"] == pytest.approx(180_000e-9)
    assert dict(got["by_span"]) == pytest.approx(
        {"ks/wait/solve": 200_000e-9, "ks/eval/block": 100_000e-9, "ks/h2d/chunk": 80_000e-9}
    )
    assert dict(got["by_stage"]) == pytest.approx(
        {"ks/stage/solve": 200_000e-9, "ks/stage/eval": 100_000e-9, "ks/stage/featurize": 80_000e-9}
    )
    # no annotation at all: every gap lies in none, all idle is the host's
    bare = reader.split_idle(gaps, [], {"wait", "d2h"})
    assert bare["host_s"] == pytest.approx(bare["idle_s"])


def test_readers_say_why_and_do_not_raise_without_stages(monkeypatch):
    """The driver lays these files over the parent's checkout, whose program
    records no stage and makes no annotation."""
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    ctx = {
        "cell": "no_such_cell", "fits_completed": 3, "untraced_walls": [0.1],
        "traced_fits": 2,
        "trace": {"window": (0, 10), "layers_ns": {}, "devices": [{"gaps": [(1, 2)]}]},
    }
    for name in NEW + ["idle_host_bound_pct"]:
        spec = manifest.load_json("metrics", f"{name}.json")
        reader = manifest.load_module("readers", spec["reader"])
        assert reader.read(spec, ctx) is None, name
    assert "no stage_ms" in ctx["notes"]["stage_samples"]
    assert "no xplane" in ctx["notes"]["idle_host_bound"]
