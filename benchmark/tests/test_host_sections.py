"""The per-layer metrics that read the program's named host sections
(PR 36), on the CPU (``python3 -m pytest benchmark/tests -q``).

* a rehearsal ``--trace 1`` run of each cell yields a finite number and the
  notes for every metric of the three readers that needs no device plane,
  the parts of ``solve`` sum to its self time, and ``idle_pct_untraced``
  reads a device busy time stood in for the trace's;
* on a registry without the family (the parent of the PR that brought it:
  the driver lays these files over its checkout) each reader returns None
  and says why; none raises;
* ``slow_fit_excess_pct`` names the stage and part of the fit into which a
  sleep is planted inside one ``wait``.
"""

import math
import time

import pytest

from benchmark import run as bench
from benchmark.lib import manifest

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
SEED = 2_147_483_801
NEW = ["solve_host_ms", "sample_host_ms", "featurize_host_ms", "idle_pct_untraced", "slow_fit_excess_pct"]
FAMILY = ("stage_host_ms.", "stage_host_n.", "stage_max_ms.")


def _read(name: str, ctx: dict):
    spec = manifest.load_json("metrics", f"{name}.json")
    return manifest.load_module("readers", spec["reader"]).read(spec, ctx)


def _ctx_of(result: dict, busy_share: float = 0.5) -> dict:
    """A reader's context for the registry a run left behind, with a device
    busy for ``busy_share`` of a traced fit stood in for the trace."""
    observed = result["observed"]
    traced = 2
    walls = [observed["fit_wall_median_s"]] * (observed["fits_completed"] - traced)
    return {
        "cell": "rehearsal", "fits_completed": observed["fits_completed"],
        "untraced_walls": walls, "traced_fits": traced,
        "trace": {"busy_ns": busy_share * 1e9 * traced * observed["fit_wall_median_s"]},
    }


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_rehearsal_reads_the_host_sections(cell_name):
    from keystone_tpu.core import trace

    trace.metrics.reset()  # a run is a process of its own; the tests share one
    result = bench.run_cell(cell_name, SEED, 2.0, True, rehearsal=True, chip_check=False)
    assert result["failed"] == 0, result["observed"]["failures"]
    listed = manifest.metrics_for(cell_name, manifest.benchmark_json(), "per_layer")
    observed = result["observed"]
    for name in NEW:
        if name == "idle_pct_untraced":  # no device plane off the chip: nothing to read
            assert name in listed and name not in result["metrics"]
        elif name in listed:
            value = result["metrics"][name]["value"]
            assert math.isfinite(value) and value >= 0, (name, value)
        else:
            assert name not in result["metrics"]
    # the parts of the solve sum to its self time: the host's work and what was taken out
    parts = observed["solve_host_by_section_ms"]
    assert {"search", "place", "dispatch"} <= set(parts), parts
    ctx = _ctx_of(result)
    win = manifest.load_module("readers", "host_sections").windows(ctx)
    for side in ("untraced", "traced"):
        for stage, by_part in win["parts"][side].items():
            sums = [sum(ms) for ms in zip(*(got["ms"] for got in by_part.values()))]
            assert sums == pytest.approx(win[side]["stage_ms"][stage], abs=1e-6), (side, stage)
    host = result["metrics"]["solve_host_ms"]["value"]
    assert 0 < host <= result["metrics"]["solve_wall_ms"]["value"]
    assert observed["solve_host_n"]["search"] >= 1, observed["solve_host_n"]
    assert "dispatch" in observed["featurize_host_by_section_ms"]["featurize"]
    if "sample_host_ms" in listed:
        assert {"stack", "draw", "dispatch"} <= set(observed["sample_host_by_section_ms"])
        assert observed["sample_host_n"]["stack"] >= 2  # one a chunk
    slow = observed["slow_fit"]
    assert slow["stage"] in observed["stage_self_ms"] and slow["wall_ms"] >= slow["median_wall_ms"]
    # the device share, with a busy time stood in for the trace's
    assert _read("idle_pct_untraced", ctx) == pytest.approx(50.0)
    assert ctx["notes"]["idle_untraced_ms"] == pytest.approx(500.0 * observed["fit_wall_median_s"])
    for side in ("untraced", "traced"):
        assert "dispatch" in ctx["notes"][f"host_work_{side}_ms"]["solve"]


def test_readers_say_why_and_do_not_raise_without_the_family(monkeypatch):
    """The parent records the four older sums a stage and nothing else."""
    from keystone_tpu.core import trace

    trace.metrics.reset()
    result = bench.run_cell(CELLS[0], SEED, 1.0, True, rehearsal=True, chip_check=False)
    assert all(_read(name, _ctx_of(result)) is not None for name in NEW if name != "sample_host_ms")
    parent = trace.Metrics()
    for name, h in trace.metrics.hist_windows().items():
        if not name.startswith(FAMILY):
            for value in h["samples"]:
                parent.observe(name, value)
    monkeypatch.setattr(trace, "metrics", parent)
    for name in NEW:
        ctx = _ctx_of(result)
        assert _read(name, ctx) is None, name
        assert "no stage_host_ms" in ctx["notes"]["host_sections"]
    # and with no stage at all, as the readers before them
    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    for name in NEW:
        ctx = _ctx_of(result)
        assert _read(name, ctx) is None, name
        assert "no stage_ms" in ctx["notes"]["stage_samples"]


def test_slow_fit_names_the_planted_wait(monkeypatch):
    import jax

    from keystone_tpu.core import trace

    planted = {"call": 0, "at": 6, "seconds": 0.4}  # the warm-up is call 0: the window's fit 5
    real_wait, real_block = trace.wait, jax.block_until_ready

    def wait(value, name="device"):
        if name == "featurize" and planted["call"] == planted["at"]:
            def slow(v):
                time.sleep(planted["seconds"])
                return real_block(v)

            monkeypatch.setattr(jax, "block_until_ready", slow)
            try:
                return real_wait(value, name)
            finally:
                monkeypatch.setattr(jax, "block_until_ready", real_block)
        return real_wait(value, name)

    def wrap_fit(fit):
        def counted(*args):
            try:
                return fit(*args)
            finally:
                planted["call"] += 1

        return counted

    monkeypatch.setattr(trace, "wait", wait)
    trace.metrics.reset()
    result = bench.run_cell(
        "cifar_rp_fit", SEED, 3.0, True, rehearsal=True, chip_check=False, wrap_fit=wrap_fit
    )
    assert result["failed"] == 0 and result["observed"]["fits_completed"] > 7
    slow = result["observed"]["slow_fit"]
    assert (slow["index"], slow["stage"], slow["part"]) == (5, "featurize", "wait"), slow
    # the device works on while the host sleeps: the sleep takes the wait's place
    assert slow["excess_ms"] >= 0.75e3 * planted["seconds"]
    assert slow["part_max_ms"] >= 1e3 * planted["seconds"] > 4 * slow["part_max_median_ms"]
    walls_ms = (slow["wall_ms"], slow["median_wall_ms"])
    assert result["metrics"]["slow_fit_excess_pct"]["value"] == pytest.approx(
        100.0 * (walls_ms[0] - walls_ms[1]) / walls_ms[1]
    )
    assert walls_ms[0] - walls_ms[1] >= 0.75e3 * planted["seconds"]
