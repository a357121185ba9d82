"""The cell across chips (``cifar_rp_fit_mesh4``), on the CPU (``python3 -m
pytest benchmark/tests -q``).

A pytest process here has one CPU device (``conftest.py`` forces no count,
and an earlier test has started the backend), so ``test_correct.py`` takes
the cell through its rehearsal's 1 x 1 mesh.  This file runs the same sizes
**four ways** in a subprocess of four forced CPU devices, the rehearsal's
``mesh`` set back to ``"4"``:

* a whole run of the harness through ``pipelines/cifar_rp_mesh.py`` ends
  ``correct`` inside the configuration's limits, every fit on tier
  ``fused[mesh 4x1]``, the design matrix's rows split four ways;
* the fault only a mesh cell can have, one chip's partial gram left out of
  the sum (``lib/mesh_faults.py``), ends with ``correct`` false;

and checks the two readers that read several device planes on a small
recorded plain trace with four of them (``fixtures/mesh_trace.json``:
device k busy 400,000 + 10,000 k ns of a 1,000,000 ns window, 100,000 ns of
it in collectives).
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest, xplane

CELL = "cifar_rp_fit_mesh4"
SEED = 2_147_483_693

CHILD = r"""
import json, sys
from benchmark import run as bench
from benchmark.lib import manifest, mesh_faults

real = manifest.resized

def four_ways(block, rehearsal):
    out = real(block, rehearsal)
    if "mesh" in out:
        out.update(mesh="4", expected_tier="fused[mesh 4x1]")
    return out

manifest.resized = four_ways
seen = []

def watch(fit):
    def watched(conf, data, seed, stem):
        out = fit(conf, data, seed, stem)
        seen.append(out["results"]["feature_rows_by_device"])
        return out
    return watched

cell, seed = sys.argv[1], int(sys.argv[2])
sound = bench.run_cell(cell, seed, 0.5, False, rehearsal=True, chip_check=False, wrap_fit=watch)
with mesh_faults.partial_gram_dropped():
    broken = bench.run_cell(cell, seed, 0.5, False, rehearsal=True, chip_check=False)
import jax
keep = ("correct", "attempted", "failed", "compared")
print("RESULT " + json.dumps(bench.finite({
    "devices": jax.device_count(),
    "rows_by_device": seen[-1],
    "sound": {k: sound[k] for k in keep} | {"failures": sound["observed"]["failures"]},
    "broken": {k: broken[k] for k in keep} | {"failures": broken["observed"]["failures"]},
})))
"""


@pytest.fixture(scope="module")
def four_ways():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a child never reaches for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = manifest.CHECKOUT + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run(
        [sys.executable, "-c", CHILD, CELL, str(SEED)],
        env=env, cwd=manifest.CHECKOUT, capture_output=True, text=True, timeout=900,
    )
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")]
    assert done.returncode == 0 and lines, done.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_four_way_run_is_correct(four_ways):
    assert four_ways["devices"] == 4
    sound = four_ways["sound"]
    assert sound["correct"], sound["compared"]
    # a fit on another tier than fused[mesh 4x1] counts as failed
    assert sound["failed"] == 0 and sound["attempted"] >= 1, sound["failures"]


def test_rows_are_split_four_ways(four_ways):
    conf = manifest.resized(manifest.cell(CELL)["traffic"], True)
    rows = conf["rows"]["train"]
    spans = sorted(four_ways["rows_by_device"].values())
    assert spans == [[k * rows // 4, (k + 1) * rows // 4] for k in range(4)]


def test_dropped_partial_gram_is_not_correct(four_ways):
    broken = four_ways["broken"]
    assert broken["compared"], "nothing was compared"
    assert broken["failed"] == 0, broken["failures"]  # the fits ran; their answer is wrong
    assert not broken["correct"], broken["compared"]


def _fixture_ctx():
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", "mesh_trace.json")) as f:
        plain = json.load(f)
    return {"trace": xplane.reduce_trace(plain), "traced_fits": 2}


def test_collective_ms_on_the_recorded_trace():
    spec = manifest.load_json("metrics", "collective_ms.json")
    reader = manifest.load_module("readers", spec["reader"])
    ctx = _fixture_ctx()
    # all-reduce-start 5,000 + -done 45,000 + all-reduce 30,000 + all-gather
    # 20,000 ns on the first plane, over two traced fits
    assert reader.read(spec, ctx) == pytest.approx(0.05)
    assert ctx["notes"]["collective_ms_by_kind"] == pytest.approx(
        {"all-reduce": 0.04, "all-gather": 0.01}
    )
    assert not reader.collective_ns({"fusion.1": 1.0, "all-reduce-scatter-fusion": 2.0, "cholesky.4": 3.0})
    assert set(reader.collective_ns({"%all-to-all.7": 1.0, "collective-permute-done.2.1": 2.0, "reduce-scatter": 3.0})) == {
        "%all-to-all.7", "collective-permute-done.2.1", "reduce-scatter"
    }


def test_device_skew_on_the_recorded_trace():
    spec = manifest.load_json("metrics", "device_skew_pct.json")
    reader = manifest.load_module("readers", spec["reader"])
    ctx = _fixture_ctx()
    # busy 400,000 / 410,000 / 420,000 / 430,000 ns: (430 - 400) / 415
    assert reader.read(spec, ctx) == pytest.approx(100 * 30_000 / 415_000)
    assert ctx["notes"]["device_busy_s"] == pytest.approx([4.0e-4, 4.1e-4, 4.2e-4, 4.3e-4])


@pytest.mark.parametrize("name", ["collective_ms", "device_skew_pct", "psum_mb"])
def test_mesh_readers_find_nothing_on_one_chip(name, monkeypatch):
    """The driver lays these files over the parent's checkout and runs the
    one-chip cells traced: one device plane, no collective, no counter."""
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", "small_trace.json")) as f:
        plain = json.load(f)
    spec = manifest.load_json("metrics", f"{name}.json")
    reader = manifest.load_module("readers", spec["reader"])
    ctx = {"trace": xplane.reduce_trace(plain), "traced_fits": 2, "rows": {"train": 1}}
    assert reader.read(spec, ctx) is None
    assert reader.read(spec, {"trace": None, "traced_fits": 0, "rows": {"train": 1}}) is None
