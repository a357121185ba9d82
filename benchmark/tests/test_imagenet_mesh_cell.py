"""The ImageNet cell across chips (``imagenet_fv_fit_mesh4``), on the CPU
(``python3 -m pytest benchmark/tests -q``).

A pytest process here has one CPU device, so ``test_correct.py`` takes the
cell through its rehearsal's 1 x 1 mesh.  This file runs the same sizes
**four ways** in a subprocess of four forced CPU devices, the rehearsal's
``mesh`` set back to ``"4"``:

* a whole traced run of the harness through ``pipelines/imagenet_fv_mesh.py``
  ends ``correct`` inside the configuration's limits, every fit on tier
  ``fused[mesh 4x1]``, the classes split four ways after one ``all_to_all``,
  and ``class_regroup_mb`` read;
* the fault only this cell can have, each chip's solutions handed to the
  next chip's classes (``lib/imagenet_mesh_faults.py``), ends with
  ``correct`` false by the solve's numbers.

The three metrics read from the device trace need device planes, which a
CPU trace has not: ``tests/test_imagenet_mesh_cell.py`` reads them on a
trace of four planes.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest

CELL = "imagenet_fv_fit_mesh4"
SEED = 2_147_483_693

CHILD = r"""
import json, sys
from benchmark import run as bench
from benchmark.lib import imagenet_mesh_faults, manifest
from keystone_tpu.core import trace

real = manifest.resized

def four_ways(block, rehearsal):
    out = real(block, rehearsal)
    if "mesh" in out:
        out.update(mesh="4", expected_tier="fused[mesh 4x1]")
    return out

manifest.resized = four_ways
plans = []

def watch(fit):
    def watched(conf, data, seed, stem):
        out = fit(conf, data, seed, stem)
        plans.append([e["args"] for e in trace.flight_events() if e["name"] == "bwls_plan"][-1])
        return out
    return watched

cell, seed = sys.argv[1], int(sys.argv[2])
sound = bench.run_cell(cell, seed, 0.5, True, rehearsal=True, chip_check=False, wrap_fit=watch)
with imagenet_mesh_faults.columns_rotated():
    broken = bench.run_cell(cell, seed, 0.5, False, rehearsal=True, chip_check=False)
import jax
keep = ("correct", "attempted", "failed", "compared", "metrics")
print("RESULT " + json.dumps(bench.finite({
    "devices": jax.device_count(),
    "plan": plans[-1],
    "sound": {k: sound[k] for k in keep} | {
        "failures": sound["observed"]["failures"],
        "compiles_in_window": sound["observed"]["compile_requests_in_window"],
    },
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
    assert sound["compiles_in_window"] == 0
    assert sound["metrics"]["class_regroup_mb"]["value"] > 0


def test_classes_split_four_ways(four_ways):
    conf = manifest.resized(manifest.cell(CELL)["config"], True)
    plan = four_ways["plan"]
    assert plan["classes_by_chip"] == [conf["num_classes"] // 4] * 4
    assert sum(plan["rows_by_chip"]) == plan["rows"]
    assert plan["regroup"] == "all_to_all" and plan["regroup_bytes"] > 0


def test_rotated_columns_are_not_correct(four_ways):
    broken = four_ways["broken"]
    assert broken["compared"], "nothing was compared"
    assert broken["failed"] == 0, broken["failures"]  # the fits ran; their answer is wrong
    assert not broken["correct"], broken["compared"]
    model = broken["compared"]["model_gap"]
    assert not model["value"] <= model["limit"], model
