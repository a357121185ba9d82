"""What decides ``correct`` has been shown to fail: at a size a test run can
hold, on the CPU (``python3 -m pytest benchmark/tests -q``).

* the control (the reference in the precision below the configuration's,
  put in the program's place) comes out as not correct, and the reference
  put there in its own precision as correct;
* a whole run of the harness, the look for a chip skipped and the timed path
  broken underneath, ends with ``correct`` false, once for each fault a
  whole-fit cell can have: the solve's state handed back unchanged, half of
  the training rows left out, an answer altered where it is produced.  (No
  cell of this benchmark exchanges anything between chips.)

The limits are the configuration files' own, so the rehearsal sizes have to
sit inside them too.
"""

import pytest

from benchmark import run as bench
from benchmark.lib import faults, manifest

CELLS = [w["name"] for w in manifest.benchmark_json()["workloads"]]
SEED = 2_147_483_659  # more than 32 signed bits hold


def _judge(conf, values):
    limits = conf["limits"]
    assert limits, "the configuration states no limit"
    return all(values[name] <= limit for name, limit in limits.items())


def _parts(cell_name):
    cell = manifest.cell(cell_name)
    conf = manifest.resized(cell["config"], True)
    traffic = manifest.resized(cell["traffic"], True)
    pipeline = manifest.load_module("pipelines", conf["pipeline"])
    datagen = manifest.load_module("datagen", pipeline.DATAGEN)
    reference = manifest.load_module("reference", pipeline.REFERENCE)
    data = pipeline.place_data(datagen.generate(conf["data"], traffic["rows"], SEED))
    return conf, pipeline, reference, data


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    conf, pipeline, reference, data = _parts(cell_name)
    seed = pipeline.program_seed(SEED)
    ref = reference.fit(conf, data, seed, "highest")
    same = reference.compare(conf, data, SEED, ref, ref)
    assert _judge(conf, same), same
    control = reference.fit(conf, data, seed, conf["compare"]["control_precision"])
    values = reference.compare(conf, data, SEED, control, ref)
    assert not _judge(conf, values), values


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    result = bench.run_cell(cell_name, SEED, 0.5, False, rehearsal=True, chip_check=False)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged", "answer_altered"])
def test_broken_path_is_not_correct(cell_name, fault):
    kwargs = {}
    if fault == "half_batch":
        kwargs["wrap_fit"] = faults.half_batch
    elif fault == "answer_altered":
        kwargs["wrap_produced"] = faults.answer_altered
    if fault == "state_unchanged":
        with faults.state_unchanged():
            result = bench.run_cell(cell_name, SEED, 0.5, False, rehearsal=True, chip_check=False)
    else:
        result = bench.run_cell(
            cell_name, SEED, 0.5, False, rehearsal=True, chip_check=False, **kwargs
        )
    assert result["compared"], "nothing was compared"
    assert not result["correct"], result["compared"]
