"""``timit_rf_fit_full``'s own checks under a whole run of the harness at the
rehearsal size on the CPU (``benchmark/tests/test_correct.py`` runs the sound
cell, the control and the three faults every whole-fit cell can have): fifty
blocks of 128 columns, made and never held because the rehearsal's ``env``
states a budget the matrix does not fit."""

import pytest

from benchmark import run as bench
from benchmark.lib import manifest, timit_full_faults

CELL = "timit_rf_fit_full"
SEED = 2_147_483_659


def test_sound_run_makes_every_block_in_every_pass():
    result = bench.run_cell(CELL, SEED, 0.5, True, rehearsal=True, chip_check=False)
    assert result["correct"], result["compared"]
    # a fit on another tier than fused[made], or with a denial, counts as failed
    assert result["failed"] == 0 and result["attempted"] >= 1, result["observed"]["failures"]
    # fifty blocks x (the moments pass, a gram, the rehearsal's one epoch)
    assert result["metrics"]["block_passes"]["value"] == 150.0
    assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_a_fit_that_holds_its_matrix_counts_as_failed(monkeypatch):
    """With room for the matrix the solver holds it (tier ``fused``), which
    is not what this configuration states: the harness fails the fit."""
    real = manifest.resized

    def roomy(block, rehearsal):
        out = real(block, rehearsal)
        if "env" in out:
            out["env"] = dict(out["env"], KEYSTONE_HBM_BUDGET="1G")  # room for the 13 MB matrix
        return out

    monkeypatch.setattr(manifest, "resized", roomy)
    result = bench.run_cell(CELL, SEED, 0.5, False, rehearsal=True, chip_check=False)
    assert result["failed"] == result["attempted"] >= 1
    assert "solver tier 'fused', not 'fused[made]'" in result["observed"]["failures"][0]
    assert not result["correct"]


def test_test_blocks_out_of_step_are_not_correct():
    result = bench.run_cell(
        CELL, SEED, 0.5, False, rehearsal=True, chip_check=False,
        wrap_fit=timit_full_faults.scored_one_block_late,
    )
    compared = result["compared"]
    assert compared and result["failed"] == 0, result["observed"]["failures"]
    assert not result["correct"], compared
    number = compared["scores_rms_gap"]
    assert not number["value"] <= number["limit"], number


def test_a_program_without_a_block_source_is_refused_at_once(monkeypatch):
    """The driver tries the new cell on the parent commit under these
    files: its solver has no ``BlockSource``, and the pipeline says so before
    any block is made (the parent would hold 29 blocks, then run out)."""
    from keystone_tpu.solvers import block

    monkeypatch.delattr(block, "BlockSource")
    with pytest.raises(SystemExit, match="takes no block source"):
        bench.run_cell(CELL, SEED, 0.5, False, rehearsal=True, chip_check=False)


@pytest.mark.parametrize("name", ["block_passes", "made_bcd_roofline"])
def test_new_readers_find_nothing_on_another_program(name, monkeypatch):
    """The driver lays these files over the parent's checkout and runs the
    old cells traced: no counter ``bcd.block_rows_made``, no kernel
    ``made_bcd`` among the cell's counts."""
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    spec = manifest.load_json("metrics", f"{name}.json")
    reader = manifest.load_module("readers", spec["reader"])
    ctx = {
        "trace": {"layers_ns": {"solvers": 1e9}}, "traced_fits": 2, "rows": {"train": 1},
        "kernels": {"bcd": {"flops": 1.0, "bytes": 1.0, "layer": "solvers"}},
        "peaks": {"flops": 1.0, "bytes": 1.0},
    }
    assert reader.read(spec, ctx) is None
