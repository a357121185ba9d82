"""``mnist_fft_fit``'s own checks under a whole run of the harness at the
rehearsal size on the CPU (``benchmark/tests/test_correct.py`` runs the sound
cell, the control and the three faults every whole-fit cell can have): fifty
blocks of four FFTs of 8 x 8 images, made and never held because the
rehearsal's ``env`` states a budget the matrix does not fit, and the faults
only a fit that makes these features can have."""

import pytest

from benchmark import run as bench
from benchmark.lib import manifest, mnist_fft_faults

CELL = "mnist_fft_fit"
SEED = 2_147_483_693


@pytest.fixture(autouse=True)
def _registry_of_its_own():
    """A run is a process of its own; the tests of the harness share one."""
    from keystone_tpu.core import trace

    trace.metrics.reset()


def test_sound_run_makes_every_block_where_it_is_consumed():
    result = bench.run_cell(CELL, SEED, 0.5, True, rehearsal=True, chip_check=False)
    assert result["correct"], result["compared"]
    # a fit on another tier than fused[made], or with a denial, counts as failed
    assert result["failed"] == 0 and result["attempted"] >= 1, result["observed"]["failures"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # fifty blocks x (the solver's moments pass, the gram pass, the training
    # split's apply) on 512 rows, fifty of the test split's 128, and the
    # blocks the sweep makes again (those not kept)
    passes = result["metrics"]["fft_block_passes"]["value"]
    assert 150 + 50 * 128 / 512 < passes <= 200 + 50 * 128 / 512, passes


@pytest.mark.parametrize("fault", sorted(mnist_fft_faults.FAULTS))
def test_a_broken_featurizer_is_not_correct(fault):
    """Each fault ends ``correct: false``: by the features, the means and
    the scores where the fit completes, by the fits failing where the broken
    blocks leave their systems singular.  Each fault makes a block's columns
    linearly dependent, so which of the two a seed meets at the rehearsal's
    lambda depends on rounding; where the warm-up fit meets it, the run ends
    typed and prints no result at all."""
    with mnist_fft_faults.FAULTS[fault]():
        try:
            result = bench.run_cell(CELL, SEED, 0.5, False, rehearsal=True, chip_check=False)
        except FloatingPointError as e:
            assert "non-finite" in str(e)
            return
    assert not result["correct"], result["compared"]
    if result["compared"]:
        assert any(not n["value"] <= n["limit"] for n in result["compared"].values())
    else:
        assert result["failed"] == result["attempted"] >= 1


def test_a_fit_that_holds_its_matrix_counts_as_failed(monkeypatch):
    """With room for the matrix the solver holds it (tier ``fused``), which
    is not what this configuration states: the harness fails the fit."""
    real = manifest.resized

    def roomy(block, rehearsal):
        out = real(block, rehearsal)
        if "env" in out:
            out["env"] = dict(out["env"], KEYSTONE_HBM_BUDGET="1G")
        return out

    monkeypatch.setattr(manifest, "resized", roomy)
    result = bench.run_cell(CELL, SEED, 0.5, False, rehearsal=True, chip_check=False)
    assert result["failed"] == result["attempted"] >= 1
    assert "solver tier 'fused', not 'fused[made]'" in result["observed"]["failures"][0]
    assert not result["correct"]


def test_a_program_whose_run_holds_the_blocks_is_refused_at_once(monkeypatch):
    """A program whose MnistRandomFFT run hands the solver fifty blocks it
    has made first cannot run this cell, and the pipeline says so before any
    block is made."""
    from keystone_tpu.workloads import mnist_random_fft

    monkeypatch.delattr(mnist_random_fft, "draw_block_featurizers")
    with pytest.raises(SystemExit, match="hands the solver its blocks as arrays"):
        bench.run_cell(CELL, SEED, 0.5, False, rehearsal=True, chip_check=False)


@pytest.mark.parametrize("name", ["fft_dev_ms", "fft_block_passes", "made_fft_roofline"])
def test_new_readers_find_nothing_on_another_program(name, monkeypatch):
    """Laid over an older program's checkout, these files leave the other
    cells' traced runs alone: no counter ``bcd.block_rows_applied``, no FFT
    operation, no kernel ``made_fft_bcd`` among the cell's counts."""
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    spec = manifest.load_json("metrics", f"{name}.json")
    reader = manifest.load_module("readers", spec["reader"])
    ctx = {
        "trace": {
            "layers_ns": {"featurizers": 1e9, "solvers": 1e9},
            "devices": [{"ops": {"%fusion.1 = f32[32768,4096]{1,0} fusion(f32[32768,440])": 1e9}}],
        },
        "traced_fits": 2, "rows": {"train": 1},
        "kernels": {"made_bcd": {"flops": 1.0, "bytes": 1.0, "layer": "solvers"}},
        "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
    }
    assert reader.read(spec, ctx) is None
