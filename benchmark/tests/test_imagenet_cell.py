"""``imagenet_fv_fit``'s own faults, planted under a whole run of the harness
at the rehearsal size on the CPU: each has to end ``correct: false`` by a
number of the comparison (``benchmark/tests/test_correct.py`` runs the sound
cell, the control and the three faults every whole-fit cell can have)."""

import pytest

from benchmark import run as bench
from benchmark.lib import imagenet_faults

SEED = 2_147_483_659

#: the fault and a number that has to read over its limit
FAULTS = {
    "mixture_weight_ignored": "model_gap",
    "one_class_unsolved": "model_gap",
    "lcs_rows_are_sift_rows": "scores_rms_gap",
    "hellinger_left_out": "sift_pca_subspace_gap",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    result = bench.run_cell(
        "imagenet_fv_fit", SEED, 0.5, False, rehearsal=True, chip_check=False,
        wrap_fit=getattr(imagenet_faults, fault),
    )
    compared = result["compared"]
    assert compared and result["failed"] == 0, result["observed"]["failures"]
    assert not result["correct"], compared
    number = compared[FAULTS[fault]]
    assert not number["value"] <= number["limit"], (fault, number)


def test_a_fit_that_solves_too_few_classes_fails():
    """``pipelines/imagenet_fv.fit`` counts the class systems a fit solved
    (``bwls.class_solves``) and raises where they are not classes x blocks x
    passes: in the window the harness counts that fit as failed."""
    from keystone_tpu.core import trace
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    def miscounting(fit):
        def one_short(*args, **kwargs):
            results = real(*args, **kwargs)
            trace.metrics.inc("bwls.class_solves", -1)
            return results

        def broken(conf, data, seed, stem):
            inet.run = one_short
            try:
                return fit(conf, data, seed, stem)
            finally:
                inet.run = real

        real = inet.run
        return broken

    # the warm-up fit is the first to raise: the run ends with no result line
    with pytest.raises(RuntimeError, match="solved 15 class systems, not 16"):
        bench.run_cell(
            "imagenet_fv_fit", SEED, 0.5, False, rehearsal=True, chip_check=False, wrap_fit=miscounting,
        )
