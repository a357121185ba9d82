"""The class-weighted solve with its classes split over a 4-way data axis
(``solvers/weighted._class_split``, ``_split_bwls_impl``): a chip solves its
own classes from its own rows after one ``all_to_all`` regroup.  On four of
the suite's eight CPU devices, at sixteen classes of uneven counts: the
split solve against the one-device solve and against the cell's plain
reference, each chip's share of the sweep against the unsplit sweep's
columns, and what the split records."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.parallel.mesh import make_mesh, row_sharding
from keystone_tpu.solvers import weighted
from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator

#: rows a class: 1 to 9, uneven, sixteen classes
COUNTS = np.array([3, 1, 9, 4, 2, 7, 5, 8, 1, 6, 2, 9, 3, 5, 4, 7])
LAM, W, BLOCK, EPOCHS = 0.05, 0.25, 16, 2


def _problem(seed: int = 3, d: int = 40):
    """Rows in an order where class 2's nine rows straddle the boundary of
    the first chip's quarter of the input (rows 18 to 26 of 76; the quarter
    ends at row 19)."""
    rng = np.random.default_rng(seed)
    labels = np.repeat(np.arange(len(COUNTS)), COUNTS)
    rng.shuffle(labels)
    labels = np.concatenate([labels[labels != 2][:18], np.full(9, 2), labels[labels != 2][18:]])
    n = len(labels)
    assert n == 76 and set(np.flatnonzero(labels == 2)) == set(range(18, 27))
    means = rng.normal(scale=1.5, size=(len(COUNTS), d))
    x = (means[labels] + rng.normal(size=(n, d))).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    y = (2.0 * np.eye(len(COUNTS))[labels] - 1.0).astype(np.float32)
    return x, y, labels


@pytest.fixture(scope="module")
def mesh4(devices):
    return make_mesh(data=4, model=1, devices=devices[:4])


def _weights(model):
    return np.concatenate([np.asarray(m) for m in model.xs]), np.asarray(model.b)


@pytest.fixture(scope="module")
def split_fit(mesh4):
    x, y, labels = _problem()
    est = BlockWeightedLeastSquaresEstimator(BLOCK, EPOCHS, LAM, W, mesh=mesh4)
    before = dict(trace.metrics.counters())
    model = est.fit(jax.device_put(jnp.asarray(x), row_sharding(mesh4)), y)
    after = dict(trace.metrics.counters())
    plan = [e for e in trace.flight_events() if e["name"] == "bwls_plan"][-1]["args"]
    counted = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    return {"x": x, "y": y, "labels": labels, "model": model, "plan": plan,
            "counted": counted, "tier": est.last_fit_report.chosen}


def test_split_solve_equals_one_device_and_the_reference(split_fit):
    x, y, labels = split_fit["x"], split_fit["y"], split_fit["labels"]
    assert split_fit["tier"] == "fused[mesh 4x1]"
    got_w, got_b = _weights(split_fit["model"])
    one_w, one_b = _weights(BlockWeightedLeastSquaresEstimator(BLOCK, EPOCHS, LAM, W).fit(x, y))
    scale = np.abs(one_w).max()
    np.testing.assert_allclose(got_w, one_w, rtol=0, atol=2e-5 * scale)
    np.testing.assert_allclose(got_b, one_b, rtol=0, atol=2e-5)
    reference = manifest.load_module("reference", "imagenet_fv_mesh")
    with jax.default_matmul_precision("highest"):
        ref_w, ref_b = reference.weighted_least_squares(
            x, labels, len(COUNTS), LAM, W, BLOCK, EPOCHS
        )
    np.testing.assert_allclose(got_w, np.asarray(ref_w), rtol=0, atol=1e-4 * scale)
    np.testing.assert_allclose(got_b, np.asarray(ref_b), rtol=0, atol=1e-4)


def test_split_records_its_chips_and_bytes(split_fit):
    """Four classes a chip, each chip's own rows and the padded share, the
    regroup's kind and bytes, and the solve's sums across the chips."""
    plan, counted = split_fit["plan"], split_fit["counted"]
    split = weighted._class_split(COUNTS, 4, int(COUNTS.max()))
    assert plan["classes_by_chip"] == [4, 4, 4, 4] and sum(plan["classes_by_chip"]) == len(COUNTS)
    assert plan["rows_by_chip"] == [17, 22, 18, 19] and sum(plan["rows_by_chip"]) == 76
    assert plan["rows_padded"] == split.rows_out == 22 + 9
    assert plan["regroup"] == "all_to_all"
    assert plan["regroup_bytes"] == counted["bwls.regroup_bytes"] > 0
    widths, c = [16, 16, 8], len(COUNTS)
    assert counted["mesh.psum_bytes"] == 4 * (
        3 * (16 + 16 * 16 + c * 16) + 3 * EPOCHS * (16 * c + c * c) + c * c
    )
    assert counted["bwls.class_solves"] == c * len(widths) * EPOCHS


def test_each_chip_solves_its_own_classes(mesh4):
    """A chip's sweep over its slots, on its own rows of the split layout,
    gives the unsplit sweep's columns for its classes; the chips' columns,
    gathered and put in class order, are the whole."""
    x, y, labels = _problem(seed=5, d=16)
    c, n_max = len(COUNTS), int(COUNTS.max())
    order = np.argsort(labels, kind="stable")
    starts = np.concatenate([[0], np.cumsum(COUNTS)[:-1]]).astype(np.int32)
    rng = np.random.default_rng(0)
    sorted_x = np.concatenate([x[order], np.zeros((n_max, 16), np.float32)])
    res = np.concatenate([(y - y.mean(0))[order], np.zeros((n_max, c), np.float32)])
    pop_cov = np.cov(x.T, bias=True).astype(np.float32)
    stats = dict(
        pop_cov=jnp.asarray(pop_cov), pop_mean=jnp.asarray(x.mean(0)),
        pop_xtr=jnp.asarray(rng.normal(size=(16, c)).astype(np.float32)),
        joint_means=jnp.asarray(rng.normal(size=(c, 16)).astype(np.float32)),
        residual_mean=jnp.asarray(rng.normal(size=(c,)).astype(np.float32)),
        model_block=jnp.asarray(rng.normal(size=(16, c)).astype(np.float32)),
        lam=jnp.float32(LAM), mixture_weight=jnp.float32(W),
    )
    whole = np.asarray(weighted._class_solves(
        jnp.asarray(sorted_x), jnp.asarray(res), jnp.asarray(starts), jnp.asarray(COUNTS),
        **stats, n_max=n_max, chunk=4,
    ))
    split = weighted._class_split(COUNTS, 4, n_max)
    laid = np.zeros((4 * split.rows_out, 16), np.float32)
    laid[split.place] = x[order]
    laid_res = np.zeros((4 * split.rows_out, c), np.float32)
    laid_res[split.place] = res[: len(order)]
    parts = []
    for k in range(4):
        rows = slice(k * split.rows_out, (k + 1) * split.rows_out)
        own = np.asarray(weighted._class_solves(
            jnp.asarray(laid[rows]), jnp.asarray(laid_res[rows]),
            jnp.asarray(split.slot_starts[k]), jnp.asarray(split.slot_counts[k]),
            **stats, n_max=n_max, chunk=4, classes=jnp.asarray(split.slot_classes[k]),
        ))
        mine = np.arange(split.bounds[k], split.bounds[k + 1])
        np.testing.assert_allclose(own[:, : len(mine)], whole[:, mine], rtol=0, atol=1e-5)
        parts.append(own)
    gathered = np.concatenate(parts, axis=1)[:, split.columns]
    np.testing.assert_allclose(gathered, whole, rtol=0, atol=1e-5)
    assert int(np.diff(split.bounds).sum()) == c


def test_split_out_of_memory_steps_down_and_records_no_split(mesh4):
    """The class-split dispatch dying with RESOURCE_EXHAUSTED steps the
    ladder down one tier; the solve that ran there is the one the plan
    reports, with no chip's share of the split and none of its sums."""
    import faults

    x, y, _labels = _problem()
    est = BlockWeightedLeastSquaresEstimator(BLOCK, EPOCHS, LAM, W, mesh=mesh4)
    before = trace.metrics.get("mesh.psum_bytes")
    with faults.oom_faults(weighted, "_execute_split_bwls", failures=1) as dispatch:
        model = est.fit(jax.device_put(jnp.asarray(x), row_sharding(mesh4)), y)
    plan = [e for e in trace.flight_events() if e["name"] == "bwls_plan"][-1]["args"]
    assert dispatch.state["calls"] == 1
    assert plan["tier"] == est.last_fit_report.chosen == "single_device/fused"
    assert plan["classes_by_chip"] is None and plan["rows_by_chip"] is None
    assert plan["rows_padded"] is None
    assert trace.metrics.get("mesh.psum_bytes") == before
    got_w, got_b = _weights(model)
    one_w, one_b = _weights(BlockWeightedLeastSquaresEstimator(BLOCK, EPOCHS, LAM, W).fit(x, y))
    np.testing.assert_allclose(got_w, one_w, rtol=0, atol=2e-5 * np.abs(one_w).max())
    np.testing.assert_allclose(got_b, one_b, rtol=0, atol=2e-5)
