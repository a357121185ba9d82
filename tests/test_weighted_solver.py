"""BlockWeightedLeastSquares tests.

Criteria mirror the reference suite
(src/test/scala/nodes/learning/BlockWeightedLeastSquaresSuite.scala): the
analytically-computed weighted-LS gradient vanishes (‖∇‖ < 1e-2) at the
solution on the reference's own fixture matrices, and the solver is invariant
to input row order.  Additionally the implementation is checked against a
direct numpy transcription of the reference algorithm (the BCD fixed point is
only approximately stationary on arbitrary data, so the transcription is the
oracle for synthetic problems).
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator

REF_RES = "/root/reference/src/test/resources"


def compute_gradient(features, labels, lam, mixture_weight, x, b):
    """Reference BWLSSuite.computeGradient (:18-60): per-example weights are
    (1-w)/n everywhere, plus w/n_c on the true-class column."""
    n = features.shape[0]
    class_idx = np.argmax(labels, axis=1)
    counts = np.bincount(class_idx, minlength=labels.shape[1])
    neg_wt = (1.0 - mixture_weight) / n
    wts = np.full(labels.shape, neg_wt)
    wts[np.arange(n), class_idx] += mixture_weight / counts[class_idx]
    out = features @ x + b - labels
    return features.T @ (out * wts) + lam * x


def naive_bwls(feats, labels, block_size, num_iter, lam, w):
    """Direct numpy transcription of reference trainWithL2 (:106-312), with
    one 'partition' per class."""
    n, num_classes = labels.shape
    ci = np.argmax(labels, 1)
    order = np.argsort(ci, kind="stable")
    feats, labels, ci = feats[order], labels[order], ci[order]
    xc = [feats[ci == c] for c in range(num_classes)]
    yc = [labels[ci == c] for c in range(num_classes)]
    counts = np.array([len(x) for x in xc])
    jlm = 2 * w + 2 * (1 - w) * counts / n - 1
    d = feats.shape[1]
    blocks = [slice(i, min(i + block_size, d)) for i in range(0, d, block_size)]
    models = [np.zeros((b.stop - b.start, num_classes)) for b in blocks]
    resid = [yc[c] - jlm for c in range(num_classes)]
    rmean = sum(r.mean(0) for r in resid) / num_classes
    stats = [None] * len(blocks)
    for _ in range(num_iter):
        for bi, bsl in enumerate(blocks):
            xb = [x[:, bsl] for x in xc]
            if stats[bi] is None:
                xall = np.concatenate(xb)
                pop_mean = xall.mean(0)
                ata = sum(x.T @ x for x in xb)
                pop_cov = ata / n - np.outer(pop_mean, pop_mean)
                jm = np.stack([x.mean(0) * w + pop_mean * (1 - w) for x in xb])
                stats[bi] = (pop_cov, pop_mean, jm)
            pop_cov, pop_mean, jm = stats[bi]
            pop_xtr = sum(x.T @ r for x, r in zip(xb, resid)) / n
            dws = []
            for c in range(num_classes):
                x, rl, nc = xb[c], resid[c][:, c], counts[c]
                cm = x.mean(0)
                zm = x - cm
                ccov = zm.T @ zm / nc
                cxtr = x.T @ rl / nc
                md = cm - pop_mean
                jxtx = pop_cov * (1 - w) + ccov * w + np.outer(md, md) * (1 - w) * w
                mmw = rmean[c] * (1 - w) + w * rl.mean()
                jxtr = pop_xtr[:, c] * (1 - w) + cxtr * w - jm[c] * mmw
                db = jxtx.shape[0]
                dws.append(
                    np.linalg.solve(
                        jxtx + lam * np.eye(db), jxtr - models[bi][:, c] * lam
                    )
                )
            dw = np.stack(dws, 1)
            models[bi] += dw
            resid = [resid[c] - xb[c] @ dw for c in range(num_classes)]
            rmean = sum(r.mean(0) for r in resid) / num_classes
    w_full = np.concatenate(models)
    jmc = np.concatenate([s[2] for s in stats], axis=1)
    b = jlm - np.einsum("cd,dc->c", jmc, w_full)
    return w_full, b


def make_problem(rng, n=90, d=8, num_classes=3):
    means = rng.normal(scale=2.0, size=(num_classes, d))
    class_idx = rng.integers(0, num_classes, n)
    feats = (means[class_idx] + rng.normal(size=(n, d))).astype(np.float32)
    labels = (2.0 * np.eye(num_classes)[class_idx] - 1.0).astype(np.float32)
    return feats, labels


def fit_full(feats, labels, block_size, num_iter, lam, w):
    est = BlockWeightedLeastSquaresEstimator(block_size, num_iter, lam, w)
    m = est.fit(jnp.asarray(feats), jnp.asarray(labels))
    return np.asarray(jnp.concatenate(m.xs, 0)), np.asarray(m.b)


class TestBlockWeightedLeastSquares:
    @pytest.mark.skipif(
        not os.path.exists(f"{REF_RES}/aMat.csv"), reason="reference fixture absent"
    )
    def test_gradient_near_zero_on_reference_fixture(self):
        # the reference suite's exact config and criterion (:73-95)
        a = np.loadtxt(f"{REF_RES}/aMat.csv", delimiter=",").astype(np.float32)
        b_mat = np.loadtxt(f"{REF_RES}/bMat.csv", delimiter=",").astype(np.float32)
        x, b = fit_full(a, b_mat, 4, 10, 0.1, 0.3)
        grad = compute_gradient(
            a.astype(np.float64), b_mat.astype(np.float64), 0.1, 0.3, x, b
        )
        assert np.linalg.norm(grad.ravel()) < 1e-2, np.linalg.norm(grad.ravel())

    def test_matches_reference_transcription(self, rng):
        feats, labels = make_problem(rng)
        x, b = fit_full(feats, labels, 4, 3, 0.1, 0.3)
        xn, bn = naive_bwls(
            feats.astype(np.float64), labels.astype(np.float64), 4, 3, 0.1, 0.3
        )
        np.testing.assert_allclose(x, xn, atol=5e-4)
        np.testing.assert_allclose(b, bn, atol=5e-4)

    def test_unsorted_input_matches_sorted(self, rng):
        feats, labels = make_problem(rng)
        x1, b1 = fit_full(feats, labels, 4, 3, 0.1, 0.3)
        perm = rng.permutation(feats.shape[0])
        x2, b2 = fit_full(feats[perm], labels[perm], 4, 3, 0.1, 0.3)
        np.testing.assert_allclose(x1, x2, atol=1e-5)
        np.testing.assert_allclose(b1, b2, atol=1e-5)

    def test_imbalanced_classes_match_transcription(self, rng):
        d = 6
        sizes = [5, 40, 17]
        means = rng.normal(scale=2.0, size=(3, d))
        feats = np.concatenate(
            [means[c] + rng.normal(size=(s, d)) for c, s in enumerate(sizes)]
        ).astype(np.float32)
        labels = np.concatenate(
            [np.tile(2.0 * np.eye(3)[c] - 1.0, (s, 1)) for c, s in enumerate(sizes)]
        ).astype(np.float32)
        x, b = fit_full(feats, labels, 6, 5, 0.1, 0.3)
        xn, bn = naive_bwls(
            feats.astype(np.float64), labels.astype(np.float64), 6, 5, 0.1, 0.3
        )
        np.testing.assert_allclose(x, xn, atol=5e-4)
        np.testing.assert_allclose(b, bn, atol=5e-4)

    def test_missing_class_raises(self, rng):
        feats = rng.normal(size=(10, 4)).astype(np.float32)
        labels = np.tile(2.0 * np.eye(3)[0] - 1.0, (10, 1)).astype(np.float32)
        est = BlockWeightedLeastSquaresEstimator(4, 1, 0.1, 0.3)
        with pytest.raises(ValueError, match="no examples"):
            est.fit(jnp.asarray(feats), jnp.asarray(labels))


def test_regroup_plan_matches_host_sort(rng, mesh42):
    """The all_to_all class-regroup (each row crosses the ICI once) must
    reproduce the host-side sort+pad exactly, including the zero tail."""
    import jax
    from keystone_tpu.parallel.mesh import DATA_AXIS, row_sharding
    from keystone_tpu.solvers.weighted import _RegroupPlan

    d_size = mesh42.shape[DATA_AXIS]
    n, n_src, cols = 37, 40, 5          # n_src divisible by data axis (4)
    assert n_src % d_size == 0
    p_tot = 48                           # sorted rows + zero tail, divisible
    x_host = rng.normal(size=(n_src, cols)).astype(np.float32)
    class_idx = rng.integers(0, 6, n)
    order = np.argsort(class_idx, kind="stable")

    expect = np.zeros((p_tot, cols), np.float32)
    expect[:n] = x_host[order]

    x_dev = jax.device_put(jnp.asarray(x_host), row_sharding(mesh42))
    got = _RegroupPlan(order, n_src, p_tot, d_size).apply(mesh42, x_dev)
    np.testing.assert_array_equal(np.asarray(got), expect)


def test_regroup_skew_guard_falls_back_exactly(rng, mesh42):
    """Class-SORTED input (near-identity permutation) makes every (src,dst)
    bucket land on the diagonal, so the all_to_all plan's padding would
    approach the full block — the skew guard must reject it and the chunked
    fallback must still produce the exact sorted+padded result."""
    import jax
    from keystone_tpu.parallel.mesh import DATA_AXIS, row_sharding, use_mesh
    from keystone_tpu.solvers.weighted import (
        BlockWeightedLeastSquaresEstimator,
        _RegroupPlan,
    )

    d_size = mesh42.shape[DATA_AXIS]
    n, cols = 64, 6
    class_idx = np.sort(rng.integers(0, 4, n))  # already grouped by class
    order = np.argsort(class_idx, kind="stable")
    plan = _RegroupPlan(order, n, n + 16, d_size)
    assert not plan.usable  # diagonal buckets -> padding ~ rows_in

    # End-to-end: the estimator on device-sharded, class-sorted features
    # must match the host-input fit exactly (fallback path).
    x = rng.normal(size=(n, 10)).astype(np.float32)
    y = (2.0 * np.eye(4)[class_idx] - 1.0).astype(np.float32)
    host_fit = BlockWeightedLeastSquaresEstimator(4, 1, 0.1, 0.5).fit(x, y)
    with use_mesh(mesh42):
        x_dev = jax.device_put(jnp.asarray(x), row_sharding(mesh42))
        y_dev = jax.device_put(jnp.asarray(y), row_sharding(mesh42))
        dev_fit = BlockWeightedLeastSquaresEstimator(4, 1, 0.1, 0.5).fit(
            x_dev, y_dev
        )
    for a, b in zip(host_fit.xs, dev_fit.xs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize(
    "case",
    [
        # the deployment's λ and mixture weight on unit-norm rows, fewer rows
        # a class than features: a class's covariance is rank-deficient and
        # the system is positive definite by the population's share and λ
        dict(n_per=4, classes=6, d=16, block=16, iters=1, lam=6e-5, w=0.25, unit=True),
        # pad columns (widths 4, 4, 2): their unit diagonal keeps the factor
        dict(n_per=12, classes=3, d=10, block=4, iters=2, lam=0.1, w=0.3, unit=False),
        # no ridge at all on full-rank data
        dict(n_per=30, classes=3, d=6, block=6, iters=1, lam=0.0, w=0.5, unit=False),
        # a mixture weight near one: the class's own statistics carry the system
        dict(n_per=20, classes=4, d=8, block=8, iters=2, lam=1e-3, w=0.9, unit=False),
    ],
    ids=["published_lambda_rank_deficient", "pad_columns", "no_ridge", "class_heavy"],
)
def test_cholesky_class_solves_match_pivoted_solve(rng, case):
    """The class systems are factored by Cholesky; the transcription solves
    the same systems by numpy's pivoted LU in float64."""
    classes, d = case["classes"], case["d"]
    idx = np.repeat(np.arange(classes), case["n_per"])
    feats = rng.normal(scale=2.0, size=(classes, d))[idx] + rng.normal(size=(len(idx), d))
    if case["unit"]:
        feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    feats = feats.astype(np.float32)
    labels = (2.0 * np.eye(classes)[idx] - 1.0).astype(np.float32)
    perm = rng.permutation(len(idx))
    feats, labels = feats[perm], labels[perm]
    args = (case["block"], case["iters"], case["lam"], case["w"])
    x, b = fit_full(feats, labels, *args)
    xn, bn = naive_bwls(feats.astype(np.float64), labels.astype(np.float64), *args)
    scale = max(1.0, np.abs(xn).max())
    np.testing.assert_allclose(x, xn, atol=2e-3 * scale)
    np.testing.assert_allclose(b, bn, atol=2e-3 * scale)


def _chunk_of_systems(rng, case):
    """Inputs of ``_class_solves`` for one block ``d`` wide (the last ``pad``
    columns zero with a unit diagonal on the population covariance, as
    ``_fused_bwls_impl`` pads a short block), and the float64 systems and
    right-hand sides they stand for."""
    classes, n_per, d, pad = case["classes"], case["n_per"], case["d"], case.get("pad", 0)
    lam, w = case["lam"], case["w"]
    n = classes * n_per
    x = rng.normal(scale=1.5, size=(classes, d)).repeat(n_per, 0) + rng.normal(size=(n, d))
    if case.get("unit"):
        x /= np.linalg.norm(x, axis=1, keepdims=True)
    x[:, d - pad:] = 0.0
    x = x.astype(np.float32).astype(np.float64)
    res = rng.normal(size=(n, classes)).astype(np.float32).astype(np.float64)
    pop_mean = x.mean(0)
    pop_cov = x.T @ x / n - np.outer(pop_mean, pop_mean) + np.diag(np.arange(d) >= d - pad)
    pop_xtr = x.T @ res / n
    xc = x.reshape(classes, n_per, d)
    class_means = xc.mean(1)
    joint_means = w * class_means + (1 - w) * pop_mean
    rmean = res.reshape(classes, n_per, classes).mean(1).mean(0)
    model = rng.normal(size=(d, classes))
    systems, rhs = [], []
    for c in range(classes):
        zm = xc[c] - class_means[c]
        md = class_means[c] - pop_mean
        r_c = res[c * n_per:(c + 1) * n_per, c]
        systems.append(
            pop_cov * (1 - w) + zm.T @ zm / n_per * w + np.outer(md, md) * (1 - w) * w
            + lam * np.eye(d)
        )
        mix = rmean[c] * (1 - w) + w * r_c.mean()
        rhs.append(
            pop_xtr[:, c] * (1 - w) + xc[c].T @ r_c / n_per * w - joint_means[c] * mix
            - model[:, c] * lam
        )
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    args = (
        f32(np.concatenate([x, np.zeros((n_per, d))])), f32(np.concatenate([res, np.zeros((n_per, classes))])),
        jnp.arange(classes, dtype=jnp.int32) * n_per, jnp.full(classes, n_per, jnp.int32),
        f32(pop_cov), f32(pop_mean), f32(pop_xtr), f32(joint_means), f32(rmean), f32(model),
        f32(lam), f32(w),
    )
    return args, np.stack(systems), np.stack(rhs)


def _dense_panel(systems, rhs):
    """``_factor_solve``'s ``panel`` for systems held whole."""
    return lambda r0, r1: jnp.concatenate([systems[:, r0:, r0:r1], rhs[:, None, r0:r1]], axis=1)


@pytest.mark.parametrize(
    "case",
    [
        dict(d=8, classes=5, n_per=6, chunk=5, lam=0.1, w=0.25),
        # the deployment's λ and mixture weight on unit-norm rows, twelve rows
        # under 128 columns: rank-deficient classes and population, one panel
        dict(d=128, classes=3, n_per=4, chunk=1, lam=6e-5, w=0.25, unit=True),
        # a last panel narrower than the others (128 + 72), no ridge, and
        # pad columns that only their unit diagonal keeps positive
        dict(d=200, classes=5, n_per=60, chunk=5, lam=0.0, w=0.3, pad=10),
        # three whole panels; seven classes in chunks of five (the last
        # chunk repeats class 0); the class's own statistics carry the system
        dict(d=384, classes=7, n_per=70, chunk=5, lam=1e-3, w=0.9),
    ],
    ids=["one_small_panel", "published_lambda_chunk_of_one", "ragged_panel_pad_columns", "three_panels_class_heavy"],
)
def test_blocked_factor_and_solve_match_float64(rng, case):
    """The solver's own blocked routine against numpy's pivoted solve in
    float64, through ``_class_solves`` (its systems assembled a panel at a
    time) and on the same systems held whole; the right-hand side that rides
    in the factorization against scipy's forward substitution."""
    import scipy.linalg

    from keystone_tpu.solvers import weighted

    d = case["d"]
    args, systems, rhs = _chunk_of_systems(rng, case)
    want = np.stack([np.linalg.solve(a, b) for a, b in zip(systems, rhs)])
    scale = np.linalg.norm(want, axis=1, keepdims=True)

    dw = np.asarray(weighted._class_solves(*args, case["n_per"], case["chunk"], None))
    assert dw.shape == (d, case["classes"])
    assert (np.linalg.norm(dw.T - want, axis=1, keepdims=True) / scale).max() < 2e-3

    # scratch that holds NaN on entry: only what the call wrote is read
    work = jnp.full((len(systems), d + 1, d), jnp.nan, jnp.float32)
    x, work = weighted._factor_solve(
        _dense_panel(jnp.asarray(systems, jnp.float32), jnp.asarray(rhs, jnp.float32)), d, work
    )
    assert (np.linalg.norm(np.asarray(x) - want, axis=1, keepdims=True) / scale).max() < 2e-3
    y_want = np.stack([
        scipy.linalg.solve_triangular(np.linalg.cholesky(a), b, lower=True)
        for a, b in zip(systems, rhs)
    ])
    y_gap = np.linalg.norm(np.asarray(work[:, d]) - y_want, axis=1) / np.linalg.norm(y_want, axis=1)
    assert y_gap.max() < 1e-3


@pytest.mark.parametrize("d,bad_column", [(8, 3), (300, 5), (300, 290)], ids=["one_panel", "first_panel", "last_panel"])
def test_indefinite_system_is_nonfinite_and_alone(rng, d, bad_column):
    """A system that is not positive definite surfaces as that class's
    solution non-finite (what ``cho_factor`` gave); the chunk's other
    classes are as sound as without it."""
    from keystone_tpu.solvers import weighted

    classes, bad_class = 4, 2
    roots = rng.normal(size=(classes, d, 2 * d))
    systems = roots @ roots.transpose(0, 2, 1) / (2 * d) + 0.1 * np.eye(d)
    systems[bad_class, bad_column, bad_column] = -1.0
    rhs = rng.normal(size=(classes, d))
    x, _work = weighted._factor_solve(
        _dense_panel(jnp.asarray(systems, jnp.float32), jnp.asarray(rhs, jnp.float32)),
        d, jnp.zeros((classes, d + 1, d), jnp.float32),
    )
    x = np.asarray(x)
    assert not np.isfinite(x[bad_class]).any()
    sound = [c for c in range(classes) if c != bad_class]
    want = np.stack([np.linalg.solve(systems[c], rhs[c]) for c in sound])
    assert np.isfinite(x[sound]).all()
    assert (np.linalg.norm(x[sound] - want, axis=1) / np.linalg.norm(want, axis=1)).max() < 1e-3


def test_fit_counts_its_factor_panels(rng):
    """``bwls_plan`` says the panel width the routine chose and the panels a
    system, and ``bwls.factor_panels`` counts them: classes x blocks x passes
    x panels."""
    from keystone_tpu.core import trace

    feats, labels = make_problem(rng, n=60, d=10, num_classes=3)
    before = trace.metrics.get("bwls.factor_panels")
    BlockWeightedLeastSquaresEstimator(4, 2, 0.1, 0.5).fit(
        jnp.asarray(feats, jnp.float32), jnp.asarray(labels, jnp.float32)
    )
    plan = [e for e in trace.flight_events() if e["name"] == "bwls_plan"][-1]["args"]
    assert (plan["factor_panel"], plan["factor_panels"]) == (4, 1)  # a block of 4 is one panel
    assert trace.metrics.get("bwls.factor_panels") - before == 3 * 3 * 2 * 1
