"""Every solve is one hand-ordered walk of ``core.memory.run_ladder``: the
forms ``TestBcdLadder`` (tests/test_memory_plan.py) does not cover — the
made block solve, the BCD mesh, the weighted solve and the weighted mesh —
through one table of cases, on a simulated HBM budget
(``KEYSTONE_HBM_BUDGET``) and the RESOURCE_EXHAUSTED injector of
tests/faults.py:

(a) a generous budget runs the head tier, with no denial and no
    ``placement`` in the report's record;
(b) a budget just under the head's charge runs the next tier and records
    the denial;
(c) a budget nothing fits denies every tier but the floor, which runs;
(d) RESOURCE_EXHAUSTED raised by the head's run steps down one tier,
    counted ``solver_oom_retry``, to the next tier's model;
(e) the same error surfacing only at the ladder's own sync of the result
    steps down the same way;
(f) a typed error that is not an OOM propagates, and nothing is counted.

Then the walk's host sections, the mesh rungs' psum byte count, the labels
padded to a caller-padded design matrix, and the report record of every
form; and ``run_ladder`` itself on hand-built tiers."""

from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Callable

import jax.numpy as jnp
import numpy as np
import pytest

from faults import oom_faults, resource_exhausted_error

from keystone_tpu.core import memory as kmem
from keystone_tpu.core import trace
from keystone_tpu.core.pipeline import Transformer, node
from keystone_tpu.core.resilience import counters
from keystone_tpu.parallel.mesh import make_mesh, padded_shard_rows
from keystone_tpu.solvers import block as block_mod
from keystone_tpu.solvers import weighted as weighted_mod
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator, BlockSource
from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator

TOL = dict(rtol=1e-5, atol=1e-5)


@node(data_fields=("w",), meta_fields=())
class _Projection(Transformer):
    """``tanh(x @ w)``: a made block, one ``[d, bs]`` slice of ``w`` a block."""

    def __init__(self, w):
        self.w = w

    def __call__(self, x):
        return jnp.tanh(x @ self.w)


@dataclasses.dataclass
class Form:
    """One solver form: its tiers in hand order (the floor last), the
    module-level dispatch its head tier's run calls, and a fit returning
    the estimator and its predictions on the training rows."""

    name: str
    tiers: tuple
    dispatch: tuple
    fit: Callable
    mesh: dict | None = None


def _classes(rng, n, c):
    cls = rng.integers(0, c, n)
    return cls, (2.0 * np.eye(c)[cls] - 1.0).astype(np.float32)


def _made_problem():
    rng = np.random.default_rng(11)
    n, d, nb, bs, k = 128, 12, 4, 16, 4
    rows = jnp.asarray(rng.uniform(0, 1, (n, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 1, (nb, d, bs)).astype(np.float32))
    labels = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    return BlockSource(rows, _Projection(w)), labels


def _made_form():
    source, labels = _made_problem()
    bs = source.block_size
    held = jnp.concatenate(list(source), axis=1)

    def fit():
        est = BlockLeastSquaresEstimator(bs, num_iter=2, lam=0.5)
        model = est.fit(source, labels)
        return est, np.asarray(model(held))

    return Form("made", ("fused[made]", "stepwise[made]"),
                (block_mod, "_execute_fused_bcd"), fit)


def _mesh_form():
    # Tall-skinny: collapsing the model axis halves the row-sharded terms
    # each chip holds, so the reduced rung is charged less than the full.
    rng = np.random.default_rng(12)
    a = rng.normal(size=(2048, 256)).astype(np.float32)
    b = rng.normal(size=(2048, 8)).astype(np.float32)

    def fit():
        est = BlockLeastSquaresEstimator(
            64, num_iter=2, lam=0.5, mesh=make_mesh(data=4, model=2)
        )
        model = est.fit(a, b)
        return est, np.asarray(model(jnp.asarray(a)))

    return Form("mesh", ("fused[mesh 4x2]", "fused[mesh 8x1]", "single_device"),
                (block_mod, "_execute_fused_bcd_mesh"), fit, {"data": 4, "model": 2})


def _weighted_form():
    rng = np.random.default_rng(13)
    cls, y = _classes(rng, 96, 8)
    x = (rng.normal(size=(96, 256)) + 0.1 * cls[:, None]).astype(np.float32)

    def fit():
        est = BlockWeightedLeastSquaresEstimator(32, num_iter=2, lam=0.1, mixture_weight=0.5)
        model = est.fit(x, y)
        return est, np.asarray(model(jnp.asarray(x)))

    return Form("weighted", ("fused", "stepwise", "host_staged"),
                (weighted_mod, "_execute_fused_bwls"), fit)


def _weighted_mesh_form():
    rng = np.random.default_rng(14)
    cls, y = _classes(rng, 512, 8)
    x = (rng.normal(size=(512, 512)) + 0.1 * cls[:, None]).astype(np.float32)

    def fit():
        est = BlockWeightedLeastSquaresEstimator(
            32, num_iter=1, lam=0.1, mixture_weight=0.5, mesh=make_mesh(data=4, model=2)
        )
        model = est.fit(x, y)
        return est, np.asarray(model(jnp.asarray(x)))

    return Form("weighted_mesh", ("fused[mesh 4x2]", "fused[mesh 8x1]", "single_device"),
                (weighted_mod, "_execute_fused_bwls"), fit, {"data": 4, "model": 2})


def _held_form():
    rng = np.random.default_rng(15)
    a = rng.normal(size=(32, 1024)).astype(np.float32)
    b = rng.normal(size=(32, 16)).astype(np.float32)

    def fit():
        est = BlockLeastSquaresEstimator(64, num_iter=2, lam=0.5)
        model = est.fit(a, b)
        return est, np.asarray(model(jnp.asarray(a)))

    return Form("held", ("fused", "stepwise", "host_staged"),
                (block_mod, "_execute_fused_bcd"), fit)


FORMS = {
    "made": _made_form,
    "mesh": _mesh_form,
    "weighted": _weighted_form,
    "weighted_mesh": _weighted_mesh_form,
    "held": _held_form,
}
TABLE = ["made", "mesh", "weighted", "weighted_mesh"]


def _generous(monkeypatch, form: Form) -> None:
    """A budget the head tier fits: 1 GB, or for a made fit one between what
    the made form is charged with and what holding the matrix needs (more,
    and the solver holds the blocks)."""
    budget = "1G"
    if form.name == "made":
        source, labels = _made_problem()
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1")
        plan = block_mod._plan_bcd(source, labels, 2, source.block_size)
        assert plan["made_bytes"] < plan["held_bytes"], plan
        budget = str((plan["made_bytes"] + plan["held_bytes"]) // 2)
    monkeypatch.setenv(kmem.HBM_BUDGET_ENV, budget)


def _pinched(monkeypatch, form: Form):
    """Fit under a generous budget, then set the budget one byte under the
    head tier's charge; returns the generous fit."""
    _generous(monkeypatch, form)
    est, preds = form.fit()
    head = form.tiers[0]
    assert est.last_fit_report.chosen == head
    monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(est.last_fit_report.plans[head].total_bytes - 1))
    return est, preds


def _next_tier_fit(monkeypatch, form: Form):
    """The fit the head's denial leaves: the second tier's model."""
    _pinched(monkeypatch, form)
    est, preds = form.fit()
    assert est.last_fit_report.chosen == form.tiers[1]
    return preds


@contextlib.contextmanager
def _oom_at_sync(monkeypatch):
    """RESOURCE_EXHAUSTED raised by the first ``solve`` wait: an error the
    device reports only when the ladder syncs on a tier's result."""
    real = trace.wait
    fired = []

    def wait(value, name="device"):
        if name == "solve" and not fired:
            fired.append(name)
            raise resource_exhausted_error()
        return real(value, name)

    monkeypatch.setattr(trace, "wait", wait)
    yield fired


# -- the table ----------------------------------------------------------------


@pytest.mark.parametrize("form_name", TABLE)
def test_a_generous_budget_runs_the_head(form_name, monkeypatch):
    form = FORMS[form_name]()
    _generous(monkeypatch, form)
    est, preds = form.fit()
    rep = est.last_fit_report
    assert rep.chosen == form.tiers[0]
    assert rep.denials == [] and rep.oom_retries == []
    assert list(rep.plans) == [form.tiers[0]]  # nothing below it planned
    assert "placement" not in rep.record()
    assert rep.mesh_shape == form.mesh
    assert np.all(np.isfinite(preds))


@pytest.mark.parametrize("form_name", TABLE)
def test_a_denied_head_runs_the_next_tier(form_name, monkeypatch):
    form = FORMS[form_name]()
    _, p_head = _pinched(monkeypatch, form)
    est, p_next = form.fit()
    rep = est.last_fit_report
    assert rep.chosen == form.tiers[1]
    assert rep.denials == [form.tiers[0]]
    assert rep.mesh_shape == (form.mesh and {"data": 8, "model": 1})
    assert list(rep.plans) == list(form.tiers[:2])
    assert rep.plans[form.tiers[1]].total_bytes < rep.plans[form.tiers[0]].total_bytes
    tol = dict(rtol=2e-4, atol=2e-4) if form_name == "weighted_mesh" else TOL
    np.testing.assert_allclose(p_next, p_head, **tol)


@pytest.mark.parametrize("form_name", TABLE)
def test_a_budget_nothing_fits_runs_the_floor(form_name, monkeypatch):
    form = FORMS[form_name]()
    monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1000")
    est, preds = form.fit()
    rep = est.last_fit_report
    floor = form.tiers[-1]
    assert rep.denials[: len(form.tiers) - 1] == list(form.tiers[:-1])
    if floor == "single_device":
        # the mesh floor walks the one-device ladder under the same budget
        assert rep.chosen == "single_device/host_staged"
        assert rep.mesh_shape is None
    else:
        assert rep.chosen == floor
        assert not rep.plans[floor].admitted  # attempted anyway
    assert np.all(np.isfinite(preds))


@pytest.mark.parametrize("form_name", TABLE)
def test_an_oom_in_the_heads_run_steps_down_one_tier(form_name, monkeypatch):
    form = FORMS[form_name]()
    p_next = _next_tier_fit(monkeypatch, form)
    _generous(monkeypatch, form)
    before = counters.get("solver_oom_retry")
    with oom_faults(*form.dispatch, failures=1):
        est, preds = form.fit()
    rep = est.last_fit_report
    assert rep.oom_retries == [form.tiers[0]]
    assert rep.chosen == form.tiers[1]  # one tier down, not the floor
    assert rep.mesh_shape == (form.mesh and {"data": 8, "model": 1})
    assert counters.get("solver_oom_retry") == before + 1
    np.testing.assert_allclose(preds, p_next, **TOL)


@pytest.mark.parametrize("form_name", TABLE + ["held"])
def test_an_oom_at_the_ladders_sync_steps_down_one_tier(form_name, monkeypatch):
    form = FORMS[form_name]()
    p_next = _next_tier_fit(monkeypatch, form)
    _generous(monkeypatch, form)
    before = counters.get("solver_oom_retry")
    with _oom_at_sync(monkeypatch) as fired:
        est, preds = form.fit()
    assert fired == ["solve"]
    rep = est.last_fit_report
    assert rep.oom_retries == [form.tiers[0]]
    assert rep.chosen == form.tiers[1]
    assert counters.get("solver_oom_retry") == before + 1
    np.testing.assert_allclose(preds, p_next, **TOL)


@pytest.mark.parametrize("form_name", TABLE)
def test_a_typed_error_propagates_uncounted(form_name, monkeypatch):
    form = FORMS[form_name]()
    _generous(monkeypatch, form)

    def boom(*args, **kw):
        raise ValueError("not a memory problem")

    monkeypatch.setattr(*form.dispatch, boom)
    before = counters.get("solver_oom_retry")
    with pytest.raises(ValueError, match="not a memory problem"):
        form.fit()
    assert counters.get("solver_oom_retry") == before


# -- the walk's host sections ---------------------------------------------------


@pytest.fixture
def sections(monkeypatch):
    """Every host section and wait a fit opens, in order, each with the
    sections open around it."""
    seen, open_ = [], []
    real_host, real_wait = trace.host, trace.wait

    @contextlib.contextmanager
    def host(section, name=None, **attrs):
        seen.append((section, name, tuple(open_)))
        open_.append(section)
        try:
            with real_host(section, name, **attrs):
                yield
        finally:
            open_.pop()

    def wait(value, name="device"):
        seen.append(("wait", name, tuple(open_)))
        return real_wait(value, name)

    monkeypatch.setattr(trace, "host", host)
    monkeypatch.setattr(trace, "wait", wait)
    return seen


@pytest.mark.parametrize("form_name", list(FORMS))
def test_the_walk_charges_search_plan_dispatch_and_the_solve_wait(
    form_name, monkeypatch, sections
):
    """One ``search`` section builds the ladder and never encloses a tier's
    run; each tier's preflight is ``plan`` and its run ``dispatch``, both
    at site ``plan:<tier>``; the result's sync is the ``solve`` wait inside
    the dispatch."""
    form = FORMS[form_name]()
    _generous(monkeypatch, form)
    sections.clear()
    est, _ = form.fit()
    head = form.tiers[0]
    assert est.last_fit_report.chosen == head
    names = [(s, n) for s, n, _ in sections]
    assert names.count(("search", "ladder")) == 1
    search_at = names.index(("search", "ladder"))
    assert names.index(("plan", f"plan:{head}")) > search_at
    assert names.index(("dispatch", f"plan:{head}")) > names.index(("plan", f"plan:{head}"))
    assert not any("search" in around for _, _, around in sections)
    waits = [around for s, n, around in sections if (s, n) == ("wait", "solve")]
    assert waits and all("dispatch" in around for around in waits)


# -- the mesh rungs' collective bytes -------------------------------------------


@pytest.mark.parametrize("data,model,reduced", [
    (4, 2, False), (4, 2, True), (2, 4, False), (1, 8, False),
])
def test_the_mesh_rung_counts_the_bytes_it_sums(rng, monkeypatch, data, model, reduced):
    """``mesh.psum_bytes`` from the shapes of the rung that ran: a gram and
    ``num_iter`` cross terms a block, the block means and the label mean,
    over the data axis only."""
    n, d, k, bs, iters = 512, 192, 5, 64, 2
    a = rng.normal(size=(n, d)).astype(np.float32)
    b = rng.normal(size=(n, k)).astype(np.float32)
    mesh = make_mesh(data=data, model=model)

    def fit():
        est = BlockLeastSquaresEstimator(bs, num_iter=iters, lam=0.5, mesh=mesh)
        est.fit(a, b)
        return est.last_fit_report

    monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
    rep = fit()
    if reduced:
        monkeypatch.setenv(
            kmem.HBM_BUDGET_ENV, str(rep.plans[rep.chosen].total_bytes - 1)
        )
    before = trace.metrics.get("mesh.psum_bytes")
    rep = fit()
    ran = rep.mesh_shape
    assert ran == ({"data": 8, "model": 1} if reduced else {"data": data, "model": model})
    nb, k_pad = d // bs, k + (-k) % ran["model"]
    want = 4 * (nb * (bs * bs + iters * bs * k_pad) + nb * bs + k_pad) if ran["data"] > 1 else 0
    assert trace.metrics.get("mesh.psum_bytes") - before == want


# -- labels padded to a caller-padded design matrix ------------------------------


@pytest.mark.parametrize("solver", ["bcd", "bwls"])
@pytest.mark.parametrize("reduced", [False, True], ids=["full", "reduced"])
def test_labels_meet_a_caller_padded_design_matrix(rng, monkeypatch, mesh42, solver, reduced):
    """A caller under an 8-way data axis hands the solver features padded to
    208 rows for 203 labels (as the mesh workloads do); the rung that runs,
    whatever its own data axis, gives the labels the design matrix's rows
    (and, on a model axis of 2, three classes a fourth column) and fits the
    model of the unpadded rows."""
    n, d, k = 203, 128, 3
    cls = rng.integers(0, k, n)
    x = (rng.normal(size=(n, d)) + 0.2 * cls[:, None]).astype(np.float32)
    y = (2.0 * np.eye(k)[cls] - 1.0).astype(np.float32)
    x_pad, nvalid = padded_shard_rows(x, make_mesh(data=8, model=1))
    assert x_pad.shape[0] == 208 and nvalid == 203

    def estimator(mesh=None):
        if solver == "bcd":
            return BlockLeastSquaresEstimator(32, num_iter=1, lam=1.0, mesh=mesh)
        return BlockWeightedLeastSquaresEstimator(32, num_iter=1, lam=0.1, mixture_weight=0.5, mesh=mesh)

    plain = estimator().fit(x, y)
    monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
    est = estimator(mesh42)
    if reduced:
        est.fit(x_pad, y, nvalid=nvalid)
        head = est.last_fit_report.plans["fused[mesh 4x2]"].total_bytes
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(head - 1))
    model = est.fit(x_pad, y, nvalid=nvalid)
    assert est.last_fit_report.chosen == ("fused[mesh 8x1]" if reduced else "fused[mesh 4x2]")
    np.testing.assert_allclose(
        np.asarray(model(jnp.asarray(x))), np.asarray(plain(jnp.asarray(x))),
        rtol=2e-4, atol=2e-4,
    )


# -- the report record ------------------------------------------------------------


@pytest.mark.parametrize("form_name", list(FORMS))
def test_the_report_record_round_trips_through_json(form_name, monkeypatch):
    form = FORMS[form_name]()
    _generous(monkeypatch, form)
    est, _ = form.fit()
    rec = est.last_fit_report.record()
    blob = json.loads(json.dumps(rec))
    assert blob == json.loads(json.dumps(blob))
    assert "placement" not in blob
    assert blob["chosen_tier"] == form.tiers[0]
    assert set(blob["tiers"]) == {form.tiers[0]}
    assert blob["tiers"][form.tiers[0]]["admitted"] is True
    assert blob["mesh_shape"] == form.mesh
    assert blob["denials"] == [] and blob["oom_retries"] == []
    assert blob["block_source"] == {"made": "made", "held": "held", "mesh": "held"}.get(form_name)


# -- run_ladder on hand-built tiers ----------------------------------------------


def _tier(name, admitted=True, run=None, planned=None):
    def plan():
        if planned is not None:
            planned.append(name)
        return kmem.MemoryPlan(label=name, admitted=admitted, reason="test")

    return kmem.Tier(name, plan, run or (lambda _plan: f"{name}:ran"))


def test_run_ladder_walks_the_hand_order():
    """The first admitted tier runs; a denied one before it is recorded;
    nothing after it is planned."""
    planned = []
    report = kmem.FitReport()
    out = kmem.run_ladder("t", [
        _tier("a", admitted=False, planned=planned),
        _tier("b", planned=planned),
        _tier("c", planned=planned),
    ], report)
    assert out == "b:ran"
    assert planned == ["a", "b"]
    assert report.chosen == "b" and report.denials == ["a"]
    assert list(report.plans) == ["a", "b"]


def test_run_ladder_runtime_oom_steps_down_counted():
    calls = []

    def dying(_plan):
        calls.append("a")
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    report = kmem.FitReport()
    before = counters.get("solver_oom_retry")
    out = kmem.run_ladder("t", [_tier("a", run=dying), _tier("b")], report)
    assert out == "b:ran" and calls == ["a"]
    assert report.chosen == "b" and report.oom_retries == ["a"]
    assert counters.get("solver_oom_retry") == before + 1


def test_run_ladder_typed_failure_not_recorded_as_oom():
    def dying(_plan):
        raise ValueError("bad data, not memory")

    report = kmem.FitReport()
    before = counters.get("solver_oom_retry")
    with pytest.raises(ValueError):
        kmem.run_ladder("t", [_tier("a", run=dying), _tier("b")], report)
    assert report.oom_retries == [] and report.chosen is None
    assert counters.get("solver_oom_retry") == before


def test_run_ladder_floor_oom_propagates():
    """Nothing is below the floor: its RESOURCE_EXHAUSTED is the fit's."""
    def dying(_plan):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    report = kmem.FitReport()
    before = counters.get("solver_oom_retry")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        kmem.run_ladder("t", [_tier("a", run=dying), _tier("b", run=dying)], report)
    assert report.oom_retries == ["a"] and report.chosen is None
    assert counters.get("solver_oom_retry") == before + 1


def test_run_ladder_sync_lets_a_result_it_cannot_sync_through(monkeypatch):
    """The sync on a tier's result is best effort: only an OOM there steps
    down; any other error of the sync leaves the result as the tier's."""
    def wait(value, name="device"):
        raise TypeError("not a pytree of arrays")

    monkeypatch.setattr(trace, "wait", wait)
    report = kmem.FitReport()
    assert kmem.run_ladder("t", [_tier("a"), _tier("b")], report) == "a:ran"
    assert report.chosen == "a" and report.oom_retries == []


@pytest.mark.parametrize("head_admitted", [True, False])
def test_run_ladder_counts_a_degraded_fit(head_admitted):
    """A fit that runs below the head tier is counted
    ``solver_tier_degraded``; one that runs the head is not."""
    before = counters.get("solver_tier_degraded")
    report = kmem.FitReport()
    kmem.run_ladder("t", [_tier("a", admitted=head_admitted), _tier("b")], report)
    assert report.chosen == ("a" if head_admitted else "b")
    assert counters.get("solver_tier_degraded") - before == (0 if head_admitted else 1)
