"""Placement search (core.autoshard, ISSUE 9): candidate enumeration from
mesh factorizations and avals, the zero-cost batch preflight prune, the
analytic-prior x learned-calibration cost model, margin-bucketed ranking
(untrained search == hand ladder bit-for-bit), the plan-outcome log, and
the ranked run_ladder execution contract — plus the parallel/mesh.py
enumeration edge cases and tools/plan_view.py rendering.
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core import autoshard
from keystone_tpu.core import memory as kmem
from keystone_tpu.core import optimize as kopt
from keystone_tpu.parallel.mesh import (
    enumerate_mesh_shapes,
    enumerate_meshes,
    make_mesh,
    mesh_desc,
    reduced_mesh,
)
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)
import plan_view  # noqa: E402  (tools/plan_view.py)


# -- parallel/mesh.py enumeration edge cases ----------------------------------


def test_enumerate_mesh_shapes_one_device():
    assert enumerate_mesh_shapes(1) == [(1, 1)]


def test_enumerate_mesh_shapes_prime_count():
    # A prime count has exactly the two degenerate factorizations.
    assert enumerate_mesh_shapes(7) == [(7, 1), (1, 7)]


def test_enumerate_mesh_shapes_composite_data_major_descending():
    assert enumerate_mesh_shapes(8) == [(8, 1), (4, 2), (2, 4), (1, 8)]
    for n in (2, 6, 12):
        shapes = enumerate_mesh_shapes(n)
        assert all(d * m == n for d, m in shapes)
        assert [d for d, _ in shapes] == sorted(
            (d for d, _ in shapes), reverse=True
        )


def test_enumerate_mesh_shapes_rejects_zero():
    with pytest.raises(ValueError):
        enumerate_mesh_shapes(0)


def test_reduced_mesh_on_already_collapsed_mesh_is_none():
    # Pure data-parallel: nothing left to collapse — the ladder's next
    # rung is the single-device floor, not another mesh.
    collapsed = make_mesh(data=8, model=1)
    assert reduced_mesh(collapsed) is None
    # And collapsing a real (data, model) mesh yields the collapsed form
    # whose own reduction is again None.
    full = make_mesh(data=4, model=2)
    rm = reduced_mesh(full)
    assert mesh_desc(rm) == "8x1"
    assert reduced_mesh(rm) is None


def test_enumerate_meshes_deterministic_over_fixed_devices():
    import jax

    devices = jax.devices()
    a = enumerate_meshes(devices)
    b = enumerate_meshes(devices)
    assert [mesh_desc(m) for m in a] == [mesh_desc(m) for m in b]
    assert [mesh_desc(m) for m in a] == [
        f"{d}x{m}" for d, m in enumerate_mesh_shapes(len(devices))
    ]
    # Same devices in the same order for every candidate mesh.
    for m in a:
        assert list(m.devices.flat) == list(devices)


# -- sharding-spec enumeration from avals -------------------------------------


def test_spec_candidates_generated_from_aval_dims():
    aval = jnp.zeros((8, 6), jnp.float32)
    specs = {
        c["spec"]: c["per_chip_bytes"]
        for c in autoshard.spec_candidates(aval, {"data": 2, "model": 3})
    }
    total = 8 * 6 * 4
    # replicated always legal; data over any dim divisible by 2; model
    # over any dim divisible by 3 — all from the aval, no hand list.
    assert specs == {
        "replicated": total,
        "data@dim0": total // 2,
        "data@dim1": total // 2,
        "model@dim1": total // 3,
    }


def test_best_spec_minimizes_per_chip_bytes_and_replicates_when_odd():
    aval = jnp.zeros((8, 6), jnp.float32)
    best = autoshard.best_spec(aval, {"data": 4, "model": 2})
    assert best["spec"] == "data@dim0"
    assert best["per_chip_bytes"] == 8 * 6 * 4 // 4
    # Nothing divides a prime dim: replicated is the only legal spec.
    odd = jnp.zeros((7,), jnp.float32)
    assert autoshard.best_spec(odd, {"data": 4, "model": 2})["spec"] == (
        "replicated"
    )


# -- spec strings -> executable layouts (ISSUE 10) -----------------------------


def test_spec_pspec_lowers_every_vocabulary_entry():
    from jax.sharding import PartitionSpec as P

    assert autoshard.spec_pspec("replicated", 2) == P(None, None)
    assert autoshard.spec_pspec("data@dim0", 2) == P("data", None)
    assert autoshard.spec_pspec("model@dim1", 2) == P(None, "model")
    assert autoshard.spec_pspec("model@dim2", 3) == P(None, None, "model")
    with pytest.raises(ValueError):
        autoshard.spec_pspec("bogus", 2)
    with pytest.raises(ValueError):
        autoshard.spec_pspec("data@dim5", 2)  # names a missing dim


def test_spec_sharding_places_arrays_per_spec(mesh42):
    import jax

    a = jnp.zeros((8, 6), jnp.float32)
    sharded = jax.device_put(
        a, autoshard.spec_sharding("data@dim0", mesh42, 2)
    )
    # data axis 4: each chip holds a [2, 6] shard
    shard_shape = sharded.sharding.shard_shape((8, 6))
    assert shard_shape == (2, 6)
    rep = jax.device_put(
        a, autoshard.spec_sharding("replicated", mesh42, 2)
    )
    assert rep.sharding.shard_shape((8, 6)) == (8, 6)


def test_spec_chip_bytes_matches_enumeration():
    mesh_shape = {"data": 2, "model": 3}
    aval = jnp.zeros((8, 6), jnp.float32)
    for c in autoshard.spec_candidates(aval, mesh_shape):
        assert autoshard.spec_chip_bytes(
            (8, 6), jnp.float32, c["spec"], mesh_shape
        ) == c["per_chip_bytes"]
    with pytest.raises(ValueError):
        autoshard.spec_chip_bytes((7,), jnp.float32, "data@dim0", mesh_shape)


def test_spec_candidates_bytes_lower_bound_of_compiled_layouts(mesh42):
    """The invariant the preflight pruning depends on (ISSUE 10 satellite):
    for every enumerated spec, the analytic per-chip bytes are a true
    LOWER bound of what the compiled admission charges for that executed
    layout (max of the analytic shard division and XLA's own
    memory_analysis, exactly as plan_program's mesh mode charges)."""
    import jax

    shapes = [(64, 48), (32, 8, 6)]
    for shape in shapes:
        aval = jnp.zeros(shape, jnp.float32)
        for c in autoshard.spec_candidates(aval, dict(mesh42.shape)):
            sharding = autoshard.spec_sharding(c["spec"], mesh42, len(shape))
            s = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
            compiled = jax.jit(lambda a: a * 2.0).lower(s).compile()
            ma = compiled.memory_analysis()
            charged = max(
                kmem.shard_bytes(s), int(ma.argument_size_in_bytes)
            )
            assert c["per_chip_bytes"] <= charged, (
                shape, c, charged, int(ma.argument_size_in_bytes),
            )


def test_spec_tag_compact():
    assert autoshard.spec_tag(None) == "default"
    assert autoshard.spec_tag(
        {"models": "replicated", "labels": "model@dim1"}
    ) == "labels=model@dim1,models=rep"


# -- the zero-cost batch preflight --------------------------------------------


def test_plan_bytes_admits_and_denies_analytically():
    ok = kmem.plan_bytes(
        "t", argument_bytes=100, temp_bytes=50, budget=1000
    )
    assert ok.admitted and not ok.analyzed  # no compile happened
    deny = kmem.plan_bytes("t", argument_bytes=2000, budget=1000)
    assert not deny.admitted
    assert "DENIED" in deny.reason
    assert deny.total_bytes == 2000


def test_plan_bytes_without_budget_skips_admission():
    plan = kmem.plan_bytes("t", argument_bytes=1 << 50, budget=None)
    assert plan.admitted
    assert "skipped" in plan.reason


def test_plan_batch_turns_planner_crash_into_deny():
    out = kmem.plan_batch([
        ("good", lambda: kmem.plan_bytes("good", argument_bytes=1, budget=10)),
        ("bad", lambda: (_ for _ in ()).throw(RuntimeError("boom"))),
    ])
    assert out["good"].admitted
    assert not out["bad"].admitted
    assert "boom" in out["bad"].reason


# -- fingerprints and the plan-outcome log ------------------------------------


def test_fingerprint_stable_and_shape_sensitive():
    a = autoshard.fingerprint("bcd", 100, 10, "f32")
    assert a == autoshard.fingerprint("bcd", 100, 10, "f32")
    assert a != autoshard.fingerprint("bcd", 200, 10, "f32")
    assert len(a) == 16


def _log_record(fp, cand, predicted, measured, outcome="ok"):
    return {
        "fingerprint": fp, "label": "t", "candidate": cand,
        "predicted_seconds": predicted, "measured_seconds": measured,
        "outcome": outcome, "devices": "cpu x1", "ts": 0.0,
    }


def test_outcome_log_roundtrip_and_calibration(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        fp = "f" * 16
        for _ in range(autoshard.MIN_TRAIN - 1):
            autoshard.append_outcome(_log_record(fp, "a", 1.0, 3.0))
        autoshard.clear_outcome_cache()
        # Below MIN_TRAIN: the analytic prior stands (factor 1.0).
        factor, n = autoshard.calibration(fp, "a")
        assert (factor, n) == (1.0, autoshard.MIN_TRAIN - 1)
        autoshard.append_outcome(_log_record(fp, "a", 1.0, 3.0))
        autoshard.clear_outcome_cache()
        factor, n = autoshard.calibration(fp, "a")
        assert n == autoshard.MIN_TRAIN
        assert factor == pytest.approx(3.0)
        # OOM outcomes never train the ratio; a torn tail line is skipped.
        autoshard.append_outcome(_log_record(fp, "a", 1.0, 9.0, outcome="oom"))
        with open(path, "a") as f:
            f.write('{"torn": ')
        autoshard.clear_outcome_cache()
        assert autoshard.calibration(fp, "a")[0] == pytest.approx(3.0)
    finally:
        autoshard.clear_outcome_cache()


def test_outcome_log_disabled_by_env(monkeypatch):
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, "off")
    assert autoshard.plan_log_path() is None
    autoshard.append_outcome({"x": 1})  # must be a no-op, not a crash
    assert autoshard.load_outcomes() == []


def test_outcome_log_read_once_per_process(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        assert autoshard.load_outcomes() == []
        # Outcomes appended DURING the process train the NEXT process: the
        # cached (empty) read stands, so a ranking can never flip between
        # a baseline and a comparison fit mid-process.
        autoshard.append_outcome(_log_record("a" * 16, "a", 1.0, 2.0))
        assert autoshard.load_outcomes() == []
        autoshard.clear_outcome_cache()
        assert len(autoshard.load_outcomes()) == 1
    finally:
        autoshard.clear_outcome_cache()


# -- search: prune, score, rank -----------------------------------------------


def _mk_cand(name, prior, dispatches, floor=False, hand=True, arg_bytes=0):
    def run(_plan, name=name):
        return f"{name}:ran"

    return autoshard.Candidate(
        name, "fused",
        plan=lambda name=name: kmem.MemoryPlan(
            label=name, admitted=True, reason="test"
        ),
        run=run,
        hints={"dispatches": dispatches, "arg_bytes": arg_bytes},
        prior_rank=prior, floor=floor, hand=hand,
    )


_FP = "0123456789abcdef"


def _search(cands, budget=kmem._UNSET):
    # Fixed CostModel: device-independent predicted seconds (1 ms per
    # dispatch), so the ranking assertions hold on any test platform.
    return autoshard.search(
        "t", cands, fingerprint=_FP, budget=budget, model=kopt.CostModel()
    )


def test_untrained_search_keeps_hand_order_within_margin():
    # b's analytic prior is ~1.4x better than a's — inside the 4x cold
    # margin, so the proven hand order stands (the bit-identical bar).
    plan = _search([_mk_cand("a", 0, 10), _mk_cand("b", 1, 7)])
    assert plan.ranking == ["a", "b"]
    assert not plan.trained
    assert plan.margin == autoshard.UNTRAINED_MARGIN


def test_untrained_search_reorders_on_decisive_analytic_advantage():
    # c is 10x faster analytically — clears the cold margin.
    plan = _search([_mk_cand("a", 0, 10), _mk_cand("c", 1, 1)])
    assert plan.ranking == ["c", "a"]


def test_margin_is_relative_not_bucketed():
    # 17 vs 15 dispatches: a 1.13x gap that straddles a power-of-4
    # boundary (0.017s vs 0.015s around 4^-3) — absolute log buckets
    # would split them and reorder; the relative margin must not.
    plan = _search([_mk_cand("a", 0, 17), _mk_cand("b", 1, 15)])
    assert plan.ranking == ["a", "b"]


def test_calibration_falls_back_to_program_median(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        fp = "e" * 16
        for _ in range(autoshard.MIN_TRAIN):
            autoshard.append_outcome(_log_record(fp, "a", 1.0, 3.0))
        autoshard.clear_outcome_cache()
        # "b" never ran: it inherits the PROGRAM-level median factor but
        # reports 0 direct samples (the pooled fallback must not count as
        # trained-ness for the tight margin).
        factor, n = autoshard.calibration(fp, "b")
        assert n == 0
        assert factor == pytest.approx(3.0)
    finally:
        autoshard.clear_outcome_cache()


def test_one_sided_training_cannot_flip_toward_unmeasured_plan(
    tmp_path, monkeypatch
):
    # Equal analytic priors; the chosen plan "a" trains to a 5x honest
    # slowdown while "b" never ran.  The program-median fallback gives
    # "b" the SAME constant factor, so the proven hand order stands —
    # one-sided measurements must never hand the ranking to whatever
    # never ran.
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        for _ in range(autoshard.MIN_TRAIN):
            autoshard.append_outcome(_log_record(_FP, "a", 0.01, 0.05))
        autoshard.clear_outcome_cache()
        plan = _search([_mk_cand("a", 0, 10), _mk_cand("b", 1, 10)])
        assert plan.ranking == ["a", "b"]
        assert not plan.trained  # "b" has no DIRECT measurements
        assert plan.candidate("b").calibration == pytest.approx(5.0)
    finally:
        autoshard.clear_outcome_cache()


def test_floor_pinned_last_regardless_of_score():
    plan = _search([
        _mk_cand("a", 0, 10),
        _mk_cand("cheap_floor", 1, 1, floor=True),
    ])
    assert plan.ranking == ["a", "cheap_floor"]
    rec = plan.candidate("cheap_floor")
    assert "floor" in rec.reason


def test_pruned_hand_candidate_stays_in_execution_order():
    # The over-budget hand candidate is denied for free by the analytic
    # preflight but keeps its hand position in the walk, so the ladder
    # records the denial exactly where the hand contract puts it.
    plan = _search(
        [
            _mk_cand("big", 0, 1, arg_bytes=10_000),
            _mk_cand("small", 1, 10),
        ],
        budget=1000,
    )
    assert plan.ranking == ["big", "small"]
    big = plan.candidate("big")
    assert big.pruned and big.outcome == "denied"
    assert "DENIED" in big.reason
    assert "big" in plan.analytic_plans  # cached deny, never re-planned


def test_pruned_extra_candidate_dropped_from_ranking():
    plan = _search(
        [
            _mk_cand("hand", 0, 10),
            _mk_cand("extra", 1, 1, hand=False, arg_bytes=10_000),
        ],
        budget=1000,
    )
    assert plan.ranking == ["hand"]
    # ...but the table still shows why the enumerated candidate lost.
    extra = plan.candidate("extra")
    assert extra.pruned and extra.outcome == "denied"


def test_search_deterministic_same_fingerprint_same_ranking():
    cands = lambda: [  # noqa: E731
        _mk_cand("a", 0, 10), _mk_cand("b", 1, 7), _mk_cand("c", 2, 2),
        _mk_cand("floor", 3, 30, floor=True),
    ]
    a, b = _search(cands()), _search(cands())
    assert a.ranking == b.ranking
    assert a.fingerprint == b.fingerprint
    assert [c.record() for c in a.candidates] == [
        c.record() for c in b.candidates
    ]


def test_trained_calibration_reorders_past_margin(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        # Equal analytic priors; measurements say b is 100x faster.  Once
        # every survivor is calibrated the margin tightens to
        # TRAINED_MARGIN and b takes the head.
        for _ in range(autoshard.MIN_TRAIN):
            autoshard.append_outcome(_log_record(_FP, "a", 0.01, 0.01))
            autoshard.append_outcome(_log_record(_FP, "b", 0.01, 0.0001))
        autoshard.clear_outcome_cache()
        plan = _search([_mk_cand("a", 0, 10), _mk_cand("b", 1, 10)])
        assert plan.trained
        assert plan.margin == autoshard.TRAINED_MARGIN
        assert plan.ranking == ["b", "a"]
        rec = plan.candidate("b")
        assert rec.samples == autoshard.MIN_TRAIN
        assert rec.calibration == pytest.approx(0.01)
    finally:
        autoshard.clear_outcome_cache()


# -- run_search: the ranked execution contract --------------------------------


def test_run_search_hand_mode_walks_hand_ladder_without_placement():
    report = kmem.FitReport(label="t")
    out = autoshard.run_search(
        "t",
        [_mk_cand("a", 0, 10), _mk_cand("x", 1, 1, hand=False)],
        report, fingerprint=_FP, plan=False,
    )
    assert out == "a:ran"
    assert report.placement is None  # the hand ladder leaves no search


def test_run_search_executes_ranked_head_and_records_placement():
    report = kmem.FitReport(label="t")
    out = autoshard.run_search(
        "t", [_mk_cand("a", 0, 10), _mk_cand("c", 1, 1)],
        report, fingerprint=_FP, plan=True,
        model=kopt.CostModel(),
    )
    assert out == "c:ran"  # decisive analytic advantage took the head
    assert report.chosen == "c"
    p = report.placement
    assert p["chosen"] == "c"
    assert p["ranking"][0] == "c"
    chosen = [c for c in p["candidates"] if c["name"] == "c"][0]
    assert chosen["outcome"] == "ok"
    assert chosen["measured_seconds"] is not None


def test_run_search_forced_ranking_keeps_floor_last():
    report = kmem.FitReport(label="t")
    out = autoshard.run_search(
        "t",
        [
            _mk_cand("a", 0, 10),
            _mk_cand("b", 1, 10),
            _mk_cand("floor", 2, 10, floor=True),
        ],
        report, fingerprint=_FP, plan=["floor", "b"],
        model=kopt.CostModel(),
    )
    # The override names the floor first, but the floor is the backstop:
    # it stays pinned last and the first non-floor named plan runs.
    assert out == "b:ran"
    assert report.placement["ranking"] == ["b", "a", "floor"]


def test_run_search_rejects_bad_plan_arg():
    with pytest.raises(TypeError):
        autoshard.run_search(
            "t", [_mk_cand("a", 0, 1)],
            kmem.FitReport(label="t"), fingerprint=_FP, plan=42,
        )


def test_run_search_runtime_oom_steps_down_ranked_list_counted():
    from keystone_tpu.core.resilience import counters

    calls = {"a": 0}

    def dying_run(_plan):
        calls["a"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    top = autoshard.Candidate(
        "a", "fused",
        plan=lambda: kmem.MemoryPlan(label="a", admitted=True, reason="test"),
        run=dying_run, hints={"dispatches": 1}, prior_rank=0,
    )
    report = kmem.FitReport(label="t")
    before = counters.get("autoshard_stepdown")
    out = autoshard.run_search(
        "t", [top, _mk_cand("b", 1, 10)], report,
        fingerprint=_FP, plan=True, model=kopt.CostModel(),
    )
    assert out == "b:ran"
    assert calls["a"] == 1
    assert report.chosen == "b"
    assert "a" in report.oom_retries
    assert counters.get("autoshard_stepdown") - before >= 1
    p = report.placement
    assert [c for c in p["candidates"] if c["name"] == "a"][0]["outcome"] == (
        "oom"
    )


def test_run_search_typed_failure_not_recorded_as_oom():
    # A non-OOM failure propagates (run_ladder's contract) and the audit
    # trail must say "error", not fabricate a memory misprediction.
    def dying_run(_plan):
        raise ValueError("bad data, not memory")

    top = autoshard.Candidate(
        "a", "fused",
        plan=lambda: kmem.MemoryPlan(label="a", admitted=True, reason="test"),
        run=dying_run, hints={"dispatches": 1}, prior_rank=0,
    )
    report = kmem.FitReport(label="t")
    with pytest.raises(ValueError):
        autoshard.run_search(
            "t", [top], report, fingerprint=_FP, plan=True,
            model=kopt.CostModel(),
        )
    rec = [c for c in report.placement["candidates"] if c["name"] == "a"][0]
    assert rec["outcome"] == "error"


# -- solver-level integration -------------------------------------------------


def _small_problem(rng, n=256, d=128, k=4):
    x = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
    y = jnp.asarray(
        2.0 * np.eye(k, dtype=np.float32)[rng.integers(0, k, n)] - 1.0
    )
    return x, y


def test_fit_searched_bit_identical_to_hand_ladder(rng):
    x, y = _small_problem(rng)
    hand = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0).fit(
        x, y, plan=False
    )
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
    searched = est.fit(x, y, plan=True)
    np.testing.assert_array_equal(np.asarray(hand.b), np.asarray(searched.b))
    for a, b in zip(hand.xs, searched.xs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p = est.last_fit_report.placement
    assert p is not None
    assert p["chosen"] == est.last_fit_report.chosen
    assert p["ranking"], p
    # The searched table carries a scored or denied rationale per row.
    assert all(c["reason"] for c in p["candidates"])


def test_fit_searched_plan_deterministic_under_fixed_devices(rng):
    x, y = _small_problem(rng)

    def one():
        est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
        est.fit(x, y, plan=True)
        return est.last_fit_report.placement

    a, b = one(), one()
    assert a["fingerprint"] == b["fingerprint"]
    assert a["ranking"] == b["ranking"]
    assert [c["name"] for c in a["candidates"]] == [
        c["name"] for c in b["candidates"]
    ]


def test_fit_plan_replay_accepts_placement_plan_and_name_list(rng):
    x, y = _small_problem(rng)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
    base = est.fit(x, y, plan=True)
    prev = est.last_fit_report.placement

    est2 = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
    replay = est2.fit(x, y, plan=list(prev["ranking"]))
    assert est2.last_fit_report.placement["ranking"] == prev["ranking"]
    np.testing.assert_array_equal(np.asarray(base.b), np.asarray(replay.b))


def test_fit_mesh_search_enumerates_factorizations_deterministically(rng):
    import jax

    n_dev = len(jax.devices())
    if n_dev < 4:
        pytest.skip("needs >= 4 devices (conftest forces 8 CPU devices)")
    mesh = make_mesh(data=n_dev // 2, model=2)
    x, y = _small_problem(rng, n=256, d=128, k=4)

    def one():
        est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
        est.fit(x, y, plan=True)
        return est.last_fit_report

    rep = one()
    p = rep.placement
    # Every (data, model) factorization of the device set is a candidate,
    # plus the single-device floor.
    meshes = {
        f"{c['mesh']['data']}x{c['mesh']['model']}"
        for c in p["candidates"] if c["mesh"]
    }
    assert meshes == {
        f"{d}x{m}" for d, m in enumerate_mesh_shapes(n_dev)
    }
    assert p["ranking"][-1] == "single_device"  # the floor stays last
    # Determinism under the fixed device set: same fingerprint, same
    # ranking, run to run.
    rep2 = one()
    assert rep2.placement["fingerprint"] == p["fingerprint"]
    assert rep2.placement["ranking"] == p["ranking"]


def test_fit_report_record_carries_placement(rng):
    x, y = _small_problem(rng)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
    est.fit(x, y, plan=True)
    rec = est.last_fit_report.record()
    assert rec["placement"] is not None
    json.dumps(rec)  # the whole audit trail must stay JSON-able


# -- executed sharding specs (ISSUE 10) ---------------------------------------


def test_mesh_search_enumerates_spec_candidates(rng):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (conftest forces 8 CPU devices)")
    mesh = make_mesh(data=len(jax.devices()) // 2, model=2)
    x, y = _small_problem(rng, n=256, d=128, k=4)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
    est.fit(x, y, plan=True)
    p = est.last_fit_report.placement
    spec_cands = [c for c in p["candidates"] if c.get("specs")]
    assert spec_cands, "no spec-assignment candidates enumerated"
    # the advertised layouts include the wide-class one on the hand mesh
    tags = {(str(c["mesh"]), str(c["specs"])) for c in spec_cands}
    assert any("model@dim1" in t for _m, t in tags)
    # spec candidates are extras: the untrained head stays the hand rung
    # (default layout), so the search is bit-compatible cold
    head = [c for c in p["candidates"] if c["name"] == p["ranking"][0]][0]
    assert head["specs"] is None
    # every candidate row carries a calibration source for the audit trail
    assert all(
        c["calibration_source"] in ("direct", "model", "pooled", "none")
        for c in p["candidates"] if not c["pruned"]
    )


def test_forced_spec_plan_executes_layout_bit_identical(rng):
    """A spec-assignment candidate EXECUTES its NamedSharding layout (not
    just byte accounting) and, on the same mesh shape, reproduces the
    default layout's model bit-for-bit — layout changes placement, never
    results."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = make_mesh(data=len(jax.devices()) // 2, model=2)
    x, y = _small_problem(rng, n=256, d=128, k=4)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
    base = est.fit(x, y, plan=True)
    p = est.last_fit_report.placement
    head_mesh = [
        c for c in p["candidates"] if c["name"] == p["ranking"][0]
    ][0]["mesh"]
    spec_names = [
        c["name"] for c in p["candidates"]
        if c.get("specs") and c["mesh"] == head_mesh
    ]
    assert spec_names
    for name in spec_names:
        est2 = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
        replay = est2.fit(x, y, plan=[name])
        assert est2.last_fit_report.chosen == name
        chosen = [
            c for c in est2.last_fit_report.placement["candidates"]
            if c["name"] == name
        ][0]
        assert chosen["outcome"] == "ok" and chosen["specs"]
        np.testing.assert_array_equal(
            np.asarray(base.b), np.asarray(replay.b)
        )
        for a, b in zip(base.xs, replay.xs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_searched_mesh_pads_labels_to_caller_padded_features(rng, mesh42):
    """A caller under a 4x2 mesh hands the solver features already
    row-padded for ITS data axis (204 rows for 203 labels, as the mesh
    workloads do); a searched candidate with another data axis (1x8 pads
    neither operand) must still give the design matrix and the labels the
    same rows — and the hand order's model."""
    from keystone_tpu.parallel.mesh import padded_shard_rows

    x, y = _small_problem(rng, n=203, d=128, k=4)
    x_pad, nvalid = padded_shard_rows(np.asarray(x), mesh42)
    assert x_pad.shape[0] == 204 and nvalid == 203

    def fit(plan):
        est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh42)
        model = est.fit(x_pad, y, nvalid=nvalid, plan=plan)
        return model, est.last_fit_report

    hand, hand_report = fit(False)
    assert hand_report.chosen == "fused[mesh 4x2]"
    searched, report = fit(["fused[mesh 1x8]"])
    assert report.chosen == "fused[mesh 1x8]"
    np.testing.assert_allclose(
        np.asarray(searched.b), np.asarray(hand.b), rtol=1e-4, atol=1e-5
    )
    for a, b in zip(hand.xs, searched.xs):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-5
        )


def test_bwls_mesh_search_spec_candidates_execute(rng):
    import jax

    from keystone_tpu.solvers.weighted import (
        BlockWeightedLeastSquaresEstimator,
    )

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = make_mesh(data=len(jax.devices()) // 2, model=2)
    x, _ = _small_problem(rng, n=256, d=128, k=4)
    y = jnp.asarray(
        2.0 * np.eye(8, dtype=np.float32)[rng.integers(0, 8, 256)] - 1.0
    )
    est = BlockWeightedLeastSquaresEstimator(64, 1, 0.5, 0.5, mesh=mesh)
    base = est.fit(x, y, plan=True)
    p = est.last_fit_report.placement
    head_mesh = [
        c for c in p["candidates"] if c["name"] == p["ranking"][0]
    ][0]["mesh"]
    wide = [
        c["name"] for c in p["candidates"]
        if c.get("specs") == {"labels": "model@dim1"}
        and c["mesh"] == head_mesh
    ]
    assert wide, "wide-class (model-axis-sharded labels) candidate missing"
    est2 = BlockWeightedLeastSquaresEstimator(64, 1, 0.5, 0.5, mesh=mesh)
    replay = est2.fit(x, y, plan=[wide[0]])
    assert est2.last_fit_report.chosen == wide[0]
    for a, b in zip(base.xs, replay.xs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_specs_env_disables_spec_dimension(rng, monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    monkeypatch.setenv(autoshard.SPECS_ENV, "0")
    mesh = make_mesh(data=len(jax.devices()) // 2, model=2)
    x, y = _small_problem(rng, n=256, d=128, k=4)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
    est.fit(x, y, plan=True)
    p = est.last_fit_report.placement
    assert not any(c.get("specs") for c in p["candidates"])


# -- the cross-program calibration model (ISSUE 10) ----------------------------


def _feat(kind="fused", bytes_=1e6, flops=1e9, data=1, model=1):
    return autoshard.plan_features(
        kind, {"data": data, "model": model},
        {"arg_bytes": bytes_, "flops": flops, "dispatches": 1},
    )


def test_calibration_model_learns_constant_ratio():
    from keystone_tpu.core import optimize as kopt

    rows = [
        (f"fp{i}", _feat(bytes_=10.0 ** (5 + i % 3)), 3.0) for i in range(10)
    ]
    model = kopt.CalibrationModel.fit_rows(rows)
    assert model is not None
    assert model.n_programs == 10
    # a constant measured/prior ratio is learned to ~3x for any features
    assert model.predict_factor(_feat(bytes_=2e6)) == pytest.approx(
        3.0, rel=0.05
    )


def test_calibration_model_factor_clipped():
    from keystone_tpu.core import optimize as kopt

    rows = [("a", _feat(bytes_=1e5), 1e9), ("b", _feat(bytes_=1e6), 1e9)]
    model = kopt.CalibrationModel.fit_rows(rows)
    assert model.predict_factor(_feat(bytes_=1e7)) <= 32.0


def test_calibrate_uses_cross_program_model_for_unseen_program(
    tmp_path, monkeypatch
):
    """Outcomes logged for OTHER programs train a model that transfers to
    a fingerprint the log never saw — the source says 'model', the direct
    sample count stays 0 (so the margin stays cold: conservative rules
    preserved)."""
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        for i in range(10):
            rec = _log_record(f"{i:016x}", "fused", 1.0, 4.0)
            rec["raw_seconds"] = 1.0
            rec["features"] = _feat(bytes_=10.0 ** (5 + i % 3))
            autoshard.append_outcome(rec)
        autoshard.clear_outcome_cache()
        factor, n, source = autoshard.calibrate(
            "f" * 16, "fused", features=_feat(bytes_=2e6)
        )
        assert source == "model"
        assert n == 0
        assert factor == pytest.approx(4.0, rel=0.1)
        # featureless lookups keep the old direct->pooled->1.0 ladder
        assert autoshard.calibration("f" * 16, "fused") == (1.0, 0)
    finally:
        autoshard.clear_outcome_cache()


def test_empty_log_keeps_untrained_hand_order_with_model_path(
    tmp_path, monkeypatch
):
    # The acceptance bar: with an EMPTY plan log the searched ranking
    # (specs included) reproduces the hand order — no model, no pooled
    # median, factor 1.0 everywhere.
    monkeypatch.setenv(
        autoshard.PLAN_LOG_ENV, str(tmp_path / "empty.jsonl")
    )
    autoshard.clear_outcome_cache()
    try:
        plan = _search([_mk_cand("a", 0, 10), _mk_cand("b", 1, 7)])
        assert plan.ranking == ["a", "b"]
        assert all(
            c.calibration == 1.0 and c.calibration_source == "none"
            for c in plan.candidates
        )
    finally:
        autoshard.clear_outcome_cache()


# -- plan-log cap + compaction (ISSUE 10 satellite) ----------------------------


def test_plan_log_cap_compacts_oldest_first(tmp_path, monkeypatch):
    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    monkeypatch.setenv(autoshard.PLAN_LOG_MAX_ENV, "50")
    autoshard.clear_outcome_cache()
    try:
        # Pre-seed an OVERSIZED log: an old fingerprint with constant
        # ratio 2.0 spread over many stale records, then a hot one.
        with open(path, "w") as f:
            for i in range(400):
                f.write(json.dumps(_log_record("old" + "0" * 13, "fused",
                                               1.0, 2.0)) + "\n")
            for i in range(40):
                f.write(json.dumps(_log_record("hot" + "0" * 13, "fused",
                                               1.0, 5.0)) + "\n")
        autoshard.append_outcome(_log_record("hot" + "0" * 13, "fused",
                                             1.0, 5.0))
        with open(path) as f:
            lines = [ln for ln in f if ln.strip()]
        assert len(lines) <= 51  # cap + the appended record
        autoshard.clear_outcome_cache()
        # medians stable through compaction (constant per-pair ratios)
        assert autoshard.calibration("old" + "0" * 13, "fused")[0] == (
            pytest.approx(2.0)
        )
        assert autoshard.calibration("hot" + "0" * 13, "fused")[0] == (
            pytest.approx(5.0)
        )
    finally:
        autoshard.clear_outcome_cache()


def test_plan_log_cap_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(autoshard.PLAN_LOG_MAX_ENV, "off")
    assert autoshard.plan_log_max() is None
    monkeypatch.delenv(autoshard.PLAN_LOG_MAX_ENV)
    assert autoshard.plan_log_max() == 20_000


def test_plan_log_cap_malformed_env_never_crashes_append(
    tmp_path, monkeypatch
):
    """Telemetry must never crash a solve: a malformed or negative
    KEYSTONE_PLAN_LOG_MAX raises at plan_log_max() (fail-fast grammar)
    but append_outcome degrades counted — and never wipes the log."""
    from keystone_tpu.core.resilience import counters

    path = str(tmp_path / "plans.jsonl")
    monkeypatch.setenv(autoshard.PLAN_LOG_ENV, path)
    autoshard.clear_outcome_cache()
    try:
        # seed one good record under a valid cap
        autoshard.append_outcome(_log_record("a" * 16, "fused", 1.0, 2.0))
        seeded = open(path).read()
        assert seeded
        for bad in ("unlimited", "-5"):
            monkeypatch.setenv(autoshard.PLAN_LOG_MAX_ENV, bad)
            with pytest.raises(ValueError):
                autoshard.plan_log_max()
            before = counters.get("plan_log_write_failed")
            autoshard.append_outcome(_log_record("a" * 16, "fused", 1.0, 2.0))
            assert counters.get("plan_log_write_failed") - before == 1
        # the seeded record survived — no negative-cap wipe, no torn write
        assert open(path).read() == seeded
    finally:
        autoshard.clear_outcome_cache()


def test_compact_log_tiny_cap_trims_never_wipes(tmp_path):
    # cap below the per-pair keep tail: a single-pair log must TRIM to
    # the watermark, not evict its only pair (wiping all history).
    path = str(tmp_path / "plans.jsonl")
    with open(path, "w") as f:
        for i in range(30):
            f.write(json.dumps(_log_record("a" * 16, "fused", 1.0,
                                           float(i))) + "\n")
    n = autoshard.compact_log(path, 5)
    assert 1 <= n <= 5
    kept = [json.loads(ln) for ln in open(path)]
    assert len(kept) == n
    # survivors are the NEWEST records
    assert kept[-1]["measured_seconds"] == 29.0


def test_compact_log_keeps_newest_per_pair(tmp_path):
    path = str(tmp_path / "plans.jsonl")
    with open(path, "w") as f:
        for i in range(30):
            r = _log_record("a" * 16, "fused", 1.0, float(i))
            f.write(json.dumps(r) + "\n")
    n = autoshard.compact_log(path, 10)
    assert n <= 10
    kept = [json.loads(ln) for ln in open(path)]
    # oldest-first: the survivors are the NEWEST records
    assert [r["measured_seconds"] for r in kept] == list(
        range(30 - len(kept), 30)
    )


# -- mesh-enumeration memoization (ISSUE 10 satellite) -------------------------


def test_enumerate_meshes_memoized_per_device_tuple():
    import jax

    devices = jax.devices()
    a = enumerate_meshes(devices)
    b = enumerate_meshes(devices)
    # same Mesh OBJECTS back (the construction happened once), but a
    # fresh list each call (callers may mutate their copy)
    assert a is not b
    assert all(x is y for x, y in zip(a, b))


def test_enumerate_mesh_shapes_memoized_returns_fresh_list():
    a = enumerate_mesh_shapes(8)
    b = enumerate_mesh_shapes(8)
    assert a == b and a is not b
    a.append(("junk", 0))
    assert enumerate_mesh_shapes(8) == b  # cache not polluted


# -- tools/plan_view.py -------------------------------------------------------


def test_plan_view_renders_placement_from_results_json(rng, tmp_path):
    x, y = _small_problem(rng)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0)
    est.fit(x, y, plan=True)
    doc = {"nested": {"solver": est.last_fit_report.record()}}
    path = tmp_path / "results.json"
    path.write_text(json.dumps(doc))
    out = plan_view.summarize(str(path))
    assert "bcd_fit" in out
    assert "chosen:" in out
    for name in est.last_fit_report.placement["ranking"]:
        assert name in out


def test_plan_view_finds_all_embedded_plans():
    plan = {
        "label": "t", "fingerprint": "f", "devices": "cpu x1",
        "ranking": ["a"], "candidates": [], "chosen": None,
    }
    doc = {"a": [plan, {"b": plan}], "c": plan}
    assert len(plan_view.find_plans(doc)) == 3


def test_plan_view_renders_spec_column(rng, tmp_path):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = make_mesh(data=len(jax.devices()) // 2, model=2)
    x, y = _small_problem(rng, n=256, d=128, k=4)
    est = BlockLeastSquaresEstimator(64, num_iter=1, lam=1.0, mesh=mesh)
    est.fit(x, y, plan=True)
    doc = {"solver": est.last_fit_report.record()}
    path = tmp_path / "results.json"
    path.write_text(json.dumps(doc))
    out = plan_view.summarize(str(path))
    assert "specs" in out  # the spec column header
    assert "labels=model@dim1" in out  # a spec assignment rendered
    assert "default" in out  # hand rungs show the default layout


def test_plan_view_summarizes_outcome_log(tmp_path):
    path = tmp_path / "plans.jsonl"
    rows = [
        _log_record("ab" * 8, "fused", 1.0, 2.0),
        _log_record("ab" * 8, "fused", 1.0, 4.0),
        _log_record("ab" * 8, "fused", 1.0, 0.0, outcome="oom"),
        _log_record("cd" * 8, "stepwise", 1.0, 1.0),
    ]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    out = plan_view.summarize(str(path))
    assert "fused" in out and "stepwise" in out
    filtered = plan_view.summarize(str(path), fingerprint="cd" * 8)
    assert "stepwise" in filtered and "fused" not in filtered
