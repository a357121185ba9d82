"""Memory-resilience suite: HBM admission control (core.memory), the
solver degradation ladder, donation ownership rules, and OOM-retry —
driven by a simulated HBM budget (``KEYSTONE_HBM_BUDGET``) and the
RESOURCE_EXHAUSTED injector in tests/faults.py.  All tier-1 fast.

The ladder-selection tests derive their budget thresholds from the
estimator's OWN preflight report (fit once with a generous budget, read
the per-tier totals, then refit with a budget pinched between two tiers)
so they assert behavior, not hard-coded byte counts.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from faults import oom_faults, resource_exhausted_error

from keystone_tpu.core import memory as kmem
from keystone_tpu.core.resilience import counters
from keystone_tpu.solvers import block as block_mod
from keystone_tpu.solvers import weighted as weighted_mod
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator
from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator


# Wide-and-short problem: the fused program's footprint (args + analytic
# temp floor) strictly dominates the stepwise block program's, which
# dominates the host-staged block program's — so a budget can select each
# tier.  (Tall-skinny shapes invert fused vs stepwise on CPU because the
# residual appears in both the block program's args and outputs.)
N, D, K, BS = 32, 1024, 16, 64


def _problem(rng):
    a = rng.normal(size=(N, D)).astype(np.float32)
    b = rng.normal(size=(N, K)).astype(np.float32)
    return a, b


def _fit(a, b, **kw):
    est = BlockLeastSquaresEstimator(BS, num_iter=2, lam=0.5)
    model = est.fit(a, b, **kw)
    return est, np.asarray(model(jnp.asarray(a)))


class TestBudget:
    def test_parse_bytes(self):
        assert kmem.parse_bytes("512M") == 512 * 2**20
        assert kmem.parse_bytes("16G") == 16 * 2**30
        assert kmem.parse_bytes("1.5GB") == int(1.5 * 2**30)
        assert kmem.parse_bytes("2KiB") == 2048
        assert kmem.parse_bytes(4096) == 4096
        with pytest.raises(ValueError, match="cannot parse"):
            kmem.parse_bytes("a lot")

    def test_env_budget_wins(self, monkeypatch):
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "3G")
        assert kmem.hbm_budget() == 3 * 2**30

    def test_no_budget_on_cpu(self, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        assert kmem.hbm_budget() is None  # CPU devices expose no memory_stats


class TestPlanProgram:
    def test_no_budget_skips_analysis(self, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        plan = kmem.plan_program(
            jax.jit(lambda x: x @ x.T),
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            label="skip",
        )
        assert plan.admitted and not plan.analyzed
        assert "admission skipped" in plan.reason

    def test_breakdown_from_memory_analysis(self):
        plan = kmem.plan_program(
            jax.jit(lambda x: x @ x.T),
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            label="mm",
            require_analysis=True,
        )
        assert plan.analyzed and plan.admitted  # no budget: analyzed, allowed
        assert plan.argument_bytes == 64 * 64 * 4
        assert plan.output_bytes == 64 * 64 * 4
        bd = plan.breakdown()
        assert set(bd) >= {
            "admitted", "argument_gb", "temp_gb", "output_gb", "total_gb",
        }
        assert plan.compiled is not None

    def test_denial_counted(self, monkeypatch):
        before = counters.get("hbm_preflight_denied")
        plan = kmem.plan_program(
            jax.jit(lambda x: x @ x.T),
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            label="tiny_budget",
            budget=100,
        )
        assert not plan.admitted and "DENIED" in plan.reason
        assert counters.get("hbm_preflight_denied") == before + 1

    def test_extra_and_floor_bytes_count(self):
        arg = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        base = kmem.plan_program(
            jax.jit(lambda x: x + 1), arg, label="b", require_analysis=True
        )
        plus = kmem.plan_program(
            jax.jit(lambda x: x + 1), arg, label="p",
            require_analysis=True, extra_bytes=10_000, min_temp_bytes=5_000,
        )
        assert plus.total_bytes == base.total_bytes + 10_000 + (
            5_000 - base.temp_bytes
        )


class TestPlanBytes:
    def test_plan_bytes_admits_and_denies_analytically(self):
        ok = kmem.plan_bytes(
            "t", argument_bytes=100, temp_bytes=50, budget=1000
        )
        assert ok.admitted and not ok.analyzed  # no compile happened
        deny = kmem.plan_bytes("t", argument_bytes=2000, budget=1000)
        assert not deny.admitted
        assert "DENIED" in deny.reason
        assert deny.total_bytes == 2000

    def test_plan_bytes_without_budget_skips_admission(self):
        plan = kmem.plan_bytes("t", argument_bytes=1 << 50, budget=None)
        assert plan.admitted
        assert "skipped" in plan.reason


class TestOomDetection:
    def test_injected_oom_is_recognized(self):
        assert kmem.is_oom_error(resource_exhausted_error())

    def test_non_oom_errors_pass_through(self):
        assert not kmem.is_oom_error(ValueError("RESOURCE_EXHAUSTED"))
        assert not kmem.is_oom_error(RuntimeError("shape mismatch"))

    def test_ladder_source_lost_is_not_oom(self):
        # The donate-guard error mentions OOM recovery in prose; it must
        # never be classified as a retryable OOM itself.
        e = kmem.LadderSourceLost(
            "donated — refit with donate=False to keep OOM recovery possible"
        )
        assert not kmem.is_oom_error(e)


class TestResidentCredit:
    def test_live_budget_credits_resident_inputs(self, monkeypatch):
        """A live free-bytes budget already excludes device-resident
        inputs; charging them again would deny fits that actually fit."""
        fn = jax.jit(lambda x: x + 0.0)
        arg = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        nbytes = 64 * 64 * 4
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)  # live
        live = kmem.plan_program(
            fn, arg, label="live", budget=nbytes + 100, resident_bytes=nbytes
        )
        assert live.admitted  # total ~2n, minus n resident -> fits n+100
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")  # capacity override
        cap = kmem.plan_program(
            fn, arg, label="cap", budget=nbytes + 100, resident_bytes=nbytes
        )
        assert not cap.admitted  # capacity budgets charge resident inputs


class TestBcdLadder:
    def _tier_totals(self, rng, monkeypatch):
        """Per-tier planned totals, walked sequentially: tiers are planned
        lazily (a tier is only planned after every better tier was denied),
        so each refit with the previous tier's total minus one as the
        budget exposes the next rung's plan."""
        a, b = _problem(rng)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        est, _ = _fit(a, b)
        totals = {"fused": est.last_fit_report.plans["fused"].total_bytes}
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(totals["fused"] - 1))
        est, _ = _fit(a, b)
        totals["stepwise"] = est.last_fit_report.plans["stepwise"].total_bytes
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(totals["stepwise"] - 1))
        est, _ = _fit(a, b)
        totals["host_staged"] = est.last_fit_report.plans[
            "host_staged"
        ].total_bytes
        return totals

    def test_generous_budget_admits_fused(self, rng, monkeypatch):
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        a, b = _problem(rng)
        est, _ = _fit(a, b)
        rep = est.last_fit_report
        assert rep.chosen == "fused" and not rep.denials
        # Lazy planning: an admitted first tier never plans (or compiles)
        # the tiers below it.
        assert list(rep.plans) == ["fused"]
        # The premise every budget-driven selection below rests on:
        totals = self._tier_totals(rng, monkeypatch)
        assert totals["host_staged"] < totals["stepwise"] < totals["fused"]

    def test_budget_denies_fused_selects_stepwise(self, rng, monkeypatch):
        totals = self._tier_totals(rng, monkeypatch)
        a, b = _problem(rng)
        monkeypatch.setenv(
            kmem.HBM_BUDGET_ENV,
            str((totals["stepwise"] + totals["fused"]) // 2),
        )
        est, _ = _fit(a, b)
        assert est.last_fit_report.chosen == "stepwise"
        assert est.last_fit_report.denials == ["fused"]

    def test_all_device_tiers_denied_selects_host_staged(self, rng, monkeypatch):
        totals = self._tier_totals(rng, monkeypatch)
        a, b = _problem(rng)
        monkeypatch.setenv(
            kmem.HBM_BUDGET_ENV,
            str((totals["host_staged"] + totals["stepwise"]) // 2),
        )
        est, _ = _fit(a, b)
        assert est.last_fit_report.chosen == "host_staged"
        assert est.last_fit_report.denials == ["fused", "stepwise"]

    def test_floor_runs_even_when_denied(self, rng, monkeypatch):
        a, b = _problem(rng)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1000")
        est, preds = _fit(a, b)
        assert est.last_fit_report.chosen == "host_staged"
        assert np.all(np.isfinite(preds))

    def test_ladder_tiers_numerically_identical(self, rng, monkeypatch):
        """On a shape every tier admits, all three tiers are the SAME
        solve: same centering, masking, pad shift, and update order."""
        totals = self._tier_totals(rng, monkeypatch)
        a, b = _problem(rng)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        _, p_fused = _fit(a, b)
        monkeypatch.setenv(
            kmem.HBM_BUDGET_ENV,
            str((totals["stepwise"] + totals["fused"]) // 2),
        )
        est_s, p_step = _fit(a, b)
        assert est_s.last_fit_report.chosen == "stepwise"
        monkeypatch.setenv(
            kmem.HBM_BUDGET_ENV,
            str((totals["host_staged"] + totals["stepwise"]) // 2),
        )
        est_h, p_host = _fit(a, b)
        assert est_h.last_fit_report.chosen == "host_staged"
        np.testing.assert_allclose(p_fused, p_step, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(p_fused, p_host, rtol=1e-5, atol=1e-5)

    def test_oom_retry_steps_down_exactly_one_tier(self, rng, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        a, b = _problem(rng)
        _, p_clean = _fit(a, b)
        before = counters.get("solver_oom_retry")
        with oom_faults(block_mod, "_execute_fused_bcd", failures=1):
            est, p_retry = _fit(a, b)
        rep = est.last_fit_report
        assert rep.oom_retries == ["fused"]
        assert rep.chosen == "stepwise"  # one tier down, not the floor
        assert counters.get("solver_oom_retry") == before + 1
        np.testing.assert_allclose(p_clean, p_retry, rtol=1e-5, atol=1e-5)

    def test_sharded_inputs_without_mesh_fall_back_to_jit(
        self, rng, mesh8, monkeypatch
    ):
        """A mesh-less fit handed row-SHARDED caller arrays while a budget
        is set must not crash on the single-device AOT executable (its
        baked placements reject sharded inputs) — the executor falls back
        to the jitted variant and the result matches the unsharded fit."""
        from keystone_tpu.parallel.mesh import padded_shard_rows

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        a, b = _problem(rng)
        _, p_clean = _fit(a, b)
        a_sh, n = padded_shard_rows(a, mesh8)
        b_sh, _ = padded_shard_rows(b, mesh8)
        est = BlockLeastSquaresEstimator(BS, num_iter=2, lam=0.5)
        model = est.fit(a_sh, b_sh, nvalid=n)
        preds = np.asarray(model(jnp.asarray(a)))
        np.testing.assert_allclose(preds, p_clean, rtol=1e-4, atol=1e-4)

    def test_non_oom_failure_propagates(self, rng, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        a, b = _problem(rng)

        def boom(*args, **kw):
            raise ValueError("not a memory problem")

        monkeypatch.setattr(block_mod, "_execute_fused_bcd", boom)
        with pytest.raises(ValueError, match="not a memory problem"):
            _fit(a, b)


class TestDonation:
    def test_device_passthrough_never_donated(self, rng, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        a, b = _problem(rng)
        a_dev, b_dev = jnp.asarray(a), jnp.asarray(b)
        est, _ = _fit(a_dev, b_dev)
        # The caller's arrays must survive a default fit untouched.
        assert not a_dev.is_deleted() and not b_dev.is_deleted()
        assert float(jnp.sum(a_dev)) == pytest.approx(float(np.sum(a)), rel=1e-5)

    def test_host_inputs_fit_matches_device_fit(self, rng, monkeypatch):
        # Host inputs take the donating fused variant (the device copies
        # are fit-owned); results must equal the non-donating fit.
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        a, b = _problem(rng)
        _, p_host_in = _fit(a, b)
        _, p_dev_in = _fit(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_allclose(p_host_in, p_dev_in, rtol=1e-6, atol=1e-6)

    def test_bwls_donate_true_frees_caller_inputs(self, rng, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        n, d, c = 96, 128, 6
        cls = rng.integers(0, c, n)
        x = (rng.normal(size=(n, d)) + 0.1 * cls[:, None]).astype(np.float32)
        y = (2.0 * np.eye(c)[cls] - 1.0).astype(np.float32)
        ref = BlockWeightedLeastSquaresEstimator(32, 1, 0.1, 0.5).fit(x, y)
        p_ref = np.asarray(ref(jnp.asarray(x)))
        xd, yd = jnp.asarray(x), jnp.asarray(y)
        model = BlockWeightedLeastSquaresEstimator(32, 1, 0.1, 0.5).fit(
            xd, yd, donate=True
        )
        assert xd.is_deleted() and yd.is_deleted()
        np.testing.assert_allclose(
            np.asarray(model(jnp.asarray(x))), p_ref, rtol=1e-6, atol=1e-6
        )


class TestBwlsLadder:
    def _bwls_problem(self, rng):
        n, d, c = 96, 256, 8
        cls = rng.integers(0, c, n)
        x = (rng.normal(size=(n, d)) + 0.1 * cls[:, None]).astype(np.float32)
        y = (2.0 * np.eye(c)[cls] - 1.0).astype(np.float32)
        return x, y

    def _bwls_fit(self, x, y):
        est = BlockWeightedLeastSquaresEstimator(
            32, num_iter=2, lam=0.1, mixture_weight=0.5
        )
        model = est.fit(x, y)
        return est, np.asarray(model(jnp.asarray(x)))

    def test_budget_walks_the_ladder(self, rng, monkeypatch):
        x, y = self._bwls_problem(rng)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        est, p_fused = self._bwls_fit(x, y)
        rep = est.last_fit_report
        assert rep.chosen == "fused"
        f_tot = rep.plans["fused"].total_bytes

        # Tiers plan lazily: pinch the budget below each rung in turn.
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(f_tot - 1))
        est_s, p_step = self._bwls_fit(x, y)
        assert est_s.last_fit_report.chosen == "stepwise"
        assert est_s.last_fit_report.denials == ["fused"]
        s_tot = est_s.last_fit_report.plans["stepwise"].total_bytes
        assert s_tot < f_tot

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(s_tot - 1))
        est_h, p_host = self._bwls_fit(x, y)
        assert est_h.last_fit_report.chosen == "host_staged"
        assert est_h.last_fit_report.denials == ["fused", "stepwise"]
        assert est_h.last_fit_report.plans["host_staged"].total_bytes < s_tot

        np.testing.assert_allclose(p_fused, p_step, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(p_fused, p_host, rtol=1e-5, atol=1e-5)


class TestReportPlumbing:
    def test_report_record_is_jsonable(self, rng, monkeypatch):
        import json

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        a, b = _problem(rng)
        est, _ = _fit(a, b)
        rec = est.last_fit_report.record()
        blob = json.loads(json.dumps(rec))
        assert blob["chosen_tier"] == "fused"
        # Lazy planning: only the considered (admitted-first) tier appears.
        assert set(blob["tiers"]) == {"fused"}
        assert blob["tiers"]["fused"]["admitted"] is True

    def test_mesh_fit_reports_mesh_tier(self, rng, mesh8):
        a = rng.normal(size=(24, 16)).astype(np.float32)
        b = rng.normal(size=(24, 4)).astype(np.float32)
        est = BlockLeastSquaresEstimator(8, num_iter=1, lam=0.1, mesh=mesh8)
        est.fit(a, b)
        assert est.last_fit_report.chosen == "fused[mesh 8x1]"
        assert est.last_fit_report.mesh_shape == {"data": 8, "model": 1}


class TestAotReuse:
    """ROADMAP leftover from PR 2: the degraded stepwise tier must execute
    the preflight's AOT-compiled per-block executable, not recompile it at
    first jit dispatch — asserted via the plan compile-counter AND the jit
    dispatch cache staying untouched."""

    def test_bcd_stepwise_compiles_per_block_program_exactly_once(
        self, rng, monkeypatch
    ):
        a, b = _problem(rng)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        _, p_clean = _fit(a, b)
        est0, _ = _fit(a, b)
        f_tot = est0.last_fit_report.plans["fused"].total_bytes
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(f_tot - 1))

        kmem.clear_plan_cache()
        compiles0 = kmem.compile_count("bcd_stepwise")
        jit_cache0 = block_mod._bcd_block_solve._cache_size()
        est, p_step = _fit(a, b)
        assert est.last_fit_report.chosen == "stepwise"
        # Exactly ONE compile of the per-block solve: the preflight's.
        assert kmem.compile_count("bcd_stepwise") == compiles0 + 1
        # ...and no second compile at jit dispatch on the degraded path.
        assert block_mod._bcd_block_solve._cache_size() == jit_cache0
        np.testing.assert_allclose(p_clean, p_step, rtol=1e-5, atol=1e-5)

        # A refit reuses the cached plan executable: zero new compiles.
        est2, _ = _fit(a, b)
        assert est2.last_fit_report.chosen == "stepwise"
        assert kmem.compile_count("bcd_stepwise") == compiles0 + 1
        assert block_mod._bcd_block_solve._cache_size() == jit_cache0

    def test_bwls_stepwise_reuses_preflight_executable(self, rng, monkeypatch):
        n, d, c = 96, 256, 8
        cls = rng.integers(0, c, n)
        x = (rng.normal(size=(n, d)) + 0.1 * cls[:, None]).astype(np.float32)
        y = (2.0 * np.eye(c)[cls] - 1.0).astype(np.float32)

        def fit():
            est = BlockWeightedLeastSquaresEstimator(
                32, num_iter=2, lam=0.1, mixture_weight=0.5
            )
            model = est.fit(x, y)
            return est, np.asarray(model(jnp.asarray(x)))

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        est0, p_clean = fit()
        f_tot = est0.last_fit_report.plans["fused"].total_bytes
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(f_tot - 1))

        kmem.clear_plan_cache()
        compiles0 = kmem.compile_count("bwls_stepwise")
        jit_cache0 = weighted_mod._class_solves._cache_size()
        est, p_step = fit()
        assert est.last_fit_report.chosen == "stepwise"
        assert kmem.compile_count("bwls_stepwise") == compiles0 + 1
        assert weighted_mod._class_solves._cache_size() == jit_cache0
        np.testing.assert_allclose(p_clean, p_step, rtol=1e-5, atol=1e-5)


class TestMeshAdmission:
    """Per-chip admission math for GSPMD programs on the forced-8-device
    CPU host: per-axis sharded operand division, conservative replicated
    accounting, minimum-free-chip budgets, and the XLA ground-truth
    cross-check."""

    def test_shard_bytes_divides_by_named_axes(self, mesh42):
        full = 64 * 32 * 4
        row = jax.ShapeDtypeStruct(
            (64, 32), jnp.float32,
            sharding=NamedSharding(mesh42, P("data", None)),
        )
        both = jax.ShapeDtypeStruct(
            (64, 32), jnp.float32,
            sharding=NamedSharding(mesh42, P("data", "model")),
        )
        repl = jax.ShapeDtypeStruct(
            (64, 32), jnp.float32, sharding=NamedSharding(mesh42, P())
        )
        bare = jax.ShapeDtypeStruct((64, 32), jnp.float32)
        assert kmem.shard_bytes(row) == full // 4
        assert kmem.shard_bytes(both) == full // 8
        assert kmem.shard_bytes(repl) == full  # replicated charges whole
        assert kmem.shard_bytes(bare) == full  # un-annotated: conservative

    def test_sharded_vs_replicated_operand_accounting(self, mesh42):
        """A row-sharded operand charges its shard; a replicated operand
        charges full size on every chip — and XLA's per-device module
        accounting (plan.reported) agrees exactly on this program."""
        fn = jax.jit(lambda x, y: x @ y)
        x_s = jax.ShapeDtypeStruct(
            (64, 32), jnp.float32,
            sharding=NamedSharding(mesh42, P("data", None)),
        )
        y_s = jax.ShapeDtypeStruct((32, 16), jnp.float32)  # replicated
        plan = kmem.plan_program(
            fn, x_s, y_s, label="mesh_acct", budget=1 << 30, mesh=mesh42
        )
        assert plan.analyzed and plan.admitted
        assert plan.mesh_axes == {"data": 4, "model": 2}
        assert plan.argument_bytes == (64 * 32 * 4) // 4 + 32 * 16 * 4
        assert plan.reported["argument"] == plan.argument_bytes
        assert "per-chip" in plan.reason and "min-free-chip" in plan.reason
        bd = plan.breakdown()
        assert bd["per_chip"] is True and bd["mesh"] == {"data": 4, "model": 2}
        assert "xla_reported_gb" in bd

    def test_min_chip_budget_takes_the_worst_chip(self, mesh42, monkeypatch):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        devices = list(mesh42.devices.flat)
        frees = {d.id: 10**9 for d in devices}
        tight = devices[3]
        frees[tight.id] = 12345
        monkeypatch.setattr(
            kmem, "hbm_budget", lambda device=None: frees[device.id]
        )
        budget, dev = kmem.min_chip_budget(mesh42)
        assert budget == 12345 and dev.id == tight.id

    def test_min_chip_budget_env_override_is_per_chip(self, mesh42, monkeypatch):
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "2G")
        budget, dev = kmem.min_chip_budget(mesh42)
        assert budget == 2 * 2**30 and dev is None

    def test_min_chip_budget_unknowable_chip_skips_admission(
        self, mesh42, monkeypatch
    ):
        monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)
        devices = list(mesh42.devices.flat)
        frees = {d.id: 10**9 for d in devices}
        frees[devices[5].id] = None  # one chip cannot report
        monkeypatch.setattr(
            kmem, "hbm_budget", lambda device=None: frees[device.id]
        )
        assert kmem.min_chip_budget(mesh42) == (None, None)

    def test_bcd_mesh_plan_within_2x_of_memory_analysis(self, rng, mesh42, monkeypatch):
        """Acceptance bar: analytic per-chip bytes for the (data=4,
        model=2) sharded BCD solve within 2x of the compiled SPMD module's
        own ``memory_analysis()`` on the forced-8-device CPU backend."""
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        a = rng.normal(size=(512, 256)).astype(np.float32)
        b = rng.normal(size=(512, 8)).astype(np.float32)
        est = BlockLeastSquaresEstimator(64, num_iter=2, lam=0.5, mesh=mesh42)
        est.fit(a, b)
        rep = est.last_fit_report
        assert rep.chosen == "fused[mesh 4x2]"
        plan = rep.plans["fused[mesh 4x2]"]
        assert plan.analyzed and plan.mesh_axes == {"data": 4, "model": 2}
        truth = plan.reported
        analytic_static = plan.argument_bytes + plan.output_bytes
        truth_static = truth["argument"] + truth["output"]
        assert truth_static / 2 <= analytic_static <= truth_static * 2
        # The charged temp never under-admits vs XLA's own number.
        assert plan.temp_bytes >= truth["temp"]


class TestMeshLadder:
    """The mesh degradation ladder: full (data, model) mesh -> model-axis-
    collapsed mesh -> single-device ladder, driven by a shrinking per-chip
    ``KEYSTONE_HBM_BUDGET`` — with every tier producing identical
    predictions (the acceptance bar)."""

    # Tall-skinny: the row-sharded design matrix/residual dominate the
    # per-chip footprint, so collapsing the model axis (data 4 -> 8)
    # strictly shrinks each chip's share and the tier totals decrease
    # monotonically down the ladder.
    N, D, K = 2048, 256, 8

    def _problem(self, rng):
        a = rng.normal(size=(self.N, self.D)).astype(np.float32)
        b = rng.normal(size=(self.N, self.K)).astype(np.float32)
        return a, b

    def _fit(self, a, b, mesh):
        est = BlockLeastSquaresEstimator(64, num_iter=2, lam=0.5, mesh=mesh)
        model = est.fit(a, b)
        return est, np.asarray(model(jnp.asarray(a)))

    def test_budget_walks_full_mesh_reduced_mesh_single_device(
        self, rng, mesh42, monkeypatch
    ):
        a, b = self._problem(rng)

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1G")
        est, p_full = self._fit(a, b, mesh42)
        rep = est.last_fit_report
        assert rep.chosen == "fused[mesh 4x2]"
        assert rep.mesh_shape == {"data": 4, "model": 2}
        assert list(rep.plans) == ["fused[mesh 4x2]"]  # lazy planning
        t_full = rep.plans["fused[mesh 4x2]"].total_bytes

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(t_full - 1))
        est, p_red = self._fit(a, b, mesh42)
        rep = est.last_fit_report
        assert rep.chosen == "fused[mesh 8x1]"
        assert rep.mesh_shape == {"data": 8, "model": 1}
        assert rep.denials == ["fused[mesh 4x2]"]
        t_red = rep.plans["fused[mesh 8x1]"].total_bytes
        assert t_red < t_full  # collapsing the model axis shrinks per-chip

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(t_red - 1))
        est, p_single = self._fit(a, b, mesh42)
        rep = est.last_fit_report
        assert rep.chosen.startswith("single_device/")
        assert rep.mesh_shape is None
        assert rep.denials[:2] == ["fused[mesh 4x2]", "fused[mesh 8x1]"]

        np.testing.assert_allclose(p_full, p_red, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(p_full, p_single, rtol=1e-5, atol=1e-5)
