"""Multi-host elastic serving (ISSUE 17) — the acceptance surface.

A REAL 2-process ``jax.distributed`` CPU fit+serve must be bit-identical
to the single-process run on the same data; the host-loss drill must end
with every request answered bit-equal (zero dropped), the loss counted
and the survivors re-anchored; bring-up faults (dead coordinator,
``EADDRINUSE``) must be typed and counted, never hangs; shutdown must
leak no service threads; and with no group configured every new path is
inert.  Multi-process tests carry the ``dist`` marker (auto-skipped where
spawn/ports are unavailable, see conftest).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from keystone_tpu.core import frontend as kfrontend
from keystone_tpu.core import serve as kserve
from keystone_tpu.core import wire
from keystone_tpu.core.ingest import host_shards
from keystone_tpu.core.resilience import DeadlineExceeded, counters
from keystone_tpu.parallel import distributed as kdist
from keystone_tpu.parallel.mesh import host_local_mesh, make_mesh
from keystone_tpu.workloads import multihost

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_group():
    """Tests that form a (membership-only) group must never leak it into
    the rest of the suite."""
    assert not kdist.is_initialized(), "a prior test leaked a process group"
    yield
    kdist.shutdown_process_group()


# -- inert single-process discipline ------------------------------------------


class TestInertWithoutAGroup:
    def test_process_count_and_index_answer_solo(self):
        assert not kdist.is_initialized()
        assert kdist.process_count() == 1
        assert kdist.process_index() == 0

    def test_shutdown_is_idempotent_noop(self):
        assert kdist.shutdown_process_group() == []

    def test_init_with_nothing_configured_is_inert(self, clean_group):
        st = kdist.init_process_group()
        assert (st.world, st.rank, st.jax_initialized) == (1, 0, False)

    def test_distributed_module_import_is_jax_free(self):
        """The decode-worker discipline (tests/test_lazy_import.py)
        extends to the new module: importing it must not pull jax."""
        res = subprocess.run(
            [
                sys.executable, "-c",
                "import sys\n"
                "import keystone_tpu.parallel.distributed as d\n"
                "assert 'jax' not in sys.modules\n"
                "assert d.process_count() == 1\n"
                "print('DIST_LAZY_OK')\n",
            ],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        )
        assert res.returncode == 0, res.stderr[-2000:]
        assert "DIST_LAZY_OK" in res.stdout


# -- shard partitioning and the fit math --------------------------------------


class TestHostShards:
    def test_partition_is_disjoint_and_covers(self):
        paths = [f"/data/shard_{i:03d}.tar" for i in range(7)]
        got = [host_shards(paths, r, 3) for r in range(3)]
        assert sorted(p for g in got for p in g) == sorted(paths)
        assert [len(g) for g in got] == [3, 2, 2]

    def test_world_one_returns_all_sorted(self):
        assert host_shards(["b.tar", "a.tar"]) == ["a.tar", "b.tar"]

    def test_rank_out_of_world_is_typed(self):
        with pytest.raises(ValueError):
            host_shards(["a.tar"], 3, 2)


def test_fit_from_moments_matches_scaler_math(rng):
    feats = rng.normal(size=(40, multihost.FEAT_DIM)).astype(np.float32)
    packed = np.concatenate(
        [
            feats.sum(axis=0, dtype=np.float32),
            (feats * feats).sum(axis=0, dtype=np.float32),
            [np.float32(len(feats))],
        ]
    )
    mean, std = multihost.fit_from_moments(packed)
    np.testing.assert_allclose(mean, feats.mean(axis=0), rtol=1e-5)
    np.testing.assert_allclose(
        std, feats.std(axis=0, ddof=1), rtol=1e-3
    )
    # degenerate column -> std guard of 1.0, never a divide-by-zero
    const = np.concatenate(
        [np.full(8, 12.0, np.float32) * 4, np.full(8, 144.0, np.float32) * 4,
         [np.float32(4)]]
    )
    _, stdc = multihost.fit_from_moments(const)
    assert np.all(stdc == 1.0)


# -- fleet membership (reform_group) ------------------------------------------


class TestReformGroup:
    def test_reform_reduces_world_and_counts(self, clean_group):
        kdist.init_process_group(
            coordinator="controller", world=3, rank=1, use_jax=False
        )
        before = counters.get("dist_reform")
        new = kdist.reform_group([0, 1])
        assert (new.world, new.rank, new.epoch) == (2, 1, 1)
        assert new.lost == (2,)
        assert not new.jax_initialized
        assert counters.get("dist_reform") - before == 1
        assert kdist.process_count() == 2

    def test_survivor_set_must_contain_self(self, clean_group):
        kdist.init_process_group(
            coordinator="controller", world=2, rank=1, use_jax=False
        )
        with pytest.raises(ValueError, match="not among survivors"):
            kdist.reform_group([0])

    def test_reform_without_group_is_typed(self):
        with pytest.raises(RuntimeError, match="no process group"):
            kdist.reform_group([0])


# -- bring-up hardening (typed faults, counted) -------------------------------


class TestBringUpHardening:
    def test_eaddrinuse_retries_then_succeeds_counted(
        self, clean_group, monkeypatch
    ):
        import jax

        calls = {"n": 0}

        def flaky_initialize(**kw):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError(
                    "Failed to bind: Address already in use (98)"
                )

        monkeypatch.setattr(jax.distributed, "initialize", flaky_initialize)
        monkeypatch.setattr(jax.distributed, "shutdown", lambda: None)
        # The real gloo flip is exercised in the subprocess tests; flipped
        # HERE it would poison this process's CPU backend (gloo demands a
        # live distributed client at backend init).
        monkeypatch.setattr(kdist, "_enable_cpu_collectives", lambda: None)
        before = counters.get("dist_port_retry")
        st = kdist.init_process_group(
            coordinator="127.0.0.1:1", world=2, rank=0,
            join_timeout_s=5.0, port_retries=4,
        )
        assert st.jax_initialized and calls["n"] == 3
        assert counters.get("dist_port_retry") - before == 2

    def test_eaddrinuse_on_nonzero_rank_propagates(
        self, clean_group, monkeypatch
    ):
        """Only the coordinator owns the port; a joiner seeing the error
        must not spin on it."""
        import jax

        def always_in_use(**kw):
            raise RuntimeError("Address already in use")

        monkeypatch.setattr(jax.distributed, "initialize", always_in_use)
        monkeypatch.setattr(kdist, "_enable_cpu_collectives", lambda: None)
        with pytest.raises(RuntimeError, match="already in use"):
            kdist.init_process_group(
                coordinator="127.0.0.1:1", world=2, rank=1,
                join_timeout_s=5.0, port_retries=4,
            )

    def test_join_timeout_is_typed_and_counted(self, clean_group, monkeypatch):
        import jax

        def never_joins(**kw):
            raise RuntimeError(
                "DEADLINE_EXCEEDED: Barrier timed out. Barrier name: "
                "PjRT_Client_Connect"
            )

        monkeypatch.setattr(jax.distributed, "initialize", never_joins)
        monkeypatch.setattr(kdist, "_enable_cpu_collectives", lambda: None)
        before = counters.get("dist_join_timeout")
        with pytest.raises(DeadlineExceeded) as ei:
            kdist.init_process_group(
                coordinator="127.0.0.1:1", world=2, rank=1,
                join_timeout_s=2.0,
            )
        assert "dist_join[1/2]" in str(ei.value)
        assert counters.get("dist_join_timeout") - before == 1
        assert not kdist.is_initialized()

    @pytest.mark.dist
    def test_missing_peer_is_a_typed_fault_in_a_real_process(self):
        """The real thing, no monkeypatch: a coordinator whose peer never
        arrives blocks inside ``client.connect()`` under XLA's ~1h
        cluster-register timeout — the exact hang the join deadline
        exists to convert.  A real process must come back typed + counted
        in ~the budget, never the hour."""
        script = (
            "import json, sys, time\n"
            "from keystone_tpu.core.resilience import DeadlineExceeded, "
            "counters\n"
            "from keystone_tpu.parallel import distributed as kdist\n"
            "t0 = time.monotonic()\n"
            "try:\n"
            "    kdist.init_process_group(kdist.pick_coordinator(), 2, 0, "
            "join_timeout_s=2.0)\n"
            "except DeadlineExceeded as e:\n"
            "    print(json.dumps({'typed': True, 'phase': str(e), "
            "'wall_s': time.monotonic() - t0, "
            "'counted': counters.get('dist_join_timeout')}))\n"
            "    sys.exit(0)\n"
            "sys.exit(3)\n"
        )
        res = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
            env=dict(
                os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=2",
            ),
            cwd=_REPO,
        )
        assert res.returncode == 0, (res.stdout + res.stderr)[-2000:]
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        assert rec["typed"] and rec["counted"] >= 1
        assert "dist_join[0/2]" in rec["phase"]
        assert rec["wall_s"] < 30.0, "the deadline did not bound the join"

    @pytest.mark.dist
    def test_dead_coordinator_joiner_is_typed_not_a_hang(self):
        """A joiner whose coordinator is dead: left to jax, its internal
        RegisterTask deadline fires inside C++ and TERMINATES the process
        (client.h fatal) — no Python frame ever sees it.  The keystone
        clock sits in FRONT of jax's, so the joiner gets the typed,
        counted fault and exits on its own terms."""
        dead = kdist.pick_coordinator()  # picked then never bound
        script = (
            "import json, sys, time\n"
            "from keystone_tpu.core.resilience import DeadlineExceeded, "
            "counters\n"
            "from keystone_tpu.parallel import distributed as kdist\n"
            "t0 = time.monotonic()\n"
            "try:\n"
            f"    kdist.init_process_group({dead!r}, 2, 1, "
            "join_timeout_s=2.0)\n"
            "except DeadlineExceeded:\n"
            "    print(json.dumps({'typed': True, "
            "'wall_s': time.monotonic() - t0, "
            "'counted': counters.get('dist_join_timeout')}))\n"
            "    sys.exit(0)\n"
            "sys.exit(3)\n"
        )
        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
            env=dict(
                os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=2",
            ),
            cwd=_REPO,
        )
        assert res.returncode == 0, (res.stdout + res.stderr)[-2000:]
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        assert rec["typed"] and rec["counted"] >= 1
        assert time.monotonic() - t0 < 60.0

    @pytest.mark.dist
    def test_shutdown_leaks_no_service_threads(self):
        """The coordinator service's threads must be GONE after
        ``shutdown_process_group`` — asserted the way a stream's
        ``join()`` is asserted, in a real process that ran a real
        (world-1) group."""
        script = (
            "import json\n"
            "from keystone_tpu.parallel import distributed as kdist\n"
            "st = kdist.init_process_group(kdist.pick_coordinator(), 1, 0, "
            "join_timeout_s=30.0)\n"
            "assert st.jax_initialized\n"
            "import jax\n"
            "assert jax.process_count() == 1\n"
            "leaked = kdist.shutdown_process_group()\n"
            "print(json.dumps({'leaked': leaked}))\n"
        )
        res = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
            env=dict(
                os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=2",
            ),
            cwd=_REPO,
        )
        assert res.returncode == 0, (res.stdout + res.stderr)[-2000:]
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        assert rec["leaked"] == []


# -- the tentpole: 2-process fit+serve, bit-identical -------------------------


@pytest.mark.dist
def test_two_process_fit_serve_bit_identical_to_single(tmp_path):
    """ISSUE 17 acceptance: a REAL 2-process ``jax.distributed`` CPU
    fit+serve (per-host tar shards through core.ingest, deterministic
    rank-ordered aggregation, cross-host checkpoint reshard) produces
    predictions bit-equal to the single-process run on the same data."""
    j = multihost.run_two_process_fit_serve(str(tmp_path), timeout_s=240.0)
    assert j["bit_identical"], {
        k: j["records"][k].get("mean") for k in ("ref", 0, 1)
    }
    assert j["mesh_spans"], "the global mesh never spanned processes"
    assert j["crosshost_reshard"] >= 1, (
        "load_pipeline(mesh=) never took the destination-pull path"
    )
    assert j["crosshost_bit_equal"], (
        "a resharded shard's bytes differ from the fit's"
    )
    assert j["leaked_threads"] == []
    assert j["parity_ok"]
    assert j["n_images"] == 24  # both fits saw every shard exactly once


# -- host fleet front-end ------------------------------------------------------


class _Ready:
    """Already-resolved future (the wire server awaits ``result``)."""

    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        return self._value


class _Doubler:
    def submit(self, arr):
        return _Ready(np.asarray(arr) * 2.0)

    def record(self):
        return {}


class TestHostFleet:
    def test_failover_reissues_and_counts(self, clean_group):
        s0 = wire.WireServer(_Doubler(), port=0, label="fleet_a")
        s1 = wire.WireServer(_Doubler(), port=0, label="fleet_b")
        try:
            fleet = kfrontend.HostFleet(
                [("127.0.0.1", s0.port), ("127.0.0.1", s1.port)],
                label="t_fleet",
            )
            with fleet:
                rows = [np.full(4, float(i), np.float32) for i in range(6)]
                for r in rows[:2]:
                    np.testing.assert_array_equal(
                        np.asarray(fleet.predict(r)), np.asarray(r) * 2.0
                    )
                before = counters.get("fleet_host_lost")
                s1.close()  # abrupt: sockets die under the clients
                for r in rows[2:]:
                    np.testing.assert_array_equal(
                        np.asarray(fleet.predict(r)), np.asarray(r) * 2.0
                    )
                assert counters.get("fleet_host_lost") - before == 1
                rec = fleet.record()
                assert len(fleet.alive_hosts()) == 1
                assert sum(h["reissued"] for h in rec["hosts"]) >= 1
        finally:
            s0.close()
            s1.close()

    def test_all_hosts_down_is_typed(self):
        s0 = wire.WireServer(_Doubler(), port=0, label="fleet_solo")
        fleet = kfrontend.HostFleet(
            [("127.0.0.1", s0.port)], label="t_fleet_down"
        )
        with fleet:
            s0.close()
            with pytest.raises(kfrontend.ServingUnavailable):
                fleet.predict(np.zeros(4, np.float32))

    def test_remote_typed_errors_pass_through_not_failover(self):
        """A typed remote answer (the server computed and refused) must
        reach the caller — reissuing it on another host would duplicate
        work the fleet already has an answer for."""

        class Refuser:
            def submit(self, arr):
                raise ValueError("typed refusal from the engine")

            def record(self):
                return {}

        s0 = wire.WireServer(Refuser(), port=0, label="fleet_refuse")
        try:
            with kfrontend.HostFleet(
                [("127.0.0.1", s0.port)], label="t_fleet_refuse"
            ) as fleet:
                with pytest.raises(wire.WireRemoteError, match="ValueError"):
                    fleet.predict(np.zeros(4, np.float32))
                assert len(fleet.alive_hosts()) == 1  # NOT marked lost
        finally:
            s0.close()


# -- host-loss drill (the in-process face; chaos drives both) -----------------


def test_host_loss_drill_inprocess_zero_loss_bit_equal(tmp_path, clean_group):
    rec = multihost.run_host_loss_drill(
        str(tmp_path), subprocess_mode=False, requests=16, timeout_s=120.0
    )
    assert rec["dropped_requests"] == 0
    assert rec["mismatches"] == 0
    assert rec["errors"] == []
    sc = rec["survivor_counters"][0]
    assert sc.get("fleet_host_lost", 0) >= 1
    assert sc.get("dist_reform", 0) >= 1
    assert sc.get("host_reanchor", 0) >= 1


# -- satellite: reanchor under live wire traffic, windows full ----------------


def test_reanchor_under_live_wire_traffic_full_windows(devices, rng):
    """The swap happens while wire clients keep the server's per-client
    in-flight window FULL: backpressure answers RETRY_AFTER (clients
    absorb and resubmit), the re-anchor swaps engines underneath, and at
    the end every request is answered correctly — zero dropped, the
    ``reanchor_dropped_requests`` invariant as a tier-1 test."""
    from keystone_tpu.ops.stats import StandardScalerModel

    import jax.numpy as jnp

    model = StandardScalerModel(
        jnp.asarray(rng.normal(size=8).astype(np.float32)),
        jnp.asarray((np.abs(rng.normal(size=8)) + 0.5).astype(np.float32)),
    )
    full = make_mesh(data=2, model=1, devices=devices[:2])
    surviving = make_mesh(data=2, model=1, devices=devices[2:4])

    def build(shape, dtype, mesh):
        return kserve.ServingEngine(
            model, np.zeros(shape, dtype),
            config=kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0),
            label="wire_swap", mesh=mesh,
        )

    factory = kfrontend.MeshEngineFactory(build, mesh=full)
    router = kfrontend.ShapeRouter(factory, label="wire_swap")
    router.add_engine(factory((8,), np.float32))
    n_clients, per_client = 3, 20
    rows = np.asarray(
        rng.normal(size=(n_clients * per_client, 8)), np.float32
    )
    expected = np.asarray(model(jnp.asarray(rows)))
    answers: dict = {}
    errors: list = []
    server = wire.WireServer(
        router, port=0, max_inflight=2, retry_after_s=0.005,
        label="wire_swap",
    )
    try:
        def client(c):
            idx = list(range(c * per_client, (c + 1) * per_client))
            try:
                cl = wire.WireClient("127.0.0.1", server.port)
                try:
                    # window 8 >> max_inflight 2: the server's window is
                    # full the whole run, RETRY_AFTER is the steady state.
                    got = cl.predict_many(
                        [rows[i] for i in idx], window=8, timeout=60.0
                    )
                finally:
                    cl.close()
                for i, g in zip(idx, got):
                    answers[i] = np.asarray(g)
            except Exception as e:  # noqa: BLE001 — judged below
                errors.append(f"client{c}: {type(e).__name__}: {e}")

        threads = [
            threading.Thread(target=client, args=(c,), daemon=True)
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30.0
        while server.stats.requests < n_clients * 2:
            assert time.monotonic() < deadline, "traffic never started"
            time.sleep(0.002)
        rec = router.reanchor(surviving, why="test: swap under full windows")
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        server.close()
        router.close()
    assert errors == []
    assert len(answers) == len(rows), (
        f"dropped {len(rows) - len(answers)} request(s) across the swap"
    )
    got = np.stack([answers[i] for i in range(len(rows))])
    np.testing.assert_array_equal(got, expected)
    assert rec["failed"] == [] and len(rec["swapped"]) == 1
    assert server.stats.retry_after >= 1, (
        "the in-flight window never filled — the test lost its point"
    )
