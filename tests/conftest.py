"""Test harness: distributed-without-a-cluster.

The reference boots a local[k] SparkContext per suite
(reference src/test/scala/pipelines/LocalSparkContext.scala:9-43); here the
analog is a virtual 8-device CPU platform so every mesh/collective path is
exercised without TPU hardware.  Must set flags before jax initializes.
"""

import os
import tempfile

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic compiles: workload mains place JAX's persistent compile cache in
# the checkout (utils.platform.init_device); a suite run neither fills it
# nor runs executables an earlier run left there.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Hermetic placement search: the plan-outcome log (core.autoshard) defaults
# to ~/.keystone_plans.jsonl and TRAINS the cost model across processes — a
# suite run must neither pollute the operator's log nor inherit a trained
# ranking that deviates from the hand ladder (the bit-identical baselines
# several suites pin).  This covers what runs while modules are imported;
# the `_plan_log_per_test` fixture below gives every test a log of its own.
os.environ["KEYSTONE_PLAN_LOG"] = os.path.join(
    tempfile.mkdtemp(prefix="keystone_plans_"), "plans.jsonl"
)

import jax  # noqa: E402

# Also in the config, for a jax that something imported before this file
# set the variable.
jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from keystone_tpu.parallel.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    """8-way data-parallel mesh (the local[8] analog)."""
    return make_mesh(data=8, model=1)


@pytest.fixture(scope="session")
def mesh42(devices):
    """4x2 data-by-model mesh for mixed-parallel tests."""
    return make_mesh(data=4, model=2)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _plan_log_per_test(tmp_path, monkeypatch):
    """Placement state is per test: a fit appends its outcome to the plan
    log and the search trains on what it reads there, so with one log a
    process the ranking a test sees depends on which tests ran before it
    in its worker (the searched `fused[mesh 1x8]` where the hand order
    says `4x2`).  A test that sets the variable itself nests inside."""
    from keystone_tpu.core import autoshard

    monkeypatch.setenv(
        autoshard.PLAN_LOG_ENV, str(tmp_path / "plans.jsonl")
    )
    autoshard.clear_outcome_cache()
    yield
    autoshard.clear_outcome_cache()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end pipeline test")
    config.addinivalue_line(
        "markers",
        "serve: serving-subsystem tests (core.serve) — tier-1 runs the "
        "deterministic set; the concurrent-client soak is also marked "
        "slow and runs under -m slow",
    )
    config.addinivalue_line(
        "markers",
        "chaos: full seeded fault-schedule suite (tests/chaos.py) — the "
        "tier-1 run covers a small schedule; select the full set with "
        "-m chaos (full-schedule tests are also marked slow so the tier-1 "
        "'-m not slow' filter excludes them)",
    )
    config.addinivalue_line(
        "markers",
        "dist: multi-process jax.distributed tests — REAL subprocesses on "
        "auto-picked ports; auto-skipped where spawn or port binding is "
        "unavailable (parallel.distributed.spawn_available)",
    )
    config.addinivalue_line(
        "markers",
        "native_entropy: tests that pin the NATIVE entropy-decode backend "
        "(ops.native_entropy) — auto-skipped where the toolchain cannot "
        "build/load the library, so tier-1 stays green on minimal hosts "
        "(the Python-pass and degradation tests carry no marker and always "
        "run)",
    )


def pytest_collection_modifyitems(config, items):
    """dist-marked tests need subprocess spawn + a bindable loopback port;
    on hosts without either they skip with the reason named, they do not
    fail."""
    dist_items = [it for it in items if it.get_closest_marker("dist")]
    if dist_items:
        from keystone_tpu.parallel.distributed import spawn_available

        if not spawn_available():
            skip = pytest.mark.skip(
                reason="multi-process unavailable (no spawn or no bindable "
                "port; see KEYSTONE_DIST_DISABLE)"
            )
            for it in dist_items:
                it.add_marker(skip)
    native_items = [
        it for it in items if it.get_closest_marker("native_entropy")
    ]
    if native_items:
        from keystone_tpu.ops import native_entropy

        if not native_entropy.available():
            skip = pytest.mark.skip(
                reason="native entropy decoder unbuildable/unloadable "
                "(no g++? see KEYSTONE_NATIVE_ENTROPY)"
            )
            for it in native_items:
                it.add_marker(skip)
