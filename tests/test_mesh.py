"""Degradation-ladder substrate coverage (ISSUE 16): ``reduced_mesh`` and
the mesh enumerators over the DEGENERATE survivor shapes the elastic
re-anchor walks — 1xN, Nx1, a single device, odd/prime device counts —
and over the happy-path 8-device shapes; plus the per-operand sharding-spec
vocabulary (``spec_candidates`` / ``best_spec`` / ``spec_pspec`` /
``spec_sharding`` / ``spec_chip_bytes``) the checkpoint's reshard loader
and the serving engine lower their layouts through."""

import jax
import jax.numpy as jnp
import pytest

from keystone_tpu.core import memory as kmem
from keystone_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    best_spec,
    enumerate_mesh_shapes,
    enumerate_meshes,
    make_mesh,
    mesh_desc,
    reduced_mesh,
    spec_candidates,
    spec_chip_bytes,
    spec_pspec,
    spec_sharding,
)


def _survivors(devices, n):
    assert len(devices) >= n, f"need {n} of the 8 virtual devices"
    return devices[:n]


class TestReducedMesh:
    def test_collapses_model_axis_onto_data(self, devices):
        mesh = make_mesh(data=2, model=2, devices=_survivors(devices, 4))
        red = reduced_mesh(mesh)
        assert mesh_desc(red) == "4x1"
        # the SAME devices, every one of them — a ladder step trades
        # layout, never capacity
        assert list(red.devices.flat) == list(mesh.devices.flat)

    def test_pure_data_mesh_has_no_rung_below(self, devices):
        for n in (1, 2, 3, 8):
            mesh = make_mesh(data=n, model=1, devices=_survivors(devices, n))
            assert reduced_mesh(mesh) is None

    def test_model_only_survivor_1xn(self, devices):
        """1xN (a data-collapsed survivor that is ALL model axis) still
        reduces to pure data-parallel over the same devices."""
        mesh = make_mesh(data=1, model=4, devices=_survivors(devices, 4))
        red = reduced_mesh(mesh)
        assert mesh_desc(red) == "4x1"
        assert list(red.devices.flat) == list(mesh.devices.flat)

    def test_two_device_model_pair(self, devices):
        mesh = make_mesh(data=1, model=2, devices=_survivors(devices, 2))
        assert mesh_desc(reduced_mesh(mesh)) == "2x1"

    def test_single_device_mesh_is_the_floor(self, devices):
        mesh = make_mesh(data=1, model=1, devices=_survivors(devices, 1))
        assert reduced_mesh(mesh) is None


class TestEnumerateMeshShapes:
    def test_single_device(self):
        assert enumerate_mesh_shapes(1) == [(1, 1)]

    @pytest.mark.parametrize("n", (3, 5, 7))
    def test_prime_counts_yield_the_two_degenerates(self, n):
        assert enumerate_mesh_shapes(n) == [(n, 1), (1, n)]

    def test_odd_composite_count(self):
        # 9 survivors of a 16-device pod: every divisor pair, data-major
        assert enumerate_mesh_shapes(9) == [(9, 1), (3, 3), (1, 9)]

    def test_data_major_descending_and_exhaustive(self):
        shapes = enumerate_mesh_shapes(6)
        assert shapes == [(6, 1), (3, 2), (2, 3), (1, 6)]
        assert all(d * m == 6 for d, m in shapes)
        datas = [d for d, _ in shapes]
        assert datas == sorted(datas, reverse=True)

    def test_zero_devices_refused(self):
        with pytest.raises(ValueError, match=">= 1 device"):
            enumerate_mesh_shapes(0)


class TestEnumerateMeshes:
    @pytest.mark.parametrize("n", (1, 3, 5, 7))
    def test_degenerate_survivor_counts_materialize(self, devices, n):
        """Odd/prime survivor sets — the shapes a device loss actually
        leaves behind — must enumerate real, usable meshes."""
        survivors = _survivors(devices, n)
        meshes = enumerate_meshes(survivors)
        assert [
            (m.shape[DATA_AXIS], m.shape[MODEL_AXIS]) for m in meshes
        ] == enumerate_mesh_shapes(n)
        for m in meshes:
            assert list(m.devices.flat) == list(survivors)

    def test_deterministic_over_one_device_set(self, devices):
        survivors = _survivors(devices, 5)
        a = enumerate_meshes(survivors)
        b = enumerate_meshes(survivors)
        # memoized per device tuple: identical Mesh objects both times
        # (searched-plan determinism), but a fresh mutable list per call
        assert a == b
        assert a is not b

    def test_survivor_order_is_the_cache_key(self, devices):
        fwd = _survivors(devices, 2)
        rev = list(reversed(fwd))
        a = enumerate_meshes(fwd)
        b = enumerate_meshes(rev)
        assert list(a[0].devices.flat) == fwd
        assert list(b[0].devices.flat) == rev


def test_ladder_walk_over_survivors(devices):
    """The exact walk MeshEngineFactory takes: any survivor mesh steps
    full -> reduced (same devices) -> None within two rungs; the floor is
    always reachable."""
    for n, model in ((4, 2), (2, 2), (3, 1), (1, 1)):
        if model > 1:
            mesh = make_mesh(
                data=n // model, model=model, devices=_survivors(devices, n)
            )
        else:
            mesh = make_mesh(data=n, model=1, devices=_survivors(devices, n))
        rungs = 0
        while mesh is not None:
            mesh = reduced_mesh(mesh)
            rungs += 1
            assert rungs <= 2, "ladder failed to reach the floor"


# -- enumeration over the full 8-device set ------------------------------------


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
    devices = jax.devices()
    a = enumerate_meshes(devices)
    b = enumerate_meshes(devices)
    assert [mesh_desc(m) for m in a] == [mesh_desc(m) for m in b]
    assert [mesh_desc(m) for m in a] == [
        f"{d}x{m}" for d, m in enumerate_mesh_shapes(len(devices))
    ]
    # Same devices in the same order for every mesh.
    for m in a:
        assert list(m.devices.flat) == list(devices)


def test_enumerate_meshes_memoized_per_device_tuple():
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


# -- the sharding-spec vocabulary ----------------------------------------------


def test_spec_candidates_generated_from_aval_dims():
    aval = jnp.zeros((8, 6), jnp.float32)
    specs = {
        c["spec"]: c["per_chip_bytes"]
        for c in spec_candidates(aval, {"data": 2, "model": 3})
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
    best = best_spec(aval, {"data": 4, "model": 2})
    assert best["spec"] == "data@dim0"
    assert best["per_chip_bytes"] == 8 * 6 * 4 // 4
    # Nothing divides a prime dim: replicated is the only legal spec.
    odd = jnp.zeros((7,), jnp.float32)
    assert best_spec(odd, {"data": 4, "model": 2})["spec"] == "replicated"


def test_spec_pspec_lowers_every_vocabulary_entry():
    from jax.sharding import PartitionSpec as P

    assert spec_pspec("replicated", 2) == P(None, None)
    assert spec_pspec("data@dim0", 2) == P("data", None)
    assert spec_pspec("model@dim1", 2) == P(None, "model")
    assert spec_pspec("model@dim2", 3) == P(None, None, "model")
    with pytest.raises(ValueError):
        spec_pspec("bogus", 2)
    with pytest.raises(ValueError):
        spec_pspec("data@dim5", 2)  # names a missing dim


def test_spec_sharding_places_arrays_per_spec(mesh42):
    a = jnp.zeros((8, 6), jnp.float32)
    sharded = jax.device_put(a, spec_sharding("data@dim0", mesh42, 2))
    # data axis 4: each chip holds a [2, 6] shard
    shard_shape = sharded.sharding.shard_shape((8, 6))
    assert shard_shape == (2, 6)
    rep = jax.device_put(a, spec_sharding("replicated", mesh42, 2))
    assert rep.sharding.shard_shape((8, 6)) == (8, 6)


def test_spec_chip_bytes_matches_enumeration():
    mesh_shape = {"data": 2, "model": 3}
    aval = jnp.zeros((8, 6), jnp.float32)
    for c in spec_candidates(aval, mesh_shape):
        assert spec_chip_bytes(
            (8, 6), jnp.float32, c["spec"], mesh_shape
        ) == c["per_chip_bytes"]
    with pytest.raises(ValueError):
        spec_chip_bytes((7,), jnp.float32, "data@dim0", mesh_shape)


def test_spec_candidates_bytes_lower_bound_of_compiled_layouts(mesh42):
    """For every enumerated spec, the analytic per-chip bytes are a true
    LOWER bound of what the compiled admission charges for that layout
    (max of the analytic shard division and XLA's own memory_analysis,
    exactly as plan_program's mesh mode charges)."""
    shapes = [(64, 48), (32, 8, 6)]
    for shape in shapes:
        aval = jnp.zeros(shape, jnp.float32)
        for c in spec_candidates(aval, dict(mesh42.shape)):
            sharding = spec_sharding(c["spec"], mesh42, len(shape))
            s = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
            compiled = jax.jit(lambda a: a * 2.0).lower(s).compile()
            ma = compiled.memory_analysis()
            charged = max(
                kmem.shard_bytes(s), int(ma.argument_size_in_bytes)
            )
            assert c["per_chip_bytes"] <= charged, (
                shape, c, charged, int(ma.argument_size_in_bytes),
            )
