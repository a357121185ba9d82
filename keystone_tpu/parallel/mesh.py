"""Device mesh + sharding helpers — the execution substrate.

Replaces the reference's Spark-RDD substrate (SURVEY §1 L1): an RDD partition
becomes a shard of a ``jax.Array`` over the mesh's ``data`` axis; the feature
/ model-block dimension (reference nodes/util/VectorSplitter.scala:10-36)
maps to the ``model`` axis.  All cross-device communication is XLA
collectives over ICI — there is no driver/executor split; host Python is the
single controller and device arrays persist in HBM between stages.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(data: int | None = None, model: int = 1, devices=None) -> Mesh:
    """Build a (data, model) mesh.  ``data=None`` uses all remaining devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        if n % model != 0:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data * model} devices, have {n}")
    arr = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def reduced_mesh(mesh: Mesh) -> Mesh | None:
    """The next rung of the mesh degradation ladder: the SAME devices with
    the ``model`` axis collapsed into ``data`` — ``(data=4, model=2)`` →
    ``(data=8, model=1)``.  Model blocks replicate instead of sharding, and
    in exchange every row-sharded operand (the design matrix, labels, the
    residual — the terms that dominate a solve's per-chip footprint) holds
    half as many rows per chip.  ``None`` when the mesh is already pure
    data-parallel (nothing left to collapse; the ladder's next rung is the
    single-device floor)."""
    if mesh.shape[MODEL_AXIS] <= 1:
        return None
    devices = list(mesh.devices.flat)
    return make_mesh(data=len(devices), model=1, devices=devices)


def mesh_desc(mesh: Mesh) -> str:
    """``'4x2'`` — the (data, model) shape tag used in tier names."""
    return f"{mesh.shape[DATA_AXIS]}x{mesh.shape[MODEL_AXIS]}"


@functools.lru_cache(maxsize=None)
def _mesh_shapes(n_devices: int) -> tuple[tuple[int, int], ...]:
    """Memoized factorization body of :func:`enumerate_mesh_shapes` (the
    device count never changes within a process)."""
    if n_devices < 1:
        raise ValueError(f"need >= 1 device, got {n_devices}")
    return tuple(
        (d, n_devices // d)
        for d in range(n_devices, 0, -1)
        if n_devices % d == 0
    )


def enumerate_mesh_shapes(n_devices: int) -> list[tuple[int, int]]:
    """Every (data, model) factorization of ``n_devices``, data-major
    descending; all devices participate in every shape.  ``n_devices=1``
    is the one-shape list ``[(1, 1)]``, and a prime count yields exactly
    its two degenerate factorizations.  Memoized per device count (a fresh
    list is returned per call; the cached tuple is never handed out
    mutable)."""
    return list(_mesh_shapes(n_devices))


#: device tuple -> materialized candidate meshes; a Mesh wraps the device
#: objects themselves, so caching on the exact device identity (same
#: devices, same order) is both safe and the determinism contract.
_mesh_cache: dict[tuple, tuple[Mesh, ...]] = {}


def enumerate_meshes(devices) -> list[Mesh]:
    """:func:`enumerate_mesh_shapes` materialized over a fixed device
    list — the same devices in the same order for every shape, so two calls
    over one device set enumerate identical meshes.  Memoized per device
    tuple: each uncached enumeration costs one jax ``Mesh`` construction per
    factorization."""
    key = tuple(devices)
    cached = _mesh_cache.get(key)
    if cached is None:
        cached = _mesh_cache[key] = tuple(
            make_mesh(data=d, model=m, devices=list(key))
            for d, m in _mesh_shapes(len(key))
        )
    return list(cached)


# -- per-operand sharding specs -----------------------------------------------
#
# A spec string names ONE mesh axis over ONE operand dimension
# ("data@dim0", "model@dim1") or full replication ("replicated").  The
# checkpoint's restore planner and the serving engine lower the strings they
# record or choose into the jax objects below, and byte accounting reads the
# same strings, so the two cannot drift.


def spec_candidates(aval, mesh_shape: dict) -> list[dict]:
    """Candidate shardings for ONE operand aval under a (data, model) mesh
    shape, generated from the aval's dimensions: the data axis over any
    evenly-divisible dim, the model axis over any other evenly-divisible
    dim, and replicated (always legal).  Each entry carries the spec's
    per-chip bytes."""
    shape = tuple(int(d) for d in aval.shape)
    itemsize = np.dtype(aval.dtype).itemsize
    total = int(np.prod(shape)) * itemsize if shape else itemsize
    out = [{"spec": "replicated", "per_chip_bytes": total}]
    d_sz = int(mesh_shape.get(DATA_AXIS, 1))
    m_sz = int(mesh_shape.get(MODEL_AXIS, 1))
    for dim, n in enumerate(shape):
        if d_sz > 1 and n % d_sz == 0:
            out.append({
                "spec": f"data@dim{dim}",
                "per_chip_bytes": total // d_sz,
            })
        if m_sz > 1 and n % m_sz == 0:
            out.append({
                "spec": f"model@dim{dim}",
                "per_chip_bytes": total // m_sz,
            })
    return out


def best_spec(aval, mesh_shape: dict) -> dict:
    """The minimum-per-chip-bytes legal sharding for one aval (replicated
    when nothing divides)."""
    cands = spec_candidates(aval, mesh_shape)
    return min(cands, key=lambda c: (c["per_chip_bytes"], c["spec"]))


def spec_pspec(spec: str, ndim: int) -> P:
    """Lower one spec string to the ``PartitionSpec`` it names."""
    if spec == "replicated":
        return P(*([None] * ndim))
    axis, sep, dim = spec.partition("@dim")
    if not sep or axis not in (DATA_AXIS, MODEL_AXIS) or not dim.isdigit():
        raise ValueError(
            f"bad sharding spec {spec!r} (want 'replicated', 'data@dimN' "
            "or 'model@dimN')"
        )
    i = int(dim)
    if i >= ndim:
        raise ValueError(f"spec {spec!r} names dim {i} of a {ndim}-d operand")
    parts: list = [None] * ndim
    parts[i] = axis
    return P(*parts)


def spec_sharding(spec: str, mesh: Mesh, ndim: int) -> NamedSharding:
    """Lower one spec string to a ``NamedSharding`` on ``mesh``."""
    return NamedSharding(mesh, spec_pspec(spec, ndim))


def spec_chip_bytes(shape, dtype, spec: str, mesh_shape: dict) -> int:
    """Analytic per-chip bytes of one operand under one spec.  The named
    dimension must divide evenly (:func:`spec_candidates` only emits legal
    specs)."""
    shape = tuple(int(d) for d in shape)
    total = int(np.prod(shape)) * np.dtype(dtype).itemsize if shape else (
        np.dtype(dtype).itemsize
    )
    if spec == "replicated":
        return total
    axis, _, dim = spec.partition("@dim")
    size = int(mesh_shape.get(axis, 1))
    n = shape[int(dim)]
    if size <= 1:
        return total
    if n % size:
        raise ValueError(
            f"spec {spec!r} does not divide dim of size {n} by {size}"
        )
    return total // size


def mesh_spans_processes(mesh: Mesh) -> bool:
    """Does this mesh place shards on devices owned by OTHER processes?
    After ``jax.distributed`` bring-up ``jax.devices()`` is global, so the
    existing ``make_mesh()`` transparently builds a data axis spanning
    hosts — and every consumer that stages host memory, reads
    ``memory_stats()``, or serves requests must know whether all of the
    mesh is addressable from here.  Always False single-process."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


def host_local_mesh(mesh: Mesh | None = None) -> Mesh:
    """The largest pure-data mesh over THIS process's addressable devices.
    ``mesh`` given: its local sub-mesh (the serving anchor for a host in a
    fleet — engines never span hosts); omitted: all local devices.  Device
    order follows ``jax.local_devices()`` so every host derives the same
    shape for a symmetric fleet."""
    if mesh is None:
        local = list(jax.local_devices())
    else:
        me = jax.process_index()
        local = [d for d in mesh.devices.flat if d.process_index == me]
        if not local:
            raise ValueError(
                f"mesh {mesh_desc(mesh)} has no devices on process {me}"
            )
    return make_mesh(data=len(local), model=1, devices=local)


_current_mesh: list[Mesh] = []


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Set the ambient mesh used by estimators when sharding inputs."""
    _current_mesh.append(mesh)
    try:
        yield mesh
    finally:
        _current_mesh.pop()


def current_mesh() -> Mesh | None:
    return _current_mesh[-1] if _current_mesh else None


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Examples sharded over the data axis; features replicated (the RDD analog)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def padded_shard_rows(x, mesh: Mesh | None = None):
    """Pad N up to a multiple of the data-axis size with zero rows, shard,
    return (x, nvalid).

    Zero rows contribute nothing to raw sums, but any estimator that
    *centers* data must be told ``nvalid`` (pad rows become ``-mean`` after
    centering and would pollute grams) — the solvers' ``fit(..., nvalid=)``
    parameter masks pad rows back to zero after centering.
    """
    mesh = mesh or current_mesh()
    n = x.shape[0]
    if mesh is None:
        return jax.device_put(x), n
    d = mesh.shape[DATA_AXIS]
    pad = (-n) % d
    if pad:
        if isinstance(x, jax.Array):
            # Device-resident: pad on device, no host round trip.
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + tuple(x.shape[1:]), x.dtype)], axis=0
            )
        else:
            # Host input: pad on host so the single device_put below
            # transfers straight into the sharded layout.
            widths = [(0, pad)] + [(0, 0)] * (np.ndim(x) - 1)
            x = np.pad(np.asarray(x), widths)
    return jax.device_put(x, row_sharding(mesh)), n


def rows_by_device(x) -> dict[str, list[int]]:
    """``{device id: [first row, end row)}`` of every addressable shard of
    ``x`` — what a record prints to show that rows are spread over the
    mesh rather than placed on its first device (a device that holds the
    whole array shows ``[0, N]``)."""
    return {
        str(s.device.id): [
            s.index[0].start or 0,
            x.shape[0] if s.index[0].stop is None else s.index[0].stop,
        ]
        for s in x.addressable_shards
    }


# -- where an array lives, as far as the code that receives it can see ----------


def input_mesh(batch):
    """The named mesh of more than one device that the input lives on, as
    far as it can be seen, or None: a concrete array says by its sharding;
    a traced one by the mesh in its type, which ``jit`` takes from an
    argument committed to a mesh (as ``featurize_chunked`` and the serving
    engine commit theirs)."""
    holder = jax.typeof(batch) if isinstance(batch, jax.core.Tracer) else batch
    mesh = getattr(getattr(holder, "sharding", None), "mesh", None)
    return mesh if mesh is not None and mesh.size > 1 else None


def on_one_device(batch) -> bool:
    """Whether the input lives on one device, as far as it can be seen."""
    if isinstance(batch, jax.core.Tracer):
        return input_mesh(batch) is None
    sharding = getattr(batch, "sharding", None)
    return sharding is None or len(sharding.device_set) == 1


def split_axes(batch) -> tuple:
    """Names of the axes of more than one device of the input's mesh;
    ``()`` on one device, ``("?",)`` for a spread with no named mesh."""
    mesh = input_mesh(batch)
    if mesh is None:
        return () if on_one_device(batch) else ("?",)
    return tuple(name for name, size in mesh.shape.items() if size > 1)


def parse_mesh(spec: str | None) -> Mesh | None:
    """Parse a ``--mesh`` flag: ``"8"`` -> 8-way data mesh, ``"4x2"`` ->
    (data=4, model=2).  None/empty -> no mesh (single device)."""
    if not spec:
        return None
    parts = spec.lower().split("x")
    if (
        len(parts) > 2
        or not all(p.strip().isdigit() for p in parts)
        or any(int(p) == 0 for p in parts)
    ):
        raise ValueError(
            f"bad --mesh spec {spec!r}: expected 'DATA' or 'DATAxMODEL' "
            "with positive sizes (e.g. '8' or '4x2')"
        )
    data = int(parts[0])
    model = int(parts[1]) if len(parts) > 1 else 1
    return make_mesh(data=data, model=model)


def mask_pad_rows(x, nvalid: int | None):
    """Zero out rows at index >= ``nvalid``.

    Needed after a featurizer that maps zero pad rows to nonzero outputs
    (e.g. ``cos(0·W + b)`` in CosineRandomFeatures) so downstream moment
    sums over the padded batch stay exact."""
    if nvalid is None or x.shape[0] == nvalid:
        return x
    mask = (jnp.arange(x.shape[0]) < nvalid).astype(x.dtype)
    return x * mask.reshape((-1,) + (1,) * (x.ndim - 1))


def pad_shard_inputs(mesh, nvalid: int | None, *arrays):
    """Row-shard ``arrays`` over the data axis with shared zero padding.

    Returns ``(list_of_sharded_arrays, nvalid)`` where ``nvalid`` is the true
    global row count whenever padding was added (callers mask pad rows after
    centering).  The shared fit preamble of the mesh-aware estimators.
    """
    n_true = nvalid if nvalid is not None else arrays[0].shape[0]
    out = [padded_shard_rows(a, mesh)[0] for a in arrays]
    if out and out[0].shape[0] != n_true:
        nvalid = n_true
    return out, nvalid
