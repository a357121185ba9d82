"""Checkpoint/restore of fitted pipelines — the load-or-fit pattern,
generalized (reference GaussianMixtureModel.scala:83-90 loads fitted GMM
state from CSV flags; SURVEY §5 calls this the artifact-checkpoint idiom).

KeystoneML got fitted-artifact reuse per node via ad-hoc CSV flags and fault
tolerance from Spark lineage.  Here every node is a registered pytree
(core.pipeline.register_node), so any fitted node — or a whole ``a >> b``
pipeline, or a dict/list bundle of them — serializes generically:

* all array leaves land in ONE ``<stem>.npz`` (host numpy arrays; extended
  dtypes like bfloat16 ride as raw bytes with the true dtype recorded);
* the tree structure goes to a ``<stem>.json`` manifest: a versioned schema
  naming each node class (resolved through ``pipeline.NODE_REGISTRY`` on
  load) plus per-array dtype/shape, validated before any state is touched.

Writes are atomic (tmp file + ``os.replace``) so a preempted save never
leaves a half-written artifact that a later ``load_or_fit`` would trust.

Public surface:
  save_pipeline(path, pipe)   -> writes <stem>.npz + <stem>.json
  load_pipeline(path)         -> rebuilt object (arrays as jax.Arrays)
  load_pipeline(path, mesh=M) -> same, with every array leaf redistributed
                                 onto mesh M (topology-portable restore)
  checkpoint_exists(path)     -> bool (both files present)
  load_or_fit(path, est, *a)  -> load if present, else fit + save
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .pipeline import NODE_REGISTRY, Pipeline

_logger = logging.getLogger("keystone_tpu.checkpoint")

FORMAT_NAME = "keystone-tpu-checkpoint"
FORMAT_VERSION = 1

# dtypes numpy serializes natively inside an .npz; anything else (bfloat16,
# fp8, ...) is stored as raw bytes and re-viewed on load.
_NATIVE_KINDS = frozenset("biufc")

#: Transfer granularity of the reshard loader: arrays larger than this go
#: host-staged shard-by-shard (jax.make_array_from_callback) instead of one
#: whole-array device_put, so the transient footprint of a restore stays
#: bounded even when no single device could stage the whole array.
RESHARD_CHUNK_ENV = "KEYSTONE_RESHARD_CHUNK_BYTES"
_DEFAULT_RESHARD_CHUNK = 64 * 2**20


class CheckpointError(RuntimeError):
    """Unserializable node, missing/corrupt artifact, or schema mismatch."""


class CheckpointMismatch(CheckpointError):
    """The checkpoint was written under a DIFFERENT device/mesh topology
    than the loading process and its arrays were not fully replicated —
    restoring would silently change placement/sharding of a model that was
    solved distributed.  Re-load on the recorded topology, or re-fit."""


def _current_topology() -> dict:
    """Device/mesh fingerprint recorded into every manifest: the platform,
    the visible device count, and the ambient ``use_mesh`` shape (if any)."""
    from ..parallel.mesh import current_mesh

    devs = jax.devices()
    mesh = current_mesh()
    topo = {
        "platform": devs[0].platform,
        "device_count": len(devs),
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }
    # Multi-process runs fingerprint their world size too (the key is
    # omitted single-process so pre-ISSUE-17 checkpoints still compare
    # equal under the topology guard).
    if jax.process_count() > 1:
        topo["processes"] = jax.process_count()
    return topo


def _is_replicated(v) -> bool:
    """True unless ``v`` is a jax.Array actually sharded over >1 device."""
    if not isinstance(v, jax.Array):
        return True
    try:
        return len(v.sharding.device_set) <= 1 or v.is_fully_replicated
    except Exception:  # noqa: BLE001 — unknown sharding: assume sharded
        return False


def _sharding_spec(v) -> str:
    """The autoshard spec string (``'replicated'`` / ``'data@dimN'`` /
    ``'model@dimN'``) an array leaf is laid out as — what the manifest
    records per array so a reshard load can re-lower the SAME layout onto
    whatever mesh survived.  A sharding outside that vocabulary (multi-axis
    partitioning, foreign axis names) records as ``'opaque'``; the reshard
    loader places those replicated."""
    if _is_replicated(v):
        return "replicated"
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    try:
        pspec = tuple(v.sharding.spec)
    except Exception:  # noqa: BLE001 — non-NamedSharding layouts
        return "opaque"
    parts: list[tuple[str, int]] = []
    for i, part in enumerate(pspec):
        names = (
            part if isinstance(part, tuple)
            else ((part,) if part is not None else ())
        )
        parts.extend((str(name), i) for name in names)
    if not parts:
        return "replicated"
    if len(parts) == 1 and parts[0][0] in (DATA_AXIS, MODEL_AXIS):
        return f"{parts[0][0]}@dim{parts[0][1]}"
    return "opaque"


def checkpoint_paths(path: str) -> tuple[str, str]:
    """``path`` is a stem (``.npz``/``.json`` suffixes are stripped if
    given); returns (npz_path, manifest_path)."""
    stem, ext = os.path.splitext(path)
    if ext not in (".npz", ".json"):
        stem = path
    return stem + ".npz", stem + ".json"


def checkpoint_exists(path: str) -> bool:
    npz, manifest = checkpoint_paths(path)
    return os.path.exists(npz) and os.path.exists(manifest)


def _atomic_write_bytes(path: str, data: bytes) -> None:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _is_array(v) -> bool:
    return isinstance(v, (np.ndarray, np.generic, jax.Array))


def _dtype_name(v) -> str | None:
    """Name for a dtype-like meta value (np.dtype, numpy scalar type, or a
    jnp dtype alias like ``jnp.bfloat16``), else None."""
    if isinstance(v, np.dtype):
        return v.name
    if isinstance(v, type) and issubclass(v, np.generic):
        return np.dtype(v).name
    # jnp scalar aliases (jnp.bfloat16 / jnp.float32 ...) are _ScalarMeta
    # instances, not types — the compute/activation dtype knobs nodes like
    # FusedConvFeaturizer and SIFTExtractor carry.  np.dtype() resolves
    # them; decode rebuilds the equivalent numpy scalar TYPE (ml_dtypes
    # for extended floats), which every jnp dtype= site accepts — so a
    # servable pipeline with bf16 activations checkpoints whole.
    if type(v).__name__ == "_ScalarMeta":
        try:
            return np.dtype(v).name
        except TypeError:
            return None
    return None


class _Encoder:
    def __init__(self):
        self.arrays: dict[str, np.ndarray] = {}
        self.specs: dict[str, dict] = {}
        self.all_replicated = True
        self._n = 0

    def add_array(self, v) -> str:
        key = f"a{self._n}"
        self._n += 1
        sharding = _sharding_spec(v)
        if not _is_replicated(v):
            self.all_replicated = False
        with trace.d2h("checkpoint_array", getattr(v, "nbytes", 0)):
            arr = np.asarray(jax.device_get(v))
        spec = {"dtype": arr.dtype.name, "shape": list(arr.shape)}
        if sharding != "replicated":
            # Per-array layout provenance (absent == replicated): the
            # reshard loader re-lowers this spec onto the target mesh.
            spec["sharding"] = sharding
        if arr.dtype.kind not in _NATIVE_KINDS:
            # raw-bytes transport for npz-hostile dtypes (e.g. bfloat16)
            spec["raw"] = True
            arr = np.frombuffer(arr.tobytes(), np.uint8)
        self.arrays[key] = arr
        self.specs[key] = spec
        return key

    def encode(self, v, where: str) -> dict:
        if v is None:
            return {"t": "none"}
        if isinstance(v, (bool, int, float, str)):
            return {"t": "py", "v": v}
        if _is_array(v):
            return {"t": "arr", "k": self.add_array(v)}
        dt = _dtype_name(v)
        if dt is not None:
            return {"t": "dtype", "v": dt, "as_type": not isinstance(v, np.dtype)}
        if isinstance(v, (list, tuple)):
            return {
                "t": "tuple" if isinstance(v, tuple) else "list",
                "v": [self.encode(x, f"{where}[{i}]") for i, x in enumerate(v)],
            }
        if isinstance(v, dict):
            if not all(isinstance(k, str) for k in v):
                raise CheckpointError(f"{where}: dict keys must be strings")
            return {
                "t": "dict",
                "v": {k: self.encode(x, f"{where}[{k!r}]") for k, x in v.items()},
            }
        if isinstance(v, Pipeline):
            return {
                "t": "pipeline",
                "nodes": [
                    self.encode(n, f"{where}.nodes[{i}]")
                    for i, n in enumerate(v.nodes)
                ],
            }
        # BlockLinearMapper registers its pytree manually (solvers.block),
        # so it is looked up by name rather than through NODE_REGISTRY.
        if type(v).__name__ == "BlockLinearMapper":
            return {
                "t": "blm",
                "xs": self.encode(list(v.xs), f"{where}.xs"),
                "b": self.encode(v.b, f"{where}.b"),
                "scalers": self.encode(
                    list(v.feature_scalers), f"{where}.feature_scalers"
                ),
                "block_size": int(v.block_size),
            }
        entry = NODE_REGISTRY.get(type(v).__name__)
        if entry is not None and type(v) is entry[0]:
            _, data_fields, meta_fields = entry
            return {
                "t": "node",
                "cls": type(v).__name__,
                "data": {
                    f: self.encode(getattr(v, f), f"{where}.{f}")
                    for f in data_fields
                },
                "meta": {
                    f: self.encode(getattr(v, f), f"{where}.{f}")
                    for f in meta_fields
                },
            }
        raise CheckpointError(
            f"{where}: cannot serialize {type(v).__name__!r} — not a "
            "registered node (see core.pipeline.register_node) and not a "
            "plain array/scalar/container.  Function-valued nodes "
            "(FunctionTransformer, Cacher with a sharding) hold live Python "
            "objects and are not checkpointable."
        )


def _decode(spec: dict, arrays, array_specs: dict, where: str, put=None) -> Any:
    t = spec.get("t")
    if t == "none":
        return None
    if t == "py":
        return spec["v"]
    if t == "arr":
        key = spec["k"]
        if key not in arrays:
            raise CheckpointError(f"{where}: array {key!r} missing from .npz")
        aspec = array_specs.get(key)
        if aspec is None:
            raise CheckpointError(f"{where}: array {key!r} missing from manifest")
        arr = arrays[key]
        if aspec.get("raw"):
            arr = np.frombuffer(arr.tobytes(), np.dtype(aspec["dtype"])).reshape(
                aspec["shape"]
            )
        if arr.dtype.name != aspec["dtype"] or list(arr.shape) != list(
            aspec["shape"]
        ):
            raise CheckpointError(
                f"{where}: array {key!r} is {arr.dtype.name}{list(arr.shape)}, "
                f"manifest says {aspec['dtype']}{aspec['shape']} — artifact "
                "corrupt or schema drift"
            )
        # ``put`` is the reshard hook (load_pipeline(mesh=)): it places the
        # host array onto the target mesh instead of the default device.
        return put(arr, key, where) if put is not None else jnp.asarray(arr)
    if t == "dtype":
        dt = np.dtype(spec["v"])
        return dt.type if spec.get("as_type") else dt
    if t in ("list", "tuple"):
        vals = [
            _decode(s, arrays, array_specs, f"{where}[{i}]", put)
            for i, s in enumerate(spec["v"])
        ]
        return tuple(vals) if t == "tuple" else vals
    if t == "dict":
        return {
            k: _decode(s, arrays, array_specs, f"{where}[{k!r}]", put)
            for k, s in spec["v"].items()
        }
    if t == "pipeline":
        return Pipeline(
            [
                _decode(s, arrays, array_specs, f"{where}.nodes[{i}]", put)
                for i, s in enumerate(spec["nodes"])
            ]
        )
    if t == "blm":
        from ..solvers.block import BlockLinearMapper

        return BlockLinearMapper(
            list(_decode(spec["xs"], arrays, array_specs, f"{where}.xs", put)),
            int(spec["block_size"]),
            _decode(spec["b"], arrays, array_specs, f"{where}.b", put),
            list(
                _decode(
                    spec["scalers"], arrays, array_specs, f"{where}.scalers", put
                )
            ),
        )
    if t == "node":
        name = spec["cls"]
        entry = NODE_REGISTRY.get(name)
        if entry is None:
            raise CheckpointError(
                f"{where}: node class {name!r} is not registered in this "
                "process — import the module defining it before loading"
            )
        cls, data_fields, meta_fields = entry
        missing = (set(spec["data"]) ^ set(data_fields)) | (
            set(spec["meta"]) ^ set(meta_fields)
        )
        if missing:
            raise CheckpointError(
                f"{where}: field schema of {name!r} changed since this "
                f"checkpoint was written (mismatched fields: {sorted(missing)})"
            )
        # Rebuild exactly the way jax unflattens the pytree: bypass __init__
        # and set the registered fields (core.pipeline.register_node).
        obj = object.__new__(cls)
        for f in data_fields:
            object.__setattr__(
                obj,
                f,
                _decode(spec["data"][f], arrays, array_specs, f"{where}.{f}", put),
            )
        for f in meta_fields:
            object.__setattr__(
                obj,
                f,
                _decode(spec["meta"][f], arrays, array_specs, f"{where}.{f}", put),
            )
        return obj
    raise CheckpointError(f"{where}: unknown manifest entry type {t!r}")


class _Resharder:
    """Redistributes checkpointed host arrays onto a TARGET mesh — the
    ``load_pipeline(mesh=)`` placement engine.

    Per array: the recorded spec (manifest ``"sharding"``) is re-lowered
    onto the new mesh when its named dimension still divides there, else the
    array lands replicated; every placement is charged analytically against
    the target's min per-chip budget (``memory.plan_bytes`` — the
    plan_program-style admission without a compile).  A replicated placement
    denied per-chip falls back to the best dividing spec (the "no common
    device fits a whole array" tier); a placement nothing admits is a TYPED
    ``CheckpointError``, never an OOM mid-restore.  Arrays above
    ``KEYSTONE_RESHARD_CHUNK_BYTES`` transfer host-staged shard-by-shard via
    ``jax.make_array_from_callback`` so the transient footprint stays
    bounded by one shard, not one whole array.

    On a mesh spanning PROCESSES every placement goes through the
    callback path unconditionally (counted ``ckpt_reshard_crosshost``):
    ``make_array_from_callback`` materializes only the shards addressable
    from each process, so every destination host pulls its own slices and
    no single host stages the whole fleet's state — the cross-host
    generalization of the chunked path, with per-host transient bounded
    by that host's largest local shard.  (``device_put`` would refuse the
    non-addressable devices outright; the single-process paths are kept
    unchanged as the fallback.)"""

    def __init__(self, mesh, array_specs: dict, manifest_path: str):
        from . import memory as kmem
        from ..parallel.mesh import mesh_spans_processes

        self.mesh = mesh
        self.crosshost = mesh_spans_processes(mesh)
        self.mesh_shape = dict(mesh.shape)
        self.array_specs = array_specs
        self.manifest_path = manifest_path
        raw = os.environ.get(RESHARD_CHUNK_ENV, "").strip()
        self.chunk_bytes = (
            kmem.parse_bytes(raw) if raw else _DEFAULT_RESHARD_CHUNK
        )
        # One budget read per load: admission below is analytic and the
        # mesh does not change mid-restore.
        self.budget, _ = kmem.min_chip_budget(mesh)
        self.stats = {
            "arrays": 0, "resharded": 0, "host_staged": 0,
            "spec_fallback": 0, "crosshost": 0, "bytes": 0,
        }

    def _target_spec(self, arr: np.ndarray, recorded: str) -> str:
        from . import autoshard

        if recorded not in ("replicated", "opaque"):
            try:
                autoshard.spec_pspec(recorded, arr.ndim)
                autoshard.spec_chip_bytes(
                    arr.shape, arr.dtype, recorded, self.mesh_shape
                )
                return recorded
            except ValueError:
                pass  # recorded dim no longer divides: replicate instead
        return "replicated"

    def put(self, arr: np.ndarray, key: str, where: str):
        from . import autoshard
        from . import memory as kmem

        recorded = self.array_specs.get(key, {}).get("sharding", "replicated")
        spec = self._target_spec(arr, recorded)
        self.stats["arrays"] += 1
        per_chip = autoshard.spec_chip_bytes(
            arr.shape, arr.dtype, spec, self.mesh_shape
        )
        plan = kmem.plan_bytes(
            f"ckpt_reshard:{key}",
            output_bytes=per_chip,
            mesh=self.mesh,
            budget=self.budget,
        )
        if not plan.admitted and spec == "replicated":
            # No chip fits the whole array: shard it instead — the
            # host-staged fallback tier of the reshard ladder.
            cand = autoshard.best_spec(arr, self.mesh_shape)
            if cand["spec"] != "replicated":
                spec = cand["spec"]
                per_chip = int(cand["per_chip_bytes"])
                self.stats["spec_fallback"] += 1
                plan = kmem.plan_bytes(
                    f"ckpt_reshard:{key}:{spec}",
                    output_bytes=per_chip,
                    mesh=self.mesh,
                    budget=self.budget,
                )
        if not plan.admitted:
            raise CheckpointError(
                f"{where}: array {key!r} "
                f"({arr.dtype.name}{list(arr.shape)}) does not fit the "
                f"target mesh — {kmem.fmt_bytes(per_chip)}/chip under spec "
                f"{spec!r} vs budget "
                f"{kmem.fmt_bytes(self.budget or 0)} ({plan.reason})"
            )
        sharding = autoshard.spec_sharding(spec, self.mesh, arr.ndim)
        if spec != "replicated" or recorded != "replicated":
            self.stats["resharded"] += 1
        self.stats["bytes"] += int(arr.nbytes)
        if self.crosshost:
            # Destination-host pull: only the shards addressable from
            # THIS process are materialized by the callback, so state is
            # redistributed across the fleet without staging through one
            # host's RAM.
            self.stats["crosshost"] += 1
            if arr.nbytes > self.chunk_bytes and arr.ndim:
                self.stats["host_staged"] += 1
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: np.asarray(arr[idx])
            )
        if arr.nbytes > self.chunk_bytes and arr.ndim:
            # Host-staged, per-shard transfer: each device receives only
            # its own slice, one shard in flight at a time.
            self.stats["host_staged"] += 1
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )
        return jax.device_put(arr, sharding)


def save_pipeline(path: str, pipe, numerics_baseline: dict | None = None) -> str:
    """Serialize a fitted node / ``Pipeline`` / container of them to
    ``<stem>.npz`` (array leaves) + ``<stem>.json`` (treedef manifest).
    Returns the stem.  Atomic: a crash mid-save leaves no partial artifact.

    ``numerics_baseline``: an optional fit-time output-distribution sketch
    (``core.numerics.OutputSketch.record()``) persisted in the manifest —
    the reference the serving tier's output-drift monitor judges live
    answers against (``serve.load_engine`` arms it on warm load).  Pure
    metadata: it never affects what the pipeline computes.
    """
    with trace.host("write", "save_pipeline"):  # its d2h reads are charged as waits
        npz_path, manifest_path = checkpoint_paths(path)
        enc = _Encoder()
        root = enc.encode(pipe, "root")
        import hashlib
        import io

        buf = io.BytesIO()
        np.savez(buf, **enc.arrays)
        npz_bytes = buf.getvalue()
        manifest = {
            "format": FORMAT_NAME,
            "version": FORMAT_VERSION,
            # Ties the pair together: the two files are replaced in separate
            # atomic renames, so a preemption between them could leave a new
            # .npz next to an old .json (or vice versa) — the hash check on
            # load rejects any mixed pair.
            "npz_sha256": hashlib.sha256(npz_bytes).hexdigest(),
            # Where this checkpoint was solved: the load path refuses to
            # restore NON-replicated arrays onto a different topology (see
            # CheckpointMismatch) instead of silently resharding them.
            "topology": _current_topology(),
            "all_replicated": enc.all_replicated,
            "root": root,
            "arrays": enc.specs,
        }
        if numerics_baseline is not None:
            manifest["numerics_baseline"] = numerics_baseline
        _atomic_write_bytes(npz_path, npz_bytes)
        _atomic_write_bytes(
            manifest_path, json.dumps(manifest, indent=1).encode("utf-8")
        )
        _logger.info(
            "saved checkpoint %s (%d arrays, %.1f KiB)",
            npz_path,
            len(enc.arrays),
            buf.getbuffer().nbytes / 1024,
        )
        return os.path.splitext(npz_path)[0]


def _ensure_standard_registry() -> None:
    """Import the library modules that register the stock node classes, so
    a FRESH process can load a checkpoint without the caller knowing which
    modules define its nodes.  (Out-of-tree nodes still need their defining
    module imported by the caller.)"""
    import importlib

    for mod in (
        "ops.stats", "ops.util", "ops.images", "ops.fisher", "ops.sift",
        "ops.lcs", "ops.hog", "ops.daisy", "ops.conv_fused",
        "solvers.pca", "solvers.gmm", "solvers.linear", "solvers.whitening",
        "solvers.naive_bayes", "solvers.block",
    ):
        try:
            importlib.import_module(f"keystone_tpu.{mod}")
        except ImportError as e:  # pragma: no cover - partial installs
            _logger.warning("registry bootstrap: could not import %s: %s", mod, e)


def load_pipeline(path: str, mesh=None):
    """Rebuild a fitted node/pipeline saved by :func:`save_pipeline`.
    Validates format version and every array's dtype/shape against the
    manifest before constructing anything.

    ``mesh``: the topology-portable restore path.  ``None`` (the default)
    keeps the strict posture — sharded state recorded under a different
    topology raises the typed :class:`CheckpointMismatch` instead of
    resharding silently.  Passing a target ``jax.sharding.Mesh``
    OPTS IN to redistribution: every array leaf is placed onto that mesh
    (its recorded spec re-lowered where it still divides, replicated
    otherwise), each placement admitted per-chip (``memory.plan_bytes``)
    and transferred chunked/host-staged above
    ``KEYSTONE_RESHARD_CHUNK_BYTES`` — see :class:`_Resharder`.  A
    placement no tier admits is a typed ``CheckpointError``."""
    _ensure_standard_registry()
    npz_path, manifest_path = checkpoint_paths(path)
    try:
        with open(manifest_path) as fh:
            manifest = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointError(f"cannot read manifest {manifest_path}: {e}") from e
    if manifest.get("format") != FORMAT_NAME:
        raise CheckpointError(
            f"{manifest_path}: not a {FORMAT_NAME} manifest"
        )
    if manifest.get("version") != FORMAT_VERSION:
        raise CheckpointError(
            f"{manifest_path}: format version {manifest.get('version')} "
            f"(this build reads {FORMAT_VERSION})"
        )
    recorded = manifest.get("topology")
    if mesh is not None:
        pass  # explicit reshard target: the topology guard is satisfied below
    elif recorded is not None and not manifest.get("all_replicated", True):
        # Sharded state is only restorable onto the topology it was
        # solved on; anything else must fail TYPED, not reshard silently.
        current = _current_topology()
        if recorded != current:
            raise CheckpointMismatch(
                f"{manifest_path}: checkpoint holds sharded (non-replicated) "
                f"arrays recorded under topology {recorded} but this process "
                f"is {current} — refusing to silently reshard.  Pass "
                "load_pipeline(..., mesh=<target Mesh>) to redistribute the "
                "state onto the mesh you have, load on the recorded "
                "topology, or re-fit"
            )
    elif recorded is None:
        _logger.warning(
            "%s: no topology recorded (pre-mesh-guard checkpoint) — "
            "loading without a placement check",
            manifest_path,
        )
    import hashlib
    import io

    try:
        with open(npz_path, "rb") as fh:
            npz_bytes = fh.read()
        want_hash = manifest.get("npz_sha256")
        if want_hash is not None:
            got_hash = hashlib.sha256(npz_bytes).hexdigest()
            if got_hash != want_hash:
                raise CheckpointError(
                    f"{npz_path}: content hash does not match the manifest — "
                    "the .npz/.json pair is from two different saves "
                    "(preempted overwrite?)"
                )
        with np.load(io.BytesIO(npz_bytes)) as zf:
            arrays = {k: zf[k] for k in zf.files}
    except (OSError, ValueError) as e:
        raise CheckpointError(f"cannot read arrays {npz_path}: {e}") from e
    extra = set(manifest["arrays"]) - set(arrays)
    if extra:
        raise CheckpointError(
            f"{npz_path}: arrays {sorted(extra)} named in manifest are missing"
        )
    resharder = (
        _Resharder(mesh, manifest["arrays"], manifest_path)
        if mesh is not None
        else None
    )
    obj = _decode(
        manifest["root"], arrays, manifest["arrays"], "root",
        resharder.put if resharder is not None else None,
    )
    if resharder is not None and resharder.stats["arrays"]:
        from ..parallel.mesh import mesh_desc
        from .resilience import counters

        st = resharder.stats
        counters.record(
            "ckpt_reshard",
            f"{npz_path}: {st['arrays']} array(s) "
            f"({st['bytes']} B) placed onto mesh {mesh_desc(mesh)} "
            f"[{st['resharded']} resharded, {st['host_staged']} "
            f"host-staged, {st['spec_fallback']} spec-fallback]",
        )
        if st["crosshost"]:
            counters.record(
                "ckpt_reshard_crosshost",
                f"{npz_path}: {st['crosshost']} array(s) pulled by "
                f"destination hosts across a process-spanning mesh "
                f"{mesh_desc(mesh)}",
            )
        _logger.info(
            "loaded checkpoint %s resharded onto mesh %s (%d arrays, "
            "%d host-staged)",
            npz_path, mesh_desc(mesh), st["arrays"], st["host_staged"],
        )
    else:
        _logger.info("loaded checkpoint %s (%d arrays)", npz_path, len(arrays))
    return obj


def load_numerics_baseline(path: str) -> dict | None:
    """The fit-time output-distribution sketch persisted by
    ``save_pipeline(numerics_baseline=...)``, or None (absent entry,
    pre-observatory artifact, unreadable manifest).  Advisory metadata for
    the drift monitor — this NEVER raises: a missing baseline means an
    unmonitored engine, not a failed load (``load_pipeline`` holds the
    manifest to the strict bar)."""
    _, manifest_path = checkpoint_paths(path)
    try:
        with open(manifest_path) as fh:
            manifest = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        _logger.warning(
            "numerics baseline unreadable from %s (%s)", manifest_path, e
        )
        return None
    baseline = manifest.get("numerics_baseline")
    return dict(baseline) if isinstance(baseline, dict) else None


def load_or_fit(path: str | None, est, *fit_args, save: bool = True, **fit_kwargs):
    """The GMM/PCA CSV-flag pattern generalized: reload the fitted artifact
    at ``path`` if present, else fit and (by default) save it there.

    ``est`` is an Estimator/LabelEstimator (``.fit`` is called with the
    remaining args) or any callable returning the fitted object.  With
    ``path=None`` this is just the fit."""
    if path and checkpoint_exists(path):
        _logger.info("load_or_fit: restoring fitted state from %s", path)
        return load_pipeline(path)
    fit = est.fit if hasattr(est, "fit") else est
    fitted = fit(*fit_args, **fit_kwargs)
    if path and save:
        save_pipeline(path, fitted)
    return fitted
