"""Multi-host fit + serve workload (ISSUE 17): the `jax.distributed`
acceptance surface and the host-loss drill, as one module with three
faces.

* **Worker entries** (``python -m keystone_tpu.workloads.multihost ...``):
  ``fit-serve`` joins the process group, streams ITS tar shards through
  ``core.ingest``, fits a scaler by deterministic rank-ordered moment
  aggregation, checkpoints, cross-host-reshards the checkpoint back onto
  the process-spanning mesh, and serves the fit host-locally;
  ``serve-host`` is one fleet member — host-local ``ShapeRouter`` behind
  a ``WireServer``, driven over stdin by the fleet controller (the
  host-loss re-anchor path).
* **Drivers** (:func:`run_two_process_fit_serve`,
  :func:`run_host_loss_drill`): spawn the workers as REAL subprocesses
  with auto-picked ports and judge the results.  tests/test_multihost.py,
  the chaos ``host_loss`` family and the ``--hosts N`` tools all drive
  these two functions — one implementation, three consumers.

Bit-identity design: XLA's cross-process reductions are NOT bit-identical
to a single-process run, so nothing numerical crosses hosts through XLA.
Each host computes per-shard moment partials with the same local program,
partials are allgathered (exact byte transport) and summed host-side in
fixed rank order — and the single-process reference partitions the same
shard list into the same per-rank groups and sums the same partials in
the same order.  Same values, same op, same order: bit-identical by
construction (see ``parallel.distributed.deterministic_allreduce``).
"""

from __future__ import annotations

import argparse
import glob
import io
import json
import os
import subprocess
import sys
import tarfile
import time

import numpy as np

FEAT_DIM = 8
_TEST_ROWS = 12


# -- synthetic shard tars -----------------------------------------------------


def make_shard_tars(
    dirpath: str,
    shards: int,
    images_per_shard: int,
    seed: int = 0,
    h: int = 48,
    w: int = 48,
) -> list[str]:
    """Deterministic random-texture JPEG tar shards — the dataset every
    fit path (distributed and reference) reads.  One rng stream per
    member, keyed on (seed, shard, image), so the bytes do not depend on
    which host generates or reads them."""
    from PIL import Image as PILImage

    os.makedirs(dirpath, exist_ok=True)
    paths = []
    for s in range(shards):
        path = os.path.join(dirpath, f"shard_{s:03d}.tar")
        with tarfile.open(path, "w") as tf:
            for i in range(images_per_shard):
                rng = np.random.default_rng((seed, s, i))
                arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
                buf = io.BytesIO()
                PILImage.fromarray(arr).save(buf, format="JPEG", quality=90)
                data = buf.getvalue()
                info = tarfile.TarInfo(f"img_{s:03d}_{i:04d}.jpg")
                info.size = len(data)
                tf.addfile(info, io.BytesIO(data))
        paths.append(path)
    return paths


# -- deterministic fit --------------------------------------------------------


def _feat_fn():
    """[b, H, W, C] device batch -> [b, 8] features: per-channel means and
    maxes plus whole-image mean/max.  Elementwise + per-image reductions
    only — one fixed program per batch shape on every host."""
    import jax
    import jax.numpy as jnp

    def feats(x):
        return jnp.concatenate(
            [
                jnp.mean(x, axis=(1, 2)),
                jnp.max(x, axis=(1, 2)),
                jnp.mean(x, axis=(1, 2, 3), keepdims=False)[:, None],
                jnp.max(x, axis=(1, 2, 3), keepdims=False)[:, None],
            ],
            axis=1,
        )

    return jax.jit(feats)


def moments_for_shards(shard_paths, batch: int = 4) -> np.ndarray:
    """One host's (or one emulated rank's) moment partial over its shard
    list, packed ``[sum(8), sumsq(8), count]`` float32.  Shards are
    streamed through ``core.ingest`` in sorted order and accumulated
    host-side in that order — the partial is a pure function of the shard
    list, independent of which process computes it."""
    from keystone_tpu.core import ingest
    from keystone_tpu.workloads.fv_common import scatter_features_streaming

    feat = _feat_fn()
    s = np.zeros(FEAT_DIM, np.float32)
    sq = np.zeros(FEAT_DIM, np.float32)
    n = 0
    for tar in sorted(shard_paths):
        with ingest.stream_batches(tar, batch) as st:
            feats, _names = scatter_features_streaming(st, feat, FEAT_DIM)
        if not st.join(10.0):
            raise RuntimeError(f"{tar}: ingest threads did not exit")
        s += feats.sum(axis=0, dtype=np.float32)
        sq += (feats * feats).sum(axis=0, dtype=np.float32)
        n += feats.shape[0]
    return np.concatenate([s, sq, [np.float32(n)]]).astype(np.float32)


def fit_from_moments(packed: np.ndarray):
    """``(mean, std)`` float32 from the reduced moments — the
    ``StandardScaler`` math (sample variance, degenerate-std guard) in
    host numpy so every rank derives bitwise-identical parameters from
    the bitwise-identical reduced moments."""
    s = packed[:FEAT_DIM].astype(np.float32)
    sq = packed[FEAT_DIM : 2 * FEAT_DIM].astype(np.float32)
    n = np.float32(packed[-1])
    mean = (s / n).astype(np.float32)
    var = ((sq - n * mean * mean) / (n - np.float32(1.0))).astype(np.float32)
    with np.errstate(invalid="ignore"):
        std = np.sqrt(var).astype(np.float32)
    bad = ~np.isfinite(std) | (np.abs(std) < np.float32(1e-12))
    std = np.where(bad, np.float32(1.0), std).astype(np.float32)
    return mean, std


def test_rows(seed: int) -> np.ndarray:
    return np.asarray(
        np.random.default_rng((seed, 7)).normal(size=(_TEST_ROWS, FEAT_DIM)),
        np.float32,
    )


# -- worker: fit-serve --------------------------------------------------------


def fit_serve_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="multihost fit-serve")
    ap.add_argument("--shards-dir", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument(
        "--emulate-world", type=int, default=None,
        help="single-process reference: partition shards into this many "
        "rank groups and sum their partials in rank order",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.checkpoint import load_pipeline, save_pipeline
    from keystone_tpu.core.resilience import counters
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.parallel import distributed as kdist
    from keystone_tpu.parallel import mesh as kmesh

    t_start = time.monotonic()
    env_world = int(os.environ.get(kdist.PROCS_ENV, "1") or 1)
    if env_world > 1:
        st = kdist.init_process_group()
        world, rank = st.world, st.rank
    else:
        st, world, rank = None, 1, 0
    record: dict = {"world": world, "rank": rank, "pid": os.getpid()}

    shards = sorted(glob.glob(os.path.join(args.shards_dir, "*.tar")))
    if not shards:
        raise SystemExit(f"no tar shards under {args.shards_dir}")
    from keystone_tpu.core.ingest import host_shards

    t0 = time.monotonic()
    if st is not None and st.jax_initialized and world > 1:
        mine = host_shards(shards)
        partial = moments_for_shards(mine, args.batch)
        total = kdist.deterministic_allreduce(partial)
        record["my_shards"] = [os.path.basename(p) for p in mine]
    else:
        ew = max(1, args.emulate_world or 1)
        parts = [
            moments_for_shards(host_shards(shards, r, ew), args.batch)
            for r in range(ew)
        ]
        total = np.stack(parts, axis=0).sum(axis=0)
        record["emulated_world"] = ew
    mean, std = fit_from_moments(total)
    record["fit_wall_s"] = round(time.monotonic() - t0, 4)
    record["n_images"] = int(total[-1])
    record["mean"] = mean.tolist()
    record["std"] = std.tolist()

    model = StandardScalerModel(jnp.asarray(mean), jnp.asarray(std))
    rows = test_rows(args.seed)
    record["predictions"] = np.asarray(model(jnp.asarray(rows))).tolist()

    if args.ckpt:
        local = kmesh.host_local_mesh()
        if rank == 0:
            # Anchor the mean SHARDED so the manifest records a real
            # non-replicated spec the cross-host reshard must re-lower.
            anchored = StandardScalerModel(
                jax.device_put(
                    jnp.asarray(mean),
                    NamedSharding(local, P(kmesh.DATA_AXIS)),
                ),
                jnp.asarray(std),
            )
            with kmesh.use_mesh(local):
                save_pipeline(args.ckpt, anchored)
        kdist.barrier("ckpt_saved")
        if st is not None and st.jax_initialized and world > 1:
            gmesh = kmesh.make_mesh()  # global devices: the spanning mesh
            record["global_mesh"] = kmesh.mesh_desc(gmesh)
            record["mesh_spans"] = kmesh.mesh_spans_processes(gmesh)
            before = counters.get("ckpt_reshard_crosshost")
            t1 = time.monotonic()
            resumed = load_pipeline(args.ckpt, mesh=gmesh)
            record["reshard_wall_s"] = round(time.monotonic() - t1, 4)
            record["crosshost_reshard"] = (
                counters.get("ckpt_reshard_crosshost") - before
            )
            # Every shard addressable HERE must hold exactly the fit's
            # bytes — the redistribution is verified without any
            # cross-process compute.
            equal = True
            for shard in resumed.mean.addressable_shards:
                want = mean[shard.index]
                if not np.array_equal(np.asarray(shard.data), want):
                    equal = False
            record["crosshost_bit_equal"] = bool(equal)
            kdist.barrier("resumed")

    # Serve host-locally (engines never span hosts).
    t2 = time.monotonic()
    engine = kserve.ServingEngine(
        model,
        np.zeros(FEAT_DIM, np.float32),
        config=kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0),
        label=f"mh{rank}",
        mesh=kmesh.host_local_mesh(),
    )
    with kserve.Server(engine) as server:
        futures = [server.submit(r) for r in rows]
        served = np.stack([f.result(30.0) for f in futures])
    record["served"] = served.tolist()
    record["serve_wall_s"] = round(time.monotonic() - t2, 4)
    record["parity_ok"] = bool(engine.parity_ok)

    if st is not None and st.jax_initialized:
        record["leaked_threads"] = kdist.shutdown_process_group()
    record["wall_s"] = round(time.monotonic() - t_start, 4)
    record["counters"] = counters.snapshot()
    with open(args.out, "w") as fh:
        json.dump(record, fh)
    return 0


# -- worker: serve-host -------------------------------------------------------


def serve_host_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="multihost serve-host")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--buckets", default="1,2,4")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core import trace
    from keystone_tpu.core import wire as kwire
    from keystone_tpu.core.checkpoint import load_pipeline
    from keystone_tpu.core.resilience import counters
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.parallel import distributed as kdist
    from keystone_tpu.parallel import mesh as kmesh

    st = kdist.init_process_group(use_jax=False)  # fleet membership only
    rank = st.rank
    buckets = tuple(int(b) for b in args.buckets.split(","))
    state: dict = {}

    def load_model():
        if args.ckpt:
            return load_pipeline(args.ckpt, mesh=kmesh.host_local_mesh())
        rng = np.random.default_rng((args.seed, 11))
        return StandardScalerModel(
            jnp.asarray(rng.normal(size=FEAT_DIM).astype(np.float32)),
            jnp.asarray(
                (np.abs(rng.normal(size=FEAT_DIM)) + 0.5).astype(np.float32)
            ),
        )

    state["model"] = load_model()

    def build(shape, dtype, mesh_or_none):
        return kserve.ServingEngine(
            state["model"],
            np.zeros(shape, dtype),
            config=kserve.ServeConfig(buckets=buckets, max_wait_ms=2.0),
            label=f"host{rank}:{'x'.join(str(d) for d in shape)}",
            mesh=mesh_or_none,
        )

    factory = kfrontend.MeshEngineFactory(build, mesh=kmesh.host_local_mesh())
    router = kfrontend.ShapeRouter(factory, label=f"host{rank}")
    router.add_engine(factory((FEAT_DIM,), np.float32))
    server = kwire.WireServer(router, port=0, label=f"host{rank}")
    print(
        json.dumps({"rank": rank, "port": server.port, "pid": os.getpid()}),
        flush=True,
    )

    rc = 0
    try:
        for line in sys.stdin:
            parts = line.strip().split()
            if not parts:
                continue
            cmd = parts[0]
            if cmd == "quit":
                break
            if cmd == "peer_lost":
                # The controller (the front-end's liveness detection) says
                # these ORIGINAL ranks survive: re-form the reduced group,
                # redistribute the checkpointed state onto this host, and
                # hot-swap every engine — zero request loss, counted.
                survivors = [int(p) for p in parts[1:]]
                t0 = time.monotonic()
                new = kdist.reform_group(survivors)
                state["model"] = load_model()
                info = router.reanchor(
                    kmesh.host_local_mesh(),
                    why=f"host loss (group epoch {new.epoch}, "
                    f"lost {list(new.lost)})",
                )
                wall = round(time.monotonic() - t0, 4)
                counters.record(
                    "host_reanchor",
                    f"host{rank}: survivors={survivors} "
                    f"world={new.world} wall={wall}s",
                )
                print(
                    json.dumps(
                        {
                            "ack": "peer_lost",
                            "world": new.world,
                            "epoch": new.epoch,
                            "reanchor_wall_s": wall,
                            "swapped": len(info.get("swapped", [])),
                            "failed": len(info.get("failed", [])),
                        }
                    ),
                    flush=True,
                )
            elif cmd == "stats":
                print(
                    json.dumps({"stats": {"counters": counters.snapshot()}}),
                    flush=True,
                )
    except (BrokenPipeError, KeyboardInterrupt):  # controller died
        rc = 1
    finally:
        server.close()
        router.close()
        final = {
            "final": {
                "rank": rank,
                "counters": counters.snapshot(),
                "wire": dataclasses_asdict_safe(server.stats),
            }
        }
        print(json.dumps(final), flush=True)
        if trace.enabled():
            trace.flush()
    return rc


def dataclasses_asdict_safe(obj) -> dict:
    import dataclasses

    try:
        return dataclasses.asdict(obj)
    except TypeError:
        return {}


# -- drivers ------------------------------------------------------------------


def _worker_cmd(mode: str, extra) -> list[str]:
    return [sys.executable, "-m", "keystone_tpu.workloads.multihost", mode, *extra]


def _hermetic_env(env: dict, *, trace_path=None) -> dict:
    """Spawned workers must not write the parent's trace."""
    env = dict(env)
    if trace_path is None:
        env.pop("KEYSTONE_TRACE", None)
    else:
        env["KEYSTONE_TRACE"] = trace_path
    return env


def run_two_process_fit_serve(
    tmpdir: str,
    *,
    shards_per_host: int = 2,
    images_per_shard: int = 6,
    seed: int = 0,
    local_devices: int = 2,
    timeout_s: float = 300.0,
) -> dict:
    """The tentpole acceptance run: a REAL 2-process ``jax.distributed``
    CPU fit+serve (auto-picked coordinator port, per-host tar shards,
    cross-host checkpoint reshard) against the single-process reference on
    the same data — judged bit-identical.  Returns the judged record;
    raises on timeout or a worker that died."""
    from keystone_tpu.parallel import distributed as kdist

    world = 2
    shard_dir = os.path.join(tmpdir, "mh_shards")
    make_shard_tars(
        shard_dir, world * shards_per_host, images_per_shard, seed
    )
    ckpt = os.path.join(tmpdir, "mh_ckpt")
    outs = {
        "ref": os.path.join(tmpdir, "mh_ref.json"),
        0: os.path.join(tmpdir, "mh_rank0.json"),
        1: os.path.join(tmpdir, "mh_rank1.json"),
    }
    coord = kdist.pick_coordinator()
    t0 = time.monotonic()
    procs = {}
    common = ["--shards-dir", shard_dir, "--seed", str(seed)]
    procs["ref"] = subprocess.Popen(
        _worker_cmd(
            "fit-serve",
            [*common, "--out", outs["ref"], "--emulate-world", str(world)],
        ),
        env=_hermetic_env(
            kdist.worker_env(0, 1, "", local_devices=local_devices),
        ),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    for r in range(world):
        procs[r] = subprocess.Popen(
            _worker_cmd(
                "fit-serve", [*common, "--out", outs[r], "--ckpt", ckpt]
            ),
            env=_hermetic_env(
                kdist.worker_env(
                    r, world, coord, local_devices=local_devices
                ),
            ),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
    tails = {}
    for key, p in procs.items():
        left = max(5.0, timeout_s - (time.monotonic() - t0))
        try:
            out, err = p.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            for q in procs.values():
                q.kill()
            raise TimeoutError(
                f"fit-serve worker {key} exceeded {timeout_s}s"
            ) from None
        tails[key] = (out or "")[-2000:] + (err or "")[-2000:]
        if p.returncode != 0:
            raise RuntimeError(
                f"fit-serve worker {key} died rc={p.returncode}: {tails[key]}"
            )
    records = {}
    for key, path in outs.items():
        with open(path) as fh:
            records[key] = json.load(fh)
    ref, r0, r1 = records["ref"], records[0], records[1]
    judged = {
        "world": world,
        "coordinator": coord,
        "wall_s": round(time.monotonic() - t0, 3),
        "fit_serve_wall_s": max(r0["wall_s"], r1["wall_s"]),
        "reshard_wall_s": max(
            r0.get("reshard_wall_s", 0.0), r1.get("reshard_wall_s", 0.0)
        ),
        "n_images": r0["n_images"],
        "bit_identical": (
            ref["predictions"] == r0["predictions"] == r1["predictions"]
            and ref["served"] == r0["served"] == r1["served"]
            and ref["mean"] == r0["mean"]
            and ref["std"] == r0["std"]
        ),
        "crosshost_reshard": min(
            r0.get("crosshost_reshard", 0), r1.get("crosshost_reshard", 0)
        ),
        "crosshost_bit_equal": bool(
            r0.get("crosshost_bit_equal") and r1.get("crosshost_bit_equal")
        ),
        "mesh_spans": bool(r0.get("mesh_spans") and r1.get("mesh_spans")),
        "leaked_threads": sorted(
            set(r0.get("leaked_threads", []) + r1.get("leaked_threads", []))
        ),
        "parity_ok": bool(
            ref["parity_ok"] and r0["parity_ok"] and r1["parity_ok"]
        ),
        "records": records,
    }
    return judged


# -- host-loss drill ----------------------------------------------------------


class _WorkerIO:
    """One serve-host subprocess with a draining stdout reader: every
    JSON line lands in a queue (a stalled parent can never deadlock the
    worker on a full pipe), stderr goes to a file for the postmortem."""

    def __init__(self, cmd, env, stderr_path: str):
        import queue
        import threading

        self.stderr_path = stderr_path
        self._err_fh = open(stderr_path, "w")
        self.proc = subprocess.Popen(
            cmd, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._err_fh, text=True, bufsize=1,
        )
        self.lines: "queue.Queue" = queue.Queue()
        self._reader = threading.Thread(
            target=self._drain, name="mh-worker-stdout", daemon=True
        )
        self._reader.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            line = line.strip()
            if not line:
                continue
            try:
                self.lines.put(json.loads(line))
            except json.JSONDecodeError:
                pass  # stray library output: not protocol
        self.lines.put(None)  # EOF marker

    def expect(self, key: str, timeout_s: float) -> dict:
        import queue

        end = time.monotonic() + timeout_s
        while True:
            left = end - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"worker pid {self.proc.pid}: no {key!r} message within "
                    f"{timeout_s}s (see {self.stderr_path})"
                )
            try:
                msg = self.lines.get(timeout=min(left, 0.5))
            except queue.Empty:
                continue
            if msg is None:
                raise RuntimeError(
                    f"worker pid {self.proc.pid} exited before sending "
                    f"{key!r} (rc={self.proc.poll()}, "
                    f"see {self.stderr_path})"
                )
            if key in msg:
                return msg

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def kill(self) -> None:
        self.proc.kill()

    def finish(self, timeout_s: float = 20.0) -> int:
        try:
            self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=5.0)
        self._err_fh.close()
        return self.proc.returncode


def _drill_model(seed: int):
    """The drill's deterministic scaler + its offline oracle answers."""
    import jax.numpy as jnp

    from keystone_tpu.ops.stats import StandardScalerModel

    rng = np.random.default_rng((seed, 11))
    mean = rng.normal(size=FEAT_DIM).astype(np.float32)
    std = (np.abs(rng.normal(size=FEAT_DIM)) + 0.5).astype(np.float32)
    model = StandardScalerModel(jnp.asarray(mean), jnp.asarray(std))
    return mean, std, model


def _drill_ckpt(tmpdir: str, seed: int, mean, std) -> str:
    """Checkpoint the scaler with its mean SHARDED under the controller's
    mesh, so every host's restore is a real reshard (and a naive load a
    typed refusal)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.core.checkpoint import save_pipeline
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.parallel import mesh as kmesh

    devs = jax.devices()
    width = max(d for d in (4, 2, 1) if len(devs) >= d and FEAT_DIM % d == 0)
    pmesh = kmesh.make_mesh(data=width, model=1, devices=devs[:width])
    anchored = StandardScalerModel(
        jax.device_put(
            jnp.asarray(mean), NamedSharding(pmesh, P(kmesh.DATA_AXIS))
        ),
        jnp.asarray(std),
    )
    stem = os.path.join(tmpdir, "drill_ckpt")
    with kmesh.use_mesh(pmesh):
        save_pipeline(stem, anchored)
    return stem


def _drive_fleet(fleet, rows, results, errors, *, indices=None, threads=4):
    """Continuous concurrent traffic: a thread pool drains an index queue
    through ``fleet.predict`` so requests are ALWAYS in flight while the
    controller kills a host.  Returns the pool's join callable."""
    import queue
    import threading

    idx_q: "queue.Queue" = queue.Queue()
    for i in range(len(rows)) if indices is None else indices:
        idx_q.put(i)

    def work():
        while True:
            try:
                i = idx_q.get_nowait()
            except queue.Empty:
                return
            try:
                results[i] = np.asarray(fleet.predict(rows[i]))
            except Exception as e:  # noqa: BLE001 — judged by the oracle
                errors.append((i, f"{type(e).__name__}: {e}"))

    pool = [
        threading.Thread(target=work, name=f"drill-client-{t}", daemon=True)
        for t in range(threads)
    ]
    for t in pool:
        t.start()

    def join(timeout_s: float) -> bool:
        end = time.monotonic() + timeout_s
        for t in pool:
            t.join(max(0.1, end - time.monotonic()))
        return not any(t.is_alive() for t in pool)

    return join


def _answered(results) -> int:
    return sum(1 for r in results if r is not None)


def _wait_answered(results, target: int, timeout_s: float) -> None:
    end = time.monotonic() + timeout_s
    while _answered(results) < target:
        if time.monotonic() >= end:
            raise TimeoutError(
                f"only {_answered(results)}/{target} answers within "
                f"{timeout_s}s"
            )
        time.sleep(0.005)


def _stitch_worker_trace(path: str, host: int) -> int:
    """Re-emit a dead-or-done worker's counted-fault instants onto the
    controller's trace timeline (host-tagged) — the stitched trace shows
    the fleet's faults, not just the controller's."""
    from keystone_tpu.core import trace

    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return 0
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else doc
    n = 0
    for ev in events:
        if ev.get("ph") == "i" and ev.get("name") == "fault":
            kind = ev.get("args", {}).get("kind")
            if kind:
                trace.instant(
                    "fault", kind=kind, host=host, stitched=True,
                    detail=ev.get("args", {}).get("detail", ""),
                )
                n += 1
    return n


def run_host_loss_drill(
    tmpdir: str,
    *,
    hosts: int = 2,
    requests: int = 30,
    seed: int = 0,
    local_devices: int = 2,
    subprocess_mode: bool | None = None,
    timeout_s: float = 240.0,
) -> dict:
    """Kill one serving host mid-flight and judge the invariant: every
    request answered bit-equal to the offline oracle, the loss counted
    (``fleet_host_lost``), the survivors re-formed (``dist_reform``) and
    re-anchored (``host_reanchor``, postmortem-linked) — never a silent
    wrong answer, never a dropped request.

    ``subprocess_mode=True`` (default where :func:`spawn_available`) runs
    each host as a REAL subprocess serving over the wire and SIGKILLs
    one; ``False`` degrades to in-process wire servers with an abrupt
    socket close standing in for the death — the same fleet/failover/
    re-anchor code paths on hosts without spawn."""
    from keystone_tpu.parallel import distributed as kdist

    if subprocess_mode is None:
        subprocess_mode = kdist.spawn_available()
    if hosts < 2:
        raise ValueError("the drill needs >= 2 hosts (one must die)")

    import jax.numpy as jnp

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import trace
    from keystone_tpu.core.resilience import counters

    mean, std, model = _drill_model(seed)
    stem = _drill_ckpt(tmpdir, seed, mean, std)
    rows = np.asarray(
        np.random.default_rng((seed, 13)).normal(size=(requests, FEAT_DIM)),
        np.float32,
    )
    expected = np.asarray(model(jnp.asarray(rows)))

    pm_dir = os.path.join(tmpdir, "postmortems")
    os.makedirs(pm_dir, exist_ok=True)
    old_pm = os.environ.get("KEYSTONE_POSTMORTEM_DIR")
    os.environ["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
    kill_rank = hosts - 1
    survivors = [r for r in range(hosts) if r != kill_rank]
    t_start = time.monotonic()
    record: dict = {
        "mode": "subprocess" if subprocess_mode else "inprocess",
        "hosts": hosts,
        "kill_rank": kill_rank,
        "requests": requests,
    }
    try:
        if subprocess_mode:
            _run_drill_subprocess(
                record, tmpdir, stem, seed, hosts, kill_rank, survivors,
                rows, expected, local_devices, timeout_s, kdist, kfrontend,
                counters,
            )
        else:
            _run_drill_inprocess(
                record, stem, seed, hosts, kill_rank, survivors, rows,
                expected, timeout_s, kdist, kfrontend, counters,
            )
    finally:
        if old_pm is None:
            os.environ.pop("KEYSTONE_POSTMORTEM_DIR", None)
        else:
            os.environ["KEYSTONE_POSTMORTEM_DIR"] = old_pm
    record["postmortems"] = sorted(os.listdir(pm_dir))
    record["wall_s"] = round(time.monotonic() - t_start, 3)
    trace.instant(
        "host_loss_drill", mode=record["mode"], hosts=hosts,
        dropped=record["dropped_requests"],
        mismatches=record["mismatches"],
    )
    return record


def _judge_answers(record, results, errors, expected) -> None:
    mismatches = [
        i
        for i, r in enumerate(results)
        if r is not None and not np.array_equal(r, expected[i])
    ]
    record["answered"] = _answered(results)
    record["dropped_requests"] = (
        len(results) - record["answered"]
    )
    record["errors"] = [e for _, e in errors][:8]
    record["mismatches"] = len(mismatches)


def _run_drill_subprocess(
    record, tmpdir, stem, seed, hosts, kill_rank, survivors, rows,
    expected, local_devices, timeout_s, kdist, kfrontend, counters,
) -> None:
    pm_dir = os.environ["KEYSTONE_POSTMORTEM_DIR"]
    workers: list[_WorkerIO] = []
    trace_paths = {}
    try:
        for r in range(hosts):
            trace_paths[r] = os.path.join(tmpdir, f"drill_host{r}.json")
            env = _hermetic_env(
                kdist.worker_env(
                    r, hosts, "controller", local_devices=local_devices
                ),
                trace_path=trace_paths[r],
            )
            env["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
            workers.append(
                _WorkerIO(
                    _worker_cmd(
                        "serve-host",
                        ["--ckpt", stem, "--seed", str(seed)],
                    ),
                    env,
                    os.path.join(tmpdir, f"drill_host{r}.err"),
                )
            )
        up = [w.expect("port", timeout_s / 2) for w in workers]
        endpoints = [("127.0.0.1", msg["port"]) for msg in up]

        n = len(rows)
        results: list = [None] * n
        errors: list = []
        with kfrontend.HostFleet(endpoints, label="drill") as fleet:
            join = _drive_fleet(fleet, rows, results, errors)
            # Mid-flight: requests are streaming when the host dies.
            _wait_answered(results, n // 3, timeout_s / 4)
            workers[kill_rank].kill()
            record["killed_at_answered"] = _answered(results)
            _wait_answered(results, (2 * n) // 3, timeout_s / 2)
            # The controller's liveness verdict reaches the survivors:
            # re-form the reduced group, reshard, re-anchor — under the
            # traffic that is still flowing.
            acks = {}
            for r in survivors:
                workers[r].send(
                    "peer_lost " + " ".join(str(s) for s in survivors)
                )
            for r in survivors:
                acks[r] = workers[r].expect("ack", timeout_s / 2)
                counters.record(
                    "host_reanchor",
                    f"controller: host{r} re-anchored after losing "
                    f"host{kill_rank} "
                    f"(wall {acks[r].get('reanchor_wall_s')}s, "
                    f"{acks[r].get('swapped')} engine(s))",
                )
            record["acks"] = acks
            if not join(timeout_s / 2):
                raise TimeoutError("drill clients did not drain")
            record["fleet"] = fleet.record()
        finals = {}
        for r in survivors:
            workers[r].send("quit")
            finals[r] = workers[r].expect("final", timeout_s / 4)["final"]
        record["survivor_counters"] = {
            r: finals[r]["counters"] for r in survivors
        }
        record["reanchor_wall_s"] = max(
            float(acks[r].get("reanchor_wall_s") or 0.0) for r in survivors
        )
    finally:
        rcs = [w.finish() for w in workers]
        record["worker_rcs"] = rcs
    record["stitched_events"] = sum(
        _stitch_worker_trace(trace_paths[r], r) for r in survivors
    )
    _judge_answers(record, results, errors, expected)


def _run_drill_inprocess(
    record, stem, seed, hosts, kill_rank, survivors, rows, expected,
    timeout_s, kdist, kfrontend, counters,
) -> None:
    import jax

    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core import wire as kwire
    from keystone_tpu.core.checkpoint import load_pipeline
    from keystone_tpu.parallel import mesh as kmesh

    devs = jax.devices()
    per = max(1, min(2, len(devs) // hosts))
    fleet_group = kdist.is_initialized()
    if not fleet_group:
        kdist.init_process_group(
            coordinator="controller", world=hosts, rank=0, use_jax=False
        )
    routers, servers = [], []
    try:
        meshes = [
            kmesh.make_mesh(
                data=per, model=1, devices=devs[r * per : (r + 1) * per]
            )
            for r in range(hosts)
        ]
        for r in range(hosts):
            model_r = load_pipeline(stem, mesh=meshes[r])
            state = {"model": model_r}

            def build(shape, dtype, mesh_or_none, _state=state, _r=r):
                return kserve.ServingEngine(
                    _state["model"],
                    np.zeros(shape, dtype),
                    config=kserve.ServeConfig(buckets=(1, 2, 4), max_wait_ms=2.0),
                    label=f"inhost{_r}:{'x'.join(str(d) for d in shape)}",
                    mesh=mesh_or_none,
                )

            factory = kfrontend.MeshEngineFactory(build, mesh=meshes[r])
            router = kfrontend.ShapeRouter(factory, label=f"inhost{r}")
            router.add_engine(factory((FEAT_DIM,), np.float32))
            routers.append(router)
            servers.append(kwire.WireServer(router, port=0, label=f"inhost{r}"))
        endpoints = [("127.0.0.1", s.port) for s in servers]

        n = len(rows)
        results: list = [None] * n
        errors: list = []
        with kfrontend.HostFleet(endpoints, label="drill") as fleet:
            # Two waves: in-process serving is fast enough that a single
            # stream can fully drain before the close lands, so the
            # post-loss continuity is driven explicitly — wave 2 hits the
            # dead endpoint (round-robin), gets marked lost, reissues.
            join = _drive_fleet(fleet, rows, results, errors,
                                indices=range(n // 2))
            if not join(timeout_s / 4):
                raise TimeoutError("drill wave 1 did not drain")
            # The abrupt stand-in for SIGKILL: the dead host's sockets
            # close under its clients; its router is simply abandoned.
            servers[kill_rank].close()
            record["killed_at_answered"] = _answered(results)
            join = _drive_fleet(fleet, rows, results, errors,
                                indices=range(n // 2, n))
            new = kdist.reform_group([0])
            t0 = time.monotonic()
            for r in survivors:
                info = routers[r].reanchor(
                    meshes[r],
                    why=f"host loss (group epoch {new.epoch})",
                )
                counters.record(
                    "host_reanchor",
                    f"controller: inhost{r} re-anchored after losing "
                    f"inhost{kill_rank} ({len(info['swapped'])} engine(s))",
                )
            record["reanchor_wall_s"] = round(time.monotonic() - t0, 4)
            if not join(timeout_s / 2):
                raise TimeoutError("drill wave 2 did not drain")
            record["fleet"] = fleet.record()
        record["survivor_counters"] = {
            r: counters.snapshot() for r in survivors
        }
        record["stitched_events"] = 0
    finally:
        for r, s in enumerate(servers):
            if r != kill_rank:
                s.close()
        for r, router in enumerate(routers):
            router.close()
        kdist.shutdown_process_group()
    _judge_answers(record, results, errors, expected)


# -- obs-capture drill --------------------------------------------------------


def _pooled_p99_oracle(record, direct, fleet_hists) -> None:
    """Acceptance (b): the collector's fleet p99 must equal the pick-rule
    percentile of the POOLED per-member samples, and sit near the numpy
    linear-interpolation percentile of the same pool."""
    metric = None
    for cand in ("serve_latency_ms", "wire_request_ms"):
        if any(
            cand in (d.get("hist_windows") or {}) for d in direct.values()
        ):
            metric = cand
            break
    record["pooled_metric"] = metric
    if metric is None:
        record["p99_match"] = False
        return
    pool = [
        float(s)
        for d in direct.values()
        for s in d["hist_windows"].get(metric, {}).get("samples", ())
    ]
    pool.sort()
    record["p99_pool_n"] = len(pool)
    fleet_p99 = fleet_hists.get(metric, {}).get("p99")
    pick = pool[min(len(pool) - 1, int(0.99 * len(pool)))] if pool else None
    p99_np = float(np.percentile(pool, 99)) if pool else None
    record["p99_fleet"] = fleet_p99
    record["p99_oracle_pick"] = pick
    record["p99_oracle_np"] = p99_np
    record["p99_match"] = (
        fleet_p99 is not None
        and fleet_p99 == pick
        and abs(fleet_p99 - p99_np) <= max(0.25 * abs(p99_np), 1e-6)
    )


def _counter_sum_check(record, snap, direct) -> None:
    """Acceptance (a): fleet counters == the sum of per-member snapshots,
    key for key, both directions."""
    sums: dict = {}
    for d in direct.values():
        stz = d.get("statusz", {})
        for group in ("counters", "faults"):
            for k, v in (stz.get(group) or {}).items():
                sums[k] = sums.get(k, 0) + v
    fleet = dict(snap.get("counters", {}))
    for k, v in snap.get("faults", {}).items():
        fleet[k] = fleet.get(k, 0) + v
    mismatched = {
        k: (fleet.get(k), sums.get(k))
        for k in set(fleet) | set(sums)
        if fleet.get(k, 0) != sums.get(k, 0)
    }
    record["counter_sum_ok"] = not mismatched
    if mismatched:
        record["counter_sum_mismatch"] = {
            k: list(v) for k, v in sorted(mismatched.items())[:8]
        }


def _judge_incident(record, bundle_path, survivor_keys) -> None:
    """Acceptance (c): ONE bundle, every surviving member's ring present
    and non-empty, events on one monotone clock-aligned timeline."""
    with open(bundle_path) as fh:
        doc = json.load(fh)
    members = doc.get("members", {})
    ts = [
        ev["ts"]
        for ev in doc.get("events", [])
        if isinstance(ev.get("ts"), (int, float))
    ]
    record["incident"] = {
        "path": bundle_path,
        "schema": doc.get("schema"),
        "trigger": doc.get("trigger", {}).get("kind"),
        "capture_wall_s": doc.get("capture_wall_s"),
        "members": sorted(members),
        "missing": doc.get("missing", []),
        "n_events": len(doc.get("events", [])),
        "survivor_rings_ok": all(
            k in members and members[k].get("events", 0) > 0
            for k in survivor_keys
        ),
        "events_monotone": ts == sorted(ts),
    }


def run_obs_capture_drill(
    tmpdir: str,
    *,
    hosts: int = 2,
    requests: int = 18,
    seed: int = 0,
    local_devices: int = 2,
    subprocess_mode: bool | None = None,
    timeout_s: float = 240.0,
) -> dict:
    """The fleet-observability acceptance drill (ISSUE 20): serve across
    N members with a :class:`~..core.fleetobs.FleetCollector` attached,
    prove on a QUIET fleet that (a) fleet counters equal the sum of
    per-member snapshots and (b) fleet p99 comes from the pooled sample
    windows, then SIGKILL one member mid-scrape and prove (c) the
    collector degrades (``obs_member_lost``), stays monotone for the
    survivors, and writes ONE clock-aligned incident bundle holding
    every surviving member's flight ring — while every request still
    answers bit-equal to the offline oracle (collection must not touch
    the serving answers).

    ``subprocess_mode=False`` degrades to in-process wire servers (one
    process, N sockets) with an abrupt socket close standing in for the
    SIGKILL — the same collector/merge/incident code paths on hosts
    without spawn."""
    from keystone_tpu.parallel import distributed as kdist

    if subprocess_mode is None:
        subprocess_mode = kdist.spawn_available()
    if hosts < 2:
        raise ValueError("the drill needs >= 2 hosts (one must die)")

    import jax.numpy as jnp

    from keystone_tpu.core import frontend as kfrontend
    from keystone_tpu.core import trace
    from keystone_tpu.core.resilience import counters

    mean, std, model = _drill_model(seed)
    stem = _drill_ckpt(tmpdir, seed, mean, std)
    rows = np.asarray(
        np.random.default_rng((seed, 17)).normal(size=(requests, FEAT_DIM)),
        np.float32,
    )
    expected = np.asarray(model(jnp.asarray(rows)))

    pm_dir = os.path.join(tmpdir, "postmortems")
    os.makedirs(pm_dir, exist_ok=True)
    incident_dir = os.path.join(tmpdir, "incidents")
    old_pm = os.environ.get("KEYSTONE_POSTMORTEM_DIR")
    os.environ["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
    kill_rank = hosts - 1
    survivors = [r for r in range(hosts) if r != kill_rank]
    t_start = time.monotonic()
    record: dict = {
        "mode": "subprocess" if subprocess_mode else "inprocess",
        "hosts": hosts,
        "kill_rank": kill_rank,
        "requests": requests,
        "incident_dir": incident_dir,
    }
    try:
        if subprocess_mode:
            _run_obs_drill_subprocess(
                record, tmpdir, stem, seed, hosts, kill_rank, survivors,
                rows, expected, local_devices, timeout_s, kdist, kfrontend,
                counters,
            )
        else:
            _run_obs_drill_inprocess(
                record, stem, seed, hosts, kill_rank, survivors, rows,
                expected, timeout_s, kdist, kfrontend, counters,
            )
    finally:
        if old_pm is None:
            os.environ.pop("KEYSTONE_POSTMORTEM_DIR", None)
        else:
            os.environ["KEYSTONE_POSTMORTEM_DIR"] = old_pm
    record["postmortems"] = sorted(os.listdir(pm_dir))
    record["wall_s"] = round(time.monotonic() - t_start, 3)
    trace.instant(
        "obs_capture_drill", mode=record["mode"], hosts=hosts,
        dropped=record["dropped_requests"],
        mismatches=record["mismatches"],
        incidents=len(record.get("incidents", [])),
    )
    return record


def _obs_drill_collector_phase(
    record, col, fleet, endpoints, kill, rows, expected, survivor_keys,
    timeout_s, counters, kwire,
):
    """The collector-side drill body shared by both modes: quiet-fleet
    merge checks, mid-scrape member death, incident + monotonicity
    judgement.  ``kill()`` is the mode's way of killing the chosen
    member."""
    from keystone_tpu.core import fleetobs  # noqa: F401 — drill subject

    n = len(rows)
    results: list = [None] * n
    errors: list = []
    fleet.attach_collector(col)
    col.start()
    # Wave 1: drive then DRAIN, so the merge checks compare a quiet fleet
    # (counters moving under the comparison would fake a mismatch).
    join = _drive_fleet(fleet, rows, results, errors, indices=range(n // 2))
    if not join(timeout_s / 4):
        raise TimeoutError("obs drill wave 1 did not drain")
    col.stop()
    # Quiet-fleet comparison discipline: one warm scrape FIRST (any
    # pending collector connect/clock handshake lands now), then the
    # direct pulls (each opens a fresh connection the member counts in
    # the very payload it returns), then the comparison scrape — which
    # reuses live connections and moves nothing, so both sides total the
    # same ``wire_connections``.
    col.scrape_once()
    direct = {}
    clients = [kwire.WireClient(ep[0], ep[1], timeout=10.0) for ep in endpoints]
    try:
        # All connections open BEFORE any payload is read: in-process
        # members share one registry, so a later connect would move the
        # counters an earlier payload already reported.
        for ep, c in zip(endpoints, clients):
            d = c.obs_snapshot()
            if d is not None:
                direct[f"{ep[0]}:{ep[1]}"] = d
    finally:
        for c in clients:
            c.close()
    t0 = time.monotonic()
    snap_before = col.scrape_once()
    record["scrape_wall_s"] = round(time.monotonic() - t0, 4)
    _counter_sum_check(record, snap_before, direct)
    _pooled_p99_oracle(record, direct, snap_before.get("histograms", {}))
    lost_before = counters.counts().get("obs_member_lost", 0)
    col.start()  # scraping again: the death below lands mid-cadence
    # Wave 2: the kill lands while requests AND scrapes are in flight.
    join = _drive_fleet(
        fleet, rows, results, errors, indices=range(n // 2, n)
    )
    kill()
    record["killed_at_answered"] = _answered(results)
    if not join(timeout_s / 2):
        raise TimeoutError("obs drill wave 2 did not drain")
    # The collector notices on its own cadence; force one pass if the
    # window closes first (alive->dead triggers exactly once either way).
    end = time.monotonic() + timeout_s / 4
    while (
        counters.counts().get("obs_member_lost", 0) <= lost_before
        and time.monotonic() < end
    ):
        time.sleep(0.05)
    col.stop()
    if counters.counts().get("obs_member_lost", 0) <= lost_before:
        col.scrape_once()
    snap_after = col.scrape_once()
    record["obs_member_lost"] = (
        counters.counts().get("obs_member_lost", 0) - lost_before
    )
    non_mono = {
        k: (v, snap_after["counters"].get(k, 0))
        for k, v in snap_before["counters"].items()
        if snap_after["counters"].get(k, 0) < v
    }
    record["monotone_ok"] = not non_mono
    if non_mono:
        record["monotone_violations"] = {
            k: list(v) for k, v in sorted(non_mono.items())[:8]
        }
    record["fleet_alive"] = snap_after["alive"]
    record["fleet_lost"] = snap_after["lost"]
    record["healthz"] = col.fleet_healthz()
    record["incidents"] = list(col.incident_paths)
    record["collector"] = col.record()
    record["fleet"] = fleet.record()
    _judge_answers(record, results, errors, expected)
    bundles = [
        p for p in col.incident_paths if "obs_member_lost" in p
    ]
    if len(bundles) == 1:
        _judge_incident(record, bundles[0], survivor_keys)
    else:
        record["incident"] = {"error": f"{len(bundles)} bundle(s)"}


def _run_obs_drill_subprocess(
    record, tmpdir, stem, seed, hosts, kill_rank, survivors, rows,
    expected, local_devices, timeout_s, kdist, kfrontend, counters,
) -> None:
    from keystone_tpu.core import fleetobs
    from keystone_tpu.core import wire as kwire

    pm_dir = os.environ["KEYSTONE_POSTMORTEM_DIR"]
    workers: list[_WorkerIO] = []
    try:
        for r in range(hosts):
            env = _hermetic_env(
                kdist.worker_env(
                    r, hosts, "controller", local_devices=local_devices
                ),
            )
            env["KEYSTONE_POSTMORTEM_DIR"] = pm_dir
            workers.append(
                _WorkerIO(
                    _worker_cmd(
                        "serve-host", ["--ckpt", stem, "--seed", str(seed)]
                    ),
                    env,
                    os.path.join(tmpdir, f"obshost{r}.err"),
                )
            )
        up = [w.expect("port", timeout_s / 2) for w in workers]
        endpoints = [("127.0.0.1", msg["port"]) for msg in up]
        survivor_keys = [f"127.0.0.1:{up[r]['port']}" for r in survivors]
        with fleetobs.FleetCollector(
            interval_s=0.1, incident_dir=record["incident_dir"],
            window_s=5.0, label="obs-drill",
        ) as col, kfrontend.HostFleet(endpoints, label="obs-drill") as fleet:
            _obs_drill_collector_phase(
                record, col, fleet, endpoints,
                workers[kill_rank].kill, rows, expected, survivor_keys,
                timeout_s, counters, kwire,
            )
        finals = {}
        for r in survivors:
            workers[r].send("quit")
            finals[r] = workers[r].expect("final", timeout_s / 4)["final"]
        record["survivor_counters"] = {
            r: finals[r]["counters"] for r in survivors
        }
    finally:
        record["worker_rcs"] = [w.finish() for w in workers]


def _run_obs_drill_inprocess(
    record, stem, seed, hosts, kill_rank, survivors, rows, expected,
    timeout_s, kdist, kfrontend, counters,
) -> None:
    import jax

    from keystone_tpu.core import fleetobs
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core import wire as kwire
    from keystone_tpu.core.checkpoint import load_pipeline
    from keystone_tpu.parallel import mesh as kmesh

    devs = jax.devices()
    per = max(1, min(2, len(devs) // hosts))
    routers, servers = [], []
    try:
        meshes = [
            kmesh.make_mesh(
                data=per, model=1, devices=devs[r * per : (r + 1) * per]
            )
            for r in range(hosts)
        ]
        for r in range(hosts):
            model_r = load_pipeline(stem, mesh=meshes[r])

            def build(shape, dtype, mesh_or_none, _m=model_r, _r=r):
                return kserve.ServingEngine(
                    _m,
                    np.zeros(shape, dtype),
                    config=kserve.ServeConfig(
                        buckets=(1, 2, 4), max_wait_ms=2.0
                    ),
                    label=f"obshost{_r}:{'x'.join(str(d) for d in shape)}",
                    mesh=mesh_or_none,
                )

            factory = kfrontend.MeshEngineFactory(build, mesh=meshes[r])
            router = kfrontend.ShapeRouter(factory, label=f"obshost{r}")
            router.add_engine(factory((FEAT_DIM,), np.float32))
            routers.append(router)
            servers.append(
                kwire.WireServer(router, port=0, label=f"obshost{r}")
            )
        endpoints = [("127.0.0.1", s.port) for s in servers]
        survivor_keys = [f"127.0.0.1:{servers[r].port}" for r in survivors]
        with fleetobs.FleetCollector(
            interval_s=0.1, incident_dir=record["incident_dir"],
            window_s=5.0, label="obs-drill",
        ) as col, kfrontend.HostFleet(endpoints, label="obs-drill") as fleet:
            _obs_drill_collector_phase(
                record, col, fleet, endpoints,
                servers[kill_rank].close, rows, expected, survivor_keys,
                timeout_s, counters, kwire,
            )
        record["survivor_counters"] = {
            r: counters.snapshot() for r in survivors
        }
    finally:
        for r, s in enumerate(servers):
            if r != kill_rank:
                s.close()
        for router in routers:
            router.close()


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("fit-serve", "serve-host"):
        print(
            "usage: python -m keystone_tpu.workloads.multihost "
            "{fit-serve|serve-host} ...",
            file=sys.stderr,
        )
        return 2
    if argv[0] == "fit-serve":
        return fit_serve_main(argv[1:])
    return serve_host_main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
