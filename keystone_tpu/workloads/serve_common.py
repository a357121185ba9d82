"""Shared serving glue for the workload CLIs (``--serve`` / ``--serveBench``).

Every workload that can checkpoint a fitted SERVABLE pipeline (one
Transformer chain: featurize -> model [-> classifier]) wires the same two
modes through here:

* ``--serve`` — warm-load the ``--pipelineFile`` artifact into a
  :class:`~..core.serve.ServingEngine` (cold start measured: checkpoint
  restore, per-bucket AOT compile, warmup), register it with a
  :class:`~..core.frontend.ShapeRouter` (the production front-end tier —
  ISSUE 12: every workload endpoint is shape-routed, so the serving record
  carries router stats: engines, routes, retires), answer every request
  through the routed online path, and assert the answers BIT-EQUAL the
  offline ``pipeline(x)`` — the smoke proof that the endpoint serves the
  same model it loaded.
* ``--serveBench`` — the SLO bench: N concurrent synthetic clients with
  pipelined depth drive the same endpoint; p50/p99 latency, sustained QPS,
  batcher occupancy, and the batched-vs-unbatched QPS ratio land in
  ``results["serving"]``.

Bucket/deadline knobs come from the ``KEYSTONE_SERVE_*`` env (see
core.serve / README): the CLI adds client-side shape only
(``--serveClients`` / ``--serveRequests``).
"""

from __future__ import annotations

import logging

import numpy as np

_logger = logging.getLogger("keystone_tpu.workloads.serve")


def add_serve_args(p) -> None:
    """The serving flag block every servable workload CLI shares."""
    p.add_argument(
        "--serve",
        action="store_true",
        help="warm-load --pipelineFile into a serving endpoint "
        "(core.serve: fused per-bucket AOT inference + dynamic request "
        "batcher), answer the test split through it, and assert served "
        "predictions bit-equal the offline apply",
    )
    p.add_argument(
        "--serveBench",
        action="store_true",
        help="the serving SLO bench: concurrent synthetic clients drive "
        "the warm endpoint; reports p50/p99 latency, sustained QPS, "
        "batcher occupancy, and batched-vs-unbatched QPS "
        "(KEYSTONE_SERVE_* env sets buckets / max wait)",
    )
    p.add_argument(
        "--serveClients",
        type=int,
        default=4,
        help="concurrent synthetic clients for --serve/--serveBench",
    )
    p.add_argument(
        "--serveRequests",
        type=int,
        default=256,
        help="max requests drawn from the test split for --serve/--serveBench",
    )
    p.add_argument(
        "--serveMesh",
        default=None,
        metavar="DxM",
        help="serve on an explicit device mesh, e.g. 2x1 — the checkpoint "
        "reshards onto it (topology-portable restore, even when it was "
        "recorded under a different topology) and every bucket "
        "AOT-compiles mesh-native; devices are taken in jax.devices() "
        "order",
    )


def resolve_serve_mesh(spec: str | None):
    """``--serveMesh DxM`` -> a live ``Mesh`` over the first D*M local
    devices (``None`` passes through — single-device serving unchanged)."""
    if spec is None:
        return None
    import jax

    from ..parallel.mesh import make_mesh

    try:
        data, model = (int(s) for s in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--serveMesh {spec!r}: expected DxM (e.g. 2x1)"
        ) from None
    devs = jax.devices()
    if data * model > len(devs):
        raise ValueError(
            f"--serveMesh {spec}: needs {data * model} devices but this "
            f"process has {len(devs)}"
        )
    return make_mesh(data=data, model=model, devices=devs[: data * model])


def serve_fitted(
    pipeline_file: str,
    example,
    requests: np.ndarray,
    *,
    label: str,
    wrap=None,
    bench: bool = False,
    clients: int = 4,
    timeout: float = 120.0,
    mesh=None,
    log=None,
) -> dict:
    """Warm-load the fitted pipeline and serve ``requests`` through the
    online path; returns the JSON-able serving record (cold start + engine
    summary + either the smoke answers or the full SLO bench).  ``mesh``
    (from ``--serveMesh``) makes the endpoint topology-portable: the
    checkpoint restores through ``load_pipeline(mesh=)`` resharding and
    the engine AOT-compiles mesh-native (ISSUE 16)."""
    from ..core import serve as kserve

    lg = log or _logger
    requests = np.asarray(requests)
    engine, cold = kserve.load_engine(
        pipeline_file, example, label=label, wrap=wrap, mesh=mesh
    )
    record: dict = {"cold_start": cold}
    lg.info(
        "%s: serving cold start %.3fs (restore %.3fs, compile %.3fs, "
        "warmup %.3fs); live buckets %s%s",
        label,
        cold["cold_start_seconds"],
        cold["checkpoint_load_seconds"],
        cold["compile_seconds"],
        cold["warmup_seconds"],
        list(engine.buckets()),
        f"; mesh {cold['mesh']}" if mesh is not None else "",
    )
    if bench:
        record["bench"] = kserve.serve_bench(
            engine, requests, clients=clients, timeout=timeout
        )
        b = record["bench"]
        lg.info(
            "%s: SLO bench — %s requests via %s clients: p50 %.2fms, "
            "p99 %.2fms, %.1f QPS (unbatched %.1f, x%.2f), occupancy "
            "%.2f, bit_identical=%s",
            label, b["requests"], b["clients"], b["p50_latency_ms"],
            b["p99_latency_ms"], b["qps"], b.get("unbatched_qps", 0.0),
            b.get("batched_vs_unbatched_qps", 0.0),
            b["batcher"]["mean_occupancy"], b["predictions_bit_identical"],
        )
    else:
        import time

        from ..core import frontend as kfrontend

        offline = engine.offline(requests)
        t0 = time.perf_counter()
        # The single-engine demo path rides the SAME front-end tier a
        # multi-shape deployment uses: the engine registers with a
        # ShapeRouter and every request is routed by shape, so the
        # serving record proves the router out on every workload (and
        # carries its stats alongside the phase breakdown).
        with kfrontend.ShapeRouter(label=f"{label}_router") as router:
            key = router.add_engine(engine)
            server = router.server_for(key)
            futs = [router.submit(r) for r in requests]
            answers = np.stack([f.result(timeout) for f in futs])
            lat_ms = sorted(f.latency_seconds() * 1e3 for f in futs)
            stats = server.stats.record()
            slo = server.slo.summary()
            router_record = router.record()
        wall = time.perf_counter() - t0
        record["served"] = {
            "requests": int(requests.shape[0]),
            "qps": round(requests.shape[0] / wall, 2),
            "p50_latency_ms": round(kserve._percentile(lat_ms, 0.50), 3),
            "p99_latency_ms": round(kserve._percentile(lat_ms, 0.99), 3),
            "batcher": stats,
            # Per-phase latency decomposition + the live SLO surface
            # (ISSUE 11) — the smoke path reports the same telemetry
            # shape as the full --serveBench record.
            "phase_breakdown": kserve.phase_breakdown(
                [f.phases for f in futs if f.phases is not None]
            ),
            # The front-end tier's view of the same traffic (ISSUE 12):
            # live engines, routes, warm adds, retires, admission ledger.
            "router": router_record,
            "slo": slo,
            "predictions_bit_identical": bool(
                np.array_equal(answers, offline)
            ),
        }
        s = record["served"]
        if not engine.parity_ok:
            # The chain failed eager-parity at warmup (counted
            # serve_parity_unverified): the honest bar is determinism
            # against the engine's own bucketed AOT apply.
            s["parity_unverified"] = True
            s["predictions_deterministic"] = bool(
                np.array_equal(answers, engine.infer(requests))
            )
        lg.info(
            "%s: served %d requests, p50 %.2fms / p99 %.2fms, %.1f QPS, "
            "bit_identical=%s%s",
            label, s["requests"], s["p50_latency_ms"], s["p99_latency_ms"],
            s["qps"], s["predictions_bit_identical"],
            (
                f" (parity unverified; deterministic="
                f"{s['predictions_deterministic']})"
                if not engine.parity_ok
                else ""
            ),
        )
        healthy = s["predictions_bit_identical"] or (
            not engine.parity_ok and s["predictions_deterministic"]
        )
        if not healthy:
            # The typed-or-equal invariant, online: unequal served answers
            # are a contract violation, not a log line.
            raise AssertionError(
                f"{label}: served predictions differ from the offline "
                "pipeline(x) apply — refusing to report a healthy endpoint"
            )
    record["engine"] = engine.record()
    return record
