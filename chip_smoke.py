#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once — fit -> checkpoint -> serve — through the entry
points a user calls, at the full width of RandomPatchCifar (100 filters,
6x6 patches, pool 14/13, 100,000 whitener patches, 50,000 train images),
on seeded synthetic data, and checks what comes out by the repo's own
means.  Legs:

  A  fit -> checkpoint -> serve: filter learning + ZCA, fused conv
     featurize, BlockLeastSquares under real memory_stats() admission,
     save, restore, per-bucket AOT compile, ShapeRouter + batcher.
  B  ingest born on the device: a JPEG tar through native entropy decode,
     the IDCT and the fused decode+featurize, against the host-decode run
     of the same tar; then one process-decoded chunk, whose workers must
     not have loaded jax.
  C  every pallas_call in the package, compiled by Mosaic at its
     production shape, against its jnp reference.
  D  (>= 4 devices) leg A's fit on a 4-way data mesh at 1,250 filters, a
     width at which the conv featurizer takes its kernel form under
     shard_map (each chip its rows of a chunk), and the __graft_entry__
     multi-chip dry run in-process on the real devices.
  E  TimitPipeline at its documented 50 blocks of 4,096 cosine features
     through ``timit.run``, 8,192 rows, under a budget the 6.7 GB design
     matrix does not fit: the solver must make the blocks inside its one
     fused program (tier ``fused[made]``, no denial), keeping what fits
     beside the made need (PR 39), count what it made, and never let the
     device's peak grow past the made need and the kept blocks.

ONE process touches the chip: this one.  It starts g++ (native decoders)
and spawned decode workers, none of which imports jax, and stops them.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean
every leg passed on a TPU.  Any other platform is refused (exit 2) before
any work, unless ``--rehearsal`` is given: that shrinks the sizes, runs
the Pallas kernels in the interpreter and labels its output a rehearsal —
its last line never says ``ok``.  ``--legs`` runs a subset, which is never
``ok`` either.  Nothing is read from outside the checkout or the network;
the walls it prints record that it ran, they are not metrics.
"""

from __future__ import annotations

# Standard library only up here: spawned decode workers re-import this
# file as their main module, and they must never import jax.
import argparse
import contextlib
import glob
import io
import json
import os
import shutil
import sys
import tarfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # seeded data + checkpoints
TEST_ERROR_BAR = 5.0  # percent; chance on 10 balanced classes is 90

FULL = dict(
    train=50_000, test=10_000, whitener=100_000, requests=256,
    jpeg_train=4_096, jpeg_test=2_048, golden=64,
    idct_images=2_048, fv=(8, 73_866, 80, 256), conv=(2_048, 1_250, 128),
    sift=(64, 375, 500), mesh_filters=1_250,
    timit=dict(train=8_192, test=2_048, width=4_096, budget="8G"),
)
TINY = dict(
    train=600, test=200, whitener=4_000, requests=24,
    jpeg_train=96, jpeg_test=48, golden=8,
    idct_images=8, fv=(3, 700, 24, 8), conv=(5, 24, 4), sift=(32, 30, 46),
    mesh_filters=24,
    timit=dict(train=1_024, test=256, width=32, budget="12M"),
)


def check(cond, msg: str) -> None:
    """A smoke assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


# -- seeded data ---------------------------------------------------------------


def _class_images(rng, palette, labels, size: int):
    """[n, size, size, 3] float32 of separable classes: the benchmark's
    generator (class colour + a class-frequency stripe on one channel +
    noise, whole levels) at the verify skill's amplitudes."""
    from benchmark.lib.manifest import load_module

    return load_module("datagen", "class_images")._images(
        rng, palette, labels,
        {"size": size, "noise_sigma": 25.0, "stripe_amp": 30.0},
    )


def write_cifar_bin(path: str, n: int, rng, palette) -> None:
    import numpy as np

    with open(path, "wb") as f:
        for start in range(0, n, 10_000):
            labels = rng.integers(0, 10, min(10_000, n - start))
            img = _class_images(rng, palette, labels, 32).astype(np.uint8)
            rec = np.empty((len(labels), 3073), np.uint8)
            rec[:, 0] = labels
            rec[:, 1:] = img.transpose(0, 3, 1, 2).reshape(len(labels), -1)
            rec.tofile(f)


def write_jpeg_tar(path: str, n: int, rng, palette) -> None:
    import numpy as np
    from PIL import Image

    labels = rng.integers(0, 10, n)
    img = _class_images(rng, palette, labels, 48).astype(np.uint8)
    with tarfile.open(path, "w") as tf:
        for i in range(n):
            buf = io.BytesIO()
            Image.fromarray(img[i]).save(
                buf, format="JPEG", quality=90
            )
            info = tarfile.TarInfo(f"{labels[i]}/img_{i:05d}.jpg")
            info.size = buf.tell()
            buf.seek(0)
            tf.addfile(info, buf)


# -- legs ----------------------------------------------------------------------


def cifar_flags(size: dict) -> list:
    """The README's canonical RandomPatchCifar flags."""
    return [
        "--numFilters", "100", "--lambda", "10.0", "--patchSize", "6",
        "--poolSize", "14", "--poolStride", "13",
        "--whitenerSize", str(size["whitener"]),
    ]


def leg_a(ctx) -> dict:
    import jax
    import numpy as np

    from keystone_tpu.core import memory as kmem
    from keystone_tpu.core.checkpoint import load_pipeline
    from keystone_tpu.workloads import cifar_random_patch as cifar

    size = ctx["size"]
    stem = os.path.join(WORK, "servable")
    res = cifar.main([
        "--trainLocation", ctx["train_bin"], "--testLocation", ctx["test_bin"],
        *cifar_flags(size), "--pipelineFile", stem,
        "--serve", "--serveRequests", str(size["requests"]),
    ])
    check(
        res["test_error"] < TEST_ERROR_BAR,
        f"test error {res['test_error']:.2f}% is over the "
        f"{TEST_ERROR_BAR}% bar",
    )
    solver = res["solver"]
    check(
        solver["tier"] == "fused" and not solver["denials"]
        and not solver["oom_retries"],
        f"the solve stepped down: {solver} — a 160 MB design matrix on a "
        "16 GB chip must run the fused tier",
    )
    if ctx["device"]["platform"] == "tpu":
        budget = kmem.hbm_budget()
        check(
            budget is not None and solver["budget_bytes"] is not None,
            f"admission did not read memory_stats(): budget {budget}, "
            f"solver {solver}",
        )
    leaves = [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(load_pipeline(stem))
        if hasattr(leaf, "dtype")
    ]
    check(
        leaves and all(
            np.isfinite(a).all() for a in leaves if a.dtype.kind == "f"
        ),
        "the restored servable pipeline holds non-finite values",
    )
    engine = res["serving"]["engine"]
    served = res["serving"]["served"]
    configured = engine["config"]["buckets"]
    check(
        engine["live_buckets"] == configured and engine["parity_ok"]
        and all(engine["parity"].values()),
        f"serve buckets: configured {configured}, live "
        f"{engine['live_buckets']}, parity_ok {engine['parity_ok']}, per "
        f"bucket {engine['parity']}",
    )
    check(
        served["predictions_bit_identical"]
        and served["requests"] == size["requests"],
        f"served answers differ from the offline apply: {served}",
    )
    return {
        "test_error_pct": round(res["test_error"], 3),
        "solver": solver,
        "live_buckets": engine["live_buckets"],
        "parity_ok": engine["parity_ok"],
        "served": served["requests"],
        "serve_cold_start": {
            k: round(v, 3) for k, v in res["serving"]["cold_start"].items()
            if isinstance(v, float)
        },
        "fit_seconds": round(res["seconds"], 2),
    }


def _jaxless_children() -> list:
    """Pids of this process's live multiprocessing children, after
    checking that none has jax or the TPU runtime mapped."""
    import multiprocessing

    pids = []
    for child in multiprocessing.active_children():
        with open(f"/proc/{child.pid}/maps") as f:
            maps = f.read()
        for lib in ("jaxlib", "libtpu"):
            check(
                lib not in maps,
                f"decode worker {child.pid} has {lib} mapped — a child "
                "that imports jax on the default platform fights this "
                "process for the chip",
            )
        pids.append(child.pid)
    return pids


def leg_b(ctx) -> dict:
    import multiprocessing

    from keystone_tpu.core import ingest, trace
    from keystone_tpu.core.resilience import counters
    from keystone_tpu.loaders import native_decode
    from keystone_tpu.ops import jpeg_device, native_entropy
    from keystone_tpu.workloads import cifar_random_patch as cifar

    # The native decoders are built in this run or not at all.
    shutil.rmtree(
        os.path.join(ROOT, "keystone_tpu", "native", "build"),
        ignore_errors=True,
    )
    native_entropy.reset()
    native_decode.reset()
    size = ctx["size"]
    flags = [
        "--trainLocation", ctx["train_tar"],
        "--streamTestTar", ctx["test_tar"], *cifar_flags(size),
    ]
    dev = cifar.main([*flags, "--deviceDecode"])
    gauges = {
        g: trace.metrics.gauge_value(g)
        for g in (
            "ingest_entropy_decoded", "ingest_entropy_native",
            "ingest_device_fallbacks",
        )
    }
    check(
        gauges == {
            "ingest_entropy_decoded": size["jpeg_test"],
            "ingest_entropy_native": 1,
            "ingest_device_fallbacks": 0,
        },
        f"device decode did not carry the whole test tar natively: {gauges}",
    )
    host = cifar.main(flags)
    check(
        dev["test_error"] == host["test_error"] < TEST_ERROR_BAR,
        f"device decode {dev['test_error']:.3f}% vs host decode "
        f"{host['test_error']:.3f}% (bar {TEST_ERROR_BAR}%)",
    )
    faults = counters.snapshot()
    bad = {
        k: v for k, v in faults.items()
        if v and (
            k.startswith(("device_decode_fallback_", "native_entropy_"))
            or k in ("jpeg_corrupt_entropy", "corrupt_image")
        )
    }
    check(not bad, f"decode degraded: {bad}")
    built = sorted(
        os.path.basename(p) for p in glob.glob(
            os.path.join(ROOT, "keystone_tpu", "native", "build", "*.so")
        )
        if os.path.getmtime(p) >= ctx["started"]
    )
    check(
        len(built) == 2 and native_entropy.available()
        and native_decode.available()
        and jpeg_device.entropy_backend() == "native",
        f"native decoders not built and loaded in this run: {built}",
    )
    # One chunk through the process decode backend: its workers are the
    # only python children this program starts.
    cfg = ingest.StreamConfig.from_env(decode_backend="process", decode_procs=2)
    with ingest.stream_batches(ctx["test_tar"], 32, config=cfg) as stream:
        first = next(iter(stream))
        first.dev().block_until_ready()
        workers = _jaxless_children()
    check(len(workers) == 2, f"expected 2 decode workers, saw {workers}")
    check(stream.join(30.0), "the process-decode stream did not shut down")
    check(
        not multiprocessing.active_children(),
        f"children left running: {multiprocessing.active_children()}",
    )
    return {
        "test_error_pct": round(dev["test_error"], 3),
        "host_test_error_pct": round(host["test_error"], 3),
        "entropy_decoded": gauges["ingest_entropy_decoded"],
        "native_built": built,
        "jaxless_workers": len(workers),
    }


def _kernel_idct(ctx, interpret, rng) -> dict:
    """One chunk of 48 px 4:2:0 luma, [images, 6, 6, 8, 8], at
    dequantized-coefficient magnitudes; then the whole decode (the
    chooser's default path) against the host decoder."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.loaders.image_loaders import decode_image
    from keystone_tpu.ops import jpeg_device as jd

    size = ctx["size"]
    blocks = jnp.asarray(
        (rng.integers(-64, 65, (size["idct_images"], 6, 6, 8, 8))
         * rng.integers(1, 17, (8, 8))).astype(np.float32)
    )
    got = np.asarray(jd.idct_blocks_pallas(blocks, interpret=interpret))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jd.idct_blocks_jnp(blocks))
    err = float(np.abs(got - want).max())
    check(err <= jd.IDCT_ATOL, f"idct_blocks_pallas off by {err}")
    with tarfile.open(ctx["test_tar"]) as tf:
        datas = [
            tf.extractfile(m).read() for m in tf.getmembers()[: size["golden"]]
        ]
    cis = [jd.entropy_decode(d) for d in datas]
    coeffs, qt = jd.stack_coeff_images(cis)
    pixels = np.asarray(jd.decode_batch(cis[0].geom, coeffs, qt))
    diff = np.abs(pixels - np.stack([decode_image(d) for d in datas]))
    check(
        diff.max() <= jd.GOLDEN_MAX_ABS and diff.mean() <= jd.GOLDEN_MEAN_ABS,
        f"device decode vs host decoder: max {diff.max()}, mean {diff.mean()}",
    )
    return {
        "shape": list(blocks.shape), "max_abs_err": err,
        "decode_vs_host_max": float(diff.max()),
        "decode_vs_host_mean": round(float(diff.mean()), 4),
    }


def _kernel_fv_stats(ctx, interpret, rng) -> dict:
    """Fisher-vector statistics with ragged counts at the published widths
    (tests/test_gmm_fisher.py TestFvPallasKernel): against the jnp form
    whose moment products round their operands to bfloat16 as the kernel's
    do, and (reported) against the one that rounds nothing."""
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.ops.fv_pallas import fv_stats_jnp, fv_stats_pallas

    n, cols, d, k = ctx["size"]["fv"]
    x = jnp.asarray(rng.normal(size=(n, d, cols)).astype(np.float32))
    means = rng.normal(size=(d, k)).astype(np.float32)
    variances = rng.uniform(0.5, 2.0, (d, k)).astype(np.float32)
    weights = rng.dirichlet(np.ones(k)).astype(np.float32)
    counts = jnp.asarray(rng.integers(cols // 2, cols + 1, size=n).astype(np.int32))
    got = fv_stats_pallas(x, counts, means, variances, weights, interpret=interpret)

    def gaps(moment_dtype):
        want = fv_stats_jnp(
            x, counts, means, variances, weights, moment_dtype=moment_dtype
        )
        return [
            float(jnp.abs(g - w).max() / jnp.abs(w).max()) for g, w in zip(got, want)
        ]

    rounded = gaps(jnp.bfloat16)
    check(max(rounded) < 2e-3, f"fv_stats_pallas off its bf16-operand form by {rounded}")
    return {
        "shape": [n, cols, d, k],
        "max_rel_err_s0_s1_s2": rounded,
        "against_f32_operands": gaps(jnp.float32),
    }


def _kernel_conv_form(ctx, interpret, rng) -> dict:
    """FusedConvFeaturizer's kernel form at the benchmark's widths (2,048
    images x 1,250 filters): against the node's XLA form on every image,
    and both against the benchmark's plain reference on a slice.  The two
    forms differ by the XLA form's bf16 activations and its products on
    un-normalized pixels; the kernel form has to be the closer one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib.manifest import load_module
    from keystone_tpu.ops.conv_fused import FusedConvFeaturizer

    n, f, ref_n = ctx["size"]["conv"]
    filters = rng.normal(size=(f, 6, 6, 3)).astype(np.float32)
    filters /= np.linalg.norm(filters.reshape(f, -1), axis=1)[:, None, None, None]
    means = (0.1 * rng.normal(size=(108,))).astype(np.float32)
    palette = rng.uniform(40, 215, (10, 3)).astype(np.float32)
    imgs = jnp.asarray(
        _class_images(rng, palette, rng.integers(0, 10, n), 32)
    )
    node = FusedConvFeaturizer(
        filters, whitener_means=means, pool_stride=13, pool_size=14, alpha=0.25
    )
    got = np.asarray(
        jax.jit(lambda im: node._kernel_form(im, interpret=interpret))(imgs)
    )
    xla = np.asarray(jax.jit(node._xla_form)(imgs))
    want = np.asarray(load_module("reference", "cifar_rp")._featurize_chunk(
        imgs[:ref_n], jnp.asarray(filters.reshape(f, -1)), jnp.asarray(means),
        0.25, ps=6, pool=14, stride=13, precision="highest",
    ))

    def rms_gap(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b**2)))

    out = {
        "shape": [n, f],
        "kernel_vs_xla_rms": rms_gap(got, xla),
        "kernel_vs_reference_rms": rms_gap(got[:ref_n], want),
        "xla_vs_reference_rms": rms_gap(xla[:ref_n], want),
    }
    check(got.shape == xla.shape == (n, 8 * f), f"shapes {got.shape} {xla.shape}")
    check(out["kernel_vs_xla_rms"] < 2e-2, f"the two forms differ: {out}")
    check(out["kernel_vs_reference_rms"] < 4e-3, f"kernel form off the reference: {out}")
    # Off the chip the XLA form's products are exact f32: no contest.
    check(
        interpret
        or out["kernel_vs_reference_rms"] <= out["xla_vs_reference_rms"] + 1e-4,
        f"kernel form further from the reference than the XLA form: {out}",
    )
    return out


def _kernel_sift_form(ctx, interpret, rng) -> dict:
    """``SIFTExtractor``'s kernel form (the descriptor assembly in
    ``ops/sift_pallas.py``) against its XLA form on a chunk of VOC's widest
    shape, bfloat16 planes: the same bytes but for the order of a float32
    sum (tests/test_sift_lcs.py TestAssemblyForms), a zero-contrast image
    all zeros."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.ops.sift import SIFTExtractor

    n, h, w = ctx["size"]["sift"]
    img = rng.uniform(size=(n, h, w)).astype(np.float32)
    img[1] = 0.5
    node = SIFTExtractor(scale_step=0, compute_dtype=jnp.bfloat16)
    got = np.asarray(
        jax.jit(lambda b: node._kernel_form(b, interpret=interpret).astype(jnp.uint8))(img)
    ).astype(np.int32)
    want = np.asarray(
        jax.jit(lambda b: node._xla_form(b).astype(jnp.uint8))(img)
    ).astype(np.int32)
    out = {
        "shape": [n, h, w, node.num_descriptors(h, w)],
        "identical_share": float((got == want).mean()),
        "max_step": int(np.abs(got - want).max()),
    }
    check(got.shape == want.shape, f"shapes {got.shape} {want.shape}")
    check(out["max_step"] <= 1 and out["identical_share"] >= 0.999, f"forms differ: {out}")
    check(want.max() > 0 and not got[1].any(), "a zero-contrast image gave descriptors")
    return out


KERNELS = {
    "idct_blocks_pallas": _kernel_idct,
    "fv_stats_pallas": _kernel_fv_stats,
    "conv_rect_pool": _kernel_conv_form,
    "sift_assemble": _kernel_sift_form,
}


def leg_c(ctx) -> dict:
    """Each kernel: the Pallas call (Mosaic on the chip; the interpreter
    only in a rehearsal) against its jnp reference at HIGHEST matmul
    precision, at the tolerance the kernel's own test states.  Every
    kernel is asked, and the leg fails if any did not compile or match."""
    import numpy as np

    interpret = ctx["rehearsal"]  # False on the chip: Mosaic compiles
    out, failed = {}, []
    for name, kernel in KERNELS.items():
        try:
            out[name] = kernel(ctx, interpret, np.random.default_rng(21))
        except Exception as e:  # noqa: BLE001 — the leg fails below
            failed.append(name)
            out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
        print(json.dumps({"kernel": name, **out[name]}), flush=True)
    check(not failed, f"kernels that did not compile or match: {failed}")
    return out


def leg_d(ctx) -> dict:
    import __graft_entry__ as graft
    from keystone_tpu.core import trace
    from keystone_tpu.workloads import cifar_random_patch as cifar

    flags = cifar_flags(ctx["size"])
    flags[flags.index("--numFilters") + 1] = str(ctx["size"]["mesh_filters"])
    kernel_before = trace.metrics.get("conv_form.kernel")
    res = cifar.main([
        "--trainLocation", ctx["train_bin"], "--testLocation", ctx["test_bin"],
        *flags, "--mesh", "4",
    ])
    check(
        res["test_error"] < TEST_ERROR_BAR,
        f"mesh fit test error {res['test_error']:.2f}%",
    )
    kernel_forms = trace.metrics.get("conv_form.kernel") - kernel_before
    if ctx["device"]["platform"] == "tpu":
        check(
            kernel_forms >= 1,
            "the mesh fit's conv featurizer did not take its kernel form: "
            f"{trace.metrics.counters()}",
        )
    rows = res["feature_rows_by_device"]
    check(
        len(rows) == 4 and len({tuple(r) for r in rows.values()}) == 4,
        f"train features are not spread over four devices: {rows}",
    )
    check(
        res["solver"]["tier"].startswith("fused[mesh 4x1"),
        f"the mesh solve stepped down: {res['solver']}",
    )
    record = graft._dryrun_impl(4)
    dry_rows = record["feature_rows_by_device"]
    check(
        len(dry_rows) == 4
        and len({tuple(r) for r in dry_rows.values()}) == 2,
        f"dry run rows are not split over the 2x2 mesh: {dry_rows}",
    )
    budget_gb = record["block_fit_report"]["budget_gb"]
    if ctx["device"]["platform"] == "tpu":
        check(
            not record["forced_host_devices"] and budget_gb < 32,
            f"the dry run did not admit against the chips' own memory: "
            f"budget {budget_gb} GB, record device {record['device']}",
        )
    return {
        "mesh_fit_test_error_pct": round(res["test_error"], 3),
        "mesh_fit_solver": res["solver"],
        "mesh_fit_conv_kernel_forms": kernel_forms,
        "mesh_fit_rows_by_device": rows,
        "dryrun_mesh": record["mesh"],
        "dryrun_rows_by_device": dry_rows,
        "dryrun_budget_gb": budget_gb,
        "dryrun_tiers": [
            record["block_fit_report"]["chosen_tier"],
            record["bwls_fit_report"]["chosen_tier"],
        ],
        "dryrun_errors": [record["block_err"], record["bwls_err"]],
    }


def leg_e(ctx) -> dict:
    import jax
    import numpy as np

    from keystone_tpu.core import trace
    from keystone_tpu.core.memory import HBM_BUDGET_ENV
    from keystone_tpu.loaders.timit import TimitFeaturesData, TimitSplit
    from keystone_tpu.workloads import timit

    size = ctx["size"]["timit"]
    conf = timit.TimitConfig(num_cosine_features=size["width"])  # 50 blocks, 5 epochs
    rng = np.random.default_rng(38)
    centres = rng.normal(0, 1.0, (conf.num_classes, conf.dimension))

    def split(n):
        labels = rng.integers(0, conf.num_classes, n).astype(np.int32)
        rows = centres[labels] + 0.3 * rng.normal(size=(n, conf.dimension))
        return TimitSplit(rows.astype(np.float32), labels)

    data = TimitFeaturesData(split(size["train"]), split(size["test"]))
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    in_use, peak_before = stats.get("bytes_in_use", 0), stats.get("peak_bytes_in_use", 0)
    made_before = trace.metrics.get("bcd.block_rows_made")
    old = os.environ.get(HBM_BUDGET_ENV)
    os.environ[HBM_BUDGET_ENV] = size["budget"]
    try:
        res = timit.run(conf, data)
    finally:
        if old is None:
            del os.environ[HBM_BUDGET_ENV]
        else:
            os.environ[HBM_BUDGET_ENV] = old
    report = res["fit_report"]
    plan = report.bcd_plan
    check(
        (report.chosen, report.denials, report.oom_retries) == ("fused[made]", [], []),
        f"the 50-block solve did not run made and fused: {report.summary()}",
    )
    check(res["test_error"] < TEST_ERROR_BAR, f"test error {res['test_error']:.2f}%")
    made = trace.metrics.get("bcd.block_rows_made") - made_before
    nb, kept = conf.num_cosines, plan["held_blocks"]
    # moments and gram every block, the epochs every block not kept
    due = size["train"] * (2 * nb + (nb - kept) * conf.num_epochs)
    check(made == due, f"bcd.block_rows_made grew by {made}, not {due}")
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    grew = max(0, peak - max(peak_before, in_use))
    check(
        grew < plan["made_bytes"] + plan["held_stack_bytes"],
        f"the device's peak grew by {grew} bytes, the made need and the kept "
        f"blocks are {plan['made_bytes'] + plan['held_stack_bytes']}",
    )
    return {
        "timit_test_error_pct": round(res["test_error"], 3),
        "timit_tier": report.chosen,
        "timit_bcd_plan": plan,
        "timit_block_rows_made": made,
        "timit_held_blocks": kept,
        "timit_peak_grew_bytes": grew,
        "timit_fit_seconds": round(res["seconds"], 2),
    }


LEGS = {"A": leg_a, "B": leg_b, "C": leg_c, "D": leg_d, "E": leg_e}


# -- driver --------------------------------------------------------------------


def _versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--rehearsal", action="store_true",
        help="run off-TPU at tiny size with Pallas in the interpreter; "
        "the output is labelled a rehearsal and is never a pass",
    )
    p.add_argument(
        "--legs", default=None,
        help="comma-separated subset of A,B,C,D,E (a subset is never a pass)",
    )
    p.add_argument(
        "--expect-warm-cache", action="store_true",
        help="fail if this run adds entries to the compile cache (the "
        "second of two runs in one chip call)",
    )
    a = p.parse_args(argv)
    legs = sorted(set(a.legs.upper().split(","))) if a.legs else None
    if legs and not set(legs) <= set(LEGS):
        p.error(f"--legs takes a subset of {sorted(LEGS)}")

    if a.rehearsal:
        # Off-TPU the production chooser takes the jnp IDCT; the rehearsal
        # wants the kernel on the path, in the interpreter.
        os.environ["KEYSTONE_PALLAS_IDCT"] = "1"

    from keystone_tpu.core.logging import configure_logging
    from keystone_tpu.utils.platform import init_device

    configure_logging()
    device = init_device()
    if device["platform"] != "tpu" and not a.rehearsal:
        print(
            f"chip_smoke: JAX selected {device}, not a TPU — refusing "
            "(--rehearsal runs the tiny CPU form)", file=sys.stderr,
        )
        return 2
    print(json.dumps({"device": device, "versions": _versions(),
                      "rehearsal": a.rehearsal}), flush=True)

    import jax
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    if legs is None:
        legs = ["A", "B", "C"] + (["D"] if device["count"] >= 4 else []) + ["E"]
    full_run = not a.legs and not a.rehearsal
    from benchmark.lib.compile_meter import CompileMeter

    meter = CompileMeter()
    cache_dir = jax.config.jax_compilation_cache_dir  # None: caching off

    def cache_entries() -> int:
        if cache_dir is None:
            return 0
        return len(glob.glob(os.path.join(cache_dir, "*-cache")))

    entries_before = cache_entries()
    ctx = {
        "device": device, "rehearsal": a.rehearsal,
        "size": TINY if a.rehearsal else FULL, "started": time.time(),
        "train_bin": os.path.join(WORK, "train.bin"),
        "test_bin": os.path.join(WORK, "test.bin"),
        "train_tar": os.path.join(WORK, "train.tar"),
        "test_tar": os.path.join(WORK, "test.tar"),
    }
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    failed = []
    records = {}
    try:
        t0 = time.perf_counter()
        rng = np.random.default_rng(21)
        palette = rng.uniform(40, 215, (10, 3)).astype(np.float32)
        size = ctx["size"]
        if set(legs) & {"A", "D"}:
            write_cifar_bin(ctx["train_bin"], size["train"], rng, palette)
            write_cifar_bin(ctx["test_bin"], size["test"], rng, palette)
        if set(legs) & {"B", "C"}:
            write_jpeg_tar(ctx["train_tar"], size["jpeg_train"], rng, palette)
            write_jpeg_tar(ctx["test_tar"], size["jpeg_test"], rng, palette)
        data_seconds = round(time.perf_counter() - t0, 2)
        interpreter = (
            pltpu.force_tpu_interpret_mode
            if a.rehearsal else contextlib.nullcontext
        )
        for name in legs:
            before = meter.read()
            t0 = time.perf_counter()
            try:
                with interpreter():
                    detail = LEGS[name](ctx)
                ok = True
            except Exception:  # noqa: BLE001 — reported, and the run fails
                detail = {"error": traceback.format_exc()[-2000:]}
                ok = False
                failed.append(name)
                traceback.print_exc()
            compiles = CompileMeter.between(before, meter.read())
            rec = {
                "leg": name, "ok": ok,
                "wall_seconds": round(time.perf_counter() - t0, 2),
                # set-up time, not work: zero-ish on a warm cache
                "compile_seconds": round(compiles["seconds"], 2),
                "cache_hits": compiles["hits"],
                "cache_misses": compiles["misses"],
                **detail,
            }
            records[name] = rec
            print(json.dumps(rec), flush=True)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)

    entries_after = cache_entries()
    added = entries_after - entries_before
    if a.expect_warm_cache and added:
        failed.append(f"compile cache grew by {added} entries on a warm run")
    passed = not failed
    print(json.dumps({
        "summary": "chip_smoke", "passed": passed, "failed": failed,
        "legs_run": legs, "rehearsal": a.rehearsal,
        "walls": {k: v["wall_seconds"] for k, v in records.items()},
        "compile_seconds": {
            k: v["compile_seconds"] for k, v in records.items()
        },
        "data_seconds": data_seconds,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": entries_after, "added": added,
        },
        "device": device, "claim": None,
    }), flush=True)
    if full_run:
        final = {"ok": passed, "device": device}
    elif a.rehearsal:
        final = {"rehearsal": True, "passed": passed, "device": device}
    else:
        final = {"partial": legs, "passed": passed, "device": device}
    print(json.dumps(final), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
