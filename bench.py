"""Benchmark driver — prints ONE JSON line.

Primary metric (BASELINE.md north star #1): RandomPatchCifar featurization —
the Convolver -> SymmetricRectifier -> Pooler -> ImageVectorizer pipeline of
reference src/main/scala/pipelines/images/cifar/RandomPatchCifar.scala:53-56
at the canonical scale (numFilters=100, 6x6 patches, 32x32x3 images) —
measured as steady-state images/sec/chip on synthetic CIFAR-shaped data.

Timing methodology: all compute timings run as a ``lax.scan`` chain with a
serial data dependency and a non-linear readout inside one compiled program
(no dispatch can be skipped, reordered or overlapped with the next, and
nothing is transferred between iterations), and fixed costs cancel by
differencing a K-length and a 2K-length chain (see timed_chain).  Rounds
1-2 timed dispatch loops instead; ``vs_baseline`` against r<=2 records
mixes methodologies.

Also reported inside the same JSON line:
- ``mfu`` / ``flops_per_sec``: achieved FLOP/s from XLA's compiled cost
  analysis divided by wall-clock, and the fraction of the chip's peak
  (bf16 systolic-array peak — TPU matmuls run bf16 passes by default).
- ``solve``: BlockLeastSquares fit time on the featurized batch — the
  reference pipeline's wall-clock is featurize + solve, so both are timed.
  The fit is ONE compiled program (solvers/block._fused_bcd_fit);
  ``solve_seconds`` is steady-state wall-clock (one dispatch round-trip),
  ``solve_device_seconds`` is chain-measured device compute only.
- ``extra_metrics.imagenet_fv_featurize``: north star #2 — the
  SIFT -> PCA-project -> FisherVector ImageNet featurization branch
  (reference ImageNetSiftLcsFV.scala:41-94) in images/sec/chip.
- ``vs_baseline``: this metric divided by the previous round's recorded
  value (BENCH_r*.json), 1.0 when no prior record of the same metric exists.

The reference itself publishes no throughput numbers (BASELINE.md), so the
baseline series is this repo's own round history.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np


from keystone_tpu.core import trace as ktrace
import keystone_tpu.core.resilience  # noqa: F401 — adopts "faults" into ktrace.metrics
from keystone_tpu.core.optimize import DEVICE_RATES
from keystone_tpu.ops.fisher import FisherVector
from keystone_tpu.ops.sift import SIFTExtractor
from keystone_tpu.solvers.block import BlockLeastSquaresEstimator
from keystone_tpu.solvers.gmm import GaussianMixtureModel
from keystone_tpu.solvers.pca import BatchPCATransformer
from keystone_tpu.utils.platform import init_device
from keystone_tpu.workloads.cifar_random_patch import (
    RandomCifarConfig,
    build_conv_pipeline,
    learn_filters,
)


def roundtrip_latency() -> float:
    """Host<->device round-trip seconds for a trivial scalar pull."""
    f = jax.jit(lambda x: x + 1.0)
    v = float(f(jnp.float32(0)))
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        v = float(f(jnp.float32(v)))
    return (time.perf_counter() - t0) / reps


class NoiseFloorError(RuntimeError):
    """timed_chain's differenced compute did not clear the jitter floor."""


def timed_chain(fn, arg, chain_len: int, repeats: int = 3) -> float:
    """Seconds per application of ``fn(arg)``, measured as a lax.scan chain
    with a serial scalar dependency: iteration i's input is perturbed by
    iteration i-1's sum-of-squares readout, so the executions can be
    neither skipped nor reordered, the readout is non-linear (see the
    comment in ``step``), and the batch stays on the device.

    Fixed costs (the round-trip, dispatch, the host pull) are cancelled by
    DIFFERENCING chains of length ``chain_len`` and ``2*chain_len`` rather
    than subtracting a separately-measured latency — the latency
    estimate's own jitter otherwise dominates when the chain's compute is
    tens of milliseconds."""

    def step(a, acc, _):
        out = fn(a + (acc * 1e-30).astype(a.dtype))
        # sum-of-SQUARES readout: a plain sum is linear, and XLA's algebraic
        # simplifier can collapse sum∘conv / sum∘pool into closed forms that
        # skip the very work being timed (observed: a lone conv "measured"
        # 2x above peak FLOP/s under a linear readout)
        return acc + jnp.sum(out * out).astype(jnp.float32), None

    # ``arg`` enters as a runtime parameter, NOT a closure: closed-over
    # arrays are embedded in the lowered program, which blows up remote
    # compile payloads for large operands
    def make_chain(length):
        @jax.jit
        def chain(seed, a):
            acc, _ = jax.lax.scan(
                lambda c, x: step(a, c, x), seed, None, length=length
            )
            return acc

        return chain

    short, long = make_chain(chain_len), make_chain(2 * chain_len)

    # distinct seed per dispatch: a repeat is never a bit-identical program
    # invocation
    float(short(jnp.float32(1.0), arg))  # compile + warm
    float(long(jnp.float32(1.5), arg))
    best_short = best_long = float("inf")
    for i in range(repeats):
        t0 = time.perf_counter()
        float(short(jnp.float32(2.0 + i), arg))
        best_short = min(best_short, time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(long(jnp.float32(20.0 + i), arg))
        best_long = min(best_long, time.perf_counter() - t0)
    diff = best_long - best_short
    # The differenced mins must clear the dispatch jitter floor — when the
    # chain's own compute is comparable to the dispatch noise the
    # difference can go near-zero (or negative) and a silent clamp would
    # report absurdly inflated throughput.  Fail loudly instead: the caller
    # should raise chain_len until the chain compute dominates the noise.
    if diff < 0.1 * best_short:
        raise NoiseFloorError(
            f"timed_chain noise floor: best_long-best_short={diff:.4f}s is "
            f"<10% of best_short={best_short:.4f}s; raise chain_len "
            f"(chain compute does not dominate dispatch jitter)"
        )
    return diff / chain_len


def timed_chain_auto(fn, arg, chain_len: int, max_len: int = 2048) -> float:
    """timed_chain, doubling chain_len until the differenced compute clears
    the dispatch-jitter noise floor (for ops whose per-iteration cost is
    not known in advance).  Only the noise-floor signal retries — real
    device/XLA failures (which also subclass RuntimeError) propagate."""
    while True:
        try:
            return timed_chain(fn, arg, chain_len)
        except NoiseFloorError:
            if chain_len * 2 > max_len:
                raise
            chain_len *= 2


def _make_jpeg_tar(
    rng,
    n_images: int,
    size: int,
    labeled: bool = False,
    subsamplings: tuple | None = None,
    qualities: tuple = (90,),
    restart_every: int = 0,
) -> str:
    """Temp tar of random ``size``-px JPEGs for the ingest benches (the
    caller unlinks it).  ``labeled=True`` prefixes members with a 0-9 class
    directory — the name-borne-label layout the CIFAR stream path reads.

    ``subsamplings`` / ``qualities`` cycle PER MEMBER (PIL subsampling
    codes: 0 = 4:4:4, 1 = 4:2:2, 2 = 4:2:0; ``None`` keeps the encoder
    default) and ``restart_every`` adds restart markers every N MCU rows
    on every third member — so the tar exercises the corpus the DEVICE
    decode path (ops.jpeg_device) actually claims, not one
    encoder-default shape."""
    import io
    import tarfile
    import tempfile

    from PIL import Image as PILImage

    with tempfile.NamedTemporaryFile(suffix=".tar", delete=False) as tmp:
        path = tmp.name
    with tarfile.open(path, "w") as tf:
        for i in range(n_images):
            arr = rng.integers(0, 256, (size, size, 3), dtype=np.uint8)
            buf = io.BytesIO()
            kw = {"quality": qualities[i % len(qualities)]}
            if subsamplings is not None:
                kw["subsampling"] = subsamplings[i % len(subsamplings)]
            if restart_every and i % 3 == 0:
                kw["restart_marker_rows"] = restart_every
            PILImage.fromarray(arr).save(buf, format="JPEG", **kw)
            data = buf.getvalue()
            name = f"{i % 10}/img_{i:05d}.jpg" if labeled else f"img_{i:05d}.jpg"
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))
    return path


def one_hot_pm1(rng, n: int, k: int):
    """+/-1 one-hot label matrix [n, k] — the reference workloads' label
    encoding (ClassLabelIndicators: +1 true class, -1 elsewhere)."""
    return jnp.asarray(2.0 * np.eye(k)[rng.integers(0, k, n)] - 1.0, jnp.float32)


def compiled_cost(jitted_fn, *args) -> tuple[float | None, float | None]:
    """(FLOPs, HBM bytes accessed) of the compiled program from XLA's cost
    analysis — the roofline numerator and denominator.

    Delegates to ``core.profiler.jit_cost`` (ISSUE 14): the profiler is
    the ONE place the raw cost_analysis quirks live; lowering still hits
    the jit cache, so a warm function never compiles twice."""
    from keystone_tpu.core import profiler as kprof

    return kprof.jit_cost(jitted_fn, *args)


def roofline(flops, bytes_accessed, per_iter, peak, bw):
    """Arithmetic intensity, memory-bound ceiling, and achieved fractions."""
    if not (flops and bytes_accessed and peak and bw):
        return {}
    intensity = flops / bytes_accessed
    ceiling = min(intensity * bw, peak)
    achieved = flops / per_iter
    return {
        "intensity_flop_per_byte": round(intensity, 2),
        "ridge_flop_per_byte": round(peak / bw, 1),
        "memory_ceiling_flops": ceiling,
        "fraction_of_ceiling": round(achieved / ceiling, 3),
        # MFU rides in every roofline block (ISSUE 14): fraction of the
        # device PEAK, the cross-round headline bench_diff watches —
        # fraction_of_ceiling above is position vs the memory-bound
        # ceiling, a different (and intensity-dependent) denominator.
        "mfu": round(achieved / peak, 4),
        "hbm_gbps_achieved": round(bytes_accessed / per_iter / 1e9, 1),
    }


def prior_bench_value(metric: str) -> float | None:
    """Most recent BENCH_r*.json record of the same metric."""
    best_round, best_val = -1, None
    for path in glob.glob(os.path.join(os.path.dirname(__file__) or ".", "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            rec = json.load(open(path))
        except (OSError, json.JSONDecodeError):
            continue
        # driver wraps the printed line under "parsed"
        rec = rec.get("parsed", rec)
        if (
            isinstance(rec, dict)
            and rec.get("metric") == metric
            and int(m.group(1)) > best_round
        ):
            best_round, best_val = int(m.group(1)), float(rec["value"])
    return best_val


def bench_cifar_featurize(rng):
    """North star #1: conv featurization + the block solve it feeds."""
    conf = RandomCifarConfig(
        num_filters=100,
        patch_size=6,
        patch_steps=1,
        pool_size=14,
        pool_stride=13,
        alpha=0.25,
        whitener_size=20000,
        featurize_chunk=1024,
    )
    n_bench = conf.featurize_chunk

    train_imgs = rng.uniform(0, 255, (512, 32, 32, 3)).astype(np.float32)
    filters, whitener = learn_filters(conf, train_imgs)
    conv_pipe = build_conv_pipeline(conf, filters, whitener)
    feat_fn = jax.jit(conv_pipe.__call__)

    batch = jnp.asarray(
        rng.uniform(0, 255, (n_bench, 32, 32, 3)).astype(np.float32)
    )
    feats = feat_fn(batch)
    feats.block_until_ready()  # materialize features for the solve below

    per_iter = timed_chain(conv_pipe.__call__, batch, chain_len=128)
    flops, bytes_accessed = compiled_cost(feat_fn, batch)
    images_per_sec = n_bench / per_iter
    flops_per_sec = flops / per_iter if flops else None

    # Solve timing: BlockLeastSquares on the featurized batch (reference
    # RandomPatchCifar.scala:68 — the other half of pipeline wall-clock).
    # The fit is ONE compiled program (solvers/block._fused_bcd_fit); the
    # first call is the compile warm-up, the second is the steady-state
    # wall-clock (dispatch + compute + one scalar pull, minus the measured
    # round-trip), and the chain measurement is device compute only.
    labels = one_hot_pm1(np.random.default_rng(1), n_bench, 10)
    est = BlockLeastSquaresEstimator(4096, num_iter=1, lam=10.0)

    def pull(model):
        # fit returns unsynced device arrays; a scalar host pull of every
        # model array is the sync
        float(
            sum(jnp.sum(x[0]) for x in model.xs) + jnp.sum(jnp.asarray(model.b))
        )

    pull(est.fit(feats, labels))  # compile warm-up
    # The timed fit gets a PERTURBED input, so it is never the warm-up's
    # bit-identical invocation.  RELATIVE perturbation (an absolute epsilon
    # is below f32 ULP for values >= 32 and would round away); synced by a
    # scalar pull (see the pull() note above).
    feats_t = feats * jnp.float32(1.0 + 1e-6)
    float(jnp.sum(feats_t[0]))
    lat = roundtrip_latency()
    t1 = time.perf_counter()
    pull(est.fit(feats_t, labels))
    solve_secs = max(time.perf_counter() - t1 - lat, 1e-9)

    # Device-compute-only: the same fused fit program in a serial chain.
    from keystone_tpu.solvers.block import _fused_bcd_fit

    def solve_fn(f):
        models, _, _ = _fused_bcd_fit(
            f, labels, jnp.float32(est.lam), f.shape[0], est.num_iter,
            (f.shape[1],), None,
        )
        return models[0]

    solve_device_secs = timed_chain_auto(solve_fn, feats, chain_len=256)

    return {
        "images_per_sec": images_per_sec,
        "flops_per_sec": flops_per_sec,
        "flops_per_image": flops / n_bench if flops else None,
        "bytes_per_image": bytes_accessed / n_bench if bytes_accessed else None,
        "per_iter": per_iter,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "solve_seconds": solve_secs,
        "solve_examples_per_sec": n_bench / solve_secs,
        "solve_device_seconds": solve_device_secs,
    }


def bench_imagenet_fv_featurize(rng):
    """North star #2: the SIFT -> PCA(64) -> FV(16) ImageNet branch
    (reference ImageNetSiftLcsFV.scala:41-94, descDim=64 vocabSize=16) on
    256x256 grayscale images."""
    n_bench = 64
    h = w = 256
    desc_dim, vocab = 64, 16

    # bf16 intermediates — the workload configuration (imagenet_sift_lcs_fv
    # passes the same; op-level default is f32 for parity-critical callers)
    sift = SIFTExtractor(scale_step=1, compute_dtype=jnp.bfloat16)
    pca = BatchPCATransformer(
        jnp.asarray(rng.normal(size=(128, desc_dim)) / 12.0, jnp.float32)
    )
    gmm = GaussianMixtureModel(  # centers as columns: [d, K]
        jnp.asarray(rng.normal(size=(desc_dim, vocab)), jnp.float32),
        jnp.asarray(rng.uniform(0.5, 1.5, (desc_dim, vocab)), jnp.float32),
        jnp.asarray(np.full(vocab, 1.0 / vocab), jnp.float32),
    )
    fv = FisherVector(gmm)

    def featurize(imgs):
        return fv(pca(sift(imgs)))

    fn = jax.jit(featurize)
    batch = jnp.asarray(rng.uniform(0, 1, (n_bench, h, w)).astype(np.float32))
    per_iter = timed_chain(featurize, batch, chain_len=12)
    flops, bytes_accessed = compiled_cost(fn, batch)
    return {
        "images_per_sec": n_bench / per_iter,
        "flops_per_sec": flops / per_iter if flops else None,
        "per_iter": per_iter,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
    }


def bench_stage_ops(rng):
    """Per-stage timings for the remaining hot ops of the north-star
    pipelines (SURVEY §3.3): GMM EM fit, LCS, ZCA whitening fit, PCA fit —
    featurize and the block solve are covered by the headline metrics.
    Shapes are the production defaults of the workloads that call each op
    (imagenet_sift_lcs_fv: descDim=64 vocabSize=16 LCS(4,16,6);
    cifar_random_patch: 6x6x3 patch ZCA)."""
    from keystone_tpu.ops.lcs import LCSExtractor
    from keystone_tpu.solvers.gmm import GaussianMixtureModelEstimator, _em_step
    from keystone_tpu.solvers.pca import compute_pca
    from keystone_tpu.solvers.whitening import ZCAWhitenerEstimator

    out = {}

    def stage(name):
        """Isolate each stage: one noisy/failed op records an error entry
        instead of discarding every other stage's measurement."""
        def deco(fn):
            try:
                out[name] = fn()
            except Exception as e:  # noqa: BLE001 - recorded, not swallowed
                out[name] = _error_record(e)
        return deco

    @stage("gmm_em_step")
    def _():
        # GMM EM (reference EncEval.cxx:122-151 — the one driver-side C++
        # hot loop): time the compiled EM step at the ImageNet-FV shape.
        n_gmm, d, k = 1 << 18, 64, 16
        x = jnp.asarray(rng.normal(size=(n_gmm, d)).astype(np.float32))
        est = GaussianMixtureModelEstimator(k, max_iter=1)
        gmm0 = est.fit(x)  # warm: init + one EM step compiles

        def em_fn(xx):
            m, v, w, _ = _em_step(
                xx, gmm0.means, gmm0.variances, gmm0.weights,
                jnp.float32(1e-3), est.chunk,
            )
            return m + jnp.sum(v) + jnp.sum(w)

        per_iter = timed_chain_auto(em_fn, x, chain_len=16)
        return {
            "n": n_gmm, "d": d, "k": k,
            "samples_per_sec": round(n_gmm / per_iter, 1),
            "seconds_per_iter": round(per_iter, 5),
        }

    @stage("lcs_featurize")
    def _():
        # LCS featurization (reference LCSExtractor.scala via imagenet LCS
        # branch): 256x256 RGB at the workload defaults.
        n_img = 32
        lcs = LCSExtractor(4, 16, 6)
        imgs = jnp.asarray(
            rng.uniform(0, 1, (n_img, 256, 256, 3)).astype(np.float32)
        )
        per_iter = timed_chain_auto(lambda b: lcs(b), imgs, chain_len=24)
        return {"images_per_sec": round(n_img / per_iter, 1)}

    @stage("zca_fit")
    def _():
        # ZCA whitening fit (reference ZCAWhitener.scala:19-64): the cifar
        # 100k x 108 patch-sample SVD.
        zca_mat = jnp.asarray(
            rng.normal(size=(100_000, 108)).astype(np.float32)
        )
        zca = ZCAWhitenerEstimator()
        per_iter = timed_chain_auto(
            lambda m: zca.fit_single(m).whitener, zca_mat, chain_len=4
        )
        return {"n": 100_000, "d": 108, "seconds": round(per_iter, 4)}

    @stage("pca_fit")
    def _():
        # PCA fit (reference PCA.scala:46-61): SIFT-descriptor sample at
        # the ImageNet shape (128-dim descriptors -> 64 components).
        pca_mat = jnp.asarray(
            rng.normal(size=(1 << 18, 128)).astype(np.float32)
        )
        per_iter = timed_chain_auto(
            lambda m: compute_pca(m, 64), pca_mat, chain_len=4
        )
        return {"n": 1 << 18, "d": 128, "dims": 64,
                "seconds": round(per_iter, 4)}

    @stage("mnist_fft_featurize")
    def _():
        # MnistRandomFFT featurization (reference MnistRandomFFT.scala:
        # 51-60): numFFTs random-sign -> padded-FFT -> rectify, zipped.
        from keystone_tpu.core.pipeline import Pipeline
        from keystone_tpu.ops.stats import (
            LinearRectifier, PaddedFFT, RandomSignNode,
        )
        from keystone_tpu.ops.util import ZipVectors

        key = jax.random.PRNGKey(0)
        chains = []
        for _ in range(4):  # canonical --numFFTs 4
            key, sub = jax.random.split(key)
            chains.append(
                Pipeline([RandomSignNode.create(784, sub), PaddedFFT(),
                          LinearRectifier(0.0)])
            )
        mnist_batch = jnp.asarray(
            rng.normal(size=(4096, 784)).astype(np.float32)
        )

        def mnist_feat(b):
            return ZipVectors.apply([c(b) for c in chains])

        per_iter = timed_chain_auto(mnist_feat, mnist_batch, chain_len=64)
        return {"num_ffts": 4, "examples_per_sec": round(4096 / per_iter, 1)}

    @stage("timit_cosine_features")
    def _():
        # TIMIT cosine random features (reference TimitPipeline.scala:
        # 63-70): one [N, 440] x [440, D] gemm + cos per cosine batch.
        from keystone_tpu.ops.stats import CosineRandomFeatures

        crf = CosineRandomFeatures.create(440, 16384, 0.555, jax.random.PRNGKey(1))
        timit_batch = jnp.asarray(
            rng.normal(size=(4096, 440)).astype(np.float32)
        )
        per_iter = timed_chain_auto(lambda b: crf(b), timit_batch, chain_len=64)
        return {"d_out": 16384, "examples_per_sec": round(4096 / per_iter, 1)}

    @stage("block_solve_multiblock")
    def _():
        # The scanned-BCD path of the fused block solve (reference
        # BlockLinearMapper.scala:147-204 with 4 feature blocks x 2
        # epochs): device compute via the serial chain, at a shape where
        # the lax.scan over stacked blocks actually iterates.
        from keystone_tpu.solvers.block import _fused_bcd_fit

        n_s, d_s, bs_s, k_s = 1024, 3200, 800, 10
        xs_ = jnp.asarray(rng.normal(size=(n_s, d_s)).astype(np.float32))
        ys_ = one_hot_pm1(rng, n_s, k_s)
        widths = (bs_s,) * (d_s // bs_s)

        def solve_fn(f):
            models, _, _ = _fused_bcd_fit(
                f, ys_, jnp.float32(1.0), f.shape[0], 2, widths, None
            )
            return models

        per_iter = timed_chain_auto(solve_fn, xs_, chain_len=64)
        return {
            "n": n_s, "d": d_s, "blocks": len(widths), "epochs": 2,
            "device_seconds": round(per_iter, 5),
            "examples_per_sec": round(n_s / per_iter, 1),
        }

    @stage("bwls_fit")
    def _():
        # BWLS fit (reference BlockWeightedLeastSquares.scala:106-312) —
        # the ImageNet pipeline's solver tail, the whole solve one compiled
        # program.  Beyond steady-state wall, the round-5 rigor ask: device
        # seconds + cost analysis of the fused solve program itself, and a
        # wall breakdown whose components are each measured at the REAL
        # shape the fit runs (VERDICT r5 weak #2: the old breakdown summed
        # to more than wall with nothing saying the components overlapped).
        import keystone_tpu.solvers.weighted as wsolver
        from keystone_tpu.solvers.weighted import (
            BlockWeightedLeastSquaresEstimator,
        )

        n_b, d_b, c_b = 8192, 2048, 64
        xw = jnp.asarray(rng.normal(size=(n_b, d_b)).astype(np.float32))
        yw = one_hot_pm1(rng, n_b, c_b)
        bwls = BlockWeightedLeastSquaresEstimator(
            1024, num_iter=1, lam=0.01, mixture_weight=0.5
        )

        # Capture the exact arguments the fit hands the fused program so it
        # can be AOT-timed in isolation (no duplicated preprocessing
        # logic).  The capture substitutes the NON-donating variant so the
        # captured buffers survive the fit for the isolated timing below;
        # both the warm and the timed fit run through it, so the timed
        # wall never includes a donating-variant compile.
        captured = {}
        orig_exec = wsolver._execute_fused_bwls

        def capture(plan, args, statics):
            captured["args"], captured["statics"] = args, statics
            return wsolver._fused_bwls_fit(*args, *statics)

        wsolver._execute_fused_bwls = capture
        try:
            m0 = bwls.fit(xw, yw)  # warm: compiles every program + captures
            float(sum(jnp.sum(x) for x in m0.xs))  # sync

            # Steady-state wall of the WHOLE fit (perturbed input, relative
            # per the solve-timing note: never the warm-up's invocation).
            xw_t = xw * jnp.float32(1.0 + 1e-6)
            float(jnp.sum(xw_t[0]))
            t0 = time.perf_counter()
            m1 = bwls.fit(xw_t, yw)
            float(sum(jnp.sum(x) for x in m1.xs))
            wall = time.perf_counter() - t0
        finally:
            wsolver._execute_fused_bwls = orig_exec

        if "args" not in captured:
            # The fit's ladder never reached the fused tier (budget denied
            # it and stepwise/host-staged ran): the AOT isolation below is
            # meaningless — record the wall + the ladder's own audit trail.
            rep = bwls.last_fit_report
            return {
                "n": n_b, "d": d_b, "classes": c_b,
                "wall_seconds": round(wall, 3),
                "note": "fused tier not chosen; AOT solve isolation skipped",
                "solver_report": rep.record() if rep is not None else None,
            }

        # Host prep: argmax pull + argsort + index builds, measured directly.
        t0 = time.perf_counter()
        ci = np.asarray(jnp.argmax(yw, axis=1))
        order_np = np.argsort(ci, kind="stable")
        host_prep = time.perf_counter() - t0

        # The regroup gather timed on the REAL fallback path the fit runs
        # (ADVICE r5 low): p_tot = n + n_max rows, column-chunked takes
        # into a preallocated output — for BOTH the design matrix and the
        # labels, since the fit sorts each.
        n_max_b = int(np.bincount(ci, minlength=c_b).max())
        p_tot_b = n_b + n_max_b
        gather_np = np.concatenate(
            [order_np, np.full(p_tot_b - n_b, n_b, order_np.dtype)]
        )
        gidx = jnp.asarray(gather_np)
        vmask = jnp.asarray((gather_np < n_b).astype(np.float32))[:, None]
        chunk_cols = max(1, wsolver._GATHER_COL_CHUNK // 4)

        def regroup(xx):
            out = jnp.zeros((p_tot_b, xx.shape[1]), xx.dtype)
            for c0 in range(0, xx.shape[1], chunk_cols):
                sl = jax.lax.slice_in_dim(
                    xx, c0, min(c0 + chunk_cols, xx.shape[1]), axis=1
                )
                g = jnp.take(sl, gidx, axis=0, mode="fill", fill_value=0)
                out = wsolver._scatter_cols(out, g * vmask, jnp.int32(c0))
            return out

        regroup_x = timed_chain_auto(regroup, xw, chain_len=64)
        regroup_y = timed_chain_auto(regroup, yw, chain_len=64)
        regroup_dev = regroup_x + regroup_y

        # The fused solve program, AOT-compiled then executed in a serial
        # chain with a perturbed lam operand (same program, fresh input).
        # args layout: (x, labels_sorted, valid, seg_ids,
        # starts, counts, counts_f, joint_label_mean, nvalid, lam, w).
        args, statics = captured["args"], captured["statics"]
        orig = wsolver._fused_bwls_fit
        compiled = orig.lower(*args, *statics).compile()
        # One cost_analysis reader for the whole repo (core.profiler):
        # same unwrap, same failure posture as every profiled program.
        from keystone_tpu.core import profiler as kprof

        flops, bytes_accessed = kprof.cost_pair(compiled)
        solve_dev = timed_chain_auto(
            lambda xs: orig(
                xs, *args[1:9], args[9] * jnp.float32(1.000001), args[10],
                *statics,
            )[0],
            args[0],
            chain_len=16,
        )
        lat = roundtrip_latency()
        explained = host_prep + regroup_dev + solve_dev + 2 * lat
        rep = bwls.last_fit_report
        return {
            "n": n_b, "d": d_b, "classes": c_b,
            "wall_seconds": round(wall, 3),
            # DISJOINT phases of a fit, each measured independently at the
            # true shape: host prep (argmax pull + argsort), the two sort
            # gathers (design matrix + labels), the fused solve program,
            # and two dispatch round-trips (argmax pull; model pull).
            "wall_breakdown": {
                "host_prep_seconds": round(host_prep, 4),
                "regroup_device_seconds": round(regroup_dev, 4),
                "solve_device_seconds": round(solve_dev, 4),
                "dispatch_roundtrips_seconds": round(2 * lat, 4),
            },
            "wall_explained_seconds": round(explained, 3),
            # >= 0: enqueue/tracing overhead not separately measured;
            # < 0: the independently-measured components overlapped inside
            # wall (async dispatch lets device work run under host prep) —
            # the breakdown is a cost model, NOT a partition of wall.
            "wall_unattributed_seconds": round(wall - explained, 3),
            "roundtrip_latency_seconds": round(lat, 4),
            "solve_flops": flops,
            "solve_bytes_accessed": bytes_accessed,
            "solver_report": rep.record() if rep is not None else None,
        }

    @stage("gmm_em_fit")
    def _():
        # The FULL GMM fit — init + EM to convergence, one compiled loop
        # (reference EncEval.cxx:122-151 runs the whole fit driver-side) —
        # at the ImageNet sampling shape (the 1e6-sample EM cap,
        # ImageNetSiftLcsFV.scala:85-86).  Planted mixture so the
        # convergence path is realistic rather than one-step.
        from keystone_tpu.solvers.gmm import GaussianMixtureModelEstimator

        n_g, d_g, k_g = 1_000_000, 64, 16
        kc, kx, ka = jax.random.split(jax.random.PRNGKey(7), 3)

        @jax.jit
        def make_data():
            centers = jax.random.normal(kc, (k_g, d_g)) * 2.0
            assign = jax.random.randint(ka, (n_g,), 0, k_g)
            return centers[assign] + jax.random.normal(kx, (n_g, d_g)) * 0.5

        x = make_data()  # device-generated: no host transfer
        x.block_until_ready()
        est = GaussianMixtureModelEstimator(k_g)
        est.fit(x)  # warm: compiles init gather + the while_loop fit
        x_t = x * jnp.float32(1.0 + 1e-6)  # never the warm-up's input
        float(jnp.sum(x_t[0]))
        t0 = time.perf_counter()
        est.fit(x_t)
        iters = int(est.last_iterations)  # the one host pull = the sync
        dt = time.perf_counter() - t0
        return {
            "n": n_g, "d": d_g, "k": k_g,
            "iterations": iters,
            "fit_wall_seconds": round(dt, 3),
            "seconds_per_iter": round(dt / max(1, iters), 4),
        }

    return out


def bench_solve_at_scale(rng, shapes=None, bwls_shapes=None, bs=4096):
    """The BCD solve at the largest single-chip-HBM shape that fits
    (VERDICT r4 #2, r5 #1): the flagship one-program claim exercised where
    memory behavior actually matters.  Round-7 discipline (ISSUE 7
    carry-over): every probed shape runs through the ESTIMATOR'S OWN
    degradation ladder — fused -> stepwise -> host-staged, mesh tiers when
    one is ambient — instead of dispatching the fused program directly.
    Bench round r05 (2026-07-30; record removed in PR 21) showed all five
    shapes raw-OOM precisely because the old probe predated the ladder: a shape whose FUSED program cannot place
    can still solve on a degraded tier, and that is the number a capacity
    plan needs.  Every attempt — success AND failure — records the
    ladder's full ``last_fit_report`` (per-tier memory_analysis
    breakdowns, denials, OOM step-downs, the tier that ran).  The
    reference's north-star solve is 1.25M x 256k spread across a cluster
    (ImageNetSiftLcsFV.scala:186-188); per chip that is ~40 GB of design
    matrix per 16 GB-HBM v5e at f32, so single-chip proof means the
    largest shape the ladder lands, with the mesh path scaling
    rows/classes out.
    """
    from keystone_tpu.core import autoshard
    from keystone_tpu.core import memory as kmem

    # Synthetic fixed-seed probes: never read or train the real plan log,
    # even on direct invocation.
    autoshard.hermetic_plan_log()
    k_cls = 128
    if shapes is None:
        shapes = [  # (n, d) descending footprint; ~GB = n*d*4/2**30
            (262144, 16384),  # 16.0 GB design matrix — expected deny
            (196608, 16384),  # 12.0 GB
            (163840, 16384),  # 10.0 GB
            (131072, 16384),  # 8.0 GB
            (131072, 8192),   # 4.0 GB
        ]
    budget = kmem.hbm_budget()
    attempts = []
    result = None
    for n, d in shapes:
        rec = {
            "n": n, "d": d,
            "design_matrix_gb": round(n * d * 4 / 2**30, 2),
        }
        est = BlockLeastSquaresEstimator(bs, num_iter=1, lam=10.0)
        try:
            key = jax.random.PRNGKey(n % 97)

            @jax.jit
            def make(key=key, n=n, d=d):
                kx, ky = jax.random.split(key)
                x = jax.random.normal(kx, (n, d), jnp.float32)
                cls = jax.random.randint(ky, (n,), 0, k_cls)
                y = 2.0 * jax.nn.one_hot(cls, k_cls, dtype=jnp.float32) - 1.0
                return x, y

            x, y = make()
            x.block_until_ready()
            # The wall includes the fit's preflight compiles (the ladder's
            # own admission work IS part of solving at this scale).
            t0 = time.perf_counter()
            model = est.fit(x, y)
            float(  # scalar pull of every model array = the sync
                sum(jnp.sum(b[0]) for b in model.xs)
                + jnp.sum(jnp.asarray(model.b))
            )
            dt = time.perf_counter() - t0
            rep = est.last_fit_report
            result = {
                **rec, "block_size": bs, "classes": k_cls,
                "blocks": d // bs,
                "wall_seconds": round(dt, 3),
                "examples_per_sec": round(n / dt, 1),
                "chosen_tier": rep.chosen if rep is not None else None,
                # The ladder's audit trail: per-tier memory_analysis for
                # every CONSIDERED tier, denials, OOM step-downs.
                "solver": rep.record() if rep is not None else None,
                "hbm_budget_gb": (
                    round(budget / 2**30, 2) if budget is not None else None
                ),
            }
            model = None  # noqa: F841 — free before the next allocation
            break
        except Exception as e:  # noqa: BLE001 — OOM boundary is data
            rep = est.last_fit_report
            attempts.append({
                **rec,
                "error": f"{type(e).__name__}: {e}"[:160],
                "solver": rep.record() if rep is not None else None,
            })
            x = y = None  # free HBM before the next probe
            kmem.clear_plan_cache()
    if result is None:
        # Even with every BCD shape failed, the BWLS probe still runs (its
        # estimator ladder can succeed via stepwise/host-staged on exactly
        # this kind of memory-starved chip) and the probe's cached
        # executables are still released first.
        kmem.clear_plan_cache()
        return {
            "error": "no probed shape fit",
            "attempts": attempts,
            "bwls": _guarded(
                lambda r: _bench_bwls_at_scale(r, shapes=bwls_shapes), rng
            ),
        }
    result["oom_attempts"] = attempts
    # Release this probe's device buffers and drop every probed shape's
    # executable — the plan cache holds them, and loaded executables can
    # reserve device program memory — BEFORE the nested BWLS bench
    # allocates its own multi-GB matrix; leaving buffers live OOMed the
    # nested probe on 16 GB-HBM chips (ADVICE r5).
    x = y = None  # noqa: F841
    kmem.clear_plan_cache()
    result["bwls"] = _guarded(
        lambda r: _bench_bwls_at_scale(r, shapes=bwls_shapes), rng
    )
    return result


def _bench_bwls_at_scale(rng, shapes=None, bs=4096):
    """The whole class-weighted fit at HBM-stressing scale (VERDICT r4 #2,
    r5 #1), probed through the estimator's OWN admission-control ladder:
    each shape's fit preflights fused/stepwise/host-staged tiers, runs the
    best admitted tier (donating the caller's x once the sorted copy
    exists), and ``last_fit_report`` lands in the record — per-tier
    memory_analysis breakdowns for every probed shape, successes AND
    failures, plus which tier actually solved it."""
    from keystone_tpu.solvers.weighted import BlockWeightedLeastSquaresEstimator

    c = 256
    if shapes is None:
        shapes = [  # (n, d) descending footprint
            (131072, 16384),  # 8.0 GB design matrix
            (131072, 8192),   # 4.0 GB
        ]
    attempts = []
    result = None
    for n, d in shapes:
        rec = {
            "n": n, "d": d, "classes": c, "block_size": bs,
            "design_matrix_gb": round(n * d * 4 / 2**30, 2),
        }
        est = BlockWeightedLeastSquaresEstimator(
            bs, num_iter=1, lam=0.01, mixture_weight=0.25
        )
        try:
            key = jax.random.PRNGKey(11 + d % 13)

            @jax.jit
            def make(key=key, n=n, d=d):
                kx, ky = jax.random.split(key)
                x = jax.random.normal(kx, (n, d), jnp.float32)
                cls = jax.random.randint(ky, (n,), 0, c)
                y = 2.0 * jax.nn.one_hot(cls, c, dtype=jnp.float32) - 1.0
                return x, y

            x, y = make()
            x.block_until_ready()
            # donate=True: the fit frees this x/y once their sorted copies
            # exist — the caller-side half of the 2x class-sort peak.
            # The wall includes the fit's one-time preflight compiles.
            t0 = time.perf_counter()
            model = est.fit(x, y, donate=True)
            float(sum(jnp.sum(b) for b in model.xs))  # scalar pull = sync
            wall = time.perf_counter() - t0
            rep = est.last_fit_report
            result = {
                **rec,
                "fit_wall_seconds": round(wall, 3),
                "examples_per_sec": round(n / wall, 1),
                "solver": rep.record() if rep is not None else None,
            }
            model = None  # noqa: F841 — free before returning to the caller
            break
        except Exception as e:  # noqa: BLE001 — the boundary is data
            rep = est.last_fit_report
            attempts.append({
                **rec,
                "error": f"{type(e).__name__}: {e}"[:160],
                "solver": rep.record() if rep is not None else None,
            })
            x = y = None  # free HBM before the next probe
    if result is None:
        return {"error": "no probed shape fit", "attempts": attempts}
    result["attempts"] = attempts
    return result


def bench_placement(rng):
    """Placement-search section (ISSUE 9): the cost-model-ranked plan
    (core.autoshard) vs the hand-enumerated ladder on the SAME BCD solve,
    across >= 3 design-matrix shapes.

    Per shape, both fits run on identical inputs after a shared warmup fit
    (so neither pays first-compile costs the other skips): ``hand`` walks
    the hand ladder (``plan=False``), ``searched`` runs the ranked
    candidate list (``plan=True``).  The acceptance bars: the searched
    fit's model is BIT-IDENTICAL to the hand fit's (an untrained cost
    model never deviates from the proven default), its wall is <= the hand
    wall within noise, and the search overhead (``search_seconds`` — the
    enumerate + prune + score pass, no compiles) stays under 5% of the fit
    wall.  ``prediction_error`` is the chosen plan's predicted/measured
    ratio — the figure the plan-outcome log's learned calibration drives
    toward 1.0 across runs.
    """
    from keystone_tpu.core import autoshard
    from keystone_tpu.core import memory as kmem

    # Even when invoked directly (the verify one-liner), this section's
    # fixed-rng fits must not read or train the operator's real plan log.
    autoshard.hermetic_plan_log()
    k_cls = 64
    bs = 1024
    shapes = [(16384, 2048), (8192, 4096), (32768, 1024)]
    rows = []
    for n, d in shapes:
        x = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
        y = jnp.asarray(
            2.0 * np.eye(k_cls, dtype=np.float32)[
                rng.integers(0, k_cls, n)
            ] - 1.0
        )

        def one_fit(plan, n=n):
            est = BlockLeastSquaresEstimator(bs, num_iter=1, lam=10.0)
            t0 = time.perf_counter()
            model = est.fit(x, y, plan=plan)
            float(  # scalar pull of every model array = the sync
                sum(jnp.sum(b) for b in model.xs)
                + jnp.sum(jnp.asarray(model.b))
            )
            return time.perf_counter() - t0, model, est.last_fit_report

        one_fit(False)  # shared warmup: compiles cached for both timed fits
        hand_wall, hand_model, hand_rep = one_fit(False)
        srch_wall, srch_model, srch_rep = one_fit(True)
        bit_identical = bool(
            np.array_equal(np.asarray(hand_model.b), np.asarray(srch_model.b))
            and all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(hand_model.xs, srch_model.xs)
            )
        )
        placement = srch_rep.placement if srch_rep is not None else None
        rows.append({
            "n": n, "d": d, "block_size": bs, "classes": k_cls,
            "hand_wall_seconds": round(hand_wall, 4),
            "searched_wall_seconds": round(srch_wall, 4),
            "searched_vs_hand": round(srch_wall / hand_wall, 4),
            "hand_chosen": hand_rep.chosen if hand_rep is not None else None,
            "searched_chosen": (
                srch_rep.chosen if srch_rep is not None else None
            ),
            "predictions_bit_identical": bit_identical,
            "search_seconds": (
                placement["search_seconds"] if placement else None
            ),
            "search_overhead_frac": (
                round(placement["search_seconds"] / srch_wall, 5)
                if placement else None
            ),
            "prediction_error": (
                placement["prediction_error"] if placement else None
            ),
            "candidates": len(placement["candidates"]) if placement else 0,
            "pruned": (
                sum(1 for c in placement["candidates"] if c["pruned"])
                if placement else 0
            ),
            "ranking": placement["ranking"] if placement else None,
        })
        hand_model = srch_model = x = y = None  # noqa: F841 — free HBM
        kmem.clear_plan_cache()
    return {
        "shapes": rows,
        "all_bit_identical": all(r["predictions_bit_identical"] for r in rows),
        "max_search_overhead_frac": max(
            (r["search_overhead_frac"] or 0.0) for r in rows
        ),
        # ISSUE 10: executed sharding specs + the cross-program
        # calibration model.
        "spec_execution": _bench_spec_execution(rng),
        "cross_program": _bench_cross_program(rng),
    }


def _bench_spec_execution(rng):
    """Searched-SPEC-vs-default fit wall (ISSUE 10) on >= 2 shapes: under
    a mesh over all live devices, fit once with the default layout
    (``plan=False`` — the hand mesh ladder) and once with a forced replay
    of a SPEC-assignment candidate (same mesh shape, non-default
    per-operand layout, e.g. model-axis-sharded label columns), asserting
    the models BIT-IDENTICAL — a spec layout changes placement, never
    results.  With one device the spec dimension is degenerate; recorded
    honestly instead of faked."""
    from keystone_tpu.core import memory as kmem
    from keystone_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < 2:
        return {
            "note": (
                f"single device ({len(devs)}): no non-trivial spec "
                "layouts to execute"
            ),
            "shapes": [],
        }
    model_ax = 2 if len(devs) % 2 == 0 else 1
    mesh = make_mesh(data=len(devs) // model_ax, model=model_ax)
    k_cls = 64
    bs = 1024
    rows = []
    for n, d in [(8192, 2048), (16384, 1024)]:
        x = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
        y = jnp.asarray(
            2.0 * np.eye(k_cls, dtype=np.float32)[
                rng.integers(0, k_cls, n)
            ] - 1.0
        )

        def one_fit(plan):
            est = BlockLeastSquaresEstimator(
                bs, num_iter=1, lam=10.0, mesh=mesh
            )
            t0 = time.perf_counter()
            model = est.fit(x, y, plan=plan)
            float(
                sum(jnp.sum(b) for b in model.xs)
                + jnp.sum(jnp.asarray(model.b))
            )
            return time.perf_counter() - t0, model, est.last_fit_report

        # Discover a same-mesh-shape spec candidate from one search pass.
        _w, _m, probe_rep = one_fit(True)
        head_mesh = None
        spec_name = None
        for c in probe_rep.placement["candidates"]:
            if c["name"] == probe_rep.placement["ranking"][0]:
                head_mesh = c["mesh"]
        for c in probe_rep.placement["candidates"]:
            if c.get("specs") and c["mesh"] == head_mesh and not c["pruned"]:
                spec_name = c["name"]
                break
        if spec_name is None:
            rows.append({
                "n": n, "d": d,
                "note": "no executable spec candidate on the head mesh",
            })
            continue
        # Warm BOTH programs before timing: the spec layout is its own jit
        # specialization, so without its own warmup the spec fit would pay
        # a full XLA compile inside the timed region while the default
        # (already compiled by the probe) did not — the same
        # neither-pays-first-compile bar the enclosing section sets.
        one_fit(False)
        one_fit([spec_name])
        def_wall, def_model, _rep = one_fit(False)
        spec_wall, spec_model, spec_rep = one_fit([spec_name])
        rows.append({
            "n": n, "d": d, "mesh": dict(mesh.shape), "spec": spec_name,
            "default_wall_seconds": round(def_wall, 4),
            "spec_wall_seconds": round(spec_wall, 4),
            "spec_vs_default": round(spec_wall / def_wall, 4),
            "chosen": spec_rep.chosen,
            "bit_identical": bool(
                np.array_equal(
                    np.asarray(def_model.b), np.asarray(spec_model.b)
                )
                and all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(def_model.xs, spec_model.xs)
                )
            ),
        })
        x = y = def_model = spec_model = None  # noqa: F841 — free HBM
        kmem.clear_plan_cache()
    return {"mesh": dict(mesh.shape), "shapes": rows}


def _bench_cross_program(rng):
    """Cross-program calibration error (ISSUE 10): train the featurized
    ratio regression (optimize.CalibrationModel) on the plan-log outcomes
    of SHAPE A's fits only, then predict the measured/prior ratio of
    SHAPE B's chosen plan — a shape the model never saw.  Reported as
    ``predicted_over_actual`` (1.0 = perfect transfer) next to the
    untrained prior's own error, so the log shows what the learned model
    buys over the raw roofline."""
    from keystone_tpu.core import autoshard
    from keystone_tpu.core import memory as kmem
    from keystone_tpu.core import optimize as kopt

    k_cls = 32
    bs = 1024

    def fit_once(n, d):
        x = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
        y = jnp.asarray(
            2.0 * np.eye(k_cls, dtype=np.float32)[
                rng.integers(0, k_cls, n)
            ] - 1.0
        )
        est = BlockLeastSquaresEstimator(bs, num_iter=1, lam=10.0)
        est.fit(x, y, plan=True)
        return est.last_fit_report.placement

    shape_a, shape_b = (8192, 2048), (16384, 1024)
    # Three measured outcomes of shape A (each appends to the hermetic
    # log; the in-process read cache keeps the rankings untrained).
    fp_a = None
    for _ in range(3):
        fp_a = fit_once(*shape_a)["fingerprint"]
    placement_b = fit_once(*shape_b)
    kmem.clear_plan_cache()
    autoshard.clear_outcome_cache()  # re-read the log written above
    rows_a = [r for r in autoshard.model_rows() if r[0] == fp_a]
    model = kopt.CalibrationModel.fit_rows(rows_a)
    chosen = next(
        (
            c for c in placement_b["candidates"]
            if c["name"] == placement_b["chosen"]
        ),
        None,
    )
    if model is None or chosen is None or not chosen.get("measured_seconds"):
        return {
            "note": "insufficient outcomes to train/evaluate",
            "train_rows": len(rows_a),
        }
    actual = chosen["measured_seconds"] / chosen["raw_seconds"]
    predicted = model.predict_factor(chosen["features"])
    return {
        "trained_on": {"n": shape_a[0], "d": shape_a[1], "rows": len(rows_a)},
        "predicted_on": {"n": shape_b[0], "d": shape_b[1]},
        "candidate": chosen["name"],
        "actual_ratio": round(actual, 4),
        "model_predicted_ratio": round(predicted, 4),
        "predicted_over_actual": round(predicted / actual, 4),
        # the raw prior's factor is 1.0 by definition — its error IS the
        # actual ratio; the model's win is |log| closer to zero.
        "prior_over_actual": round(1.0 / actual, 4),
        "model": model.record(),
    }


def bench_e2e_ingest(rng):
    """Streaming-ingest e2e (ROADMAP "End-to-end ingest overlap"): tar ->
    decode -> featurize(-> solve) through core.ingest — decoder threads fill
    the host ring while the device featurizes the previous batch behind a
    double-buffered H2D.  Three rates per workload, each over the SAME tar:

    * ``decode_images_per_sec``  — stream with the H2D/featurize stages off
      (the producer-side ceiling);
    * ``featurize_images_per_sec`` — H2D + featurize over pre-decoded host
      chunks (the consumer-side ceiling; inputs perturbed so the pass never
      repeats the e2e pass's identical data);
    * ``e2e_images_per_sec`` — the full overlapped pipeline.

    ``overlap_efficiency = e2e / min(decode, featurize)`` — 1.0 means the
    slower stage fully hides the faster one; the target is >= 0.9.  Ring
    depth/stall counters come from the stream's own stats.  Images are
    48 px (the loaders' 36 px MIN_DIM floor rules out true-32px CIFAR
    JPEGs) and CIFAR labels ride in the member names."""
    from keystone_tpu.core.ingest import StreamConfig, stream_batches

    def no_snap():
        # The decode/e2e passes must MEASURE DECODE: an ambient
        # KEYSTONE_SNAPSHOT_DIR would silently serve them from the cache
        # and report shard-read rates as decode rates.  Empty string
        # survives from_env's None-filter and disables the cache.
        return StreamConfig.from_env(snapshot_dir="")

    def rates(tar_path, n_images, batch, feat_fn):
        # decode-only: producer-side ceiling (no H2D, no featurize)
        t0 = time.perf_counter()
        with stream_batches(
            tar_path, batch, transfer=False, config=no_snap()
        ) as st:
            chunks = [b.host for b in st]
        decode_secs = time.perf_counter() - t0
        n_decoded = sum(c.shape[0] for c in chunks)
        assert n_decoded == n_images, (n_decoded, n_images)
        # featurize-only over the pre-decoded chunks; RELATIVE perturbation
        # so the e2e pass never repeats this pass's identical data
        chunks = [c * np.float32(1.0 + 1e-6) for c in chunks]
        np.asarray(feat_fn(jax.device_put(chunks[0])))  # compile warm-up
        t0 = time.perf_counter()
        for c in chunks:
            np.asarray(feat_fn(jax.device_put(c)))
        feat_secs = time.perf_counter() - t0
        del chunks
        # e2e: the overlapped pipeline (decode threads + ring + double-
        # buffered H2D + featurize, synced per consumed batch)
        feats = []
        t0 = time.perf_counter()
        with stream_batches(tar_path, batch, config=no_snap()) as st:
            for b in st:
                feats.append((b.indices, np.asarray(feat_fn(b.device))))
        e2e_secs = time.perf_counter() - t0
        decode_rate = n_images / decode_secs
        feat_rate = n_images / feat_secs
        e2e_rate = n_images / e2e_secs
        # snapshot-warm e2e (ISSUE 7 target: e2e within 10% of the pure-
        # featurize rate): a cold pass materializes the decoded chunks,
        # then the e2e pipeline streams the SHARDS — the decode wall is
        # gone and only shard IO bounds the producer.  The featurize input
        # is perturbed relative to the plain-e2e pass above.
        import shutil as _sh
        import tempfile as _tf

        snap_root = _tf.mkdtemp(prefix="bench_e2e_snap_")
        try:
            with stream_batches(
                tar_path, batch, transfer=False,
                config=StreamConfig.from_env(
                    snapshot_dir=snap_root, snapshot_mode="decoded"
                ),
            ) as st_cold:
                for _ in st_cold:
                    pass
            t0 = time.perf_counter()
            with stream_batches(
                tar_path, batch,
                config=StreamConfig.from_env(
                    snapshot_dir=snap_root, snapshot_mode="decoded"
                ),
            ) as st_warm:
                for b in st_warm:
                    np.asarray(feat_fn(b.device * jnp.float32(1.0 + 1e-6)))
            snap_e2e_rate = n_images / (time.perf_counter() - t0)
            warm_chunks_read = st_warm.stats.snapshot_chunks_read
        finally:
            _sh.rmtree(snap_root, ignore_errors=True)
        # What a NON-overlapped pipeline does: decode everything, then
        # featurize (total = t_decode + t_featurize).  e2e/serial_bound is
        # the speedup the overlap actually bought; on a host whose decode
        # threads and featurize compute share the SAME cores (CPU backend)
        # the serial bound — not min(decode, featurize) — is the physical
        # ceiling, so both ratios are recorded.
        serial_bound = n_images / (decode_secs + feat_secs)
        return {
            "images": n_images,
            "batch": batch,
            "decode_images_per_sec": round(decode_rate, 2),
            "featurize_images_per_sec": round(feat_rate, 2),
            "e2e_images_per_sec": round(e2e_rate, 2),
            "overlap_efficiency": round(
                e2e_rate / min(decode_rate, feat_rate), 3
            ),
            "serial_bound_images_per_sec": round(serial_bound, 2),
            "speedup_vs_serial": round(e2e_rate / serial_bound, 3),
            # The decode wall removed: e2e off the materialized snapshot,
            # and its fraction of the pure-featurize ceiling (the ISSUE 7
            # target is >= 0.9 — shard IO is the remaining bound when it
            # falls short).
            "snapshot_e2e_images_per_sec": round(snap_e2e_rate, 2),
            "snapshot_e2e_vs_featurize": round(snap_e2e_rate / feat_rate, 3),
            "snapshot_chunks_read": warm_chunks_read,
            "ring": st.stats.record(),
        }, feats

    out = {"overlap_target": 0.9}

    # -- CIFAR conv featurize (north star #1's pipeline) off a JPEG tar
    from keystone_tpu.workloads.cifar_random_patch import cifar_tar_label

    n_cifar, size, batch = 1024, 48, 128
    tar_path = _make_jpeg_tar(rng, n_cifar, size, labeled=True)
    try:
        conf = RandomCifarConfig(
            num_filters=100, patch_size=6, patch_steps=1, pool_size=14,
            pool_stride=13, whitener_size=20000, featurize_chunk=batch,
        )
        seed_imgs = rng.uniform(0, 255, (256, size, size, 3)).astype(np.float32)
        filters, whitener = learn_filters(conf, seed_imgs)
        feat_fn = jax.jit(build_conv_pipeline(conf, filters, whitener).__call__)
        cifar_rec, feats = rates(tar_path, n_cifar, batch, feat_fn)
        # (-> solve): the streamed features feed the block solve — labels
        # decoded from the member names, the reference pipeline's tail.
        order = np.argsort(np.concatenate([ix for ix, _ in feats]))
        x = jnp.asarray(np.concatenate([f for _, f in feats], axis=0)[order])
        labels = one_hot_pm1(np.random.default_rng(2), n_cifar, 10)
        est = BlockLeastSquaresEstimator(4096, num_iter=1, lam=10.0)
        t0 = time.perf_counter()
        model = est.fit(x, labels)
        float(sum(jnp.sum(b[0]) for b in model.xs))  # scalar pull = sync
        solve_secs = time.perf_counter() - t0
        cifar_rec["solve_seconds"] = round(solve_secs, 3)
        cifar_rec["e2e_solve_images_per_sec"] = round(
            n_cifar / (n_cifar / cifar_rec["e2e_images_per_sec"] + solve_secs),
            2,
        )
        assert cifar_tar_label("3/img_00000.jpg") == 3  # name-borne labels
        out["cifar"] = cifar_rec
    finally:
        os.unlink(tar_path)

    # -- ImageNet-FV branch (north star #2's featurize) off a JPEG tar
    from keystone_tpu.workloads.fv_common import grayscale

    n_fv, size_fv, batch_fv = 96, 256, 16
    tar_path = _make_jpeg_tar(rng, n_fv, size_fv, labeled=True)
    try:
        desc_dim, vocab = 64, 16
        sift = SIFTExtractor(scale_step=1, compute_dtype=jnp.bfloat16)
        pca = BatchPCATransformer(
            jnp.asarray(rng.normal(size=(128, desc_dim)) / 12.0, jnp.float32)
        )
        gmm = GaussianMixtureModel(
            jnp.asarray(rng.normal(size=(desc_dim, vocab)), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, (desc_dim, vocab)), jnp.float32),
            jnp.asarray(np.full(vocab, 1.0 / vocab), jnp.float32),
        )
        fv = FisherVector(gmm)
        fv_fn = jax.jit(lambda imgs: fv(pca(sift(grayscale(imgs)))))
        out["imagenet_fv"], _ = rates(tar_path, n_fv, batch_fv, fv_fn)
    finally:
        os.unlink(tar_path)

    return out


def bench_optimizer(rng):
    """Pipeline-optimizer section (ISSUE 6): the cost-based auto-Cacher on
    the CIFAR conv >> StandardScaler fit chain, and the closed-loop ingest
    autotuner on a stall-injected stream.

    * ``auto_cache``: the fit pattern — ``chain.fit(x)`` then one fitted
      application to the SAME x (the workload usage) — runs the conv
      featurizer twice uncached and once with the optimizer's memoizing
      Cacher.  Both walls are measured on the same warmed program; the
      features must be bit-identical (the memo replays the fit's arrays).
    * ``autotune``: decode is slowed artificially so the stream starts
      decode-bound at a deliberately-starved static config; the tuned run
      starts from the SAME config with the controller on.  Overlap
      efficiency = e2e rate / the decode-ceiling rate measured at the
      static config — the tuned run must not be below the static one.
    """
    from keystone_tpu.core import optimize
    from keystone_tpu.core.ingest import StreamConfig, stream_batches
    from keystone_tpu.core.pipeline import FunctionTransformer
    from keystone_tpu.loaders import image_loaders
    from keystone_tpu.ops.stats import StandardScaler
    from keystone_tpu.workloads.cifar_random_patch import featurize_chunked

    out = {}

    # -- auto-Cacher: cached vs uncached fit wall over the conv chain
    n, chunk = 2048, 512
    conf = RandomCifarConfig(
        num_filters=100, patch_size=6, patch_steps=1, pool_size=14,
        pool_stride=13, whitener_size=20000, featurize_chunk=chunk,
    )
    imgs = rng.uniform(0, 255, (n, 32, 32, 3)).astype(np.float32)
    filters, whitener = learn_filters(conf, imgs[:512])
    feat_fn = jax.jit(build_conv_pipeline(conf, filters, whitener).__call__)
    # Warm the chunk-shaped compile so both timed fits are steady-state.
    jax.block_until_ready(feat_fn(jnp.zeros((chunk, 32, 32, 3), jnp.float32)))

    def make_chain():
        return FunctionTransformer(
            lambda im: featurize_chunked(feat_fn, np.asarray(im), chunk),
            name="conv_featurize",
        ).then_estimator(StandardScaler())

    t0 = time.perf_counter()
    fitted_u = make_chain().fit(imgs)
    feats_u = jax.block_until_ready(fitted_u(imgs))
    wall_uncached = time.perf_counter() - t0

    opt_chain, plan = optimize.auto_cache_chain(
        make_chain(), imgs[:chunk], dataset_rows=n
    )
    t0 = time.perf_counter()
    fitted_c = opt_chain.fit(imgs)
    feats_c = jax.block_until_ready(fitted_c(imgs))
    wall_cached = time.perf_counter() - t0
    bit_identical = bool(
        np.array_equal(np.asarray(feats_u), np.asarray(feats_c))
    )
    optimize.release_caches(fitted_c)
    out["auto_cache"] = {
        "images": n,
        "uncached_fit_wall_seconds": round(wall_uncached, 3),
        "cached_fit_wall_seconds": round(wall_cached, 3),
        "speedup": round(wall_uncached / wall_cached, 3),
        "predictions_bit_identical": bit_identical,
        "plan": plan.record(),
    }
    feats_u = feats_c = fitted_u = fitted_c = None  # noqa: F841 — free HBM

    # -- closed-loop autotuner on a stall-injected stream
    n_img, size, batch = 192, 48, 16
    tar_path = _make_jpeg_tar(rng, n_img, size)

    small_feat = jax.jit(lambda x: jnp.mean(x, axis=(1, 2, 3)))
    real_decode = image_loaders.decode_image

    def stalled_decode(data):
        time.sleep(0.005)  # the injected stall: decode-bound by fiat
        return real_decode(data)

    def run_stream(cfg, tuner=None):
        t0 = time.perf_counter()
        feats = []
        with stream_batches(tar_path, batch, config=cfg, tuner=tuner) as st:
            for b in st:
                feats.append((b.indices, np.asarray(small_feat(b.dev()))))
        secs = time.perf_counter() - t0
        assert st.join(10.0)
        return n_img / secs, feats, st

    starved = dict(
        decode_threads=1, decode_ahead=0, ring_capacity=2,
        max_decode_threads=8,
    )
    image_loaders.decode_image = stalled_decode
    try:
        # The decode ceiling AT the static config: no featurize, no H2D.
        t0 = time.perf_counter()
        with stream_batches(
            tar_path, batch, transfer=False, config=StreamConfig(**starved)
        ) as st:
            for _ in st:
                pass
        decode_rate = n_img / (time.perf_counter() - t0)
        static_rate, static_feats, _ = run_stream(StreamConfig(**starved))
        tuned_cfg = StreamConfig(**starved, autotune_interval=2)
        # Backend promotion is pinned OFF here, deliberately: the stall is
        # a parent-process monkeypatch that spawned decode workers would
        # bypass, so a process-backend measurement under it is fiction —
        # this section measures the knob-tuning loop; the process
        # backend's real rates live in the jpeg_decode section.
        tuned_rate, tuned_feats, st = run_stream(
            tuned_cfg,
            tuner=optimize.IngestAutotuner(
                interval=2, allow_backend_switch=False
            ),
        )
    finally:
        image_loaders.decode_image = real_decode
        os.unlink(tar_path)

    stream_identical = len(static_feats) == len(tuned_feats) and all(
        np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        for a, b in zip(static_feats, tuned_feats)
    )
    out["autotune"] = {
        "images": n_img,
        "static_images_per_sec": round(static_rate, 2),
        "tuned_images_per_sec": round(tuned_rate, 2),
        "speedup": round(tuned_rate / static_rate, 3),
        # efficiency vs the ceiling of the STATIC config's decode stage —
        # the tuned run beats 1.0 by widening decode past that config.
        "static_overlap_efficiency": round(static_rate / decode_rate, 3),
        "tuned_overlap_efficiency": round(tuned_rate / decode_rate, 3),
        "output_bit_identical": stream_identical,
        "tuner": st.tuner.record(),
    }
    return out


def _decode_path_breakdown(
    rng, batch: int = 16, n_images: int = 48, size: int = 96
):
    """The ISSUE 13 per-path decode ledger: ONE mixed corpus tar (4:4:4 /
    4:2:2 / 4:2:0, qualities 85/90/95, restart markers — the subset the
    device path claims) measured through three ingest paths:

    * ``host_pool`` — threaded host decode, device featurize;
    * ``device`` — entropy-only host pass, batched dequant+IDCT+upsample+
      colorspace FUSED into the featurize (ops.jpeg_device);
    * ``device_snapshot_warm`` — warm epoch off the device-format
      snapshot tier (pure DMA: zero host decode/transform).

    Each path records e2e, decode-only and featurize-only images/sec plus
    ``overlap_efficiency`` = e2e / min(decode, featurize) (the PR 4
    definition), and the device path records its golden parity vs the
    host decoder.  Every path runs one untimed warmup pass first so
    compile time never pollutes a rate."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from keystone_tpu.core.ingest import StreamConfig, stream_batches

    n = n_images
    tar_path = _make_jpeg_tar(
        rng, n, size, subsamplings=(0, 1, 2), qualities=(85, 90, 95),
        restart_every=2,
    )
    feat = jax.jit(
        lambda x: jnp.stack(
            [jnp.mean(x, axis=(1, 2, 3)), jnp.max(x, axis=(1, 2, 3))],
            axis=1,
        )
    )
    snap_root = tempfile.mkdtemp(prefix="bench_devsnap_")

    def one_pass(transfer, featurize, collect=False, **cfg_kw):
        cfg_kw.setdefault("snapshot_dir", "")  # ambient cache pinned off
        cfg = StreamConfig.from_env(**cfg_kw)
        chunks = []
        t0 = time.perf_counter()
        count = 0
        with stream_batches(
            tar_path, batch, transfer=transfer, config=cfg
        ) as st:
            for b in st:
                if featurize:
                    np.asarray(b.apply(feat))
                if collect:
                    chunks.append(b)
                count += len(b)
        secs = time.perf_counter() - t0
        assert st.join(20.0), "ingest threads leaked"
        assert count == n, (count, n)
        return n / secs, st.stats, chunks

    def feat_only_rate(chunks):
        # warmup already happened in the pass that collected the chunks
        t0 = time.perf_counter()
        for b in chunks:
            np.asarray(b.apply(feat))
        return n / (time.perf_counter() - t0)

    out = {}
    try:
        # -- host thread pool -------------------------------------------------
        one_pass(True, True)  # warmup (jit compiles)
        host_e2e, _s, _ = one_pass(True, True)
        host_dec, _s, host_chunks = one_pass(False, False, collect=True)
        host_feat = feat_only_rate(host_chunks)
        out["host_pool"] = {
            "images_per_sec": round(host_e2e, 2),
            "decode_images_per_sec": round(host_dec, 2),
            "featurize_images_per_sec": round(host_feat, 2),
            "overlap_efficiency": round(
                host_e2e / max(1e-9, min(host_dec, host_feat)), 3
            ),
        }

        # -- device decode (entropy host pass + fused on-device pixels) -------
        one_pass(True, True, decode_mode="device")  # warmup
        dev_e2e, dev_stats, _ = one_pass(True, True, decode_mode="device")
        dev_dec, _s, dev_chunks = one_pass(
            False, False, collect=True, decode_mode="device"
        )
        dev_feat = feat_only_rate(dev_chunks)
        # golden parity: device vs host pixels matched BY MEMBER NAME —
        # the two paths bucket differently (device buckets fold the
        # sampling geometry in), so chunk i holds different images.
        def pixels_by_name(chunks, limit=9):
            got = {}
            for b in chunks:
                px = np.asarray(b.dev())
                for j, nm in enumerate(b.names):
                    if len(got) < limit:
                        got[nm] = px[j]
                if len(got) >= limit:
                    break
            return got

        from keystone_tpu.ops.jpeg_device import GOLDEN_MAX_ABS

        host_px = pixels_by_name(host_chunks)
        dev_px = pixels_by_name(dev_chunks, limit=n)
        common = sorted(set(host_px) & set(dev_px))
        assert common, "no overlapping members between the two paths"
        parity = max(
            float(np.max(np.abs(dev_px[nm] - host_px[nm])))
            for nm in common
        )
        out["device"] = {
            "images_per_sec": round(dev_e2e, 2),
            "decode_images_per_sec": round(dev_dec, 2),  # entropy pass
            "featurize_images_per_sec": round(dev_feat, 2),
            "overlap_efficiency": round(
                dev_e2e / max(1e-9, min(dev_dec, dev_feat)), 3
            ),
            "entropy_decoded": dev_stats.entropy_decoded,
            # the scan hot-loop backend this pass ACTUALLY ran — "native"
            # (ops.native_entropy) or "python" (the portable fallback)
            "entropy_backend": dev_stats.entropy_backend,
            "fallbacks": dev_stats.device_fallbacks,
            "coeff_bytes": dev_stats.coeff_bytes,
            "golden_max_abs_vs_host": parity,
            "within_golden_tolerance": bool(parity <= GOLDEN_MAX_ABS),
        }

        # -- entropy hot-loop backends (ISSUE 19) -----------------------------
        # Direct entropy_decode rates over the SAME corpus members, native
        # vs Python, single-threaded — the isolated cost of the scan loop
        # the backends swap (the e2e device rate above shows what the
        # swap buys the stream).  Native numbers are recorded only when
        # the library actually built; the leg always records which
        # backend the live device path resolved to.
        from keystone_tpu.loaders.image_loaders import _iter_tar_members
        from keystone_tpu.ops import jpeg_device as _jd
        from keystone_tpu.ops import native_entropy as _ne

        members = [d for _nm, d in _iter_tar_members(tar_path)]

        def entropy_rate(backend):
            _jd.entropy_decode(members[0], backend=backend)  # warm LUT cache
            t0 = time.perf_counter()
            for d in members:
                _jd.entropy_decode(d, backend=backend)
            return n / (time.perf_counter() - t0)

        py_rate = entropy_rate("python")
        entropy_leg = {
            "images": n,
            "python_images_per_sec": round(py_rate, 2),
            "backend_live": _jd.entropy_backend(),
            "e2e_device_images_per_sec": round(dev_e2e, 2),
            "e2e_overlap_efficiency": out["device"]["overlap_efficiency"],
        }
        if _ne.available():
            nat_rate = entropy_rate("native")
            entropy_leg["native_images_per_sec"] = round(nat_rate, 2)
            entropy_leg["speedup"] = round(nat_rate / py_rate, 3)
        out["entropy_native"] = entropy_leg

        # -- warm device-format snapshot (pure DMA) ---------------------------
        # cold pass (host decode + device-format tee), untimed
        one_pass(
            True, True, snapshot_dir=snap_root, snapshot_mode="device"
        )
        warm_e2e, warm_stats, _ = one_pass(
            True, True, snapshot_dir=snap_root, snapshot_mode="device"
        )
        warm_dec, _s, _ = one_pass(
            False, False, snapshot_dir=snap_root, snapshot_mode="device"
        )
        out["device_snapshot_warm"] = {
            "images_per_sec": round(warm_e2e, 2),
            "decode_images_per_sec": round(warm_dec, 2),  # shard DMA
            "featurize_images_per_sec": round(host_feat, 2),
            "overlap_efficiency": round(
                warm_e2e / max(1e-9, min(warm_dec, host_feat)), 3
            ),
            "dma_bytes": warm_stats.snapshot_dma_bytes,
            # the acceptance bar: a warm device epoch does ZERO host-side
            # decode/transform — recorded, not assumed
            "zero_host_decode": bool(
                warm_stats.entropy_decoded == 0
                and warm_stats.device_fallbacks == 0
                and warm_stats.snapshot_chunks_read > 0
            ),
        }
    finally:
        os.unlink(tar_path)
        shutil.rmtree(snap_root, ignore_errors=True)
    return out


def bench_decode(rng):
    """Host ingest: JPEG-tar decode throughput — serial, thread-pool,
    PROCESS-pool at 1/2/4/8 workers, and snapshot cold-write vs warm-read
    (reference decodes per-executor in parallel off streamed tars,
    ImageLoaderUtils.scala:60-100).  The thread pool is GIL-bound
    (bench round r05, 2026-07-30, record removed in PR 21: 1.04x); the
    process pool and the snapshot cache are ISSUE
    7's two attacks on that wall, so their rates sit next to the old
    numbers where the wall's removal is visible.  Speedups are whatever
    the bench host's core budget yields — reported, not assumed, with the
    bounding resource named when scaling falls short."""
    import shutil
    import tempfile

    from keystone_tpu.core.ingest import (
        StreamConfig,
        _host_cores,
        stream_batches,
    )
    from keystone_tpu.core.optimize import advise_snapshot
    from keystone_tpu.loaders.image_loaders import (
        _iter_tar_images,
        decode_threads,
    )

    n_images = 192
    tar_path = _make_jpeg_tar(rng, n_images, 256)

    def timed(threads):
        t0 = time.perf_counter()
        count = sum(1 for _ in _iter_tar_images(tar_path, num_threads=threads))
        dt = time.perf_counter() - t0
        assert count == n_images
        return n_images / dt

    try:
        serial = timed(1)
        threads = decode_threads()
        threaded = timed(threads)
        # Native-vs-PIL at ONE thread: isolates the C++ decoder's gain from
        # thread scaling (which a 1-core bench host cannot show).  Skipped
        # when the user disabled the native decoder on entry — the serial
        # number above is already the PIL path then, and the comparison
        # would silently measure PIL vs PIL.
        import keystone_tpu.loaders.native_decode as nd

        prior = os.environ.get("KEYSTONE_NATIVE_DECODE")
        native_enabled = (prior or "").strip() != "0" and nd.available()
        pil_serial = None
        if native_enabled:
            os.environ["KEYSTONE_NATIVE_DECODE"] = "0"
            try:
                nd.reset()  # re-evaluate the env gate (takes the module lock)
                pil_serial = timed(1)
            finally:
                if prior is None:
                    del os.environ["KEYSTONE_NATIVE_DECODE"]
                else:
                    os.environ["KEYSTONE_NATIVE_DECODE"] = prior
                nd.reset()

        # -- process-pool decode at 1/2/4/8 workers (the GIL-free backend).
        # total = whole stream including the one-time worker spawn (each
        # spawned worker pays a fresh interpreter + package import);
        # steady = images/sec measured from the FIRST chunk's arrival, the
        # rate a long tar actually sustains.
        proc_total, proc_steady = {}, {}
        for w in (1, 2, 4, 8):
            # snapshot pinned OFF: an ambient KEYSTONE_SNAPSHOT_DIR would
            # turn the decode-scaling probe into a shard-read benchmark.
            cfg = StreamConfig.from_env(
                decode_threads=w, decode_ahead=8, ring_capacity=8,
                decode_backend="process", decode_procs=w,
                snapshot_dir="",
            )
            t0 = time.perf_counter()
            t_first = None
            n_done = first_n = 0
            with stream_batches(
                tar_path, 32, transfer=False, config=cfg
            ) as st:
                for b in st:
                    if t_first is None:
                        t_first = time.perf_counter()
                        first_n = len(b)
                    n_done += len(b)
            t_end = time.perf_counter()
            assert st.join(20.0), "decode worker processes leaked"
            assert n_done == n_images, (n_done, n_images)
            proc_total[str(w)] = round(n_images / (t_end - t0), 2)
            if t_first is not None and n_done > first_n and t_end > t_first:
                proc_steady[str(w)] = round(
                    (n_done - first_n) / (t_end - t_first), 2
                )

        # -- snapshot cache: cold write (live decode + shard tee) vs warm
        # read (shards only — the repeat-epoch rate) over the same tar,
        # measured for BOTH shard formats (KEYSTONE_SNAPSHOT_COMPRESS):
        # deflated shards cost cold-pass CPU but shrink the warm pass's IO.
        from keystone_tpu.core import snapshot as ksnap

        snap_variants = {}
        for compress in (True, False):
            snap_root = tempfile.mkdtemp(prefix="bench_snap_")
            prev_env = os.environ.get(ksnap.SNAPSHOT_COMPRESS_ENV)
            os.environ[ksnap.SNAPSHOT_COMPRESS_ENV] = "1" if compress else "0"
            try:
                t0 = time.perf_counter()
                with stream_batches(
                    tar_path, 32, transfer=False,
                    config=StreamConfig.from_env(
                        snapshot_dir=snap_root, snapshot_mode="decoded"
                    ),
                ) as st:
                    n_cold = sum(len(b) for b in st)
                cold_secs = time.perf_counter() - t0
                assert st.join(10.0) and n_cold == n_images
                t0 = time.perf_counter()
                with stream_batches(
                    tar_path, 32, transfer=False,
                    config=StreamConfig.from_env(
                        snapshot_dir=snap_root, snapshot_mode="decoded"
                    ),
                ) as st:
                    n_warm = sum(len(b) for b in st)
                warm_secs = time.perf_counter() - t0
                assert st.join(10.0) and n_warm == n_images
                assert st.stats.snapshot_chunks_read > 0, "warm pass re-decoded"
                [committed] = [
                    s for s in ksnap.list_snapshots(snap_root) if s["valid"]
                ]
                snap_variants["compressed" if compress else "uncompressed"] = {
                    "cold_write_images_per_sec": round(n_images / cold_secs, 2),
                    "warm_read_images_per_sec": round(n_images / warm_secs, 2),
                    "warm_speedup_vs_cold": round(cold_secs / warm_secs, 2),
                    "shard_bytes": committed["bytes"],
                    "cold_secs": cold_secs,
                    "warm_secs": warm_secs,
                }
            finally:
                if prev_env is None:
                    os.environ.pop(ksnap.SNAPSHOT_COMPRESS_ENV, None)
                else:
                    os.environ[ksnap.SNAPSHOT_COMPRESS_ENV] = prev_env
                shutil.rmtree(snap_root, ignore_errors=True)
        # BENCH_r0x row continuity: the top-level cold/warm keys stay, fed
        # by the DEFAULT (compressed) variant.
        cold_secs = snap_variants["compressed"].pop("cold_secs")
        warm_secs = snap_variants["compressed"].pop("warm_secs")
        snap_variants["uncompressed"].pop("cold_secs")
        snap_variants["uncompressed"].pop("warm_secs")
    finally:
        os.unlink(tar_path)
    out = {
        "decode_threads": threads,
        "serial_images_per_sec": round(serial, 2),
        "threaded_images_per_sec": round(threaded, 2),
        "speedup": round(threaded / serial, 2),
        "host_cores": _host_cores(),
        "process_pool_images_per_sec": proc_total,
        "process_pool_steady_images_per_sec": proc_steady,
    }
    best_proc = max((proc_steady or proc_total).values(), default=None)
    if best_proc is not None:
        out["process_best_speedup_vs_serial"] = round(best_proc / serial, 2)
        if out["process_best_speedup_vs_serial"] < 2.0:
            # The acceptance target (>=2x on >=4 workers) needs cores to
            # scale over; name the bounding resource instead of leaving a
            # bare shortfall.
            out["process_scaling_bound"] = (
                f"{_host_cores()} schedulable core(s) on this host bound "
                "process-pool scaling; the backend removes the GIL, not "
                "the core budget"
            )
    out["snapshot"] = {
        "cold_write_images_per_sec": round(n_images / cold_secs, 2),
        "warm_read_images_per_sec": round(n_images / warm_secs, 2),
        "warm_speedup_vs_cold": round(cold_secs / warm_secs, 2),
        "warm_speedup_vs_serial_decode": round(
            (n_images / warm_secs) / serial, 2
        ),
        # Write-path compression (KEYSTONE_SNAPSHOT_COMPRESS, default on):
        # per-format cold/warm rates + on-disk shard bytes, so the
        # CPU-vs-IO trade is measured, not assumed.
        "by_format": snap_variants,
        "compression_ratio": round(
            snap_variants["uncompressed"]["shard_bytes"]
            / max(snap_variants["compressed"]["shard_bytes"], 1),
            2,
        ),
        # The cost-model view of the same numbers: is materializing worth
        # it for a nominal 5-epoch fit at this tar's decoded footprint?
        "advice": advise_snapshot(
            images=n_images,
            bytes_per_image=256 * 256 * 3 * 4,
            decode_images_per_sec=threaded,
            epochs=5,
        ).record(),
    }
    if pil_serial is not None:
        out["pil_serial_images_per_sec"] = round(pil_serial, 2)
        out["native_vs_pil_speedup"] = round(serial / pil_serial, 2)
    else:
        out["native_vs_pil_speedup"] = None  # native decoder disabled/absent
    # ISSUE 13: per-path breakdown over the mixed device-decode corpus —
    # host pool vs device decode vs warm device-snapshot DMA, with
    # overlap efficiency and golden parity recorded per path.
    out["by_path"] = _decode_path_breakdown(rng)
    return out


def bench_serving(rng):
    """Low-latency serving SLOs (ISSUE 8): two fitted pipelines — the
    MnistRandomFFT chain and the RandomPatchCifar conv chain — checkpointed,
    warm-loaded through ``core.serve.load_engine`` (cold start measured:
    restore + per-bucket AOT compile + warmup), then driven by concurrent
    synthetic clients through the dynamic batcher.  Each record carries
    p50/p99 latency, sustained QPS, batcher occupancy, and the
    batched-vs-unbatched QPS ratio (same engine behind a flush-per-request
    server; target >= 2x at bit-equal answers)."""
    import shutil
    import tempfile

    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.checkpoint import save_pipeline
    from keystone_tpu.core.pipeline import Pipeline
    from keystone_tpu.ops.stats import StandardScaler
    from keystone_tpu.ops.util import (
        ClassLabelIndicatorsFromIntLabels,
        GroupConcatFeaturizer,
        MaxClassifier,
    )
    from keystone_tpu.workloads.cifar_random_patch import featurize_chunked
    from keystone_tpu.workloads.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer_batches,
    )

    cfg = kserve.ServeConfig(buckets=(1, 4, 16), max_wait_ms=2.0)
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    engines = {}

    def slo(pipe, example, requests, label):
        from keystone_tpu.core import numerics as kbnum

        stem = os.path.join(tmp, f"{label}_pipe")
        # Fit-time output baseline in the manifest (ISSUE 15): load_engine
        # arms the drift monitor from it, so the serving records carry a
        # real drift verdict (the benched mix IS the fit mix — divergence
        # ~0 is the healthy reading).
        save_pipeline(
            stem, pipe,
            numerics_baseline=kbnum.OutputSketch.for_outputs(
                np.asarray(pipe(jnp.asarray(requests)))
            ).record(),
        )
        engine, cold = kserve.load_engine(
            stem, example, config=cfg, label=label
        )
        engines[label] = engine
        rec = kserve.serve_bench(engine, requests, clients=4, depth=16)
        rec["cold_start"] = cold
        return rec

    out = {}
    try:
        # -- workload 1: the MnistRandomFFT servable chain --------------------
        d, k, n_req = 128, 10, 384
        conf = MnistRandomFFTConfig(
            num_ffts=4, block_size=1024, mnist_image_size=d, num_classes=k
        )
        x = rng.normal(size=(768, d)).astype(np.float32)
        y = rng.integers(0, k, 768)
        gfeat = GroupConcatFeaturizer(build_featurizer_batches(conf))
        feats = gfeat(jnp.asarray(x))
        labels = ClassLabelIndicatorsFromIntLabels(k)(jnp.asarray(y))
        model = BlockLeastSquaresEstimator(
            int(feats.shape[1]), 1, 1e-2
        ).fit(feats, labels)
        out["mnist_fft"] = slo(
            Pipeline([gfeat, model, MaxClassifier()]),
            jax.ShapeDtypeStruct((d,), np.float32),
            x[:n_req],
            "mnist_fft",
        )

        # -- workload 2: the RandomPatchCifar conv servable chain -------------
        # Light conv config: on a CPU bench host the conv is compute-bound
        # and batch-linear, so the batching win is the per-request dispatch
        # overhead — a heavyweight conv would bury it (on TPU hardware the
        # MXU's batch amortization does the burying in the other direction).
        cconf = RandomCifarConfig(
            num_filters=4, patch_size=6, patch_steps=8, pool_size=14,
            pool_stride=13, whitener_size=2000, featurize_chunk=128,
            num_classes=4,
        )
        imgs = rng.uniform(0, 255, (256, 32, 32, 3)).astype(np.float32)
        clabels = rng.integers(0, 4, 256)
        filters, whitener = learn_filters(cconf, imgs)
        conv_pipe = build_conv_pipeline(cconf, filters, whitener)
        conv_fn = jax.jit(conv_pipe.__call__)
        train_conv = featurize_chunked(conv_fn, imgs, cconf.featurize_chunk)
        scaler = StandardScaler().fit(train_conv)
        cmodel = BlockLeastSquaresEstimator(4096, 1, 10.0).fit(
            scaler(train_conv),
            ClassLabelIndicatorsFromIntLabels(4)(jnp.asarray(clabels)),
        )
        out["cifar_conv"] = slo(
            Pipeline([*conv_pipe.nodes, scaler, cmodel, MaxClassifier()]),
            jax.ShapeDtypeStruct((32, 32, 3), np.float32),
            imgs[:192],
            "cifar_conv",
        )

        # -- observability overhead probes ------------------------------------
        # ONE harness, three tiers: the SAME warm engine serves the same
        # request set with a tier off, then on — the p99 ratio IS that
        # tier's cost on a live endpoint.  Telemetry (ISSUE 11, < 2%),
        # profiler (ISSUE 14, <= 5%), numerics probes (ISSUE 15, <= 5%).
        import contextlib as _contextlib

        from keystone_tpu.core import numerics as kbnum
        from keystone_tpu.core import profiler as kbprof
        from keystone_tpu.core import telemetry as ktelemetry

        probe_engine = engines["mnist_fft"]
        probe_reqs = x[:256]

        def overhead_pass(reqs):
            return kserve.serve_bench(
                probe_engine, reqs, clients=4, depth=16,
                unbatched_baseline=False,
            )

        def overhead_probe(off_ctx=None, on_ctx=None, warm_on=False,
                           capture=None):
            """(off record, on record, captured extras): the off pass runs
            under ``off_ctx`` (the telemetry tier is on by DEFAULT, so its
            control arm is the suppressed one), the on pass under
            ``on_ctx`` — preceded, when ``warm_on``, by one small warmup
            pass so first-use setup (cost_analysis, jitted-reducer trace)
            never charges the steady-state bound."""
            with (off_ctx or _contextlib.nullcontext()):
                off = overhead_pass(probe_reqs)
            with (on_ctx or _contextlib.nullcontext()):
                if warm_on:
                    overhead_pass(probe_reqs[:64])
                on = overhead_pass(probe_reqs)
                captured = capture() if capture is not None else {}
            return off, on, captured

        def overhead_rows(off, on, frac_key, target):
            return {
                "requests": int(probe_reqs.shape[0]),
                "p99_off_ms": off["p99_latency_ms"],
                "p99_on_ms": on["p99_latency_ms"],
                "qps_off": off["qps"],
                "qps_on": on["qps"],
                frac_key: round(
                    on["p99_latency_ms"]
                    / max(off["p99_latency_ms"], 1e-9)
                    - 1.0,
                    4,
                ),
                "target_frac": target,
            }

        off, on, _ = overhead_probe(off_ctx=ktelemetry.telemetry_disabled())
        out["telemetry_overhead"] = overhead_rows(
            off, on, "p99_overhead_frac", 0.02
        )

        kbprof.reset_state()
        off, on, prof_ledger = overhead_probe(
            on_ctx=kbprof.profiled(True), warm_on=True,
            capture=lambda: {
                label: row
                for label, row in kbprof.ledger().items()
                if label.startswith("serve:")
            },
        )
        out["profiler_overhead"] = {
            **overhead_rows(off, on, "p99_overhead_frac", 0.05),
            "bit_identical_on": on["predictions_bit_identical"],
            # The per-bucket MFU rows the profiled pass produced — the
            # serve half of the bench "profiler" section's ledger.
            "ledger": prof_ledger,
        }

        kbnum.reset_state()
        off, on, num_sites = overhead_probe(
            on_ctx=kbnum.monitored(True), warm_on=True,
            capture=lambda: {
                site: row
                for site, row in kbnum.site_stats().items()
                if site.startswith("serve.")
            },
        )
        kbnum.reset_state()
        out["numerics_overhead"] = {
            **overhead_rows(off, on, "probe_overhead_frac", 0.05),
            # Probes must be bit-inert online too: the monitored pass's
            # answers stay bit-equal to the offline oracle.
            "bit_identical_on": on["predictions_bit_identical"],
            "output_drift": on.get("output_drift"),
            "sites": num_sites,
        }

        # -- the wire front-end (ISSUE 12) --------------------------------
        # The SAME two warm engines behind a ShapeRouter + WireServer,
        # driven over real localhost sockets by concurrent clients — the
        # headline serving.wire_p99_ms and the router's own route
        # overhead (serving.router_route_overhead_us) are what
        # tools/bench_diff.py regresses on across rounds.
        import sys as _sys
        import threading as _threading

        from keystone_tpu.core import frontend as kfrontend
        from keystone_tpu.core import trace as _ktrace
        from keystone_tpu.core import wire as kwire

        _tools = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"
        )
        if _tools not in _sys.path:
            _sys.path.insert(0, _tools)
        from serve_client import drive as wire_drive

        wire_reqs = {
            "mnist_fft": x[:128],
            "cifar_conv": imgs[:64].astype(np.float32),
        }
        router = kfrontend.ShapeRouter(label="bench_router")
        try:
            router.add_engine(engines["mnist_fft"])
            router.add_engine(engines["cifar_conv"])
            lat_all: list = []
            per_engine: dict = {}
            errors: list = []
            lock = _threading.Lock()
            with kwire.WireServer(router, port=0, label="bench") as ws:

                def wire_client(label, reqs):
                    try:
                        with kwire.WireClient(port=ws.port, timeout=60.0) as c:
                            rec = wire_drive(
                                c, list(reqs), window=8, timeout=120.0
                            )
                        with lock:
                            lats = rec.pop("latencies_ms")
                            lat_all.extend(lats)
                            per_engine.setdefault(label, []).extend(lats)
                    except BaseException as e:  # noqa: BLE001 — recorded
                        errors.append(f"{label}: {type(e).__name__}: {e}")

                ts = [
                    _threading.Thread(target=wire_client, args=(lbl, reqs))
                    for lbl, reqs in wire_reqs.items()
                    for _ in range(2)  # two concurrent clients per shape
                ]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(300.0)
                wall = time.perf_counter() - t0
                ws_record = ws.record()
            lat_all.sort()
            pick = lambda q: round(  # noqa: E731
                lat_all[min(len(lat_all) - 1, int(q * len(lat_all)))], 3
            ) if lat_all else 0.0
            overhead = _ktrace.metrics.snapshot()["histograms"].get(
                "router_route_overhead_us", {}
            )
            out["wire"] = {
                "requests": len(lat_all),
                "wall_seconds": round(wall, 3),
                "qps": round(len(lat_all) / wall, 2) if wall > 0 else 0.0,
                "per_shape": {
                    lbl: {
                        "requests": len(v),
                        "p50_ms": round(sorted(v)[len(v) // 2], 3),
                        "p99_ms": round(
                            sorted(v)[min(len(v) - 1, int(0.99 * len(v)))], 3
                        ),
                    }
                    for lbl, v in per_engine.items()
                    if v
                },
                "server": ws_record,
                "router": router.record(),
                "errors": errors,
            }
            out["wire_p50_ms"] = pick(0.50)
            out["wire_p99_ms"] = pick(0.99)
            out["router_route_overhead_us"] = round(
                float(overhead.get("p99", 0.0)), 3
            )
        finally:
            router.close()

        # -- elastic serving (ISSUE 16): ckpt -> foreign mesh -> serve ----
        # The mnist_fft artifact saved above is RELOADED onto an explicit
        # smaller mesh (load_pipeline(mesh=) resharding + mesh-native AOT),
        # served bit-equal against the warm engine's offline oracle, then a
        # MeshEngineFactory-backed router is shrunk mid-flight with requests
        # straddling the swap.  bench_diff regresses on
        # serving.reshard_wall_s and pins serving.reanchor_dropped_requests
        # at zero.
        from keystone_tpu.parallel.mesh import make_mesh, mesh_desc

        devs = jax.devices()
        if len(devs) < 2:
            out["reshard"] = {"skipped": "single-device host"}
        else:
            n_full = 4 if len(devs) >= 4 else 2
            full = make_mesh(data=n_full, model=1, devices=devs[:n_full])
            surviving = make_mesh(
                data=n_full // 2, model=1, devices=devs[: n_full // 2]
            )
            stem = os.path.join(tmp, "mnist_fft_pipe")
            reqs = x[:64]
            oracle = np.asarray(engines["mnist_fft"].offline(reqs))

            t0 = time.perf_counter()
            foreign, fcold = kserve.load_engine(
                stem, jax.ShapeDtypeStruct((d,), np.float32),
                config=cfg, label="mnist_fft_foreign", mesh=surviving,
            )
            answers = np.asarray(foreign.infer(reqs))
            reshard_wall = time.perf_counter() - t0

            # Live device-loss drill: requests in flight across the shrink;
            # every one must answer — dropped stays 0 across rounds.
            factory = kfrontend.MeshEngineFactory(
                lambda shape, dtype, m: kserve.load_engine(
                    stem, jax.ShapeDtypeStruct(shape, dtype),
                    config=cfg, label="mnist_fft_elastic", mesh=m,
                )[0],
                mesh=full,
            )
            drill_router = kfrontend.ShapeRouter(
                factory, label="bench_reanchor"
            )
            dropped, got = 0, []
            try:
                drill_router.add_engine(factory((d,), np.dtype(np.float32)))
                futs = [drill_router.submit(r) for r in reqs[:16]]
                rrec = drill_router.reanchor(
                    surviving, why="bench device-loss drill"
                )
                futs += [drill_router.submit(r) for r in reqs[16:32]]
                for f in futs:
                    try:
                        got.append(np.asarray(f.result(120.0)))
                    except Exception:  # noqa: BLE001 — counted as dropped
                        dropped += 1
            finally:
                drill_router.close()

            out["reshard"] = {
                "full_mesh": mesh_desc(full),
                "surviving_mesh": mesh_desc(surviving),
                "cold_start": fcold,
                "round_trip_bit_equal": bool(np.array_equal(answers, oracle)),
                "reanchor": rrec,
                "drill_requests": 32,
                "drill_bit_equal": bool(
                    len(got) == 32
                    and np.array_equal(np.stack(got), oracle[:32])
                ),
            }
            out["reshard_wall_s"] = round(reshard_wall, 4)
            out["reanchor_dropped_requests"] = dropped
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_profiler(rng):
    """Device cost attribution (ISSUE 14): a laddered BCD fit runs with
    the profiler ON — the per-program MFU ledger rows for the solve
    tiers, the hand-flops-hint-vs-compiled audit table, and the HBM
    watermark sampler's surface (on CPU hosts ``memory_stats`` is
    unavailable and the sampler retires itself; the record says so rather
    than inventing a watermark).  The headline ``solve_mfu`` is the fused
    solve's ledger MFU — the first number the BENCH_r06 hardware round
    reads from this section."""
    from keystone_tpu.core import autoshard
    from keystone_tpu.core import profiler as kprof
    from keystone_tpu.core.resilience import counters as _counters

    autoshard.hermetic_plan_log()
    kprof.reset_state()
    n, d, k = 8192, 1024, 32
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = one_hot_pm1(rng, n, k)
    with kprof.profiled(True, interval_ms=5.0):
        est = BlockLeastSquaresEstimator(d, 2, 1e-2)
        est.fit(x, y)
        sampler = kprof.sampler()
        sampler_rec = sampler.record() if sampler is not None else None
        ledger = kprof.ledger_record()
    solve_rows = {
        label: row
        for label, row in ledger["programs"].items()
        if label.startswith("bcd_fit")
    }
    solve_mfu = max(
        (row["mfu"] or 0.0 for row in solve_rows.values()), default=None
    )
    audits = ledger["flops_audits"]
    worst_audit = max(
        (
            max(a["ratio"], 1.0 / a["ratio"])
            for a in audits.values()
            if a.get("ratio")
        ),
        default=None,
    )
    # Drift rows this profiled fit appended to the (hermetic) plan log —
    # on hardware these are the calibration evidence; on CPU the column
    # records 0 honestly (no watermark, no drift row).  The once-per-
    # process log cache predates the appends, so drop it before reading.
    autoshard.clear_outcome_cache()
    drift = autoshard.drift_rows()
    return {
        "n": n, "d": d, "classes": k,
        "solve_mfu": solve_mfu,
        "ledger": ledger,
        "flops_audit_worst_factor": (
            round(worst_audit, 3) if worst_audit else None
        ),
        "flops_audits_ok": all(a.get("ok") for a in audits.values()),
        "hbm_sampler": sampler_rec,
        "plan_drift_rows": len(drift),
        "plan_drift_count": _counters.get("plan_drift"),
    }


def bench_multihost(rng):
    """Multi-host elastic serving (ISSUE 17): the REAL 2-process
    ``jax.distributed`` fit+serve (bit-identical to single-process on the
    same shards, crosshost checkpoint reshard timed) and the host-loss
    drill (SIGKILL one serving host mid-flight; survivors re-form,
    reshard, re-anchor; zero request loss).  bench_diff regresses on
    ``multihost.fit_serve_wall_s``, ``multihost.reshard_wall_s``, and
    ``multihost.host_loss.reanchor_wall_s``, and pins
    ``multihost.host_loss.dropped_requests`` at zero.  Where process
    spawn is unavailable the section records zero-base rows and says so
    — never a fake measurement."""
    import shutil
    import tempfile

    from keystone_tpu.parallel.distributed import spawn_available
    from keystone_tpu.workloads import multihost as mh

    if not spawn_available():
        return {
            "available": False,
            "fit_serve_wall_s": 0.0,
            "reshard_wall_s": 0.0,
            "host_loss": {"reanchor_wall_s": 0.0, "dropped_requests": 0},
        }
    tmp = tempfile.mkdtemp(prefix="bench_multihost_")
    try:
        fs = mh.run_two_process_fit_serve(
            tmp, shards_per_host=2, images_per_shard=6, seed=0
        )
        drill = mh.run_host_loss_drill(
            os.path.join(tmp, "drill"), hosts=2, requests=24, seed=0
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "available": True,
        "fit_serve_wall_s": round(float(fs["fit_serve_wall_s"]), 3),
        "reshard_wall_s": round(float(fs["reshard_wall_s"]), 4),
        "bit_identical": fs["bit_identical"],
        "crosshost_bit_equal": fs["crosshost_bit_equal"],
        "n_images": fs["n_images"],
        "leaked_threads": fs["leaked_threads"],
        "host_loss": {
            "mode": drill["mode"],
            "hosts": drill["hosts"],
            "reanchor_wall_s": round(
                float(drill.get("reanchor_wall_s") or 0.0), 4
            ),
            "dropped_requests": int(drill["dropped_requests"]),
            "mismatches": int(drill["mismatches"]),
            "answered": int(drill["answered"]),
            "postmortems": len(drill["postmortems"]),
        },
    }


def bench_lifecycle(rng):
    """Closed-loop model lifecycle (core.lifecycle, ISSUE 18): the
    drift→refit→validate→swap drill from tools/serve_bench.py — a
    shifted mix trips the armed incumbent's drift monitor, the
    controller warm-refits on fresh data, validates on a holdout, and
    hot-swaps the router's engine while a pump thread keeps requests in
    flight.  ``tools/bench_diff.py`` regresses on
    ``lifecycle.refit_wall_s`` / ``lifecycle.swap_wall_s`` /
    ``lifecycle.drift_to_healthy_wall_s`` (lower is better) and pins
    ``lifecycle.dropped_requests`` at zero — the hot-swap's zero-downtime
    claim, re-proven every round."""
    import shutil
    import sys as _sys
    import tempfile

    _tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    if _tools not in _sys.path:
        _sys.path.insert(0, _tools)
    from serve_bench import drift_refit_drill

    tmp = tempfile.mkdtemp(prefix="bench_lifecycle_")
    try:
        drill = drift_refit_drill(tmp, requests=24, seed=0)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # The full cycle record stays in the drill dict; keep the section's
    # top level to the dotted paths the observatory reads.
    return {
        "tripped": drill.get("tripped"),
        "outcome": (drill.get("cycle") or {}).get("outcome"),
        "drift_to_healthy_wall_s": drill.get("drift_to_healthy_wall_s"),
        "refit_wall_s": drill.get("refit_wall_s"),
        "validate_wall_s": drill.get("validate_wall_s"),
        "swap_wall_s": drill.get("swap_wall_s"),
        "in_flight_across_swap": drill.get("in_flight_across_swap"),
        "dropped_requests": drill.get("dropped_requests"),
        "post_swap_bit_equal": drill.get("post_swap_bit_equal"),
        "quality": (drill.get("cycle") or {}).get("quality"),
        "statusz": drill.get("lifecycle"),
        "ok": drill.get("ok", False),
    }


def bench_fleet_observability(rng):
    """Fleet observability plane (core.fleetobs, ISSUE 20): the
    cross-host metrics fabric measured four ways — (1) the pure
    window-merge wall over a synthetic 16-member fleet, (2) a live
    2-agent scrape wall over real sockets, (3) the collector's serving
    cost with the SAME off/on harness as the telemetry/profiler/numerics
    tiers (one warm wire endpoint, same request set, collector detached
    then attached at a hot interval; <= 5% p99, answers bit-equal), and
    (4) the 2-subprocess obs-capture drill (SIGKILL one member
    mid-scrape) whose incident-capture wall and acceptance verdicts ride
    along.  ``tools/bench_diff.py`` regresses on the walls and the
    overhead frac (lower is better) and pins
    ``fleet_observability.drill.dropped_requests`` at zero."""
    import shutil
    import tempfile

    from keystone_tpu.core import fleetobs
    from keystone_tpu.parallel.distributed import spawn_available
    from keystone_tpu.workloads import multihost as mh

    out: dict = {}

    # -- merge wall: pure window math, 16 members x 4 hists x 512 samples.
    member_wins = []
    for _m in range(16):
        win = {}
        for h in range(4):
            samples = np.abs(
                rng.normal(loc=5.0 + h, scale=1.0, size=512)
            ).astype(float).tolist()
            win[f"lat{h}_ms"] = {
                "count": len(samples), "total": float(sum(samples)),
                "min": float(min(samples)), "max": float(max(samples)),
                "samples": samples,
            }
        member_wins.append(win)
    t0 = time.perf_counter()
    merged = {
        name: fleetobs.merge_windows([m[name] for m in member_wins])
        for name in member_wins[0]
    }
    summaries = {k: fleetobs.window_summary(v) for k, v in merged.items()}
    out["merge_wall_s"] = round(time.perf_counter() - t0, 4)
    out["merge_members"] = len(member_wins)
    out["merge_samples"] = int(sum(s["count"] for s in summaries.values()))

    # -- scrape wall: two live in-process agents, one timed scrape (the
    # warm pass absorbs connect + clock sync, as in steady state).
    with fleetobs.ObsAgent(label="bench-a") as a_agent, \
            fleetobs.ObsAgent(label="bench-b") as b_agent:
        col = fleetobs.FleetCollector(
            [f"{a_agent.host}:{a_agent.port}",
             f"{b_agent.host}:{b_agent.port}"],
            interval_s=3600.0, label="bench_fleetobs",
        )
        with col:
            col.scrape_once()
            t0 = time.perf_counter()
            snap = col.scrape_once()
            out["scrape_wall_s"] = round(time.perf_counter() - t0, 4)
            out["scrape_members"] = snap.get("alive")

    # -- collector on/off serve p99: the same off/on discipline as the
    # telemetry/profiler/numerics tiers — ONE warm compute-bound engine
    # (real members spend their wall in GIL-releasing XLA work, so a
    # trivial engine would measure pure scheduler-convoy noise), the
    # SAME request set, the on arm scraped by an attached collector.
    # best-of-3 p99 per arm keeps a shared box's scheduler jitter out of
    # the ratio.
    from keystone_tpu.core import serve as kserve
    from keystone_tpu.core.pipeline import FunctionTransformer

    d = 1024
    w1 = jnp.asarray(rng.standard_normal((d, d)).astype(np.float32))
    probe_pipe = FunctionTransformer(
        lambda v: jnp.tanh(jnp.tanh(v @ w1) @ w1.T) @ w1, name="obsprobe"
    )
    probe_engine = kserve.ServingEngine(
        probe_pipe,
        np.zeros((d,), np.float32),
        config=kserve.ServeConfig.from_env(buckets=(1, 4, 16),
                                           max_wait_ms=2.0),
        label="bench_fleetobs_probe",
    )
    probe_reqs = rng.normal(size=(256, d)).astype(np.float32)

    def serve_pass():
        return kserve.serve_bench(
            probe_engine, probe_reqs, clients=4, depth=16,
            unbatched_baseline=False,
        )

    serve_pass()  # warm: compile every bucket
    p99_off = min(serve_pass()["p99_latency_ms"] for _ in range(3))
    agent = fleetobs.ObsAgent(label="bench_fleetobs_member")
    pcol = fleetobs.FleetCollector(
        [f"{agent.host}:{agent.port}"], interval_s=0.2,
        label="bench_fleetobs_on",
    )
    try:
        pcol.start()
        on_runs = [serve_pass() for _ in range(3)]
        pcol.stop()
        scrapes = pcol.scrapes
    finally:
        pcol.close()
        agent.close()
    p99_on = min(r["p99_latency_ms"] for r in on_runs)
    out["collector_overhead"] = {
        "requests": int(probe_reqs.shape[0]),
        "p99_off_ms": round(p99_off, 4),
        "p99_on_ms": round(p99_on, 4),
        "collector_overhead_frac": round(
            p99_on / max(p99_off, 1e-9) - 1.0, 4
        ),
        "target_frac": 0.05,
        "scrapes_during_on_pass": scrapes,
        # The scraped arm's answers stay bit-equal to the offline
        # oracle — the collector must never perturb served bytes.
        "bit_identical_on": bool(
            all(r["predictions_bit_identical"] for r in on_runs)
        ),
    }

    # -- the obs-capture drill: 2 REAL worker processes, SIGKILL one
    # mid-scrape; one clock-aligned incident bundle or the drill says why.
    if not spawn_available():
        out["drill"] = {"available": False, "dropped_requests": 0}
        out["incident_capture_wall_s"] = 0.0
        return out
    tmp = tempfile.mkdtemp(prefix="bench_fleetobs_")
    try:
        drill = mh.run_obs_capture_drill(
            tmp, hosts=2, requests=16, seed=0, subprocess_mode=True
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    inc = drill.get("incident") or {}
    out["drill"] = {
        "available": True,
        "mode": drill.get("mode"),
        "wall_s": round(float(drill.get("wall_s") or 0.0), 3),
        "scrape_wall_s": drill.get("scrape_wall_s"),
        "counter_sum_ok": drill.get("counter_sum_ok"),
        "p99_match": drill.get("p99_match"),
        "monotone_ok": drill.get("monotone_ok"),
        "obs_member_lost": drill.get("obs_member_lost"),
        "dropped_requests": int(drill.get("dropped_requests") or 0),
        "mismatches": int(drill.get("mismatches") or 0),
        "incident": {
            k: inc.get(k)
            for k in (
                "trigger", "capture_wall_s", "members", "missing",
                "n_events", "survivor_rings_ok", "events_monotone",
                "error",
            )
            if k in inc
        },
    }
    out["incident_capture_wall_s"] = float(inc.get("capture_wall_s") or 0.0)
    return out


def bench_numerics(rng, serving: dict | None = None):
    """Numerics observatory (ISSUE 15): a laddered BCD fit runs MONITORED
    — the per-block κ table lands in ``FitReport.conditioning`` (the
    ACCURACY.md §6 sweep live, with the predictive ``cond_warn`` armed) —
    and the serving probe-overhead measurement from ``bench_serving``
    (same warm engine, observatory off vs on, <= 5% p99 acceptance) is
    folded in as the section's headline rows: ``probe_overhead`` and the
    probed-serve p99 are what ``tools/bench_diff.py`` regresses on across
    rounds."""
    from keystone_tpu.core import numerics as knum
    from keystone_tpu.core.resilience import counters as _counters

    knum.reset_state()
    n, d, k = 4096, 1024, 16
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = one_hot_pm1(rng, n, k)
    with knum.monitored(True):
        est = BlockLeastSquaresEstimator(d // 2, 1, 1e-2)
        est.fit(x, y)
        cond = (
            list(est.last_fit_report.conditioning or [])
            if est.last_fit_report is not None
            else []
        )
    knum.reset_state()
    probe = (serving or {}).get("numerics_overhead")
    out = {
        "conditioning": cond,
        # kappa=None rows (non-finite gram / estimator failure) are a
        # documented shape — filter them or max() dies on float vs None.
        "kappa_max": max(
            (r["kappa"] for r in cond if r.get("kappa") is not None),
            default=None,
        ),
        "cond_warns": _counters.get("cond_warn"),
        # The serving-path probe overhead (measured in bench_serving on
        # the warm mnist_fft engine) — the bench_diff thresholds read
        # THESE two rows.
        "probe_overhead": probe,
        "probed_serve_p99_ms": (
            probe.get("p99_on_ms") if isinstance(probe, dict) else None
        ),
    }
    return out


def bench_self_diff(record: dict, dirpath: str | None = None) -> dict:
    """Regression observatory (ISSUE 11): compare THIS round's record
    against the newest USABLE prior ``BENCH_r*.json`` (a truncated newest
    round — r05's ``parsed: null`` — falls back to the round before it)
    via ``tools/bench_diff.py``'s thresholds, and embed the verdict in the
    round artifact so every hardware round self-reports regressions."""
    import sys

    root = os.path.dirname(os.path.abspath(__file__)) or "."
    tools_dir = os.path.join(root, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import bench_diff

    prev = bench_diff.latest_usable_round(dirpath or root)
    if prev is None:
        return {"note": "no usable prior BENCH round on record"}
    num, path, base = prev
    out = bench_diff.compare(base, record)
    out["baseline"] = os.path.basename(path)
    out["baseline_round"] = num
    # The full per-metric table stays in the tool; the embedded section
    # keeps the verdict + the rows that moved (artifact size discipline).
    out.pop("rows", None)
    return out


def _error_record(e: Exception) -> dict:
    return {"error": f"{type(e).__name__}: {e}"[:300]}


def _guarded(fn, rng):
    """Secondary benches must not kill the whole JSON artifact: a transient
    failure (noise-floor miss on a busy shared chip, OOM on a smaller
    device) degrades to an error record; the headline metric stays strict."""
    try:
        return fn(rng)
    except Exception as e:  # noqa: BLE001 - recorded, not swallowed
        return _error_record(e)


def main():
    from keystone_tpu.core import autoshard

    # Hermetic placement search: the bench asserts searched-vs-hand
    # bit-equality and ranking-dependent bars that a TRAINED operator log
    # (~/.keystone_plans.jsonl) could legitimately reorder, and its
    # synthetic shapes must not pollute the log that calibrates real
    # workload fits.  Each bench process gets a throwaway log (the
    # placement/at-scale sections also pin one for direct invocations).
    autoshard.hermetic_plan_log()
    device = init_device()
    if device["platform"] != "tpu":
        # The headline is a device metric: a CPU number under its name is
        # worse than no number.  (Sections stay importable off-TPU — the
        # tests call them one by one.)
        raise SystemExit(
            f"bench.py: the device is {device}, not a TPU — no record"
        )
    rng = np.random.default_rng(0)
    n_chips = device["count"]
    rates = DEVICE_RATES.get(device["kind"])
    peak = rates and rates["peak_flops"]
    bw = rates and rates["hbm_gbps"] * 1e9

    cifar = bench_cifar_featurize(rng)
    fv = _guarded(bench_imagenet_fv_featurize, rng)
    stages = _guarded(bench_stage_ops, rng)
    decode = _guarded(bench_decode, rng)
    e2e = _guarded(bench_e2e_ingest, rng)
    optimizer = _guarded(bench_optimizer, rng)
    serving = _guarded(bench_serving, rng)
    placement = _guarded(bench_placement, rng)
    profiler_sec = _guarded(bench_profiler, rng)
    numerics_sec = _guarded(lambda r: bench_numerics(r, serving), rng)
    multihost_sec = _guarded(bench_multihost, rng)
    lifecycle_sec = _guarded(bench_lifecycle, rng)
    fleetobs_sec = _guarded(bench_fleet_observability, rng)
    at_scale = _guarded(bench_solve_at_scale, rng)

    # ONE atomic registry snapshot feeds both the back-compat "faults" key
    # and the full "metrics" section — two separate snapshot calls could
    # disagree about a fault recorded between them.
    metrics_snapshot = ktrace.metrics.snapshot()

    value = round(cifar["images_per_sec"] / n_chips, 2)
    prior = prior_bench_value("random_patch_cifar_featurize")
    mfu = (
        round(cifar["flops_per_sec"] / (peak * n_chips), 4)
        if cifar["flops_per_sec"] and peak
        else None
    )
    fv_mfu = (
        round(fv["flops_per_sec"] / (peak * n_chips), 4)
        if fv.get("flops_per_sec") and peak
        else None
    )
    record = {
        "metric": "random_patch_cifar_featurize",
        "value": value,
        "unit": "images/sec/chip",
        "device": device,
        "vs_baseline": round(value / prior, 4) if prior else 1.0,
        "mfu": mfu,
        "flops_per_sec": cifar["flops_per_sec"],
        "flops_per_image": cifar["flops_per_image"],
        "bytes_per_image": cifar["bytes_per_image"],
        "roofline": roofline(
            cifar["flops"], cifar["bytes_accessed"],
            cifar["per_iter"],
            peak * n_chips if peak else None,
            bw * n_chips if bw else None,
        ),
        "peak_flops_per_chip": peak,
        "solve_seconds": round(cifar["solve_seconds"], 4),
        "solve_examples_per_sec": round(
            cifar["solve_examples_per_sec"], 2
        ),
        "solve_device_seconds": round(cifar["solve_device_seconds"], 6),
        # Degradation ledger for this whole bench process: IO retries,
        # corrupt-member skips, jitter recoveries, OOM step-downs,
        # skew-guard fallbacks... — so BENCH_r06+ rows show the faults the
        # numbers were earned under, not just the perf (empty dict = clean).
        # Kept as its own key for BENCH_r0x row continuity, sourced from
        # the same atomic snapshot as "metrics" below.
        "faults": metrics_snapshot["faults"],
        # The unified metrics registry (core.trace): counters/gauges/
        # histograms accumulated anywhere in the process, faults group
        # included — every bench record carries the full metrics surface.
        "metrics": metrics_snapshot,
        "extra_metrics": {
            "imagenet_fv_featurize": (
                fv
                if "error" in fv
                else {
                    "value": round(fv["images_per_sec"] / n_chips, 2),
                    "unit": "images/sec/chip",
                    "mfu": fv_mfu,
                    "flops_per_sec": fv["flops_per_sec"],
                    "roofline": roofline(
                        fv["flops"], fv["bytes_accessed"],
                        fv["per_iter"],
                        peak * n_chips if peak else None,
                        bw * n_chips if bw else None,
                    ),
                }
            ),
            "stage_ops": stages,
            "solve_at_scale": at_scale,
            "jpeg_decode": decode,
            # Streaming-ingest e2e: tar -> decode -> featurize(-> solve)
            # with decode/featurize overlap (core.ingest); includes the
            # per-stream ring depth/stall counters and the overlap
            # efficiency vs its 0.9 target.
            "e2e": e2e,
            # Pipeline optimizer (core.optimize): auto-Cacher cached-vs-
            # uncached fit wall + decision table, and the closed-loop
            # ingest autotuner's knob trajectory + overlap efficiency on a
            # stall-injected stream.
            "optimizer": optimizer,
            # Low-latency serving (core.serve): per-workload online SLOs —
            # cold start (restore/compile/warmup), p50/p99 latency,
            # sustained QPS, batcher occupancy, batched-vs-unbatched QPS
            # (>= 2x target at bit-equal answers).
            "serving": serving,
            # Placement search (core.autoshard): searched-vs-hand-ladder
            # fit wall on >= 3 BCD shapes (bit-identical models required),
            # the search's enumerate+prune+score overhead as a fraction of
            # fit wall (< 5% bar), and the chosen plan's
            # predicted-vs-measured cost ratio.
            "placement": placement,
            # Device cost attribution (core.profiler, ISSUE 14): the
            # per-program MFU ledger of a profiled BCD fit, the
            # flops-hint audit table, the HBM sampler surface, and the
            # plan-drift row count — the section BENCH_r06 reads for the
            # first hardware MFU/drift numbers.
            "profiler": profiler_sec,
            # Numerics observatory (core.numerics, ISSUE 15): a monitored
            # BCD fit's per-block κ table (the live ACCURACY.md §6 sweep)
            # plus the serving probe-overhead rows (<= 5% p99 acceptance)
            # bench_diff regresses on.
            "numerics": numerics_sec,
            # Multi-host elastic serving (parallel.distributed +
            # workloads.multihost, ISSUE 17): real 2-process fit+serve
            # bit-identity + crosshost reshard wall, and the host-loss
            # drill's re-anchor wall with dropped_requests pinned at 0.
            # Zero-base rows (available: false) where spawn is off.
            "multihost": multihost_sec,
            # Closed-loop model lifecycle (core.lifecycle, ISSUE 18): the
            # drift→refit→validate→swap drill's walls (refit/swap/
            # drift-to-healthy, all lower-is-better across rounds) with
            # dropped_requests pinned at 0 — the zero-downtime hot-swap
            # claim, re-proven every round.
            "lifecycle": lifecycle_sec,
            # Fleet observability plane (core.fleetobs, ISSUE 20): the
            # window-merge and live-scrape walls, the collector's
            # off/on serving p99 (<= 5% bar, answers bit-equal), and the
            # 2-subprocess obs-capture drill's incident-capture wall
            # with dropped_requests pinned at 0.
            "fleet_observability": fleetobs_sec,
        },
    }
    # Regression observatory (ISSUE 11): this round judged against the
    # newest usable prior round's record, verdict embedded in the artifact.
    record["bench_diff"] = _guarded(lambda _rng: bench_self_diff(record), rng)
    # Artifact-truncation guard (VERDICT r5 "Driver artifacts"): the driver
    # keeps a bounded TAIL of stdout, and round 5's record — one JSON line
    # emitted last, after all bench log noise — got cut mid-record
    # (`parsed: null`, headline number lost).  Emit the machine record
    # FIRST, flushed, and keep everything after it (the human-readable
    # summary below) tiny, so any tail window that reaches the end of the
    # output contains the complete JSON line.
    print(json.dumps(record), flush=True)
    ex = record["extra_metrics"]
    print(
        f"# {record['metric']}: {value} images/sec/chip "
        f"(vs_baseline {record['vs_baseline']}, mfu {mfu})"
    )
    fvx = ex["imagenet_fv_featurize"]
    print(
        "# imagenet_fv_featurize: "
        + (fvx.get("error", "") if "error" in fvx else f"{fvx['value']} images/sec/chip")
    )
    sas = ex["solve_at_scale"]
    if "error" in sas:
        print(f"# solve_at_scale: {sas['error'][:120]}")
    else:
        print(
            f"# solve_at_scale: n={sas['n']} d={sas['d']} "
            f"({sas['design_matrix_gb']} GB) in {sas['wall_seconds']} s, "
            f"{len(sas.get('oom_attempts', []))} OOM attempt(s)"
        )
    jd = ex["jpeg_decode"]
    if "error" not in jd:
        print(
            f"# jpeg_decode: serial {jd['serial_images_per_sec']}/s, "
            f"threaded {jd['threaded_images_per_sec']}/s "
            f"(x{jd['speedup']})"
        )
        pp = (
            jd.get("process_pool_steady_images_per_sec")
            or jd.get("process_pool_images_per_sec")
        )
        if pp:
            print(
                f"# jpeg_decode process pool (steady): {pp} "
                f"(best x{jd.get('process_best_speedup_vs_serial')} vs serial)"
            )
        sn = jd.get("snapshot")
        if sn:
            print(
                f"# jpeg_decode snapshot: cold "
                f"{sn['cold_write_images_per_sec']}/s -> warm "
                f"{sn['warm_read_images_per_sec']}/s "
                f"(x{sn['warm_speedup_vs_serial_decode']} vs serial decode)"
            )
        bp = jd.get("by_path")
        if bp:
            dev = bp["device"]
            print(
                "# jpeg_decode by_path e2e: host_pool "
                f"{bp['host_pool']['images_per_sec']}/s, device "
                f"{dev['images_per_sec']}/s (overlap "
                f"{dev['overlap_efficiency']}, parity "
                f"{dev['golden_max_abs_vs_host']}), warm device-snapshot "
                f"{bp['device_snapshot_warm']['images_per_sec']}/s "
                "(zero_host_decode="
                f"{bp['device_snapshot_warm']['zero_host_decode']})"
            )
    e2x = ex["e2e"]
    if "error" in e2x:
        print(f"# e2e: {e2x['error'][:120]}")
    else:
        for wk in ("cifar", "imagenet_fv"):
            r = e2x[wk]
            print(
                f"# e2e {wk}: decode {r['decode_images_per_sec']}/s, "
                f"featurize {r['featurize_images_per_sec']}/s, "
                f"e2e {r['e2e_images_per_sec']}/s "
                f"(overlap {r['overlap_efficiency']}); snapshot-warm e2e "
                f"{r.get('snapshot_e2e_images_per_sec')}/s "
                f"({r.get('snapshot_e2e_vs_featurize')} of featurize)"
            )
    opt = ex["optimizer"]
    if "error" in opt:
        print(f"# optimizer: {opt['error'][:120]}")
    else:
        ac, at = opt["auto_cache"], opt["autotune"]
        print(
            f"# optimizer auto_cache: {ac['uncached_fit_wall_seconds']}s -> "
            f"{ac['cached_fit_wall_seconds']}s (x{ac['speedup']}, "
            f"bit_identical {ac['predictions_bit_identical']})"
        )
        print(
            f"# optimizer autotune: {at['static_images_per_sec']}/s -> "
            f"{at['tuned_images_per_sec']}/s (x{at['speedup']}, "
            f"{at['tuner']['retunes']} retune(s), overlap "
            f"{at['static_overlap_efficiency']} -> "
            f"{at['tuned_overlap_efficiency']})"
        )
    srv = ex["serving"]
    if "error" in srv:
        print(f"# serving: {srv['error'][:120]}")
    else:
        for wk, r in srv.items():
            if not isinstance(r, dict):
                continue  # scalar headline metrics (wire_p99_ms, ...)
            if wk == "telemetry_overhead":
                print(
                    f"# serving telemetry overhead: p99 {r['p99_off_ms']}ms "
                    f"off -> {r['p99_on_ms']}ms on "
                    f"({r['p99_overhead_frac']:+.2%}, target < "
                    f"{r['target_frac']:.0%})"
                )
                continue
            if wk == "profiler_overhead":
                print(
                    f"# serving profiler overhead: p99 {r['p99_off_ms']}ms "
                    f"off -> {r['p99_on_ms']}ms on "
                    f"({r['p99_overhead_frac']:+.2%}, target <= "
                    f"{r['target_frac']:.0%}, bit_identical "
                    f"{r['bit_identical_on']})"
                )
                continue
            if wk == "numerics_overhead":
                print(
                    f"# serving numerics overhead: p99 {r['p99_off_ms']}ms "
                    f"off -> {r['p99_on_ms']}ms probed "
                    f"({r['probe_overhead_frac']:+.2%}, target <= "
                    f"{r['target_frac']:.0%}, bit_identical "
                    f"{r['bit_identical_on']})"
                )
                continue
            if wk == "wire":
                rt = r["router"]["stats"]
                print(
                    f"# serving wire: {r['requests']} requests over real "
                    f"sockets, p50 {srv.get('wire_p50_ms')}ms / p99 "
                    f"{srv.get('wire_p99_ms')}ms, {r['qps']} QPS, route "
                    f"overhead p99 "
                    f"{srv.get('router_route_overhead_us')}us, "
                    f"{rt['routes']} routed / {rt['retires']} retire(s)"
                    + (f", ERRORS {r['errors']}" if r["errors"] else "")
                )
                continue
            burn = r.get("slo", {}).get("window", {}).get("burn_rate")
            print(
                f"# serving {wk}: p50 {r['p50_latency_ms']}ms / p99 "
                f"{r['p99_latency_ms']}ms, {r['qps']} QPS "
                f"(x{r.get('batched_vs_unbatched_qps')} vs unbatched), "
                f"occupancy {r['batcher']['mean_occupancy']}, burn_rate "
                f"{burn}, cold start "
                f"{r['cold_start']['cold_start_seconds']}s, bit_identical "
                f"{r['predictions_bit_identical']}"
            )
    numx = ex["numerics"]
    if "error" in numx:
        print(f"# numerics: {numx['error'][:120]}")
    else:
        po = numx.get("probe_overhead") or {}
        kmax = numx.get("kappa_max")
        print(
            f"# numerics: kappa_max "
            f"{f'{kmax:.3g}' if kmax is not None else 'n/a'} over "
            f"{len(numx['conditioning'])} block(s) "
            f"({numx['cond_warns']} cond_warn), probed-serve p99 "
            f"{numx.get('probed_serve_p99_ms')}ms "
            f"({po.get('probe_overhead_frac', 0.0):+.2%} vs unprobed)"
        )
    prof = ex["profiler"]
    if "error" in prof:
        print(f"# profiler: {prof['error'][:120]}")
    else:
        smp = prof.get("hbm_sampler") or {}
        print(
            f"# profiler: solve_mfu {prof['solve_mfu']}, flops audit worst "
            f"x{prof['flops_audit_worst_factor']} "
            f"(ok={prof['flops_audits_ok']}), drift rows "
            f"{prof['plan_drift_rows']}, sampler "
            + (
                "unavailable (no device memory_stats)"
                if smp.get("unavailable")
                else f"{smp.get('samples', 0)} sample(s)"
            )
        )
    mhx = ex["multihost"]
    if "error" in mhx:
        print(f"# multihost: {mhx['error'][:120]}")
    elif not mhx.get("available"):
        print("# multihost: process spawn unavailable — zero-base rows")
    else:
        hl = mhx["host_loss"]
        print(
            f"# multihost: 2-process fit+serve "
            f"{mhx['fit_serve_wall_s']}s (bit_identical "
            f"{mhx['bit_identical']}, crosshost reshard "
            f"{mhx['reshard_wall_s']}s), host-loss drill ({hl['mode']}) "
            f"reanchor {hl['reanchor_wall_s']}s, "
            f"{hl['dropped_requests']} dropped / {hl['mismatches']} "
            f"mismatched of {hl['answered']}"
        )
    lcx = ex["lifecycle"]
    if "error" in lcx:
        print(f"# lifecycle: {lcx['error'][:120]}")
    else:
        print(
            f"# lifecycle: tripped on {lcx['tripped']}, {lcx['outcome']} in "
            f"{lcx['drift_to_healthy_wall_s']}s (refit "
            f"{lcx['refit_wall_s']}s, swap {lcx['swap_wall_s']}s), "
            f"{lcx['in_flight_across_swap']} in flight across the swap, "
            f"{lcx['dropped_requests']} dropped, bit-equal "
            f"{lcx['post_swap_bit_equal']}"
        )
    fox = ex["fleet_observability"]
    if "error" in fox:
        print(f"# fleet_observability: {fox['error'][:120]}")
    else:
        co = fox["collector_overhead"]
        print(
            f"# fleet_observability: scrape {fox['scrape_wall_s']}s "
            f"({fox['scrape_members']} member(s)), merge "
            f"{fox['merge_wall_s']}s ({fox['merge_samples']} samples), "
            f"collector p99 {co['p99_off_ms']}ms off -> "
            f"{co['p99_on_ms']}ms on "
            f"({co['collector_overhead_frac']:+.2%}, target <= "
            f"{co['target_frac']:.0%}, bit_identical "
            f"{co['bit_identical_on']})"
        )
        fdr = fox["drill"]
        if not fdr.get("available"):
            print("# fleet_observability drill: spawn unavailable — "
                  "zero-base rows")
        else:
            print(
                f"# fleet_observability drill ({fdr['mode']}): incident "
                f"capture {fox['incident_capture_wall_s']}s, counter_sum "
                f"{fdr['counter_sum_ok']}, p99_match {fdr['p99_match']}, "
                f"monotone {fdr['monotone_ok']}, "
                f"{fdr['dropped_requests']} dropped / "
                f"{fdr['mismatches']} mismatched"
            )
    bd = record["bench_diff"]
    if "verdict" in bd:
        print(
            f"# bench_diff vs {bd.get('baseline')}: {bd['verdict']} "
            f"({bd.get('compared')} compared, "
            f"{len(bd.get('regressions', []))} regression(s))"
        )
    else:
        print(f"# bench_diff: {bd.get('note') or bd.get('error')}")
    print(f"# faults: {record['faults'] if record['faults'] else 'none'}")


if __name__ == "__main__":
    main()
