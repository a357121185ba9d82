"""Shared machinery for the Fisher-vector workloads (VOCSIFTFisher,
ImageNetSiftLcsFV — reference pipelines/images/voc/VOCSIFTFisher.scala and
pipelines/images/imagenet/ImageNetSiftLcsFV.scala).

The reference maps per-image JNI featurizers over RDDs of arbitrarily-sized
images.  XLA wants static shapes, so images are grouped into same-shape
buckets, each bucket is featurized by one jitted program, and the resulting
fixed-dimension feature rows are scattered back to original order.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import memory as kmem
from ..core import trace
from ..ops.fisher import FisherVector
from ..ops.images import GrayScaler, PixelScaler
from ..ops.sift import DESC_DIM
from ..ops.stats import NormalizeRows, SignedHellingerMapper
from ..ops.util import MatrixVectorizer
from ..parallel.mesh import padded_shard_rows
from ..solvers.gmm import GaussianMixtureModel


def bucket_by_shape(images: list) -> dict:
    """Group per-image arrays by (H, W): shape -> (orig_indices, [n,H,W,C])."""
    groups: dict = {}
    for i, img in enumerate(images):
        groups.setdefault(img.shape[:2], []).append(i)
    return {
        shape: (np.asarray(idx), np.stack([images[i] for i in idx]))
        for shape, idx in groups.items()
    }


def shard_batch(batch, mesh):
    """Row-shard one bucket's [n, H, W, C] batch over the mesh's data axis
    (zero-padding n up to an axis multiple), or plain device_put without a
    mesh.  Pad rows ride through the per-image featurizers as garbage rows
    and are dropped at scatter time (``scatter_features`` slices to the
    bucket's true image count; ``featurize_chunks`` to a chunk's real
    images) and at sampling time (only valid images are drawn from) — the
    bucket featurize program itself is purely data-parallel, so no masking
    is needed in between."""
    dev, _n = padded_shard_rows(np.asarray(batch), mesh)
    return dev


def grayscale(batch) -> jnp.ndarray:
    """PixelScaler then GrayScaler -> [n, H, W] in [0, 1]."""
    return GrayScaler()(PixelScaler()(jnp.asarray(batch)))[..., 0]


def draw_columns(totals: dict, num_samples: int, seed: int = 42) -> dict:
    """The ColumnSampler's draw, before any descriptor exists: ``totals`` is
    ``{shape: (images, descriptors an image)}`` in bucket order; returns
    ``{shape: sorted indices into the bucket's images * descriptors
    columns}``.  Each bucket gets its proportional quota, drawn uniformly
    without replacement (the reference samples per image,
    Sampling.scala:12-22); a set no larger than ``num_samples`` is taken
    whole."""
    with trace.host("draw", "columns", samples=num_samples):
        rng = np.random.default_rng(seed)
        grand_total = sum(n * c for n, c in totals.values())
        draws = {}
        for shape, (n, c) in totals.items():
            total = n * c
            if grand_total <= num_samples:
                draws[shape] = np.arange(total)
            else:
                quota = min(total, max(1, int(num_samples * total / grand_total)))
                draws[shape] = np.sort(rng.choice(total, quota, replace=False))
    return draws


def sample_columns(desc_buckets: dict, num_samples: int, seed: int = 42) -> jnp.ndarray:
    """ColumnSampler analog over per-bucket [n, d, cols] descriptor arrays:
    uniform sample of descriptor columns -> [d, <= num_samples].

    Only the drawn columns (:func:`draw_columns`) are materialized — never
    the full descriptor set."""
    # valid image count is len(idx) — descriptor arrays may carry sharding
    # pad rows past it (see shard_batch) which must never be sampled
    draws = draw_columns(
        {
            shape: (len(idx), descs.shape[2])
            for shape, (idx, descs) in desc_buckets.items()
        },
        num_samples, seed,
    )
    picks = []
    for shape, (_idx, descs) in desc_buckets.items():
        # gather the quota columns directly — no transposed full copy
        im, col = np.divmod(draws[shape], descs.shape[2])
        picks.append(descs[jnp.asarray(im), :, jnp.asarray(col)].T)  # [d, quota]
    return jnp.concatenate(picks, axis=1)


def fisher_feature_pipeline(gmm: GaussianMixtureModel):
    """FisherVector -> vectorize (col-major) -> L2 norm -> signed sqrt ->
    L2 norm (reference constructFisherFeaturizer / VOCSIFTFisher.scala:73-80).
    Returns a callable [n, d, cols]-descriptors -> [n, 2·d·K] features."""
    fv = FisherVector(gmm)
    vec = MatrixVectorizer()
    norm = NormalizeRows()
    hell = SignedHellingerMapper()

    def featurize(descs):
        return norm(hell(norm(vec(fv(descs)))))

    return featurize


def scatter_features(buckets: dict, transform, n_total: int, feature_dim: int) -> np.ndarray:
    """Apply ``transform`` ([n, d, cols] descriptors -> [n, D] features) per
    bucket and scatter rows back to original image order."""
    out = np.zeros((n_total, feature_dim), np.float32)
    for _shape, (idx, descs) in buckets.items():
        # slice off sharding pad rows (see shard_batch): only the bucket's
        # true images scatter back
        out[np.asarray(idx)] = np.asarray(transform(descs))[: len(idx)]
    return out


def plan_pca_materialization(
    desc_buckets: dict, batch_pca, reuse: int, *, mesh=None,
    label: str = "pca_descriptors",
):
    """Auto-Cacher decision for the PCA-projected descriptor buckets
    (core.optimize): the FV workloads consume them up to twice — GMM
    sampling, then Fisher featurization — and today always hold the whole
    projected set resident between the two.  Profile the projection on the
    smallest bucket, scale seconds/bytes to the full set, and run the
    caching inequality through the HBM admission gate.  Returns
    ``(CachePlan, materialize)``: ``materialize=False`` means each consumer
    projects on the fly (bit-identical — the projection is deterministic)
    instead of pinning the set through the GMM EM fit."""
    from ..core import optimize

    shape, (_idx, probe) = min(
        desc_buckets.items(), key=lambda kv: kv[1][1].size
    )
    # Warm the projection's compile before timing: a cold first call would
    # fold one-off JIT time into probe_secs and then SCALE it by the
    # dataset ratio, overpricing recompute and biasing every decision
    # toward materialize.
    jax.block_until_ready(batch_pca(probe))
    t0 = time.perf_counter()
    out = jax.block_until_ready(batch_pca(probe))
    probe_secs = time.perf_counter() - t0
    probe_cols = int(probe.shape[0]) * int(probe.shape[2])
    total_cols = sum(
        int(d.shape[0]) * int(d.shape[2]) for _, d in desc_buckets.values()
    )
    scale = total_cols / max(1, probe_cols)
    plan = optimize.plan_caches(
        [
            optimize.CacheCandidate(
                index=0,
                name=label,
                seconds=probe_secs * scale,
                output_bytes=int(out.nbytes * scale),
                reuse=reuse,
            )
        ],
        mesh=mesh,
    )
    return plan, plan.decisions[0].cached


# -- the chunked two-pass fit ---------------------------------------------------
#
# At the reference's own sizes the descriptors of a training set do not fit
# a chip (VOC 2007: 73,866 descriptors x 128 x 4 B an image, 189 GB for 5,011
# images), so a fit never holds them.  A *sampling pass* runs the descriptor
# nodes chunk by chunk and keeps only the columns drawn for the PCA and GMM
# samples; a *featurizing pass* runs descriptors -> PCA -> Fisher vector ->
# normalize chunk by chunk and keeps only each chunk's feature rows.  A
# descriptor node is one program a shape bucket, run by both passes; each pass
# adds a small program of its own on the chunk's descriptors.  A bucket's
# last chunk is padded to the chunk.  A pipeline with several descriptor
# branches (ImageNetSiftLcsFV: SIFT and LCS) feeds them all from one trip of
# the chunk's bytes to the device.

#: most images one chunk program takes
MAX_CHUNK = 64
#: the share of the admission budget (``core.memory.hbm_budget``) that a
#: chunk's reckoned descriptors, projections and posteriors may take.  The
#: reckoning errs high (compiled for the v5e at 64 images of 375x500: the
#: Fisher-vector half asks 6.4 GB of scratch beside 0.6 GB of descriptor
#: bytes, SIFT 1.9 GB, against 8.8 GB reckoned), and the features and samples
#: a fit holds beside it take under 2 GB of a 16 GB chip.
CHUNK_BUDGET_SHARE = 0.6
#: chunks the host may dispatch ahead of the device.  A chunk's descriptors
#: (0.6 GB of bytes at 64 images of 375x500) are a program's output, held from
#: dispatch until the pass's own half has read them; unbounded, the host ran
#: ~12 chunks ahead and the allocator's peak read 10.5 GB of a 16 GB chip
#: (my chip run, PR 28).  Two keep the device's queue full.
RUN_AHEAD = 2
#: a chunk's gather of drawn columns is padded to a multiple of this.  The most
#: any chunk draws moves with the draw (26,100-26,900 of a chunk's 4.7 M
#: columns at VOC's sizes), and every new value is a new gather program to
#: compile; a step of many standard deviations (~160 there) keeps one shape.
SAMPLE_CAP_STEP = 4096


@functools.partial(jax.jit, static_argnames=("image_shape",))
def _describe_chunk(sift, flat, *, image_shape):
    """SIFT of one chunk: the one program a shape that both passes run (the
    SIFT programs are the large ones: ~38 MB compiled at VOC's sizes, against
    a compile cache of 192 MiB on the benchmark's machine).  A quantized
    descriptor entry is a whole number to 255, so the chunk's descriptors
    pass to the next program as bytes, exactly, at a quarter of float32."""
    images = flat.reshape((flat.shape[0],) + image_shape)
    return sift(grayscale(images)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("image_shape",))
def _describe_lcs_chunk(lcs, flat, *, image_shape):
    """Local colour statistics of one chunk, on the images' own channels and
    0..255 levels as the reference takes them (ImageNetSiftLcsFV.scala:96-99:
    no pixel scaler before LCS).  Means and deviations are no whole numbers,
    so they pass on as float32."""
    images = flat.reshape((flat.shape[0],) + image_shape)
    return lcs(images.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class DescriptorBranch:
    """A descriptor node as the chunk programs take it: its name (the suffix
    of its counters), the node (an argument of ``describe``), the entries of
    one descriptor, the compiled chunk program ``(node, [chunk, H*W*C] bytes,
    image_shape=) -> [chunk, dim, descriptors]`` in the type its descriptors
    cross to the next program as, and ``(H, W) -> descriptors an image``."""

    name: str
    node: object
    dim: int
    describe: object
    cols: object


def sift_branch(sift) -> DescriptorBranch:
    """Dense SIFT of the grayscale image: 128 entries, handed on as bytes."""
    return DescriptorBranch("sift", sift, DESC_DIM, _describe_chunk, sift.num_descriptors)


def lcs_branch(lcs, channels: int = 3) -> DescriptorBranch:
    """Local colour statistics of the image's ``channels``: a mean and a
    deviation at 4 x 4 places a channel (96 entries for three), float32."""
    return DescriptorBranch(
        "lcs", lcs, 2 * 16 * channels, _describe_lcs_chunk, lcs.num_keypoints
    )


def _branch_list(nodes) -> tuple:
    """``nodes`` as a list of branches, and whether a list was given: a node
    given alone (a branch, or a bare SIFT extractor as VOCSIFTFisher's callers
    pass it) takes and gives its draws, samples and fitted nodes unwrapped."""
    if isinstance(nodes, (list, tuple)):
        return list(nodes), True
    one = nodes if isinstance(nodes, DescriptorBranch) else sift_branch(nodes)
    return [one], False


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """How a split's images go through the chunk programs: per shape bucket
    (in first-occurrence order) the images' ordinals, the descriptors an
    image of each branch, the bytes an image reckoned against the budget, and
    the chunk."""

    index: dict  # shape -> np.ndarray of image ordinals
    cols: dict  # shape -> descriptors an image, one entry a branch
    image_bytes: dict  # shape -> reckoned float32 bytes an image, all branches
    chunk: dict  # shape -> images a chunk
    budget: int | None

    def totals_of(self, branch: int) -> dict:
        """``{shape: (images, descriptors an image)}`` of one branch, for
        :func:`draw_columns`."""
        return {s: (len(idx), self.cols[s][branch]) for s, idx in self.index.items()}

    @property
    def totals(self) -> dict:
        """:meth:`totals_of` the first branch (the only one of a plan made
        for one node)."""
        return self.totals_of(0)

    @property
    def order(self) -> np.ndarray:
        """Image ordinals in the order the chunk programs emit their rows."""
        if not self.index:
            return np.zeros(0, np.int64)
        return np.concatenate(list(self.index.values()))


def plan_chunks(images: list, nodes, desc_dim: int, vocab_size: int, mesh=None) -> ChunkPlan:
    """Bucket ``images`` by shape and choose each bucket's chunk from what
    can be seen: an image's descriptors (each branch's own dimension), their
    projection and their posteriors in float32, summed over the branches,
    against ``CHUNK_BUDGET_SHARE`` of the admission budget.  The chunk is the
    largest power of two that fits (a budget read
    live moves a little between fits; a power of two does not move with
    it), at most ``MAX_CHUNK`` and at most the bucket; with no budget to read
    (the CPU) it is ``MAX_CHUNK``.  Under a mesh the rule gives each chip of
    the data axis what it gives one chip, reckoned against the fullest
    chip's free bytes: the chunk is that many images a chip, at most a
    chip's share of the bucket, times the axis.  Records one ``fv_plan``
    instant."""
    from ..parallel.mesh import DATA_AXIS

    branches, _ = _branch_list(nodes)
    groups: dict = {}
    for i, img in enumerate(images):
        groups.setdefault(tuple(img.shape[:2]), []).append(i)
    shards = 1 if mesh is None else mesh.shape[DATA_AXIS]
    budget = kmem.hbm_budget() if mesh is None else kmem.min_chip_budget(mesh)[0]
    index, cols, image_bytes, chunk, branch_bytes = {}, {}, {}, {}, {}
    for shape, idx in groups.items():
        c = tuple(b.cols(*shape) for b in branches)
        each = [4 * n * (b.dim + desc_dim + vocab_size) for n, b in zip(c, branches)]
        per_image = sum(each)
        fit = MAX_CHUNK
        if budget is not None:
            fit = max(1, int(CHUNK_BUDGET_SHARE * budget) // per_image)
            fit = min(MAX_CHUNK, 1 << (fit.bit_length() - 1))
        fit = min(fit, -(-len(idx) // shards)) * shards
        index[shape] = np.asarray(idx)
        cols[shape], image_bytes[shape], chunk[shape] = c, per_image, fit
        branch_bytes[shape] = each
    plan = ChunkPlan(index, cols, image_bytes, chunk, budget)
    trace.instant(
        "fv_plan",
        chunk={f"{h}x{w}": c for (h, w), c in chunk.items()},
        buckets={f"{h}x{w}": len(i) for (h, w), i in index.items()},
        chunk_bytes={f"{h}x{w}": chunk[(h, w)] * b for (h, w), b in image_bytes.items()},
        branch_chunk_bytes={
            b.name: {f"{h}x{w}": chunk[(h, w)] * each[k] for (h, w), each in branch_bytes.items()}
            for k, b in enumerate(branches)
        },
        budget=budget,
        shards=shards,
    )
    return plan


def _chunks(plan: ChunkPlan, images: list, mesh):
    """``(shape, images in the chunk that are real, device block, image
    shape)`` for every chunk of every bucket.  A block crosses as a matrix ``[chunk, H*W*C]`` in
    the images' own dtype (a matrix copies at several times the rate of an
    image batch, whose batch axis the device keeps innermost) and is given
    its shape back inside the program; a short last chunk is padded by
    repeating its last image, and the pad rows are dropped by the caller.
    Every branch of a pass reads the one block."""
    for shape, idx in plan.index.items():
        c = plan.chunk[shape]
        for start in range(0, len(idx), c):
            sel = idx[start : start + c]
            with trace.host("stack", "chunk"):
                block = np.stack([images[i] for i in sel])
                if len(sel) < c:
                    block = np.pad(
                        block, ((0, c - len(sel)), (0, 0), (0, 0), (0, 0)), mode="edge"
                    )
                flat = block.reshape(c, -1)
            with trace.h2d("chunk", flat.nbytes):
                dev = shard_batch(flat, mesh)
            trace.metrics.inc(f"fv.chunks.{shape[0]}x{shape[1]}")
            yield shape, len(sel), dev, block.shape[1:]


@jax.jit
def _sample_chunk(descs, im, col):
    """The sampling pass's half of a chunk: the drawn columns only.  ``im``,
    ``col``: a sample set's positions inside the chunk (each set padded with
    zeros to its own cap) -> a set's ``[cap, dim]`` rows."""
    return [descs[i, :, c].astype(jnp.float32) for i, c in zip(im, col)]


@jax.jit
def _encode_chunk(chains, descs):
    """The featurizing pass's half of a chunk: each branch's ``(pca, gmm)``
    on its descriptors, PCA -> Fisher features, the branches' rows side by
    side.  The fitted nodes are arguments, so every fit runs the one
    program."""
    rows = [
        fisher_feature_pipeline(gmm)(pca(d.astype(jnp.float32)))
        for (pca, gmm), d in zip(chains, descs)
    ]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)


@jax.jit
def _gather_samples(parts, positions):
    """The chunks' padded gathers -> each set's ``[samples, dim]`` rows."""
    return [
        jnp.concatenate([p[s] for p in parts], axis=0)[pos]
        for s, pos in enumerate(positions)
    ]


def sample_descriptor_columns(
    plan: ChunkPlan, images: list, nodes, draws: list, mesh=None
) -> list:
    """The sampling pass.  ``draws``: one :func:`draw_columns` result a
    sample set (with a list of branches: such a list a branch).  Returns each
    set's ``[samples, dim]`` descriptor rows, in bucket order and sorted
    inside a bucket."""
    branches, listed = _branch_list(nodes)
    draws = list(draws) if listed else [draws]
    if not any(draws):
        return [[] for _ in branches] if listed else []
    cap = {}  # (branch, shape) -> per set, the most columns a chunk gathers
    cuts = {}  # (branch, shape) -> per set, the draw's boundaries at chunk starts
    with trace.host("draw", "cuts"):
        for b, sets in enumerate(draws):
            for shape, idx in plan.index.items():
                c, cols = plan.chunk[shape], plan.cols[shape][b]
                edges = np.arange(0, len(idx) + c, c) * cols
                cuts[b, shape] = [np.searchsorted(d[shape], edges) for d in sets]
                cap[b, shape] = [
                    max(SAMPLE_CAP_STEP, -(-int(np.max(np.diff(cut), initial=0)) // SAMPLE_CAP_STEP) * SAMPLE_CAP_STEP)
                    for cut in cuts[b, shape]
                ]
    parts = [[] for _ in branches]
    positions = [[[] for _ in sets] for sets in draws]
    offset = [[0] * len(sets) for sets in draws]
    seen: dict = {}
    for shape, _valid, dev, image_shape in _chunks(plan, images, mesh):
        k = seen[shape] = seen.get(shape, -1) + 1
        bucket = f"{shape[0]}x{shape[1]}"
        for b, (branch, sets) in enumerate(zip(branches, draws)):
            if not sets:
                continue
            with trace.host("draw", "chunk", bucket=bucket, branch=branch.name):
                cols = plan.cols[shape][b]
                first = k * plan.chunk[shape] * cols
                im, col = [], []
                for s, d in enumerate(sets):
                    lo, hi = cuts[b, shape][s][k : k + 2]
                    at = np.zeros((2, cap[b, shape][s]), np.int32)
                    at[0, : hi - lo], at[1, : hi - lo] = np.divmod(d[shape][lo:hi] - first, cols)
                    im.append(at[0])
                    col.append(at[1])
                    positions[b][s].append(offset[b][s] + np.arange(hi - lo))
                    offset[b][s] += cap[b, shape][s]
            with trace.host("dispatch", "chunk", bucket=bucket, branch=branch.name):
                descs = branch.describe(branch.node, dev, image_shape=image_shape)
                parts[b].append(_sample_chunk(descs, im, col))
        last = [p[-1 - RUN_AHEAD] for p in parts if len(p) > RUN_AHEAD]
        if last:
            trace.wait(last, "chunk")
    out = []
    for b, branch in enumerate(branches):
        if not draws[b]:
            out.append([])
            continue
        with trace.host("concat", "samples", chunks=len(parts[b]), branch=branch.name):
            at = [np.concatenate(p).astype(np.int32) for p in positions[b]]
            drawn = int(sum(len(p) for p in at))
            trace.metrics.inc("fv.descriptors_sampled", drawn)
            trace.metrics.inc(f"fv.descriptors_sampled.{branch.name}", drawn)
            out.append(_gather_samples(parts[b], at))
        parts[b] = None  # a branch's padded gathers go as its samples are made
    return out if listed else out[0]


def featurize_chunks(plan: ChunkPlan, images: list, nodes, pca, gmm, mesh=None):
    """The featurizing pass: ``[n, 2 * desc_dim * vocab]`` Fisher features a
    branch, side by side, on the device, rows in ``plan.order`` (bucket by
    bucket), not image order: the caller permutes what is small (labels,
    scores), never this.  With a list of branches ``pca`` and ``gmm`` are
    lists too, one fitted node a branch."""
    branches, listed = _branch_list(nodes)
    chains = tuple(zip(pca, gmm)) if listed else ((pca, gmm),)
    outs = []
    for shape, valid, dev, image_shape in _chunks(plan, images, mesh):
        with trace.host("dispatch", "chunk", bucket=f"{shape[0]}x{shape[1]}"):
            descs = tuple(b.describe(b.node, dev, image_shape=image_shape) for b in branches)
            feats = _encode_chunk(chains, descs)
            outs.append(feats if valid == feats.shape[0] else feats[:valid])
        if len(outs) > RUN_AHEAD:
            trace.wait(outs[-1 - RUN_AHEAD], "chunk")
    with trace.host("concat", "chunks", chunks=len(outs)):
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


# -- streaming ingest (core.ingest) -------------------------------------------


def stream_config_from_flags(
    *, autotune: bool = False, decode_backend: str | None = None,
    snapshot_dir: str | None = None, snapshot_extra: str | None = None,
    supports_featurized: bool = False, device_decode: bool | None = None,
):
    """One ``StreamConfig`` builder for every streaming workload: env-seeded
    (``KEYSTONE_*``), with the workload's ``--autoTune`` / ``--decodeBackend``
    / ``--snapshotDir`` / ``--deviceDecode`` flags overriding the env
    defaults.  ``snapshot_extra`` keys the stream's member-selection inputs
    (keep filters, label files) into the snapshot content hash.
    ``device_decode=True`` selects ``decode_mode="device"`` (pixels born
    on-device, ops.jpeg_device; env ``KEYSTONE_DEVICE_DECODE``).

    ``supports_featurized``: set by callers that wrap the stream in
    :func:`stream_features_snapshot`.  Everywhere else a
    ``KEYSTONE_SNAPSHOT_MODE=featurized`` request degrades to DECODED
    caching — counted (``snapshot_mode_unsupported``), never a silently
    inert cache dir."""
    from ..core.ingest import StreamConfig
    from ..core.resilience import counters

    cfg = StreamConfig.from_env(
        autotune=True if autotune else None,
        decode_backend=decode_backend,
        snapshot_dir=snapshot_dir,
        snapshot_extra=snapshot_extra,
        decode_mode="device" if device_decode else None,
    )
    if (
        cfg.snapshot_dir
        and cfg.snapshot_mode == "featurized"
        and not supports_featurized
    ):
        counters.record(
            "snapshot_mode_unsupported",
            "featurized snapshots are not implemented on this stream — "
            "caching decoded chunks instead",
        )
        cfg.snapshot_mode = "decoded"
    return cfg


def stream_features_snapshot(
    make_stream, per_batch, *, root=None, key=None, tar_path=None, meta=None
):
    """Featurized-snapshot wrapper around a streaming featurize pass.

    ``per_batch``: ``StreamBatch -> np.ndarray [b, D]`` feature rows.
    With ``root``/``key`` set and a committed FEATURIZED snapshot present,
    the features stream straight from the shards — no tar read, no decode,
    no device featurize (``key`` must fold in the fitted featurizer's
    digest, ``core.snapshot.featurizer_digest``, so refits never replay
    stale features).  Otherwise the live pass runs (decode of chunk *i+1*
    overlapping featurize of chunk *i*) and its per-batch features are teed
    into a fresh snapshot, committed only on clean completion.  A corrupt
    shard mid-read is a counted ``snapshot_fallback`` to the live pass.

    Returns ``(features [n, D] f32, names, stream_or_None)`` — the stream
    is None when the snapshot served the pass (nothing streamed, so there
    is no autotune record)."""
    from ..core import snapshot as ksnap
    from ..core.resilience import counters

    if root is not None and key is not None:
        # tar_path (when given) powers the staleness classification: a
        # committed FEATURIZED snapshot for the same tar under another key
        # means the featurizer or input moved — counted, not silent.
        snap, reason = ksnap.lookup(
            root, key, tar_path=tar_path, mode="featurized"
        )
        if reason == "stale":
            counters.record(
                "snapshot_stale",
                f"{root}: featurized snapshot keyed differently "
                "(featurizer or input moved) — recomputing",
            )
        if snap is not None:
            parts, name_pairs, n = [], [], 0
            try:
                for _entry, arrays in snap.iter_chunks():
                    idx = np.asarray(arrays["indices"], np.int64)
                    parts.append((idx, np.asarray(arrays["payload"], np.float32)))
                    name_pairs.extend(
                        zip(idx.tolist(), [str(x) for x in arrays["names"]])
                    )
                    n += len(idx)
                feats, names = _scatter_parts(parts, name_pairs, n)
                return feats, names, None
            except ksnap.SnapshotCorrupt as e:
                counters.record(
                    "snapshot_fallback",
                    f"{snap.path}: {e} — recomputing features live",
                )

    writer = None
    if root is not None and key is not None:
        meta = dict(meta or {})
        if tar_path is not None:
            # The manifest's tar identity is what classifies a later
            # different-key lookup as STALE rather than a plain miss.
            meta.setdefault("tar", ksnap.tar_identity(tar_path))
        try:
            writer = ksnap.SnapshotWriter(
                root, key, mode="featurized", meta=meta
            )
        except (OSError, ksnap.SnapshotError) as e:
            # An unusable snapshot root never kills the featurize pass —
            # same counted-degrade contract as a failed shard write.
            counters.record(
                "snapshot_write_failed",
                f"cannot open featurized snapshot writer: {e}",
            )
    parts, name_pairs, n = [], [], 0
    try:
        with make_stream() as st:
            for batch in st:
                feats = np.asarray(per_batch(batch), np.float32)[: len(batch)]
                parts.append((batch.indices, feats))
                name_pairs.extend(zip(batch.indices.tolist(), batch.names))
                n += len(batch)
                if writer is not None:
                    try:
                        writer.add_chunk(
                            batch.index, batch.indices, batch.names, feats
                        )
                    except (OSError, ksnap.SnapshotError) as e:
                        # Same contract as the ingest tee: the cache is an
                        # optimization — a full disk drops the WRITER,
                        # counted, never the featurize pass.
                        counters.record("snapshot_write_failed", str(e))
                        writer.abort()
                        writer = None
        if writer is not None:
            try:
                writer.commit()
            except (OSError, ksnap.SnapshotError) as e:
                counters.record(
                    "snapshot_write_failed", f"commit failed: {e}"
                )
    finally:
        if writer is not None:
            writer.abort()  # no-op after commit; drops partials on error
    feats, names = _scatter_parts(parts, name_pairs, n)
    return feats, names, st


def record_stream_autotune(src, stream) -> None:
    """Append a finished stream's autotuner record to its source (one
    record per streaming pass — ImageNet streams a source once per
    descriptor branch).  No-op without a tuner."""
    if stream.tuner is not None:
        records = getattr(src, "last_autotune", None) or []
        records.append(stream.tuner.record())
        src.last_autotune = records


def collect_autotune(train, test) -> dict:
    """The ``results["autotune"]`` section: per-split knob-trajectory
    record lists accumulated by :func:`record_stream_autotune` (empty dict
    when nothing streamed with a tuner)."""
    return {
        split: getattr(src, "last_autotune", None)
        for split, src in (("train", train), ("test", test))
        if getattr(src, "last_autotune", None)
    }


def _ordered_names(pairs: list, n: int) -> list:
    names = [None] * n
    for i, name in pairs:
        names[i] = name
    return names


def _scatter_parts(
    parts: list, name_pairs: list, n: int, feature_dim: int | None = None
) -> tuple[np.ndarray, list]:
    """Scatter accumulated ``(indices, [b, D] features)`` parts back to
    stream-ordinal (decode-survival) order — the one copy of the
    scatter-to-ordinal contract every streaming feature pass shares
    (``feats[: len(idx)]`` drops sharding pad rows, see shard_batch).
    ``feature_dim`` is inferred from the first part when omitted."""
    if feature_dim is None:
        feature_dim = parts[0][1].shape[1] if parts else 0
    out = np.zeros((n, feature_dim), np.float32)
    for idx, feats in parts:
        out[np.asarray(idx)] = feats[: len(idx)]
    return out, _ordered_names(name_pairs, n)


def stream_descriptor_buckets(stream, per_batch) -> tuple[dict, list]:
    """Build the ``bucket_by_shape``-shaped descriptor dict by consuming a
    ``core.ingest`` stream: ``per_batch`` ([b, H, W, C] device batch ->
    per-image descriptor array) runs on chunk *i* while chunk *i+1* decodes
    on the host and transfers (the decode/featurize overlap the eager path
    lacks — it decoded the whole tar before the first device batch).

    Per-batch results stay on device (async dispatch — no sync until a
    downstream consumer pulls), and are concatenated per shape at
    end-of-stream, so ``{shape: (idx, descs)}`` is element-identical to the
    eager ``bucket_by_shape`` + per-bucket featurize.  Returns the buckets
    plus member names in stream-ordinal order (the loaders' filename
    order)."""
    parts: dict = {}
    name_pairs: list = []
    n = 0
    for batch in stream:
        # batch.apply fuses the device decode into the featurize program
        # for coefficient chunks (decode_mode="device"); for pixel chunks
        # it is exactly per_batch(batch.dev())
        descs = batch.apply(per_batch)
        parts.setdefault(batch.shape, []).append((batch.indices, descs))
        name_pairs.extend(zip(batch.indices.tolist(), batch.names))
        n += len(batch)
    buckets = {}
    # Insertion order = each shape's FIRST image ordinal, matching eager
    # bucket_by_shape's first-occurrence order exactly: downstream seeded
    # column sampling (sample_columns) iterates the dict sequentially from
    # one rng, so a chunk-emission order (first FULL batch first) would
    # silently pick different PCA/GMM samples than the eager path.
    for shape, chunks in sorted(
        parts.items(), key=lambda kv: kv[1][0][0][0]
    ):
        idx = np.concatenate([c[0] for c in chunks])
        descs = (
            chunks[0][1]
            if len(chunks) == 1
            else jnp.concatenate([c[1] for c in chunks], axis=0)
        )
        buckets[shape] = (idx, descs)
    return buckets, _ordered_names(name_pairs, n)


def scatter_features_streaming(stream, transform, feature_dim: int) -> tuple[np.ndarray, list]:
    """Streaming variant of :func:`scatter_features`: consume shape-bucketed
    device batches from ``core.ingest``, apply ``transform`` ([b, H, W, C]
    device batch -> [b, D] features) per batch, and scatter rows back to
    stream-ordinal (decode-survival) order.

    The host sync (``np.asarray``) lands only on the CONSUMED batch —
    decode threads keep filling the ring and the next batch's H2D is
    already in flight while this batch's features are pulled.  Returns
    ``(features [n, D] f32, names)``."""
    parts: list = []
    name_pairs: list = []
    n = 0
    for batch in stream:
        # fused decode+featurize for coefficient chunks (device decode),
        # plain transform(batch.dev()) for pixel chunks
        feats = batch.apply(transform)
        # sync on the consumed batch only; later batches decode/transfer on
        parts.append((batch.indices, np.asarray(feats, np.float32)))
        name_pairs.extend(zip(batch.indices.tolist(), batch.names))
        n += len(batch)
    return _scatter_parts(parts, name_pairs, n, feature_dim)
