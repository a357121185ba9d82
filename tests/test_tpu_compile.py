"""The main path's Pallas kernels, compiled at the benchmark's widths for a
v5e that is described and not attached: what Mosaic and the TPU compiler
refuse (a slice off the tiling, too much VMEM, a program over the chip's
memory) fails here, at no chip time.  Nothing runs, so nothing here says a
result is right or fast: tests/test_conv_fused.py and chip_smoke.py do.

Every TPU compile of the suite lives in this one file, and the topology is
described inside a fixture: one process at a time may load libtpu, and only
the worker that runs this file does.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from keystone_tpu.ops.conv_fused import FusedConvFeaturizer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    """The four described chips as ``--mesh 4`` builds them: data 4 x model 1."""
    from keystone_tpu.parallel.mesh import make_mesh

    return make_mesh(data=4, model=1, devices=topo.devices)


def test_conv_kernel_form_compiles_at_benchmark_widths(one_chip):
    """2,048 images x 1,250 filters (`cifar_rp_10k_share8`): the program
    holds the kernel, no array of the activations' shape, and a fraction of
    the XLA form's 3.8 GB of temporaries."""
    rng = np.random.default_rng(0)
    node_ = FusedConvFeaturizer(
        rng.normal(size=(1250, 6, 6, 3)).astype(np.float32),
        whitener_means=rng.normal(size=(108,)).astype(np.float32),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    chunk = jax.ShapeDtypeStruct((2048, 32, 32, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(node_._kernel_form).lower(chunk).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "27,27,1250]" not in text and "27,27,1280]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_fv_kernel_form_compiles_at_published_widths(one_chip):
    """64 images x 73,866 descriptors x 80 values against 256 centres
    (`voc_sift_fv_256`, a chunk of 375x500 images): the program holds the
    kernel, no array of the posteriors' shape (4.8 GB in the XLA form), and
    next to no temporaries; the ragged last block (73,866 is 36 blocks and
    138 columns) needs no padded copy."""
    from keystone_tpu.ops.fisher import FisherVector
    from keystone_tpu.solvers.gmm import GaussianMixtureModel

    rng = np.random.default_rng(0)
    d, k = 80, 256
    node_ = FisherVector(
        GaussianMixtureModel(
            rng.normal(size=(d, k)).astype(np.float32),
            rng.uniform(0.5, 2.0, (d, k)).astype(np.float32),
            rng.dirichlet(np.ones(k)).astype(np.float32),
        )
    )
    chunk = jax.ShapeDtypeStruct((64, d, 73866), jnp.float32, sharding=one_chip)
    compiled = jax.jit(node_._kernel_form).lower(chunk).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "73866,256]" not in text and "256,73866]" not in text
    assert "74112" not in text and "75776" not in text  # no padded descriptors
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


#: the chunk program before the assembly had a kernel form (commit 44be420,
#: compiled here for the same described chip): temporaries and the bytes of
#: XLA's cost analysis a chunk of 64 images, by image shape
SIFT_PARENT = {(375, 500): (1906e6, 33.5e9), (333, 500): (1647e6, 28.5e9)}


def _compiled_sift_chunk(one_chip, monkeypatch, shape):
    """`fv_common._describe_chunk` at 64 images of ``shape`` in the kernel
    form, compiled for the described chip, and the frames an image.  The form
    is steered here, in the test: `sift_form` asks `jax.default_backend()`,
    which is the CPU's."""
    from keystone_tpu.ops import sift
    from keystone_tpu.workloads import fv_common

    monkeypatch.setattr(sift, "sift_form", lambda *a: "kernel")
    h, w = shape
    node_ = sift.SIFTExtractor(scale_step=0, compute_dtype=jnp.bfloat16)
    flat = jax.ShapeDtypeStruct((64, h * w * 3), jnp.uint8, sharding=one_chip)
    fv_common._describe_chunk.clear_cache()
    try:
        compiled = fv_common._describe_chunk.lower(
            node_, flat, image_shape=(h, w, 3)
        ).compile()
    finally:
        fv_common._describe_chunk.clear_cache()
    return compiled, node_.num_descriptors(h, w)


@pytest.mark.parametrize("shape", sorted(SIFT_PARENT), ids=lambda s: f"{s[0]}x{s[1]}")
def test_sift_kernel_form_compiles_at_published_widths(one_chip, monkeypatch, shape):
    """`fv_common._describe_chunk` at 64 images of VOC's shapes
    (`voc_sift_fv_256`) in the kernel form: one `sift_assemble` call a scale
    writing the chunk's bytes in place, no staged `[64, D, 128]` array wider
    than a byte (1.21 GB in bfloat16 before), under the parent's temporaries
    and bytes."""
    import re

    compiled, frames = _compiled_sift_chunk(one_chip, monkeypatch, shape)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    staged = re.findall(rf"(\w+)\[(?:64,{frames},128|64,128,{frames}|{frames},64,128)\]", text)
    assert staged and set(staged) == {"u8"}, set(staged)
    assert not re.search(rf"dynamic-update-slice\S* = \w+\[64,{frames},128\]", text)
    temp, moved = SIFT_PARENT[shape]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * temp
    assert compiled.cost_analysis()["bytes accessed"] < 0.5 * moved


def test_sift_chunk_smooths_by_banded_products(one_chip, monkeypatch):
    """`_describe_chunk` at 64 x 375x500 since the Gaussian smoothing is two
    banded products a scale (PR 35): every `convolution` of the compiled
    program is a product (a window of one; eight of them the smoothing's,
    contracting 375 rows or 500 columns), none has one feature in and out,
    and no plane is padded beyond the image (the eight edge pads made
    `[64, 375 + 2r, 500]` and `[64, 375, 500 + 2r]`).  While the smoothing was
    eight one-channel convolutions (commit abbf73a, compiled here) the
    program moved 14.78 GB a chunk by XLA's reckoning and held 651 MB of
    temporaries and 28.4 MB of code."""
    import re

    h, w = 375, 500
    compiled, _frames = _compiled_sift_chunk(one_chip, monkeypatch, (h, w))
    text = compiled.as_text()
    convs = re.findall(
        r"= (\w+)\[([\d,]+)\]\S* convolution\(.*?window=\{size=([\dx]+)[ }].*?dim_labels=\w+_\w+->(\w+)",
        text,
    )
    assert len(convs) == 16, convs  # two products a scale each: smoothing, binning
    for _dtype, dims, window, out_labels in convs:
        assert set(window.split("x")) == {"1"}, (dims, window)
        assert int(dims.split(",")[out_labels.index("f")]) > 1, (dims, out_labels)
    smoothing = [dims for _d, dims, _w, _o in convs if dims in (f"64,{h},{w}", f"64,{w},{h}")]
    assert len(smoothing) == 8, convs
    padded = [
        (int(a), int(b))
        for a, b in re.findall(r"= \w+\[64,(\d+),(\d+)\]\S* pad\(", text)
        if (int(a), int(b)) != (h, w)
    ]
    assert not padded, padded
    mem = compiled.memory_analysis()
    moved = compiled.cost_analysis()["bytes accessed"]
    assert moved <= 14.78e9
    assert mem.temp_size_in_bytes <= 651.4e6
    print("sift chunk: code", mem.generated_code_size_in_bytes, "temp", mem.temp_size_in_bytes, "bytes", moved)



def _collectives(text: str) -> list:
    """``(operation, result type)`` of every collective of a compiled program."""
    import re

    return [
        (m.group(2), m.group(1))
        for m in re.finditer(
            r"= (.+?) (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
            r"(?:-start)?\(", text,
        )
    ]


def test_mesh_featurizer_compiles_at_published_widths(data_mesh):
    """2,048 images x 10,000 filters over four chips (`cifar_rp_10k_mesh4`):
    the kernel form under ``shard_map`` holds the kernel, no array of the
    activations' shape, exchanges nothing, and leaves a chip its 512 rows of
    the chunk's features."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.ops.conv_fused import _input_mesh
    from keystone_tpu.parallel.mesh import row_sharding

    rng = np.random.default_rng(0)
    node_ = FusedConvFeaturizer(
        rng.normal(size=(10000, 6, 6, 3)).astype(np.float32),
        whitener_means=rng.normal(size=(108,)).astype(np.float32),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    everywhere = NamedSharding(data_mesh, P())
    node_s = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=everywhere), node_
    )
    chunk = jax.ShapeDtypeStruct(
        (2048, 32, 32, 3), jnp.float32, sharding=row_sharding(data_mesh)
    )
    compiled = jax.jit(
        lambda nd, b: nd._sharded_kernel_form(b, _input_mesh(b))
    ).lower(node_s, chunk).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "27,27,10000]" not in text and "27,27,10240]" not in text
    assert not _collectives(text)
    assert "f32[512,80000]" in text and "f32[2048,80000]" not in text
    assert compiled.output_shardings.spec == row_sharding(data_mesh).spec
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_mesh_solve_compiles_at_published_widths(data_mesh):
    """25,000 x 81,920 over four chips, twenty blocks of 4,096: a chip holds
    its 6,250 rows and no operand of all 25,000, the grams cross chips as
    all-reduces of ``f32[4096,4096]``, and arguments, results and temporaries
    together stay under 14 GB a chip."""
    from keystone_tpu.parallel.mesh import row_sharding
    from keystone_tpu.solvers import block

    sds = jax.ShapeDtypeStruct
    row = row_sharding(data_mesh)
    widths = tuple([4096] * 19 + [80000 - 19 * 4096])
    compiled = block._fused_bcd_fit.lower(
        sds((25000, 81920), jnp.float32, sharding=row),
        sds((25000, 10), jnp.float32, sharding=row),
        sds((), jnp.float32), sds((), jnp.int32), 1, widths, data_mesh,
    ).compile()
    text = compiled.as_text()
    reduced = [kind for op, kind in _collectives(text) if op == "all-reduce"]
    assert any("f32[4096,4096]" in kind for kind in reduced), reduced
    assert "[25000," not in text and "f32[6250,81920]" in text
    mem = compiled.memory_analysis()
    per_chip = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert 2.0e9 < per_chip < 14e9, per_chip


def test_local_concatenation_moves_nothing_between_chips(data_mesh):
    """The chunks of a 25,000-row fit joined where they lie: no collective,
    next to no temporaries (XLA's own concatenate along the sharded axis:
    all-to-alls over the whole matrix and 4 GB of temporaries a chip)."""
    from keystone_tpu.parallel.mesh import row_sharding
    from keystone_tpu.workloads.cifar_random_patch import _local_concat

    row = row_sharding(data_mesh)
    parts = [jax.ShapeDtypeStruct((2048, 80000), jnp.float32, sharding=row)] * 13
    compiled = _local_concat(data_mesh, 6250 - 12 * 512).lower(*parts).compile()
    assert not _collectives(compiled.as_text())
    assert compiled.output_shardings.spec == row.spec
    mem = compiled.memory_analysis()
    assert 6250 * 80000 * 4 <= mem.output_size_in_bytes < 2.01e9  # rows padded to a tile
    assert mem.temp_size_in_bytes < 1 << 28


def _described_mapper(widths, classes, sharding):
    """A fitted block model of shapes only, placed by ``sharding``."""
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.solvers.block import BlockLinearMapper

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return BlockLinearMapper(
        [sds(w, classes) for w in widths], 4096, sds(classes),
        [StandardScalerModel(sds(w)) for w in widths],
    )


def test_mesh_apply_compiles_at_published_widths(data_mesh):
    """The fitted model applied to ``f32[25000, 80000]`` over four chips
    (`cifar_rp_10k_mesh4`: twenty blocks, ten classes, the model on every
    chip): nothing crosses chips, a chip reads its 6,250 rows once and
    writes their scores where they lie, and no centred ``[6250, 4096]``
    block (102 MB) is ever written: under 32 MiB of temporaries a chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import row_sharding
    from keystone_tpu.solvers import block

    row = row_sharding(data_mesh)
    model = _described_mapper(
        [4096] * 19 + [80000 - 19 * 4096], 10, NamedSharding(data_mesh, P())
    )
    batch = jax.ShapeDtypeStruct((25000, 80000), jnp.float32, sharding=row)
    compiled = block._block_apply.lower(model, batch).compile()
    text = compiled.as_text()
    assert not _collectives(text)
    assert "[25000," not in text and "f32[6250,80000]" in text
    assert compiled.output_shardings.spec == row.spec
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 32 << 20, mem.temp_size_in_bytes
    assert mem.output_size_in_bytes < 1 << 20
    moved = compiled.cost_analysis()["bytes accessed"]
    assert moved < 1.1 * 6250 * 80000 * 4, moved


def test_apply_compiles_at_benchmark_widths(one_chip):
    """``f32[50000, 10000]`` against three blocks on one chip
    (`cifar_rp_10k_share8`): the slices and the centring are the products'
    operands, so the 2 GB matrix is read once (the eager chain wrote and
    read a sliced and a centred copy of it) and nothing temporary is kept."""
    from keystone_tpu.solvers import block

    model = _described_mapper([4096, 4096, 10000 - 8192], 10, one_chip)
    batch = jax.ShapeDtypeStruct((50000, 10000), jnp.float32, sharding=one_chip)
    compiled = block._block_apply.lower(model, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 32 << 20, mem.temp_size_in_bytes
    moved = compiled.cost_analysis()["bytes accessed"]
    assert moved < 1.1 * 50000 * 10000 * 4, moved


# -- TimitPipeline at its documented 50 blocks (`timit_rf_50`) ---------------------


def test_made_block_solve_compiles_at_published_widths(one_chip):
    """``f32[32768, 440]`` rows x 50 chains of 4,096 cosine features x 147
    classes, 5 epochs, as one fused program that makes each block where it
    consumes it (``timit_rf_fit_full``): no array of the 204,800 columns in
    the compiled text (the design matrix would be 26.8 GB), the cosine inside
    a loop's body, and arguments, temporaries and results together under
    6 GB, of it the fifty Cholesky factors 3.36 GB as a result (a buffer the
    allocator counts) and under 1 GB of temporaries.  And the held program at
    ``timit_rf_share8``'s shape, six blocks sliced out of a 3.2 GB matrix,
    keeps the temporaries it had before the solver could make a block."""
    import re

    from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScalerModel
    from keystone_tpu.solvers import block
    from keystone_tpu.workloads.timit import FeaturizerBlock

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, d, nb, bs, k = 32768, 440, 50, 4096, 147
    chains = FeaturizerBlock([
        CosineRandomFeatures(sds(nb, bs, d), sds(nb, bs)),
        StandardScalerModel(sds(nb, bs), sds(nb, bs)),
    ])
    source = block.BlockSource(sds(n, d), chains, None, sds(nb, bs))
    scalars = (sds(), sds(dtype=jnp.int32))
    compiled = block._fused_bcd_fit.lower(
        source, sds(n, k), *scalars, 5, (bs,) * nb, None
    ).compile()
    text = compiled.as_text()
    assert not re.search(rf"[\[,]{nb * bs}[\],]", text)  # in no array's shape
    row_arrays = {int(w) for w in re.findall(r"\[32768,(\d+)\]", text)}
    assert bs in row_arrays and max(row_arrays) == bs, sorted(row_arrays)
    cosines = re.findall(r' cosine\(.*?op_name="([^"]*)"', text)
    assert cosines and all("/while/body/" in name for name in cosines), cosines
    mem = compiled.memory_analysis()
    factors = 4 * nb * bs * bs
    assert factors < mem.output_size_in_bytes < factors + (1 << 28)
    assert mem.temp_size_in_bytes < 1 << 30
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes < 6e9
    )

    held = block._fused_bcd_fit.lower(
        sds(n, 6 * bs), sds(n, k), *scalars, 5, (bs,) * 6, None
    ).compile()
    assert held.memory_analysis().temp_size_in_bytes == pytest.approx(561_119_232, rel=0.01)


#: sha256 of the lowered text of the fused program before it could keep a
#: made block (PR 38's, read from its own checkout: PERF.md §6, PR 39): the
#: held form at ``timit_rf_share8``'s shape and the made form keeping none
_HELD_TEXT = "f106907f3dd05c18361d1516bcac3a3936f26e6d472123f305e3d7fe77061383"
_MADE_TEXT = "2ae5d08dfd2453c8dbede0cfe9dce97c16657ca1e38370513fe2f96ba31d254e"
#: and the compiled text of the made form keeping none, in characters
_MADE_COMPILED_CHARS = 2_188_943


def test_made_solve_keeping_blocks_compiles_at_published_widths(one_chip, monkeypatch):
    """``timit_rf_fit_full`` with the made blocks kept that the rule keeps
    under the v5e's 16.91 GB limit: the compiled program's arguments,
    temporaries and results fit under that limit less its tenth; the kept
    blocks are one ``bf16[h + 1, 32768, 4096]`` result, allocated and not
    zeroed, that every gram and step reads in place and each later block is
    made into (no block-sized buffer in any loop, no copy of the stack), so
    the program is no larger than the one that keeps nothing; no array of
    the 204,800 columns exists.  The programs for an array ``x`` and for a
    source that keeps nothing lower to PR 38's text, byte for byte."""
    import hashlib
    import re

    from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScalerModel
    from keystone_tpu.solvers import block
    from keystone_tpu.workloads.timit import FeaturizerBlock

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, d, nb, bs, k, limit = 32768, 440, 50, 4096, 147, 16_909_336_064
    chains = FeaturizerBlock([
        CosineRandomFeatures(sds(nb, bs, d), sds(nb, bs)),
        StandardScalerModel(sds(nb, bs), sds(nb, bs)),
    ])
    source = block.BlockSource(sds(n, d), chains, None, sds(nb, bs))
    scalars = (sds(), sds(dtype=jnp.int32))
    widths = (bs,) * nb

    def digest(*args):
        text = block._fused_bcd_fit.lower(*args).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    assert digest(sds(n, 6 * bs), sds(n, k), *scalars, 5, (bs,) * 6, None) == _HELD_TEXT
    assert digest(source, sds(n, k), *scalars, 5, widths, None) == _MADE_TEXT

    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", str(limit))
    monkeypatch.setattr(block, "_kept_dtype", lambda dtype: np.dtype(jnp.bfloat16))  # the TPU's
    plan = block._plan_bcd(source, sds(n, k), 5, bs)
    h = plan["held_blocks"]
    assert plan["block_source"] == "made" and 30 <= h < nb, plan
    assert plan["held_stack_bytes"] == (h + 1) * n * bs * 2
    compiled = block._fused_bcd_fit.lower(
        source, sds(n, k), *scalars, 5, widths, None, hold=h, hold_dtype="bfloat16"
    ).compile()
    text = compiled.as_text()
    stack = f"bf16[{h + 1},32768,4096]"
    assert not re.search(rf"[\[,]{nb * bs}[\],]", text)
    assert not re.search(rf"= {re.escape(stack)}\S* (copy|broadcast)\(", text)
    bodies = dict(re.findall(r"^(%\S+) \(.*?\) -> .*?\{\n(.*?)^\}", text, re.M | re.S))
    loops = [bodies[b] for b in set(re.findall(r"body=(%[\w.-]+)", text)) if stack in bodies[b]]
    assert len(loops) >= 4  # the stack's scan, the grams, the steps, a block made again
    for body in loops:  # every buffer of a block's size is the stack itself
        sizes = re.findall(r"^\s*%\S+ = (\w+\[(?:\d+,)?32768,4096\])", body, re.M)
        assert set(sizes) <= {stack}, set(sizes)
    mem = compiled.memory_analysis()
    kept, factors = plan["held_stack_bytes"], 4 * nb * bs * bs
    assert kept + factors < mem.output_size_in_bytes < kept + factors + (1 << 28)
    assert mem.temp_size_in_bytes < 1 << 29
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert total < limit - limit // 10, total
    assert len(text) < 1.1 * _MADE_COMPILED_CHARS, len(text)  # PR 39's first form: 4.4 M


def test_a_cosine_make_holds_nothing_beside_its_block(one_chip):
    """``timit_rf_fit_full``'s make is its product's output: the compiled
    make asks no scratch beyond the block, so the plan charges nothing and
    keeps the blocks it kept before the charge existed."""
    from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScalerModel
    from keystone_tpu.solvers import block
    from keystone_tpu.workloads.timit import FeaturizerBlock

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, d, nb, bs = 32768, 440, 50, 4096
    chains = FeaturizerBlock([
        CosineRandomFeatures(sds(nb, bs, d), sds(nb, bs)),
        StandardScalerModel(sds(nb, bs), sds(nb, bs)),
    ])
    assert block._make_scratch(block.BlockSource(sds(n, d), chains, None, sds(nb, bs))) == 0


# -- MnistRandomFFT at its parser's defaults (`mnist_fft_200`) -------------------------


def test_made_fft_solve_compiles_at_published_widths(one_chip, monkeypatch):
    """``mnist_fft_fit``: 200 FFTs in fifty blocks of 2,048 on 60,000 rows.
    A block's four FFTs are one float32 product against their table: the
    TPU's transform (convolutions by stages, ``[rows, 4, 8, 128]`` and the
    like) runs on the table's 784 rows, the compiled make holds no array of
    the 60,000 rows but the rows and the block, and less than one block
    (0.49 GB) beyond its block; the plan keeps every block but the one
    the last slot makes, and the fused program that keeps them neither
    copies its ``bf16[50, 60000, 2048]`` stack nor holds a block's bytes of
    scratch, and fits under the v5e's 16.91 GB less its tenth."""
    import re

    from keystone_tpu.ops.stats import RandomFFTBlock
    from keystone_tpu.solvers import block

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, d, nb, f, k, limit = 60000, 784, 50, 4, 10, 16_909_336_064
    bs = 2048
    monkeypatch.setenv("KEYSTONE_HBM_BUDGET", str(limit))
    monkeypatch.setattr(block, "_kept_dtype", lambda dtype: np.dtype(jnp.bfloat16))  # the TPU's
    plan = block._plan_bcd(block.BlockSource(sds(n, d), RandomFFTBlock(sds(nb, f, d))), sds(n, k), 1, bs)
    assert plan["make_scratch_bytes"] < n * bs * 4, plan
    h = plan["held_blocks"]
    assert plan["block_source"] == "made" and h == nb - 1, plan
    source = block.BlockSource(sds(n, d), RandomFFTBlock(sds(nb, f, d)), None, sds(nb, bs))
    make = block._make_block.lower(source, sds(dtype=jnp.int32)).compile().as_text()
    rows_stages = set(re.findall(rf"= (f32\[{n},[\d,]+\])", make))
    assert rows_stages == {f"f32[{n},{d}]", f"f32[{n},{bs}]"}, rows_stages
    assert re.search(rf"= f32\[{d},{f},8,128\]", make)  # the table's transform
    scalars = (sds(), sds(dtype=jnp.int32))
    compiled = block._fused_bcd_fit.lower(
        source, sds(n, k), *scalars, 1, (bs,) * nb, None, hold=h, hold_dtype="bfloat16"
    ).compile()
    stack = re.escape(f"bf16[{h + 1},{n},{bs}]")
    assert not re.search(rf"= {stack}\S* (copy|broadcast)\(", compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < n * bs * 4, mem
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert total < limit - limit // 10, total


# -- ImageNetSiftLcsFV at its own widths (`imagenet_sift_lcs_fv_16`) ------------------


def test_weighted_solve_compiles_at_published_classes(one_chip):
    """The fused weighted solve at ``f32[4000 + 4, 4096]`` x 1,000 classes of
    4 rows (``imagenet_fv_fit``): sixteen classes at a time through the
    solver's own blocked routine, so the program holds one ``[16, 4097,
    4096]`` scratch for the factors' panels and no ``[16, 4096, 4096]``
    system, mask or zero-padded factor beside it, never a ``[1000, ...]``
    stack, and inverts each diagonal block once."""
    import re

    from keystone_tpu.solvers import weighted

    n, d, classes, n_max, chunk = 4000, 4096, 1000, 4, 16
    p = n + n_max
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = weighted._fused_bwls_fit_variant((0, 1)).lower(
        sds((p, d)), sds((p, classes)), sds((p, 1)), sds((p,), jnp.int32),
        sds((classes,), jnp.int32), sds((classes,), jnp.int32), sds((classes,)), sds((classes,)),
        sds((), jnp.int32), sds(()), sds(()),
        1, n_max, chunk, classes, (d,), None,
    ).compile()
    text = compiled.as_text()
    assert "jit__fused_bwls_impl" in text
    assert not re.search(rf"\[{classes},{d},{d}\]", text)
    assert not re.search(rf"\[{classes},{n_max},{d}\]", text)
    # the scratch is written a panel at a time, in place; nothing selects
    # over it (the library's NaN-on-failure and triangle masks), pads a
    # block out to it or copies it, and no whole system exists
    scratch = rf"f32\[{chunk},{d + 1},{d}\]"
    assert re.search(scratch, text)
    assert not re.search(rf"f32\[{chunk},{d},{d}\]", text)
    assert not re.search(rf"= {scratch}\S* (select|pad|copy|convolution|add|multiply)\(", text)
    # each of a class's d/128 diagonal blocks is inverted once (the library
    # pair inverted 31 of them in cho_factor and all 32 again in cho_solve)
    inverted = re.findall(
        rf"= f32\[{chunk},(\d+),128,128\]\S* custom-call\([^\n]*InvertDiagBlocksLowerTriangular", text
    )
    assert inverted and sum(int(k) for k in inverted) <= d // 128, inverted
    mem = compiled.memory_analysis()
    # the scratch (1.07 GB) and a panel's temporaries, plus a quarter
    assert mem.temp_size_in_bytes < 1.5 * (1 << 30), mem.temp_size_in_bytes
    print("weighted solve: temp", mem.temp_size_in_bytes, "args", mem.argument_size_in_bytes)


def _imagenet_branches():
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    return inet.descriptor_branches(inet.ImageNetSiftLcsFVConfig())


def test_lcs_chunk_compiles_at_published_widths(one_chip):
    """The LCS branch's chunk program at 64 images of 375x500: its own module
    name, float32 descriptors ``[64, 96, 10062]``, and no array with the
    three channels innermost after the first transpose (an accelerator pads
    such an axis to a whole tile)."""
    from keystone_tpu.workloads import fv_common

    lcs = _imagenet_branches()[1]
    assert (lcs.dim, lcs.cols(375, 500)) == (96, 10062)
    flat = jax.ShapeDtypeStruct((64, 375 * 500 * 3), jnp.uint8, sharding=one_chip)
    compiled = lcs.describe.lower(lcs.node, flat, image_shape=(375, 500, 3)).compile()
    assert lcs.describe is fv_common._describe_lcs_chunk
    text = compiled.as_text()
    assert "jit__describe_lcs_chunk" in text
    assert "f32[64,96,10062]" in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes < 1.02 * 64 * 96 * 10062 * 4  # the tile's pad
    assert mem.temp_size_in_bytes < 2 << 30, mem.temp_size_in_bytes
    print("lcs chunk: temp", mem.temp_size_in_bytes)


def test_two_branch_encode_compiles_at_published_widths(one_chip, monkeypatch):
    """Both branches' PCA and Fisher vectors of a 64-image chunk of 375x500
    as one program: SIFT's bytes ``[64, 128, 40584]`` and LCS's float32
    ``[64, 96, 10062]`` in, ``[64, 4096]`` rows out; no operand holds every
    image's descriptors (``[8000, 40584, 128]``)."""
    from keystone_tpu.ops import fisher
    from keystone_tpu.solvers.gmm import GaussianMixtureModel
    from keystone_tpu.solvers.pca import BatchPCATransformer
    from keystone_tpu.workloads import fv_common
    from keystone_tpu.workloads.imagenet_sift_lcs_fv import branch_projection

    monkeypatch.setattr(fisher, "fv_form", lambda *a: "kernel")
    rng = np.random.default_rng(0)
    d, k = 64, 16
    chains, descs = [], []
    for branch, dtype in zip(_imagenet_branches(), (jnp.uint8, jnp.float32)):
        pca = BatchPCATransformer(rng.normal(size=(branch.dim, d)).astype(np.float32))
        gmm = GaussianMixtureModel(
            rng.normal(size=(d, k)).astype(np.float32),
            rng.uniform(0.5, 2.0, (d, k)).astype(np.float32),
            rng.dirichlet(np.ones(k)).astype(np.float32),
        )
        chains.append((branch_projection(branch.name, pca, jnp.zeros((branch.dim,), jnp.float32)), gmm))
        descs.append(
            jax.ShapeDtypeStruct((64, branch.dim, branch.cols(375, 500)), dtype, sharding=one_chip)
        )
    fv_common._encode_chunk.clear_cache()
    try:
        compiled = fv_common._encode_chunk.lower(tuple(chains), tuple(descs)).compile()
    finally:
        fv_common._encode_chunk.clear_cache()
    text = compiled.as_text()
    assert "f32[64,4096]" in text
    assert "8000," not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 << 30, mem.temp_size_in_bytes
    print("two-branch encode: temp", mem.temp_size_in_bytes)


@pytest.mark.parametrize("samples", [10_000_000, 1_000_000])
def test_pca_svd_at_the_published_sample(one_chip, samples):
    """``compute_pca`` of SIFT's ``[samples, 128]`` PCA sample.  At the
    published 1e7 the sample is 5.1 GB and the program's temporaries are
    several copies of it: beside the LCS branch's 3.8 GB sample and a chunk it
    does not fit a 16 GB chip, which is what cuts ``num_pca_samples`` to 1e6
    in ``imagenet_sift_lcs_fv_16`` (PERF.md section 4)."""
    from keystone_tpu.solvers.pca import compute_pca

    x = jax.ShapeDtypeStruct((samples, 128), jnp.float32, sharding=one_chip)
    try:
        compiled = jax.jit(compute_pca, static_argnums=1).lower(x, 64).compile()
    except Exception as e:  # noqa: BLE001 - the compiler refusing the size is the finding
        assert samples == 10_000_000 and "RESOURCE_EXHAUSTED" in str(e), e
        return
    mem = compiled.memory_analysis()
    held = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    print(f"svd at {samples}: temp {mem.temp_size_in_bytes} args {mem.argument_size_in_bytes}")
    if samples == 10_000_000:
        assert held > 10e9, held
    else:
        assert held < 2.5e9, held


def _replicated_like(tree, mesh):
    """``tree``'s arrays as shapes replicated on every chip of ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    everywhere = NamedSharding(mesh, P())
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=everywhere), tree)


def test_mesh_sift_chunk_compiles_at_published_widths(data_mesh, monkeypatch):
    """`fv_common._describe_chunk` at 256 images of 375x500 over four chips
    (`imagenet_sift_lcs_fv_16_mesh4`), the kernel form chosen as on the chip:
    the assembly's kernel under ``shard_map`` a scale, nothing exchanged, a
    chip writing its 64 images' bytes and no array of all 256."""
    from keystone_tpu.ops import sift
    from keystone_tpu.parallel.mesh import row_sharding
    from keystone_tpu.workloads import fv_common

    rule = sift.sift_form
    monkeypatch.setattr(sift, "sift_form", lambda _b, *a: rule("tpu", *a))
    node_ = sift.SIFTExtractor(scale_step=1, compute_dtype=jnp.bfloat16)
    frames = node_.num_descriptors(375, 500)
    flat = jax.ShapeDtypeStruct((256, 375 * 500 * 3), jnp.uint8, sharding=row_sharding(data_mesh))
    fv_common._describe_chunk.clear_cache()
    try:
        compiled = fv_common._describe_chunk.lower(node_, flat, image_shape=(375, 500, 3)).compile()
    finally:
        fv_common._describe_chunk.clear_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not _collectives(text)
    assert f"u8[64,128,{frames}]" in text
    assert "u8[256," not in text and "[256,375,500" not in text
    assert compiled.output_shardings.spec == row_sharding(data_mesh).spec
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes
    print("mesh sift chunk: temp a chip", mem.temp_size_in_bytes)


def test_mesh_two_branch_encode_compiles_at_published_widths(data_mesh, monkeypatch):
    """Both branches' PCA and Fisher vectors of a 256-image chunk over four
    chips, the mixtures and projections replicated: the statistics' kernel
    under ``shard_map``, nothing exchanged, a chip's 64 rows of features."""
    from keystone_tpu.ops import fisher
    from keystone_tpu.parallel.mesh import row_sharding
    from keystone_tpu.solvers.gmm import GaussianMixtureModel
    from keystone_tpu.solvers.pca import BatchPCATransformer
    from keystone_tpu.workloads import fv_common
    from keystone_tpu.workloads.imagenet_sift_lcs_fv import branch_projection

    rule = fisher.fv_form
    monkeypatch.setattr(fisher, "fv_form", lambda _b, *a: rule("tpu", *a))
    rng = np.random.default_rng(0)
    d, k = 64, 16
    chains, descs = [], []
    for branch, dtype in zip(_imagenet_branches(), (jnp.uint8, jnp.float32)):
        pca = BatchPCATransformer(rng.normal(size=(branch.dim, d)).astype(np.float32))
        gmm = GaussianMixtureModel(
            rng.normal(size=(d, k)).astype(np.float32),
            rng.uniform(0.5, 2.0, (d, k)).astype(np.float32),
            rng.dirichlet(np.ones(k)).astype(np.float32),
        )
        chains.append((branch_projection(branch.name, pca, jnp.zeros((branch.dim,), jnp.float32)), gmm))
        descs.append(jax.ShapeDtypeStruct(
            (256, branch.dim, branch.cols(375, 500)), dtype, sharding=row_sharding(data_mesh)
        ))
    fv_common._encode_chunk.clear_cache()
    try:
        compiled = fv_common._encode_chunk.lower(
            _replicated_like(tuple(chains), data_mesh), tuple(descs)
        ).compile()
    finally:
        fv_common._encode_chunk.clear_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not _collectives(text)
    assert "f32[64,4096]" in text and "f32[256,4096]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 << 30, mem.temp_size_in_bytes
    print("mesh two-branch encode: temp a chip", mem.temp_size_in_bytes)


def test_class_split_solve_compiles_at_published_classes(data_mesh):
    """The class-split weighted solve of ``imagenet_fv_fit_mesh4``: 16,000
    rows of 4,096 over four chips, 1,000 classes of 16 rows, 250 a chip.  A
    chip holds its 4,016 rows and no operand of all of them, the population
    statistics cross the chips as all-reduces, the model's columns as one
    all-gather of every chip's 250, and a chip's sweep has the one-chip scratch of
    sixteen systems; what a chip holds stays well inside its 16 GB."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import DATA_AXIS
    from keystone_tpu.solvers import weighted

    counts = np.full(1000, 16)
    split = weighted._class_split(counts, 4, 16)
    assert split.rows_out == 4016 and split.slot_classes.shape == (4, 250)
    p, d, c = 4 * split.rows_out, 4096, 1000
    row = NamedSharding(data_mesh, P(DATA_AXIS, None))
    sds = jax.ShapeDtypeStruct
    compiled = weighted._split_bwls_fit.lower(
        sds((p, d), jnp.float32, sharding=row), sds((p, c), jnp.float32, sharding=row),
        sds((p, 1), jnp.float32, sharding=row),
        sds((p,), jnp.int32, sharding=NamedSharding(data_mesh, P(DATA_AXIS))),
        *[sds((4, 250), jnp.int32, sharding=row)] * 3, sds((c,), jnp.int32),
        sds((c,), jnp.float32), sds((c,), jnp.float32), sds((), jnp.int32),
        sds((), jnp.float32), sds((), jnp.float32),
        1, 16, 16, c, (d,), data_mesh,
    ).compile()
    text = compiled.as_text()
    assert "jit__split_bwls_impl" in text
    kinds = _collectives(text)
    reduced = [kind for op, kind in kinds if op == "all-reduce"]
    assert any("f32[4096,4096]" in kind for kind in reduced), reduced
    gathered = [kind for op, kind in kinds if op == "all-gather"]
    assert gathered and all("[4,250,4096]" in kind for kind in gathered), gathered
    assert not [op for op, _kind in kinds if op == "all-to-all"]
    assert f"[{p}," not in text and "f32[4016,4096]" in text
    assert re.search(r"f32\[16,4097,4096\]", text)
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    print("class-split solve a chip: args", mem.argument_size_in_bytes, "temp", mem.temp_size_in_bytes)
    assert per_chip < 4e9, per_chip
