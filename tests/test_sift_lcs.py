"""Dense SIFT / LCS extractor tests.

The reference's golden-file fixtures (feats128.csv for SIFT) are absent from
its own test resources, so the criteria here are: structural invariants
(shape, quantization range, descriptor count), naive-loop equivalence for
LCS against a direct transcription of the reference's per-pixel code, and
behavioral SIFT properties (rotation shifts orientation mass, flat images
give zero descriptors, contrast threshold)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core import trace
from keystone_tpu.ops import sift as sift_ops
from keystone_tpu.ops.lcs import LCSExtractor, _same_conv2d_zero
from keystone_tpu.ops.sift import SIFTExtractor, sift_form
from keystone_tpu.utils.stats import about_eq


class TestSIFT:
    def test_shapes_and_quantization(self, rng):
        img = rng.uniform(size=(2, 48, 48)).astype(np.float32)
        ext = SIFTExtractor(step_size=4, bin_size=4, scales=2, scale_step=0)
        out = np.asarray(ext(jnp.asarray(img)))
        assert out.shape[0] == 2 and out.shape[1] == 128
        assert out.shape[2] == ext.num_descriptors(48, 48)
        assert out.min() >= 0.0 and out.max() <= 255.0
        assert np.all(out == np.floor(out))  # quantized
        assert out.max() > 0  # something fired on random texture

    def test_flat_image_zero_descriptors(self):
        img = jnp.full((1, 40, 40), 0.5, jnp.float32)
        ext = SIFTExtractor(step_size=4, bin_size=4, scales=2, scale_step=0)
        out = np.asarray(ext(img))
        # no gradient -> norms below contrast threshold -> all zeroed
        assert np.all(out == 0.0)

    def test_contrast_threshold_zeroes_weak_regions(self, rng):
        # left half flat, right half textured: descriptors fully inside the
        # flat half must be zero, textured ones nonzero
        img = np.full((1, 60, 60), 0.5, np.float32)
        img[0, :, 30:] = rng.uniform(size=(60, 30)).astype(np.float32)
        ext = SIFTExtractor(step_size=3, bin_size=4, scales=1, scale_step=0)
        out = np.asarray(ext(jnp.asarray(img)))
        col_norms = np.linalg.norm(out[0], axis=0)
        assert (col_norms == 0).any() and (col_norms > 0).any()

    def test_90deg_rotation_permutes_orientations(self, rng):
        # rotating the image by 90° must keep descriptor energy but move it
        # across orientation bins: total energy is preserved ~exactly
        img = rng.uniform(size=(36, 36)).astype(np.float32)
        ext = SIFTExtractor(step_size=3, bin_size=4, scales=1, scale_step=0)
        a = np.asarray(ext(jnp.asarray(img[None])))
        b = np.asarray(ext(jnp.asarray(np.rot90(img).copy()[None])))
        assert a.shape == b.shape
        assert abs(a.sum() - b.sum()) / max(a.sum(), 1.0) < 0.05

    def test_multiscale_grids_nested_when_steps_equal(self):
        # scaleStep=0: all scales share step; offsets are arranged so frame
        # centers coincide (VLFeat.cxx:92-95)
        ext = SIFTExtractor(step_size=2, bin_size=4, scales=3, scale_step=0)
        from keystone_tpu.ops.sift import _scale_geometry

        centers = []
        for s in range(3):
            b = 4 + 2 * s
            ys, xs = _scale_geometry(64, 64, 2, b, 3, s)
            centers.append(ys[0] + 1.5 * b)  # first frame center
        assert centers[0] == centers[1] == centers[2]


def _conv1d_axis(batch, kernel, axis):
    """Convolve [N, H, W] along ``axis`` (1=rows/y, 2=cols/x) with edge pad."""
    k = jnp.asarray(kernel, batch.dtype)
    klen = k.shape[0]
    r = (klen - 1) // 2
    pad = [(0, 0), (0, 0), (0, 0)]
    pad[axis] = (r, klen - 1 - r)
    x = jnp.pad(batch, pad, mode="edge")
    # depthwise conv via conv_general_dilated on a singleton channel
    x4 = x[:, None, :, :]  # [N, 1, H, W]
    if axis == 1:
        kern = k[::-1].reshape(1, 1, klen, 1)
    else:
        kern = k[::-1].reshape(1, 1, 1, klen)
    out = jax.lax.conv_general_dilated(
        x4, kern, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW")
    )
    return out[:, 0]


def conv_smooth(batch, sigma: float):
    """``ops/sift._smooth`` as it stood while the smoothing was two edge pads
    and two one-channel convolutions a scale (to PR 34), kept word for word
    with ``_conv1d_axis`` above: the plain reference the banded products are
    held to."""
    k = sift_ops._gaussian_kernel(sigma)
    return _conv1d_axis(_conv1d_axis(batch, k, 1), k, 2)


def parent_sift(ext: SIFTExtractor, batch, smooth=conv_smooth):
    """``SIFTExtractor.__call__`` as it stood before the assembly had two
    forms (PR 30), kept word for word, on the smoothing as it stood before it
    was banded products (PR 35): what both forms are held to.  With ``smooth``
    the program's own, only the assembly differs from the program's."""
    n, h, w = batch.shape
    cdt = ext.compute_dtype
    batch = batch.astype(cdt)
    per_scale = []
    for s in range(ext.scales):
        b = ext.bin_size + 2 * s
        step = ext.step_size + s * ext.scale_step
        ys, xs = sift_ops._scale_geometry(h, w, step, b, ext.scales, s)
        if len(ys) == 0 or len(xs) == 0:
            continue
        smoothed = smooth(batch, b / sift_ops.MAGNIF)
        gy, gx = sift_ops._gradients(smoothed)
        planes = sift_ops._orientation_planes(gy, gx).astype(cdt)
        tri = sift_ops._triangular_kernel(b)
        bin_off = np.arange(4) * b
        yy = (ys[:, None] + bin_off[None, :]).ravel()
        xx = (xs[:, None] + bin_off[None, :]).ravel()
        s_y = jnp.asarray(sift_ops._binned_sampling_matrix(h, yy, tri), cdt)
        s_x = jnp.asarray(sift_ops._binned_sampling_matrix(w, xx, tri), cdt)
        part = jnp.einsum(
            "ph,nthw->ntpw", s_y, planes, preferred_element_type=jnp.float32
        ).astype(cdt)
        sampled = jnp.einsum(
            "ntpw,qw->ntpq", part, s_x, preferred_element_type=jnp.float32
        ).astype(cdt)
        fy, fx = len(ys), len(xs)
        sampled = sampled.reshape(n, 8, fy, 4, fx, 4)
        per_scale.append(
            jnp.einsum("ntybxc->nyxbct", sampled).reshape(n, fy * fx, 128)
        )
    descs = jnp.concatenate(per_scale, axis=1)
    norms = jnp.sqrt(
        jnp.sum(jnp.square(descs.astype(jnp.float32)), axis=-1, keepdims=True)
    )
    normed = descs.astype(jnp.float32) / jnp.maximum(norms, 1e-12)
    clamped = jnp.minimum(normed, 0.2)
    norms2 = jnp.linalg.norm(clamped, axis=-1, keepdims=True)
    final = clamped / jnp.maximum(norms2, 1e-12)
    final = jnp.where(norms > sift_ops.CONTRAST_THRESHOLD, final, 0.0)
    return jnp.swapaxes(jnp.minimum(jnp.floor(512.0 * final), 255.0), 1, 2)


def _textured(rng, n, h, w):
    """Images in [0, 1] with gradients everywhere but in image 1, which has
    no contrast at all."""
    img = rng.uniform(size=(n, h, w)).astype(np.float32)
    img[1] = 0.5
    return jnp.asarray(img)


#: VOC's three aspect ratios (375x500, 500x375, 333x500) at an eighth, and a
#: grid whose coarsest scale (bin 10: 31 rows) has no frame.
GRIDS = [(47, 63), (63, 47), (42, 63), (30, 46)]


def _parent(ext, smooth=conv_smooth):
    return jax.jit(functools.partial(parent_sift, ext, smooth=smooth))


def _bf16_steps(got, want):
    """``|got - want|`` in steps of bfloat16 at ``want`` (8 bits: a step is
    ``2**(exponent - 7)``)."""
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return np.abs(got - want) / step


#: sigma of the four scales (bin 4, 6, 8, 10): radii 3, 4, 6, 7
SIGMAS = [b / sift_ops.MAGNIF for b in (4, 6, 8, 10)]


class TestBandedSmoothing:
    """``ops/sift._smooth`` (two banded products, the edge padding folded into
    the matrices) against the edge pads and one-channel convolutions it
    replaced (``conv_smooth``)."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("sigma", SIGMAS, ids=lambda s: f"sigma{s:.2f}")
    @pytest.mark.parametrize(
        "hw", [(32, 32), (37, 53), (48, 5), (3, 41)], ids=lambda hw: f"{hw[0]}x{hw[1]}"
    )
    def test_banded_smooth_is_the_convolution(self, rng, hw, sigma, dtype):
        """Float32: to the order of the sum.  bfloat16: the same weights and
        roundings wherever the window lies inside the image (one step at
        most); within the kernel's radius of a border the taps that fold
        onto the edge pixel are one weight of the matrix, rounded once more
        (48x5 and 3x41 are narrower than every radius: all border)."""
        h, w = hw
        batch = jnp.asarray(rng.uniform(size=(3, h, w)).astype(np.float32), dtype)
        want = conv_smooth(batch, sigma)
        got = jax.jit(sift_ops._smooth, static_argnums=1)(batch, sigma)
        assert got.shape == want.shape and got.dtype == want.dtype == dtype
        if dtype == jnp.float32:
            assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-6
            return
        steps = _bf16_steps(got, want)
        assert steps.max() <= 2.0
        r = (len(sift_ops._gaussian_kernel(sigma)) - 1) // 2
        inside = steps[:, r : h - r, r : w - r]
        assert inside.size == 0 or inside.max() <= 1.0

    def test_constant_image_stays_constant(self):
        """No contrast in, none out: the rows of both matrices sum to one
        closely enough that a flat image stays within one bfloat16 step of
        itself, edge rows (the folded weights) included."""
        levels = jnp.linspace(0.05, 1.0, 20, dtype=jnp.float32)
        for dtype in (jnp.float32, jnp.bfloat16):
            batch = jnp.broadcast_to(levels[:, None, None], (20, 24, 31)).astype(dtype)
            for sigma in SIGMAS:
                out = np.asarray(sift_ops._smooth(batch, sigma).astype(jnp.float32))
                spread = out.max(axis=(1, 2)) - out.min(axis=(1, 2))
                assert (spread <= np.asarray(levels) * 2.0**-7).all()


class TestAssemblyForms:
    """The descriptor assembly (binned planes -> normalized bytes) has a
    kernel form (ops/sift_pallas.py, here in the Pallas interpreter) and the
    XLA form; both give the descriptors the extractor gave before."""

    @pytest.mark.parametrize(
        "form,dtype",
        [("xla", jnp.float32), ("xla", jnp.bfloat16), ("kernel", jnp.bfloat16)],
        ids=["xla-f32", "xla-bf16", "kernel-bf16"],
    )
    @pytest.mark.parametrize("scale_step", [0, 1])
    @pytest.mark.parametrize("hw", GRIDS, ids=lambda hw: f"{hw[0]}x{hw[1]}")
    def test_forms_follow_the_convolved_parent(self, rng, hw, scale_step, form, dtype):
        """Both forms against the parent on the smoothing it had (edge pads
        and one-channel convolutions).  Float32: the order of a sum, so a
        floor now and then.  bfloat16: the border band's weights are rounded
        once more (``TestBandedSmoothing``), and these grids are an eighth of
        VOC's, so the band is a third of the image: the envelope is the one
        the bfloat16 chain has against the float32 chain (class docstring of
        ``SIFTExtractor``)."""
        ext = SIFTExtractor(scale_step=scale_step, compute_dtype=dtype)
        batch = _textured(rng, 32 if form == "kernel" else 3, *hw)
        want = np.asarray(_parent(ext)(batch))
        run = ext.__call__ if form == "xla" else functools.partial(ext._kernel_form, interpret=True)
        off = np.abs(np.asarray(jax.jit(run)(batch)) - want)
        assert want.max() > 0 and off[1].max() == 0  # zero contrast: zeros in both
        if dtype == jnp.float32:
            assert off.max() <= 1.0 and (off == 0).mean() >= 0.9995
        else:
            assert (off <= 1.0).mean() >= 0.99 and (off == 0).mean() >= 0.95
            assert off.max() <= 32.0

    @pytest.mark.parametrize("scale_step", [0, 1])
    @pytest.mark.parametrize("hw", GRIDS, ids=lambda hw: f"{hw[0]}x{hw[1]}")
    def test_kernel_form_gives_the_parents_descriptors(self, rng, hw, scale_step):
        ext = SIFTExtractor(scale_step=scale_step, compute_dtype=jnp.bfloat16)
        batch = _textured(rng, 32, *hw)
        want = np.asarray(_parent(ext, sift_ops._smooth)(batch))
        got = np.asarray(
            jax.jit(functools.partial(ext._kernel_form, interpret=True))(batch)
        )
        assert got.shape == want.shape == (32, 128, ext.num_descriptors(*hw))
        assert got.dtype == np.float32
        assert np.abs(got - want).max() <= 1.0
        assert (got == want).mean() >= 0.999
        assert want.max() > 0 and not got[1].any()  # zero contrast: zero columns

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("scale_step", [0, 1])
    @pytest.mark.parametrize("hw", GRIDS, ids=lambda hw: f"{hw[0]}x{hw[1]}")
    def test_xla_form_is_the_parents(self, rng, hw, scale_step, dtype):
        """What every CPU run, mesh run and float32 caller gets."""
        ext = SIFTExtractor(scale_step=scale_step, compute_dtype=dtype)
        batch = _textured(rng, 3, *hw)
        want = np.asarray(_parent(ext, sift_ops._smooth)(batch))
        got = np.asarray(jax.jit(ext.__call__)(batch))
        np.testing.assert_array_equal(got, want)
        assert not got[1].any()

    @pytest.mark.parametrize(
        "backend,split,dtype,images,fx,shards,want",
        [
            pytest.param("tpu", (), jnp.bfloat16, 64, 160, 1, "kernel", id="tpu-True-bfloat16-64-160-kernel"),
            pytest.param("tpu", (), jnp.bfloat16, 32, 79, 1, "kernel", id="tpu-True-bfloat16-32-79-kernel"),
            # Mosaic: TPU only
            pytest.param("cpu", (), jnp.bfloat16, 64, 160, 1, "xla", id="cpu-True-bfloat16-64-160-xla"),
            # 64 images a chip under shard_map
            pytest.param("tpu", ("data",), jnp.bfloat16, 256, 160, 4, "kernel", id="tpu-data-bfloat16-256-160-kernel"),
            # 16 a chip: no byte tile
            pytest.param("tpu", ("data",), jnp.bfloat16, 64, 160, 4, "xla", id="tpu-data-bfloat16-64-160-xla"),
            # a model split: the kernel is not partitioned over it
            pytest.param("tpu", ("data", "model"), jnp.bfloat16, 256, 160, 4, "xla", id="tpu-False-bfloat16-64-160-xla"),
            # spread over no named mesh
            pytest.param("tpu", ("?",), jnp.bfloat16, 64, 160, 1, "xla", id="tpu-spread-bfloat16-64-160-xla"),
            # two bf16 rows a word
            pytest.param("tpu", (), jnp.float32, 64, 160, 1, "xla", id="tpu-True-float32-64-160-xla"),
            # byte tiles of 32 images
            pytest.param("tpu", (), jnp.bfloat16, 1, 160, 1, "xla", id="tpu-True-bfloat16-1-160-xla"),
            pytest.param("tpu", (), jnp.bfloat16, 48, 160, 1, "xla", id="tpu-True-bfloat16-48-160-xla"),
            # a frame row over VMEM
            pytest.param("tpu", (), jnp.bfloat16, 64, 2000, 1, "xla", id="tpu-True-bfloat16-64-2000-xla"),
            # no frames at all
            pytest.param("tpu", (), jnp.bfloat16, 64, 0, 1, "xla", id="tpu-True-bfloat16-64-0-xla"),
        ],
    )
    def test_sift_form_rule(self, backend, split, dtype, images, fx, shards, want):
        assert sift_form(backend, split, dtype, images, fx, shards) == want

    def test_sift_form_counter_moves(self, rng):
        """``sift_form.<form>`` counts a traced program, not its calls, and
        the instant says what the form was chosen on and how the planes were
        smoothed (one way: two banded products a scale, of these sizes)."""
        ext = SIFTExtractor(scale_step=0, compute_dtype=jnp.bfloat16)
        fn = jax.jit(ext.__call__)
        before = trace.metrics.get("sift_form.xla")
        kernel_before = trace.metrics.get("sift_form.kernel")
        for _ in range(3):
            fn(_textured(rng, 32, 30, 46))
        assert trace.metrics.get("sift_form.xla") == before + 1
        assert trace.metrics.get("sift_form.kernel") == kernel_before
        last = [e for e in trace.flight_events() if e["name"] == "sift_form"][-1]
        assert last["args"] == {
            "form": "xla", "images": 32, "scales": 3,
            "frames": ext.num_descriptors(30, 46), "shards": 1, "split": "",
            "smooth": "banded", "smooth_rows": "30x30", "smooth_cols": "46x46",
        }


class TestShardedAssembly:
    """The kernel form of the assembly under ``shard_map`` over a 4-way data
    axis (Pallas in the interpreter): each chip its own 32 images."""

    @pytest.mark.parametrize(
        "layout,form,shards", [((4, 1), "kernel", 4), ((2, 2), "xla", 1)], ids=["data", "model"]
    )
    def test_form_follows_the_split(self, rng, devices, monkeypatch, layout, form, shards):
        """What ``__call__`` does under a mesh, the rule asked as on a TPU:
        a data split takes the kernel under ``shard_map``, each chip's
        descriptors those of the one-device kernel form row for row and left
        on its chip; a model split takes the XLA form.  The counter and the
        instant say which."""
        from keystone_tpu.parallel.mesh import make_mesh, row_sharding

        rule = sift_ops.sift_form
        monkeypatch.setattr(sift_ops, "sift_form", lambda _b, *a: rule("tpu", *a))
        kernel = SIFTExtractor._kernel_form
        monkeypatch.setattr(
            SIFTExtractor, "_kernel_form", lambda self, b, interpret=True: kernel(self, b, True)
        )
        mesh = make_mesh(*layout, devices=devices[:4])
        ext = SIFTExtractor(scale_step=0, compute_dtype=jnp.bfloat16)
        batch = _textured(rng, 128, 30, 46)
        before = trace.metrics.get(f"sift_form.{form}")
        out = jax.jit(ext.__call__)(jax.device_put(batch, row_sharding(mesh)))
        if form == "kernel":
            frames = ext.num_descriptors(30, 46)
            assert {s.data.shape for s in out.addressable_shards} == {(32, 128, frames)}
        got = np.asarray(out)
        assert trace.metrics.get(f"sift_form.{form}") == before + 1
        last = [e for e in trace.flight_events() if e["name"] == "sift_form"][-1]["args"]
        assert (last["form"], last["shards"]) == (form, shards)
        assert last["split"] == ("data" if layout[1] == 1 else "data,model")
        want = kernel(ext, batch, True) if form == "kernel" else ext._xla_form(batch)
        np.testing.assert_array_equal(got, np.asarray(want))


def naive_lcs(img, stride, stride_start, sub):
    """Direct transcription of LCSExtractor.scala:52-126 (with x = column
    axis, y = row axis; spatially symmetric ops make the convention moot)."""
    h, w, c = img.shape
    box = np.full(sub, 1.0 / sub)

    def conv_same(plane):
        padded = np.zeros((h + sub - 1, w + sub - 1))
        lo = (sub - 1) // 2
        padded[lo : lo + h, lo : lo + w] = plane
        mid = np.zeros((h, w + sub - 1))
        for y in range(h):
            for x in range(w + sub - 1):
                acc = 0.0
                for i in range(sub):
                    acc += padded[y + i, x] * box[sub - 1 - i]
                mid[y, x] = acc
        out = np.zeros((h, w))
        for y in range(h):
            for x in range(w):
                acc = 0.0
                for i in range(sub):
                    acc += mid[y, x + i] * box[sub - 1 - i]
                out[y, x] = acc
        return out

    means = [conv_same(img[:, :, ch]) for ch in range(c)]
    stds = [
        np.sqrt(np.maximum(conv_same(img[:, :, ch] ** 2) - means[ch] ** 2, 0))
        for ch in range(c)
    ]
    xs = list(range(stride_start, w - stride_start, stride))
    ys = list(range(stride_start, h - stride_start, stride))
    nbr = list(range(-2 * sub + sub // 2 - 1, sub + sub // 2 - 1 + 1, sub))
    cols = []
    for x in xs:
        for y in ys:
            vals = []
            for ch in range(c):
                for nx in nbr:
                    for ny in nbr:
                        vals.append(means[ch][y + ny, x + nx])
                        vals.append(stds[ch][y + ny, x + nx])
            cols.append(vals)
    return np.array(cols).T  # [descDim, K]


class TestLCS:
    def test_conv_same_matches_reference_padding(self, rng):
        img = rng.uniform(size=(1, 7, 9, 1)).astype(np.float32)
        box = np.full(4, 0.25, np.float32)
        got = np.asarray(_same_conv2d_zero(jnp.asarray(img), box, box))[0, :, :, 0]
        h, w = 7, 9
        padded = np.zeros((h + 3, w + 3))
        padded[1 : 1 + h, 1 : 1 + w] = img[0, :, :, 0]  # lo = (4-1)//2 = 1
        full = np.zeros((h, w))
        for y in range(h):
            for x in range(w):
                acc = 0.0
                for i in range(4):
                    for j in range(4):
                        acc += padded[y + i, x + j] * box[3 - i] * box[3 - j]
                full[y, x] = acc
        assert about_eq(got, full, 1e-4)

    def test_matches_naive_transcription(self, rng):
        img = rng.uniform(size=(32, 32, 3)).astype(np.float32)
        ext = LCSExtractor(stride=5, stride_start=12, sub_patch_size=3)
        got = np.asarray(ext(jnp.asarray(img[None])))[0]
        expected = naive_lcs(img.astype(np.float64), 5, 12, 3)
        assert got.shape == expected.shape
        assert about_eq(got, expected, 1e-3)

    def test_descriptor_dim_96_for_rgb(self, rng):
        # the canonical config: 4x4 neighborhood x 3 channels x (mean, std)
        img = rng.uniform(size=(1, 64, 64, 3)).astype(np.float32)
        ext = LCSExtractor(stride=4, stride_start=16, sub_patch_size=6)
        out = np.asarray(ext(jnp.asarray(img)))
        assert out.shape[1] == 96
        assert out.shape[2] == ext.num_keypoints(64, 64)
