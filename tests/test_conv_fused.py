"""FusedConvFeaturizer vs the op-by-op chain (the fused path is the cifar
workload default; equivalence here is what licenses that swap — reference
chain RandomPatchCifar.scala:53-56, ConvolverSuite/PoolingSuite spirit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.core import trace
from keystone_tpu.ops.conv_fused import (
    _IMAGES_PER_STEP,
    FusedConvFeaturizer,
    conv_form,
)
from keystone_tpu.ops.images import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.core.pipeline import Pipeline


def _unfused(filters, means, alpha, stride, size):
    return Pipeline(
        [
            Convolver(filters, whitener_means=means, normalize_patches=True,
                      img_channels=filters.shape[-1]),
            SymmetricRectifier(alpha=alpha),
            Pooler(stride, size, None, "sum"),
            ImageVectorizer(),
        ]
    )


@pytest.mark.parametrize(
    "h,w,fsz,ws,stride,size",
    [
        (32, 32, 100, 6, 13, 14),  # the RandomPatchCifar shape
        (20, 24, 7, 5, 4, 6),      # uneven dims, truncated edge pools
        (16, 16, 3, 3, 5, 5),      # odd pool size (span ps-1 semantics)
    ],
)
def test_fused_matches_unfused_f32(rng, h, w, fsz, ws, stride, size):
    imgs = jnp.asarray(rng.uniform(0, 255, (5, h, w, 3)).astype(np.float32))
    filters = jnp.asarray(rng.normal(size=(fsz, ws, ws, 3)).astype(np.float32))
    means = jnp.asarray(rng.normal(size=(ws * ws * 3,)).astype(np.float32))
    ref = np.asarray(_unfused(filters, means, 0.25, stride, size)(imgs))
    got = np.asarray(
        FusedConvFeaturizer(
            filters, whitener_means=means, pool_stride=stride, pool_size=size,
            alpha=0.25, activation_dtype=jnp.float32,
        )(imgs)
    )
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5 * np.abs(ref).max())


def test_fused_bf16_within_storage_rounding(rng):
    imgs = jnp.asarray(rng.uniform(0, 255, (4, 32, 32, 3)).astype(np.float32))
    filters = jnp.asarray(rng.normal(size=(24, 6, 6, 3)).astype(np.float32))
    means = jnp.asarray(rng.normal(size=(108,)).astype(np.float32))
    ref = np.asarray(_unfused(filters, means, 0.25, 13, 14)(imgs))
    got = np.asarray(
        FusedConvFeaturizer(
            filters, whitener_means=means, pool_stride=13, pool_size=14,
            alpha=0.25,  # default bf16 activations
        )(imgs)
    )
    # bf16 storage rounds each activation once (~2^-8 relative); pooled sums
    # of 196 activations stay within ~1% of the f32 chain.
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert err < 1e-2, err


def test_fused_no_normalization_no_means(rng):
    imgs = jnp.asarray(rng.uniform(0, 1, (3, 16, 16, 2)).astype(np.float32))
    filters = jnp.asarray(rng.normal(size=(5, 4, 4, 2)).astype(np.float32))
    ref = np.asarray(
        Pipeline(
            [
                Convolver(filters, normalize_patches=False, img_channels=2),
                SymmetricRectifier(alpha=0.1),
                Pooler(4, 4, None, "sum"),
                ImageVectorizer(),
            ]
        )(imgs)
    )
    got = np.asarray(
        FusedConvFeaturizer(
            filters, pool_stride=4, pool_size=4, alpha=0.1,
            normalize_patches=False, activation_dtype=jnp.float32,
        )(imgs)
    )
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5 * np.abs(ref).max())


# -- the kernel form (Pallas interpreter here; Mosaic in chip_smoke leg C) -----


@pytest.mark.parametrize(
    "n,h,w,fsz,ws,stride,size,alpha,means,normalize",
    [
        # the RandomPatchCifar geometry, N under one image block
        (5, 32, 32, 24, 6, 13, 14, 0.25, True, True),
        # N over one block and not a multiple of it; F over one filter tile
        # and not a multiple of a lane tile
        (_IMAGES_PER_STEP + 3, 32, 32, 300, 6, 13, 14, 0.25, True, True),
        # alpha 0, uneven dims, truncated edge pools, many pools
        (3, 20, 24, 7, 5, 4, 6, 0.0, True, True),
        # no whitener means
        (4, 16, 16, 130, 3, 5, 5, 0.25, False, True),
        # no patch normalization, odd pool size
        (4, 16, 16, 9, 3, 5, 5, 0.25, True, False),
        (2, 16, 16, 5, 4, 4, 4, 0.0, False, False),
    ],
)
def test_kernel_form_matches_xla_form(
    rng, n, h, w, fsz, ws, stride, size, alpha, means, normalize
):
    """Same mathematics, two forms.  The kernel form rounds patches and
    filters to bf16 for its one MXU pass (as every TPU product does); on
    the CPU the XLA form's products are exact, so the two differ by that
    rounding and no more."""
    lo, hi = (0, 255) if normalize else (0, 1)
    imgs = jnp.asarray(rng.uniform(lo, hi, (n, h, w, 3)).astype(np.float32))
    filters = jnp.asarray(rng.normal(size=(fsz, ws, ws, 3)).astype(np.float32))
    node_ = FusedConvFeaturizer(
        filters,
        whitener_means=(
            jnp.asarray(rng.normal(size=(ws * ws * 3,)).astype(np.float32))
            if means else None
        ),
        pool_stride=stride, pool_size=size, alpha=alpha,
        normalize_patches=normalize, activation_dtype=jnp.float32,
    )
    ref = np.asarray(node_._xla_form(imgs))
    got = np.asarray(node_._kernel_form(imgs, interpret=True))
    assert got.shape == ref.shape
    assert np.sqrt(np.mean((got - ref) ** 2)) < 3e-3 * np.sqrt(np.mean(ref**2))
    np.testing.assert_allclose(got, ref, rtol=0, atol=4e-3 * np.abs(ref).max())


CIFAR_STREAMS = dict(positions=27 * 27, d=108)


@pytest.mark.parametrize(
    "backend,num_filters,split_axes,form",
    [
        ("tpu", 1250, (), "kernel"),
        # ROOFLINE.md's widths keep the XLA form
        ("tpu", 100, (), "xla"),
        ("tpu", 16, (), "xla"),
        # rows split over the data axis alone: the kernel form, a chip its rows
        ("tpu", 10000, ("data",), "kernel"),
        ("tpu", 176, ("data",), "kernel"),
        ("tpu", 100, ("data",), "xla"),
        # a model-axis split, a spread with no named mesh, or no TPU: the
        # XLA form at any width
        ("tpu", 1250, ("data", "model"), "xla"),
        ("tpu", 10000, ("model",), "xla"),
        ("tpu", 1250, ("?",), "xla"),
        ("cpu", 1250, (), "xla"),
        ("cpu", 10000, ("data",), "xla"),
        ("gpu", 5000, (), "xla"),
    ],
)
def test_conv_form_rule(backend, num_filters, split_axes, form):
    """Which form for which (backend, shape, placement): the kernel form on
    a TPU, on one device or on rows split over the data axis, where the
    activation stream dwarfs the patch stream."""
    got = conv_form(
        backend, num_filters=num_filters, split_axes=split_axes, **CIFAR_STREAMS
    )
    assert got == form


def test_conv_form_has_one_threshold():
    """Monotone in the filter count, on one device as on a data mesh."""
    for axes in ((), ("data",)):
        forms = [
            conv_form("tpu", num_filters=f, split_axes=axes, **CIFAR_STREAMS)
            for f in range(16, 2049, 16)
        ]
        assert forms == sorted(forms, reverse=True)  # "xla"... then "kernel"...
        assert forms.index("kernel") == 176 // 16 - 1  # ~170 filters


def test_node_sees_where_its_input_lives(mesh8, mesh42):
    """What the node can see of where its input lives, concrete and traced."""
    from keystone_tpu.ops.conv_fused import _on_one_device, _split_axes
    from keystone_tpu.parallel.mesh import row_sharding

    seen = []
    imgs = jnp.zeros((8, 12, 12, 3), jnp.float32)
    probe = jax.jit(lambda x: seen.append((_on_one_device(x), _split_axes(x))) or x)
    probe(imgs)
    probe(jax.device_put(imgs, row_sharding(mesh8)))
    probe(jax.device_put(imgs, row_sharding(mesh42)))
    assert seen == [(True, ()), (False, ("data",)), (False, ("data", "model"))]
    assert _on_one_device(imgs) and _on_one_device(np.zeros((2, 12, 12, 3)))
    assert not _on_one_device(jax.device_put(imgs, row_sharding(mesh8)))
    assert _split_axes(jax.device_put(imgs, row_sharding(mesh8))) == ("data",)
    assert _split_axes(jax.device_put(imgs, row_sharding(mesh42))) == ("data", "model")


@pytest.mark.parametrize("images", [64, 40])
def test_sharded_kernel_form_equals_one_device(rng, devices, images):
    """The kernel form under ``shard_map`` over a 4-way data axis (Pallas in
    the interpreter) against the one-device kernel form, row for row; 40
    images leave every chip's 10 short of the kernel's block of 16."""
    from keystone_tpu.parallel.mesh import make_mesh, row_sharding

    mesh = make_mesh(data=4, model=1, devices=devices[:4])
    node_ = FusedConvFeaturizer(
        jnp.asarray(rng.normal(size=(200, 6, 6, 3)).astype(np.float32)),
        whitener_means=jnp.asarray(rng.normal(size=(108,)).astype(np.float32)),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    imgs = rng.uniform(0, 255, (images, 32, 32, 3)).astype(np.float32)
    one = np.asarray(node_._kernel_form(jnp.asarray(imgs), interpret=True))
    sharded = node_._sharded_kernel_form(
        jax.device_put(imgs, row_sharding(mesh)), mesh, interpret=True
    )
    assert sharded.sharding.spec == row_sharding(mesh).spec
    assert {s.data.shape for s in sharded.addressable_shards} == {(images // 4, 1600)}
    np.testing.assert_array_equal(np.asarray(sharded), one)
    # and traced, the mesh taken from the input's type as __call__ takes it
    from keystone_tpu.ops.conv_fused import _input_mesh

    traced = jax.jit(
        lambda nd, b: nd._sharded_kernel_form(b, _input_mesh(b), interpret=True)
    )(node_, jax.device_put(imgs, row_sharding(mesh)))
    np.testing.assert_allclose(
        np.asarray(traced), one, rtol=0, atol=2e-3 * np.abs(one).max()
    )


def test_conv_form_counter_moves(rng):
    """``conv_form.<form>`` counts a traced program, not its calls, and the
    shapes ride an instant on the timeline."""
    node_ = FusedConvFeaturizer(
        jnp.asarray(rng.normal(size=(8, 6, 6, 3)).astype(np.float32)),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    imgs = jnp.asarray(rng.uniform(0, 255, (3, 32, 32, 3)).astype(np.float32))
    before = trace.metrics.get("conv_form.xla")
    kernel_before = trace.metrics.get("conv_form.kernel")
    fn = jax.jit(node_.__call__)
    fn(imgs)
    fn(imgs)
    assert trace.metrics.get("conv_form.xla") == before + 1
    assert trace.metrics.get("conv_form.kernel") == kernel_before
    last = [e for e in trace.flight_events() if e["name"] == "conv_form"][-1]
    assert last["args"] == {
        "form": "xla", "images": 3, "positions": 729, "filters": 8, "shards": 1,
    }


def test_conv_form_kernel_counts_on_a_data_mesh(rng, devices, monkeypatch):
    """What the mesh cell's program does on the chip, walked through here:
    with the backend reading ``tpu`` (Pallas in the interpreter), a chunk
    committed to a 4-way data mesh takes the kernel form under ``shard_map``,
    counted as ``conv_form.kernel`` with its shards on the instant; the same
    chunk on a 2 x 2 mesh keeps the XLA form."""
    from jax.experimental.pallas import tpu as pltpu

    from keystone_tpu.parallel.mesh import make_mesh, row_sharding

    node_ = FusedConvFeaturizer(
        jnp.asarray(rng.normal(size=(192, 6, 6, 3)).astype(np.float32)),
        whitener_means=jnp.asarray(rng.normal(size=(108,)).astype(np.float32)),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    imgs = rng.uniform(0, 255, (32, 32, 32, 3)).astype(np.float32)
    want = np.asarray(node_._xla_form(jnp.asarray(imgs)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    counts = {f: trace.metrics.get(f"conv_form.{f}") for f in ("kernel", "xla")}
    for (data, model), form, shards in (((4, 1), "kernel", 4), ((2, 2), "xla", 1)):
        mesh = make_mesh(data=data, model=model, devices=devices[:4])
        with pltpu.force_tpu_interpret_mode():
            got = jax.jit(FusedConvFeaturizer.__call__)(
                node_, jax.device_put(imgs, row_sharding(mesh))
            )
        counts[form] += 1
        assert trace.metrics.get(f"conv_form.{form}") == counts[form]
        last = [e for e in trace.flight_events() if e["name"] == "conv_form"][-1]
        assert last["args"] == {
            "form": form, "images": 32, "positions": 729, "filters": 192,
            "shards": shards,
        }
        assert len(got.sharding.device_set) == 4
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=0, atol=1e-2 * np.abs(want).max()
        )
