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


def test_conv_form_rule(mesh8):
    """Which form for which (backend, shape, placement): the kernel form on
    one TPU device where the activation stream dwarfs the patch stream."""
    cifar = dict(positions=27 * 27, d=108)
    assert conv_form("tpu", num_filters=1250, one_device=True, **cifar) == "kernel"
    # ROOFLINE.md's widths keep the XLA form
    assert conv_form("tpu", num_filters=100, one_device=True, **cifar) == "xla"
    assert conv_form("tpu", num_filters=16, one_device=True, **cifar) == "xla"
    # a mesh, or no TPU: the XLA form at any width
    assert conv_form("tpu", num_filters=1250, one_device=False, **cifar) == "xla"
    assert conv_form("cpu", num_filters=1250, one_device=True, **cifar) == "xla"
    assert conv_form("gpu", num_filters=5000, one_device=True, **cifar) == "xla"
    # monotone in the filter count: one threshold
    forms = [
        conv_form("tpu", num_filters=f, one_device=True, **cifar)
        for f in range(16, 2049, 16)
    ]
    assert forms == sorted(forms, reverse=True)  # "xla"... then "kernel"...

    # what the node can see of where its input lives
    from keystone_tpu.ops.conv_fused import _on_one_device
    from keystone_tpu.parallel.mesh import row_sharding

    seen = []
    imgs = jnp.zeros((8, 12, 12, 3), jnp.float32)
    probe = jax.jit(lambda x: seen.append(_on_one_device(x)) or x)
    probe(imgs)
    probe(jax.device_put(imgs, row_sharding(mesh8)))
    assert seen == [True, False]
    assert _on_one_device(imgs) and _on_one_device(np.zeros((2, 12, 12, 3)))
    assert not _on_one_device(jax.device_put(imgs, row_sharding(mesh8)))


def test_conv_form_counter_moves(rng):
    """``conv_form.<form>`` counts a traced program, not its calls, and the
    shapes ride an instant on the timeline."""
    node_ = FusedConvFeaturizer(
        jnp.asarray(rng.normal(size=(8, 6, 6, 3)).astype(np.float32)),
        pool_stride=13, pool_size=14, alpha=0.25,
    )
    imgs = jnp.asarray(rng.uniform(0, 255, (3, 32, 32, 3)).astype(np.float32))
    before = trace.metrics.get("conv_form.xla")
    fn = jax.jit(node_.__call__)
    fn(imgs)
    fn(imgs)
    assert trace.metrics.get("conv_form.xla") == before + 1
    assert trace.metrics.get("conv_form.kernel") == 0
    last = [e for e in trace.flight_events() if e["name"] == "conv_form"][-1]
    assert last["args"] == {
        "form": "xla", "images": 3, "positions": 729, "filters": 8,
    }
