"""GMM / Fisher vector tests mirroring the reference criteria
(src/test/scala/utils/external/EncEvalSuite.scala: planted-mixture recovery;
naive-equivalence replaces the FV golden-file test because the reference's
feats.csv fixture is absent from its own test resources)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops.fisher import FisherVector, fisher_vector
from keystone_tpu.solvers.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from keystone_tpu.utils.stats import about_eq


class TestGMM:
    def test_recovers_planted_1d_mixture(self, rng):
        # EncEvalSuite "Compute a GMM from scala" (:42-64): two 1-D gaussians
        n = 10000
        x = rng.normal(-1.0, 0.5, n)
        y = rng.normal(5.0, 1.0, n)
        z = np.concatenate([x, y])[:, None].astype(np.float32)
        rng.shuffle(z)
        gmm = GaussianMixtureModelEstimator(2).fit(jnp.asarray(z))
        means = np.sort(np.asarray(gmm.means).ravel())
        sds = np.sort(np.sqrt(np.asarray(gmm.variances).ravel()))
        assert abs(means[0] - (-1.0)) < 1e-1
        assert abs(means[1] - 5.0) < 1e-1
        assert abs(sds[0] - 0.5) < 1e-1
        assert abs(sds[1] - 1.0) < 1e-1
        assert about_eq(np.asarray(gmm.weights).sum(), 1.0, 1e-5)

    def test_recovers_planted_2d_mixture(self, rng):
        centers = np.array([[0.0, 0.0], [4.0, 4.0], [-4.0, 4.0]])
        samples = np.concatenate(
            [c + 0.5 * rng.normal(size=(2000, 2)) for c in centers]
        ).astype(np.float32)
        rng.shuffle(samples)
        gmm = GaussianMixtureModelEstimator(3).fit(jnp.asarray(samples))
        got = np.sort(np.asarray(gmm.means).T, axis=0)  # [k, d] sorted
        expected = np.sort(centers, axis=0)
        assert np.all(np.abs(got - expected) < 0.2), (got, expected)

    def test_posteriors_sum_to_one(self, rng):
        x = rng.normal(size=(50, 4)).astype(np.float32)
        gmm = GaussianMixtureModelEstimator(5, max_iter=5).fit(jnp.asarray(x))
        q = np.asarray(gmm(jnp.asarray(x)))
        assert q.shape == (50, 5)
        np.testing.assert_allclose(q.sum(axis=1), 1.0, atol=1e-5)

    def test_load_from_csv(self, tmp_path):
        means = np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])  # d=3, k=2
        variances = np.ones((3, 2))
        weights = np.array([0.4, 0.6])
        np.savetxt(tmp_path / "m.csv", means, delimiter=",")
        np.savetxt(tmp_path / "v.csv", variances, delimiter=",")
        np.savetxt(tmp_path / "w.csv", weights[None], delimiter=",")
        gmm = GaussianMixtureModel.load(
            str(tmp_path / "m.csv"), str(tmp_path / "v.csv"), str(tmp_path / "w.csv")
        )
        assert gmm.dim == 3 and gmm.k == 2
        np.testing.assert_allclose(np.asarray(gmm.means), means)


def naive_fisher(x, means, variances, weights):
    """Direct per-descriptor-loop improved-FV (mean+var gradients)."""
    n, d = x.shape
    k = weights.shape[0]
    sigma = np.sqrt(variances)
    # posteriors
    q = np.zeros((n, k))
    for i in range(n):
        logp = np.zeros(k)
        for j in range(k):
            diff = (x[i] - means[:, j]) / sigma[:, j]
            logp[j] = (
                np.log(weights[j])
                - 0.5 * np.sum(diff**2)
                - 0.5 * np.sum(np.log(2 * np.pi * variances[:, j]))
            )
        p = np.exp(logp - logp.max())
        q[i] = p / p.sum()
    g_mean = np.zeros((d, k))
    g_var = np.zeros((d, k))
    for j in range(k):
        for i in range(n):
            u = (x[i] - means[:, j]) / sigma[:, j]
            g_mean[:, j] += q[i, j] * u
            g_var[:, j] += q[i, j] * (u**2 - 1.0)
        g_mean[:, j] /= n * np.sqrt(weights[j])
        g_var[:, j] /= n * np.sqrt(2.0 * weights[j])
    return np.concatenate([g_mean, g_var], axis=1)


class TestFisherVector:
    def _random_gmm(self, rng, d, k):
        means = rng.normal(size=(d, k)).astype(np.float32)
        variances = rng.uniform(0.5, 2.0, (d, k)).astype(np.float32)
        w = rng.uniform(0.5, 1.5, k)
        weights = (w / w.sum()).astype(np.float32)
        return GaussianMixtureModel(means, variances, weights)

    def test_matches_naive(self, rng):
        d, k, n = 6, 4, 30
        gmm = self._random_gmm(rng, d, k)
        x = rng.normal(size=(n, d)).astype(np.float32)
        got = np.asarray(
            fisher_vector(jnp.asarray(x), gmm.means, gmm.variances, gmm.weights)
        )
        expected = naive_fisher(
            x,
            np.asarray(gmm.means),
            np.asarray(gmm.variances),
            np.asarray(gmm.weights),
        )
        assert got.shape == (d, 2 * k)
        assert about_eq(got, expected, 1e-3)

    def test_batched_node_shape_and_layout(self, rng):
        d, k, cols, n_imgs = 5, 3, 20, 4
        gmm = self._random_gmm(rng, d, k)
        batch = rng.normal(size=(n_imgs, d, cols)).astype(np.float32)
        fv = FisherVector(gmm)
        out = np.asarray(fv(jnp.asarray(batch)))
        assert out.shape == (n_imgs, d, 2 * k)
        assert fv.num_features == d * k * 2
        for i in range(n_imgs):
            expected = naive_fisher(
                batch[i].T,
                np.asarray(gmm.means),
                np.asarray(gmm.variances),
                np.asarray(gmm.weights),
            )
            assert about_eq(out[i], expected, 1e-3)

    def test_mask_equals_truncation(self, rng):
        d, k, cols, valid = 5, 3, 20, 12
        gmm = self._random_gmm(rng, d, k)
        mat = rng.normal(size=(d, cols)).astype(np.float32)
        mask = (np.arange(cols) < valid).astype(np.float32)
        fv = FisherVector(gmm)
        with_mask = np.asarray(
            fv(jnp.asarray(mat[None]), jnp.asarray(mask[None]))
        )[0]
        truncated = np.asarray(fv(jnp.asarray(mat[:, :valid][None])))[0]
        assert about_eq(with_mask, truncated, 1e-4)

    def test_descriptors_from_gmm_give_small_fv(self, rng):
        # FV measures deviation from the generative model: sampling from the
        # GMM itself must give a near-zero encoding
        d, k = 4, 2
        means = np.array([[0.0, 5.0]] * d, np.float32)
        variances = np.ones((d, k), np.float32)
        weights = np.array([0.5, 0.5], np.float32)
        comp = rng.integers(0, k, 4000)
        x = (means[:, comp].T + rng.normal(size=(4000, d))).astype(np.float32)
        out = np.asarray(
            fisher_vector(jnp.asarray(x), jnp.asarray(means), jnp.asarray(variances), jnp.asarray(weights))
        )
        assert np.abs(out).max() < 0.1, np.abs(out).max()


class TestFvPallasKernel:
    """The node's kernel form (ops/fv_pallas.py) is the XLA form's
    mathematics, reassociated, at the XLA form's precisions on a TPU: run in
    interpret mode on the CPU test platform; on TPU hardware the same kernel
    compiles via Mosaic (tests/test_tpu_compile.py, chip_smoke.py leg C) and
    ``fv_form`` routes FisherVector to it."""

    def _case(self, rng, n=3, cols=700, d=24, k=8, ragged=True, spread=1.0):
        x = rng.normal(size=(n, cols, d)).astype(np.float32)
        means = (spread * rng.normal(size=(d, k))).astype(np.float32)
        variances = rng.uniform(0.5, 2.0, (d, k)).astype(np.float32)
        weights = rng.dirichlet(np.ones(k)).astype(np.float32)
        counts = None
        if ragged:
            counts = rng.integers(cols // 2, cols + 1, size=n).astype(np.int32)
        return x, counts, means, variances, weights

    @staticmethod
    def _stats(x, counts, means, variances, weights, moment_dtype):
        from keystone_tpu.ops.fv_pallas import fv_stats_jnp

        return fv_stats_jnp(
            jnp.asarray(np.swapaxes(x, 1, 2)),
            None if counts is None else jnp.asarray(counts),
            means, variances, weights, moment_dtype=moment_dtype,
        )

    @staticmethod
    def _gap(got, want):
        return float(np.abs(np.asarray(got) - np.asarray(want)).max() / np.abs(want).max())

    @pytest.mark.parametrize(
        "shape",
        [
            # ragged counts (the first kernel test, PR 21 and before)
            dict(ragged=True, block=256),
            # no counts, and 333 columns are no multiple of the block: the
            # last block's lanes past the array must count for nothing
            dict(cols=333, ragged=False, block=128),
            # the published widths; 1,100 columns leave a last block of 76
            dict(n=2, cols=1100, d=80, k=256, ragged=False, block=512, spread=0.1),
        ],
        ids=["ragged_counts", "unaligned_block", "d80_k256_unaligned"],
    )
    def test_stats_match_xla(self, rng, shape):
        from keystone_tpu.ops.fisher import _fv_from_stats, fisher_vector
        from keystone_tpu.ops.fv_pallas import fv_stats_pallas

        block, case = shape["block"], {k: v for k, v in shape.items() if k != "block"}
        x, counts, means, variances, weights = self._case(rng, **case)
        got = fv_stats_pallas(
            jnp.asarray(np.swapaxes(x, 1, 2)),  # [N, d, cols] descriptor columns
            None if counts is None else jnp.asarray(counts),
            means, variances, weights, block=block, interpret=True,
        )
        # the statistics, against the form whose products round as the
        # kernel's do
        want = self._stats(x, counts, means, variances, weights, jnp.bfloat16)
        for g, w in zip(got, want):
            assert self._gap(g, w) < 1e-3
        # and the Fisher vector against the CPU's float32 XLA form: one
        # bfloat16 rounding of the moment products' operands apart
        n_valid = np.full((x.shape[0],), x.shape[1]) if counts is None else counts
        fv = _fv_from_stats(
            *got, means, variances, weights, jnp.asarray(n_valid, jnp.float32)
        )
        mask = (np.arange(x.shape[1])[None, :] < n_valid[:, None]).astype(np.float32)
        ref = jax.vmap(
            lambda xi, mi: fisher_vector(xi, means, variances, weights, mi)
        )(jnp.asarray(x), jnp.asarray(mask))
        assert self._gap(fv, ref) < 2e-2

    def test_moment_products_round_as_xla_form(self, rng):
        """Soft posteriors (every centre takes many descriptors), so that a
        rounding of the operands shows: the kernel lies an order nearer the
        bfloat16-operand form than the float32 one, and its log-density
        products (s0 never meets a bfloat16) are float32's."""
        from keystone_tpu.ops.fv_pallas import fv_stats_pallas

        x, _, means, _, weights = self._case(
            rng, n=2, cols=700, d=80, k=256, ragged=False, spread=0.1
        )
        variances = rng.uniform(0.9, 1.1, means.shape).astype(np.float32)
        got = fv_stats_pallas(
            jnp.asarray(np.swapaxes(x, 1, 2)), None, means, variances, weights,
            block=256, interpret=True,
        )
        rounded = self._stats(x, None, means, variances, weights, jnp.bfloat16)
        full = self._stats(x, None, means, variances, weights, jnp.float32)
        assert self._gap(got[0], full[0]) < 2e-5

        def rms(a, b):
            a, b = np.asarray(a), np.asarray(b)
            return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b**2)))

        # four seeds read 0.9-7.6e-5 against 2.4e-4-1.2e-3: 16-20x apart
        for g, r, f in zip(got[1:], rounded[1:], full[1:]):
            assert rms(g, r) < min(1.5e-4, rms(g, f) / 8)

    def test_dead_centre_gives_finite_zeros(self, rng):
        """A centre of weight 0 (log 0 = -inf) takes no posterior mass and
        poisons nothing, in the statistics and in the node's kernel form."""
        from keystone_tpu.ops.fv_pallas import fv_stats_pallas

        x, _, means, variances, weights = self._case(rng, cols=300, ragged=False)
        weights[2] = 0.0
        weights /= weights.sum()
        cols_first = jnp.asarray(np.swapaxes(x, 1, 2))
        s0, s1, s2 = fv_stats_pallas(
            cols_first, None, means, variances, weights, block=128, interpret=True
        )
        for s in (s0, s1, s2):
            assert np.isfinite(np.asarray(s)).all()
        assert np.all(np.asarray(s0)[:, 2] == 0) and np.all(np.asarray(s1)[:, :, 2] == 0)
        node_ = FisherVector(GaussianMixtureModel(means, variances, weights))
        fv = np.asarray(node_._kernel_form(cols_first, interpret=True))
        assert np.isfinite(fv).all()
        assert np.all(fv[:, :, [2, 8 + 2]] == 0)
        np.testing.assert_allclose(
            fv, np.asarray(node_._xla_form(cols_first)), atol=2e-2 * np.abs(fv).max()
        )

    @pytest.mark.parametrize(
        "backend,split,masked,images,shards,want",
        [
            # VOC's cell, a served request
            pytest.param("tpu", (), False, 64, 1, "kernel", id="tpu-True-False-kernel"),
            # a chip's images under shard_map
            pytest.param("tpu", ("data",), False, 256, 4, "kernel", id="tpu-data-False-256-4-kernel"),
            # no equal shares to map
            pytest.param("tpu", ("data",), False, 250, 4, "xla", id="tpu-data-False-250-4-xla"),
            # a model split: the kernel is not partitioned over it
            pytest.param("tpu", ("data", "model"), False, 256, 4, "xla", id="tpu-False-False-xla"),
            # spread over no named mesh
            pytest.param("tpu", ("?",), False, 256, 1, "xla", id="tpu-spread-False-256-1-xla"),
            # the kernel knows prefix counts only
            pytest.param("tpu", (), True, 64, 1, "xla", id="tpu-True-True-xla"),
            # every tier-1 test
            pytest.param("cpu", (), False, 64, 1, "xla", id="cpu-True-False-xla"),
            pytest.param("gpu", (), False, 64, 1, "xla", id="gpu-True-False-xla"),
        ],
    )
    def test_fv_form_rule(self, backend, split, masked, images, shards, want):
        """Which form for which (backend, split, mask); no width enters
        (ops/fisher.py's table: the XLA form won at none on the chip)."""
        from keystone_tpu.ops.fisher import fv_form

        assert fv_form(backend, split, masked, images, shards) == want

    def test_fv_form_sees_mesh_and_mask(self, rng, mesh8):
        """What the node hands the rule: it sees a sharded input and a mask,
        counts each eager call under ``fv_form.xla`` (a CPU never picks the
        kernel), and all three give the same Fisher vectors."""
        from keystone_tpu.core import trace
        from keystone_tpu.ops.conv_fused import _on_one_device
        from keystone_tpu.parallel.mesh import row_sharding

        x, _, means, variances, weights = self._case(rng, n=8, cols=40, ragged=False)
        node_ = FisherVector(GaussianMixtureModel(means, variances, weights))
        descs = jnp.asarray(np.swapaxes(x, 1, 2))
        sharded = jax.device_put(descs, row_sharding(mesh8))
        assert _on_one_device(descs) and not _on_one_device(sharded)
        before = trace.metrics.get("fv_form.xla")
        kernel_before = trace.metrics.get("fv_form.kernel")
        alone = np.asarray(node_(descs))
        np.testing.assert_allclose(np.asarray(node_(sharded)), alone, atol=1e-5)
        masked = np.asarray(node_(descs, mask=jnp.ones(descs.shape[::2], jnp.float32)))
        np.testing.assert_allclose(masked, alone, atol=1e-5)
        assert trace.metrics.get("fv_form.xla") == before + 3
        assert trace.metrics.get("fv_form.kernel") == kernel_before

    def test_sharded_kernel_form_equals_one_device(self, rng, devices):
        """The statistics' kernel under ``shard_map`` over a 4-way data axis,
        the mixture replicated (Pallas in the interpreter), against the
        one-device kernel form, row for row."""
        from keystone_tpu.parallel.mesh import make_mesh, row_sharding

        mesh = make_mesh(data=4, model=1, devices=devices[:4])
        x, _, means, variances, weights = self._case(rng, n=8, cols=300, ragged=False)
        node_ = FisherVector(GaussianMixtureModel(means, variances, weights))
        descs = jnp.asarray(np.swapaxes(x, 1, 2))
        one = np.asarray(node_._kernel_form(descs, interpret=True))
        sharded = jax.jit(lambda b: node_._sharded_kernel_form(b, mesh, interpret=True))(
            jax.device_put(descs, row_sharding(mesh))
        )
        assert sharded.sharding.spec == row_sharding(mesh).spec
        assert {s.data.shape for s in sharded.addressable_shards} == {(2, 24, 16)}
        # a ulp apart where the gradients' assembly fuses otherwise under jit
        np.testing.assert_allclose(np.asarray(sharded), one, rtol=0, atol=1e-6 * np.abs(one).max())

    @pytest.mark.parametrize(
        "layout,form,shards", [((4, 1), "kernel", 4), ((2, 2), "xla", 1)], ids=["data", "model"]
    )
    def test_form_follows_the_split(self, rng, devices, monkeypatch, layout, form, shards):
        """What ``__call__`` does under a mesh, the rule asked as on a TPU:
        a data split takes the kernel under ``shard_map``, a model split the
        XLA form; the counter and the instant say which."""
        from keystone_tpu.ops import fisher
        from keystone_tpu.parallel.mesh import make_mesh, row_sharding

        rule = fisher.fv_form
        monkeypatch.setattr(fisher, "fv_form", lambda _b, *a: rule("tpu", *a))
        kernel = FisherVector._kernel_form
        monkeypatch.setattr(
            FisherVector, "_kernel_form", lambda self, b, interpret=True: kernel(self, b, True)
        )
        mesh = make_mesh(*layout, devices=devices[:4])
        x, _, means, variances, weights = self._case(rng, n=8, cols=300, ragged=False)
        node_ = FisherVector(GaussianMixtureModel(means, variances, weights))
        descs = jnp.asarray(np.swapaxes(x, 1, 2))
        before = trace_metrics_get(f"fv_form.{form}")
        got = np.asarray(jax.jit(node_.__call__)(jax.device_put(descs, row_sharding(mesh))))
        assert trace_metrics_get(f"fv_form.{form}") == before + 1
        from keystone_tpu.core import trace

        last = [e for e in trace.flight_events() if e["name"] == "fv_form"][-1]["args"]
        assert (last["form"], last["shards"]) == (form, shards)
        assert last["split"] == ("data" if layout[1] == 1 else "data,model")
        want = kernel(node_, descs, True) if form == "kernel" else node_._xla_form(descs)
        # a ulp apart where the gradients' assembly fuses otherwise under jit;
        # GSPMD's partitioned sums of the XLA form run in another order
        atol = (1e-6 if form == "kernel" else 1e-5) * np.abs(np.asarray(want)).max()
        np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=atol)

    def test_fv_form_counter_moves(self, rng):
        """``fv_form.<form>`` counts a traced program, not its calls, and the
        shapes ride an instant on the timeline."""
        from keystone_tpu.core import trace

        x, _, means, variances, weights = self._case(rng, cols=40, ragged=False)
        node_ = FisherVector(GaussianMixtureModel(means, variances, weights))
        descs = jnp.asarray(np.swapaxes(x, 1, 2))
        before = trace.metrics.get("fv_form.xla")
        kernel_before = trace.metrics.get("fv_form.kernel")
        fn = jax.jit(node_.__call__)
        fn(descs)
        fn(descs)
        assert trace.metrics.get("fv_form.xla") == before + 1
        assert trace.metrics.get("fv_form.kernel") == kernel_before
        last = [e for e in trace.flight_events() if e["name"] == "fv_form"][-1]
        assert last["args"] == {
            "form": "xla", "images": 3, "cols": 40, "d": 24, "k": 8, "shards": 1, "split": "",
        }


def trace_metrics_get(name: str) -> float:
    from keystone_tpu.core import trace

    return trace.metrics.get(name)
