"""TIMIT loader + pipeline e2e on synthetic separable phone data."""

import numpy as np

from keystone_tpu.loaders.timit import timit_features_loader
from keystone_tpu.workloads.timit import TimitConfig, run


def write_split(tmp_path, name, n, rng, centers):
    k, d = centers.shape
    labels = rng.integers(0, k, n)
    data = centers[labels] + 0.4 * rng.normal(size=(n, d))
    data_path = tmp_path / f"{name}.csv"
    labels_path = tmp_path / f"{name}.labels"
    np.savetxt(data_path, data, delimiter=",", fmt="%.5f")
    with open(labels_path, "w") as fh:
        for i, l in enumerate(labels):
            fh.write(f"{i + 1} {l + 1}\n")  # 1-indexed rows and labels
    return str(data_path), str(labels_path), labels


class TestTimitLoader:
    def test_roundtrip(self, tmp_path, rng):
        centers = rng.normal(size=(5, 8))
        dp, lp, labels = write_split(tmp_path, "train", 20, rng, centers)
        data = timit_features_loader(dp, lp, dp, lp)
        assert data.train.data.shape == (20, 8)
        np.testing.assert_array_equal(data.train.labels, labels)


class TestTimitPipelineE2E:
    def test_learns_synthetic_phones(self, tmp_path, rng):
        d, k = 24, 6
        centers = rng.normal(scale=2.0, size=(k, d))
        tdp, tlp, _ = write_split(tmp_path, "train", 300, rng, centers)
        sdp, slp, _ = write_split(tmp_path, "test", 100, rng, centers)
        data = timit_features_loader(tdp, tlp, sdp, slp)
        conf = TimitConfig(
            num_cosines=3,
            num_cosine_features=128,
            num_epochs=2,
            gamma=0.2,
            lam=1e-3,
            num_classes=k,
            dimension=d,
        )
        results = run(conf, data)
        assert results["test_error"] < 10.0, results
        self.check_what_run_hands_back(conf, data, results)

    @staticmethod
    def check_what_run_hands_back(conf, data, results):
        """The fitted model, the stacked chains, the scores the evaluator
        last saw and the fit's report: enough to make the scores again."""
        import jax
        import jax.numpy as jnp

        from keystone_tpu.solvers.block import BlockSource

        model, chains = results["model"], results["featurizers"]
        assert len(model.xs) == conf.num_cosines
        assert chains.nodes[0].W.shape == (conf.num_cosines, conf.num_cosine_features, conf.dimension)
        report = results["fit_report"]
        assert report.chosen == "fused" and report.block_source == "held"
        test = jnp.asarray(data.test.data)
        blocks = list(BlockSource(test, chains))
        again = model(blocks)
        np.testing.assert_allclose(
            np.asarray(results["test_scores"]), np.asarray(again), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(
            np.asarray(results["test_predictions"]), np.asarray(jnp.argmax(again, axis=1))
        )
        per_block = jax.tree.map(lambda a: a[1], chains)
        np.testing.assert_allclose(
            np.asarray(per_block(test)), np.asarray(blocks[1]), rtol=1e-6, atol=1e-6
        )

    def test_featurizers_follow_the_per_block_recipe(self, rng):
        """The stacked chains drawn by one program are the per-block loop's
        draws (``key, sub = split(key)`` a block; the same bits, and values
        an ulp apart at most where the fused program rounds ``normal * gamma``
        otherwise than the eager ops), and the scalers fitted from the
        moments pass are the eager scaler's."""
        import jax
        import jax.numpy as jnp

        from keystone_tpu.ops.stats import CosineRandomFeatures, StandardScaler
        from keystone_tpu.workloads.timit import build_batch_featurizers

        conf = TimitConfig(num_cosines=3, num_cosine_features=16, gamma=0.2, dimension=5, seed=11)
        rows = jnp.asarray(rng.normal(size=(40, 5)), jnp.float32)
        chains = build_batch_featurizers(conf, jnp.pad(rows, ((0, 8), (0, 0))), nvalid=40)
        key = jax.random.PRNGKey(conf.seed)
        for chain in chains:
            key, sub = jax.random.split(key)
            rf = CosineRandomFeatures.create(5, 16, 0.2, sub)
            np.testing.assert_allclose(np.asarray(chain.nodes[0].W), np.asarray(rf.W), rtol=3e-7)
            np.testing.assert_allclose(np.asarray(chain.nodes[0].b), np.asarray(rf.b), rtol=3e-7)
            scaler = StandardScaler().fit(rf(rows))
            np.testing.assert_allclose(np.asarray(chain.nodes[1].mean), np.asarray(scaler.mean), atol=1e-6)
            np.testing.assert_allclose(np.asarray(chain.nodes[1].std), np.asarray(scaler.std), rtol=1e-4)
