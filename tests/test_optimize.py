"""Cost-based optimizer tests (core.optimize): the auto-Cacher decision
table on synthetic profiles (cache/no-cache boundary, budget-denied ->
cheapest wins dropped first, reuse=1 never cached), memoizing-Cacher
pipeline semantics (one recompute saved, bit-identical outputs, test
inputs untouched), StreamConfig env seeding / live mutation, and the
closed-loop ingest autotuner converging on a stall-injected synthetic
stream with bit-equal output."""

import io
import json
import tarfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import faults

from keystone_tpu.core import ingest, optimize
from keystone_tpu.core import memory as kmem
from keystone_tpu.core.pipeline import (
    Cacher,
    ChainedEstimator,
    Estimator,
    FunctionTransformer,
    Pipeline,
    PipelineProfile,
    track_reuse,
)
from keystone_tpu.loaders import image_loaders


def cand(name, seconds, nbytes, reuse, index=0):
    return optimize.CacheCandidate(
        index=index, name=name, seconds=seconds, output_bytes=nbytes,
        reuse=reuse,
    )


@pytest.fixture
def no_budget(monkeypatch):
    monkeypatch.delenv(kmem.HBM_BUDGET_ENV, raising=False)


# -- decision table on synthetic profiles -------------------------------------


class TestPlanCaches:
    def test_reuse_one_never_cached(self, no_budget):
        plan = optimize.plan_caches([cand("expensive", 100.0, 1024, reuse=1)])
        d = plan.decisions[0]
        assert not d.cached
        assert "reuse" in d.reason
        assert plan.cached_bytes == 0

    def test_cache_no_cache_boundary(self, no_budget):
        # gbps=1.0: 1 GiB costs 1 s amortized.  win = seconds * (reuse-1).
        gib = 2**30
        plan = optimize.plan_caches(
            [
                cand("worth_it", seconds=2.0, nbytes=gib, reuse=2, index=0),
                cand("not_worth_it", seconds=0.5, nbytes=gib, reuse=2, index=1),
            ],
            gbps=1.0,
        )
        worth, not_worth = plan.decisions
        assert worth.cached and worth.win_seconds == pytest.approx(2.0)
        assert not not_worth.cached
        assert "amortized" in not_worth.reason

    def test_budget_denied_drops_cheapest_win_first(self, monkeypatch):
        # Budget admits ~1.5 MB of cache (3M * 0.5 headroom): only the
        # bigger win fits; the cheaper one is dropped and the denial
        # recorded — never an over-budget cache.
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(3 * 2**20))
        mb = 2**20
        plan = optimize.plan_caches(
            [
                cand("small_win", seconds=10.0, nbytes=mb, reuse=2, index=0),
                cand("big_win", seconds=100.0, nbytes=mb, reuse=2, index=1),
            ],
            gbps=1.0,
        )
        by_name = {d.name: d for d in plan.decisions}
        assert by_name["big_win"].cached
        assert not by_name["small_win"].cached
        assert plan.dropped == ["small_win"]
        assert plan.denials == ["small_win"]
        assert plan.cached_bytes == mb

    def test_oversized_win_does_not_abandon_smaller_fits(self, monkeypatch):
        # Greedy knapsack, not first-failure abort: a biggest-win cache
        # over budget is dropped, but a smaller one that fits is kept.
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, str(4 * 2**20))
        plan = optimize.plan_caches(
            [
                cand("small_fits", seconds=10.0, nbytes=2**20, reuse=2, index=0),
                cand("huge_win", seconds=1000.0, nbytes=2**30, reuse=2, index=1),
            ],
            gbps=1000.0,  # both pass the inequality
        )
        by_name = {d.name: d for d in plan.decisions}
        assert not by_name["huge_win"].cached
        assert by_name["small_fits"].cached
        assert plan.dropped == ["huge_win"]
        assert plan.cached_bytes == 2**20

    def test_budget_denial_is_counted(self, monkeypatch):
        from keystone_tpu.core.resilience import counters

        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1K")
        before = counters.get("cache_admission_denied")
        plan = optimize.plan_caches([cand("x", 100.0, 2**20, reuse=3)])
        assert not plan.decisions[0].cached
        assert counters.get("cache_admission_denied") == before + 1

    def test_no_budget_admits_eligible(self, no_budget):
        plan = optimize.plan_caches([cand("x", 100.0, 2**20, reuse=3)])
        assert plan.decisions[0].cached
        assert plan.cached_bytes == 2**20

    def test_reuse_scales_the_win(self, no_budget):
        # reuse=3 doubles the win of reuse=2 — the KeystoneML inequality
        # counts SAVED recomputes, not touches.
        p2 = optimize.plan_caches([cand("x", 1.0, 0, reuse=2)])
        p3 = optimize.plan_caches([cand("x", 1.0, 0, reuse=3)])
        assert p3.decisions[0].win_seconds == pytest.approx(
            2 * p2.decisions[0].win_seconds
        )

    def test_to_json_round_trips(self, no_budget):
        plan = optimize.plan_caches(
            [cand("a", 5.0, 1024, reuse=2), cand("b", 0.0, 9, reuse=1)],
            dataset_rows=1000,
            sample_rows=10,
        )
        doc = json.loads(plan.to_json())
        assert doc["cached"] == ["a"]
        assert doc["dataset_rows"] == 1000
        assert len(doc["decisions"]) == 2
        assert all("reason" in d for d in doc["decisions"])


def test_pipeline_profile_to_json_round_trips():
    pipe = Pipeline([
        FunctionTransformer(lambda x: x * 2, name="double"),
        FunctionTransformer(lambda x: x + 1, name="inc"),
    ])
    prof = pipe.profile(jnp.ones((4, 3), jnp.float32))
    back = PipelineProfile.from_json(prof.to_json())
    assert [n.name for n in back.nodes] == ["double", "inc"]
    assert back.nodes[0].output_bytes == prof.nodes[0].output_bytes
    assert back.input_bytes == prof.input_bytes
    # embeddable: the JSON parses as one document
    assert json.loads(prof.to_json())["nodes"][1]["name"] == "inc"


# -- reuse tracking and the memoizing Cacher ----------------------------------


class _MeanCenter(Estimator):
    def fit(self, data):
        m = float(np.asarray(data).mean())
        return FunctionTransformer(lambda x, m=m: x - m, name="center")


def _counting_node(calls, name="expensive"):
    def fn(x):
        calls[name] = calls.get(name, 0) + 1
        return x * 2.0

    return FunctionTransformer(fn, name=name)


class TestReuseAndMemo:
    def test_track_reuse_counts_chained_fit_pattern(self):
        calls = {}
        node = _counting_node(calls)
        chain = node.then_estimator(_MeanCenter())
        x = np.ones((8, 4), np.float32)
        with track_reuse() as counts:
            fitted = chain.fit(x)
            fitted(x)
        # fit pushes through the xform once, the fitted apply again
        assert counts[id(node)] == 2

    def test_measure_chain_reuse(self):
        calls = {}
        node = _counting_node(calls)
        chain = node.then_estimator(_MeanCenter())
        reuse = optimize.measure_chain_reuse(chain, np.ones((4, 2), np.float32))
        assert reuse == {0: 2}

    def test_memoizing_cacher_saves_the_recompute(self):
        calls = {}
        pipe = Pipeline([
            _counting_node(calls),
            Cacher(name="auto", memoize=True),
            FunctionTransformer(lambda x: x + 1.0, name="inc"),
        ])
        x = np.ones((4, 2), np.float32)
        out1 = pipe(x)
        out2 = pipe(x)  # same object -> memo hit, no recompute
        assert calls["expensive"] == 1
        assert np.array_equal(np.asarray(out1), np.asarray(out2))

    def test_memo_is_keyed_on_input_identity(self):
        calls = {}
        pipe = Pipeline([
            _counting_node(calls), Cacher(name="auto", memoize=True),
        ])
        a = np.ones((4, 2), np.float32)
        b = np.ones((4, 2), np.float32)  # equal VALUES, different object
        out_a = pipe(a)
        out_b = pipe(b)  # must recompute: identity, not value, is the key
        assert calls["expensive"] == 2
        assert np.array_equal(np.asarray(out_a), np.asarray(out_b))
        # ...and the second input did not evict the armed entry
        pipe(a)
        assert calls["expensive"] == 2

    def test_clear_memo_releases_the_entry(self):
        calls = {}
        cacher = Cacher(name="auto", memoize=True)
        pipe = Pipeline([_counting_node(calls), cacher])
        x = np.ones((2, 2), np.float32)
        pipe(x)
        optimize.release_caches(pipe)
        pipe(x)
        assert calls["expensive"] == 2

    def test_memoizing_cacher_is_inert_under_jit(self):
        pipe = Pipeline([
            FunctionTransformer(lambda x: x * 2.0, name="double"),
            Cacher(name="auto", memoize=True),
        ])
        out = jax.jit(pipe.__call__)(jnp.ones((2, 2), jnp.float32))
        assert np.allclose(np.asarray(out), 2.0)

    def test_non_memoizing_cacher_unchanged(self):
        # The pre-existing Cacher contract: a pure materialization barrier.
        pipe = Pipeline([FunctionTransformer(lambda x: x + 1, name="inc"), Cacher()])
        x = jnp.ones((2, 2), jnp.float32)
        assert np.allclose(np.asarray(pipe(x)), 2.0)
        assert pipe._memo_cachers == ()


class TestAutoCacheChain:
    def test_cached_chain_computes_once_and_matches(self, no_budget):
        calls = {}
        chain = _counting_node(calls).then_estimator(_MeanCenter())
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        fitted_u = chain.fit(x)
        out_u = fitted_u(x)
        assert calls["expensive"] == 2  # the uncached fit pattern

        calls.clear()
        opt, plan = optimize.auto_cache_chain(
            _counting_node(calls).then_estimator(_MeanCenter()),
            x[:4], dataset_rows=16,
        )
        assert [d.name for d in plan.cached()] == ["expensive"]
        calls.clear()
        fitted_c = opt.fit(x)
        out_c = fitted_c(x)
        assert calls["expensive"] == 1  # the Cacher replayed the fit value
        assert np.array_equal(np.asarray(out_u), np.asarray(out_c))
        # a DIFFERENT input (the test split) computes normally
        y = x + 1.0
        calls.clear()
        fitted_c(y)
        assert calls["expensive"] == 1

    def test_budget_denied_chain_is_uncached_but_equal(self, monkeypatch):
        calls = {}
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        fitted_u = (
            _counting_node(calls).then_estimator(_MeanCenter()).fit(x)
        )
        out_u = fitted_u(x)
        monkeypatch.setenv(kmem.HBM_BUDGET_ENV, "1")
        calls.clear()
        opt, plan = optimize.auto_cache_chain(
            _counting_node(calls).then_estimator(_MeanCenter()),
            x[:4], dataset_rows=16,
        )
        assert plan.cached() == [] and plan.dropped == ["expensive"]
        # no Cacher inserted: node count unchanged
        assert len(opt.xform.nodes) == 1
        calls.clear()
        out_c = opt.fit(x)(x)
        assert calls["expensive"] == 2
        assert np.array_equal(np.asarray(out_u), np.asarray(out_c))


# -- StreamConfig -------------------------------------------------------------


class TestStreamConfig:
    def test_from_env_seeds_the_initial_values(self, monkeypatch):
        monkeypatch.setenv("KEYSTONE_DECODE_THREADS", "3")
        monkeypatch.setenv("KEYSTONE_DECODE_AHEAD", "5")
        monkeypatch.setenv("KEYSTONE_RING_CAPACITY", "7")
        monkeypatch.setenv("KEYSTONE_AUTOTUNE", "1")
        monkeypatch.setenv("KEYSTONE_AUTOTUNE_INTERVAL", "9")
        cfg = ingest.StreamConfig.from_env()
        assert (cfg.decode_threads, cfg.decode_ahead, cfg.ring_capacity) == (3, 5, 7)
        assert cfg.autotune and cfg.autotune_interval == 9
        assert cfg.max_decode_threads >= cfg.decode_threads

    def test_overrides_beat_env(self, monkeypatch):
        monkeypatch.setenv("KEYSTONE_DECODE_THREADS", "3")
        cfg = ingest.StreamConfig.from_env(decode_threads=2, ring_capacity=1)
        assert cfg.decode_threads == 2 and cfg.ring_capacity == 1

    def test_validation(self):
        with pytest.raises(ValueError):
            ingest.StreamConfig(decode_threads=0, decode_ahead=0, ring_capacity=1)
        with pytest.raises(ValueError):
            ingest.StreamConfig(decode_threads=1, decode_ahead=-1, ring_capacity=1)
        with pytest.raises(ValueError):
            ingest.StreamConfig(decode_threads=1, decode_ahead=0, ring_capacity=0)
        # an EXPLICIT tuner cap below the width is a contradiction, never
        # silently widened past the caller's bound
        with pytest.raises(ValueError, match="max_decode_threads"):
            ingest.StreamConfig(
                decode_threads=4, decode_ahead=0, ring_capacity=1,
                max_decode_threads=2,
            )

    def test_legacy_kwargs_are_validated(self, tmp_path, rng):
        path = str(tmp_path / "v.tar")
        _small_tar(path, 2, rng)
        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=0, ring_capacity=2
        )
        with pytest.raises(ValueError):
            ingest.stream_batches(path, 2, config=cfg, num_threads=0)

    def test_legacy_kwargs_override_config(self, tmp_path, rng):
        path = str(tmp_path / "t.tar")
        _small_tar(path, 4, rng)
        cfg = ingest.StreamConfig(
            decode_threads=4, decode_ahead=4, ring_capacity=4
        )
        with ingest.stream_batches(path, 2, config=cfg, num_threads=1, capacity=2) as st:
            list(st)
        assert st.config is cfg
        assert cfg.decode_threads == 1 and cfg.ring_capacity == 2
        assert st.join(10.0)


def _small_tar(path, n, rng, size=48):
    with tarfile.open(path, "w") as tf:
        for i in range(n):
            data = faults.make_jpeg_bytes(rng, size, size)
            info = tarfile.TarInfo(f"img_{i:04d}.jpg")
            info.size = len(data)
            tf.addfile(info, io.BytesIO(data))


def _collect(path, batch, config=None, tuner=None):
    with ingest.stream_batches(path, batch, config=config, tuner=tuner) as st:
        out = [
            (b.indices.copy(), b.host.copy(), list(b.names)) for b in st
        ]
    assert st.join(10.0)
    return out, st


def _streams_equal(a, b):
    return len(a) == len(b) and all(
        np.array_equal(x[0], y[0])
        and np.array_equal(x[1], y[1])
        and x[2] == y[2]
        for x, y in zip(a, b)
    )


# -- the closed-loop autotuner ------------------------------------------------


class TestIngestAutotuner:
    def test_converges_on_a_stall_injected_stream(self, tmp_path, rng, monkeypatch):
        """Decode slowed artificially -> the consumer stalls on an empty
        ring -> the controller must widen decode from its static default,
        and the retuned stream's output must be BIT-EQUAL to the static
        run (typed-or-equal: retuning changes speed, never results)."""
        path = str(tmp_path / "stall.tar")
        _small_tar(path, 24, rng)

        real = image_loaders.decode_image

        def slow(data):
            time.sleep(0.01)  # the injected stall: decode-bound by fiat
            return real(data)

        monkeypatch.setattr(image_loaders, "decode_image", slow)

        static_cfg = ingest.StreamConfig(
            decode_threads=1, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        static, _ = _collect(path, 4, config=static_cfg)

        tuned_cfg = ingest.StreamConfig(
            decode_threads=1, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8, autotune=True, autotune_interval=2,
        )
        tuned, st = _collect(path, 4, config=tuned_cfg)

        rec = st.tuner.record()
        assert rec["retunes"] >= 1, rec
        # at least one knob moved off its static default
        assert tuned_cfg.decode_threads > 1, rec
        assert _streams_equal(static, tuned)

    def test_quiet_stream_is_left_alone(self, tmp_path, rng):
        """No stall signal -> no retune (the controller must not thrash a
        converged pipeline)."""
        path = str(tmp_path / "quiet.tar")
        _small_tar(path, 8, rng)
        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=2, ring_capacity=4,
            autotune=True, autotune_interval=1,
        )
        tuner = optimize.IngestAutotuner()
        with ingest.stream_batches(path, 2, config=cfg, tuner=tuner) as st:
            # The consumer asks for a chunk only once the ring holds what
            # that takes (yielding chunk i first takes chunk i + 1 out of
            # the ring: two device batches in flight), so no get the tuner
            # sees finds the ring empty however slowly a loaded host
            # decodes (a 20 ms sleep a chunk lost that race under six
            # suite workers and the tuner, rightly, widened decode).
            it = iter(st)
            for i in range(4):
                deadline = time.monotonic() + 30.0
                while (
                    st.stats.batches < min(i + 2, 4)
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.002)
                next(it)
            assert next(it, None) is None
        assert st.join(10.0)
        # producer-blocked intervals may deepen the ring / narrow decode,
        # but the decode-bound escalation must not fire
        assert cfg.decode_threads <= 2

    def test_manual_mid_stream_retune_is_bit_equal(self, tmp_path, rng):
        """StreamConfig is a programmatic surface: mutating it mid-stream
        (no tuner at all) must preserve output identity."""
        path = str(tmp_path / "manual.tar")
        _small_tar(path, 12, rng)
        baseline, _ = _collect(path, 3)

        cfg = ingest.StreamConfig(
            decode_threads=1, decode_ahead=0, ring_capacity=1,
            max_decode_threads=4,
        )
        got = []
        with ingest.stream_batches(path, 3, config=cfg) as st:
            for i, b in enumerate(st):
                got.append((b.indices.copy(), b.host.copy(), list(b.names)))
                if i == 1:
                    cfg.decode_threads = 4
                    cfg.decode_ahead = 6
                    cfg.ring_capacity = 8
        assert st.join(10.0)
        assert _streams_equal(baseline, got)

    def test_retunes_land_in_metrics_and_trajectory(self, tmp_path, rng, monkeypatch):
        from keystone_tpu.core import trace

        path = str(tmp_path / "metrics.tar")
        _small_tar(path, 16, rng)
        real = image_loaders.decode_image
        monkeypatch.setattr(
            image_loaders, "decode_image",
            lambda data: (time.sleep(0.01), real(data))[1],
        )
        cfg = ingest.StreamConfig(
            decode_threads=1, decode_ahead=0, ring_capacity=2,
            max_decode_threads=4, autotune=True, autotune_interval=1,
        )
        before = trace.metrics.get("ingest_retunes")
        _, st = _collect(path, 4, config=cfg)
        rec = st.tuner.record()
        assert trace.metrics.get("ingest_retunes") - before == rec["retunes"]
        for entry in rec["trajectory"]:
            assert set(entry) == {
                "chunk", "producer_stalls_delta", "consumer_stalls_delta",
                "changes",
            }
            for knob, (old, new) in entry["changes"].items():
                assert knob in (
                    "decode_threads", "decode_ahead", "ring_capacity",
                    "decode_backend", "decode_procs",
                )
                assert old != new
        assert rec["final_config"] == cfg.record()


class TestBackendPromotion:
    """The autotuner's decode-backend knob (ISSUE 7): a decode-width
    doubling that buys < SCALING_FLOOR (1.3x) chunk throughput while the
    stream stays decode-bound reads as GIL-bound, and promotes the stream
    to the spawned-process backend."""

    def _tuner_on(self, cfg):
        import types

        stats = ingest.StreamStats()
        stream = types.SimpleNamespace(config=cfg, stats=stats)
        tuner = optimize.IngestAutotuner(interval=1)
        tuner.attach(stream)
        clock = {"t": 0.0}
        tuner._now = lambda: clock["t"]

        def tick(dt, consumer_stalls=1, producer_stalls=0):
            stats.consumer_stalls += consumer_stalls
            stats.producer_stalls += producer_stalls
            clock["t"] += dt
            tuner.on_chunk(stream)

        return tuner, tick

    def test_flat_scaling_promotes_to_process(self):
        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        tuner, tick = self._tuner_on(cfg)
        tick(1.0)  # warm-up interval, discarded
        tick(1.0)  # decode-bound at rate 1.0 -> widen 2->4, rate remembered
        assert cfg.decode_threads == 4 and cfg.decode_backend == "thread"
        tick(0.9)  # rate 1.11: a 2x widen bought 1.11x < 1.3x -> GIL-bound
        assert cfg.decode_backend == "process"
        # the pool width follows the TUNED decode width, not the starved
        # initial decode_procs resolution (a 1-worker "parallel" pool
        # would defeat the promotion)
        assert cfg.decode_procs == cfg.decode_threads == 4
        assert any(
            "decode_backend" in e["changes"] for e in tuner.trajectory
        )

    def test_capped_widen_scales_the_promotion_floor(self):
        """A ceiling-capped widen (7->8, ratio 1.14) only promises ~1.04x
        even core-bound — holding it to the full-doubling 1.3x floor would
        misread perfect linear scaling as GIL-bound and promote."""
        cfg = ingest.StreamConfig(
            decode_threads=7, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        _tuner, tick = self._tuner_on(cfg)
        tick(1.0)  # warm-up
        tick(1.0)  # decode-bound at rate 1.0 -> widen 7->8 (NOT a 2x)
        assert cfg.decode_threads == 8 and cfg.decode_backend == "thread"
        tick(0.875)  # rate 8/7: perfect linear scaling for a 7->8 widen
        assert cfg.decode_backend == "thread"  # core-bound, not GIL-bound

    def test_real_scaling_keeps_widening_threads(self):
        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        _tuner, tick = self._tuner_on(cfg)
        tick(1.0)  # warm-up
        tick(1.0)  # widen 2->4 at rate 1.0
        tick(0.4)  # rate 2.5: the widen scaled -> widen again, no promotion
        assert cfg.decode_backend == "thread"
        assert cfg.decode_threads == 8

    def test_promotion_can_be_disallowed(self):
        import types

        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        stats = ingest.StreamStats()
        stream = types.SimpleNamespace(config=cfg, stats=stats)
        tuner = optimize.IngestAutotuner(
            interval=1, allow_backend_switch=False
        )
        tuner.attach(stream)
        clock = {"t": 0.0}
        tuner._now = lambda: clock["t"]
        for dt in (1.0, 1.0, 0.9, 0.9, 0.9):
            stats.consumer_stalls += 1
            clock["t"] += dt
            tuner.on_chunk(stream)
        assert cfg.decode_backend == "thread"

    def test_consumer_bound_interval_resets_the_evidence(self):
        cfg = ingest.StreamConfig(
            decode_threads=2, decode_ahead=0, ring_capacity=2,
            max_decode_threads=8,
        )
        _tuner, tick = self._tuner_on(cfg)
        tick(1.0)  # warm-up
        tick(1.0)  # widen, rate remembered
        tick(1.0, consumer_stalls=0, producer_stalls=1)  # device-bound now
        tick(0.9)  # decode-bound again, but stale evidence was dropped
        assert cfg.decode_backend == "thread"
