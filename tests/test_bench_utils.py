"""Unit tests for bench.py's measurement scaffolding (the parts that guard
the round artifact — no TPU required), and the bench regression
observatory (tools/bench_diff.py) exercised over a generated five-round
history shaped like the driver's r01–r05 records (an early metric, the
headline growing sections, a truncated newest round) so the observatory
itself runs in tier-1 without hardware."""

import json
import os
import sys

import pytest

import bench

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import bench_diff  # noqa: E402  (tools/bench_diff.py)


def test_error_record_shape():
    rec = bench._error_record(ValueError("x" * 500))
    assert rec["error"].startswith("ValueError: ")
    assert len(rec["error"]) <= 300


def test_guarded_returns_error_record_not_exception():
    def boom(_rng):
        raise RuntimeError("chip fell over")

    rec = bench._guarded(boom, None)
    assert rec == {"error": "RuntimeError: chip fell over"}

    def ok(_rng):
        return {"v": 1}

    assert bench._guarded(ok, None) == {"v": 1}


def test_timed_chain_auto_retries_only_noise_floor(monkeypatch):
    calls = []

    def fake_timed_chain(fn, arg, chain_len, repeats=3):
        calls.append(chain_len)
        if chain_len < 64:
            raise bench.NoiseFloorError("too short")
        return 0.001

    monkeypatch.setattr(bench, "timed_chain", fake_timed_chain)
    assert bench.timed_chain_auto(None, None, chain_len=16) == 0.001
    assert calls == [16, 32, 64]  # doubled until the floor cleared


def test_timed_chain_auto_propagates_real_failures(monkeypatch):
    def fake_timed_chain(fn, arg, chain_len, repeats=3):
        raise RuntimeError("XlaRuntimeError: RESOURCE_EXHAUSTED")

    monkeypatch.setattr(bench, "timed_chain", fake_timed_chain)
    try:
        bench.timed_chain_auto(None, None, chain_len=16)
    except RuntimeError as e:
        assert "RESOURCE_EXHAUSTED" in str(e)
    else:
        raise AssertionError("real failure was swallowed")


def test_solve_at_scale_records_fit_report_per_attempt(monkeypatch):
    """Regression for the PR 7 probe fix (bench round r05, 2026-07-30,
    record removed in PR 21, showed raw-OOM rows with no ladder evidence): every probed shape — failures INCLUDED —
    must carry the estimator's own ``last_fit_report`` record in the
    emitted JSON, and (ISSUE 9) the searched ``placement`` table rides in
    it.  Every probe is made to FAIL (injected post-fit OOM, the report
    already populated — the shape a real runtime OOM leaves) so the
    all-attempts-failed worst case is what gets audited."""
    import numpy as np

    class FailingEstimator(bench.BlockLeastSquaresEstimator):
        def fit(self, *args, **kwargs):
            super().fit(*args, **kwargs)
            raise RuntimeError("RESOURCE_EXHAUSTED: injected probe failure")

    monkeypatch.setattr(bench, "BlockLeastSquaresEstimator", FailingEstimator)
    monkeypatch.setattr(
        bench, "_bench_bwls_at_scale", lambda rng, shapes=None, bs=4096: {
            "error": "stubbed", "attempts": [],
        },
    )
    out = bench.bench_solve_at_scale(
        np.random.default_rng(0), shapes=[(256, 128), (128, 128)], bs=64
    )
    assert out["error"] == "no probed shape fit"
    assert len(out["attempts"]) == 2
    for att in out["attempts"]:
        rep = att["solver"]
        assert rep is not None, att  # the ladder's evidence, per attempt
        assert "RESOURCE_EXHAUSTED" in att["error"]
        assert rep["placement"] is not None  # the searched plan (ISSUE 9)
        assert rep["placement"]["candidates"]
        assert rep["placement"]["ranking"]
    json.dumps(out)  # the whole probe record must stay JSON-able


# -- the regression observatory (tools/bench_diff.py, ISSUE 11) ---------------


@pytest.fixture
def rounds_dir(tmp_path):
    """Five driver-wrapped round records: r01 an early metric, r02 the bare
    headline, r03/r04 the headline with solve and decode sections (r04
    improved), r05 truncated by the driver (``parsed: null``)."""
    early = {"metric": "mnist_random_fft_featurize", "unit": "examples/sec"}
    head = {"metric": "random_patch_cifar_featurize", "unit": "images/sec/chip"}
    records = {
        1: {**early, "value": 1.0e6, "vs_baseline": 1.0},
        2: {**head, "value": 186858.0, "vs_baseline": 1.0},
        3: {
            **head, "value": 497658.16, "mfu": 0.043, "solve_seconds": 10.8,
            "extra_metrics": {
                "imagenet_fv_featurize": {"value": 4169.53, "mfu": 0.0462},
                "jpeg_decode": {"speedup": 1.03},
            },
        },
        4: {
            **head, "value": 1186580.0, "mfu": 0.103, "solve_seconds": 0.024,
            "extra_metrics": {
                "imagenet_fv_featurize": {"value": 5800.0, "mfu": 0.05},
                "jpeg_decode": {"speedup": 1.04},
            },
        },
        5: None,
    }
    for n, parsed in records.items():
        with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
            json.dump({"n": n, "rc": 0, "tail": "...", "parsed": parsed}, f)
    return tmp_path


def _round(dirpath, n: int) -> str:
    return str(dirpath / f"BENCH_r{n:02d}.json")


def test_bench_diff_truncated_candidate_emits_machine_verdict(
    rounds_dir, capsys
):
    """The ISSUE 11 acceptance pair: a candidate whose driver artifact was
    truncated (``parsed: null``) must yield an INCOMPARABLE verdict as
    machine-readable JSON — naming the problem — instead of crashing."""
    rc = bench_diff.main([_round(rounds_dir, 4), _round(rounds_dir, 5)])
    assert rc == 2
    first_line = capsys.readouterr().out.splitlines()[0]
    record = json.loads(first_line)
    assert record["metric"] == "bench_diff"
    assert record["verdict"] == "incomparable"
    assert record["compared"] == 0
    assert "null" in record["problems"]["cand"]


def test_bench_diff_improved_pair_is_comparable_and_clean(rounds_dir, capsys):
    """An improvement round (featurize 497k -> 1.19M images/sec/chip):
    comparable, no regressions, improvements named."""
    rc = bench_diff.main([_round(rounds_dir, 3), _round(rounds_dir, 4)])
    assert rc == 0
    record = json.loads(capsys.readouterr().out.splitlines()[0])
    assert record["verdict"] == "ok"
    assert record["compared"] >= 3
    assert record["regressions"] == []
    improved = {r["metric"] for r in record["improvements"]}
    assert "value" in improved


def test_bench_diff_every_pair_yields_a_verdict(rounds_dir):
    """The observatory over a whole round history: every consecutive
    pair produces a structurally-valid verdict (the truncated record
    degrades to incomparable, never a crash)."""
    rounds = bench_diff.list_rounds(str(rounds_dir))
    assert [n for n, _ in rounds] == [1, 2, 3, 4, 5]
    for (n_a, p_a), (n_b, p_b) in zip(rounds, rounds[1:]):
        record = bench_diff.diff_files(p_a, p_b)
        assert record["verdict"] in ("ok", "regressed", "incomparable"), (
            n_a, n_b, record,
        )
        json.dumps(record)  # machine-readable throughout
        if n_b == 5:
            assert record["verdict"] == "incomparable"
        else:
            assert record["compared"] >= 1, (n_a, n_b)


def test_bench_diff_detects_regression_and_direction():
    base = {
        "metric": "m", "value": 100.0, "solve_seconds": 1.0,
        "extra_metrics": {"serving": {"mnist_fft": {
            "qps": 50.0, "p99_latency_ms": 10.0,
        }}},
    }
    # value collapsed far past its 15% threshold -> regressed
    worse = json.loads(json.dumps(base))
    worse["value"] = 50.0
    out = bench_diff.compare(base, worse)
    assert out["verdict"] == "regressed"
    assert [r["metric"] for r in out["regressions"]] == ["value"]
    # lower-is-better: p99 doubling regresses, halving improves
    slower = json.loads(json.dumps(base))
    slower["extra_metrics"]["serving"]["mnist_fft"]["p99_latency_ms"] = 30.0
    out = bench_diff.compare(base, slower)
    assert any(
        r["metric"].endswith("p99_latency_ms") for r in out["regressions"]
    )
    faster = json.loads(json.dumps(base))
    faster["extra_metrics"]["serving"]["mnist_fft"]["p99_latency_ms"] = 2.0
    out = bench_diff.compare(base, faster)
    assert out["verdict"] == "ok"
    assert any(
        r["metric"].endswith("p99_latency_ms") for r in out["improvements"]
    )


def test_bench_diff_metric_overrides():
    metrics = bench_diff.parse_metric_overrides(
        ["value=0.01", "custom.path=0.2:lower"]
    )
    table = {p: (d, t) for p, d, t in metrics}
    assert table["value"] == ("higher", 0.01)
    assert table["custom.path"] == ("lower", 0.2)
    with pytest.raises(ValueError):
        bench_diff.parse_metric_overrides(["nonsense"])
    with pytest.raises(ValueError):
        bench_diff.parse_metric_overrides(["a=0.1:sideways"])


def test_latest_usable_round_skips_truncated_newest(rounds_dir):
    found = bench_diff.latest_usable_round(str(rounds_dir))
    assert found is not None
    num, path, record = found
    assert num == 4  # r05 is parsed:null — the newest USABLE round is r04
    assert record["metric"] == "random_patch_cifar_featurize"


def test_bench_self_compare_section(tmp_path):
    """bench.py's in-round observatory: the record self-compares against
    the newest usable prior round and embeds the verdict."""
    base = {"metric": "m", "value": 100.0, "unit": "u"}
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        json.dump({"parsed": base}, f)
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"parsed": None}, f)  # truncated newest -> falls back
    out = bench.bench_self_diff({"metric": "m", "value": 95.0}, str(tmp_path))
    assert out["baseline"] == "BENCH_r01.json"
    assert out["baseline_round"] == 1
    assert out["verdict"] == "ok"
    regressed = bench.bench_self_diff(
        {"metric": "m", "value": 10.0}, str(tmp_path)
    )
    assert regressed["verdict"] == "regressed"
    # no prior rounds at all -> an honest note, not a crash
    empty = bench.bench_self_diff({"metric": "m"}, str(tmp_path / "void"))
    assert "note" in empty


def test_solve_at_scale_success_records_searched_plan(monkeypatch):
    """The landing shape's record carries the searched placement with the
    chosen plan and its predicted-vs-actual cost."""
    import numpy as np

    monkeypatch.setattr(
        bench, "_bench_bwls_at_scale", lambda rng, shapes=None, bs=4096: {
            "error": "stubbed", "attempts": [],
        },
    )
    out = bench.bench_solve_at_scale(
        np.random.default_rng(0), shapes=[(256, 128)], bs=64
    )
    assert "error" not in out
    rep = out["solver"]
    assert rep is not None
    placement = rep["placement"]
    assert placement is not None
    assert placement["chosen"] == rep["chosen_tier"]
    assert placement["measured_seconds"] is not None
    json.dumps(out)


def test_decode_path_breakdown_records_all_three_paths():
    """ISSUE 13 acceptance: the jpeg_decode by-path ledger (CPU tier-1
    scale) records host pool, device decode, and warm device-snapshot DMA
    — with the device path inside golden tolerance of the host decoder
    and the warm device-snapshot epoch doing ZERO host-side decode."""
    import numpy as np

    out = bench._decode_path_breakdown(
        np.random.default_rng(0), batch=6, n_images=12, size=64
    )
    # ISSUE 19 added a fourth leg: the raw entropy-decode A/B (python vs
    # native scan loop) over the same corpus.
    assert set(out) == {
        "host_pool", "device", "device_snapshot_warm", "entropy_native"
    }
    for path in ("host_pool", "device", "device_snapshot_warm"):
        rec = out[path]
        assert rec["images_per_sec"] > 0, path
        assert rec["overlap_efficiency"] > 0, path
    dev = out["device"]
    assert dev["entropy_decoded"] == 12 and dev["fallbacks"] == 0
    assert dev["within_golden_tolerance"], dev["golden_max_abs_vs_host"]
    warm = out["device_snapshot_warm"]
    assert warm["zero_host_decode"]
    assert warm["dma_bytes"] > 0
    ent = out["entropy_native"]
    assert ent["images"] == 12
    assert ent["python_images_per_sec"] > 0
    assert ent["backend_live"] in ("native", "python")
    if "native_images_per_sec" in ent:
        # ISSUE 19 acceptance bar: native entropy decode >= 3x the Python
        # bit-reader over the bench corpus (observed ~30x).
        assert ent["native_images_per_sec"] > 0
        assert ent["speedup"] >= 3.0, ent
    json.dumps(out)
