#!/usr/bin/env python
"""Bench regression observatory: diff two bench rounds (``BENCH_r*.json``)
with per-metric thresholds and a machine-readable verdict.

Each bench round is one JSON record (the first stdout line of ``bench.py``,
usually stored wrapped by the driver as ``{"parsed": <record>, "tail": ...}``).
This tool compares a curated set of throughput/latency/efficiency metrics
between a BASE round and a CANDIDATE round and judges each against a
relative threshold in its good direction — a ``higher``-is-better metric
regresses when ``cand < base * (1 - threshold)``; a ``lower``-is-better
metric when ``cand > base * (1 + threshold)``.  Thresholds default to the
run-to-run spread observed in rounds 3-5 (~10-15%, 2026-07-30) plus
margin; override any metric with ``--metric``.

The first stdout line is the machine-readable JSON verdict (the bench.py
truncation-proof convention); human-readable lines follow.  Exit status:
0 = ok (no regressions), 1 = regression(s), 2 = incomparable (a record is
missing/unparsed — a driver artifact whose tail was cut mid-JSON arrives as
``parsed: null``, as bench round r05 (2026-07-30; record removed in PR 21)
did — or no metric exists in both rounds).

Usage:
    python tools/bench_diff.py BENCH_r06.json BENCH_r07.json
    python tools/bench_diff.py BENCH_r06.json BENCH_r07.json \
        --metric value=0.10 --metric extra_metrics.jpeg_decode.speedup=0.5

``bench.py`` runs the same comparison in-process at the end of every round
(the ``bench_diff`` section of its record) against the newest usable prior
round, so the observatory rides along on hardware rounds automatically.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: (dotted path, good direction, relative threshold).  Curated rather than
#: exhaustive: these are the metrics whose movement means something across
#: rounds; everything else in the record is context, not a pass/fail bar.
DEFAULT_METRICS: tuple = (
    ("value", "higher", 0.15),
    ("mfu", "higher", 0.15),
    ("solve_seconds", "lower", 0.30),
    ("solve_device_seconds", "lower", 0.30),
    ("extra_metrics.imagenet_fv_featurize.value", "higher", 0.20),
    ("extra_metrics.imagenet_fv_featurize.mfu", "higher", 0.20),
    ("extra_metrics.jpeg_decode.serial_images_per_sec", "higher", 0.25),
    ("extra_metrics.jpeg_decode.threaded_images_per_sec", "higher", 0.25),
    (
        "extra_metrics.jpeg_decode.snapshot.warm_read_images_per_sec",
        "higher", 0.30,
    ),
    # ISSUE 13: the three-path decode ledger (host pool vs device decode
    # vs warm device-snapshot DMA).  Rates are higher-is-better; overlap
    # efficiency regressing means a path's decode/featurize pipelining
    # broke; the device path's golden parity is lower-is-better (a LARGER
    # divergence from the host decoder is a correctness drift, not noise).
    (
        "extra_metrics.jpeg_decode.by_path.host_pool.images_per_sec",
        "higher", 0.30,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.device.images_per_sec",
        "higher", 0.30,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.device_snapshot_warm.images_per_sec",
        "higher", 0.30,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.host_pool.overlap_efficiency",
        "higher", 0.15,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.device.overlap_efficiency",
        "higher", 0.15,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.device.golden_max_abs_vs_host",
        "lower", 0.50,
    ),
    # ISSUE 19: the entropy hot-loop backends (native C vs pure Python).
    # The native rate regressing means the C loop got slower; the Python
    # rate is the portable-fallback floor; the speedup regressing toward
    # 1.0 means the native build stopped paying for itself.
    (
        "extra_metrics.jpeg_decode.by_path.entropy_native."
        "native_images_per_sec",
        "higher", 0.30,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.entropy_native."
        "python_images_per_sec",
        "higher", 0.30,
    ),
    (
        "extra_metrics.jpeg_decode.by_path.entropy_native.speedup",
        "higher", 0.30,
    ),
    ("extra_metrics.e2e.cifar.e2e_images_per_sec", "higher", 0.25),
    ("extra_metrics.e2e.cifar.overlap_efficiency", "higher", 0.15),
    ("extra_metrics.e2e.imagenet_fv.e2e_images_per_sec", "higher", 0.25),
    ("extra_metrics.e2e.imagenet_fv.overlap_efficiency", "higher", 0.15),
    ("extra_metrics.optimizer.auto_cache.speedup", "higher", 0.30),
    ("extra_metrics.optimizer.autotune.speedup", "higher", 0.30),
    ("extra_metrics.serving.mnist_fft.qps", "higher", 0.30),
    ("extra_metrics.serving.mnist_fft.p99_latency_ms", "lower", 0.50),
    (
        "extra_metrics.serving.mnist_fft.batched_vs_unbatched_qps",
        "higher", 0.30,
    ),
    ("extra_metrics.serving.cifar_conv.qps", "higher", 0.30),
    ("extra_metrics.serving.cifar_conv.p99_latency_ms", "lower", 0.50),
    # ISSUE 12: the wire front-end's socket-path tail latency and the
    # shape router's own routing cost — both lower-is-better so a slow
    # route table or a chatty protocol regresses loudly across rounds.
    ("extra_metrics.serving.wire_p99_ms", "lower", 0.50),
    ("extra_metrics.serving.router_route_overhead_us", "lower", 1.00),
    ("extra_metrics.solve_at_scale.examples_per_sec", "higher", 0.30),
    ("extra_metrics.placement.max_search_overhead_frac", "lower", 1.00),
    # ISSUE 14: the device cost-attribution section — the profiled fused
    # solve's ledger MFU regressing means the solve lost device
    # efficiency (or cost attribution broke); the profiled-serve p99 is
    # lower-is-better so a profiler that starts costing the endpoint real
    # tail latency across rounds fails loudly (the <=5% acceptance bound
    # is enforced in-round by the record itself).
    ("extra_metrics.profiler.solve_mfu", "higher", 0.30),
    ("extra_metrics.serving.profiler_overhead.p99_on_ms", "lower", 0.50),
    # ISSUE 15: the numerics observatory's serving cost — the probed-serve
    # p99 and the probe overhead fraction are both lower-is-better, so an
    # observatory that starts costing the endpoint real tail latency
    # across rounds fails loudly (the <= 5% acceptance bound is enforced
    # in-round by the record's target_frac).
    ("extra_metrics.numerics.probed_serve_p99_ms", "lower", 0.50),
    (
        "extra_metrics.numerics.probe_overhead.probe_overhead_frac",
        "lower", 1.00,
    ),
    # ISSUE 16: elastic serving — the checkpoint->foreign-mesh->serve
    # reshard wall must not creep across rounds, and a live re-anchor
    # must never drop a request (zero stays zero: any nonzero candidate
    # against a zero base is a regression, see compare()).
    ("extra_metrics.serving.reshard_wall_s", "lower", 0.50),
    ("extra_metrics.serving.reanchor_dropped_requests", "lower", 0.00),
    # ISSUE 17: multi-host elastic serving — the 2-process fit+serve wall
    # and its crosshost checkpoint-reshard wall must not creep, the
    # host-loss drill's survivor re-anchor must stay fast, and the fleet
    # must never drop a request across the loss (zero stays zero).  On
    # spawn-less hosts the section records zero-base rows, which compare
    # clean against themselves.
    ("extra_metrics.multihost.fit_serve_wall_s", "lower", 0.50),
    ("extra_metrics.multihost.reshard_wall_s", "lower", 0.50),
    ("extra_metrics.multihost.host_loss.reanchor_wall_s", "lower", 0.50),
    ("extra_metrics.multihost.host_loss.dropped_requests", "lower", 0.00),
    # ISSUE 18: closed-loop model lifecycle — the drift→refit→validate→
    # swap drill's walls must not creep across rounds (a slower warm
    # refit or hot-swap means the serving fleet spends longer answering
    # from a stale model), and the atomic hot-swap must NEVER drop a
    # request (zero stays zero: any nonzero candidate against the zero
    # base is a regression, see compare()).
    ("extra_metrics.lifecycle.refit_wall_s", "lower", 0.50),
    ("extra_metrics.lifecycle.swap_wall_s", "lower", 0.50),
    ("extra_metrics.lifecycle.drift_to_healthy_wall_s", "lower", 0.50),
    ("extra_metrics.lifecycle.dropped_requests", "lower", 0.00),
    # ISSUE 20: fleet observability plane — the live fleet-scrape and
    # pure window-merge walls must not creep across rounds, the attached
    # collector must not start costing the endpoint real tail latency
    # (the <= 5% acceptance is recorded in-round as target_frac; the
    # frac row gets the same loose threshold as the numerics tier
    # because a ratio of two noisy p99s swings hard on shared boxes),
    # the one-file incident capture must stay fast, and the obs-capture
    # drill must never drop a request across the member kill (zero
    # stays zero).
    ("extra_metrics.fleet_observability.scrape_wall_s", "lower", 1.00),
    ("extra_metrics.fleet_observability.merge_wall_s", "lower", 1.00),
    (
        "extra_metrics.fleet_observability.collector_overhead.p99_on_ms",
        "lower", 0.50,
    ),
    (
        "extra_metrics.fleet_observability.collector_overhead."
        "collector_overhead_frac",
        "lower", 1.00,
    ),
    (
        "extra_metrics.fleet_observability.incident_capture_wall_s",
        "lower", 1.00,
    ),
    (
        "extra_metrics.fleet_observability.drill.dropped_requests",
        "lower", 0.00,
    ),
)


def get_path(record: dict, dotted: str):
    """Numeric leaf at ``dotted`` path, or None (missing / non-numeric)."""
    node = record
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)


def load_round(path: str) -> tuple[dict | None, str | None]:
    """(bench record, problem).  Unwraps the driver's ``{"parsed": ...}``
    envelope; a missing file, unparsable JSON, or a null/recordless parse
    (a truncated driver artifact) returns ``(None, reason)``."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        return None, f"unreadable: {e}"
    except json.JSONDecodeError as e:
        return None, f"invalid JSON: {e}"
    record = doc.get("parsed", doc) if isinstance(doc, dict) else doc
    if record is None:
        return None, "record is null (truncated round artifact — no parsed bench line)"
    if not isinstance(record, dict) or "metric" not in record:
        return None, "not a bench record (no 'metric' key)"
    return record, None


def compare(
    base: dict,
    cand: dict,
    metrics=DEFAULT_METRICS,
) -> dict:
    """Diff two bench records metric-by-metric.  Returns the verdict dict
    (``verdict``: ok | regressed | incomparable, plus per-metric rows)."""
    rows = []
    regressions = []
    improvements = []
    for path, direction, threshold in metrics:
        b, c = get_path(base, path), get_path(cand, path)
        if b is None or c is None:
            continue
        if b == 0:
            if direction == "lower":
                # A zero base on a lower-is-better metric is a pin, not a
                # meaningless ratio: dropped-request counts and their kin
                # are REQUIRED to stay zero, so any nonzero candidate is a
                # regression (ratio reported as the raw candidate value).
                ratio = float(c)
                regressed = c > 0
                improved = False
            else:
                continue  # zero-base ratio on higher-is-better: no signal
        else:
            ratio = c / b
            if direction == "higher":
                regressed = ratio < 1.0 - threshold
                improved = ratio > 1.0 + threshold
            else:
                regressed = ratio > 1.0 + threshold
                improved = ratio < 1.0 - threshold
        status = (
            "regressed" if regressed else "improved" if improved else "ok"
        )
        row = {
            "metric": path,
            "direction": direction,
            "threshold": threshold,
            "base": b,
            "cand": c,
            "ratio": round(ratio, 4),
            "status": status,
        }
        rows.append(row)
        if regressed:
            regressions.append(row)
        elif improved:
            improvements.append(row)
    verdict = (
        "incomparable"
        if not rows
        else "regressed" if regressions else "ok"
    )
    return {
        "verdict": verdict,
        "compared": len(rows),
        "regressions": regressions,
        "improvements": improvements,
        "rows": rows,
    }


def diff_files(base_path: str, cand_path: str, metrics=DEFAULT_METRICS) -> dict:
    """File-level wrapper: load both rounds, compare, and fold any load
    problem into an ``incomparable`` verdict instead of crashing — a
    truncated round is a finding, not a tool failure."""
    base, base_problem = load_round(base_path)
    cand, cand_problem = load_round(cand_path)
    record = {
        "metric": "bench_diff",
        "base": os.path.basename(base_path),
        "cand": os.path.basename(cand_path),
    }
    problems = {}
    if base_problem:
        problems["base"] = base_problem
    if cand_problem:
        problems["cand"] = cand_problem
    if problems:
        record.update(
            verdict="incomparable", compared=0,
            regressions=[], improvements=[], rows=[], problems=problems,
        )
        return record
    record.update(compare(base, cand, metrics=metrics))
    return record


def list_rounds(dirpath: str) -> list[tuple[int, str]]:
    """(round number, path) of every BENCH_r*.json, ascending."""
    out = []
    for path in glob.glob(os.path.join(dirpath, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def latest_usable_round(dirpath: str) -> tuple[int, str, dict] | None:
    """The newest round whose record actually parses (a truncated newest
    round falls back to the one before it)."""
    for num, path in reversed(list_rounds(dirpath)):
        record, problem = load_round(path)
        if record is not None:
            return num, path, record
    return None


def parse_metric_overrides(specs: list[str], metrics=DEFAULT_METRICS):
    """``--metric path=threshold[:higher|lower]`` entries merged over the
    default metric set (an unknown path is ADDED, default direction
    ``higher``)."""
    table = {path: (direction, thr) for path, direction, thr in metrics}
    for spec in specs:
        path, _, rest = spec.partition("=")
        if not rest:
            raise ValueError(
                f"--metric {spec!r}: expected path=threshold[:direction]"
            )
        thr_s, _, direction = rest.partition(":")
        thr = float(thr_s)
        if direction and direction not in ("higher", "lower"):
            raise ValueError(
                f"--metric {spec!r}: direction must be higher|lower"
            )
        prev_dir = table.get(path, ("higher", None))[0]
        table[path] = (direction or prev_dir, thr)
    return tuple((p, d, t) for p, (d, t) in table.items())


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench_diff")
    p.add_argument("base", help="base round (BENCH_rNN.json or raw record)")
    p.add_argument("cand", help="candidate round to judge against base")
    p.add_argument(
        "--metric",
        action="append",
        default=[],
        metavar="PATH=THRESH[:DIR]",
        help="override/add a metric threshold, e.g. value=0.10 or "
        "extra_metrics.serving.mnist_fft.p99_latency_ms=0.3:lower",
    )
    a = p.parse_args(argv)
    metrics = parse_metric_overrides(a.metric)
    record = diff_files(a.base, a.cand, metrics=metrics)
    # Machine-readable verdict FIRST, flushed (the bench.py convention) —
    # any tail window that reaches the end has the whole JSON line.
    print(json.dumps(record), flush=True)
    if record.get("problems"):
        for side, why in record["problems"].items():
            print(f"# {side} {record[side]}: {why}")
    for row in record["rows"]:
        mark = {"regressed": "BAD", "improved": "+++", "ok": "ok "}[row["status"]]
        print(
            f"# {mark} {row['metric']}: {row['base']:g} -> {row['cand']:g} "
            f"(x{row['ratio']}, {row['direction']} better, "
            f"threshold {row['threshold']})"
        )
    print(
        f"# bench_diff {record['base']} -> {record['cand']}: "
        f"{record['verdict']} ({record['compared']} metric(s) compared, "
        f"{len(record['regressions'])} regression(s), "
        f"{len(record['improvements'])} improvement(s))"
    )
    return {"ok": 0, "regressed": 1, "incomparable": 2}[record["verdict"]]


if __name__ == "__main__":
    sys.exit(main())
