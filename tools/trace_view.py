#!/usr/bin/env python
"""Summarize a keystone trace file (core.trace output).

Reads a Chrome trace_event JSON (``KEYSTONE_TRACE=out.json`` / ``--trace``)
or a JSONL event log (``*.jsonl``) and prints:

* per-stage totals — spans aggregated by name (count, total/mean/max ms),
  sorted by total time;
* the top-k individual spans by duration;
* instant-event summaries (fault counts by kind, HBM admission decisions);
* streaming-ingest overlap efficiency recomputed FROM span intervals:
  ``max(decode_busy, consume_busy) / wall`` over the ``ingest.decode`` /
  ``ingest.consume`` spans — the quantity three separate rate passes
  (decode only, consume only, both) would give, here read off one
  timeline (decode busy time is the union of the parallel decode lanes' intervals).

Usage:
    python tools/trace_view.py /tmp/t.json [--top 10]
    python tools/trace_view.py server.json --stitch client.jsonl

``--stitch`` treats the positional path as a wire SERVER's trace and
merges it with a client trace (``tools/serve_client.py --trace``) into one
cross-process request waterfall joined by wire rid: per request, client
latency decomposes into network time (what the server never saw) plus the
server's own queue / H2D / device-wait / execute / D2H phases.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


def load_events(path: str) -> list:
    """Events from a Chrome trace_event JSON or a JSONL event log."""
    with open(path) as f:
        if path.endswith(".jsonl"):
            return [json.loads(line) for line in f if line.strip()]
        doc = json.load(f)
    if isinstance(doc, dict):
        return doc.get("traceEvents", [])
    return doc  # bare event array — also valid Chrome format


def spans(events: list) -> list:
    return [e for e in events if e.get("ph") == "X"]


def instants(events: list) -> list:
    return [e for e in events if e.get("ph") == "i"]


def per_stage(events: list) -> dict:
    """name -> {count, total_ms, mean_ms, max_ms}, insertion = total desc."""
    agg: dict = defaultdict(lambda: {"count": 0, "total_us": 0.0, "max_us": 0.0})
    for ev in spans(events):
        a = agg[ev["name"]]
        a["count"] += 1
        a["total_us"] += float(ev.get("dur", 0.0))
        a["max_us"] = max(a["max_us"], float(ev.get("dur", 0.0)))
    out = {}
    for name, a in sorted(
        agg.items(), key=lambda kv: kv[1]["total_us"], reverse=True
    ):
        out[name] = {
            "count": a["count"],
            "total_ms": round(a["total_us"] / 1e3, 3),
            "mean_ms": round(a["total_us"] / a["count"] / 1e3, 3),
            "max_ms": round(a["max_us"] / 1e3, 3),
        }
    return out


def top_spans(events: list, k: int = 10) -> list:
    return sorted(
        spans(events), key=lambda e: float(e.get("dur", 0.0)), reverse=True
    )[:k]


def _union_us(intervals: list) -> float:
    """Total covered microseconds of possibly-overlapping [t0, t1) spans —
    parallel decode lanes count wall coverage once, not per thread."""
    total = 0.0
    end = float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 <= end:
            continue
        total += t1 - max(t0, end)
        end = t1
    return total


def overlap_from_spans(events: list) -> dict | None:
    """Streaming-ingest overlap efficiency recomputed from one timeline.

    ``decode_busy`` = union of ``ingest.decode`` span intervals (the wall
    time during which at least one decoder thread was decoding — the
    producer-side ceiling); ``consume_busy`` = union of ``ingest.consume``
    spans (the consumer's featurize time); ``wall`` spans first ingest
    event to last.  A perfectly overlapped pipeline has
    ``wall ≈ max(decode_busy, consume_busy)``, so

        overlap_efficiency = max(decode_busy, consume_busy) / wall

    — the span-interval form of ``e2e / min(decode_rate,
    featurize_rate)``.  Returns None when the trace has no ingest spans.
    """
    decode, consume, all_ingest = [], [], []
    for ev in spans(events):
        iv = (float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0.0)))
        if ev["name"] in ("ingest.decode", "ingest.entropy_decode"):
            # entropy_decode is the device-decode path's producer-side
            # work (ops.jpeg_device): same lane, same ceiling semantics
            decode.append(iv)
        elif ev["name"] == "ingest.consume":
            consume.append(iv)
        if ev.get("cat") == "ingest":
            all_ingest.append(iv)
    if not decode or not consume:
        return None
    wall = max(t1 for _, t1 in all_ingest) - min(t0 for t0, _ in all_ingest)
    decode_busy = _union_us(decode)
    consume_busy = _union_us(consume)
    return {
        "decode_busy_ms": round(decode_busy / 1e3, 3),
        "consume_busy_ms": round(consume_busy / 1e3, 3),
        "wall_ms": round(wall / 1e3, 3),
        "overlap_efficiency": round(
            max(decode_busy, consume_busy) / wall, 3
        ) if wall > 0 else None,
        "decode_spans": len(decode),
        "consume_spans": len(consume),
    }


def stitch(server_events: list, client_events: list) -> dict:
    """Cross-process request waterfall: join a wire CLIENT's trace
    (``tools/serve_client.py --trace``: ``client.submit``/``client.answer``
    instants keyed by wire rid + the ``client.clock`` offset meta) with
    the SERVER's trace (``wire.request``/``wire.response`` instants tying
    wire rids to serve request ids; ``serve.request`` spans carrying the
    per-phase decomposition) into one per-request row set decomposing

        client latency = network + server wire time
        server wire time ≈ queue-wait + H2D + device-wait + execute + D2H
                           + answer

    ``network_ms`` is the residual the server never saw (socket + frame
    parse + responder queue on both ends).  Rows join by wire rid — the id
    both processes logged — and the clock offset is reported so the two
    timelines can also be aligned absolutely."""
    c_submit: dict = {}
    c_answer: dict = {}
    clock = None
    for ev in instants(client_events):
        args = ev.get("args", {})
        if ev["name"] == "client.submit":
            c_submit[args.get("rid")] = ev
        elif ev["name"] == "client.answer":
            c_answer[args.get("rid")] = ev
        elif ev["name"] == "client.clock":
            clock = args
    # Server events keyed PER CONNECTION: wire rids are per-connection
    # counters starting at 1, so a server trace holding several clients
    # has colliding rids — joining on rid alone would pair this client's
    # latencies with another connection's phases.
    req_by_conn: dict = {}  # conn -> {rid: wire.request args}
    resp_by_conn: dict = {}
    serve_phases: dict = {}
    for ev in server_events:
        args = ev.get("args", {})
        if ev.get("ph") == "i" and ev.get("name") == "wire.request":
            req_by_conn.setdefault(args.get("conn"), {})[
                args.get("wire_rid")
            ] = args
        elif ev.get("ph") == "i" and ev.get("name") == "wire.response":
            resp_by_conn.setdefault(args.get("conn"), {})[
                args.get("wire_rid")
            ] = args
        elif ev.get("ph") == "X" and ev.get("name") == "serve.request":
            serve_phases[args.get("request_id")] = args

    # Pick THIS client's connection: most answered-rid overlap, with
    # matching trace-context span ids (traced clients send their span on
    # every request, and the server records it) breaking the tie — two
    # identical-window clients overlap on rids but not on span mapping.
    def conn_score(reqs: dict):
        overlap = sum(1 for rid in c_answer if rid in reqs)
        spans = sum(
            1
            for rid, ans in c_answer.items()
            if rid in reqs
            and reqs[rid].get("client_span") is not None
            and reqs[rid].get("client_span")
            == ans.get("args", {}).get("span")
        )
        return (spans, overlap)

    conn = (
        max(req_by_conn, key=lambda c: conn_score(req_by_conn[c]))
        if req_by_conn
        else None
    )
    s_request = req_by_conn.get(conn, {})
    s_response = resp_by_conn.get(conn, {})

    rows = []
    for rid in sorted(set(c_answer) & set(s_request)):
        ans = c_answer[rid]
        args = ans.get("args", {})
        client_ms = float(args.get("ms", 0.0))
        sreq = s_request[rid]
        sresp = s_response.get(rid, {})
        server_ms = float(sresp.get("ms", 0.0))
        row = {
            "wire_rid": rid,
            "client_span": sreq.get("client_span"),
            "request_id": sreq.get("request_id"),
            "client_ms": round(client_ms, 3),
            "server_ms": round(server_ms, 3),
            # What the server never saw: socket transit + framing + the
            # responder/reader queues on both sides.
            "network_ms": round(client_ms - server_ms, 3),
        }
        phases = serve_phases.get(sreq.get("request_id"))
        if phases:
            for key in (
                "queue_wait_ms", "h2d_ms", "device_wait_ms", "execute_ms",
                "d2h_ms", "answer_ms", "pad_overhead_ms",
            ):
                if key in phases:
                    row[key] = phases[key]
        rows.append(row)

    def mean(key: str):
        vals = [r[key] for r in rows if isinstance(r.get(key), (int, float))]
        return round(sum(vals) / len(vals), 3) if vals else None

    return {
        "requests": len(rows),
        # Submits exceed answers when RETRY_AFTER resubmits happened —
        # the backpressure the waterfall's latencies already include.
        "client_submits": len(c_submit),
        "client_requests": len(c_answer),
        "server_requests": len(s_request),
        "server_connections": len(req_by_conn),
        "connection": conn,
        "clock": clock,
        "mean": {
            k: mean(k)
            for k in (
                "client_ms", "server_ms", "network_ms", "queue_wait_ms",
                "h2d_ms", "device_wait_ms", "execute_ms", "d2h_ms",
                "answer_ms",
            )
        },
        "rows": rows,
    }


def stitch_summary(server_path: str, client_path: str, top: int = 10) -> str:
    merged = stitch(load_events(server_path), load_events(client_path))
    lines = [
        f"# stitched waterfall: {merged['requests']} request(s) joined "
        f"({client_path} x {server_path})"
    ]
    if merged.get("clock"):
        lines.append(f"# clock: {merged['clock']}")
    if merged.get("server_connections", 0) > 1:
        lines.append(
            f"# server trace holds {merged['server_connections']} "
            f"connection(s); joined against conn {merged['connection']}"
        )
    m = merged["mean"]
    lines.append(
        f"# mean: client {m['client_ms']}ms = network {m['network_ms']}ms "
        f"+ server {m['server_ms']}ms (queue {m['queue_wait_ms']}ms, "
        f"device {m['execute_ms']}ms)"
    )
    cols = (
        "wire_rid", "client_ms", "network_ms", "server_ms",
        "queue_wait_ms", "h2d_ms", "device_wait_ms", "execute_ms", "d2h_ms",
    )
    lines.append(" ".join(f"{c:>14}" for c in cols))
    for row in merged["rows"][:top]:
        lines.append(
            " ".join(f"{row.get(c, ''):>14}" for c in cols)
        )
    if len(merged["rows"]) > top:
        lines.append(f"... {len(merged['rows']) - top} more row(s)")
    return "\n".join(lines)


def instant_summary(events: list) -> dict:
    """Counts of instant events: faults by kind, admissions by verdict."""
    out: dict = {"faults": defaultdict(int), "hbm_admission": defaultdict(int)}
    for ev in instants(events):
        args = ev.get("args", {})
        if ev["name"] == "fault":
            out["faults"][args.get("kind", "?")] += 1
        elif ev["name"] == "hbm_admission":
            key = "admitted" if args.get("admitted") else "denied"
            out["hbm_admission"][key] += 1
    return {k: dict(v) for k, v in out.items() if v}


def summarize(path: str, top: int = 10) -> str:
    events = load_events(path)
    lines = [f"# {path}: {len(events)} events"]

    stages = per_stage(events)
    lines.append("")
    lines.append("## per-stage totals (spans aggregated by name)")
    lines.append(f"{'name':<40} {'count':>6} {'total_ms':>12} {'mean_ms':>10} {'max_ms':>10}")
    for name, a in stages.items():
        lines.append(
            f"{name:<40} {a['count']:>6} {a['total_ms']:>12.3f} "
            f"{a['mean_ms']:>10.3f} {a['max_ms']:>10.3f}"
        )

    lines.append("")
    lines.append(f"## top {top} spans by duration")
    for ev in top_spans(events, top):
        err = ev.get("args", {}).get("error")
        lines.append(
            f"{ev['name']:<40} {float(ev.get('dur', 0.0)) / 1e3:>10.3f} ms "
            f"tid={ev.get('tid')}" + (f" ERROR={err}" if err else "")
        )

    inst = instant_summary(events)
    if inst:
        lines.append("")
        lines.append("## instants")
        for group, counts in inst.items():
            lines.append(f"{group}: " + ", ".join(
                f"{k}={v}" for k, v in sorted(counts.items())
            ))

    overlap = overlap_from_spans(events)
    if overlap is not None:
        lines.append("")
        lines.append("## ingest overlap (recomputed from span intervals)")
        lines.append(
            f"decode busy {overlap['decode_busy_ms']} ms "
            f"({overlap['decode_spans']} spans), "
            f"consume busy {overlap['consume_busy_ms']} ms "
            f"({overlap['consume_spans']} spans), "
            f"wall {overlap['wall_ms']} ms -> "
            f"overlap_efficiency {overlap['overlap_efficiency']}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("trace_view")
    p.add_argument("path", help="trace file (.json Chrome format or .jsonl)")
    p.add_argument("--top", type=int, default=10, help="top-k spans to list")
    p.add_argument(
        "--stitch", default=None, metavar="CLIENT.jsonl",
        help="treat PATH as the SERVER trace and merge it with this "
        "client trace (serve_client.py --trace) into one request "
        "waterfall joined by wire rid",
    )
    a = p.parse_args(argv)
    if a.stitch:
        print(stitch_summary(a.path, a.stitch, a.top))
        return 0
    print(summarize(a.path, a.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
