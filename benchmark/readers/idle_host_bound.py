"""Of the traced fits' device idle time, the share the host owes.

While tracing is enabled the program's spans are also profiler annotations
named ``ks/<cat>/<name>`` (``keystone_tpu.core.trace``), so they sit in the
xplane's host plane on the device trace's own clock.  Each idle gap of the
device goes to the innermost annotation that covers its midpoint
(``xplane.attribute_gaps``).  In a ``wait`` or ``d2h`` span the host is
blocked on the device: that idle is the device's own (launch and copy
latency, a program's tail).  Anywhere else, or in no span at all, the host
was working while the device had nothing to run: idle the host owes.

Read under the profiler, which slows some hosts (``PERF.md``); the note
``layer_idle_ms`` is the untraced view.  Notes: ``idle_gaps_program`` (the
ten largest entries of the attribution, seconds over the traced fits),
``idle_by_stage_ms`` (the same gaps by innermost stage, a traced fit),
``ks_spans`` / ``ks_spans_outside_markers`` (annotations found, and how many
start outside every ``bench_fit`` marker: should be 0), ``ks_read_s`` (what
reading the xplane a second time cost)."""

import os
import time

from benchmark.lib import manifest, xplane

PREFIX = "ks/"


def program_spans(plain: dict) -> list:
    """``(name, start_ns, end_ns)`` of the program's annotations in the host
    planes, a name cut to ``ks/<cat>/<name>`` (an annotation's arguments may
    follow it after a ``#``)."""
    out = []
    for plane in plain["planes"]:
        if xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(PREFIX):
                    out.append((name.split("#", 1)[0], start, start + dur))
    return out


def cat_of(name: str) -> str:
    parts = name.split("/", 2)
    return parts[1] if len(parts) == 3 else ""


def split_idle(gaps, spans, blocked_cats) -> dict:
    """``{"idle_s", "host_s", "by_span": [[name, s]], "by_stage": [[name, s]]}``:
    all idle, the part whose innermost span is none of ``blocked_cats``, and
    the gaps by innermost span and by innermost stage."""
    names = {name for name, _, _ in spans}
    by_span = xplane.attribute_gaps(gaps, spans, len(names) + 1)
    stages = [sp for sp in spans if cat_of(sp[0]) == "stage"]
    return {
        "idle_s": sum(e - s for s, e in gaps) / 1e9,
        "host_s": sum(sec for name, sec in by_span if cat_of(name) not in blocked_cats),
        "by_span": by_span,
        "by_stage": xplane.attribute_gaps(gaps, stages, len(names) + 1),
    }


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    notes = ctx.setdefault("notes", {})
    if not trace or not ctx["traced_fits"]:
        return None
    t0 = time.perf_counter()
    try:
        path = xplane.find_xplane(os.path.join(manifest.CHECKOUT, ".bench_work", ctx["cell"], "trace"))
        plain = xplane.plain_from_xplane(path, keep_host_prefix=PREFIX)
    except (OSError, ValueError) as e:
        notes["idle_host_bound"] = f"no xplane to read a second time: {e}"
        return None
    spans = program_spans(plain)
    del plain  # the device planes came along; only the host's are needed
    notes["ks_read_s"] = time.perf_counter() - t0
    notes["ks_spans"] = len(spans)
    if not spans:
        notes["idle_host_bound"] = "no ks/ annotation in the host planes: the program makes none"
        return None
    w0, w1 = trace["window"]
    notes["ks_spans_outside_markers"] = sum(1 for _, s, _ in spans if not w0 <= s <= w1)
    got = split_idle(trace["devices"][0]["gaps"], spans, set(metric["blocked_cats"]))
    if got["idle_s"] <= 0:
        return None
    notes["idle_gaps_program"] = got["by_span"][:10]
    notes["idle_by_stage_ms"] = {
        name: 1e3 * sec / ctx["traced_fits"] for name, sec in got["by_stage"]
    }
    return 100.0 * got["host_s"] / got["idle_s"]
