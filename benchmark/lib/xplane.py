"""Reduction from a profiler trace to busy time, per-program time and gaps.

The reducer works on a plain form of the trace, so that it can be checked
on a small recorded one (``benchmark/fixtures/small_trace.json``):

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

``plain_from_xplane`` makes that form from the ``.xplane.pb`` the JAX
profiler writes, with nothing but ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "bench_fit"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(logdir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def plain_from_xplane(path: str, keep_host_prefix: str = MARKER) -> dict:
    """Device planes whole; of the host planes only the events whose name
    starts with ``keep_host_prefix`` (the benchmark's own markers)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(keep_host_prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def module_name(event_name: str) -> str:
    """``jit_run(123456)`` -> ``jit_run``: the id changes between runs."""
    return _ID_SUFFIX.sub("", event_name.strip())


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, window) -> list:
    """The parts of ``window`` that no interval covers, as ``(start, end)``."""
    w0, w1 = window
    out, cursor = [], w0
    for s, e in sorted(intervals):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        out.append((cursor, w1))
    return out


def _clip(events, window):
    w0, w1 = window
    for name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            yield name, s, e


def markers(plain: dict) -> list:
    """``(name, start_ns, end_ns)`` of the benchmark's host markers, in
    order of start."""
    out = []
    for plane in plain["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(MARKER):
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda m: m[1])


def reduce_trace(plain: dict, window=None) -> dict:
    """Busy time, per-module and per-op time of each device plane inside
    ``window`` (default: first marker's start to last marker's end).

    Returns ``{"window_ns", "devices": [{"name", "busy_ns", "modules":
    {name: ns}, "ops": {name: ns}, "gaps": [(start, end)]}], "busy_ns"}``
    with ``busy_ns`` the mean over the device planes.  Busy is the union of
    the op events; a plane with no op line falls back to its module events.
    """
    if window is None:
        marks = markers(plain)
        if not marks:
            raise ValueError("trace holds no benchmark marker and no window")
        window = (marks[0][1], marks[-1][2])
    devices = []
    for plane in plain["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = list(_clip(lines.get(OPS_LINE, ()), window))
        mods = list(_clip(lines.get(MODULES_LINE, ()), window))
        busy_src = ops or mods
        intervals = [(s, e) for _, s, e in busy_src]
        per_mod, per_op = {}, {}
        for name, s, e in mods:
            key = module_name(name)
            per_mod[key] = per_mod.get(key, 0.0) + (e - s)
        for name, s, e in ops:
            per_op[name] = per_op.get(name, 0.0) + (e - s)
        devices.append(
            {
                "name": plane["name"],
                "busy_ns": union_ns(intervals),
                "modules": per_mod,
                "ops": per_op,
                "gaps": gaps_ns(intervals, window),
            }
        )
    if not devices:
        raise ValueError("trace holds no device plane")
    return {
        "window_ns": window[1] - window[0],
        "window": window,
        "devices": devices,
        "busy_ns": sum(d["busy_ns"] for d in devices) / len(devices),
    }


def layer_ns(modules: dict, patterns: dict) -> dict:
    """Sum per-module time into layers.  ``patterns`` maps a layer to a list
    of regular expressions, searched in the module name; the first layer
    that matches takes the module.  What no layer takes is ``unmapped``."""
    out = {layer: 0.0 for layer in patterns}
    out["unmapped"] = 0.0
    compiled = [
        (layer, [re.compile(p) for p in pats]) for layer, pats in patterns.items()
    ]
    for name, ns in modules.items():
        for layer, pats in compiled:
            if any(p.search(name) for p in pats):
                out[layer] += ns
                break
        else:
            out["unmapped"] += ns
    return out


def top(mapping: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]`` of the ``n`` largest, from name -> ns."""
    ranked = sorted(mapping.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def attribute_gaps(gaps, spans, n: int = 10) -> list:
    """Idle time by what the host was doing: each gap goes to the innermost
    (shortest) host span that covers its midpoint, else to ``outside_spans``.
    ``spans`` are ``(name, start_ns, end_ns)`` on the trace's clock."""
    by_name = {}
    for s, e in gaps:
        mid = (s + e) / 2
        best = None
        for name, s0, e0 in spans:
            if s0 <= mid <= e0 and (best is None or e0 - s0 < best[1]):
                best = (name, e0 - s0)
        key = best[0] if best else "outside_spans"
        by_name[key] = by_name.get(key, 0.0) + (e - s)
    return top(by_name, n)
