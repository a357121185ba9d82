"""Idle share of the device in the traced window.  Where the window also
holds fits the profiler did not trace, the note ``idle_pct_untraced`` sets
the traced fits' device time against an untraced fit's median wall: the
profiler slows the host, never the device."""

import statistics


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or trace["window_ns"] <= 0:
        return None
    if ctx["untraced_walls"] and ctx["traced_fits"]:
        busy = trace["busy_ns"] / 1e9 / ctx["traced_fits"]
        wall = statistics.median(ctx["untraced_walls"])
        ctx.setdefault("notes", {})["idle_pct_untraced"] = 100.0 * (1.0 - busy / wall)
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
