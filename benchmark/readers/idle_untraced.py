"""Idle share of the device in a fit the profiler did not trace: the traced
fits' device busy time a fit (the union of the device's operations; on more
than one chip the mean of the chips') against the median wall of the
window's untraced fits.  The profiler slows the host, never the device, so
the device time is the traced fits' and the wall the untraced fits'.

Notes: ``idle_untraced_ms`` (that wall less that busy time) and, to set it
against, ``host_work_untraced_ms``: what the host was doing in the untraced
fits, ``{stage: {section: ms}}``, medians, without the waits and the
copies; ``host_work_traced_ms``: the same of the traced fits, which says
which section the profiler slows."""

import statistics

from benchmark.readers import host_sections


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"] or not ctx["untraced_walls"]:
        return None
    win = host_sections.windows(ctx)
    if win is None:
        return None
    busy_ms = trace["busy_ns"] / 1e6 / ctx["traced_fits"]
    wall_ms = 1e3 * statistics.median(ctx["untraced_walls"])
    notes = ctx.setdefault("notes", {})
    notes["idle_untraced_ms"] = wall_ms - busy_ms
    for side in ("untraced", "traced"):
        notes[f"host_work_{side}_ms"] = {
            stage: {p: ms for p, ms in parts.items() if p not in ("wait", "h2d")}
            for stage, parts in host_sections.medians(win["parts"][side]).items()
        }
    return 100.0 * (1.0 - busy_ms / wall_ms)
