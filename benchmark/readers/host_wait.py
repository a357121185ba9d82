"""The share of a fit its host thread spends blocked on the device: summed
``stage_wait_ms`` over summed ``stage_ms`` of every stage, over the window's
untraced fits.  The rest of a stage's self time the host works (or copies)
while the device has nothing queued or runs what was queued before.

Notes, all in milliseconds a fit unless said: ``stage_self_ms`` /
``stage_wait_ms`` / ``stage_h2d_ms`` (medians, untraced), ``untiled_pct``
(median of a fit's wall less its stages' self times, over the median wall:
what no stage covers), ``layer_idle_ms.entry`` (the metric's ``own_stages``,
which run no program), and ``stage_traced_over_untraced``: each stage's
working, waiting and copying time in the traced fits over the untraced,
with both medians, which says what part of which stage the profiler slows."""

import statistics

from benchmark.readers import stage_samples

#: a median under this many milliseconds gives no ratio worth printing
FLOOR_MS = 0.05


def _medians(samples: dict) -> dict:
    return {s: statistics.median(v) for s, v in samples.items() if v}


def _parts(cut: dict) -> dict:
    """Stage by stage, the medians of working, waiting and copying time."""
    out = {}
    for stage, selfs in cut["stage_ms"].items():
        if not selfs:
            continue
        waits, copies = cut["stage_wait_ms"][stage], cut["stage_h2d_ms"][stage]
        out[stage] = {
            "work": statistics.median(s - w - c for s, w, c in zip(selfs, waits, copies)),
            "wait": statistics.median(waits),
            "h2d": statistics.median(copies),
        }
    return out


def read(metric: dict, ctx: dict):
    win = stage_samples.windows(ctx)
    if win is None:
        return None
    un = win["untraced"]
    selfs = stage_samples.per_fit_sum(un["stage_ms"])
    waits = stage_samples.per_fit_sum(un["stage_wait_ms"])
    if not selfs or sum(selfs) <= 0:
        return None
    notes = ctx.setdefault("notes", {})
    notes["stage_self_ms"] = _medians(un["stage_ms"])
    notes["stage_wait_ms"] = _medians(un["stage_wait_ms"])
    notes["stage_h2d_ms"] = {s: v for s, v in _medians(un["stage_h2d_ms"]).items() if v}
    own = stage_samples.per_fit_sum(un["stage_ms"], metric.get("own_stages", []))
    if own:
        notes.setdefault("layer_idle_ms", {})["entry"] = statistics.median(own)
    if win["walls"]:
        wall_ms = 1e3 * statistics.median(win["walls"])
        untiled = statistics.median(1e3 * w - s for w, s in zip(win["walls"], selfs))
        notes["untiled_ms"] = untiled
        notes["untiled_pct"] = 100.0 * untiled / wall_ms
    traced, untraced = _parts(win["traced"]), _parts(un)
    if traced:
        notes["stage_traced_over_untraced"] = {
            stage: {
                part: {
                    "untraced_ms": untraced[stage][part],
                    "traced_ms": ms,
                    "ratio": ms / untraced[stage][part]
                    if untraced[stage][part] >= FLOOR_MS else None,
                }
                for part, ms in parts.items()
                if max(ms, untraced[stage][part]) >= FLOOR_MS
            }
            for stage, parts in traced.items()
        }
    return 100.0 * sum(waits) / sum(selfs)
