"""Median over the window's untraced fits of one kind of stage sample
(``hist``: ``stage_ms``, ``stage_h2d_mb``, ...) summed over the metric's
``stages`` (null: every stage), a fit.  Where the metric names a
``program_layer`` and the run was traced, the note ``layer_idle_ms`` sets
the value against that layer's device time a traced fit: the layer's share
of ``idle_pct_untraced``, with the profiler off the host."""

import statistics

from benchmark.readers import stage_samples


def read(metric: dict, ctx: dict):
    win = stage_samples.windows(ctx)
    if win is None:
        return None
    per_fit = stage_samples.per_fit_sum(win["untraced"][metric["hist"]], metric.get("stages"))
    if not per_fit:
        stage_samples.note(
            ctx, metric["name"], f"none of {metric.get('stages')} among the stages {win['stages']}"
        )
        return None
    value = statistics.median(per_fit)
    layer, trace = metric.get("program_layer"), ctx.get("trace")
    if layer and trace and ctx["traced_fits"]:
        device_ms = trace["layers_ns"].get(layer, 0.0) / 1e6 / ctx["traced_fits"]
        ctx.setdefault("notes", {}).setdefault("layer_idle_ms", {})[layer] = value - device_ms
    return value
