"""A counter of the program's registry (``keystone_tpu.core.trace.metrics``),
a fit: the counter's total over the number of fits the registry saw, which
is the sample count of the histogram named by the metric's ``per`` (a stage
every fit enters once), and over the metric's ``rows`` split of the cell's
rows where it names one.  Counters run from the process's start, warm-up
fit included, and so does the histogram's count.  None where the program
counts nothing under that name."""


def read(metric: dict, ctx: dict):
    from keystone_tpu.core.trace import metrics

    total = metrics.counters().get(metric["counter"])
    fits = metrics.hist_windows().get(metric["per"], {}).get("count", 0)
    if total is None or not fits:
        return None
    value = total / fits
    if metric.get("rows"):
        value /= ctx["rows"][metric["rows"]]
    return value
