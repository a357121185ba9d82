"""Device time of one layer's programs in the traced fits, a fit.  The
layer's programs are those the pipeline lists under ``PROGRAMS``."""


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"]:
        return None
    ns = trace["layers_ns"].get(metric["program_layer"], 0.0)
    if ns <= 0:
        return None
    return ns / 1e6 / ctx["traced_fits"]
