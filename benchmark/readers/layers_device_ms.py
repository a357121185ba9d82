"""Device time of several layers' programs in the traced fits, a fit:
``layer_device_ms`` summed over the metric's ``program_layers``, for a layer
of the benchmark whose programs the pipeline lists under more than one key of
``PROGRAMS`` (one of them a kernel's own, for its roofline share)."""


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"]:
        return None
    ns = sum(trace["layers_ns"].get(layer, 0.0) for layer in metric["program_layers"])
    if ns <= 0:
        return None
    return ns / 1e6 / ctx["traced_fits"]
