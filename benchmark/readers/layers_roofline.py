"""A kernel's share of its roofline where its work lies in the programs of
several layers (blocks made inside the solver's programs and in the
featurizer's): the least time for the kernel's work in one fit (operations
and bytes from the cell's shapes, ``benchmark/counts``) over the device time
of the metric's ``program_layers`` together in one traced fit.  The time
holds every program of those layers, so the share errs low, never high.
None where the cell's counts have no such kernel."""

from benchmark.lib.roofline import roofline_pct


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    kernel = ctx["kernels"].get(metric["kernel"])
    if not trace or not ctx["traced_fits"] or kernel is None or ctx["peaks"] is None:
        return None
    ns = sum(trace["layers_ns"].get(layer, 0.0) for layer in metric["program_layers"])
    if ns <= 0:
        return None
    share = roofline_pct(
        kernel["flops"], kernel["bytes"], ns / 1e9 / ctx["traced_fits"], ctx["peaks"]
    )
    if share is None:
        return None
    ctx.setdefault("notes", {})[metric["name"] + "_bound"] = share[1]
    return share[0]
