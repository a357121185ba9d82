"""A kernel's share of its roofline: the least time the chip could take for
the kernel's work in one fit (operations and bytes from the cell's shapes,
``benchmark/counts``) over the device time of the layer's programs in one
traced fit.  The time holds every program of the layer, so the share errs
low, never high."""

from benchmark.lib.roofline import roofline_pct


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    kernel = ctx["kernels"].get(metric["kernel"])
    if not trace or not ctx["traced_fits"] or kernel is None or ctx["peaks"] is None:
        return None
    ns = trace["layers_ns"].get(kernel["layer"], 0.0)
    if ns <= 0:
        return None
    share = roofline_pct(
        kernel["flops"], kernel["bytes"], ns / 1e9 / ctx["traced_fits"], ctx["peaks"]
    )
    if share is None:
        return None
    ctx.setdefault("notes", {})[metric["name"] + "_bound"] = share[1]
    return share[0]
