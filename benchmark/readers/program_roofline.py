"""A kernel's share of its roofline where the kernel is one program of its
layer: the least time the chip could take for the kernel's work in one fit
(operations and bytes from the cell's shapes, ``benchmark/counts``; on a
mesh one chip's share) over the device time of the programs that the
metric's ``program_layer`` key of the pipeline's ``PROGRAMS`` names, the
mean over the device planes, in one traced fit.  None where the trace holds
no such program or the cell's counts have no such kernel."""

from benchmark.lib.manifest import load_module
from benchmark.lib.roofline import roofline_pct


def read(metric: dict, ctx: dict):
    kernel = ctx["kernels"].get(metric["kernel"])
    if kernel is None or ctx["peaks"] is None:
        return None
    program_ms = load_module("readers", "program_device_ms").read(metric, ctx)
    if program_ms is None:
        return None
    share = roofline_pct(kernel["flops"], kernel["bytes"], program_ms / 1e3, ctx["peaks"])
    if share is None:
        return None
    ctx.setdefault("notes", {})[metric["name"] + "_bound"] = share[1]
    return share[0]
