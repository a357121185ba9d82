"""How unevenly the chips ran one program: the device time of the programs
that the metric's ``program_layer`` key of the pipeline's ``PROGRAMS``
names, on the busiest device plane less the idlest, over their mean, in
percent, in the traced window.  None on fewer than two device planes or
where no plane ran such a program."""

import re

from benchmark.lib.manifest import load_module


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    devices = (trace or {}).get("devices", ())
    if len(devices) < 2:
        return None
    programs = load_module("pipelines", ctx["conf"]["pipeline"]).PROGRAMS
    patterns = [re.compile(p) for p in programs.get(metric["program_layer"], ())]
    ns = [
        sum(t for name, t in dev["modules"].items() if any(p.search(name) for p in patterns))
        for dev in devices
    ]
    if sum(ns) <= 0:
        return None
    ctx.setdefault("notes", {})[metric["name"] + "_device_s"] = [t / 1e9 for t in ns]
    return 100.0 * (max(ns) - min(ns)) / (sum(ns) / len(ns))
