"""Device time, a traced fit, of the programs that one key of the pipeline's
``PROGRAMS`` names, whichever layer took them: ``layer_device_ms`` gives a
program to the first layer whose patterns match it, so a key that names a
part of another layer (one descriptor branch of the featurizers) reads
nothing there.  This reader matches the key's own patterns against every
program of the trace, the mean over the device planes."""

import re

from benchmark.lib.manifest import load_module


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"] or not trace.get("devices"):
        return None
    programs = load_module("pipelines", ctx["conf"]["pipeline"]).PROGRAMS
    patterns = [re.compile(p) for p in programs.get(metric["program_layer"], ())]
    ns = sum(
        t
        for dev in trace["devices"]
        for name, t in dev["modules"].items()
        if any(p.search(name) for p in patterns)
    ) / len(trace["devices"])
    if ns <= 0:
        return None
    return ns / 1e6 / ctx["traced_fits"]
