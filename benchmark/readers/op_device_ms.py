"""Device time, a traced fit, of the operations whose names one of the
metric's ``op_patterns`` finds, in every program: for work that lies inside
larger programs (a featurizer made inside the solver's loop) and so has no
program of its own.  The mean over the device planes; None where no
operation matches."""

import re


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"] or not trace.get("devices"):
        return None
    patterns = [re.compile(p) for p in metric["op_patterns"]]
    ns = sum(
        t
        for dev in trace["devices"]
        for name, t in dev["ops"].items()
        if any(p.search(name) for p in patterns)
    ) / len(trace["devices"])
    if ns <= 0:
        return None
    return ns / 1e6 / ctx["traced_fits"]
