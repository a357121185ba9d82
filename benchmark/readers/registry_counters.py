"""Several counters of the program's registry added, a fit: each of the
metric's ``counters`` as ``registry_counter`` reads it (over the fits the
registry saw and the ``rows`` split), summed.  None where the program counts
any of them not: a part of the sum would read as less work."""

from benchmark.lib.manifest import load_module

_counter = load_module("readers", "registry_counter")


def read(metric: dict, ctx: dict):
    values = [_counter.read(dict(metric, counter=c), ctx) for c in metric["counters"]]
    if any(v is None for v in values):
        return None
    return sum(values)
