"""``readers/registry_counter.py``'s number, a counter of bytes a fit, in
MB (1e6 bytes).  None where that reader finds nothing."""

from benchmark.lib.manifest import load_module

_counter = load_module("readers", "registry_counter")


def read(metric: dict, ctx: dict):
    value = _counter.read(metric, ctx)
    return None if value is None else value / 1e6
