"""Device time in collective operations, a traced fit, on the first device
plane: every ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute`` and ``all-to-all`` operation, a ``-start`` and its
``-done`` both (the start is the issue, the done the wait for the fabric).
The time is the operations' own: what of it ran beside compute is not taken
off.  None where the trace holds no such operation, as on one chip."""

import re

COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)*$"
)


def collective_ns(ops: dict) -> dict:
    """name -> ns of the operations of ``ops`` that are collectives."""
    return {name: ns for name, ns in ops.items() if COLLECTIVE.match(name.split(" ")[0])}


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    if not trace or not ctx["traced_fits"] or not trace.get("devices"):
        return None
    found = collective_ns(trace["devices"][0].get("ops", {}))
    if not found:
        return None
    kinds = {}
    for name, ns in found.items():
        kind = COLLECTIVE.match(name.split(" ")[0]).group(1)
        kinds[kind] = kinds.get(kind, 0.0) + ns / 1e6 / ctx["traced_fits"]
    ctx.setdefault("notes", {})["collective_ms_by_kind"] = kinds
    return sum(found.values()) / 1e6 / ctx["traced_fits"]
