"""How unevenly the chips of a cell worked in the traced window: the
busiest device plane's busy time less the idlest's, over their mean, in
percent.  None on fewer than two device planes."""


def read(metric: dict, ctx: dict):
    trace = ctx.get("trace")
    busy = [d["busy_ns"] for d in (trace or {}).get("devices", ())]
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    ctx.setdefault("notes", {})["device_busy_s"] = [b / 1e9 for b in busy]
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
