"""Peak device memory of the fullest chip, after the window."""


def read(metric: dict, ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
