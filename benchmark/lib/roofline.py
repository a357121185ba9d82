"""Roofline arithmetic (the arithmetic of ``bench.roofline``, copied) and
the table of peaks."""

from __future__ import annotations

from .manifest import load_json


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind that is not in
    ``benchmark/peaks.json`` is an error, never a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has: {', '.join(table)}): no peak, so no utilization"
        )
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time the chip could take, which bound sets it)."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def roofline_pct(flops: float, nbytes: float, seconds: float, peaks: dict):
    """Share of the roofline in percent, or None where no time was read."""
    if not seconds or seconds <= 0:
        return None
    least, bound = least_seconds(flops, nbytes, peaks)
    return 100.0 * least / seconds, bound
