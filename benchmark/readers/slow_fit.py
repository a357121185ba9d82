"""How much slower than the median the window's slowest untraced fit was,
and where: 100 x (its wall - the median wall) / the median wall.

The note ``slow_fit`` names the fit (``index``: its place among the
window's completed fits) and the stage and part (``wait``, ``h2d``, a named
section, ``other``) whose time in that fit exceeds its own median over the
untraced fits by most: ``excess_ms``, and the part's longest single
occurrence in that fit beside its median (``part_max_ms``,
``part_max_median_ms``; None for ``other``, which has no occurrences): one
long wait and many slower ones have the same sum and not the same maximum."""

import statistics

from benchmark.readers import host_sections, stage_samples


def read(metric: dict, ctx: dict):
    win = host_sections.windows(ctx)
    if win is None:
        return None
    walls = win["walls"]
    if not walls:  # the stage samples read are the traced fits', whose walls the profiler made
        stage_samples.note(ctx, metric["name"], "no untraced fit in the window")
        return None
    slow = max(range(len(walls)), key=walls.__getitem__)
    median_s = statistics.median(walls)
    worst = None
    for stage, parts in win["parts"]["untraced"].items():
        for part, got in parts.items():
            excess = got["ms"][slow] - statistics.median(got["ms"])
            if worst is None or excess > worst["excess_ms"]:
                longest = got["max"]
                worst = {
                    "stage": stage,
                    "part": part,
                    "excess_ms": excess,
                    "part_ms": got["ms"][slow],
                    "part_max_ms": longest[slow] if longest else None,
                    "part_max_median_ms": statistics.median(longest) if longest else None,
                }
    ctx.setdefault("notes", {})["slow_fit"] = {
        "index": ctx["fits_completed"] - len(walls) + slow,
        "wall_ms": 1e3 * walls[slow],
        "median_wall_ms": 1e3 * median_s,
        **(worst or {}),
    }
    return 100.0 * (walls[slow] - median_s) / median_s
