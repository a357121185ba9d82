"""The host's own work beneath the metric's ``stages``: median over the
window's untraced fits of their summed self time (``hist``) less what the
metric's ``less`` names of it, the time blocked on the device and the copies
to it.  What is left the host spent working, while the device ran what was
queued before or nothing.

Notes, named from the metric (``solve_host_ms`` -> ``solve_host_...``), all
medians over the untraced fits, milliseconds a fit: ``..._by_section_ms``
``{stage: {part: ms}}`` (one stage: ``{part: ms}``), the parts being
``wait`` and ``h2d`` (what was taken out, for the sum), each named section
and ``other``; ``..._n`` the sections' occurrences a fit."""

import statistics

from benchmark.readers import host_sections, stage_samples


def read(metric: dict, ctx: dict):
    win = host_sections.windows(ctx)
    if win is None:
        return None
    un = win["untraced"]
    stages = [s for s in metric["stages"] if un["stage_ms"].get(s)]
    if not stages:
        stage_samples.note(
            ctx, metric["name"], f"none of {metric['stages']} among the stages {win['stages']}"
        )
        return None
    per_fit = stage_samples.per_fit_sum(un[metric["hist"]], stages)
    for kind in metric["less"]:
        taken = stage_samples.per_fit_sum(un[kind], stages)
        per_fit = [a - b for a, b in zip(per_fit, taken)]
    stem = metric["name"].removesuffix("_ms")
    parts = {s: win["parts"]["untraced"][s] for s in stages}
    by_section = host_sections.medians(parts)
    counts = host_sections.medians(parts, "n")
    one = len(metric["stages"]) == 1
    notes = ctx.setdefault("notes", {})
    notes[f"{stem}_by_section_ms"] = by_section[stages[0]] if one else by_section
    notes[f"{stem}_n"] = counts[stages[0]] if one else counts
    return statistics.median(per_fit)
