"""The window's named host sections, read from the program's own registry.

Beside the four sums ``stage_samples`` cuts, ``keystone_tpu.core.trace``
keeps a family beneath every stage, one sample a stage instance each:
``stage_host_ms.<stage>.<section>`` the summed self time of a named section
of host work (``trace.host``; ``other`` is the self time nothing covers),
``stage_host_n.<stage>.<section>`` its occurrences, and
``stage_max_ms.<stage>.<part>`` the longest single occurrence of a part: a
section, ``wait`` or ``h2d``.  A stage's self time is the sum of its parts.

A part is recorded from the first stage instance that charges it on, as 0
where an instance does not, so its samples line up with ``stage_ms.<stage>``
from the end and what is missing at the front reads 0.

Not a reader: the readers of this directory share it.  Nothing here raises
on a program without the family (the parent of the PR that brought it):
``windows`` then returns None and says why in ``ctx["notes"]``.
"""

from __future__ import annotations

import statistics

from benchmark.readers import stage_samples

HOST, COUNT, LONGEST = "stage_host_ms.", "stage_host_n.", "stage_max_ms."

#: a median under this many milliseconds is left out of a note
FLOOR_MS = 0.05


def _tail(hist: dict | None, first: int, last: int) -> list:
    """The samples ``first`` to ``last`` from the end, 0 where the histogram
    has fewer (it began later than the stage's own)."""
    samples = list(hist["samples"]) if hist else []
    samples = [0.0] * max(0, first - len(samples)) + samples
    return samples[len(samples) - first : len(samples) - last]


def windows(ctx: dict):
    """``stage_samples.windows`` with one key more, ``parts``:
    ``{"untraced": {stage: {part: {"ms": [a sample a fit], "n": [...] or
    None, "max": [...] or None}}}, "traced": the same}``; a part is
    ``wait``, ``h2d``, a section or ``other``."""
    from keystone_tpu.core.trace import metrics

    win = stage_samples.windows(ctx)
    if win is None:
        return None
    hists = metrics.hist_windows()
    names = [n[len(HOST):] for n in hists if n.startswith(HOST)]
    if not names:
        stage_samples.note(
            ctx, "host_sections",
            "the program recorded no stage_host_ms.<stage>.<section> histogram",
        )
        return None
    sections: dict = {}
    for name in names:
        stage, _, section = name.rpartition(".")
        sections.setdefault(stage, []).append(section)
    win["parts"] = {}
    for side in ("untraced", "traced"):
        cut = win[side]
        n_side = {s: len(v) for s, v in cut["stage_ms"].items()}
        # the untraced fits are the last samples, the traced those before them
        skip = {s: 0 if side == "untraced" else len(win["untraced"]["stage_ms"][s]) for s in n_side}
        out = win["parts"][side] = {}
        for stage, n in n_side.items():
            if not n:
                continue
            first, last = n + skip[stage], skip[stage]
            parts = out[stage] = {
                "wait": {"ms": cut["stage_wait_ms"][stage], "n": None},
                "h2d": {"ms": cut["stage_h2d_ms"][stage], "n": None},
            }
            for section in sections.get(stage, ()):
                key = f"{stage}.{section}"
                parts[section] = {
                    "ms": _tail(hists.get(HOST + key), first, last),
                    "n": _tail(hists.get(COUNT + key), first, last) if COUNT + key in hists else None,
                }
            for part, got in parts.items():
                key = f"{LONGEST}{stage}.{part}"
                got["max"] = _tail(hists[key], first, last) if key in hists else None
    return win


def medians(parts: dict, kind: str = "ms") -> dict:
    """``{stage: {part: median}}`` of one side of ``windows(...)["parts"]``,
    without what reads under :data:`FLOOR_MS` (``kind`` ``ms``) or 0."""
    floor = FLOOR_MS if kind == "ms" else 0.0
    out = {}
    for stage, by_part in parts.items():
        row = {
            part: statistics.median(got[kind])
            for part, got in by_part.items() if got[kind]
        }
        out[stage] = {p: v for p, v in row.items() if abs(v) >= floor and v}
    return out
