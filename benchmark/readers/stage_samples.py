"""The window's stage samples, read from the program's own registry.

``keystone_tpu.core.trace.metrics`` keeps, for every stage a fit enters
(``core.logging.stage_timer``), a histogram a kind: ``stage_ms.<name>`` its
self time, ``stage_wait_ms.<name>`` the time beneath it blocked on the
device, ``stage_h2d_ms.<name>`` / ``stage_h2d_mb.<name>`` the time and
megabytes of the copies to the device beneath it.  A stage's name occurs
once a fit and nothing that runs after the window enters a stage, so the
last ``len(ctx["untraced_walls"])`` samples of a name are the window's
untraced fits, in order, and the traced fits are the samples before them.

Not a reader: the readers of this directory share it.  Nothing here raises
on a program that records no stage (the parent of the PR that brought them):
``windows`` then returns None and says why in ``ctx["notes"]``.
"""

from __future__ import annotations

KINDS = ("stage_ms", "stage_wait_ms", "stage_h2d_ms", "stage_h2d_mb")


def note(ctx: dict, key: str, value) -> None:
    ctx.setdefault("notes", {})[key] = value


def windows(ctx: dict):
    """``{"stages": [name], "untraced": {kind: {stage: [a sample a fit]}},
    "traced": the same, "walls": the untraced fits' walls in seconds or
    None}``.  A window of traced fits only is taken whole, as ``untraced``
    (``fit_mfu`` does the same)."""
    from keystone_tpu.core.trace import metrics

    hists = metrics.hist_windows()
    stages = sorted(n[len("stage_ms."):] for n in hists if n.startswith("stage_ms."))
    if not stages:
        note(ctx, "stage_samples", "the program recorded no stage_ms.<name> histogram")
        return None
    fits = ctx["fits_completed"]
    walls = list(ctx["untraced_walls"])
    # one warm-up fit in set-up, then the window's: a count that differs is a
    # fit that failed midway, a stage entered twice a fit, or another run's
    # samples in the registry, and the samples then line up with no fit
    odd = {s: hists[f"stage_ms.{s}"]["count"] for s in stages}
    odd = {s: c for s, c in odd.items() if c != fits + 1}
    if odd or not fits or fits > len(hists[f"stage_ms.{stages[0]}"]["samples"]):
        note(
            ctx, "stage_samples",
            f"stage counts {odd} do not line up with one warm-up fit and "
            f"{fits} completed in the window",
        )
        return None
    if not walls:
        note(ctx, "stage_samples", "no untraced fit in the window: the traced fits are read")
    n_un = len(walls) or fits

    def cut(first: int, last: int) -> dict:
        out = {}
        for kind in KINDS:
            out[kind] = {}
            for s in stages:
                samples = hists.get(f"{kind}.{s}", {}).get("samples", [])
                out[kind][s] = samples[len(samples) - first : len(samples) - last]
        return out

    return {
        "stages": stages,
        "untraced": cut(n_un, 0),
        "traced": cut(fits, n_un),
        "walls": walls or None,
    }


def per_fit_sum(samples: dict, stages=None) -> list:
    """Fit by fit, the sum over ``stages`` (default: all) of a kind's samples."""
    rows = [v for s, v in samples.items() if (stages is None or s in stages) and v]
    return [sum(vals) for vals in zip(*rows)]
