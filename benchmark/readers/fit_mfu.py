"""The whole fits' share of the chip's peak: the operations their
mathematics needs over their wall time.  The profiler slows the host, so
the fits it traced are left out where the window holds others; a window of
traced fits only is taken whole."""


def read(metric: dict, ctx: dict):
    if ctx["peaks"] is None:  # a rehearsal: no peak off the chip, so no share of one
        return None
    walls = ctx["untraced_walls"]
    done, seconds = (len(walls), sum(walls)) if walls else (ctx["fits_completed"], ctx["window_s"])
    if not done or seconds <= 0:
        return None
    peak = ctx["peaks"]["flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["fit_flops"] * done / (seconds * peak)
