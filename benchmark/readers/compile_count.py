"""Programs really compiled inside the window, from the compile meter."""


def read(metric: dict, ctx: dict):
    return float(ctx["compile_in_window"]["compiled"])
