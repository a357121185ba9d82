"""Operations and bytes of RandomPatchCifar's mathematics on a mesh, from
``counts/cifar_rp.py``'s functions.  ``fit`` is the whole fit's work (the
reader of ``fit_mfu_pct`` sets it against the peak of all the cell's chips);
``kernels`` is **one chip's share** of it, because a kernel's roofline share
sets the work against one chip's peak and the mean of the chips' device
times.  The Cholesky factors, which every chip computes, are counted once,
as the mathematics needs them: the share errs low."""

from __future__ import annotations

from benchmark.lib.manifest import load_module

_one_chip = load_module("counts", "cifar_rp")
feature_width = _one_chip.feature_width
fit = _one_chip.fit


def chips(conf: dict) -> int:
    """Devices of the configuration's mesh (``"4"`` or ``"4x1"``)."""
    n = 1
    for part in str(conf["mesh"]).lower().split("x"):
        n *= int(part)
    return n


def kernels(conf: dict, rows: dict) -> dict:
    share = chips(conf)
    return {
        name: dict(k, flops=k["flops"] / share, bytes=k["bytes"] / share)
        for name, k in _one_chip.kernels(conf, rows).items()
    }
