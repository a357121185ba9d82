"""Operations and bytes of ImageNetSiftLcsFV's mathematics on a mesh, from
``counts/imagenet_fv.py``'s functions.  ``fit`` is the whole fit's work (the
reader of ``fit_mfu_pct`` sets it against the peak of all the cell's
chips); ``kernels`` is **one chip's share** of it, because a kernel's
roofline share sets the work against one chip's peak and the mean of the
chips' device times: the two-branch chain on a chip's images, and of the
weighted solve the population statistics, ``X^T R`` and residual over a
chip's rows with every class's column, and its own classes' covariances,
systems, Choleskys and solves.  What crosses between chips is not counted."""

from __future__ import annotations

from benchmark.lib.manifest import load_module

_one_chip = load_module("counts", "imagenet_fv")
fit = _one_chip.fit


def chips(conf: dict) -> int:
    """Devices of the configuration's mesh (``"4"`` or ``"4x1"``)."""
    n = 1
    for part in str(conf["mesh"]).lower().split("x"):
        n *= int(part)
    return n


def class_systems(widths: list, classes: float, epochs: int) -> dict:
    """The part of ``counts/imagenet_fv.weighted_solve`` that is a class's
    own: its system put together, its Cholesky and its two solves, and its
    factor written, every block and pass."""
    flops = sum(epochs * classes * (w**3 / 3.0 + 7.0 * w * w) for w in widths)
    nbytes = sum(epochs * classes * 4.0 * 2 * w * w for w in widths)
    return {"flops": flops, "bytes": nbytes}


def kernels(conf: dict, rows: dict) -> dict:
    share = chips(conf)
    d = 2 * 2 * conf["desc_dim"] * conf["vocab_size"]
    widths = _one_chip.block_widths(d, conf["solver_block"])
    classes, epochs = conf["num_classes"], conf["num_epochs"]
    rows_share = _one_chip.weighted_solve(rows["train"] / share, widths, classes, epochs)
    others = class_systems(widths, classes - classes / share, epochs)
    return {
        "wsolve": {
            "flops": rows_share["flops"] - others["flops"],
            "bytes": rows_share["bytes"] - others["bytes"],
            "layer": "solvers",
            "programs": "wsolve",
        },
        "fv2_chain": dict(
            _one_chip.chain(conf, (rows["train"] + rows["test"]) / share), layer="featurizers"
        ),
    }
