"""Operations and bytes that RandomPatchCifar's mathematics needs, from the
cell's shapes alone: the work, not the implementation, so that a kernel put
in a program's place is read by the same yardstick."""

from __future__ import annotations


def _out(conf: dict) -> int:
    return conf["image_size"] - conf["patch_size"] + 1


def feature_width(conf: dict) -> int:
    import math

    pools = math.ceil((_out(conf) - conf["pool_size"] // 2) / conf["pool_stride"])
    return pools * pools * 2 * conf["num_filters"]


def conv(conf: dict, images: int) -> dict:
    """conv -> rectify -> pool of ``images`` images: one product of each
    patch with each filter; bytes are the images in, the filter bank once
    and the pooled features out, in float32."""
    d = conf["patch_size"] ** 2 * conf["num_channels"]
    positions = _out(conf) ** 2
    flops = 2.0 * positions * d * conf["num_filters"] * images
    nbytes = 4.0 * (
        images * conf["image_size"] ** 2 * conf["num_channels"]
        + d * conf["num_filters"]
        + images * feature_width(conf)
    )
    return {"flops": flops, "bytes": nbytes}


def block_widths(d: int, block: int) -> list:
    return [min(block, d - i) for i in range(0, d, block)]


def bcd(rows: int, widths: list, classes: int, epochs: int) -> dict:
    """Block coordinate descent: a gram (2 N w^2) and a Cholesky (w^3 / 3) a
    block; the cross term and the residual update (4 N w k) and two
    triangular solves (2 w^2 k) a block and epoch.  Bytes: each block is
    read once for its gram and twice in each epoch, the residual read and
    written once a block and epoch, in float32."""
    flops = nbytes = 0.0
    for w in widths:
        flops += 2.0 * rows * w * w + w**3 / 3.0
        flops += epochs * (4.0 * rows * w * classes + 2.0 * w * w * classes)
        nbytes += 4.0 * rows * w * (1 + 2 * epochs)
        nbytes += epochs * 4.0 * (2 * rows * classes + w * w)
    return {"flops": flops, "bytes": nbytes}


def predict(rows: int, d: int, classes: int) -> dict:
    return {"flops": 2.0 * rows * d * classes, "bytes": 4.0 * rows * (d + classes)}


def fit(conf: dict, rows: dict) -> dict:
    """One whole fit: featurize train and test, solve, score both splits."""
    d = feature_width(conf)
    widths = block_widths(d, conf["solver_block"])
    parts = {
        "conv": conv(conf, rows["train"] + rows["test"]),
        "bcd": bcd(rows["train"], widths, conf["num_classes"], conf["num_epochs"]),
        "predict": predict(rows["train"] + rows["test"], d, conf["num_classes"]),
    }
    parts["total_flops"] = sum(p["flops"] for p in parts.values())
    return parts


def kernels(conf: dict, rows: dict) -> dict:
    """The kernels whose roofline share is reported, with the layer whose
    programs' device time each is held against; one fit's work."""
    parts = fit(conf, rows)
    return {
        "conv": dict(parts["conv"], layer="featurizers"),
        "bcd": dict(parts["bcd"], layer="solvers"),
    }
