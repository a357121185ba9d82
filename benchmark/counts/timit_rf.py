"""Operations and bytes that TimitPipeline's mathematics needs, from the
cell's shapes alone."""

from __future__ import annotations

from benchmark.lib.manifest import load_module

_shared = load_module("counts", "cifar_rp")
bcd, predict = _shared.bcd, _shared.predict


def cosine(conf: dict, rows: int) -> dict:
    """``cos(x W^T + b)`` for every block, each row featurized once."""
    dim, width, blocks = conf["dimension"], conf["num_cosine_features"], conf["num_cosines"]
    flops = 2.0 * rows * dim * width * blocks
    nbytes = 4.0 * blocks * (rows * dim + dim * width + rows * width)
    return {"flops": flops, "bytes": nbytes}


def fit(conf: dict, rows: dict) -> dict:
    widths = [conf["num_cosine_features"]] * conf["num_cosines"]
    parts = {
        "cosine": cosine(conf, rows["train"] + rows["test"]),
        "bcd": bcd(rows["train"], widths, conf["num_classes"], conf["num_epochs"]),
        "predict": predict(rows["test"], sum(widths), conf["num_classes"]),
    }
    parts["total_flops"] = sum(p["flops"] for p in parts.values())
    return parts


def kernels(conf: dict, rows: dict) -> dict:
    parts = fit(conf, rows)
    return {"bcd": dict(parts["bcd"], layer="solvers")}
