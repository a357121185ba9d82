"""Operations and bytes that MnistRandomFFT's mathematics needs at its
option parser's defaults, from the cell's shapes alone, with
``counts/cifar_rp.py``'s block solve and apply.

An FFT of a row is the real transform of ``n = next_pow2(d)`` points,
``2.5 n log2 n`` operations (the split-radix count of a real transform);
its bytes are the rows read once a block, the signs, and the block's
features written once, in float32.

``fit`` is the whole fit's work as the mathematics needs it: every row of
both splits featurized once a block, the grams, Choleskys and the one
sweep's steps, both splits' scores.  ``kernels``: ``made_fft_bcd`` is the
same without the evaluation (the training blocks' features once and the
block solve), held against the device time of the layers ``featurizers``
and ``solvers`` together, where a program that holds no design matrix makes
the blocks.  **The count does not grow with the passes a program makes**,
so any form of the FFT (a library transform, a DFT as a product, one fused
into the gram) is read against the same work, and recomputation shows as a
lower share."""

from __future__ import annotations

import math

from benchmark.lib.manifest import load_module

_shared = load_module("counts", "cifar_rp")
bcd, predict = _shared.bcd, _shared.predict


def _shape(conf: dict) -> tuple:
    """(blocks, FFTs a block, pixels, padded points, block width)."""
    f = conf["block_size"] // 512
    d = conf["mnist_image_size"]
    n = 1 << (d - 1).bit_length()
    return -(-conf["num_ffts"] // f), f, d, n, f * n // 2


def fft(conf: dict, rows: int) -> dict:
    """Every block's features of ``rows`` rows, each row featurized once a
    block."""
    blocks, f, d, n, width = _shape(conf)
    flops = 2.5 * n * math.log2(n) * rows * f * blocks
    nbytes = 4.0 * blocks * (rows * d + f * d + rows * width)
    return {"flops": flops, "bytes": nbytes}


def fit(conf: dict, rows: dict) -> dict:
    blocks, _, _, _, width = _shape(conf)
    widths = [width] * blocks
    parts = {
        "fft": fft(conf, rows["train"] + rows["test"]),
        "bcd": bcd(rows["train"], widths, conf["num_classes"], conf["num_iters"]),
        "predict": predict(rows["train"] + rows["test"], sum(widths), conf["num_classes"]),
    }
    parts["total_flops"] = sum(p["flops"] for p in parts.values())
    return parts


def kernels(conf: dict, rows: dict) -> dict:
    made = fft(conf, rows["train"])
    solve = fit(conf, rows)["bcd"]
    return {
        "made_fft_bcd": {
            "flops": made["flops"] + solve["flops"],
            "bytes": made["bytes"] + solve["bytes"],
            "layers": ["featurizers", "solvers"],
        }
    }
