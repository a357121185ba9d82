"""Operations and bytes that TimitPipeline's mathematics needs at its
documented 50 blocks, from the cell's shapes alone, by ``counts/timit_rf.py``'s
and ``counts/cifar_rp.py``'s functions.

``fit`` is the whole fit's work as the mathematics needs it: every row's
features once a block, the grams, the Choleskys, the steps, the test scores.
A program that holds no design matrix makes a training block again in every
pass (seven times a fit as this PR runs it); **the count does not grow with
the passes a program chooses to make**, so ``fit_mfu_pct`` and
``made_bcd_roofline`` read the same work whatever implements it, and
recomputation shows as a lower share.

``kernels``: ``made_bcd`` is what the solver's programs do now that the
making lies inside them: a training block's features once, then ``bcd``'s
gram, Cholesky and steps, held against the device time of the layer
``solvers``.  Bytes: the block solve's (each block read once for its gram
and twice an epoch, as if held) plus the rows and a block's parameters read
once a block."""

from __future__ import annotations

from benchmark.lib.manifest import load_module

_blocks = load_module("counts", "timit_rf")
fit = _blocks.fit


def kernels(conf: dict, rows: dict) -> dict:
    parts = fit(conf, rows)
    made = _blocks.cosine(conf, rows["train"])
    return {
        "made_bcd": {
            "flops": parts["bcd"]["flops"] + made["flops"],
            "bytes": parts["bcd"]["bytes"] + made["bytes"],
            "layer": "solvers",
        }
    }
