"""A fault only a fit that streams made blocks can have, for
``benchmark/tests/test_timit_full.py`` (``lib/faults.py`` has the three
every whole-fit cell can have).  It takes the pipeline's ``fit`` and returns
a broken one."""

from __future__ import annotations


def scored_one_block_late(fit):
    """The test split's blocks reach the streamed apply one step late: block
    ``i`` is scored with block ``i + 1``'s model.  Every block is made, every
    step runs, the fit's state is sound; only the scores are wrong."""
    from keystone_tpu.solvers import block

    def broken(conf, data, seed, stem):
        real = block.BlockSource.__iter__

        def late(self):
            blocks = list(real(self))
            yield from blocks[-1:] + blocks[:-1]

        block.BlockSource.__iter__ = late
        try:
            return fit(conf, data, seed, stem)
        finally:
            block.BlockSource.__iter__ = real

    return broken
