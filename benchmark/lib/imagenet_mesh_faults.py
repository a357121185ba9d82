"""The fault only ``imagenet_fv_fit_mesh4`` can have, for ``benchmark/tests``:
the class-split solve's columns gathered out of order, so that each chip's
solutions land on the next chip's classes."""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def columns_rotated():
    """The class-split solve with its ``columns`` (where each class's
    solution lies among the chips' gathered ``[chips * slots]``) moved on by
    one chip's slots: what a gather that takes the chips' columns in the
    wrong order makes.  Every system is solved, from its own chip's rows;
    only the model's columns are another chip's.  Stands in for
    ``solvers.weighted._execute_split_bwls``, which is at module level so
    that a harness can."""
    import jax.numpy as jnp

    from keystone_tpu.solvers import weighted

    real = weighted._execute_split_bwls

    def broken(args, statics):
        slots = args[6].shape[1]  # the slot tables are [chips, slots]
        width = args[6].shape[0] * slots
        args = list(args)
        args[7] = (args[7] + slots) % width
        return real(tuple(args), statics)

    weighted._execute_split_bwls = broken
    try:
        yield
    finally:
        weighted._execute_split_bwls = real
