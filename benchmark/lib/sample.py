"""Rows drawn from the seed for a comparison that does not take every row."""

from __future__ import annotations

import numpy as np


def pick_rows(total: int, count: int, seed: int) -> np.ndarray:
    """``count`` of ``total`` row indices, sorted, the same for one seed."""
    rng = np.random.default_rng([seed, 0x5C0])
    return np.sort(rng.permutation(total)[: min(count, total)])
