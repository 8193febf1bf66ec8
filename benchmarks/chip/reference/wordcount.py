"""Plain word count: the exact count of every id over a job's splits.

The yardstick of the fold cells.  It imports nothing of the system under
test: the ids are read back to the host and counted by ``np.bincount``.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np


def counts(splits: Iterable[np.ndarray], num_keys: int) -> np.ndarray:
    """(num_keys,) int64 counts over all ``splits`` (1-D arrays of ids)."""
    total = np.zeros((num_keys,), np.int64)
    for ids in splits:
        ids = np.asarray(ids).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= num_keys):
            raise ValueError(f"ids outside [0, {num_keys})")
        total += np.bincount(ids, minlength=num_keys)
    return total
