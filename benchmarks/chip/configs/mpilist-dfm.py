"""Plain reference of one bulk-synchronous step over the distributed list,
in NumPy int32 on the host: every verb exact.  It imports nothing of the
program.

The cell's control (kinds/bsp.py, `use_control`) runs the program's sum
and scan over the list cast to float32, the precision below the
configuration's exact int32: past 2^24 a float32 sum rounds, and the
exact comparison must fail it.
"""
from __future__ import annotations

import numpy as np


def step(x: np.ndarray, chips: int) -> dict:
    sq = x * x + 1
    dest = x.sum(1, dtype=np.int64) % chips
    return {"map": sq, "sum": sq.sum(0, dtype=np.int64),
            "scan": np.cumsum(sq, axis=0, dtype=np.int32),
            "group": x[np.argsort(dest, kind="stable")]}
