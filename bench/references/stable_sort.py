"""The plain reference for every sort configuration: numpy, nothing else.

A stable sort of int32 keys orders (key, position) pairs: equal keys keep
their input order. Biasing each key into [0, 2^32) and packing it above its
32-bit position gives one uint64 per key whose plain ``np.sort`` is that
order, several times faster at 2^26 keys than ``np.argsort(kind="stable")``
(3.5 s against 21 s on one x86 host core).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def stable_sort(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted keys, stable argsort) of the flattened int32 ``keys``."""
    k = np.asarray(keys, np.int32).reshape(-1)
    if k.size >= 2**32:
        raise ValueError("positions must fit in 32 bits")
    comp = (k.astype(np.int64) + 2**31).astype(np.uint64) << np.uint64(32)
    comp |= np.arange(k.size, dtype=np.uint64)
    comp.sort()
    order = (comp & np.uint64(0xFFFFFFFF)).astype(np.int64)
    return ((comp >> np.uint64(32)).astype(np.int64) - 2**31).astype(np.int32), order


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Positions at which ``got`` differs from ``want``, missing ones included."""
    got, want = np.asarray(got).reshape(-1), np.asarray(want).reshape(-1)
    n = min(got.size, want.size)
    return int(np.count_nonzero(got[:n] != want[:n])) + abs(got.size - want.size)
