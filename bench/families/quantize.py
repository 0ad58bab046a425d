"""Quantile bucketing of sorted timestamps, shared by the families."""
from __future__ import annotations

import numpy as np


def sorted_quantiles(x: np.ndarray, q: np.ndarray) -> np.ndarray:
    """``np.quantile(x, q)`` (linear method) of an already sorted ``x``, bit
    for bit, without the partition numpy runs first (which costs
    O(len(x) * len(q)))."""
    n = x.shape[0]
    virtual = (n - 1) * q
    prev = np.floor(virtual)
    nxt = prev + 1
    above = virtual >= n - 1
    prev[above] = -1
    nxt[above] = -1
    below = virtual < 0
    prev[below] = 0
    nxt[below] = 0
    prev = prev.astype(np.intp)
    gamma = virtual - prev
    a, b = x[prev], x[nxt.astype(np.intp)]
    diff = b - a
    out = a + diff * gamma
    np.subtract(b, diff * (1 - gamma), out=out, where=gamma >= 0.5)
    return out
