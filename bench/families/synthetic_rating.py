"""Zipfian user activity and item popularity with wave-shaped arrivals.

A copy of ``repro.streams.generators.synthetic_rating_stream`` for its
``temporal`` modes ``"uniform"`` (rating-like) and ``"wave"`` (its docstring
calls that mode wiki-edit-like), with the quantile bucketing done by
:func:`sorted_quantiles` rather than ``np.quantile``: the same values,
without numpy's partition.
"""
from __future__ import annotations

import numpy as np

from bench.families.quantize import sorted_quantiles


def generate(n_records: int, seed: int, *, n_users: int, n_items: int,
             user_exponent: float, item_exponent: float,
             temporal: str, edges_per_timestamp: int) -> tuple:
    rng = np.random.default_rng(seed)
    users = (rng.zipf(user_exponent, size=4 * n_records) - 1) % n_users
    items = (rng.zipf(item_exponent, size=4 * n_records) - 1) % n_items
    # distinct pairs, first occurrences in draw order
    key = users.astype(np.int64) << 32 | items.astype(np.int64)
    _, idx = np.unique(key, return_index=True)
    idx = np.sort(idx)[:n_records]
    users, items = users[idx].astype(np.int64), items[idx].astype(np.int64)
    n = users.shape[0]
    if temporal == "uniform":
        tau = np.sort(rng.uniform(0, 1e6, size=n))
    elif temporal == "wave":
        base = np.sort(rng.uniform(0, 1e6, size=n))
        tau = base + 5e4 * np.sin(base / 5e4)
        tau = np.sort(tau - tau.min())
    else:
        raise ValueError(f"unknown temporal mode {temporal!r}")
    n_unique = n // edges_per_timestamp
    qs = sorted_quantiles(tau, np.linspace(0, 1, n_unique))
    tau = qs[np.clip(np.searchsorted(qs, tau), 0, n_unique - 1)]
    return tau, users, items
