"""The work of counting one window's butterflies, whatever tier counts it.

A window with ``n_i`` and ``n_j`` distinct vertices is charged its exact
Gram count: ``2 * n_i * n_j * min(n_i, n_j)`` operations at the chip's
bf16 peak, and ``n_i * n_j`` bytes of its 0/1 biadjacency read once at the
HBM peak.  The least time is the larger of the two.
"""
from __future__ import annotations


def gram_work(n_i: int, n_j: int) -> tuple[float, float]:
    """(operations, bytes) of one window's Gram count."""
    return 2.0 * n_i * n_j * min(n_i, n_j), float(n_i) * n_j


def least_time_s(n_i: int, n_j: int, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) where bound is ``"compute"`` or ``"memory"``."""
    ops, nbytes = gram_work(n_i, n_j)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
