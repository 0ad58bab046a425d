"""The counter's share of its roofline (%), the same whatever tier counts.

Each window published in the traced window is charged the work of its
exact Gram count, from its own distinct-vertex counts: ``bench.roofline``.
The share is the sum of those least times over the device-operation time
of the traced window."""
import numpy as np

from bench.record import published_in_window
from bench.roofline import least_time_s


def read(run):
    sel = published_in_window(run)
    if run.trace is None or not sel.any() or run.trace["busy_s"] <= 0:
        return None
    t = run.tables
    need = sum(least_time_s(int(a), int(b), run.peaks)[0]
               for a, b in zip(t["ref_n_i"][sel], t["ref_n_j"][sel]))
    return float(100.0 * need / run.trace["busy_s"])
