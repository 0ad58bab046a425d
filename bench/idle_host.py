"""How much of a traced window the device sits idle while the program's host
steps run.

``idle_host_share(devices, host)`` takes what :func:`bench.trace.load`
returns and gives the share (%) of the window (the ``bench_window`` mark)
in which no operation runs on the device while at least one ``sgrapp.*``
span (``repro.core.telemetry``) is open on any host thread, averaged over
the devices that ran anything.  That is idle time the host's own work
explains; the rest of the idle share is time in which the server waits, for
its clients or between steps it does not name.
"""
from __future__ import annotations

import numpy as np

from bench.trace import WINDOW_SPAN, _clip, _union

PREFIX = "sgrapp."


def _intervals(pairs) -> np.ndarray:
    return np.asarray(pairs, dtype=np.float64).reshape(-1, 2)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two sets of sorted, disjoint
    ``[start, end)`` rows (each the output of ``_union``)."""
    if a.shape[0] == 0 or b.shape[0] == 0:
        return 0.0
    # the length of b below t, exact between b's ends, where it is linear
    # or flat
    knots = b.ravel()
    below = np.r_[0.0, np.cumsum(b[:, 1] - b[:, 0])]
    cum = np.repeat(below, 2)[1:-1]
    f = np.interp(a.ravel(), knots, cum).reshape(-1, 2)
    return float((f[:, 1] - f[:, 0]).sum())


def idle_host_share(devices: dict, host: list,
                    prefix: str = PREFIX) -> float | None:
    """The share (%) described in the module doc; None when no device ran
    anything in the window."""
    marks = [(s, e) for _, name, s, e in host if name == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0]
    else:
        ends = [x for evs in devices.values() for _, s, e in evs
                for x in (s, e)]
        if not ends:
            return None
        lo, hi = min(ends), max(ends)
    spans = _union(_clip(_intervals(
        [(s, e) for _, name, s, e in host if name.startswith(prefix)]),
        lo, hi))
    open_ns = float((spans[:, 1] - spans[:, 0]).sum())
    shares = []
    for evs in devices.values():
        busy = _union(_clip(_intervals([(s, e) for _, s, e in evs]), lo, hi))
        if busy.shape[0] == 0:
            continue
        shares.append(open_ns - overlap(spans, busy))
    if not shares or hi <= lo:
        return None
    return 100.0 * float(np.mean(shares)) / (hi - lo)
