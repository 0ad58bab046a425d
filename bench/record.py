"""What one run recorded, and the arithmetic the metric readers share.

All times are ``time.monotonic()`` seconds.  ``push_*``, ``est_*`` and
``ref_*`` are the load generator's tables (``bench/loadgen.py``): one row
per push sent, per estimate received, and per window the reference closed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Run:
    workload: str
    t0: float                     # the measured window [t0, t1)
    t1: float
    setup_s: float
    tables: dict                  # the generator's arrays, by name
    server0: dict | None = None   # server counters at t0 and at t1
    server1: dict | None = None
    compiles: list = field(default_factory=list)   # compile event times
    trace: dict | None = None     # bench.trace.reduce of the traced window
    peaks: dict | None = None     # this device's row of bench/peaks.json

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def drain_end(self) -> float:
        return float(self.tables["drain_end"])


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile: the smallest sample with at least ``q`` of
    all samples at or below it."""
    x = np.sort(np.asarray(values, dtype=np.float64))
    if x.shape[0] == 0:
        return None
    return float(x[max(math.ceil(q * x.shape[0]) - 1, 0)])


def arrivals(run: Run) -> np.ndarray:
    """Arrival of each reference window's estimate (first one received),
    NaN where none came."""
    t = run.tables
    out = np.full(t["ref_window"].shape[0], np.nan)
    first: dict = {}
    for s, w, a in zip(t["est_tenant"], t["est_window"], t["est_arrival"]):
        first.setdefault((int(s), int(w)), float(a))
    for r, (s, w) in enumerate(zip(t["ref_tenant"], t["ref_window"])):
        out[r] = first.get((int(s), int(w)), np.nan)
    return out


def window_records(run: Run) -> np.ndarray:
    """Records (edges pushed, duplicates included) in each reference
    window."""
    t = run.tables
    cum = t["ref_cum_sgrs"].astype(np.int64)
    prev = np.r_[0, cum[:-1]]
    first = np.r_[True, t["ref_tenant"][1:] != t["ref_tenant"][:-1]]
    prev[first] = 0
    return cum - prev


def published_in_window(run: Run) -> np.ndarray:
    """Mask of reference windows whose estimate reached the subscriber in
    the measured window."""
    a = arrivals(run)
    return (a >= run.t0) & (a < run.t1)


def estimate_latencies_ms(run: Run) -> np.ndarray:
    """For every window closed in the measured window: from the due time of
    the push that closed it to its estimate's arrival, or to the end of the
    drain where none came."""
    t = run.tables
    due = t["ref_close_due"].astype(np.float64)
    sel = (due >= run.t0) & (due < run.t1)
    a = arrivals(run)[sel]
    a = np.where(np.isnan(a), run.drain_end, a)
    return (a - due[sel]) * 1e3


def ack_latencies_ms(run: Run) -> np.ndarray:
    """For every push due in the measured window: from its due time to its
    ack; a push rejected or never acked counts to the end of the drain."""
    t = run.tables
    due = t["push_due"].astype(np.float64)
    sel = (due >= run.t0) & (due < run.t1)
    ok = t["push_ok"][sel].astype(bool)
    ack = np.where(ok, t["push_ack"][sel], run.drain_end)
    return (ack - due[sel]) * 1e3


def counter_delta(run: Run, path: tuple) -> tuple[float, float] | None:
    """(sum, count) of a ``/metrics`` mean-and-count entry over the window,
    e.g. ``("aggregate", "push_latency_ms")``."""
    if run.server0 is None or run.server1 is None:
        return None

    def get(snap):
        d = snap
        for p in path:
            d = d[p]
        return d["mean"] * d["count"], d["count"]

    s0, c0 = get(run.server0)
    s1, c1 = get(run.server1)
    if c1 - c0 <= 0:
        return None
    return s1 - s0, c1 - c0


def lag_trend_ms(run: Run) -> float | None:
    """Median estimate latency of the windows closed in the last third of
    the measured window minus that of the first third: a backlog that grows
    through the window shows as a positive trend."""
    t = run.tables
    due = t["ref_close_due"].astype(np.float64)
    sel = (due >= run.t0) & (due < run.t1)
    lat = estimate_latencies_ms(run)
    d = due[sel]
    third = run.seconds / 3
    first, last = lat[d < run.t0 + third], lat[d >= run.t1 - third]
    if first.shape[0] == 0 or last.shape[0] == 0:
        return None
    return float(np.median(last) - np.median(first))
