"""The server's phase timers and counters as the metric readers see them.

``/metrics`` publishes ``aggregate.phases_ms.<timer>`` (count and mean of
each host step of the served path), ``aggregate.dispatch_count`` and
``aggregate.windows_dispatched``, and ``aggregate.executor.wedge_lanes`` and
``.wedges``.  A program older than the phase timers lacks them, or counts
no dispatch at ``latency_budget_ms`` 0; each function here returns None
there instead of raising, so the reader leaves its metric out.
"""
from __future__ import annotations

from bench.record import Run, counter_delta


def _get(snap: dict | None, path: tuple):
    for p in path:
        if not isinstance(snap, dict) or p not in snap:
            return None
        snap = snap[p]
    return snap


def count_delta(run: Run, *path: str) -> float | None:
    """Increase of a plain ``/metrics`` counter over the window, under
    ``aggregate``."""
    a = _get(run.server0, ("aggregate",) + path)
    b = _get(run.server1, ("aggregate",) + path)
    if a is None or b is None:
        return None
    return float(b - a)


def phase_delta(run: Run, timer: str) -> tuple[float, float] | None:
    """(sum ms, count) of one phase timer over the window."""
    path = ("aggregate", "phases_ms", timer)
    if _get(run.server0, path) is None or _get(run.server1, path) is None:
        return None
    return counter_delta(run, path)


def per_dispatch_ms(run: Run, timer: str) -> float | None:
    """A phase timer's time over the window per dispatch made in it."""
    d = phase_delta(run, timer)
    n = count_delta(run, "dispatch_count")
    if d is None or not n:
        return None
    return d[0] / n


def mean_ms(run: Run, timer: str) -> float | None:
    """A phase timer's mean over the spans that ended in the window."""
    d = phase_delta(run, timer)
    return None if d is None else d[0] / d[1]
