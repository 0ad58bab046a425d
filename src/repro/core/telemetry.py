"""Phase timers and profiler spans of the served path.

:meth:`Telemetry.phase` wraps one host step (decode, WAL append, windowize,
pack, launch, count wait, ...).  It opens a
``jax.profiler.TraceAnnotation`` named ``sgrapp.<name>``, which the profiler
records on its host plane, on the same clock as the device planes, while a
trace is being taken (and costs about a microsecond otherwise).  It also adds
the step's wall time to a named :class:`Timer` of the table, always.

Spans are flat: no phase opens inside another on the same thread, so a
device idle gap in a trace is overlapped most by the step itself, and the
phase means of one engine cycle add up to the cycle.

``StreamServer``'s ``ServerMetrics`` owns one table and hands it to its
engine and executor; ``/metrics`` publishes it as ``aggregate.phases_ms``.
A standalone engine or executor keeps a table of its own.
"""
from __future__ import annotations

import bisect
import math
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

__all__ = ["LATENCY_BOUNDS_MS", "SPAN_PREFIX", "Telemetry", "Timer"]

# upper bounds (ms) of the fixed histogram buckets every timer keeps; one
# more bucket holds everything above the last
LATENCY_BOUNDS_MS = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                     500.0, 1000.0)
SPAN_PREFIX = "sgrapp."


class Timer:
    """Count, sum, max and a fixed-bucket histogram of durations in ms."""

    __slots__ = ("count", "sum_ms", "max_ms", "buckets")

    def __init__(self):
        self.count = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0
        self.buckets = [0] * (len(LATENCY_BOUNDS_MS) + 1)

    def observe(self, ms: float) -> None:
        self.count += 1
        self.sum_ms += ms
        self.max_ms = max(self.max_ms, ms)
        self.buckets[bisect.bisect_left(LATENCY_BOUNDS_MS, ms)] += 1

    def quantile(self, q: float) -> float:
        """Upper bound of the bucket holding the ``q`` quantile, capped at
        the largest duration seen (which also stands for the open top
        bucket)."""
        if not self.count:
            return 0.0
        rank = max(math.ceil(q * self.count), 1)
        seen = 0
        for bound, n in zip(LATENCY_BOUNDS_MS, self.buckets):
            seen += n
            if seen >= rank:
                return min(bound, self.max_ms)
        return self.max_ms

    def snapshot(self) -> dict:
        buckets = {f"<={b}ms": n for b, n in
                   zip(LATENCY_BOUNDS_MS, self.buckets)}
        buckets[f">{LATENCY_BOUNDS_MS[-1]}ms"] = self.buckets[-1]
        return {"count": self.count,
                "mean": self.sum_ms / self.count if self.count else 0.0,
                "p50": self.quantile(0.50), "p99": self.quantile(0.99),
                "max": self.max_ms, "buckets": buckets}


class Telemetry:
    """One table of phase timers and event counters.  The event loop and
    the engine thread both write to it, so every access holds its lock."""

    def __init__(self):
        self._timers: dict[str, Timer] = {}
        self._counts: dict[str, int] = {}
        self._lock = threading.Lock()

    def observe(self, timer: str, ms: float) -> None:
        with self._lock:
            t = self._timers.get(timer)
            if t is None:
                t = self._timers[timer] = Timer()
            t.observe(ms)

    def add(self, counter: str, n: int = 1) -> None:
        with self._lock:
            self._counts[counter] = self._counts.get(counter, 0) + int(n)

    def count(self, counter: str) -> int:
        with self._lock:
            return self._counts.get(counter, 0)

    @contextmanager
    def phase(self, name: str, timer: str | None = None, **args):
        """Span ``sgrapp.<name>`` (with ``args`` as its trace metadata)
        around the block, and its wall time added to timer ``timer``
        (default ``name``)."""
        t0 = time.monotonic()
        try:
            with TraceAnnotation(SPAN_PREFIX + name, **args):
                yield
        finally:
            self.observe(timer or name, (time.monotonic() - t0) * 1e3)

    def timers(self) -> dict:
        """``{timer: Timer.snapshot()}``, by name."""
        with self._lock:
            return {k: t.snapshot() for k, t in sorted(self._timers.items())}
