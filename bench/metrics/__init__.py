"""Metric readers, one module per metric, found by name.

The benchmark looks for ``bench/metrics/<name>.py`` and, failing that, for
``bench/metrics/<stem>.py`` where ``<stem>`` is the name before its first
dot: ``engine_cycle_ms.catchup`` and ``engine_cycle_ms.live`` are one
quantity, split by the end-to-end metric each moves.  A reader is
``read(run: bench.record.Run) -> float | None``; None means it found
nothing to read, and the metric is left out of the result line.
"""
