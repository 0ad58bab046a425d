"""Mean engine cycle of the server (``/metrics`` ``push_latency_ms``: one
coalesced batch's WAL append, fsync, engine apply and dispatch), over the
cycles that ended in the measured window."""
from bench.record import counter_delta


def read(run):
    d = counter_delta(run, ("aggregate", "push_latency_ms"))
    return None if d is None else d[0] / d[1]
