"""Mean host time blocked on device counts per reap (``/metrics``
``reap_wait_ms``), over the reaps in the measured window."""
from bench.record import counter_delta


def read(run):
    d = counter_delta(run, ("aggregate", "reap_wait_ms"))
    return None if d is None else d[0] / d[1]
