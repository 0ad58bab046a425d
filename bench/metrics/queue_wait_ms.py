"""Mean time a push waits between its admission into the ingress queue and
the start of the engine cycle that applies it (``/metrics``
``phases_ms.queue_wait``): the coalescing window, the wait behind the cycle
in progress, and the hop to the engine thread."""
from bench.served import mean_ms


def read(run):
    return mean_ms(run, "queue_wait")
