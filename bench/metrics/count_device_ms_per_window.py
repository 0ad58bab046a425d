"""Device-operation time in the traced window per window whose estimate
was published in it (ms).  The device does nothing but count, so this is
the counter kernels' time per window."""
from bench.record import published_in_window


def read(run):
    n = int(published_in_window(run).sum())
    if run.trace is None or n == 0:
        return None
    return 1e3 * run.trace["busy_s"] / n
