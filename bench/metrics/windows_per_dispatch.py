"""Windows counted per bucketed dispatch over the window: the increase of
``/metrics`` ``windows_dispatched`` over that of ``dispatch_count``, which
the engine counts for every dispatch, its own included."""
from bench.served import count_delta


def read(run):
    n = count_delta(run, "dispatch_count")
    w = count_delta(run, "windows_dispatched")
    if not n or w is None:
        return None
    return w / n
