"""Share (%) of the sparse counter's wedge lanes that hold padding, over the
sparse-routed windows dispatched in the window: 100 * (1 - wedges /
wedge_lanes), where each window is given a lane of its bucket's wedge rung
``cap_w`` and holds its own deduped wedge count (``/metrics``
``executor.wedge_lanes`` and ``executor.wedges``)."""
from bench.served import count_delta


def read(run):
    lanes = count_delta(run, "executor", "wedge_lanes")
    wedges = count_delta(run, "executor", "wedges")
    if not lanes or wedges is None:
        return None
    return 100.0 * (1.0 - wedges / lanes)
