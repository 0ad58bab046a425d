"""Edges (records pushed) in the windows whose estimates reached their
subscriber inside the measured window, over all tenants, per second of
window: completed work, not accepted work."""
from bench.record import published_in_window, window_records


def read(run):
    return float(window_records(run)[published_in_window(run)].sum()
                 / run.seconds)
