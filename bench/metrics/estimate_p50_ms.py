"""Median, over every window closed in the measured window, of the time
from the due time of the push that closed it to its estimate's arrival."""
from bench.record import estimate_latencies_ms, percentile


def read(run):
    return percentile(estimate_latencies_ms(run), 0.50)
