"""95th percentile of the same samples as ``estimate_p50_ms``, over all of
them."""
from bench.record import estimate_latencies_ms, percentile


def read(run):
    return percentile(estimate_latencies_ms(run), 0.95)
