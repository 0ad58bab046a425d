"""99th percentile, over every push due in the measured window, of the
time from its due time to its ack (which the server sends only after the
WAL fsync).  A rejected push counts to the end of the drain."""
from bench.record import ack_latencies_ms, percentile


def read(run):
    return percentile(ack_latencies_ms(run), 0.99)
