"""Mean time of the WAL's group-commit fsync, once per engine cycle
(``/metrics`` ``phases_ms.wal_sync``, span ``sgrapp.wal_sync``)."""
from bench.served import mean_ms


def read(run):
    return mean_ms(run, "wal_sync")
