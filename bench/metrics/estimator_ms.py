"""Mean time, per reap, of advancing the estimators over the reaped windows
one jitted step at a time (``/metrics`` ``phases_ms.estimator``, span
``sgrapp.estimate``)."""
from bench.served import mean_ms


def read(run):
    return mean_ms(run, "estimator")
