"""Host time to submit one dispatch: resolving and packing the closed
windows and planning their buckets (span ``sgrapp.pack``), then staging and
launching each bucket's program (``sgrapp.launch``); ``/metrics``
``phases_ms.submit`` over the window per dispatch made in it."""
from bench.served import per_dispatch_ms


def read(run):
    return per_dispatch_ms(run, "submit")
