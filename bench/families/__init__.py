"""Stream families: one module per family, found by the name a
configuration's ``stream.family`` gives.  Each exposes
``generate(n_records, seed, **params) -> (tau, edge_i, edge_j)``, sorted by
``tau``.  They are the benchmark's own copies of the generators in
``repro.streams.generators``, so a change to the program cannot move the
traffic."""
