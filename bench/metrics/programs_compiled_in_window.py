"""Programs JAX compiled or loaded from its persistent cache inside the
measured window (its ``backend_compile_duration`` events, which fire for
both): each is a bucket shape the warm-up did not reach."""


def read(run):
    return float(sum(run.t0 <= t < run.t1 for t in run.compiles))
