"""From the start of the benchmark's process to the start of the measured
window: JAX start-up, traffic set-up, server start and the warm-up, with
every compilation it needed."""


def read(run):
    return run.setup_s
