"""Record the small trace that ``test_trace.py`` reduces.

    python3 bench/tests/make_trace.py OUT_DIR      # on a TPU

Inside a host span named ``bench_window`` it runs a jitted matmul three
times, with a host span ``host_wait`` (a 20 ms sleep) between the second
and the third, so the trace holds device operations, an idle gap the host
span explains, and the window mark.  The ``.xplane.pb`` lands under
``OUT_DIR/plugins/profile/<time>/``.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench.trace import WINDOW_SPAN

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for k in range(3):
            if k == 2:
                with jax.profiler.TraceAnnotation("host_wait"):
                    time.sleep(0.02)
            f(x).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
