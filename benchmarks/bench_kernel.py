"""Butterfly-kernel micro-bench: tiled-JAX vs dense-Gram vs (interpret-mode)
Pallas on window-sized biadjacencies; derived column = GMAC/s of the Gram
contraction (the kernel's roofline axis).  A second section benches the
window executor end-to-end per tier on a windowized stream (bucketed
capacities — the production dispatch path).

Runs standalone as the CI smoke check:

    PYTHONPATH=src python benchmarks/bench_kernel.py --quick
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.butterfly import count_butterflies_dense, count_butterflies_tiled
from repro.core.executor import WindowExecutor
from repro.launch.compile_cache import use_compile_cache
from repro.streams import bipartite_pa_stream

__all__ = ["run"]


def run(*, quick: bool = False) -> list[tuple]:
    rows = []
    rng = np.random.default_rng(0)
    shapes = [(512, 1024, 0.02)] if quick else [
        (1024, 2048, 0.01), (2048, 4096, 0.005)]
    for n_i, n_j, dens in shapes:
        adj = jnp.asarray((rng.random((n_i, n_j)) < dens), jnp.float32)
        macs = n_i * n_i * n_j / 2

        dense = jax.jit(count_butterflies_dense)
        tiled = jax.jit(lambda a: count_butterflies_tiled(a, tile=512))
        jax.block_until_ready(dense(adj)); jax.block_until_ready(tiled(adj))
        for name, fn in [("dense", dense), ("tiled512", tiled)]:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(adj))
            dt = time.perf_counter() - t0
            rows.append((f"kernel/{name}_{n_i}x{n_j}", dt * 1e6,
                         f"{macs / dt / 1e9:.2f} GMAC/s"))

    # -- executor dispatch per tier (bucketed window batch) --------------------
    n = 2_000 if quick else 8_000
    s = bipartite_pa_stream(n, temporal="uniform", n_unique=n // 5, seed=3)
    wb = s.windowize(60)
    tiers = ("dense", "tiled") if quick else ("dense", "tiled", "pallas")
    for tier in tiers:
        ex = WindowExecutor(tier)
        ex.window_counts(wb)  # compile buckets
        t0 = time.perf_counter()
        counts = ex.window_counts(wb)
        dt = time.perf_counter() - t0
        rows.append((f"kernel/executor_{tier}", dt * 1e6,
                     f"{wb.n_windows / dt:.0f} win/s sum={counts.sum():.0f}"))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes + fewer tiers (CI smoke check)")
    args = ap.parse_args()
    use_compile_cache()
    print("name,us_per_call,derived")
    for name, us, derived in run(quick=args.quick):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
