"""Find the knee of an open-loop cell: run it at a few fixed total rates
in one process and print, for each, the rate offered, the edges published
per second, the estimate latency and its trend over the window.

    python3 bench/sweep.py --workload netflix-win512.live \\
        --rates 20000,40000,80000 --seconds 20 --seed 7

The knee is the highest rate at which the published edges keep pace with
the offered ones and the estimate lag does not trend up.  The cell's
traffic file then states four fifths of it as a number.  The benchmark's
own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench_run.CACHE_DIR
    import jax

    bench_run.use_cache(jax)
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    traffic = bench_run.load_json("traffic", f"{cell['traffic']}.json")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(traffic, rate_edges_per_s=rate)
        res = bench_run.run_cell(bench, args.workload, args.seed + k,
                                 args.seconds, False,
                                 configs={cell["traffic"]: traffic})
        print(json.dumps({"sweep_rate": rate, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
