"""The control: the reference put in the served path's place with one of
the configuration's guarantees broken, which the comparison must refuse.

    python3 bench/control.py --workload movielens-win8k.catchup \\
        --pushes 128 --seeds 1,2,3

The configurations state exactly-once delivery: every acked record lies
in a counted window or in the open one.  The control delivers at most once:
one acked push, drawn from the seed among the first half, is never applied,
as a server that acked a batch and then lost it would.  (Applying a push
twice is no control here: its timestamps would run backwards, and the
windowizer refuses that.)  The control's windows and estimates stand for
what was published, the reference of the pushes acked stands for what
should have been, and :func:`compare` must find them apart.  ``--pushes`` is the pushes each tenant had acked, as many as a
run of the cell acks.  Each seed prints the compared numbers; the least of
them over seeds is the upper reading of each limit (``PERF.md``).  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.reference import compare, replay  # noqa: E402
from bench.streams import TenantStream, stream_params  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))


def control_numbers(cfg: dict, seed: int, pushes: int) -> dict:
    """The compared numbers of the at-most-once control for one seed."""
    rng = np.random.default_rng([seed % 2**64, 7])
    published, reference, closed = [], [], []
    for s in range(int(cfg["tenants"])):
        st = TenantStream(stream_params(cfg), cfg["nt_w"], seed, s,
                          int(cfg["push_records"]))
        cols = [st.push(k) for k in range(pushes)]
        lost = int(rng.integers(0, max(pushes // 2, 1)))
        applied = cols[:lost] + cols[lost + 1:]
        args = (int(cfg["nt_w"]), float(cfg["alpha0"]))
        ref = replay(*(np.concatenate(c) for c in zip(*cols)), *args)
        bad = replay(*(np.concatenate(c) for c in zip(*applied)), *args)
        published.append({"window": np.arange(bad["count"].shape[0]),
                          **{k: bad[k] for k in ("count", "estimate",
                                                 "cum_sgrs", "end_tau")}})
        reference.append(ref)
        closed.append(bad["count"].shape[0])
    return compare(published, reference, closed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pushes", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        cfg = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "pushes": args.pushes,
                          **control_numbers(cfg, seed, args.pushes)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
