"""The spread of a cell's metrics over repeated runs, and the bound it
suggests.

    python3 bench/spread.py SET1.jsonl SET2.jsonl

Each file holds the result lines (the last line of ``bench/run.py``'s
output) of one set of runs of one cell.  For every metric it prints each
set's median and spread, the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median,
and five times the wider spread, clamped to [0.01, 0.25]: the bound that
``BENCHMARK.json`` would state.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def read_set(path: str) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            res = json.loads(line)
            for name, m in res.get("metrics", {}).items():
                out.setdefault(name, []).append(float(m["value"]))
    return out


def main(argv=None) -> int:
    paths = sys.argv[1:] if argv is None else argv
    sets = [read_set(p) for p in paths]
    names = sorted(set().union(*sets))
    for name in names:
        rows = [s[name] for s in sets if len(s.get(name, [])) >= 2]
        if not rows:
            continue
        spreads = [spread(v) for v in rows]
        print(json.dumps({
            "metric": name, "runs": [len(v) for v in rows],
            "medians": [statistics.median(v) for v in rows],
            "spreads": spreads,
            "bound": min(max(5 * max(spreads), 0.01), 0.25)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
