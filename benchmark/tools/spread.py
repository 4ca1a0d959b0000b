"""Medians and spreads of a set of runs, as the bound's rule reads them.

    python3 -m benchmark.tools.spread <file.out> [<file.out> ...]

Each file holds one run's standard output (its last line the result). A
spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

from __future__ import annotations

import json
import statistics
import sys


def last_result(path: str) -> dict | None:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    for ln in reversed(lines):
        obj = json.loads(ln)
        if "correct" in obj and "metrics" in obj:
            return obj
    return None


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv: list[str]) -> int:
    results = [(p, last_result(p)) for p in argv[1:]]
    missing = [p for p, r in results if r is None]
    runs = [r for _, r in results if r is not None]
    names = sorted({n for r in runs for n in r["metrics"]})
    print(json.dumps({"runs": len(runs), "no_result": missing,
                      "correct": [r["correct"] for r in runs],
                      "failed": [r["failed"] for r in runs]}))
    for name in names:
        vals = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
        row = {"metric": name, "n": len(vals), "values": [round(v, 3) for v in vals]}
        if len(vals) >= 2:
            row.update(median=statistics.median(vals), spread=round(spread(vals), 4))
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
