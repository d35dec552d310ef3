#!/usr/bin/env python3
"""Medians and spreads of a cell's runs, as the driver reads them.

    python3 perfbench/tools/spread.py <file.jsonl> [runs per set = 6]

For each metric and each set of runs: the median and the spread (distance
between the quartiles over the median); then the wider spread, five times it
(what a bound is set to, never under 1%), and how far the second set's median
is from the first's.
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    per_set = int(argv[1]) if len(argv) > 1 else 6
    with open(argv[0]) as f:
        runs = [json.loads(line) for line in f if line.startswith("{")]
    assert all(r["correct"] and r["failed"] == 0 for r in runs), "a run was not correct"
    sets = [runs[i:i + per_set] for i in range(0, len(runs), per_set)]
    for name in runs[0]["metrics"]:
        medians, spreads = [], []
        for s in sets:
            values = [r["metrics"][name]["value"] for r in s]
            medians.append(statistics.median(values))
            spreads.append(spread(values) if len(values) > 1 else float("nan"))
        drift = (medians[1] - medians[0]) / medians[0] if len(medians) > 1 else float("nan")
        print(f"{name}: medians {[round(m, 4) for m in medians]}, spreads "
              f"{[f'{100 * s:.3f}%' for s in spreads]}, 5 x widest "
              f"{500 * max(spreads):.2f}%, second set's median off by {100 * drift:+.3f}%")


if __name__ == "__main__":
    main(sys.argv[1:])
