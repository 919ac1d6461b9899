#!/usr/bin/env python3
"""Median and spread of each metric over repeated runs of one cell.

  python chipbench/spread.py RUN_OUTPUT [RUN_OUTPUT ...]

Reads every result line (a JSON object with ``metrics``) in the given
files, in order.  A spread is the distance between the first and third
quartiles (``statistics.quantiles(values, n=4)``) as a share of the
median.  Where leaving out the run farthest from the median narrows it,
that narrower spread is shown too, as the check does for tightness.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med) if med else float("inf")


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = values[:far] + values[far + 1:]
    return min(spread(values), spread(rest)) if len(rest) >= 2 else None


def results(paths):
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line.startswith("{") and '"metrics"' in line:
                    yield json.loads(line)


def main(argv) -> int:
    runs = list(results(argv))
    if len(runs) < 2:
        print("need two runs or more", file=sys.stderr)
        return 1
    names = sorted({k for r in runs for k in r["metrics"]})
    print(f"{len(runs)} runs; correct: {[r.get('correct') for r in runs]}")
    for n in names:
        v = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
        if len(v) < 2:
            continue
        s, t = spread(v), trimmed(v)
        print(f"{n}: median {statistics.median(v)!r} spread {s:.4f} "
              f"trimmed {t if t is None else round(t, 4)} "
              f"x5 {5 * s:.4f} values {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
