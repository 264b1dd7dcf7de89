#!/usr/bin/env python3
"""Median and spread of each metric over a set of result lines.

    python bench/tools/spread.py run1.out run2.out ...

Each file's last line is a result line of ``bench/run.py``. The spread
is the distance between the first and third quartiles, as
``statistics.quantiles(values, n=4)`` gives them, over the median: the
measure the regression bounds are set from.
"""

from __future__ import annotations

import json
import statistics
import sys


def last_result(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(paths) -> int:
    results = [last_result(p) for p in paths]
    names = sorted({m for r in results for m in r["metrics"]})
    print(f"{len(results)} runs; correct in "
          f"{sum(r['correct'] for r in results)}")
    for name in names:
        vals = [r["metrics"][name]["value"] for r in results
                if name in r["metrics"]]
        line = f"{name}: median {statistics.median(vals)!r} over {len(vals)}"
        if len(vals) >= 2:
            line += f", spread {spread(vals):.5f}, min {min(vals)!r}, " \
                    f"max {max(vals)!r}"
        print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
