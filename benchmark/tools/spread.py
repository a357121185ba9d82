#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over sets of runs, as the bound's
rule reads it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.

    python3 benchmark/tools/spread.py <rows> <dir> [<dir> ...]

Each ``<dir>`` holds one set: the ``*.t0.out`` files that
``benchmark/tools/chip_runs.sh`` leaves.  ``<rows>`` is the cell's training
rows a fit.  Beside the whole window it reads the same runs cut to shorter
windows from ``observed.fit_ends_s`` (a window ends when the fit that was
running at its length completes), to show what a shorter ``run_seconds``
would have given.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def rate_at(ends: list, rows: int, seconds: float) -> float:
    """Rows a second of the window that ``--seconds seconds`` would have
    made of these fits."""
    for done, end in enumerate(ends, 1):
        if end >= seconds:
            return rows * done / end
    return rows * len(ends) / ends[-1]


def main(argv) -> int:
    rows = int(argv[1])
    for directory in argv[2:]:
        runs = []
        for path in sorted(glob.glob(os.path.join(directory, "*.t0.out"))):
            with open(path) as f:
                lines = f.read().strip().splitlines()
            if lines:
                runs.append(json.loads(lines[-1]))
        print(f"{directory}: {len(runs)} runs, correct {sum(r['correct'] for r in runs)}, "
              f"failed fits {sum(r['failed'] for r in runs)}")
        names = sorted({n for r in runs for n in r["metrics"]})
        for name in names:
            values = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            print(f"  {name}: median {statistics.median(values):.6g} spread {100 * spread(values):.3f}% "
                  f"min {min(values):.6g} max {max(values):.6g}")
        for seconds in (10, 20):
            values = [rate_at(r["observed"]["fit_ends_s"], rows, seconds) for r in runs]
            print(f"  rate over the first {seconds} s: median {statistics.median(values):.6g} "
                  f"spread {100 * spread(values):.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
