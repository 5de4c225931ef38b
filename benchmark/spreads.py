#!/usr/bin/env python3
"""The spreads a bound is set from, out of the result lines of two sets of
runs of one cell.

    python3 benchmark/spreads.py SET1_FILE... -- SET2_FILE...

Each file is the standard output of one ``run.py``; its last line is read.
Per metric it prints both sets' medians and spreads (interquartile distance
over the median, ``harness/stats.spread``), the wider of the two (a bound is
about five times the widest over the cells), the mean of the two with each
set's run farthest from its median left out (a bound under twice that is
too tight), and the second median against the first.  Touches no device.
"""

from __future__ import annotations

import json
import sys

from harness import stats


def last_line(path: str) -> dict:
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])


def trimmed(values):
    mid = stats.median(values)
    far = max(values, key=lambda v: abs(v - mid))
    rest = list(values)
    rest.remove(far)
    return rest


def main(argv) -> int:
    if "--" not in argv:
        print(__doc__, file=sys.stderr)
        return 2
    cut = argv.index("--")
    sets = [[last_line(p) for p in part]
            for part in (argv[:cut], argv[cut + 1:])]
    wrong = [r for rows in sets for r in rows if not r["correct"]]
    print(f"runs {[len(rows) for rows in sets]}, not correct {len(wrong)}")
    for name in sets[0][0]["metrics"]:
        vals = [[r["metrics"][name]["value"] for r in rows] for rows in sets]
        med = [stats.median(v) for v in vals]
        spr = [stats.spread(v) for v in vals]
        tight = sum(stats.spread(trimmed(v)) for v in vals) / 2
        print(f"{name}: medians {med[0]:.6g} {med[1]:.6g} "
              f"(second {100 * (med[1] - med[0]) / med[0]:+.3f}%), "
              f"spreads {100 * spr[0]:.3f}% {100 * spr[1]:.3f}%, "
              f"wider {100 * max(spr):.3f}%, "
              f"trimmed mean {100 * tight:.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
