#!/usr/bin/env python3
"""Spread of each end-to-end metric over the runs of
``chip_sets.sh``: per set the median and the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median; the wider of the two is what a bound is five times of.

    python3 benchmark/tools/spread.py chiprun_out/sets/<cell>
"""

import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    from benchmark.harness.stats import iqr_share

    sets = {}
    for path in sorted(glob.glob(os.path.join(argv[0], "set*.out"))):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            print(f"{path}: no result")
            continue
        line = json.loads(lines[-1])
        tag = os.path.basename(path).split(".")[0]
        sets.setdefault(tag, []).append(line)
        if not line["correct"] or line["failed"]:
            print(f"{path}: correct={line['correct']} "
                  f"failed={line['failed']}")
    widest = {}
    for tag, lines in sorted(sets.items()):
        for name in lines[0]["metrics"]:
            vals = [ln["metrics"][name]["value"] for ln in lines]
            spread = iqr_share(vals) if len(vals) >= 2 else float("nan")
            widest[name] = max(widest.get(name, 0.0), spread)
            print(f"{tag} {name}: n={len(vals)} median "
                  f"{statistics.median(vals):.6g} spread {spread:.4%} "
                  f"min {min(vals):.6g} max {max(vals):.6g}")
    for name, s in widest.items():
        print(f"widest {name}: {s:.4%}  x5 = {5 * s:.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
