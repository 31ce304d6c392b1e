#!/usr/bin/env python3
"""Read one profiler trace by hand: print its planes, their lines and
the event names that took most time on each, and write a small
recording (a slice of every line, as ``trace_reduce.read_xplane`` gives
it) that the tests keep.

    python3 benchmark/tools/trace_summary.py <dir or .xplane.pb> <out.json> [slice_ms]
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    from benchmark.harness import trace_reduce as T

    path = argv[0]
    if os.path.isdir(path):
        path = max(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    trace = T.read_xplane(path)
    slice_ns = int(float(argv[2]) * 1e6) if len(argv) > 2 else 400_000_000
    starts = [e[1] for p in T.device_planes(trace)
              for e in T.line_events(p, T.OPS_LINE)]
    t0 = min(starts) + (max(starts) - min(starts)) // 2 if starts else 0
    small = {"planes": []}
    for p in trace["planes"]:
        print(f"plane {p['name']!r}: {len(p['lines'])} lines")
        keep = []
        for line in p["lines"]:
            ev = line["events"]
            total = sum(e[2] for e in ev) / 1e9
            print(f"  line {line['name']!r}: {len(ev)} events, "
                  f"{total:.4f} s summed")
            for name, sec in T.top_ops(ev, 12):
                print(f"      {sec:10.6f} s  {name[:140]}")
            cut = [[T.short_name(e[0]), e[1] - t0, e[2]] for e in ev
                   if t0 <= e[1] < t0 + slice_ns]
            if cut and (p["name"].startswith("/device:")
                        or any(e[0].startswith("bench.") for e in cut)):
                if not p["name"].startswith("/device:"):
                    cut = [e for e in cut if e[0].startswith("bench.")]
                keep.append({"name": line["name"], "events": cut})
        if keep:
            small["planes"].append({"name": p["name"], "lines": keep})
    with open(argv[1], "w") as f:
        json.dump(small, f)
    red = T.reduce(trace)
    if red:
        print("busy_s", red["busy_s"], "span_s", red["span_s"])
        print(json.dumps(red["breakdown"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
