#!/usr/bin/env python3
"""What a serving trace's prefill programs spent, bucket by bucket.

    python3 tools/prefill_trace.py <checkout that holds .bench_trace> [out.json]

Reads the newest ``.xplane.pb`` a ``benchmark/run.py --trace 1`` run of a
serving cell left under ``<checkout>/.bench_trace`` (with that checkout's
own ``benchmark/harness`` readers) and prints, for every
``pt_prefill_<bucket>`` program in it: its runs, the median device time
of a run (the ``XLA Modules`` event), the median ``serve.prefill`` span
(``prefill_ms`` by bucket), and the self time a run of the operations
whose result has ONE ROW: every array of the result has at most one
dimension above 1, and that one is not the bucket (a chunk's per-position
sums are the chunk's; so are, at bucket 1024, the one-row products of that
width, which this rule then misses). In a program that steps the last
prompt token through the whole model after its chunk these are that
step: one-row products that each stream a whole weight. ``out.json`` gets every
operation of every bucket (name, result, self ms a run, one_row) for a
reader who wants another rule. Nothing here is a benchmark metric."""

import json
import os
import re
import sys


def result_dims(name: str):
    """The dimension lists of the arrays an HLO instruction's text says
    it returns (a tuple's elements each), or None where it gives none."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return None
    result, depth = rest.split(" ", 1)[0], 0
    for i, ch in enumerate(rest if rest.startswith("(") else ""):
        depth += (ch == "(") - (ch == ")")
        if not depth:          # a tuple: to its closing parenthesis
            result = rest[:i + 1]
            break
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", result)]


def one_row(name: str, bucket: int) -> bool:
    dims = result_dims(name)
    if not dims:
        return False
    for shape in dims:
        big = [d for d in shape if d > 1]
        if len(big) > 1 or (big and big[0] == bucket):
            return False
    return True


def main(argv):
    root = os.path.abspath(argv[0])
    sys.path.insert(0, root)
    from benchmark.harness import program_scopes as S
    from benchmark.harness import program_spans as P
    from benchmark.harness import trace_reduce as T

    trace = P.read_xplane(P.newest_xplane(root))
    self_ns = P.self_ns(trace["ops"])
    spans = {}
    for s in P.named(trace["host"], "serve.prefill"):
        spans.setdefault(str(s["stats"].get("bucket")), []).append(s["dur"])
    table = {}
    buckets = sorted({int(m["name"].split("pt_prefill_")[1].split("(")[0])
                      for m in S.runs_of(trace, "pt_prefill_")
                      if re.match(r"jit_pt_prefill_\d+\(", m["name"])})
    for lb in buckets:
        runs = S.runs_of(trace, f"pt_prefill_{lb}")
        edges = [(m["start"], m["start"] + m["dur"]) for m in runs]
        rows = {}
        for e, ns in zip(trace["ops"], self_ns):
            if any(a <= e["start"] < b for a, b in edges):
                row = rows.setdefault(e["name"], [0, 0])
                row[0] += ns
                row[1] += 1
        ops = sorted(({"op": T.short_name(name),
                       "result": name.partition(" = ")[2][:160],
                       "ms_a_run": ns / len(runs) / 1e6, "events": n,
                       "one_row": one_row(name, lb)}
                      for name, (ns, n) in rows.items()),
                     key=lambda r: -r["ms_a_run"])
        table[lb] = ops
        thin = [r for r in ops if r["one_row"]]
        fams = {}
        for r in thin:
            fam = re.sub(r"\.\d+ ", " ", r["op"] + " ")
            fams[fam] = fams.get(fam, 0) + r["ms_a_run"]
        print(f"pt_prefill_{lb}: {len(runs)} runs, device "
              f"{P.median_ms([m['dur'] for m in runs]):.3f} ms a run in "
              f"the median, serve.prefill "
              f"{P.median_ms(spans.get(str(lb), [0])):.3f} ms "
              f"(n={len(spans.get(str(lb), []))}); operations "
              f"{sum(r['ms_a_run'] for r in ops):.3f} ms a run, of which "
              f"one-row results {sum(r['ms_a_run'] for r in thin):.3f} ms "
              f"over {len(thin)} of {len(ops)} operations: "
              + ", ".join(f"{k.strip()} {v:.3f}" for k, v in sorted(
                  fams.items(), key=lambda kv: -kv[1])[:8]))
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump(table, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
