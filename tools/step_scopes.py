#!/usr/bin/env python3
"""Where a serving trace's programs spent their device time, by the
program's own scopes.

    python3 tools/step_scopes.py <checkout that holds .bench_trace> [program prefix ...]

Reads the newest ``.xplane.pb`` a ``benchmark/run.py --trace 1`` run of a
serving cell left under ``<checkout>/.bench_trace`` (with that
checkout's own ``benchmark/harness`` readers) and prints, for the runs
of each program prefix (default ``pt_decode_step`` and ``pt_prefill_``),
the self time a run of the operations under each of the known scopes
(the first of :data:`SCOPES` found in the operation's ``op_name``) with
each scope's five largest operation families, and
for the rest, under ``other``, the ten largest operation families with
the tail of their ``op_name``: a fusion carries its root's scope, so
work the compiler fused across a scope's edge shows here or under the
neighbour. Nothing here is a benchmark metric."""

import bisect
import os
import re
import sys

SCOPES = ("pt_mla_decode", "mla_decode", "mla_prefill", "mhc_mix",
          "moe_route", "moe_experts", "moe_shared", "ssm_step", "ssm_scan",
          "retention_step", "retention_scan", "pt_flash_decode")


def main(argv):
    root = os.path.abspath(argv[0])
    sys.path.insert(0, root)
    from benchmark.harness import program_scopes as S
    from benchmark.harness import program_spans as P
    from benchmark.harness import trace_reduce as T

    trace = P.read_xplane(P.newest_xplane(root))
    names = P.op_names(trace["path"]) if trace.get("path") else {}
    self_ns = P.self_ns(trace["ops"])
    for prefix in argv[1:] or ("pt_decode_step", "pt_prefill_"):
        runs = S.runs_of(trace, prefix)
        if not runs:
            print(f"{prefix}: no run in the trace")
            continue
        starts = [m["start"] for m in runs]
        by_scope, other, inside = {}, {}, {}
        for e, ns in zip(trace["ops"], self_ns):
            i = bisect.bisect_right(starts, e["start"]) - 1
            if i < 0 or e["start"] >= runs[i]["start"] + runs[i]["dur"]:
                continue
            op = names.get(e["name"], e["stats"].get("tf_op", ""))
            scope = next((s for s in SCOPES if s in op), None)
            fam = re.sub(r"\.\d+", "", T.short_name(e["name"]))
            if scope:
                by_scope[scope] = by_scope.get(scope, 0) + ns
                fams = inside.setdefault(scope, {})
                fams[fam] = fams.get(fam, 0) + ns
                continue
            row = other.setdefault(fam, [0, op])
            row[0] += ns
        n = len(runs)
        ms = lambda ns: ns / n / 1e6
        print(f"{prefix}: {n} runs, device "
              f"{P.median_ms([m['dur'] for m in runs]):.3f} ms a run in the "
              f"median; self time a run by scope: "
              + ", ".join(f"{k} {ms(v):.3f}" for k, v in sorted(
                  by_scope.items(), key=lambda kv: -kv[1]))
              + f"; other {ms(sum(v[0] for v in other.values())):.3f}")
        for scope, fams in inside.items():
            print(f"    {scope}: " + ", ".join(
                f"{k} {ms(v):.3f}" for k, v in sorted(
                    fams.items(), key=lambda kv: -kv[1])[:5]))
        for fam, (ns, op) in sorted(other.items(),
                                    key=lambda kv: -kv[1][0])[:10]:
            print(f"    other {fam}: {ms(ns):.3f} ms a run  ...{op[-90:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
