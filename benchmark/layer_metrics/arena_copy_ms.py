"""Serving arena (`serving.py::BatchedDecoder`): device self time a decode
step spends copying: the `XLA Ops` events whose name starts with `%copy`
(`%copy`, `%copy-start`, `%copy-done`, and a fusion the compiler names
after the copy at its root) that start inside a `pt_decode_step` run,
over those runs. A step that takes the arena as a parameter it does not
own writes a whole new one, which is a `%copy` of every leaf; a step the
arena is donated to writes in place and keeps only the small copies
(layout changes of a row, operands moved between memories). It asks
nothing of the program, so every commit reads it. 0.0, not None, where
the steps ran and held no such operation."""

import bisect
import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    trace = P.load(run)
    if not trace or not trace.get("ops"):
        return None
    runs = program_scopes.runs_of(trace, "pt_decode_step")
    if not runs:
        return None
    starts = [m["start"] for m in runs]
    by_head = {}
    for e, ns in zip(trace["ops"], P.self_ns(trace["ops"])):
        if not e["name"].startswith("%copy"):
            continue
        i = bisect.bisect_right(starts, e["start"]) - 1
        if i < 0 or e["start"] >= runs[i]["start"] + runs[i]["dur"]:
            continue
        head = e["name"].split(".")[0].split(" ")[0]
        n, total = by_head.get(head, (0, 0))
        by_head[head] = n + 1, total + ns
    ms = sum(total for _, total in by_head.values()) / len(runs) / 1e6
    split = ", ".join(f"{head} {total / len(runs) / 1e6:.3f} ms ({n})"
                      for head, (n, total) in sorted(by_head.items()))
    print(f"[arena_copy_ms] {ms:.3f} ms of self time a step in copies over "
          f"{len(runs)} decode steps: {split or 'no copy operation'}",
          file=sys.stderr)
    return ms
