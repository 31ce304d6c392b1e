"""Serving arena (`serving.py::BatchedDecoder._prefill_fn`): median
device duration (`XLA Modules`) of the runs of `pt_prefill_<b>`, b the
bucket the traffic's median prompt pads to; every bucket's median goes
to stderr. The program's own time, whatever the host waited for around
it: what `prefill_ms` read until its span began to wait out the step in
flight. It asks nothing of the program, so every commit reads it. None
where the traced seconds held no prefill of that bucket."""

import re
import sys

from benchmark.harness import program_scopes, program_spans as P

BUCKET = re.compile(r"^jit_pt_prefill_(\d+)\(")


def read(run):
    if run.get("kind") != "serve":
        return None
    trace = P.load(run)
    if not trace:
        return None
    by_bucket = {}
    for m in program_scopes.runs_of(trace, "pt_prefill_"):
        hit = BUCKET.match(m["name"])
        if hit:
            by_bucket.setdefault(int(hit.group(1)), []).append(m["dur"])
    step = run["config"]["serve"]["prompt_bucket"]
    at = -(-int(run["traffic"]["prompt_tokens"]["median"]) // step) * step
    print(f"[prefill_run_ms] median device ms a run by bucket: "
          + (", ".join(f"{b} {P.median_ms(d):.3f} ({len(d)})"
                       for b, d in sorted(by_bucket.items()))
             or "no prefill in the trace")
          + f"; the median prompt's bucket is {at}", file=sys.stderr)
    return P.median_ms(by_bucket.get(at, ()))
