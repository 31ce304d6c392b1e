"""Kernels (`ops/retention.py::retention_chunked` under
`RetentionMixer.forward_chunk`): device self time a prefill spends in
the retention mixers' chunked form: the `XLA Ops` events traced under
`jax.named_scope("retention_scan")` that start inside a
`pt_prefill_<bucket>` run, over those runs (a mean over the buckets the
traced seconds happened to hold). Prints what share of the bf16 peak the
recurrence's own operations (the family's `retention_scan_flops`, the
padded bucket counted) reach in that time."""

import re
import sys

from benchmark.harness import program_scopes, program_spans as P

BUCKET = re.compile(r"^jit_pt_prefill_(\d+)\(")


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("retention_scan",),
                                        "pt_prefill_")
    if got is None:
        return None
    ms, events, runs = got
    fam, dims = run["family"], run["dims"]
    buckets = [int(m.group(1)) for m in
               (BUCKET.match(r["name"]) for r in runs) if m]
    flops = sum(fam.kinds(dims, "retention")
                * fam.retention_scan_flops(dims, b) for b in buckets)
    share = (flops / run["device"]["peaks"]["bf16_flops_per_s"]
             / (ms * 1e-3 * len(runs))) if buckets else float("nan")
    print(f"[retention_scan_ms] {events} operations under retention_scan "
          f"over {len(runs)} prefills (buckets {sorted(set(buckets))}): "
          f"{ms:.3f} ms of self time a prefill, {100 * share:.2f}% of the "
          f"bf16 peak for the recurrence's own operations",
          file=sys.stderr)
    return ms
