"""Kernels (`ops/latent_attention.py::span_pick` under
`LatentAttention.forward_chunk`): device self time a prefill spends in
the latent mixers' indexers: the `XLA Ops` events traced under
`jax.named_scope("dsa_index")` (the indexer's projections, the index
keys' norm, rotary part and write, each span's index scores,
`%pt_dsa_scores`, and its exact pick) that start inside a
`pt_prefill_<bucket>` run.

As `mla_prefill_ms`, the number is FOR ONE BUCKET, that of the
traffic's median prompt: each bucket's time a run is scaled by the
indexers' own operations at the median's bucket over those at its own
(the family's `dsa_prefill_index_flops`, every causal pair scored) and
the runs are averaged; every bucket's own line goes to stderr. None for
a program without the scope, and for traced seconds that held no
prefill."""

import re
import sys

from benchmark.harness import program_scopes, program_spans as P

BUCKET = re.compile(r"^jit_pt_prefill_(\d+)\(")


def read(run):
    if run.get("kind") != "serve":
        return None
    trace = P.load(run)
    fam, dims = run["family"], run["dims"]
    if not trace or not hasattr(fam, "dsa_prefill_index_flops"):
        return None
    buckets = sorted({int(m.group(1)) for m in (
        BUCKET.match(r["name"]) for r in
        program_scopes.runs_of(trace, "pt_prefill_")) if m})
    flops = lambda b: fam.dsa_prefill_index_flops(dims, b)
    step = run["config"]["serve"]["prompt_bucket"]
    at = -(-int(run["traffic"]["prompt_tokens"]["median"]) // step) * step
    total = n = 0
    for b in buckets:
        got = program_scopes.scope_ms_a_run(trace, ("dsa_index",),
                                            f"pt_prefill_{b}")
        if got is None:
            continue
        ms, events, runs = got
        total += len(runs) * ms * flops(at) / flops(b)
        n += len(runs)
        print(f"[dsa_prefill_index_ms] bucket {b}: {events} operations "
              f"under dsa_index over {len(runs)} prefills, {ms:.3f} ms of "
              f"self time a prefill", file=sys.stderr)
    if not n:
        return None
    print(f"[dsa_prefill_index_ms] {total / n:.3f} ms a prefill at bucket "
          f"{at} (each run scaled by the indexers' operations)",
          file=sys.stderr)
    return total / n
