"""Kernels (`nn/gated_attention.py::GatedAttention.forward_chunk`):
device self time a prefill spends in the attention mixers of both
kinds: the `XLA Ops` events traced under
`jax.named_scope("gqa_full_prefill")` or `("gqa_window_prefill")`
(projections, rotary parts, the causal or banded flash kernel over the
chunk, the gate, the chunk's write into the cache or the last window's
into the ring, the output projection) that start inside a
`pt_prefill_<bucket>` run.

Which buckets the traced seconds hold is the deck's draw, and a full
layer's time grows with the square of the bucket, so the number is FOR
ONE BUCKET, that of the traffic's median prompt, as `mla_prefill_ms`
does it: each bucket's time a run is scaled by the mixers' own
operations at the median's bucket over those at its own (the family's
`gqa_prefill_flops`, both kinds) and the runs are averaged; every
bucket's own line goes to stderr. None for a program without the
scopes, and for traced seconds that held no prefill."""

import re
import sys

from benchmark.harness import program_scopes, program_spans as P

BUCKET = re.compile(r"^jit_pt_prefill_(\d+)\(")
SCOPES = ("gqa_full_prefill", "gqa_window_prefill")


def read(run):
    if run.get("kind") != "serve":
        return None
    trace = P.load(run)
    if not trace:
        return None
    buckets = sorted({int(m.group(1)) for m in (
        BUCKET.match(r["name"]) for r in
        program_scopes.runs_of(trace, "pt_prefill_")) if m})
    fam, dims = run["family"], run["dims"]
    flops = lambda b: sum(
        fam.kinds(dims, k) * fam.gqa_prefill_flops(dims, k, b)
        for k in ("full_attention", "sliding_attention"))
    step = run["config"]["serve"]["prompt_bucket"]
    at = -(-int(run["traffic"]["prompt_tokens"]["median"]) // step) * step
    peak = run["device"]["peaks"]["bf16_flops_per_s"]
    total = n = 0
    for b in buckets:
        got = program_scopes.scope_ms_a_run(trace, SCOPES,
                                            f"pt_prefill_{b}")
        if got is None:
            continue
        ms, events, runs = got
        total += len(runs) * ms * flops(at) / flops(b)
        n += len(runs)
        print(f"[gqa_prefill_ms] bucket {b}: {events} operations under "
              f"both prefill scopes over {len(runs)} prefills, {ms:.3f} ms "
              f"of self time a prefill, "
              f"{100 * flops(b) / peak / (ms * 1e-3):.2f}% of the bf16 "
              f"peak for the mixers' own operations", file=sys.stderr)
    if not n:
        return None
    print(f"[gqa_prefill_ms] {total / n:.3f} ms a prefill at bucket {at} "
          f"(each run scaled by the mixers' operations)", file=sys.stderr)
    return total / n
