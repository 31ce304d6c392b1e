"""Kernels (`ops/latent_attention.py::causal_attention` under
`LatentAttention.forward_chunk`): device self time a prefill spends in
the latent-attention mixers, decompressed: the `XLA Ops` events traced
under `jax.named_scope("mla_prefill")` (projections, norms, rotary
parts, the records' write, `W_kvb` over the whole chunk, the causal
attention of heads of 192 / 128, the output projection) that start
inside a `pt_prefill_<bucket>` run.

Which buckets the traced seconds hold is the deck's draw (three to
five prefills of 4096 to 14336 tokens), and a mixer's time grows with
the square of the bucket, so a plain mean over them follows the draw
(87 and 142 ms on two seeds of one program). The number is therefore
FOR ONE BUCKET, that of the traffic's median prompt: each bucket's
time a run is scaled by the mixers' own operations at the median's
bucket over those at its own (the family's `mla_prefill_flops`) and
the runs are averaged; every bucket's own line goes to stderr with the
share of the bf16 peak its operations reach. None for a program
without the scope, and for traced seconds that held no prefill."""

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
    buckets = sorted({int(m.group(1)) for m in (
        BUCKET.match(r["name"]) for r in
        program_scopes.runs_of(trace, "pt_prefill_")) if m})
    fam, dims = run["family"], run["dims"]
    flops = lambda b: fam.kinds(dims, "latent") * fam.mla_prefill_flops(
        dims, b)
    step = run["config"]["serve"]["prompt_bucket"]
    at = -(-int(run["traffic"]["prompt_tokens"]["median"]) // step) * step
    peak = run["device"]["peaks"]["bf16_flops_per_s"]
    total = n = 0
    for b in buckets:
        got = program_scopes.scope_ms_a_run(trace, ("mla_prefill",),
                                            f"pt_prefill_{b}")
        if got is None:
            continue
        ms, events, runs = got
        total += len(runs) * ms * flops(at) / flops(b)
        n += len(runs)
        print(f"[mla_prefill_ms] bucket {b}: {events} operations under "
              f"mla_prefill over {len(runs)} prefills, {ms:.3f} ms of "
              f"self time a prefill, {100 * flops(b) / peak / (ms * 1e-3):.2f}"
              f"% of the bf16 peak for the mixers' own operations",
              file=sys.stderr)
    if not n:
        return None
    print(f"[mla_prefill_ms] {total / n:.3f} ms a prefill at bucket {at} "
          f"(each run scaled by the mixers' operations)", file=sys.stderr)
    return total / n
