"""Kernels (`nn/gated_attention.py::GatedAttention.forward_step_rows`
without a window): device self time a decode step spends in the
full-attention mixers: the `XLA Ops` events traced under
`jax.named_scope("gqa_full_step")` (the projections, the rotary part,
the gate, the key's and value's write at the row's cursor, the read of
the live positions (`%pt_flash_decode` where the kernel runs), the
output projection) that start inside a `pt_decode_step` run, over those
runs. None for a program without the scope, as the parent of the PR
that added it."""

import sys

from benchmark.harness import program_scopes, program_spans as P

SCOPE = "gqa_full_step"


def read(run, scope=SCOPE):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), (scope,),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[{scope}_ms] {events} operations under {scope} over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
