"""Kernels (`ops/latent_attention.py::latent_read` under
`nn/latent.py::LatentAttention.forward_step_rows`): device self time a
decode step spends in the latent-attention mixers: the `XLA Ops` events
traced under `jax.named_scope("mla_decode")` (the low-rank projections,
both latent norms, the rotary parts, the record's write at the row's
cursor, the absorption of `W^K` into the queries, the read of the live
records (`%pt_mla_decode` where the kernel runs), `W^V` and the output
projection) that start inside a `pt_decode_step` run, over those runs.
None for a program without the scope, as the parent of the PR that
added it."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("mla_decode",),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[mla_decode_ms] {events} operations under mla_decode over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
