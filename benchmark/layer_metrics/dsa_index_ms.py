"""Kernels (`nn/latent.py::LatentAttention._index` and the scores and
pick of `ops/latent_attention.py` under
`LatentAttention.forward_step_rows`): device self time a decode step
spends in the latent mixers' indexers: the `XLA Ops` events traced
under `jax.named_scope("dsa_index")` (the indexer's three projections,
the index key's norm, rotary part and write at the row's cursor, the
index scores over the row's positions and the exact pick of
`index_topk` of them) that start inside a `pt_decode_step` run, over
those runs. The scope is a sibling of `mla_decode`, which keeps the
rest of the mixer and the read under the pick. None for a program
without the scope, as the parent of the PR that added it."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("dsa_index",),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[dsa_index_ms] {events} operations under dsa_index over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
