"""Model (`nn/latent.py::HyperConnection` around both sublayers of a
`models/hybrid.py::HybridBlock`): device self time a decode step spends
in the hyper-connections' maps: the `XLA Ops` events traced under
`jax.named_scope("mhc_mix")` (the norm over the flattened state, the
product with `phi`, the sigmoids, the Sinkhorn rounds, the read-in of
the sublayer's input and the write-back over the streams, in float32)
that start inside a `pt_decode_step` run, over those runs: two maps a
block, small operations in a chain, so latency and not bytes. None for
a program without the scope."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("mhc_mix",),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[mhc_mix_ms] {events} operations under mhc_mix over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
