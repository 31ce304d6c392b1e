"""Kernels (`nn/moe.py::dropless_moe`, the grouped product): device self
time a decode step spends in the routed experts: the `XLA Ops` events
traced under `jax.named_scope("moe_experts")` (gather by expert, the
three grouped matmuls, the weighted scatter back) and the compiler's own
`%ragged-dot*` operations (the kernels `lax.ragged_dot` becomes, which
carry no scope) that start inside a `pt_decode_step` run, over those
runs. Routing (`moe_route`) and the shared MLP (`moe_shared`) are not in
it."""

import sys

from benchmark.harness import program_scopes, program_spans as P

SCOPES, HEADS = ("moe_experts",), ("%ragged-dot", "%ragged_dot")


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), SCOPES,
                                        "pt_decode_step", HEADS)
    if got is None:
        return None
    ms, events, runs = got
    print(f"[moe_experts_ms] {events} operations under moe_experts over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
