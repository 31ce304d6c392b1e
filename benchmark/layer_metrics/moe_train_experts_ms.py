"""Kernels (`nn/moe.py::dropless_moe` in a TRAINING step): device self
time a `pt_train_step` run spends in the routed experts in all three
passes (forward, remat's second forward, backward): the `XLA Ops`
events traced under the scope `moe_experts` (the gather of the pairs'
rows by expert, the three grouped products, the weighted gather back,
and their transposes: scatters and two more grouped products a matrix)
and the compiler's own `%ragged-dot*` operations, which may carry no
scope. Routing (`moe_route`) and the shared experts (`moe_shared`) are
not in it. None for a program without the scope."""

import sys

from benchmark.harness import program_scopes, program_spans as P

SCOPES, HEADS = ("moe_experts",), ("%ragged-dot", "%ragged_dot")


def read(run):
    if run.get("kind") != "train":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), SCOPES,
                                        "pt_train_step", HEADS)
    if got is None:
        return None
    ms, events, runs = got
    print(f"[moe_train_experts_ms] {events} operations under moe_experts "
          f"over {len(runs)} steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
