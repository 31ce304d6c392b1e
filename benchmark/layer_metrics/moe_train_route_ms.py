"""Kernels (`nn/moe.py::dropless_moe` in a TRAINING step): device self
time a `pt_train_step` run spends under the scope `moe_route` in all
three passes (forward, remat's second forward, backward): the router's
product, sigmoid and top-k, the counts and the load, and (the grouped
body) the sort of the (token, pick) pairs by expert, its inverse and
their transposes; the bias rule's own step is `moe_bias_update` in the
scope table. None for a program without the scope."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "train":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("moe_route",),
                                        "pt_train_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[moe_train_route_ms] {events} operations under moe_route over "
          f"{len(runs)} steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
