"""Model (`ops/fused_loss.py::linear_cross_entropy`, the fused head and
loss): device self time a train step spends in the operations traced
under `jax.named_scope("linear_ce")`, forward and backward: the `XLA Ops`
events one of whose stats names the scope, over the `pt_train_step` runs
of the trace. Self time, because the head's two `while` loops and the
operations of their bodies are all events of the one line."""

import sys

from benchmark.harness import program_spans as P


def read(run):
    if run.get("kind") != "train":
        return None
    got = P.scope_ms_a_step(P.load(run), "linear_ce")
    if got is None:
        return None
    ms, events, steps = got
    print(f"[ce_head_ms] {events} operations under linear_ce over {steps} "
          f"steps: {ms:.2f} ms of self time a step", file=sys.stderr)
    return ms
