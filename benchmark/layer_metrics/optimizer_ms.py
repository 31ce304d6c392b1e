"""Trainer (`parallel/api.py::Trainer._step`): device self time a train
step spends under the scope `optimizer`: `self.optimizer.apply` with the
clipping, unscaling and casting it holds, not the gradients' reduction
before it, over the `pt_train_step` runs of the trace
(`harness/scope_table.py`). None for a program without the list of
scopes."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "train":
        return None
    return scope_table.scope_ms(P.load(run), "pt_train_step", "optimizer")
