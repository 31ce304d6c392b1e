"""Model: device self time a train step spends under NO listed scope,
over the `pt_train_step` runs of the trace: what `block_attn_ms`,
`block_mlp_ms`, `ce_head_ms`, `optimizer_ms` and the table's `embed` and
`head` leave (`harness/scope_table.py` prints it by operation family).
0.0 where every operation has an owner; None for a program without the
list of scopes."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "train":
        return None
    return scope_table.unscoped_ms(P.load(run), "pt_train_step")
