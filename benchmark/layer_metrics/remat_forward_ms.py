"""Model (`models/gpt.py::GPTForCausalLM._trunk`, `jax.checkpoint` a
block): device self time a train step spends in remat's second forward:
the operations of every listed scope whose `op_name` lies under
`rematted_computation`, over the `pt_train_step` runs of the trace
(`harness/scope_table.py`). It is part of `block_attn_ms` and
`block_mlp_ms`, not beside them. None for a program without the list of
scopes, or one that recomputes nothing."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "train":
        return None
    return scope_table.scope_ms(P.load(run), "pt_train_step",
                                passes=("recompute",))
