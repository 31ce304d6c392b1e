"""Model (`models/gpt.py::GPTBlock`): device self time a train step
spends under the scope `attn` (norm1, the projections, rotary, the flash
kernels, the output projection, the residual add) in all three passes,
over the `pt_train_step` runs of the trace
(`harness/scope_table.py`, which prints the step's whole table). None
for a program without the list of scopes."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "train":
        return None
    return scope_table.scope_ms(P.load(run), "pt_train_step", "attn")
