"""Model (`models/gpt.py::GPTForCausalLM._cached_blocks`, an attention
block of `models/hybrid.py`): device self time a decode step spends
under the scope `attn` (norm1, the projections, rotary, the cache's
write, the decode kernel, the output projection, the residual add), over
the `pt_decode_step` runs of the trace (`harness/scope_table.py`, which
prints the step's whole table). None for a program without the list of
scopes or without such a block."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "serve":
        return None
    return scope_table.scope_ms(P.load(run), "pt_decode_step", "attn")
