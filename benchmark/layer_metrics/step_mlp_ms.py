"""Model (`models/gpt.py::GPTForCausalLM._cached_blocks`,
`models/hybrid.py::HybridBlock.channel_mix`): device self time a decode
step spends under the scope `mlp` (norm2 and the dense SwiGLU; in
`models/gpt.py` its residual add too), over the `pt_decode_step` runs of
the trace (`harness/scope_table.py`). The routed experts are not in it
(`moe_*`). None for a program without the list of scopes or without a
dense MLP."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "serve":
        return None
    return scope_table.scope_ms(P.load(run), "pt_decode_step", "mlp")
