"""Model (`GPTForCausalLM._head`, `HybridForCausalLM._head`,
`serving.py::_build_multi_step`'s `pick`): device self time a decode
step spends under the scope `head`: the final norm, the head's product,
the logits' scaling and the argmax or sampling, over the
`pt_decode_step` runs of the trace (`harness/scope_table.py`). None for
a program without the list of scopes."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "serve":
        return None
    return scope_table.scope_ms(P.load(run), "pt_decode_step", "head")
