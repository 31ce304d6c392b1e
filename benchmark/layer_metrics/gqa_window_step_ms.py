"""Kernels (`nn/gated_attention.py::GatedAttention.forward_step_rows`
with a window): device self time a decode step spends in the
sliding-window mixers: the `XLA Ops` events traced under
`jax.named_scope("gqa_window_step")` (the projections, the rotary
embedding, the gate, the key's and value's write at the row's place in
its ring, the read of the whole ring (`%pt_flash_decode` where the
kernel runs), the output projection) that start inside a
`pt_decode_step` run, over those runs. None for a program without the
scope, as the parent of the PR that added it."""

from benchmark.harness import manifest


def read(run):
    return manifest.load_reader("gqa_full_step_ms")(
        run, scope="gqa_window_step")
