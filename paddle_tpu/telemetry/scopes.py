"""The block-level scopes a step's device time is divided into.

A scope is a ``jax.named_scope``: a component of the ``op_name`` of
every operation traced inside it, metadata of the lowered program that
costs nothing when it runs. A profiler trace keeps an operation's
``op_name``, so a reader (``benchmark/harness/scope_table.py``) splits
the device time of a program's runs by these names and by the pass
(forward, remat's second forward, backward) the same path shows. This
is the ONE list: a site enters a scope through :func:`scope`, which
refuses a name that is not here, and the reader matches these names
and no others. A scope is entered in the model shells, the mixers and
the trainer, never inside a ``nn/`` library layer, which serves many
callers; ``weight_cast`` alone sits in ``nn.Layer.functional_call``,
where every model's parameters enter it. Kernel names
(``pt_flash_*``, ``pt_mla_decode``, ``pt_dsa_*``,
``pt_retention_step``) name one ``custom-call`` each and are not block
scopes.
"""

from __future__ import annotations

import jax

from ..core.enforce import enforce

SCOPES = {
    "embed": "the token embedding, its multiplier and the copies into "
             "residual streams; in training the gradient's scatter",
    "attn": "a softmax-attention sublayer: norm1, the projections, "
            "rotary, the cache's write, the attention kernel, the "
            "output projection and the residual add",
    "mlp": "a dense gated-MLP sublayer: norm2 and the SwiGLU, with its "
           "residual add where the residual path is the plain one",
    "head": "the final norm, the head's product, the logits' scaling "
            "and the step's pick (argmax or sampling)",
    "optimizer": "the optimizer's apply with the clipping, unscaling "
                 "and casting it holds; not the gradients' reduction",
    "linear_ce": "the fused linear cross-entropy head of training, "
                 "forward and backward",
    "ssm_scan": "a state-space mixer over a chunk: projections, "
                "convolution, chunked scan, gated norm, out projection",
    "ssm_step": "the same mixer at one position a row",
    "retention_scan": "a power-retention mixer over a chunk: "
                      "projections, head norms, rotary, chunked form, "
                      "output projection",
    "retention_step": "the same mixer at one position a row, the step "
                      "kernel and the denominators included",
    "mla_decode": "a latent-attention mixer at one position a row: "
                  "projections, norms, rotary, the record's write, the "
                  "absorbed read, output projection",
    "mla_prefill": "the same mixer over a chunk, decompressed",
    "dsa_index": "a latent mixer's indexer, beside mla_decode / "
                 "mla_prefill and never inside them: its three "
                 "projections, the index key's norm, rotary and write, "
                 "the index scores and the pick",
    "gqa_full_step": "a full-attention gated GQA mixer at one position "
                     "a row: projections, rotary, the gate, the cache's "
                     "write, the read, the output projection",
    "gqa_window_step": "the same mixer with a window: its write goes "
                       "round a ring and its read is the whole ring",
    "gqa_full_prefill": "a full-attention gated GQA mixer over a chunk: "
                        "projections, rotary, causal attention, the "
                        "gate, the chunk's write, the output projection",
    "gqa_window_prefill": "the same mixer with a window: banded "
                          "attention, and the chunk's last window "
                          "written into the ring",
    "mhc_mix": "a hyper-connection's maps: both halves around a "
               "sublayer, never the sublayer itself",
    "moe_route": "an expert layer's router: logits, top-k, counts and "
                 "the gate matrix or the sort by expert",
    "moe_experts": "the routed experts' products and their weighted "
                   "sum or gather back",
    "moe_shared": "the shared gated MLP beside the routed experts",
    "moe_bias_update": "after a training call, the auxiliary-loss-free "
                       "rule's step on a router's selection bias, from "
                       "the load the call's batch gave each output",
    "weight_cast": "the one convert of each declared parameter to a "
                   "narrower compute type where the parameters enter the "
                   "model (nn.Layer.functional_call); in training the "
                   "gradient's convert back",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`: a context
    manager and a decorator, as that is."""
    enforce(name in SCOPES, "scope %r is not on the list of "
            "telemetry.scopes: %s", name, ", ".join(SCOPES))
    return jax.named_scope(name)
