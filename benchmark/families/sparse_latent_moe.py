"""The ``sparse_latent_moe`` family: the decoder of
``reference/sparse_latent_moe_f32.py`` (multi-head latent attention
that reads only the positions a learned indexer picks, so a position
leaves three arrays in a layer's cache: the latent, the shared rotary
key and the index key; a SwiGLU in the leading dense layers and
sigmoid-routed experts plus a shared expert after them; the plain
residual path), run by the program's ``HybridForCausalLM`` with the
mixer kind ``"latent"`` and the indexer's three sizes, the channel mix
by layer and the routing rule ``"sigmoid_noaux_tc"``.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out), expert weights are
stacked over the experts HELD here (``dims.held = (first, count)`` of
the router's ``dims.experts``, read from the configuration's
``reduced``), the head is (hidden, vocab) over the vocabulary's slice.

The shape formulas count only what the mathematics requires, whatever
implements it: a multiply-add is two operations, a weight or a record
is moved once, and a query reads the records it picked and no others.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "sparse_latent_moe_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    """Block ``i``'s leaves: the latent mixer with its indexer, and a
    SwiGLU (``dims.is_dense(i)``) or the expert block."""
    h, p = dims.hidden, f"blocks.{i}."
    m = p + "mixer."
    out = {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
           m + "q_a_proj.weight": (h, dims.q_rank),
           m + "q_a_norm.weight": (dims.q_rank,),
           m + "q_b_proj.weight": (dims.q_rank,
                                   dims.heads * (dims.nope + dims.rope)),
           m + "kv_a_proj.weight": (h, dims.kv_rank + dims.rope),
           m + "kv_a_norm.weight": (dims.kv_rank,),
           m + "kv_b_proj.weight": (dims.kv_rank,
                                    dims.heads * (dims.nope + dims.v_dim)),
           m + "out_proj.weight": (dims.heads * dims.v_dim, h),
           m + "index_q_proj.weight": (dims.q_rank,
                                       dims.index_heads * dims.index_dim),
           m + "index_k_proj.weight": (h, dims.index_dim),
           m + "index_k_norm.weight": (dims.index_dim,),
           m + "index_k_norm.bias": (dims.index_dim,),
           m + "index_w_proj.weight": (h, dims.index_heads)}
    if dims.is_dense(i):
        out.update({p + "mlp.gate.weight": (h, dims.ffn),
                    p + "mlp.up.weight": (h, dims.ffn),
                    p + "mlp.down.weight": (dims.ffn, h)})
        return out
    held = dims.held[1]
    out.update({p + "moe.router.weight": (h, dims.experts),
                p + "moe.score_bias": (dims.experts,),
                p + "moe.w_gate": (held, h, dims.expert_width),
                p + "moe.w_up": (held, h, dims.expert_width),
                p + "moe.w_down": (held, dims.expert_width, h),
                p + "shared.gate.weight": (h, dims.shared_width),
                p + "shared.up.weight": (h, dims.shared_width),
                p + "shared.down.weight": (dims.shared_width, h)})
    return out


def leaf_rule(name: str, shape) -> str:
    """Every matrix is seeded uniform, the indexer's three and the
    routed experts' among them (``families/latent_moe.py`` says what
    that does to a near-tied expert pick and how the reference answers
    it). The index scores are then sums of 32 heads' ``w relu(q . k)``
    with ``w`` of either sign: they spread over the live positions
    without a preference for near or far ones, so a pick of 2048 among
    12k reads records all over the row. The router's selection bias is
    seeded uniform; the index key's LayerNorm starts at scale 1 and
    bias 0, the blocks' norm scales at 1; the FINAL norm's scale is
    seeded uniform, not 1 (``families/hybrid_moe.py`` has the
    reckoning)."""
    if name.endswith(".bias"):
        return "zeros"
    if (name.endswith("score_bias") or name == "norm_f.weight"
            or len(shape) > 1):
        return "uniform"
    return "ones"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``HybridForCausalLM`` with latent mixers and their indexers, the
    plain residual path, the channel mix by layer and sigmoid routing
    at the configuration's sizes (its records are sized by the arena,
    so ``max_position`` is not read)."""
    from paddle_tpu.models import hybrid as H

    if remat:
        raise ValueError("the hybrid shell has no remat option: its "
                         "training path is not a cell")
    return H.HybridForCausalLM(H.HybridConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        layer_types=("latent",) * dims.layers, num_heads=dims.heads,
        channel_mix=tuple("mlp" if dims.is_dense(i) else "experts"
                          for i in range(dims.layers)),
        mlp_width=dims.ffn, expert_width=dims.expert_width,
        shared_width=dims.shared_width, num_experts=dims.experts,
        experts_per_token=dims.top_k, experts_held=dims.held,
        routing="sigmoid_noaux_tc", routed_scaling_factor=dims.scaling,
        rope_theta=dims.theta, q_lora_rank=dims.q_rank,
        kv_lora_rank=dims.kv_rank, qk_nope_head_dim=dims.nope,
        qk_rope_head_dim=dims.rope, v_head_dim=dims.v_dim,
        index_n_heads=dims.index_heads, index_head_dim=dims.index_dim,
        index_topk=dims.index_topk, settle_residual=True,
        tie_embeddings=False,
        rms_norm_eps=dims.eps))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def kinds(dims, kind: str) -> int:
    """How many blocks are of ``kind``: every one is ``"latent"``;
    ``"experts"`` counts the blocks after the leading dense ones."""
    if kind == "experts":
        return dims.layers - dims.dense_layers
    return dims.layers if kind == "latent" else 0


def record_bytes(dims, itemsize: int = 2) -> int:
    """What one position leaves in one layer's cache FOR THE READ: the
    latent and the shared rotary key (1152 B at the published sizes)."""
    return (dims.kv_rank + dims.rope) * itemsize


def index_key_bytes(dims, itemsize: int = 2) -> int:
    """The third array of a position's record: the index key (256 B)."""
    return dims.index_dim * itemsize


def mixer_weights(dims) -> int:
    """One latent mixer's parameters outside its indexer: the five
    projections and the two latent norms' scales (165.0 M)."""
    qk = dims.nope + dims.rope
    return (dims.hidden * dims.q_rank + dims.q_rank * dims.heads * qk
            + dims.hidden * (dims.kv_rank + dims.rope)
            + dims.kv_rank * dims.heads * (dims.nope + dims.v_dim)
            + dims.heads * dims.v_dim * dims.hidden
            + dims.q_rank + dims.kv_rank)


def index_weights(dims) -> int:
    """One indexer's parameters: ``W^I_q``, ``W^I_k``, ``W^I_w`` and
    the key norm's scale and bias (9.37 M)."""
    return (dims.q_rank * dims.index_heads * dims.index_dim
            + dims.hidden * dims.index_dim + dims.hidden * dims.index_heads
            + 2 * dims.index_dim)


def read_tokens(dims, contexts) -> float:
    """The records a step's rows read under the selection: ``min(context,
    index_topk)`` a row."""
    return float(sum(min(c, dims.index_topk) for c in contexts))


def dsa_index_bytes(dims, context_tokens: float, itemsize: int = 2) -> float:
    """One indexer, one decode step: the live positions' index keys once
    (``context_tokens`` is the sum of the live rows' contexts) and the
    indexer's weights once."""
    return (context_tokens * index_key_bytes(dims, itemsize)
            + index_weights(dims) * itemsize)


def dsa_index_flops(dims, rows: int, context_tokens: float) -> float:
    """One indexer, one decode step over ``rows`` rows: the three
    projections of ``rows`` tokens, and per live position a dot product
    of ``index_dim`` numbers a head and the heads' weighted sum."""
    proj = 2 * (index_weights(dims) - 2 * dims.index_dim)
    score = 2 * dims.index_heads * (dims.index_dim + 1)
    return rows * proj + context_tokens * score


def mla_decode_bytes(dims, read: float, itemsize: int = 2) -> float:
    """One latent block, one decode step: the records the rows' picks
    name read once (``read`` = :func:`read_tokens`) and the mixer's
    weights once."""
    return read * record_bytes(dims, itemsize) + mixer_weights(dims) * itemsize


def mla_decode_flops(dims, rows: int, read: float) -> float:
    """One latent block, one decode step over ``rows`` rows, in the
    absorbed form over the ``read`` picked records."""
    proj = 2 * (mixer_weights(dims) - dims.q_rank - dims.kv_rank)
    per = 2 * dims.heads * (2 * dims.kv_rank + dims.rope)
    return rows * proj + read * per


def attended_pairs(dims, tokens: int) -> int:
    """The (query, key) pairs of a causal prefill of ``tokens``
    positions under the selection: ``min(t + 1, index_topk)`` a
    query."""
    k = min(tokens, dims.index_topk)
    return k * (k + 1) // 2 + (tokens - k) * dims.index_topk


def mla_prefill_flops(dims, tokens: int) -> int:
    """One latent block over ``tokens`` positions of one sequence,
    decompressed, each query over its pick: the projections, and a score
    over ``nope + rope`` and a value sum over ``v`` a pair a head."""
    proj = 2 * (mixer_weights(dims) - dims.q_rank - dims.kv_rank)
    pair = 2 * dims.heads * (dims.nope + dims.rope + dims.v_dim)
    return tokens * proj + attended_pairs(dims, tokens) * pair


def dsa_prefill_index_flops(dims, tokens: int) -> int:
    """One indexer over ``tokens`` positions of one sequence: the
    projections and the index score of every causal pair (the scores
    come before the pick, so every pair is scored)."""
    proj = 2 * (index_weights(dims) - 2 * dims.index_dim)
    score = 2 * dims.index_heads * (dims.index_dim + 1)
    return tokens * proj + tokens * (tokens + 1) // 2 * score
