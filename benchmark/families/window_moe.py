"""The ``window_moe`` family: the decoder of
``reference/window_moe_f32.py`` (gated grouped-query attention, FULL or
SLIDING by layer with query heads and a rotary embedding by kind; a
SwiGLU in the ``"dense"`` layers and sigmoid-routed experts plus a
shared expert in the ``"sparse"`` ones), run by the program's
``HybridForCausalLM`` with the mixer kinds ``"full_attention"`` and
``"sliding_attention"`` (the configuration's own ``layer_types``), the
channel mix by layer and the routing rule ``"sigmoid_noaux_tc"``. A
sliding layer's cache is a ring of ``sliding_window`` positions beside
the full layers' caches of the arena's whole capacity.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out), expert weights are
stacked over the experts HELD here (``dims.held = (first, count)`` of
the router's ``dims.experts``, read from the configuration's
``reduced``), the head is (hidden, vocab). A layer's shapes depend on
its index: its query heads are ``dims.heads[i]``, its channel mix
``dims.mixes[i]``.

The shape formulas count only what the mathematics requires, whatever
implements it: a multiply-add is two operations, a weight or a cached
key or value is moved once.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "window_moe_f32")
Dims = reference.Dims
FULL, SLIDING = reference.FULL, reference.SLIDING


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    """Block ``i``'s leaves: the gated attention at ``dims.heads[i]``
    query heads, and a SwiGLU (``dims.is_dense(i)``) or the expert
    block."""
    h, p = dims.hidden, f"blocks.{i}."
    m, wide = p + "mixer.", dims.heads[i] * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    out = {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
           m + "q_proj.weight": (h, wide), m + "k_proj.weight": (h, kv),
           m + "v_proj.weight": (h, kv),
           m + "gate_proj.weight": (h, dims.heads[i]),
           m + "out_proj.weight": (wide, h)}
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
    """Every matrix is seeded uniform, the gate's and every expert's
    among them; the router's selection bias is seeded uniform (it
    changes a pick now and then and never a gate). The blocks' norm
    scales are 1; the FINAL norm's scale is seeded uniform, not 1
    (``families/hybrid_moe.py`` has the reckoning). With every expert
    matrix seeded a near-tied pick goes either way in bfloat16; by this
    family's own chip readings that costs at most 0.26 deviations and
    the reference levels no position (``reference/window_moe_f32.py``:
    ``PICK_MARGIN`` 0)."""
    if (name.endswith("score_bias") or name == "norm_f.weight"
            or len(shape) > 1):
        return "uniform"
    return "ones"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``HybridForCausalLM`` with the configuration's ``layer_types`` as
    its mixer kinds, copied and not translated, heads by kind, the
    rotary settings by kind, the window, the gate, the channel mix by
    layer and sigmoid routing (its caches are sized by the arena and its
    rings by the window, so ``max_position`` is not read). The residual
    sum is settled where it is written (``settle_residual``, as the
    ``sparse_latent_moe`` family has it): left to itself the compiler
    keeps a 14336-token prefill's stream as the embedding plus every
    sublayer's float32 output (112 MB each) to the end, 4.9 GB of
    temporaries where the chip has 3.5 left."""
    from paddle_tpu.models import hybrid as H

    if remat:
        raise ValueError("this family's training path is not a cell")
    return H.HybridForCausalLM(H.HybridConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        layer_types=dims.kinds, num_kv_heads=dims.kv_heads,
        channel_mix=tuple("mlp" if dims.is_dense(i) else "experts"
                          for i in range(dims.layers)),
        mlp_width=dims.ffn, expert_width=dims.expert_width,
        shared_width=dims.shared_width, num_experts=dims.experts,
        experts_per_token=dims.top_k, experts_held=dims.held,
        routing="sigmoid_noaux_tc", routed_scaling_factor=dims.scaling,
        attn_head_dim=dims.head_dim, sliding_window=dims.window,
        attn_gate=True,
        attn_heads={k: dims.heads_of(k) for k in set(dims.kinds)},
        attn_rope={
            FULL: dict(rope_theta=dims.full_theta,
                       rotary_dim=dims.full_rotary,
                       attention_factor=dims.attention_factor,
                       yarn=dict(factor=dims.yarn_factor,
                                 original_max_position=dims.yarn_original,
                                 beta_fast=dims.beta_fast,
                                 beta_slow=dims.beta_slow)),
            SLIDING: dict(rope_theta=dims.sliding_theta,
                          rotary_dim=dims.sliding_rotary)},
        settle_residual=True, tie_embeddings=False, rms_norm_eps=dims.eps))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def kinds(dims, kind: str) -> int:
    """How many blocks are of ``kind``: an attention kind of
    ``layer_types``, or ``"experts"`` (the ``"sparse"`` layers)."""
    if kind == "experts":
        return sum(not dims.is_dense(i) for i in range(dims.layers))
    return sum(k == kind for k in dims.kinds)


def kv_bytes(dims, itemsize: int = 2) -> int:
    """What one position leaves in one layer's cache or ring: a key and
    a value for each key-value head (4096 bytes at 8 heads of 128)."""
    return 2 * dims.kv_heads * dims.head_dim * itemsize


def mixer_weights(dims, kind: str) -> int:
    """One mixer's parameters at its kind's query heads: W_q, W_k, W_v,
    W_g, W_o."""
    heads, d = dims.heads_of(kind), dims.head_dim
    return dims.hidden * (2 * heads * d + 2 * dims.kv_heads * d + heads)


def arena_bytes(dims, slots: int, capacity: int, itemsize: int = 2):
    """(the full layers' caches, the sliding layers' rings) of an arena
    of ``slots`` x ``capacity``, in bytes."""
    one = slots * kv_bytes(dims, itemsize)
    return (kinds(dims, FULL) * capacity * one,
            kinds(dims, SLIDING) * min(capacity, dims.window) * one)


def gqa_step_bytes(dims, kind: str, positions: float,
                   itemsize: int = 2) -> float:
    """One mixer of ``kind``, one decode step: the keys and values of
    the ``positions`` its rows read (a row's context in a full layer,
    the window's share of it in a sliding one; every query head of a
    group reads the same key-value head) once, and the mixer's weights
    once."""
    return (positions * kv_bytes(dims, itemsize)
            + mixer_weights(dims, kind) * itemsize)


def gqa_step_flops(dims, kind: str, rows: int, positions: float) -> float:
    """One mixer of ``kind``, one decode step over ``rows`` rows: the
    projections of ``rows`` tokens, and for each position read a score
    and a value sum over ``head_dim`` for every query head."""
    read = 4 * dims.heads_of(kind) * dims.head_dim
    return rows * 2 * mixer_weights(dims, kind) + positions * read


def attended_pairs(dims, kind: str, tokens: int) -> int:
    """The (query, key) pairs of one sequence of ``tokens`` positions:
    ``t (t + 1) / 2`` causal, and under a window each query's last
    ``window`` positions alone."""
    w = tokens if kind == FULL else min(dims.window, tokens)
    return w * (w + 1) // 2 + (tokens - w) * w


def gqa_prefill_flops(dims, kind: str, tokens: int) -> int:
    """One mixer of ``kind`` over ``tokens`` positions of one sequence:
    the projections, and a score and a value sum for each attended
    pair a query head."""
    return (tokens * 2 * mixer_weights(dims, kind)
            + attended_pairs(dims, kind, tokens) * 4 * dims.heads_of(kind)
            * dims.head_dim)
