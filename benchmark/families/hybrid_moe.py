"""The ``hybrid_moe`` family: the decoder of
``reference/hybrid_moe_f32.py`` (layers that mix by a Mamba-2 recurrence
or by attention without positions, each followed by dropless top-k
routed experts and a shared gated MLP), run by the program's
``HybridForCausalLM``.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out), expert weights are
stacked over the experts HELD here (``dims.held = (first, count)`` of
the router's ``dims.experts``, read from the configuration's
``reduced``), the head is the embedding.

The shape formulas count only what the mathematics requires: a
multiply-add is two operations, a weight or a state is moved once.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "hybrid_moe_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    """Block ``i``'s leaves, by its kind (``dims.layer_types[i]``)."""
    h, p = dims.hidden, f"blocks.{i}."
    held = dims.held[1]
    out = {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
           p + "moe.router.weight": (h, dims.experts),
           p + "moe.w_gate": (held, h, dims.expert_width),
           p + "moe.w_up": (held, h, dims.expert_width),
           p + "moe.w_down": (held, dims.expert_width, h),
           p + "shared.gate.weight": (h, dims.shared_width),
           p + "shared.up.weight": (h, dims.shared_width),
           p + "shared.down.weight": (dims.shared_width, h)}
    m = p + "mixer."
    if dims.layer_types[i] == "mamba":
        out.update({
            m + "in_proj.weight": (h, dims.inner + dims.conv_dim
                                   + dims.ssm_heads),
            m + "conv_weight": (dims.ssm_conv, dims.conv_dim),
            m + "conv_bias": (dims.conv_dim,),
            m + "dt_bias": (dims.ssm_heads,),
            m + "A_log": (dims.ssm_heads,),
            m + "D": (dims.ssm_heads,),
            m + "norm.weight": (dims.inner,),
            m + "out_proj.weight": (dims.inner, h)})
    else:
        q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
        out.update({m + "q_proj.weight": (h, q),
                    m + "k_proj.weight": (h, kv),
                    m + "v_proj.weight": (h, kv),
                    m + "out_proj.weight": (q, h)})
    return out


def leaf_rule(name: str, shape) -> str:
    """Matrices and the convolution's bias are seeded uniform. The
    blocks' norm scales and ``D`` are 1. ``A_log`` and ``dt_bias`` are
    0: every head decays with ``A = -1`` and ``dt = softplus(projection)``,
    about 0.9, so the state forgets within a few tokens (the generator
    has no rule that spreads ``A`` over 1..16 as the published
    initialisation does; PERF.md section 7).

    The FINAL norm's scale is seeded uniform, not 1. The head is the
    embedding and the stream starts as 12 times it, so under a scale of
    1 the last token's own logit stands 15 / rms(h), about 11, standard
    deviations above the other 100351 whatever the ten layers add (rms
    1.3 from weights of deviation 0.02): every served token would echo
    the one before it, in the program, in the reference and in the
    float8 control alike, and the comparison that decides ``correct``
    could tell none of them apart. A scale of random sign a channel
    takes that systematic term away (its mean over channels is zero) and
    leaves logits that depend on the whole stack."""
    if name.endswith(("A_log", "dt_bias")):
        return "zeros"
    if (name.endswith("conv_bias") or name == "norm_f.weight"
            or len(shape) > 1):
        return "uniform"
    return "ones"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``HybridForCausalLM`` at the configuration's sizes (its caches
    are sized by the arena, so ``max_position`` is not read)."""
    from paddle_tpu.models import hybrid as H

    if remat:
        raise ValueError("the hybrid model has no remat option: its "
                         "training path is not a cell")
    return H.HybridForCausalLM(H.HybridConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        layer_types=dims.layer_types, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, expert_width=dims.expert_width,
        shared_width=dims.shared_width, num_experts=dims.experts,
        experts_per_token=dims.top_k, experts_held=dims.held,
        ssm_heads=dims.ssm_heads, ssm_head_dim=dims.ssm_head_dim,
        ssm_state=dims.ssm_state, ssm_conv=dims.ssm_conv,
        ssm_chunk=dims.ssm_chunk,
        embedding_multiplier=dims.embedding_multiplier,
        attention_multiplier=dims.attention_multiplier,
        residual_multiplier=dims.residual_multiplier,
        logits_scaling=dims.logits_scaling, rms_norm_eps=dims.eps))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def kinds(dims, kind: str) -> int:
    """How many blocks are of ``kind`` (``"mamba"``, ``"attention"``)."""
    return sum(k == kind for k in dims.layer_types)


def ssm_step_bytes(dims, slots: int, itemsize: int = 2) -> int:
    """One state-space block, one decode step over ``slots`` rows: the
    float32 state read and written, the convolution tail read and
    written, and the block's projections, convolution and norm weights
    read once (``itemsize`` bytes each)."""
    state = slots * dims.ssm_heads * dims.ssm_head_dim * dims.ssm_state * 4
    tail = slots * (dims.ssm_conv - 1) * dims.conv_dim * itemsize
    weights = (dims.hidden * (dims.inner + dims.conv_dim + dims.ssm_heads)
               + dims.inner * dims.hidden
               + (dims.ssm_conv + 1) * dims.conv_dim + dims.inner
               + 3 * dims.ssm_heads) * itemsize
    return 2 * state + 2 * tail + weights


def ssm_scan_flops(dims, tokens: int) -> int:
    """One state-space block over ``tokens`` positions of one sequence,
    as the recurrence needs them (the chunked form spends more: its
    quadratic part inside a chunk is not counted): the two projections,
    the convolution, and per position and head the state's update
    (decay, outer product, add: 3 P N) and its read (2 P N)."""
    proj = 2 * dims.hidden * (2 * dims.inner + dims.conv_dim
                              + dims.ssm_heads)
    conv = 2 * dims.ssm_conv * dims.conv_dim
    scan = 5 * dims.ssm_heads * dims.ssm_head_dim * dims.ssm_state
    return tokens * (proj + conv + scan)


def expert_step_bytes(dims, itemsize: int = 2) -> int:
    """One expert layer, one decode step: the weights of every held
    expert read once (three matrices each). At 32 rows of 10 picks of 72
    an expert is untouched with probability (62/72)^32 = 0.8%, so a step
    streams them all; the tokens' own bytes are left out."""
    return dims.held[1] * 3 * dims.hidden * dims.expert_width * itemsize


def expert_flops(dims, tokens: int) -> int:
    """One expert layer over ``tokens`` tokens: the expected (token,
    pick) pairs that fall on held experts, three matmuls each."""
    pairs = tokens * dims.top_k * dims.held[1] / dims.experts
    return int(pairs * 3 * 2 * dims.hidden * dims.expert_width)


def decode_attention_flops(dims, context_tokens: int) -> int:
    """One attention layer, one tick: each live slot's query against its
    own keys and values; ``context_tokens`` is the sum of their
    contexts."""
    return 4 * dims.heads * dims.head_dim * context_tokens


def decode_attention_bytes(dims, context_tokens: int,
                           itemsize: int = 2) -> int:
    """One attention layer, one tick: the live K and V read once."""
    return 2 * dims.kv_heads * dims.head_dim * context_tokens * itemsize
