"""The ``latent_moe_train`` family: the decoder of
``reference/latent_moe_train_f32.py`` (multi-head latent attention with
the queries projected directly or through a rank; a SwiGLU in the
leading dense layers and sigmoid-routed experts plus shared experts
after them; the plain residual path; an untied head), TRAINED: run by
the program's ``HybridForCausalLM`` with the mixer kind ``"latent"``,
the channel mix by layer, the routing rule ``"sigmoid_noaux_tc"`` with
its bias rule, ``hc_mult`` 1 and remat, stepped by the one
``parallel.Trainer`` through ``forward_loss``.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out), expert weights are
stacked over the experts HELD here (``dims.held = (first, count)`` of
the router's ``dims.experts``, read from the configuration's
``reduced``), the head is (hidden, vocab) over the vocabulary's slice.
The routers' ``bias_shift`` / ``expert_load`` are buffers, not leaves:
both sides start the rule from zero.

The shape formulas count only the USEFUL work, whatever implements it:
a multiply-add is two operations; heads of ``nope + rope`` (scores) and
``v`` (values), not the 256 the kernel pads them to; the lower
triangle; the (token, pick) pairs that land on a held expert in the
mean, ``top_k x held / experts`` a token; the head's slice. Recomputed
work (remat, the flash backward's second look at the scores) is not
counted.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "latent_moe_train_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    """Block ``i``'s leaves: the latent mixer and a SwiGLU
    (``dims.is_dense(i)``) or the expert block."""
    h, p = dims.hidden, f"blocks.{i}."
    m, qk = p + "mixer.", dims.heads * (dims.nope + dims.rope)
    out = {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
           m + "kv_a_proj.weight": (h, dims.kv_rank + dims.rope),
           m + "kv_a_norm.weight": (dims.kv_rank,),
           m + "kv_b_proj.weight": (dims.kv_rank,
                                    dims.heads * (dims.nope + dims.v_dim)),
           m + "out_proj.weight": (dims.heads * dims.v_dim, h)}
    if dims.q_rank:
        out.update({m + "q_a_proj.weight": (h, dims.q_rank),
                    m + "q_a_norm.weight": (dims.q_rank,),
                    m + "q_b_proj.weight": (dims.q_rank, qk)})
    else:
        out[m + "q_proj.weight"] = (h, qk)
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
    """Every matrix is seeded uniform, the routed experts' three among
    them; the router's selection bias is seeded uniform too (it changes
    a pick now and then, never a gate, and takes no gradient: its
    parameter change is 0 on both sides); norm scales are 1."""
    if name.endswith("score_bias") or len(shape) > 1:
        return "uniform"
    return "ones"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``HybridForCausalLM`` with latent mixers over the plain residual
    path, the channel mix by layer, sigmoid routing with its bias rule
    at ``dims.gamma``, at the configuration's sizes (``max_position`` is
    not read: the model keeps no table of positions)."""
    from paddle_tpu.models import hybrid as H

    return H.HybridForCausalLM(H.HybridConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        layer_types=("latent",) * dims.layers, num_heads=dims.heads,
        channel_mix=tuple("mlp" if dims.is_dense(i) else "experts"
                          for i in range(dims.layers)),
        mlp_width=dims.ffn, expert_width=dims.expert_width,
        shared_width=dims.shared_width, num_experts=dims.experts,
        experts_per_token=dims.top_k, experts_held=dims.held,
        routing="sigmoid_noaux_tc", routed_scaling_factor=dims.scaling,
        router_bias_update_rate=dims.gamma, rope_theta=dims.theta,
        q_lora_rank=dims.q_rank, kv_lora_rank=dims.kv_rank,
        qk_nope_head_dim=dims.nope, qk_rope_head_dim=dims.rope,
        v_head_dim=dims.v_dim, hc_mult=1, tie_embeddings=False,
        rms_norm_eps=dims.eps, remat=remat))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def kinds(dims, kind: str) -> int:
    """How many blocks are of ``kind``: every one is ``"latent"``;
    ``"experts"`` counts the blocks after the leading dense ones."""
    if kind == "experts":
        return dims.layers - dims.dense_layers
    return dims.layers if kind == "latent" else 0


def mixer_matmul_params(dims) -> int:
    """One latent mixer's matrices (the two latent norms' scales are
    not products)."""
    qk = dims.heads * (dims.nope + dims.rope)
    q = (dims.hidden * dims.q_rank + dims.q_rank * qk if dims.q_rank
         else dims.hidden * qk)
    return (q + dims.hidden * (dims.kv_rank + dims.rope)
            + dims.kv_rank * dims.heads * (dims.nope + dims.v_dim)
            + dims.heads * dims.v_dim * dims.hidden)


def held_pairs_per_token(dims) -> float:
    """The (token, pick) pairs of one token that land on a held expert,
    in the mean over uniform picks: 6 x 16 / 128 = 0.75."""
    return dims.top_k * dims.held[1] / dims.experts


def expert_params(dims) -> int:
    """One routed expert's three matrices."""
    return 3 * dims.hidden * dims.expert_width


def matmul_params(dims) -> float:
    """Every matmul weight a token meets in the mean: the mixers, the
    dense SwiGLUs, in an expert layer the router, the shared experts and
    ``held_pairs_per_token`` routed experts, and the head's slice. The
    embedding is a lookup."""
    dense = 3 * dims.hidden * dims.ffn
    sparse = (dims.hidden * dims.experts + 3 * dims.hidden * dims.shared_width
              + held_pairs_per_token(dims) * expert_params(dims))
    return (dims.layers * mixer_matmul_params(dims)
            + dims.dense_layers * dense + kinds(dims, "experts") * sparse
            + dims.hidden * dims.vocab)


def attention_flops(dims, seq: int, backward: bool) -> int:
    """One layer, one sequence, the lower triangle at the published
    widths: scores over ``nope + rope`` and a value sum over ``v``
    forward (2 matmuls); the backward needs four (dV and dP over ``v``,
    dQ and dK over ``nope + rope``)."""
    pairs = seq * (seq + 1) // 2
    per = 2 * dims.heads * (dims.nope + dims.rope + dims.v_dim) * pairs
    return per * (2 if backward else 1)


def attention_bytes(dims, seq: int, itemsize: int, backward: bool) -> int:
    """One layer, one sequence: q and k (``nope + rope`` a head), v and
    the output (``v`` a head) moved once; the backward reads those and
    dO and writes dq, dk, dv."""
    qk = seq * dims.heads * (dims.nope + dims.rope)
    v = seq * dims.heads * dims.v_dim
    fwd = (2 * qk + 2 * v) * itemsize
    return (fwd + (2 * qk + 2 * v) * itemsize) if backward else fwd


def train_flops_per_token(dims, seq: int) -> float:
    """Forward + backward: 6 x every matmul parameter a token meets,
    plus causal attention (forward 2 matmuls, backward 4)."""
    attn = dims.layers * (attention_flops(dims, seq, False)
                          + attention_flops(dims, seq, True)) / seq
    return 6.0 * matmul_params(dims) + attn


def expert_train_flops(dims, tokens: int) -> float:
    """One expert layer, one training step of ``tokens`` tokens: three
    products forward and two more each backward (3 x 3 passes of a
    product's operations) over the EXPECTED held pairs, ``tokens x
    held_pairs_per_token``. The count is the expectation, not the
    step's own: a reader reaches the trace and ``run`` only."""
    return 6.0 * expert_params(dims) * held_pairs_per_token(dims) * tokens


def expert_train_bytes(dims, itemsize: int = 2) -> float:
    """One expert layer, one training step: the held experts' weights
    read once a pass in the compute type (forward, backward's two) and
    their gradients written once in float32."""
    n = dims.held[1] * expert_params(dims)
    return n * (3 * itemsize + 4)
