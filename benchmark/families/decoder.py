"""The ``decoder`` family: the dense pre-norm decoder of
``reference/decoder_f32.py`` (RMSNorm, rotary GQA attention, SwiGLU, no
biases, tied or untied head), run by the program's ``GPTForCausalLM``.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``; this is the worked example. Leaf
names are the program's ``named_parameters()``; linear weights are
(in, out) and the head is (hidden, vocab).

The shape formulas count only what the mathematics requires. A
multiply-add is two operations; recomputation (remat, the flash
backward's second look at the scores) is not counted, and causal
attention is the lower triangle.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "decoder_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    """The leaves outside the blocks."""
    out = {"embed.weight": (dims.vocab, dims.hidden),
           "norm_f.weight": (dims.hidden,)}
    if not dims.tied:
        out["lm_head"] = (dims.hidden, dims.vocab)
    return out


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    h, f = dims.hidden, dims.ffn
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    p = f"blocks.{i}."
    return {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
            p + "self_attn.q_proj.weight": (h, q),
            p + "self_attn.k_proj.weight": (h, kv),
            p + "self_attn.v_proj.weight": (h, kv),
            p + "self_attn.out_proj.weight": (q, h),
            p + "ffn.gate.weight": (h, f), p + "ffn.up.weight": (h, f),
            p + "ffn.down.weight": (f, h)}


def leaf_rule(name: str, shape) -> str:
    """Norm scales (the only rank-1 leaves) are 1; every matrix is
    seeded uniform."""
    return "ones" if len(shape) == 1 else "uniform"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``GPTForCausalLM`` at the configuration's sizes."""
    from paddle_tpu.models import gpt as G

    if dims.hidden // dims.heads != dims.head_dim:
        raise ValueError("GPTConfig derives head_dim as hidden / heads")
    return G.GPTForCausalLM(G.GPTConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        num_layers=dims.layers, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, intermediate_size=dims.ffn,
        max_position=max_position, rope_theta=dims.theta, remat=remat,
        attn_window=dims.window, tie_embeddings=dims.tied))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def layer_matmul_params(dims) -> int:
    """Weights of one block that a token is multiplied by."""
    q = dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    return (dims.hidden * q + 2 * dims.hidden * kv + q * dims.hidden
            + 3 * dims.hidden * dims.ffn)


def matmul_params(dims) -> int:
    """Every matmul weight a token meets: the blocks and the head. The
    embedding is a lookup; a tied head is still one matmul."""
    return (dims.layers * layer_matmul_params(dims)
            + dims.hidden * dims.vocab)


def _attended(seq: int, window) -> int:
    """Key positions attended, summed over the queries of one causal
    sequence of ``seq`` tokens (diagonal included)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(dims, seq: int, backward: bool) -> int:
    """One layer, one sequence: QK^T and PV forward (2 matmuls); the
    backward needs four (dV, dP, dQ, dK)."""
    per = 2 * dims.heads * dims.head_dim * _attended(seq, dims.window)
    return per * (4 if backward else 2)


def attention_bytes(dims, seq: int, itemsize: int, backward: bool) -> int:
    """One layer, one sequence: q, k, v and the output moved once; the
    backward reads those and dO and writes dq, dk, dv."""
    q = seq * dims.heads * dims.head_dim
    kv = seq * dims.kv_heads * dims.head_dim
    fwd = (2 * q + 2 * kv) * itemsize
    return (fwd + (2 * q + 2 * kv) * itemsize) if backward else fwd


def train_flops_per_token(dims, seq: int) -> float:
    """Forward + backward: 6 x every matmul parameter, plus causal
    attention (forward 2 matmuls, backward 4)."""
    attn = dims.layers * (attention_flops(dims, seq, False)
                          + attention_flops(dims, seq, True)) / seq
    return 6.0 * matmul_params(dims) + attn


def decode_attention_flops(dims, context_tokens: int) -> int:
    """One layer, one tick: each live slot's query against its own
    ``context`` keys and values; ``context_tokens`` is their sum."""
    return 4 * dims.heads * dims.head_dim * context_tokens


def decode_attention_bytes(dims, context_tokens: int, itemsize: int) -> int:
    """One layer, one tick: the live K and V read once."""
    return 2 * dims.kv_heads * dims.head_dim * context_tokens * itemsize
