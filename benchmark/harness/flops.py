"""Operations and bytes the algorithms need, from shapes alone.

Counted here, with the benchmark, so that no later PR can move them.
A multiply-add is two operations. Only what the mathematics requires is
counted: recomputation (remat, the flash backward's second look at the
scores) is not, and causal attention is the lower triangle.
"""

from __future__ import annotations


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


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds the chip could take, which bound applies)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
