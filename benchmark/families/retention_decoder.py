"""The ``retention_decoder`` family: the dense pre-norm decoder of
``reference/retention_f32.py`` (RMSNorm, a power-retention mixer of
degree 2 with per-head RMSNorm and rotary embedding on queries and keys
and one gate a key-value head, SwiGLU, no biases, an untied head), run
by the program's ``HybridForCausalLM`` with the block kinds
``"retention"`` and ``"mlp"``.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out) and the head is
(hidden, vocab).

The shape formulas count only what the mathematics requires, with the
state at its least size ``D = d (d + 1) / 2`` products a head
**whatever the program's tiling** (the program rounds ``D`` up to whole
rotations of the head, ``ops.retention.phi_dim``): a roofline share
then reads the same work for any implementation. A multiply-add is two
operations; a weight or a state is moved once.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "retention_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    h, f, d = dims.hidden, dims.ffn, dims.head_dim
    q, kv = dims.heads * d, dims.kv_heads * d
    p, m = f"blocks.{i}.", f"blocks.{i}.mixer."
    return {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
            m + "q_proj.weight": (h, q), m + "k_proj.weight": (h, kv),
            m + "v_proj.weight": (h, kv),
            m + "gate_proj.weight": (h, dims.kv_heads),
            m + "q_norm.weight": (d,), m + "k_norm.weight": (d,),
            m + "out_proj.weight": (q, h),
            p + "mlp.gate.weight": (h, f), p + "mlp.up.weight": (h, f),
            p + "mlp.down.weight": (f, h)}


def leaf_rule(name: str, shape) -> str:
    """Norm scales (the only rank-1 leaves, the per-head ones of queries
    and keys among them) are 1; every matrix is seeded uniform, the
    gate's too: a gate's logit is then of deviation 0.02 x sqrt(hidden)
    = 1.4 around 0, so ``g`` lies around 0.5 and the state forgets
    within a few tokens (the configuration's ``assumed`` says what that
    hides). The head is a matrix of its own, so no token echoes."""
    return "ones" if len(shape) == 1 else "uniform"


# --------------------------------------------------------------------------
# the program's model
# --------------------------------------------------------------------------

def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """``HybridForCausalLM`` with retention mixers and a gated MLP at
    the configuration's sizes (its state is sized by the arena's slots,
    so ``max_position`` is not read)."""
    from paddle_tpu.models import hybrid as H

    if remat:
        raise ValueError("the hybrid shell has no remat option: its "
                         "training path is not a cell")
    if dims.hidden // dims.heads != dims.head_dim:
        raise ValueError("HybridConfig derives head_dim as hidden / heads")
    return H.HybridForCausalLM(H.HybridConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        layer_types=("retention",) * dims.layers, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, channel_mix="mlp",
        mlp_width=dims.ffn, rope_theta=dims.theta,
        retention_degree=dims.degree, retention_eps=dims.retention_eps,
        tie_embeddings=False, rms_norm_eps=dims.eps))


# --------------------------------------------------------------------------
# operations and bytes, from shapes alone
# --------------------------------------------------------------------------

def kinds(dims, kind: str) -> int:
    """How many blocks are of ``kind``: every one is ``"retention"``."""
    return dims.layers if kind == "retention" else 0


def state_width(dims) -> int:
    """``D``: the distinct products of two coordinates of a head."""
    return dims.head_dim * (dims.head_dim + 1) // 2


def mixer_weights(dims) -> int:
    """One mixer's parameters: q, k, v, gate and output projections and
    the two per-head norm scales."""
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return (dims.hidden * (2 * q + 2 * kv + dims.kv_heads)
            + 2 * dims.head_dim)


def retention_state_bytes(dims, slots: int) -> int:
    """One block's float32 state over ``slots`` rows: ``S`` (D x d) and
    ``z`` (D) a key-value head."""
    return slots * dims.kv_heads * state_width(dims) * (
        dims.head_dim + 1) * 4


def retention_step_bytes(dims, slots: int, itemsize: int = 2) -> int:
    """One retention block, one decode step over ``slots`` rows: the
    float32 state of every slot read and written, and the mixer's
    weights read once (``itemsize`` bytes each)."""
    return (2 * retention_state_bytes(dims, slots)
            + mixer_weights(dims) * itemsize)


def retention_scan_flops(dims, tokens: int) -> int:
    """One retention block over ``tokens`` positions of one sequence,
    as the recurrence needs them (the chunked form spends more: its
    quadratic part inside a chunk is not counted): the projections, and
    per position the state's update a key-value head (decay, outer
    product, add: 3 D (d + 1)) and its read a query head (2 D (d + 1))."""
    proj = 2 * (mixer_weights(dims) - 2 * dims.head_dim)
    cell = state_width(dims) * (dims.head_dim + 1)
    return tokens * (proj + 3 * dims.kv_heads * cell
                     + 2 * dims.heads * cell)
