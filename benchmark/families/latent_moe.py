"""The ``latent_moe`` family: the decoder of
``reference/latent_moe_f32.py`` (multi-head latent attention whose
cache is one compressed record a position; a SwiGLU in the leading
dense layers and sigmoid-routed experts plus a shared expert after
them; a residual state of ``n`` streams joined by manifold-constrained
hyper-connections), run by the program's ``HybridForCausalLM`` with the
mixer kind ``"latent"``, the channel mix by layer, the routing rule
``"sigmoid_noaux_tc"`` and ``hc_mult`` streams.

What a family file gives the harness is listed in
``harness/manifest.py::load_family``. Leaf names are the program's
``named_parameters()``; linear weights are (in, out), expert weights are
stacked over the experts HELD here (``dims.held = (first, count)`` of
the router's ``dims.experts``, read from the configuration's
``reduced``), the head is (hidden, vocab).

The shape formulas count only what the mathematics requires, whatever
implements it: a multiply-add is two operations, a weight or a record
is moved once.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "latent_moe_f32")
Dims = reference.Dims


# --------------------------------------------------------------------------
# leaves
# --------------------------------------------------------------------------

def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    """Block ``i``'s leaves: the latent mixer, two hyper-connections,
    and a SwiGLU (``dims.is_dense(i)``) or the expert block."""
    h, n, p = dims.hidden, dims.streams, f"blocks.{i}."
    m, maps = p + "mixer.", n * n + 2 * n
    out = {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
           m + "q_a_proj.weight": (h, dims.q_rank),
           m + "q_a_norm.weight": (dims.q_rank,),
           m + "q_b_proj.weight": (dims.q_rank,
                                   dims.heads * (dims.nope + dims.rope)),
           m + "kv_a_proj.weight": (h, dims.kv_rank + dims.rope),
           m + "kv_a_norm.weight": (dims.kv_rank,),
           m + "kv_b_proj.weight": (dims.kv_rank,
                                    dims.heads * (dims.nope + dims.v_dim)),
           m + "out_proj.weight": (dims.heads * dims.v_dim, h)}
    for r in (p + "res1.", p + "res2."):
        out.update({r + "phi": (n * h, maps), r + "bias": (maps,),
                    r + "gain": (3,)})
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
    """Every matrix is seeded uniform, ``phi`` among them: ``x~`` has
    unit mean square over its 14336 numbers, so ``m = x~ phi`` has
    deviation 0.02 x sqrt(14336) = 2.4 a token, and with gains of 1 and
    biases of 0 ``H_res`` is far from both the identity and the uniform
    matrix, ``H_pre`` and ``H_post`` spread over most of their ranges.
    The router's selection bias is seeded uniform (it changes a pick
    now and then and never a gate). The blocks' norm scales are 1; the
    FINAL norm's scale is seeded uniform, not 1 (``families/
    hybrid_moe.py`` has the reckoning).

    Every expert matrix is seeded too, the routed experts' down
    projection among them: a pick then carries a gate of about 0.5 and
    an output as large as a sublayer's, bfloat16's own noise decides a
    near-tied pick either way, and the REFERENCE says which positions
    it leaves undecided for that reason (``reference/latent_moe_f32.py::route``'s margin
    and ``hold_undecided``; PERF.md section 6, PR 41, has the chip
    readings both constants are set from)."""
    if name.endswith(".gain"):
        return "ones"
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
    """``HybridForCausalLM`` with latent mixers, hyper-connections, the
    channel mix by layer and sigmoid routing at the configuration's
    sizes (its records are sized by the arena, so ``max_position`` is
    not read)."""
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
        rope_yarn=dict(factor=dims.yarn_factor,
                       original_max_position=dims.yarn_original,
                       beta_fast=dims.beta_fast, beta_slow=dims.beta_slow),
        rope_mscale_all_dim=dims.mscale_all_dim, hc_mult=dims.streams,
        hc_sinkhorn_iters=dims.sinkhorn_iters, hc_eps=dims.hc_eps,
        hc_clamp=dims.clamp, tie_embeddings=False, rms_norm_eps=dims.eps))


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
    """What one position leaves in one layer's cache: the latent and the
    shared rotary key."""
    return (dims.kv_rank + dims.rope) * itemsize


def mixer_weights(dims) -> int:
    """One latent mixer's parameters: the five projections and the two
    latent norms' scales."""
    qk = dims.nope + dims.rope
    return (dims.hidden * dims.q_rank + dims.q_rank * dims.heads * qk
            + dims.hidden * (dims.kv_rank + dims.rope)
            + dims.kv_rank * dims.heads * (dims.nope + dims.v_dim)
            + dims.heads * dims.v_dim * dims.hidden
            + dims.q_rank + dims.kv_rank)


def mla_decode_bytes(dims, context_tokens: float, itemsize: int = 2) -> float:
    """One latent block, one decode step: the live records read once
    (``context_tokens`` is the sum of the live rows' contexts; all heads
    read the same record, for the score and for the value) and the
    mixer's weights once."""
    return (context_tokens * record_bytes(dims, itemsize)
            + mixer_weights(dims) * itemsize)


def mla_decode_flops(dims, rows: int, context_tokens: float) -> float:
    """One latent block, one decode step over ``rows`` rows, in the
    ABSORBED form (what makes one read of the records serve every head):
    the projections of ``rows`` tokens (``W_kvb`` meets each query head
    once on either side of the read, as many operations as a position's
    decompression), and per live position and head a score over
    ``kv_rank + rope`` numbers and a value sum over ``kv_rank``: 69.6
    kFLOP a position at the published widths."""
    proj = 2 * (mixer_weights(dims) - dims.q_rank - dims.kv_rank)
    read = 2 * dims.heads * (2 * dims.kv_rank + dims.rope)
    return rows * proj + context_tokens * read


def mla_prefill_flops(dims, tokens: int) -> int:
    """One latent block over ``tokens`` positions of one sequence,
    decompressed and causal: the projections, and a score over ``nope +
    rope`` and a value sum over ``v`` for each of the ``tokens (tokens +
    1) / 2`` (query, key) pairs a head."""
    proj = 2 * (mixer_weights(dims) - dims.q_rank - dims.kv_rank)
    pair = 2 * dims.heads * (dims.nope + dims.rope + dims.v_dim)
    return tokens * proj + tokens * (tokens + 1) // 2 * pair


def mhc_weights(dims) -> int:
    """One sublayer's hyper-connection: phi, biases, gains."""
    maps = dims.streams * dims.streams + 2 * dims.streams
    return dims.streams * dims.hidden * maps + maps + 3


def expert_step_bytes(dims, itemsize: int = 2) -> float:
    """What ``moe_experts_roofline_pct`` would multiply by
    ``dims.layers``: the held experts' weights of every EXPERT layer
    read once a decode step (three matrices each), spread over all the
    layers, the leading dense ones included, so that ``dims.layers x
    expert_step_bytes`` is the expert layers' held bytes. At 16 rows of
    4 picks of 64 a held expert is idle in a step with probability
    (60/64)^16 = 36%, and the dense body reads it all the same. The
    cell is on NEITHER ``moe_experts_ms``'s list NOR the share's: the
    compiler moves a third of these bytes (every layer's ``w_gate``,
    0.47 GB a step) by asynchronous slices that overlap other
    operations, outside the scope both readers time (PERF.md section
    7)."""
    one = dims.held[1] * 3 * dims.hidden * dims.expert_width * itemsize
    return one * kinds(dims, "experts") / dims.layers

