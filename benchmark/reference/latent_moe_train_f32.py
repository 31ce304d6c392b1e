"""The plain reference of the ``latent_moe_train`` family: the loss of a
DeepSeek-V3-shaped decoder (``model_type`` ``deepseek_v3``) whose layers
mix tokens by multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434) and channels by a SwiGLU (the leading dense layers)
or by sigmoid-routed experts plus shared experts (DeepSeek-V3,
arXiv:2412.19437, ``topk_method`` ``noaux_tc``), pre-norm over the plain
residual path, in jax.numpy, for ``jax.grad``.

The equations of one layer, ``x`` a position's input, head ``j``::

    h = RMSNorm(x)
    [q^N_j ; q^R_j] = (h W_q)_j                       (no query rank)
      or  c_q = RMSNorm(h W_qa); [q^N_j ; q^R_j] = (c_q W_qb)_j
    [c ; k^R] = h W_kva;  c_t = RMSNorm(c);  r_t = rope(k^R, t)
    [k^N_ij ; v_ij] = (c_i W_kvb)_j
    s_tij = (q^N_j . k^N_ij + rope(q^R_j, t) . r_i) (nope + rope)^-1/2
    o_j = sum_{i<=t} softmax_i(s) v_ij;   x = x + concat_j(o_j) W_o

    u = RMSNorm(x)
    layers < first_k_dense_replace:  x = x + W_d (silu(u W_g) * (u W_u))
    the others:  sc = sigmoid(u W_r);  picks = top_k(sc + b)
      g = scaling * sc[picks] / (sum sc[picks] + 1e-20)
      x = x + sum_{e in picks, e held} g_e W_d,e (silu(u W_g,e) * (u W_u,e))
            + Shared(u)

    loss = mean over positions t < T - 1 of -log softmax(RMSNorm(x_t) W_head)[id_{t+1}]

``rope`` is the rotate-half rotary embedding at ``rope_theta`` with no
scaling (``rope_scaling`` null). The bias ``b`` selects and does not
weigh: it takes no gradient. The loss has no balance term (the
configuration names none), so it is a mean over rows that do not see
each other, which ``check.train_reference`` needs. After a step the
rule of ``noaux_tc`` moves the bias, :func:`bias_update`; the loss of
one step does not see it.

**The share.** ``dims.held = (first, count)`` of the router's
``dims.experts`` outputs are held: the router keeps its width and its
``top_k`` picks, a pick on an absent expert adds nothing. The vocabulary
is the slice the leaves have (``dims.vocab`` rows of embedding and
head): ids, logits and the loss are over the slice.

Attention goes by blocks of :data:`QUERY_BLOCK` queries over all the
keys under the causal mask, each block under ``jax.checkpoint`` (the
(heads, T, T) float32 score of a 8192-token row would be 8.6 GB whole);
the routed experts are a loop over the held ones, every token through
each with a gate of 0.0 where it was not picked. Neither changes a
number. No kernels, no cache, no sort.

Float32 throughout, every product at ``Precision.HIGHEST``. Imports
nothing of the program nor of the other reference files. ``mode``
selects the arithmetic of every matrix multiplication by a weight (the
router among them): ``"f32"`` the reference, ``"fp8"`` the control
(inputs scaled per tensor and rounded to float8 e4m3, gradients passed
straight through); scores, softmax, sigmoids and norms stay float32 in
both.

Leaf layout (the program's ``named_parameters()``): linear weights are
(in, out), the expert tensors are stacked over the held experts, the
head is (hidden, vocab).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    dense_layers: int            # leading layers whose FFN is a SwiGLU
    heads: int
    q_rank: int                  # 0: the queries are projected directly
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ffn: int                     # the dense SwiGLU's width
    expert_width: int
    shared_width: int
    experts: int                 # the router's width
    top_k: int
    held: Tuple[int, int]        # (first, count) of the experts held
    scaling: float
    vocab: int
    theta: float
    eps: float
    gamma: float                 # the bias rule's step

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """The router's width is the published ``n_routed_experts``
        where ``reduced`` names the key (the file then gives the number
        held and ``first``)."""
        cut = {r["key"]: r for r in c.get("reduced", [])}
        row = cut.get("n_routed_experts")
        held = c["n_routed_experts"]
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("moe_layer_freq", 1), ("rope_scaling", None),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False), ("hidden_act", "silu")):
            if c[key] != want:
                raise ValueError(f"{key} = {c[key]!r}: {want!r} is what "
                                 "is written")
        return cls(
            hidden=c["hidden_size"], layers=c["num_hidden_layers"],
            dense_layers=min(c["first_k_dense_replace"],
                             c["num_hidden_layers"]),
            heads=c["num_attention_heads"],
            q_rank=int(c["q_lora_rank"] or 0),
            kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            ffn=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
            experts=row["published"] if row else held,
            top_k=c["num_experts_per_tok"],
            held=(row.get("first", 0) if row else 0, held),
            scaling=float(c["routed_scaling_factor"]),
            vocab=c["vocab_size"], theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            gamma=float(c["train"]["router_bias_update_rate"]))

    def is_dense(self, i: int) -> bool:
        return i < self.dense_layers


def _round_fp8(x):
    """``x`` rounded to float8 e4m3 after scaling its largest magnitude
    to the format's 448; gradients pass straight through the rounding."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def matmul(x, w, mode: str):
    """``x @ w`` in the arithmetic ``mode`` names, float32 out."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    elif mode != "f32":
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, weight, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding of (T, H, D) at ``positions`` (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_block(t: int) -> int:
    return QUERY_BLOCK if t % QUERY_BLOCK == 0 else t


def attention(q, k, v, scale: float):
    """Causal softmax attention of one sequence with heads whose score
    and value widths differ: q, k (T, H, dq), v (T, H, dv) -> (T, H,
    dv). A block of queries at a time against every key under the mask;
    a block keeps nothing for the backward pass but its inputs."""
    t = q.shape[0]
    qb = _query_block(t)
    at = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qs, first = args                                # (qb, H, dq)
        s = jnp.einsum("qhd,khd->hqk", qs, k, precision=HIGHEST) * scale
        keep = at[None, :] <= (first + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (q.reshape(t // qb, qb, *q.shape[1:]),
                              jnp.arange(0, t, qb)))
    return out.reshape(t, *out.shape[2:])


def latent_attention(h, w: Dict[str, jax.Array], p: str, dims: Dims,
                     mode: str):
    """The mixer of one sequence ``h`` (T, hidden), already normed."""
    t, hd = h.shape[0], dims.heads
    pos = jnp.arange(t)
    if dims.q_rank:
        cq = rms_norm(matmul(h, w[p + "q_a_proj.weight"], mode),
                      w[p + "q_a_norm.weight"], dims.eps)
        q = matmul(cq, w[p + "q_b_proj.weight"], mode)
    else:
        q = matmul(h, w[p + "q_proj.weight"], mode)
    q = q.reshape(t, hd, dims.nope + dims.rope)
    q = jnp.concatenate([q[..., :dims.nope],
                         rope(q[..., dims.nope:], pos, dims.theta)], -1)
    kva = matmul(h, w[p + "kv_a_proj.weight"], mode)
    c = rms_norm(kva[:, :dims.kv_rank], w[p + "kv_a_norm.weight"],
                 dims.eps)
    r = rope(kva[:, None, dims.kv_rank:], pos, dims.theta)   # (T, 1, rope)
    kv = matmul(c, w[p + "kv_b_proj.weight"], mode).reshape(
        t, hd, dims.nope + dims.v_dim)
    k = jnp.concatenate([kv[..., :dims.nope],
                         jnp.broadcast_to(r, (t, hd, dims.rope))], -1)
    o = attention(q, k, kv[..., dims.nope:],
                  (dims.nope + dims.rope) ** -0.5)
    return matmul(o.reshape(t, -1), w[p + "out_proj.weight"], mode)


def swiglu(u, gate, up, down, mode: str):
    return matmul(jax.nn.silu(matmul(u, gate, mode)) * matmul(u, up, mode),
                  down, mode)


def route(u, router_w, bias, dims: Dims, mode: str):
    """(gates (T, k) float32, picks (T, k) int) of the tokens ``u``:
    sigmoid scores, the ``top_k`` largest of score + bias (the bias
    takes no gradient), gates = scaling x the picks' own scores over
    their sum."""
    scores = jax.nn.sigmoid(matmul(u, router_w, mode))
    _, picks = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)),
        dims.top_k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    return (dims.scaling * picked
            / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)), picks


def routed_experts(u, w: Dict[str, jax.Array], p: str, dims: Dims,
                   mode: str):
    """The held experts' part of the routed sum: one held expert after
    the other over every token, weighted by the gate of the pick that
    chose it and by 0.0 elsewhere."""
    first, held = dims.held
    gates, picks = route(u, w[p + "router.weight"], w[p + "score_bias"],
                         dims, mode)

    def one(y, e):
        gate = jnp.sum(jnp.where(picks == first + e, gates, 0.0), axis=-1)
        out = swiglu(u, w[p + "w_gate"][e], w[p + "w_up"][e],
                     w[p + "w_down"][e], mode)
        return y + gate[:, None] * out, None

    return jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(u),
                        jnp.arange(held))[0]


def mixed(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """Block ``i``'s first sublayer on one sequence: (the stream after
    the mixer, the normed input of the channel mix)."""
    p = f"blocks.{i}."
    x = x + latent_attention(rms_norm(x, w[p + "norm1.weight"], dims.eps),
                             w, p + "mixer.", dims, mode)
    return x, rms_norm(x, w[p + "norm2.weight"], dims.eps)


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """One block on one sequence ``x`` (T, hidden)."""
    p = f"blocks.{i}."
    x, u = mixed(x, w, i, dims, mode)
    if dims.is_dense(i):
        return x + swiglu(u, w[p + "mlp.gate.weight"],
                          w[p + "mlp.up.weight"], w[p + "mlp.down.weight"],
                          mode)
    shared = swiglu(u, w[p + "shared.gate.weight"],
                    w[p + "shared.up.weight"], w[p + "shared.down.weight"],
                    mode)
    return x + routed_experts(u, w, p + "moe.", dims, mode) + shared


def logits(tokens, w, dims: Dims, mode: str = "f32", remat: bool = False):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``. ``remat``
    recomputes each block in a backward pass instead of keeping its
    activations: the same mathematics in less memory."""
    x = w["embed.weight"].astype(jnp.float32)[tokens]
    step = (jax.checkpoint(layer, static_argnums=(2, 3, 4)) if remat
            else layer)
    for i in range(dims.layers):
        x = step(x, w, i, dims, mode)
    x = rms_norm(x, w["norm_f.weight"], dims.eps)
    return matmul(x, w["lm_head"], mode)


def loss(w, batch, dims: Dims, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of (B, T) ``batch``: position t
    predicts token t+1, the last position of each row predicts nothing."""
    def row(tokens):
        lp = jax.nn.log_softmax(
            logits(tokens, w, dims, mode, remat)[:-1])
        return -jnp.sum(jnp.take_along_axis(lp, tokens[1:, None], 1))
    total = jnp.sum(jax.lax.map(row, batch))
    return total / (batch.shape[0] * (batch.shape[1] - 1))


def picks_of(w, batch, i: int, dims: Dims, mode: str = "f32"):
    """The (B T, top_k) picks of expert layer ``i`` over the whole
    (B, T) ``batch``: what :func:`bias_update` counts."""
    def row(tokens):
        x = w["embed.weight"].astype(jnp.float32)[tokens]
        for j in range(i):
            x = layer(x, w, j, dims, mode)
        p = f"blocks.{i}.moe."
        return route(mixed(x, w, i, dims, mode)[1], w[p + "router.weight"],
                     w[p + "score_bias"], dims, mode)[1]
    return jnp.concatenate([row(r) for r in batch])


def bias_update(b, picks, gamma: float):
    """DeepSeek-V3's auxiliary-loss-free rule after one step: ``b`` (E,)
    the router's selection bias, ``picks`` (N, k) the experts the step's
    batch picked; every output moves by ``gamma`` towards the mean load,
    ``b_e + gamma sign(mean_e'(n_e') - n_e)``."""
    load = jnp.bincount(jnp.asarray(picks).reshape(-1),
                        length=b.shape[0]).astype(jnp.float32)
    return b + gamma * jnp.sign(jnp.mean(load) - load)
