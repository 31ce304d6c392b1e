"""The plain reference of the ``latent_moe`` family: a decoder whose
layers mix tokens by multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434) and channels by a SwiGLU (the leading dense layers)
or by sigmoid-routed experts plus a shared expert (DeepSeek-V3's
``noaux_tc`` rule), over a residual state of ``n`` streams joined by
manifold-constrained hyper-connections (mHC; Xie et al.,
arXiv:2512.24880), in jax.numpy.

The equations (``X`` the residual state of one position, ``n`` streams
of ``C`` numbers; ``RMSNorm_w`` with a learned scale, ``RMSNorm_0``
without)::

    X_0[i] = E[id];  h = sum_i X[i];  logits = RMSNorm_w(h) W_head
    layer:  X = mHC(X; Attn);  X = mHC(X; FFN)

    mHC around a sublayer F, all float32:
      x~ = RMSNorm_0(vec(X));  m = x~ phi                      (n n + 2 n)
      H_pre = sigmoid(a_pre m[0:n] + b_pre)
      H_post = 2 sigmoid(a_post m[n:2n] + b_post)
      H_res = SK(clip(a_res mat(m[2n:]) + b_res, lo, hi))
      SK(A): M = exp(A); `iters` times M = M / (rowsum(M) + eps),
             M = M / (colsum(M) + eps)
      u = sum_i H_pre[i] X[i];  y = F(RMSNorm_w(u))
      X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

    Attn, x at position t, head j:
      c_q = RMSNorm_w(x W_qa);  [q^N_j ; q^R_j] = (c_q W_qb)_j
      [c ; k^R] = x W_kva;  c_t = RMSNorm_w(c);  r_t = rope(k^R, t)
      [k^N_ij ; v_ij] = (c_i W_kvb)_j
      s_tij = (q^N_j . k^N_ij + rope(q^R_j, t) . r_i) (nope + rope)^-1/2 mscale^2
      o_j = sum_{i<=t} softmax_i(s) v_ij;  Attn = concat_j(o_j) W_o
      rope: YaRN frequencies (blend between beta_fast and beta_slow turns
      over the original context), mscale = 0.1 mscale_all_dim ln(factor) + 1

    FFN, layers < first_k_dense_replace:  W_d (silu(x W_g) * (x W_u))
    FFN, the others:  sc = sigmoid(x W_r);  picks = top_k(sc + e_bias)
      g = scaling * sc[picks] / (sum sc[picks] + 1e-20)
      sum_{e in picks, e held} g_e W_d,e (silu(x W_g,e) * (x W_u,e))  + Shared(x)

The attention is the NON-absorbed form: every head's keys and values
are made from the records and attend as heads; no cache, no kernels,
Sinkhorn a plain loop. Queries go in blocks of :data:`QUERY_BLOCK`
inside groups of :data:`KEY_GROUP` rows whose keys end with the group
(a 16384-token row's scores never exist whole); that changes no
number. The routed experts are a masked loop over the experts HELD
here, ``(first, count)`` of the router's width: what the absent
experts would add is left out, as in the program.

**What the reference leaves undecided.** A pick is a step function of
the scores: where the lowest picked and the highest unpicked of ``sc +
e_bias`` lie closer than the arithmetic's own noise, a served model in
bfloat16 takes either, both are the model, and a pick carries a gate
of about ``scaling / top_k`` and an output as large as a sublayer's.
:func:`route` therefore also gives each token's MARGIN: how far the
nearest HELD expert lies from the edge of the picks (a picked one above
the highest unpicked score, an unpicked one below the lowest picked; an
edge between two experts that are not held here moves nothing).
:func:`layerwise` carries the least margin over the expert layers to
each position, and :func:`layerwise_logits` (what ``check.serve_gap``
compares) holds a position whose margin is under :data:`PICK_MARGIN`
to less: its logits are levelled :data:`UNDECIDED_DEPTH` deviations
under their best (:func:`hold_undecided`), so that any token among the
reference's near-best passes there and a token far below them still
fails, while every other position is compared as it stands. Both
constants are set from chip readings (``tools/route_margins.py``;
PERF.md section 6, PR 41).

Float32 throughout with ``jax.default_matmul_precision("highest")``
semantics (every product names ``Precision.HIGHEST``). Imports nothing
of the program. ``mode`` selects the arithmetic of every matrix
multiplication by a weight (``phi`` and the router among them), as in
``decoder_f32``: ``"f32"`` the reference, ``"fp8"`` the control (inputs
rounded to float8 e4m3); scores, softmax, sigmoids, Sinkhorn and the
norms stay float32 in both.

Departures and assumptions are listed in the configuration file under
``assumed``. Leaf layout: linear weights are (in, out), expert weights
are stacked over the held experts, the head is (hidden, vocab).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK, KEY_GROUP = 256, 2048
# A position is UNDECIDED where, in some expert layer, a held expert's
# score + bias lies within PICK_MARGIN of the edge of the picks; its
# logits are then levelled UNDECIDED_DEPTH deviations under their best.
# On the chip, over 31,217 served positions of 10 seeds (PERF.md section
# 6, PR 41): every position more than 0.1 sd under the reference's best
# had a margin under 0.0051 (more than 0.3 sd: under 0.0036), and the
# widest gap at any margin was 1.07 sd (1.59 over 20 earlier seeds).
PICK_MARGIN = 0.01
UNDECIDED_DEPTH = 2.5


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    dense_layers: int            # leading layers whose FFN is a SwiGLU
    heads: int
    q_rank: int
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
    streams: int
    sinkhorn_iters: int
    hc_eps: float
    clamp: Tuple[float, float]
    vocab: int
    theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """The router's width is the published ``n_routed_experts``
        where ``reduced`` names the key (the file then gives the number
        held and ``first``)."""
        cut = {r["key"]: r for r in c.get("reduced", [])}
        row = cut.get("n_routed_experts")
        held = c["n_routed_experts"]
        ys = c["rope_scaling"]
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("moe_layer_freq", 1),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False), ("hidden_act", "silu")):
            if c[key] != want:
                raise ValueError(f"{key} = {c[key]!r}: {want!r} is what "
                                 "is written")
        if ys["type"] != "yarn" or ys["mscale"] != ys["mscale_all_dim"]:
            raise ValueError("YaRN with mscale = mscale_all_dim (cosines "
                             "unscaled) is what is written")
        return cls(
            hidden=c["hidden_size"], layers=c["num_hidden_layers"],
            dense_layers=min(c["first_k_dense_replace"],
                             c["num_hidden_layers"]),
            heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
            kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            ffn=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
            experts=row["published"] if row else held,
            top_k=c["num_experts_per_tok"],
            held=(row.get("first", 0) if row else 0, held),
            scaling=float(c["routed_scaling_factor"]),
            streams=c["hc_mult"], sinkhorn_iters=c["hc_sinkhorn_iters"],
            hc_eps=float(c["hc_eps"]),
            clamp=(float(c["mhc_h_res_clamp_min"]),
                   float(c["mhc_h_res_clamp_max"])),
            vocab=c["vocab_size"], theta=float(c["rope_theta"]),
            yarn_factor=float(ys["factor"]),
            yarn_original=int(ys["original_max_position_embeddings"]),
            beta_fast=float(ys["beta_fast"]),
            beta_slow=float(ys["beta_slow"]),
            mscale_all_dim=float(ys["mscale_all_dim"]),
            eps=float(c["rms_norm_eps"]))

    def is_dense(self, i: int) -> bool:
        return i < self.dense_layers

    @property
    def score_scale(self) -> float:
        m = 0.1 * self.mscale_all_dim * math.log(self.yarn_factor) + 1.0
        return (self.nope + self.rope) ** -0.5 * m * m


def _round_fp8(x):
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
    y = x * jax.lax.rsqrt(var + eps)
    return y if weight is None else y * weight.astype(jnp.float32)


def yarn_frequencies(dims: Dims):
    """(rope / 2,) rotary frequencies: pair ``i`` turns at ``theta^(-2i /
    rope)`` where it makes more than ``beta_fast`` turns over the
    original context, at that over ``factor`` where it makes fewer than
    ``beta_slow``, a linear blend between."""
    half = dims.rope // 2

    def pair_of(turns):
        return half * math.log(dims.yarn_original / (turns * 2 * math.pi)
                               ) / math.log(dims.theta)

    low = max(math.floor(pair_of(dims.beta_fast)), 0)
    high = min(math.ceil(pair_of(dims.beta_slow)), half - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    plain = dims.theta ** (-i / half)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / dims.yarn_factor * ramp + plain * (1.0 - ramp)


def rope(x, positions, dims: Dims):
    """Rotate-half rotary embedding of (T, H, rope) at ``positions``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_frequencies(dims)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_attention(q, k, v, scale: float):
    """Causal attention of one sequence: ``q``, ``k`` (T, H, dq), ``v``
    (T, H, dv) -> (T, H, dv); query blocks inside key groups."""
    t = q.shape[0]
    group = KEY_GROUP if t % KEY_GROUP == 0 else t
    block = QUERY_BLOCK if group % QUERY_BLOCK == 0 else group
    out = []
    for g0 in range(0, t, group):
        kk, vv = k[:g0 + group], v[:g0 + group]
        keys = jnp.arange(g0 + group)

        def one(inp):
            qb, at = inp
            s = jnp.einsum("ihd,jhd->hij", qb, kk,
                           precision=HIGHEST) * scale
            s = jnp.where(keys[None, :] <= at[:, None], s, -jnp.inf)
            return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, axis=-1),
                              vv, precision=HIGHEST)

        qb = q[g0:g0 + group].reshape(-1, block, *q.shape[1:])
        at = (g0 + jnp.arange(group)).reshape(-1, block)
        out.append(jax.lax.map(one, (qb, at)).reshape(group, *v.shape[1:]))
    return jnp.concatenate(out, axis=0)


def attention(x, w, p: str, dims: Dims, mode: str):
    """Latent attention, non-absorbed, on one sequence ``x`` (T, C)."""
    t, h = x.shape[0], dims.heads
    pos = jnp.arange(t)
    cq = rms_norm(matmul(x, w[p + "q_a_proj.weight"], mode),
                  w[p + "q_a_norm.weight"], dims.eps)
    q = matmul(cq, w[p + "q_b_proj.weight"], mode).reshape(
        t, h, dims.nope + dims.rope)
    kv = matmul(x, w[p + "kv_a_proj.weight"], mode)
    c = rms_norm(kv[:, :dims.kv_rank], w[p + "kv_a_norm.weight"], dims.eps)
    r = rope(kv[:, None, dims.kv_rank:], pos, dims)            # (T, 1, R)
    kvb = matmul(c, w[p + "kv_b_proj.weight"], mode).reshape(
        t, h, dims.nope + dims.v_dim)
    q = jnp.concatenate([q[..., :dims.nope],
                         rope(q[..., dims.nope:], pos, dims)], -1)
    k = jnp.concatenate([kvb[..., :dims.nope],
                         jnp.broadcast_to(r, (t, h, dims.rope))], -1)
    o = softmax_attention(q, k, kvb[..., dims.nope:], dims.score_scale)
    return matmul(o.reshape(t, -1), w[p + "out_proj.weight"], mode)


def gated(u, gate, up, down, mode: str):
    return matmul(jax.nn.silu(matmul(u, gate, mode)) * matmul(u, up, mode),
                  down, mode)


def route(u, w, p: str, dims: Dims, mode: str):
    """(picks (T, k), gates (T, k), margin (T,)) of tokens ``u`` (T, C):
    sigmoid scores over the router's whole width, picks by score + bias,
    gates from the picks' own scores alone. ``margin`` is the least
    distance, in score + bias, of a HELD expert from the edge of the
    picks: a picked one above the highest unpicked score, an unpicked
    one below the lowest picked."""
    k = dims.top_k
    sc = jax.nn.sigmoid(matmul(u, w[p + "router.weight"], mode))
    sel = sc + w[p + "score_bias"].astype(jnp.float32)
    top_s, top_i = jax.lax.top_k(sel, k + 1)
    top_i = top_i[:, :k]
    picked = jnp.take_along_axis(sc, top_i, axis=-1)
    gates = dims.scaling * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    first, count = dims.held
    held = jnp.arange(first, first + count)
    is_picked = jnp.any(top_i[:, :, None] == held, axis=1)     # (T, count)
    margin = jnp.where(is_picked, sel[:, held] - top_s[:, k:],
                       top_s[:, k - 1:k] - sel[:, held])
    return top_i, gates, jnp.min(margin, axis=-1)


def experts(u, w, p: str, dims: Dims, mode: str):
    """What the held routed experts add for tokens ``u`` (T, C): one
    held expert at a time on every token, weighted by the gate of the
    tokens that picked it (zero for the others)."""
    top_i, gates, _ = route(u, w, p, dims, mode)
    first, count = dims.held
    out = jnp.zeros_like(u)
    for e in range(count):
        g = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        out = out + g[:, None] * gated(
            u, w[p + "w_gate"][e], w[p + "w_up"][e], w[p + "w_down"][e],
            mode)
    return out


def sinkhorn(a, iters: int, eps: float):
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def mhc(X, w, p: str, dims: Dims, mode: str, sublayer, norm_w):
    """``X`` (T, n, C) -> ``X'`` around ``sublayer`` (a function of the
    normed (T, C) input); ``p`` names the sublayer's phi, bias, gain."""
    n = dims.streams
    m = matmul(rms_norm(X.reshape(X.shape[0], -1), None, dims.eps),
               w[p + "phi"], mode)
    a = w[p + "gain"].astype(jnp.float32)
    b = w[p + "bias"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    h_res = sinkhorn(jnp.clip(
        (a[2] * m[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n), *dims.clamp),
        dims.sinkhorn_iters, dims.hc_eps)
    u = jnp.sum(h_pre[:, :, None] * X, axis=1)
    y = sublayer(rms_norm(u, norm_w, dims.eps))
    return (jnp.sum(h_res[:, :, :, None] * X[:, None, :, :], axis=2)
            + h_post[:, :, None] * y[:, None, :])


def layer(X, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """Block ``i`` on one sequence's state ``X`` (T, n, C); its leaves
    are named ``blocks.<i>.*`` and its FFN is the dense one where
    ``dims.is_dense(i)``. Returns (X', routed): ``routed`` is the
    expert layer's (picks (T, k), margin (T,)) of :func:`route`, None
    for a dense layer."""
    p = f"blocks.{i}."
    X = mhc(X, w, p + "res1.", dims, mode,
            lambda x: attention(x, w, p + "mixer.", dims, mode),
            w[p + "norm1.weight"])
    routed = []
    if dims.is_dense(i):
        ffn = lambda x: gated(x, w[p + "mlp.gate.weight"],
                              w[p + "mlp.up.weight"],
                              w[p + "mlp.down.weight"], mode)
    else:
        def ffn(x):
            top_i, _, margin = route(x, w, p + "moe.", dims, mode)
            routed.append((top_i, margin))
            return experts(x, w, p + "moe.", dims, mode) + gated(
                x, w[p + "shared.gate.weight"], w[p + "shared.up.weight"],
                w[p + "shared.down.weight"], mode)
    X = mhc(X, w, p + "res2.", dims, mode, ffn, w[p + "norm2.weight"])
    return X, (routed[0] if routed else None)


def embed(tokens, table, dims: Dims):
    """(...,) tokens -> (..., n, C): every stream the embedding."""
    e = table[tokens].astype(jnp.float32)       # rows first, then float32
    return jnp.broadcast_to(e[..., None, :],
                            (*e.shape[:-1], dims.streams, e.shape[-1]))


def head(X, w, dims: Dims, mode: str):
    """(..., n, C) -> (..., vocab): the streams' sum, normed."""
    return matmul(rms_norm(jnp.sum(X, axis=-2), w["norm_f.weight"],
                           dims.eps), w["lm_head"], mode)


def logits(tokens, w, dims: Dims, mode: str = "f32"):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``."""
    X = embed(tokens, w["embed.weight"], dims)
    for i in range(dims.layers):
        X, _ = layer(X, w, i, dims, mode)
    return head(X, w, dims, mode)


def hold_undecided(lg, margin, pick_margin: float, depth: float):
    """Logits (..., V) with the positions whose ``margin`` (...) is
    under ``pick_margin`` levelled ``depth`` deviations under their
    best: there every token above that level counts as the best, and
    one below it lies as far under the level as it did."""
    level = jnp.max(lg, axis=-1, keepdims=True) - depth * jnp.std(
        lg, axis=-1, keepdims=True)
    return jnp.where((margin < pick_margin)[..., None],
                     jnp.minimum(lg, level), lg)


def layerwise(tokens, positions, dims: Dims, mode: str,
              get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
              shapes_of_layer: Callable[[int], Dict[str, tuple]],
              top_shapes: Dict[str, tuple]):
    """(logits (B, P, V), margin (B, P)) at ``positions`` (B, P) of
    (B, T) ``tokens``: the logits as they stand and the least margin
    over the expert layers (:func:`route`; +inf with no expert layer),
    holding one layer's leaves and walking one row at a time (a row's
    float32 state is T x n x C: 0.94 GB at 16384 positions; the rows
    are kept apart and each is consumed by its layer's program):
    ``get(shapes)`` makes the named leaves. Sequences are independent
    and every layer is causal, so padding a row's tail changes nothing
    at earlier positions. One program a kind of layer: a layer's leaves
    go in under the first index of its kind."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    rows = [_embed(tokens[r], emb["embed.weight"], dims)
            for r in range(tokens.shape[0])]
    del emb
    margins = [jnp.full(tokens.shape[1:], jnp.inf)] * len(rows)
    for i in range(dims.layers):
        j = 0 if dims.is_dense(i) else dims.dense_layers
        w = {k.replace(f"blocks.{i}.", f"blocks.{j}."): a
             for k, a in get(shapes_of_layer(i)).items()}
        for r in range(len(rows)):
            rows[r], routed = _layer_row(rows[r], w, j, dims, mode)
            if routed is not None:
                margins[r] = jnp.minimum(margins[r], routed[1])
        del w
    picked = jnp.stack([_pick(X, positions[r])
                        for r, X in enumerate(rows)])
    del rows
    margin = jnp.stack([m[positions[r]] for r, m in enumerate(margins)])
    w = get({k: s for k, s in top_shapes.items() if k != "embed.weight"})
    return _head(picked, w, dims, mode), margin


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple]):
    """What ``check.serve_reference`` asks for. ``"f32"``:
    :func:`layerwise`'s logits with the undecided positions held to
    less (:func:`hold_undecided`); they are what a served token's gap is
    measured on. ``"fp8"`` (the control, read for its best token only):
    the logits as they stand."""
    lg, margin = layerwise(tokens, positions, dims, mode, get,
                           shapes_of_layer, top_shapes)
    if mode != "f32":
        return lg
    return _hold(lg, margin, PICK_MARGIN, UNDECIDED_DEPTH)


_embed = jax.jit(embed, static_argnums=2)
_layer_row = jax.jit(layer, static_argnums=(2, 3, 4), donate_argnums=0)
_pick = jax.jit(lambda X, at: X[at])
_head = jax.jit(head, static_argnums=(2, 3))
_hold = jax.jit(hold_undecided, static_argnums=(2, 3), donate_argnums=0)
