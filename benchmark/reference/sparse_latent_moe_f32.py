"""The plain reference of the ``sparse_latent_moe`` family: a decoder
whose layers mix tokens by multi-head latent attention (DeepSeek-V2,
arXiv:2405.04434) that reads only the positions a learned indexer
picks (the lightning indexer of DeepSeek-V3.2-Exp's sparse attention,
whose keys ``index_n_heads``, ``index_head_dim``, ``index_topk`` the
configuration carries) and channels by a SwiGLU (the leading dense
layers) or by sigmoid-routed experts plus a shared expert
(DeepSeek-V3's ``noaux_tc`` rule), over the plain residual path, in
jax.numpy.

The equations (``x`` the residual of one position)::

    x_0 = E[id];  logits = RMSNorm_w(x) W_head
    layer:  x = x + Attn(RMSNorm_w(x));  x = x + FFN(RMSNorm_w(x))

    Attn, u at position t, head j:
      c_q = RMSNorm_w(u W_qa);  [q^N_j ; q^R_j] = (c_q W_qb)_j
      [c ; k^R] = u W_kva;  c_t = RMSNorm_w(c);  r_t = rope(k^R, t)
      [k^N_sj ; v_sj] = (c_s W_kvb)_j
      a_tsj = (q^N_j . k^N_sj + rope(q^R_j, t) . r_s) (nope + rope)^-1/2
    indexer, head i of index_n_heads, index_head_dim numbers:
      q^I_i = rope'((c_q W^I_q)_i, t);  k^I_s = rope'(LayerNorm(u_s W^I_k), s)
      w_i = (u W^I_w)_i index_n_heads^-1/2 index_head_dim^-1/2
      I_ts = sum_i w_ti relu(q^I_ti . k^I_s)
      rope': the rotary embedding on the FIRST qk_rope_head_dim numbers
    selection:  S_t = the min(t + 1, index_topk) positions s <= t of
      largest I_ts, ties to the lower position
      o_j = sum_{s in S_t} softmax_{s in S_t}(a_tsj) v_sj
      Attn = concat_j(o_j) W_o

    FFN, layers < first_k_dense_replace:  W_d (silu(u W_g) * (u W_u))
    FFN, the others:  sc = sigmoid(u W_r);  picks = top_k(sc + e_bias)
      g = scaling * sc[picks] / (sum sc[picks] + 1e-20)
      sum_{e in picks, e held} g_e W_d,e (silu(u W_g,e) * (u W_u,e))  + Shared(u)

The attention is the NON-absorbed form with the selection as a mask:
every head's keys and values are made from the records and attend as
heads; no cache, no kernels, and the pick is a SORT (the program
searches for the threshold bit by bit). Queries go in blocks of
:data:`QUERY_BLOCK` inside groups of :data:`KEY_GROUP` rows whose keys
end with the group, and heads in groups of :data:`HEAD_GROUP`: a
block's index scores, its pick and its attention scores are (block,
keys), so a 32768-position row's ``T x T`` never exists; that changes
no number. The routed experts are a masked loop over the experts HELD
here, ``(first, count)`` of the router's width, and the vocabulary is
the slice ``[0, vocab)`` the configuration keeps (ids, head and logits
alike): what the absent experts or columns would add is left out, as
in the program.

**What the reference leaves undecided.** An expert pick is a step
function of the scores: where the lowest picked and the highest
unpicked of ``sc + e_bias`` lie closer than the arithmetic's own
noise, a served model in bfloat16 takes either, both are the model,
and a pick carries a gate of about ``scaling / top_k`` and an output as
large as a sublayer's. :func:`route` therefore also gives each token's
MARGIN (how far the nearest HELD expert lies from the edge of the
picks), :func:`layerwise` carries the least margin over the expert
layers to each position, and :func:`layerwise_logits` (what
``check.serve_gap`` compares) holds a position whose margin is under
:data:`PICK_MARGIN` to less: its logits are levelled
:data:`UNDECIDED_DEPTH` deviations under their best
(:func:`hold_undecided`). **A near-tied POSITION pick is a second
margin with constants of its own.** The selection is a step function
too: where the ``index_topk``-th and the next largest index score of a
query lie closer than the arithmetic's noise, bfloat16 index scores
pick other positions than float32 ones, and with SEEDED weights that
matters as it would not in a trained model: seeded scores are all but
level, so attention is a plain mean over the picked records and the
record at rank ``index_topk`` weighs what every other does (a trained
indexer picks what attention would weigh most, and the edge of the
pick carries next to nothing). On the chip the program with and the
reference with the selection differ at a quarter of the served tokens,
where both without it differ at 3% (PERF.md section 6, PR 45).
:func:`pick` therefore gives each query's POSITION MARGIN, the
distance between those two scores in deviations of the query's live
scores (+inf while every live position is picked), carried like the
other as the least over the layers; a position under
:data:`POSITION_MARGIN` is levelled :data:`POSITION_DEPTH` deviations
under its best. With a dozen thousand candidates a query the scores at
the edge lie 3e-4 deviations apart for a noise of 4e-3: at the cell's
contexts EVERY served position is held so, and the comparison reads how
far below the reference's NEAR-best a served token lies; what it still
sees: the float8 control, and the program with its selection switched
off, which serves other tokens altogether.

Float32 throughout with ``jax.default_matmul_precision("highest")``
semantics (every product names ``Precision.HIGHEST``). Imports nothing
of the program. ``mode`` selects the arithmetic of every matrix
multiplication by a weight (the indexer's and the router's among
them), as in ``decoder_f32``: ``"f32"`` the reference, ``"fp8"`` the
control (inputs rounded to float8 e4m3); scores, softmax, sigmoids, the
selection and the norms stay float32 in both. ``select=False`` (no
caller of the harness passes it) attends every live position: what the
model would be without its indexer, for the test that the comparison
sees the mechanism.

Departures and assumptions are listed in the configuration file under
``assumed``. Leaf layout: linear weights are (in, out), expert weights
are stacked over the held experts, the head is (hidden, vocab).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK, KEY_GROUP, HEAD_GROUP = 256, 8192, 16
# A position is UNDECIDED where, in some expert layer, a held expert's
# score + bias lies within PICK_MARGIN of the edge of the picks; its
# logits are then levelled UNDECIDED_DEPTH deviations under their best.
# The constants are reference/latent_moe_f32.py's (the same routing rule
# and the same seeded leaves; PERF.md section 6, PR 41 and PR 45).
PICK_MARGIN = 0.01
UNDECIDED_DEPTH = 2.5
# ... and where, in some layer, the index scores at ranks index_topk and
# index_topk + 1 of its query lie within POSITION_MARGIN deviations of
# the query's live scores; then levelled POSITION_DEPTH deviations. On
# the chip (tools/route_margins.py, 8,189 served positions of 4 seeds;
# PERF.md section 6, PR 45): the widest position margin of any served
# position was 6.4e-4 (median 4.7e-5), so every one is held; with the
# expert hold alone the widest gap was 0.35 to 0.49 sd a seed (99.9% of
# the positions under 0.59 as they stand), and with neither side
# selecting 0.019.
POSITION_MARGIN = 0.002
POSITION_DEPTH = 0.75


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
    index_heads: int
    index_dim: int
    index_topk: int
    index_eps: float             # the index key's LayerNorm
    ffn: int                     # the dense SwiGLU's width
    expert_width: int
    shared_width: int
    experts: int                 # the router's width
    top_k: int
    held: Tuple[int, int]        # (first, count) of the experts held
    scaling: float
    vocab: int                   # the slice [0, vocab) kept here
    theta: float
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """The router's width is the published ``n_routed_experts``
        where ``reduced`` names the key (the file then gives the number
        held and ``first``); ``vocab_size`` is the slice kept."""
        cut = {r["key"]: r for r in c.get("reduced", [])}
        row = cut.get("n_routed_experts")
        held = c["n_routed_experts"]
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("moe_layer_freq", 1),
                          ("tie_word_embeddings", False),
                          ("attention_bias", False), ("hidden_act", "silu")):
            if c[key] != want:
                raise ValueError(f"{key} = {c[key]!r}: {want!r} is what "
                                 "is written")
        if c["rope_parameters"]["rope_type"] != "default":
            raise ValueError("the unscaled rotary embedding is what is "
                             "written")
        if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
            raise ValueError("qk_head_dim is not nope + rope")
        return cls(
            hidden=c["hidden_size"], layers=c["num_hidden_layers"],
            dense_layers=min(c["first_k_dense_replace"],
                             c["num_hidden_layers"]),
            heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
            kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
            index_topk=c["index_topk"],
            index_eps=float(c.get("index_layer_norm_eps", 1e-6)),
            ffn=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
            experts=row["published"] if row else held,
            top_k=c["num_experts_per_tok"],
            held=(row.get("first", 0) if row else 0, held),
            scaling=float(c["routed_scaling_factor"]),
            vocab=c["vocab_size"],
            theta=float(c["rope_parameters"]["rope_theta"]),
            eps=float(c["rms_norm_eps"]))

    def is_dense(self, i: int) -> bool:
        return i < self.dense_layers

    @property
    def score_scale(self) -> float:
        return (self.nope + self.rope) ** -0.5

    @property
    def index_scale(self) -> float:
        return self.index_heads ** -0.5 * self.index_dim ** -0.5


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
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def layer_norm(x, weight, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32) + bias.astype(jnp.float32))


def rope(x, positions, dims: Dims):
    """Rotate-half rotary embedding of the first ``dims.rope`` numbers
    of (T, H, d) at ``positions``; the rest pass."""
    half = dims.rope // 2
    freqs = dims.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:dims.rope]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dims.rope:]], -1)


def pick(scores, live, k: int):
    """((block, keys) bool, (block,) float32): per row the ``min(k,
    live)`` live positions of largest score, ties to the lower position,
    by a sort; and the row's POSITION MARGIN, the distance from the
    ``k``-th largest live score to the next in standard deviations of
    the row's live scores, +inf where no live position is left out."""
    s = jnp.where(live, scores, -jnp.inf)
    if s.shape[-1] <= k:
        return live, jnp.full(s.shape[:1], jnp.inf)
    srt = -jnp.sort(-s, axis=-1)
    kth = srt[:, k - 1:k]
    above, tied = s > kth, s == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    keep = (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & live
    n = jnp.sum(live, axis=-1)
    mean = jnp.sum(jnp.where(live, scores, 0.0), axis=-1) / n
    dev = jnp.sqrt(jnp.sum(jnp.where(live, jnp.square(
        scores - mean[:, None]), 0.0), axis=-1) / n)
    margin = jnp.where(n > k, (kth[:, 0] - srt[:, k]) / dev, jnp.inf)
    return keep, margin


def sparse_attention(q, k, v, qi, wi, ki, dims: Dims, select: bool = True):
    """Causal attention of one sequence under the indexer's pick:
    ``q``, ``k`` (T, H, dq), ``v`` (T, H, dv), ``qi`` (T, Hi, di),
    ``wi`` (T, Hi), ``ki`` (T, di) -> ((T, H, dv), the queries'
    position margins (T,) of :func:`pick`); query blocks inside key
    groups, heads in groups."""
    t, h = q.shape[:2]
    group = KEY_GROUP if t % KEY_GROUP == 0 else t
    block = QUERY_BLOCK if group % QUERY_BLOCK == 0 else group
    hg = HEAD_GROUP if h % HEAD_GROUP == 0 else h
    out, margins = [], []
    for g0 in range(0, t, group):
        end = g0 + group
        keys = jnp.arange(end)

        def one(inp, end=end, keys=keys):
            qb, qib, wib, at = inp
            live = keys[None, :] <= at[:, None]
            keep, margin = live, jnp.full(at.shape, jnp.inf)
            if select and end > dims.index_topk:
                s = jnp.einsum("ihd,jd->hij", qib, ki[:end],
                               precision=HIGHEST)
                keep, margin = pick(jnp.sum(wib.T[:, :, None]
                                            * jnp.maximum(s, 0.0), axis=0),
                                    live, dims.index_topk)
            parts = []
            for h0 in range(0, h, hg):
                a = jnp.einsum("ihd,jhd->hij", qb[:, h0:h0 + hg],
                               k[:end, h0:h0 + hg],
                               precision=HIGHEST) * dims.score_scale
                a = jnp.where(keep[None], a, -jnp.inf)
                parts.append(jnp.einsum(
                    "hij,jhd->ihd", jax.nn.softmax(a, axis=-1),
                    v[:end, h0:h0 + hg], precision=HIGHEST))
            return jnp.concatenate(parts, axis=1), margin

        cut = lambda a: a[g0:end].reshape(-1, block, *a.shape[1:])
        at = (g0 + jnp.arange(group)).reshape(-1, block)
        o, m = jax.lax.map(one, (cut(q), cut(qi), cut(wi), at))
        out.append(o.reshape(group, *v.shape[1:]))
        margins.append(m.reshape(group))
    return jnp.concatenate(out, axis=0), jnp.concatenate(margins)


def attention(x, w, p: str, dims: Dims, mode: str, select: bool = True):
    """Latent attention under the indexer's pick, non-absorbed, on one
    sequence ``x`` (T, C): ((T, C), the position margins (T,))."""
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
    qi = rope(matmul(cq, w[p + "index_q_proj.weight"], mode).reshape(
        t, dims.index_heads, dims.index_dim), pos, dims)
    ki = rope(layer_norm(matmul(x, w[p + "index_k_proj.weight"], mode),
                         w[p + "index_k_norm.weight"],
                         w[p + "index_k_norm.bias"],
                         dims.index_eps)[:, None, :], pos, dims)[:, 0, :]
    wi = matmul(x, w[p + "index_w_proj.weight"], mode) * dims.index_scale
    o, margin = sparse_attention(q, k, kvb[..., dims.nope:], qi, wi, ki,
                                 dims, select)
    return matmul(o.reshape(t, -1), w[p + "out_proj.weight"], mode), margin


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
    """(what the held routed experts add for tokens ``u`` (T, C),
    margin (T,)): one held expert at a time on every token, weighted by
    the gate of the tokens that picked it (zero for the others)."""
    top_i, gates, margin = route(u, w, p, dims, mode)
    first, count = dims.held
    out = jnp.zeros_like(u)
    for e in range(count):
        g = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        out = out + g[:, None] * gated(
            u, w[p + "w_gate"][e], w[p + "w_up"][e], w[p + "w_down"][e],
            mode)
    return out, margin


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str,
          select: bool = True):
    """Block ``i`` on one sequence's residual ``x`` (T, C); its leaves
    are named ``blocks.<i>.*`` and its FFN is the dense one where
    ``dims.is_dense(i)``. Returns (x', margin, position margin): the
    expert layer's margin (T,) of :func:`route`, +inf for a dense
    layer, and the mixer's position margin (T,) of :func:`pick`."""
    p = f"blocks.{i}."
    a, at = attention(rms_norm(x, w[p + "norm1.weight"], dims.eps), w,
                      p + "mixer.", dims, mode, select)
    x = x + a
    u = rms_norm(x, w[p + "norm2.weight"], dims.eps)
    if dims.is_dense(i):
        return x + gated(u, w[p + "mlp.gate.weight"],
                         w[p + "mlp.up.weight"],
                         w[p + "mlp.down.weight"],
                         mode), jnp.full(at.shape, jnp.inf), at
    routed, margin = experts(u, w, p + "moe.", dims, mode)
    return x + routed + gated(
        u, w[p + "shared.gate.weight"], w[p + "shared.up.weight"],
        w[p + "shared.down.weight"], mode), margin, at


def embed(tokens, table):
    return table[tokens].astype(jnp.float32)    # rows first, then float32


def head(x, w, dims: Dims, mode: str):
    return matmul(rms_norm(x, w["norm_f.weight"], dims.eps), w["lm_head"],
                  mode)


def logits(tokens, w, dims: Dims, mode: str = "f32", select: bool = True):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``."""
    x = embed(tokens, w["embed.weight"])
    for i in range(dims.layers):
        x = layer(x, w, i, dims, mode, select)[0]
    return head(x, w, dims, mode)


def hold(lg, depth):
    """Logits (..., V) with each position levelled ``depth`` (...)
    deviations under its best (0: as it stands): there every token
    above that level counts as the best, and one below it lies as far
    under the level as it did."""
    level = jnp.max(lg, axis=-1, keepdims=True) - depth[..., None] * jnp.std(
        lg, axis=-1, keepdims=True)
    return jnp.where((depth > 0)[..., None], jnp.minimum(lg, level), lg)


def hold_undecided(lg, margin, pick_margin: float, depth: float):
    """:func:`hold` of the positions whose ``margin`` (...) is under
    ``pick_margin``, ``depth`` deviations."""
    return hold(lg, jnp.where(margin < pick_margin, depth, 0.0))


def undecided_depth(margin, at):
    """How far each position is levelled: :data:`UNDECIDED_DEPTH` under
    :data:`PICK_MARGIN` of expert margin, :data:`POSITION_DEPTH` under
    :data:`POSITION_MARGIN` of position margin, the deeper where both."""
    return jnp.maximum(jnp.where(margin < PICK_MARGIN, UNDECIDED_DEPTH, 0.0),
                       jnp.where(at < POSITION_MARGIN, POSITION_DEPTH, 0.0))


def layerwise(tokens, positions, dims: Dims, mode: str,
              get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
              shapes_of_layer: Callable[[int], Dict[str, tuple]],
              top_shapes: Dict[str, tuple], select: bool = True):
    """(logits (B, P, V), margin (B, P), position margin (B, P)) at
    ``positions`` (B, P) of (B, T) ``tokens``: the logits as they stand,
    the least expert margin over the expert layers (:func:`route`) and
    the least position margin over all layers (:func:`pick`), holding
    one layer's leaves and walking one row at a time (a row's float32
    residual is T x C: 0.81 GB at 32768 positions; the rows are kept
    apart and each is consumed by its layer's program): ``get(shapes)``
    makes the named leaves. Sequences are independent and every layer is
    causal, so padding a row's tail changes nothing at earlier
    positions. One program a kind of layer: a layer's leaves go in
    under the first index of its kind."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    rows = [_embed(tokens[r], emb["embed.weight"])
            for r in range(tokens.shape[0])]
    del emb
    margins = [(jnp.full(tokens.shape[1:], jnp.inf),) * 2] * len(rows)
    for i in range(dims.layers):
        j = 0 if dims.is_dense(i) else dims.dense_layers
        w = {k.replace(f"blocks.{i}.", f"blocks.{j}."): a
             for k, a in get(shapes_of_layer(i)).items()}
        for r in range(len(rows)):
            rows[r], *got = _layer_row(rows[r], w, j, dims, mode, select)
            margins[r] = tuple(jnp.minimum(a, b)
                               for a, b in zip(margins[r], got))
        del w
    picked = jnp.stack([_pick(x, positions[r])
                        for r, x in enumerate(rows)])
    del rows
    margin, at = (jnp.stack([m[which][positions[r]]
                             for r, m in enumerate(margins)])
                  for which in (0, 1))
    w = get({k: s for k, s in top_shapes.items() if k != "embed.weight"})
    return _head(picked, w, dims, mode), margin, at


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple]):
    """What ``check.serve_reference`` asks for. ``"f32"``:
    :func:`layerwise`'s logits with the undecided positions held to
    less, by expert margin and by position margin, each at its depth
    (:func:`undecided_depth`, :func:`hold`); they are
    what a served token's gap is measured on. ``"fp8"`` (the control,
    read for its best token only): the logits as they stand."""
    lg, margin, at = layerwise(tokens, positions, dims, mode, get,
                               shapes_of_layer, top_shapes)
    if mode != "f32":
        return lg
    return _hold(lg, undecided_depth(margin, at))


_embed = jax.jit(embed)
_layer_row = jax.jit(layer, static_argnums=(2, 3, 4, 5), donate_argnums=0)
_pick = jax.jit(lambda x, at: x[at])
_head = jax.jit(head, static_argnums=(2, 3))
_hold = jax.jit(hold, donate_argnums=0)
