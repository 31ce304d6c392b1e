"""The plain reference of the ``window_moe`` family: a decoder whose
layers mix tokens by gated grouped-query softmax attention, FULL
(causal over every earlier position) or SLIDING (over the last
``window`` positions) by layer, with query heads and a rotary embedding
that depend on the kind, and mix channels by a SwiGLU (``"dense"``
layers) or by sigmoid-routed experts plus a shared expert (``"sparse"``
layers), in jax.numpy.

The equations, from the configuration's keys (what the config does not
carry is marked †; the reading taken and the one not taken are below).
Layer ``l`` has a kind ``layer_types[l]`` and ``H_l =
num_attention_heads_per_layer[l]`` query heads over ``KV =
num_key_value_heads`` key-value heads, every head ``d = head_dim`` wide
(the queries are ``H_l d`` wide whatever the hidden size), no biases::

    x_0 = E[id];  logits = RMSNorm_w(x_L) W_head
    u   = RMSNorm_w(x)
    q   = u W_q  (H_l x d);   k = u W_k  (KV x d);   v = u W_v  (KV x d)
    g   = sigmoid(u W_g)      (H_l numbers a position †)
    full layer:    q, k turned on the FIRST  partial_rotary_factor x d
                   numbers of each head (pairs (i, i + half) of that
                   part), at YaRN's frequencies over those pairs (theta,
                   factor over original_max_position_embeddings,
                   beta_fast, beta_slow), cosines and sines times
                   attention_factor; the rest of the head unrotated
    sliding layer: q, k turned on all d numbers, its own theta, no scaling
    s[t, j] = q_t . k_j / sqrt(d)   for j <= t              (full)
                                    for t - window < j <= t (sliding:
                                    ``window`` positions with itself)
    a_h = softmax_j(s) v,  head h reading key-value head h // (H_l / KV)
    x   = x + concat_h(g_h a_h) W_o
    u   = RMSNorm_w(x)
    "dense" layer:   x = x + W_d (silu(u W_gate) * (u W_up))
    "sparse" layer:  sc = sigmoid(u W_r) †;  picks = top_k(sc + e_bias) †
        gate_e = scaling x sc_e / (sum of the picks' sc + 1e-20) †
        x = x + sum_{e in picks, e held} gate_e W_d,e (silu(u W_g,e) *
                (u W_u,e))  +  Shared(u)

† What ``config.json`` leaves open, how it is read here, and the reading
NOT taken:

- ``"gating": true``. Read as ONE sigmoid gate a head on the attention
  output, computed from the layer's normed input (``W_g``: hidden x
  H_l). The published parameter count closes with it (33.44 B against
  "33.4B") and leaves room for no wider form: an elementwise gate (hidden
  x H_l d) would add 0.63 B. NOT taken: a sigmoid gate on the shared
  expert's output, which ``qwen2_moe`` has under the same key name
  ``shared_expert_intermediate_size``.
- the router's score function and normalisation. Read as DeepSeek-V3's
  ``noaux_tc`` rule, which 256 outputs, 8 picks and a factor of 2.5 come
  from: sigmoid scores in float32, picks by score plus a selection bias
  (one a router output), gates the picks' own scores over their sum,
  times ``moe_routed_scaling_factor``; ``moe_apply_router_weight_on_input``
  false: a gate weighs its expert's OUTPUT. NOT taken: softmax over the
  router's outputs with or without renormalised top-k.
- a per-head norm on queries and keys. Read as none (no key names one).
  NOT taken: RMSNorm over each head of q and k before the rotation.
- YaRN's convention. Frequencies over the ``partial_rotary_factor x d /
  2`` rotated pairs; cosines and sines scaled by ``attention_factor``, so
  the rotated part of a score is times its square and the unrotated
  part is not. NOT taken: the factor on the whole score.

No cache, no ring, no kernel: a sliding layer is a banded mask over the
whole sequence. Queries go in blocks of :data:`QUERY_BLOCK` inside
groups of :data:`KEY_GROUP` rows whose keys end with the group (a
16384-token row's scores never exist whole); that changes no number.
The routed experts are a masked loop over the experts HELD here,
``(first, count)`` of the router's width: what the absent experts would
add is left out, as in the program.

**What the reference leaves undecided: nothing, by this family's own
chip readings.** ``latent_moe_f32.py``'s machinery is here whole: a pick
is a step function of the scores, a served model in bfloat16 takes
either side of a near-tie, :func:`route` gives each token's MARGIN (how
far the nearest HELD expert lies from the edge of the picks),
:func:`layerwise` carries the least margin over the expert layers, and
:func:`layerwise_logits` levels a position whose margin is under
:data:`PICK_MARGIN` :data:`UNDECIDED_DEPTH` deviations under its best
(:func:`hold_undecided`). But with 19 expert layers of 32 held experts
99.9% of the positions have a margin under that family's 0.01, and a
flipped pick costs little here (one of eight picks at a gate of 0.3): on
the chip the served tokens lie at most 0.26 deviations under the
reference's best AS THE LOGITS STAND, while levelling every position 2.5
deviations let the float8 control pass. So :data:`PICK_MARGIN` is 0 (a
margin is never negative: no position is levelled) and every served
position is compared as it stands (PERF.md section 6, PR 52).

Float32 throughout with ``jax.default_matmul_precision("highest")``
semantics (every product names ``Precision.HIGHEST``). Imports nothing
of the program. ``mode`` selects the arithmetic of every matrix
multiplication by a weight (the router among them): ``"f32"`` the
reference, ``"fp8"`` the control (inputs rounded to float8 e4m3);
scores, softmax, sigmoids and the norms stay float32 in both.
``window_off=True`` (no caller of the harness passes it) lets a sliding
layer attend every earlier position: what the model would be without
its windows, for the test that the comparison sees the mechanism.

Leaf layout: linear weights are (in, out), expert weights are stacked
over the held experts, the head is (hidden, vocab).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK, KEY_GROUP = 128, 2048
# A position would be UNDECIDED where a held expert's score + bias lies
# within PICK_MARGIN of the edge of the picks, and then levelled
# UNDECIDED_DEPTH deviations under its best. On the chip (PERF.md section
# 6, PR 52; tools/window_control.py --as-they-stand 1, 8,879 served
# positions of 3 seeds): 99.9% of the positions have a margin under
# latent_moe_f32.py's 0.01 (96% under 0.005) and the widest gap AS THE
# LOGITS STAND is 0.224 to 0.258 sd a seed (99.9% of the positions under
# 0.21), the float8 control's 0.659: nothing needs levelling, and
# levelling everything hid the control. The depth is latent_moe_f32.py's,
# for whoever sets a margin again.
PICK_MARGIN = 0.0
UNDECIDED_DEPTH = 2.5
FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    kinds: Tuple[str, ...]           # FULL or SLIDING, a layer
    heads: Tuple[int, ...]           # query heads, a layer
    kv_heads: int
    head_dim: int
    window: int
    mixes: Tuple[str, ...]           # "dense" or "sparse", a layer
    ffn: int                         # a dense layer's SwiGLU width
    expert_width: int
    shared_width: int
    experts: int                     # the router's width
    top_k: int
    held: Tuple[int, int]            # (first, count) of the experts held
    scaling: float
    vocab: int
    eps: float
    full_theta: float
    full_rotary: int                 # rotated numbers of a full layer's head
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    attention_factor: float
    sliding_theta: float
    sliding_rotary: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """The router's width is the published ``num_experts`` where
        ``reduced`` names the key (the file then gives the number held
        and ``first``); the three lists a layer are cut to
        ``num_hidden_layers`` entries."""
        cut = {r["key"]: r for r in c.get("reduced", [])}
        row = cut.get("num_experts")
        held = c["num_experts"]
        for key, want in (("gating", True), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("moe_apply_router_weight_on_input", False)):
            if c[key] != want:
                raise ValueError(f"{key} = {c[key]!r}: {want!r} is what "
                                 "is written")
        n = c["num_hidden_layers"]
        kinds = tuple(c["layer_types"][:n])
        heads = tuple(c["num_attention_heads_per_layer"][:n])
        mixes = tuple(c["mlp_layer_types"][:n])
        if not (len(kinds) == len(heads) == len(mixes) == n):
            raise ValueError(f"the lists a layer are shorter than {n}")
        if set(kinds) - {FULL, SLIDING} or set(mixes) - {"dense", "sparse"}:
            raise ValueError(f"layer kinds {set(kinds)} / {set(mixes)}")
        full = c["rope_parameters"][FULL]
        slide = c["rope_parameters"][SLIDING]
        if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
            raise ValueError("YaRN on the full layers and a plain rotary "
                             "embedding on the sliding ones is what is "
                             "written")
        d = c["head_dim"]
        return cls(
            hidden=c["hidden_size"], layers=n, kinds=kinds, heads=heads,
            kv_heads=c["num_key_value_heads"], head_dim=d,
            window=c["sliding_window"], mixes=mixes,
            ffn=c["intermediate_size"],
            expert_width=c["moe_intermediate_size"],
            shared_width=c["shared_expert_intermediate_size"],
            experts=row["published"] if row else held,
            top_k=c["num_experts_per_tok"],
            held=(row.get("first", 0) if row else 0, held),
            scaling=float(c["moe_routed_scaling_factor"]),
            vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
            full_theta=float(full["rope_theta"]),
            full_rotary=int(round(d * full["partial_rotary_factor"])),
            yarn_factor=float(full["factor"]),
            yarn_original=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]),
            sliding_theta=float(slide["rope_theta"]),
            sliding_rotary=int(round(d * slide["partial_rotary_factor"])))

    def is_dense(self, i: int) -> bool:
        return self.mixes[i] == "dense"

    def is_sliding(self, i: int) -> bool:
        return self.kinds[i] == SLIDING

    def heads_of(self, kind: str) -> int:
        """The query heads of every layer of ``kind`` (one number: a
        layer's heads follow its kind)."""
        got = {h for k, h in zip(self.kinds, self.heads) if k == kind}
        if len(got) != 1:
            raise ValueError(f"{kind} layers have {sorted(got)} heads")
        return got.pop()


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


def frequencies(dims: Dims, sliding: bool):
    """(rotated / 2,) rotary frequencies of a layer of that kind. A
    sliding layer: ``theta^(-i / half)``. A full layer, YaRN: pair ``i``
    turns at that where it makes more than ``beta_fast`` turns over the
    original context, at that over ``factor`` where it makes fewer than
    ``beta_slow``, a linear blend between."""
    if sliding:
        half = dims.sliding_rotary // 2
        return dims.sliding_theta ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    half = dims.full_rotary // 2

    def pair_of(turns):
        return half * math.log(dims.yarn_original / (turns * 2 * math.pi)
                               ) / math.log(dims.full_theta)

    low = max(math.floor(pair_of(dims.beta_fast)), 0)
    high = min(math.ceil(pair_of(dims.beta_slow)), half - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    plain = dims.full_theta ** (-i / half)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / dims.yarn_factor * ramp + plain * (1.0 - ramp)


def rope(x, positions, dims: Dims, sliding: bool):
    """(T, H, d) with the leading rotated part of each head turned
    (rotate-half over that part), the rest as it is."""
    freqs = frequencies(dims, sliding)
    half = freqs.shape[0]
    factor = 1.0 if sliding else dims.attention_factor
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def softmax_attention(q, k, v, scale: float, window=None):
    """Causal attention of one sequence: ``q`` (T, H, d), ``k``, ``v``
    (T, KV, d) -> (T, H, d), query head ``h`` on key-value head ``h //
    (H / KV)``; with a ``window`` only the last ``window`` positions, the
    query's own among them. Query blocks inside key groups."""
    t, h, d = q.shape
    kv = k.shape[1]
    q = q.reshape(t, kv, h // kv, d)
    group = KEY_GROUP if t % KEY_GROUP == 0 else t
    block = QUERY_BLOCK if group % QUERY_BLOCK == 0 else group
    out = []
    for g0 in range(0, t, group):
        kk, vv = k[:g0 + group], v[:g0 + group]
        keys = jnp.arange(g0 + group)

        def one(inp):
            qb, at = inp
            s = jnp.einsum("ikgd,jkd->kgij", qb, kk,
                           precision=HIGHEST) * scale
            keep = keys[None, :] <= at[:, None]
            if window is not None:
                keep &= keys[None, :] > at[:, None] - window
            s = jnp.where(keep, s, -jnp.inf)
            return jnp.einsum("kgij,jkd->ikgd", jax.nn.softmax(s, axis=-1),
                              vv, precision=HIGHEST)

        qb = q[g0:g0 + group].reshape(-1, block, *q.shape[1:])
        at = (g0 + jnp.arange(group)).reshape(-1, block)
        out.append(jax.lax.map(one, (qb, at)).reshape(group, h, d))
    return jnp.concatenate(out, axis=0)


def attention(u, w, p: str, dims: Dims, mode: str, sliding: bool,
              window_off: bool = False):
    """Gated attention on one sequence's normed input ``u`` (T, C); the
    query heads are read off ``W_q``'s width."""
    t, d = u.shape[0], dims.head_dim
    pos = jnp.arange(t)
    q = matmul(u, w[p + "q_proj.weight"], mode).reshape(t, -1, d)
    k = matmul(u, w[p + "k_proj.weight"], mode).reshape(t, dims.kv_heads, d)
    v = matmul(u, w[p + "v_proj.weight"], mode).reshape(t, dims.kv_heads, d)
    g = jax.nn.sigmoid(matmul(u, w[p + "gate_proj.weight"], mode))
    a = softmax_attention(
        rope(q, pos, dims, sliding), rope(k, pos, dims, sliding), v,
        d ** -0.5, dims.window if sliding and not window_off else None)
    return matmul((a * g[:, :, None]).reshape(t, -1),
                  w[p + "out_proj.weight"], mode)


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
    """(what the held routed experts add for tokens ``u`` (T, C), the
    tokens' margins): one held expert at a time on every token, weighted
    by the gate of the tokens that picked it (zero for the others)."""
    top_i, gates, margin = route(u, w, p, dims, mode)
    first, count = dims.held

    def one(out, e):
        g = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        return out + g[:, None] * gated(
            u, w[p + "w_gate"][e], w[p + "w_up"][e], w[p + "w_down"][e],
            mode), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))
    return out, margin


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str,
          window_off: bool = False):
    """Block ``i`` on one sequence's stream ``x`` (T, C); its leaves
    are named ``blocks.<i>.*``. Returns (x', margin): the expert
    layer's (T,) margins of :func:`route`, None for a dense layer."""
    p = f"blocks.{i}."
    x = x + attention(rms_norm(x, w[p + "norm1.weight"], dims.eps), w,
                      p + "mixer.", dims, mode, dims.is_sliding(i),
                      window_off)
    u = rms_norm(x, w[p + "norm2.weight"], dims.eps)
    if dims.is_dense(i):
        return x + gated(u, w[p + "mlp.gate.weight"], w[p + "mlp.up.weight"],
                         w[p + "mlp.down.weight"], mode), None
    routed, margin = experts(u, w, p + "moe.", dims, mode)
    shared = gated(u, w[p + "shared.gate.weight"], w[p + "shared.up.weight"],
                   w[p + "shared.down.weight"], mode)
    return x + routed + shared, margin


def embed(tokens, table):
    return table[tokens].astype(jnp.float32)    # rows first, then float32


def head(x, w, dims: Dims, mode: str):
    return matmul(rms_norm(x, w["norm_f.weight"], dims.eps), w["lm_head"],
                  mode)


def logits(tokens, w, dims: Dims, mode: str = "f32",
           window_off: bool = False):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``."""
    x = embed(tokens, w["embed.weight"])
    for i in range(dims.layers):
        x, _ = layer(x, w, i, dims, mode, window_off)
    return head(x, w, dims, mode)


def hold_undecided(lg, margin, pick_margin: float, depth: float):
    """Logits (..., V) with the positions whose ``margin`` (...) is
    under ``pick_margin`` levelled ``depth`` deviations under their
    best: there every token above that level counts as the best, and
    one below it lies as far under the level as it did."""
    level = jnp.max(lg, axis=-1, keepdims=True) - depth * jnp.std(
        lg, axis=-1, keepdims=True)
    return jnp.where((margin < pick_margin)[..., None],
                     jnp.minimum(lg, level), lg)


def program_key(dims: Dims, i: int) -> int:
    """The first layer of layer ``i``'s kind and channel mix: one
    compiled program serves every layer that shares both."""
    return next(j for j in range(dims.layers)
                if (dims.kinds[j], dims.mixes[j], dims.heads[j])
                == (dims.kinds[i], dims.mixes[i], dims.heads[i]))


def layerwise(tokens, positions, dims: Dims, mode: str,
              get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
              shapes_of_layer: Callable[[int], Dict[str, tuple]],
              top_shapes: Dict[str, tuple], window_off: bool = False):
    """(logits (B, P, V), margin (B, P)) at ``positions`` (B, P) of
    (B, T) ``tokens``: the logits as they stand and the least margin
    over the expert layers (:func:`route`; +inf with no expert layer),
    holding one layer's leaves and walking one row at a time:
    ``get(shapes)`` makes the named leaves. Sequences are independent
    and every layer is causal, so padding a row's tail changes nothing
    at earlier positions. One program a kind of layer: a layer's leaves
    go in under the first index of its kind."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    rows = [_embed(tokens[r], emb["embed.weight"])
            for r in range(tokens.shape[0])]
    del emb
    margins = [jnp.full(tokens.shape[1:], jnp.inf)] * len(rows)
    for i in range(dims.layers):
        j = program_key(dims, i)
        w = {k.replace(f"blocks.{i}.", f"blocks.{j}."): a
             for k, a in get(shapes_of_layer(i)).items()}
        for r in range(len(rows)):
            rows[r], margin = _layer_row(rows[r], w, j, dims, mode,
                                         window_off)
            if margin is not None:
                margins[r] = jnp.minimum(margins[r], margin)
        del w
    picked = jnp.stack([_pick(x, positions[r]) for r, x in enumerate(rows)])
    del rows
    margin = jnp.stack([m[positions[r]] for r, m in enumerate(margins)])
    w = get({k: s for k, s in top_shapes.items() if k != "embed.weight"})
    return _head(picked, w, dims, mode), margin


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple], window_off: bool = False):
    """What ``check.serve_reference`` asks for. ``"f32"``:
    :func:`layerwise`'s logits with the undecided positions held to
    less (:func:`hold_undecided`); they are what a served token's gap is
    measured on. ``"fp8"`` (the control, read for its best token only):
    the logits as they stand."""
    lg, margin = layerwise(tokens, positions, dims, mode, get,
                           shapes_of_layer, top_shapes, window_off)
    if mode != "f32":
        return lg
    return _hold(lg, margin, PICK_MARGIN, UNDECIDED_DEPTH)


_embed = jax.jit(embed)
_layer_row = jax.jit(layer, static_argnums=(2, 3, 4, 5), donate_argnums=0)
_pick = jax.jit(lambda x, at: x[at])
_head = jax.jit(head, static_argnums=(2, 3))
_hold = jax.jit(hold_undecided, static_argnums=(2, 3), donate_argnums=0)
