"""Layers of the latent-attention decoders: :class:`LatentAttention`
(multi-head latent attention, ``ops/latent_attention.py``) and the
residual paths a block is written over, :class:`PlainResidual` and
:class:`HyperConnection` (manifold-constrained hyper-connections)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.dtypes import compute_dtype_of, default_dtype
from ..core.enforce import enforce
from ..ops import latent_attention as LA
from ..ops.attention import rotary_embedding
from ..telemetry.scopes import scope
from .layer import Layer
from .layers import LayerNorm, Linear, RMSNorm

HIGHEST = lax.Precision.HIGHEST


class LatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2 / V3), ``x`` one
    position's input, head ``j`` of ``num_heads``::

        c_q = RMSNorm(x W_qa);  [q^N_j ; q^R_j] = (c_q W_qb)_j
        (``q_rank`` 0 or None, no query latent:  [q^N_j ; q^R_j] = (x W_q)_j)
        [c ; k^R] = x W_kva;  c_t = RMSNorm(c);  r_t = rope(k^R, t)
        [k^N_ij ; v_ij] = (c_i W_kvb)_j
        s_tij = (q^N_j . k^N_ij + rope(q^R_j, t) . r_i) * scale
        o_j = sum_{i <= t} softmax_i(s) v_ij;  out = concat_j(o_j) W_o

    ``scale`` = ``(nope + rope)^-1/2 * mscale^2``, ``mscale`` = ``0.1
    mscale_all_dim ln(factor) + 1`` under a YaRN-extended rotary
    embedding (``yarn``: the keyword arguments of
    ``ops.attention.yarn_frequencies``) and 1 without.

    **The cache is the record** ``(c_i, r_i)``: :meth:`init_cache`
    gives ``(slots, capacity, kv_rank)`` + ``(slots, capacity, rope)``,
    ``kv_rank + rope`` numbers a position where keys and values by head
    would be ``heads * (nope + rope + v)``. A chunk (a prefill: offset 0
    alone, :meth:`forward_chunk`; :meth:`forward`) decompresses its own
    records and attends over itself
    (``ops.latent_attention.causal_attention``); a step runs the
    **absorbed** form over the records (``latent_read``): ``q'_j =
    W^K_j q^N_j`` against ``c``, the sum of ``p c`` times ``W^V_j``
    afterwards, equal term by term. The three cached entries are the
    mixers' convention (``models/hybrid.py``): the cache goes in and
    comes back whole, as :meth:`init_cache` gave it; ``valid_len`` and
    ``decode_kernel`` are not read here (records past a prompt's end sit
    above the cursor, and the bodies are chosen by static shapes and the
    platform alone). From empty state the sublayer runs under ``attn``.

    **With an indexer** (``index_heads``, ``index_dim``, ``index_topk``
    all given; DeepSeek-V3.2-Exp's sparse attention) a query attends
    only the ``index_topk`` positions ``s <= t`` of largest index
    score, every one while there are no more than that (ties to the
    lower position; the selection is exact)::

        q^I_j = (c_q W^I_q)_j (j < index_heads, index_dim numbers)
        k^I_s = LayerNorm(x_s W^I_k)  (index_dim, one for all heads;
                                       scale, bias, epsilon 1e-6)
        w_j = (x W^I_w)_j * index_heads^-1/2 * index_dim^-1/2
        I_ts = sum_j w_tj relu(rope(q^I_tj, t) . rope(k^I_s, s))

    with the rotary embedding on the first ``rope_dim`` numbers of
    ``q^I`` and ``k^I`` (unscaled frequencies), bfloat16 inputs and
    float32 sums. The cache then holds a third array a position, the
    index key: :meth:`init_cache` gives ``(c, r, k^I)``. The indexer
    runs under the scope ``dsa_index``, beside the mixer's own scopes
    and not inside them. A step then counts ``dsa_positions_live`` /
    ``dsa_positions_read`` (``counted``): the records the step's rows
    held and those their attention was given (int32, idle rows' counted
    too; a chunk counts none; valid inside the trace of the call)."""

    state_kind, cache_record = "kv", "latent"
    cached_scope, empty_scope = None, "attn"
    counted = {}

    def __init__(self, hidden: int, num_heads: int, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, yarn: Optional[dict] = None,
                 mscale_all_dim: float = 1.0, epsilon: float = 1e-6,
                 index_heads: int = 0, index_dim: int = 0,
                 index_topk: int = 0):
        super().__init__()
        self.heads, self.kv_rank = num_heads, kv_rank
        self.nope, self.rope, self.v_dim = nope_dim, rope_dim, v_dim
        self.theta = float(rope_theta)
        self.yarn = dict(yarn) if yarn else None
        m = (0.1 * mscale_all_dim * math.log(self.yarn["factor"]) + 1.0
             if self.yarn and self.yarn["factor"] > 1 else 1.0)
        self.scale = (nope_dim + rope_dim) ** -0.5 * m * m
        self.q_rank = q_rank = int(q_rank or 0)
        if q_rank:
            self.q_a_proj = Linear(hidden, q_rank, bias_attr=False)
            self.q_a_norm = RMSNorm(q_rank, epsilon=epsilon)
            self.q_b_proj = Linear(
                q_rank, num_heads * (nope_dim + rope_dim), bias_attr=False)
        else:
            self.q_proj = Linear(hidden, num_heads * (nope_dim + rope_dim),
                                 bias_attr=False)
        self.kv_a_proj = Linear(hidden, kv_rank + rope_dim,
                                bias_attr=False)
        self.kv_a_norm = RMSNorm(kv_rank, epsilon=epsilon)
        self.kv_b_proj = Linear(kv_rank, num_heads * (nope_dim + v_dim),
                                bias_attr=False)
        self.out_proj = Linear(num_heads * v_dim, hidden, bias_attr=False)
        enforce(bool(index_heads) == bool(index_dim) == bool(index_topk),
                "an indexer is its three sizes together, got heads %s "
                "dim %s topk %s", index_heads, index_dim, index_topk)
        self.index_heads, self.index_dim = index_heads, index_dim
        self.index_topk = index_topk
        if index_topk:
            enforce(rope_dim <= index_dim, "the indexer's rotary part "
                    "(%s) is wider than its heads (%s)", rope_dim, index_dim)
            enforce(q_rank, "an indexer reads the query latent: it needs "
                    "a q_rank, got %s", q_rank)
            self.index_q_proj = Linear(q_rank, index_heads * index_dim,
                                       bias_attr=False)
            self.index_k_proj = Linear(hidden, index_dim, bias_attr=False)
            self.index_k_norm = LayerNorm(index_dim, epsilon=1e-6)
            self.index_w_proj = Linear(hidden, index_heads, bias_attr=False)

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """Zeroed records: ``c`` (B, capacity, kv_rank) and ``r`` (B,
        capacity, rope); with an indexer ``k^I`` (B, capacity,
        index_dim) as a third."""
        dt = dtype or default_dtype()
        rec = (jnp.zeros((batch, capacity, self.kv_rank), dt),
               jnp.zeros((batch, capacity, self.rope), dt))
        if self.index_topk:
            rec += (jnp.zeros((batch, capacity, self.index_dim), dt),)
        return rec

    def _query_latent(self, x):
        """``c_q`` (B, S, q_rank), RMS-normed: what ``W_qb`` and the
        indexer's ``W^I_q`` both read; ``x`` itself where the queries
        are projected directly."""
        return self.q_a_norm(self.q_a_proj(x)) if self.q_rank else x

    def _queries(self, cq, positions):
        """(q^N (B, S, H, nope), rope(q^R) (B, S, H, rope)) of the query
        latents ``cq``."""
        b, s, _ = cq.shape
        q = (self.q_b_proj if self.q_rank else self.q_proj)(cq).reshape(
            b, s, self.heads, self.nope + self.rope)
        return q[..., :self.nope], rotary_embedding(
            q[..., self.nope:], positions, self.theta, self.yarn)

    def _arrays(self, cache):
        """``cache`` as (c, r, k^I or None)."""
        n = 2 + bool(self.index_topk)
        enforce(len(cache) == n, "a latent mixer with%s an indexer takes "
                "%s cache arrays, got %s", "" if n == 3 else "out", n,
                len(cache))
        return (*cache, None)[:3]

    def _index_rope(self, a, positions):
        """The rotary embedding on the first ``rope`` numbers of (B, S,
        heads, index_dim)."""
        return jnp.concatenate(
            [rotary_embedding(a[..., :self.rope], positions, self.theta),
             a[..., self.rope:]], axis=-1)

    def _index(self, x, cq, positions):
        """(q^I (B, S, index_heads, index_dim), w (B, S, index_heads)
        float32, k^I (B, S, index_dim)) of the positions."""
        b, s, _ = x.shape
        f32 = jnp.float32
        qi = self._index_rope(self.index_q_proj(cq).reshape(
            b, s, self.index_heads, self.index_dim), positions)
        ki = self.index_k_norm(self.index_k_proj(x).astype(f32)).astype(
            x.dtype)
        ki = self._index_rope(ki[:, :, None, :], positions)[:, :, 0, :]
        w = self.index_w_proj(x).astype(f32) * (
            self.index_heads ** -0.5 * self.index_dim ** -0.5)
        return qi, w, ki

    def _records(self, x, positions):
        """(c (B, S, kv_rank), r (B, S, rope)) of the positions."""
        kv = self.kv_a_proj(x)
        r = rotary_embedding(kv[..., None, self.kv_rank:], positions,
                             self.theta, self.yarn)[..., 0, :]
        return self.kv_a_norm(kv[..., :self.kv_rank]), r

    def _w_kvb(self):
        """``W_kvb`` by head: (kv_rank, H, nope + v)."""
        return self.kv_b_proj.weight.reshape(
            self.kv_rank, self.heads, self.nope + self.v_dim)

    def _decompressed(self, x, qn, qr, c, r):
        """Heads of nope + rope / v over the chunk's own records ``c``,
        ``r``, which arrive in the type the cache keeps them in: the
        queries, keys and values meet in that type (as the absorbed
        read's do), with float32 sums."""
        b, s, _ = x.shape
        kv = self.kv_b_proj(c).reshape(b, s, self.heads,
                                       self.nope + self.v_dim)
        k = jnp.concatenate(
            [kv[..., :self.nope].astype(c.dtype),
             jnp.broadcast_to(r[:, :, None, :],
                              (b, s, self.heads, self.rope))], axis=-1)
        q = jnp.concatenate([qn, qr], axis=-1).astype(c.dtype)
        o = LA.causal_attention(q, k, kv[..., self.nope:].astype(c.dtype),
                                self.scale)
        return self.out_proj(o.astype(x.dtype).reshape(b, s, -1))

    def _sparse(self, x, cq, c, r, qi, wi, ki):
        """:meth:`_decompressed` where each position attends its pick,
        from the query latent ``cq``. The picks first, a span of queries
        at a time over the keys up to the span's end
        (``ops.latent_attention.sparse_spans``; one span where that
        gives none); then the heads in groups, one after another
        (``lax.map``): a group's queries, keys and values are made,
        attended span by span under the picks and dropped, so a
        28672-token chunk holds a quarter of its heads at a time."""
        b, s, _ = x.shape
        f32, kept = jnp.float32, c.dtype
        spans = LA.sparse_spans(s) or [(0, s)]
        with scope("dsa_index"):
            keeps = [LA.span_pick(qi, wi, ki, q0, q1, self.index_topk
                                  ).astype(jnp.int8) for q0, q1 in spans]
        with scope("mla_prefill"):
            hg = LA.head_group(self.heads)
            by_group = lambda w, lead: jnp.moveaxis(w.reshape(
                lead, self.heads // hg, hg, -1), 1, 0)
            pos = jnp.arange(s, dtype=jnp.int32)

            def group(ws):
                wq, wkv = ws                  # (q_rank | kv_rank, hg, .)
                q = jnp.einsum("bsl,lhd->bhsd", cq, wq,
                               preferred_element_type=f32).astype(cq.dtype)
                qr = rotary_embedding(
                    q[..., self.nope:].reshape(b * hg, s, 1, self.rope),
                    pos, self.theta, self.yarn).reshape(b, hg, s, self.rope)
                q = jnp.concatenate([q[..., :self.nope].astype(kept),
                                     qr.astype(kept)], axis=-1)
                k = jnp.concatenate(
                    [jnp.einsum("bsl,lhn->bhsn", c, wkv[..., :self.nope],
                                preferred_element_type=f32).astype(kept),
                     jnp.broadcast_to(r[:, None], (b, hg, s, self.rope))],
                    axis=-1)
                v = jnp.einsum("bsl,lhv->bhsv", c, wkv[..., self.nope:],
                               preferred_element_type=f32).astype(kept)
                return jnp.concatenate(
                    [LA.masked_attention(q, k, v, keep, self.scale, q0)
                     for (q0, _), keep in zip(spans, keeps)], axis=2)

            o = lax.map(group, (
                by_group(self.q_b_proj.weight, self.q_b_proj.weight.shape[0]),
                by_group(self.kv_b_proj.weight, self.kv_rank)))
            wo = self.out_proj.weight.reshape(
                self.heads // hg, hg, self.v_dim, -1)
            return jnp.einsum("gbhsv,ghvd->bsd", o.astype(x.dtype), wo,
                              preferred_element_type=f32).astype(x.dtype)

    def _absorbed(self, x, qn, qr, c, r, t_rows, keep=None):
        """The absorbed form of one position a row, ``x`` (B, 1, D),
        over the records ``c``, ``r`` (B, T, .): row ``b``'s query sees
        records ``<= t_rows[b]``; with an indexer's ``keep`` (B, T), those
        of them it allows (``ops.latent_attention.step_pick``)."""
        w = self._w_kvb()
        f32 = jnp.float32
        qa = jnp.einsum("bhn,lhn->bhl", qn[:, 0], w[..., :self.nope],
                        preferred_element_type=f32)
        o = LA.latent_read(qa, qr[:, 0], c, r, t_rows, self.scale, keep)
        o = jnp.einsum("bhl,lhv->bhv", o.astype(x.dtype),
                       w[..., self.nope:], preferred_element_type=f32)
        return self.out_proj(o.astype(x.dtype).reshape(x.shape[0], 1, -1))

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel: bool = False):
        """A prefill: ``x`` (B, S, D) at positions [0, S) writes their
        records there and attends each position over records ``<=`` its
        own (with an indexer: over its pick of them), decompressed.
        Returns (out (B, S, D), the cache). ``t0`` is the static 0: a
        chunk that continues a cache would have to read it, which no
        serving path does for a latent record (``serving.BatchedDecoder``
        refuses each by name)."""
        cache_c, cache_r, cache_ki = self._arrays(cache)
        enforce(isinstance(t0, int) and t0 == 0, "a latent chunk starts "
                "at the static offset 0, got %r: a chunk that continues "
                "a cache is not written", t0)
        s = x.shape[1]
        pos = jnp.arange(s, dtype=jnp.int32)
        put = lambda cache, new: lax.dynamic_update_slice_in_dim(
            cache, new, 0, axis=1)
        with scope("mla_prefill"):
            cq = self._query_latent(x)
            if not self.index_topk:
                qn, qr = self._queries(cq, pos)
            c, r = self._records(x, pos)
            c, r = c.astype(cache_c.dtype), r.astype(cache_r.dtype)
            cache_c, cache_r = put(cache_c, c), put(cache_r, r)
            if not self.index_topk:
                out = self._decompressed(x, qn, qr, c, r)
                return out, (cache_c, cache_r)
        with scope("dsa_index"):
            qi, wi, ki = self._index(x, cq, pos)
            ki = ki.astype(cache_ki.dtype)
            cache_ki = put(cache_ki, ki)
            self.counted = dict.fromkeys(
                ("dsa_positions_live", "dsa_positions_read"), jnp.int32(0))
        return (self._sparse(x, cq, c, r, qi, wi, ki),
                (cache_c, cache_r, cache_ki))

    def forward_step(self, x, cache, t, decode_kernel: bool = False):
        """One decode step at the shared cursor ``t``: ``x`` (B, 1, D)."""
        return self.forward_step_rows(
            x, cache, jnp.broadcast_to(t, x.shape[:1]))

    def forward_step_rows(self, x, cache, t_rows,
                          decode_kernel: bool = False):
        """One position PER ROW at per-row cursors ``t_rows`` (B,), the
        continuous-batching step: each row's record is written at its
        own cursor and its query reads the row's records ``<= t`` (with
        an indexer: its pick of them), absorbed. ``x``: (B, 1, D)."""
        cache_c, cache_r, cache_ki = self._arrays(cache)
        write = jax.vmap(lambda a, u, s: lax.dynamic_update_slice_in_dim(
            a, u, s, axis=0))
        with scope("mla_decode"):
            pos = t_rows.astype(jnp.int32)[:, None]               # (B, 1)
            cq = self._query_latent(x)
            qn, qr = self._queries(cq, pos)
            c, r = self._records(x, pos)
            cache_c = write(cache_c, c.astype(cache_c.dtype), pos[:, 0])
            cache_r = write(cache_r, r.astype(cache_r.dtype), pos[:, 0])
            if not self.index_topk:
                out = self._absorbed(x, qn, qr, cache_c, cache_r, pos[:, 0])
                return out, (cache_c, cache_r)
        with scope("dsa_index"):
            qi, wi, ki = self._index(x, cq, pos)
            cache_ki = write(cache_ki, ki.astype(cache_ki.dtype), pos[:, 0])
            keep, n = LA.step_pick(
                LA.step_index_scores(qi[:, 0], wi[:, 0], cache_ki),
                pos[:, 0], self.index_topk)
            self.counted = {
                "dsa_positions_read": jnp.sum(n, dtype=jnp.int32),
                "dsa_positions_live": jnp.sum(pos + 1, dtype=jnp.int32)}
        with scope("mla_decode"):
            out = self._absorbed(x, qn, qr, cache_c, cache_r, pos[:, 0],
                                 keep)
        return out, (cache_c, cache_r, cache_ki)

    def forward(self, x, causal: bool = True):
        """Causal self-attention of (B, T, D) from no cache."""
        enforce(causal, "latent attention is written causal")
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        # as a cache would keep them: the weights' type, or the policy's
        kept = compute_dtype_of(self.kv_b_proj.weight.dtype)
        with scope("mla_prefill"):
            c, r = self._records(x, pos)
            cq = self._query_latent(x)
            if not self.index_topk:
                return self._decompressed(x, *self._queries(cq, pos),
                                          c.astype(kept), r.astype(kept))
        with scope("dsa_index"):
            qi, wi, ki = self._index(x, cq, pos)
        return self._sparse(x, cq, c.astype(kept), r.astype(kept), qi, wi,
                            ki.astype(kept))


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` (..., n, n) float32 made doubly stochastic by
    ``iters`` rounds of dividing rows, then columns, by their sums plus
    ``eps`` (Sinkhorn & Knopp)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


class PlainResidual:
    """``x + m F(norm(x))``: the one-stream residual path. ``read``
    hands the stream itself to the sublayer's norm, ``write`` adds the
    sublayer's output times ``m``. No parameters. ``settle``: the sum
    is made where it is written (``lax.optimization_barrier``) and not
    wherever the compiler next reads it. Left to itself XLA keeps a long
    chunk's stream as the embedding plus every sublayer's float32
    output so far and adds them up again at each use: at 28672 positions
    of 6144 numbers that is 0.67 GB a sublayer held to the end."""

    counted = {}

    def __init__(self, multiplier: float = 1.0, settle: bool = False):
        self.m, self.settle = float(multiplier), bool(settle)

    def read(self, x):
        return x, None

    def write(self, x, y, held):
        out = x + self.m * y
        return lax.optimization_barrier(out) if self.settle else out


class HyperConnection(Layer):
    """Manifold-constrained hyper-connections (mHC; Xie et al.,
    arXiv:2512.24880) around one sublayer ``F``. The residual state
    ``X`` of a position is ``n`` streams of ``C`` numbers, (..., n, C);
    three maps are computed from it, in float32 whatever the weights'
    type::

        x~ = RMSNorm_0(vec(X));  m = x~ phi                (n n + 2 n)
        H_pre  = sigmoid(a_pre m[:n] + b_pre)              (n)
        H_post = 2 sigmoid(a_post m[n:2n] + b_post)        (n)
        H_res  = SK(clip(a_res mat(m[2n:]) + b_res, lo, hi))   (n, n)

    ``SK`` = :func:`sinkhorn`, a doubly stochastic matrix after
    ``iters`` rounds. ``read(X)`` gives ``u = sum_i H_pre[i] X[i]``
    (what the sublayer's norm sees) and holds the other two;
    ``write(X, y, held)`` gives ``X'[i] = sum_j H_res[i, j] X[j] +
    H_post[i] y``. With ``n`` = 1 and unit maps this is
    :class:`PlainResidual`. **The state is float32 between sublayers**
    (:attr:`state_dtype`; ``read`` gives ``u`` in float32 too, as the
    plain path's stream is under the float32 policy): every sublayer
    rewrites all ``n`` streams, and a state rounded to bfloat16 each
    time would add 2^-9 of the whole state, several times a sublayer's
    own output, to what it carries (``PERF.md`` section 6, PR 41).
    ``counted`` then holds ``mhc_unbalanced``: how many of the call's
    positions have a row or column sum of ``H_res`` off 1 by more than
    1e-3 after the Sinkhorn rounds (int32; valid inside the trace of the
    call)."""

    state_dtype = jnp.float32

    def __init__(self, hidden: int, streams: int, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, clamp=(-30.0, 30.0),
                 norm_eps: float = 1e-6):
        super().__init__()
        n = self.streams = int(streams)
        self.iters, self.eps, self.norm_eps = sinkhorn_iters, eps, norm_eps
        self.clamp = (float(clamp[0]), float(clamp[1]))
        self.create_parameter("phi", (n * hidden, n * n + 2 * n), None)
        # b_pre, b_post, b_res: H_res starts near the identity
        self.create_parameter(
            "bias", (n * n + 2 * n,), None, lambda k, s, d: jnp.concatenate(
                [jnp.zeros(2 * n), 8.0 * jnp.eye(n).reshape(-1)]).astype(d),
            is_bias=True)
        # a_pre, a_post, a_res: the maps start all but static
        self.create_parameter("gain", (3,), None,
                              lambda k, s, d: jnp.full(s, 0.01, d))
        self.counted = {}

    def maps(self, x):
        """(H_pre (..., n), H_post (..., n), H_res (..., n, n)) float32
        of the state ``x`` (..., n, C)."""
        n, f32 = self.streams, jnp.float32
        flat = x.astype(f32).reshape(*x.shape[:-2], -1)
        flat = flat * lax.rsqrt(
            jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
            + self.norm_eps)
        m = jnp.dot(flat, self.phi.astype(f32), precision=HIGHEST)
        a, b = self.gain.astype(f32), self.bias.astype(f32)
        pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
        res = (a[2] * m[..., 2 * n:] + b[2 * n:]).reshape(
            *m.shape[:-1], n, n)
        res = sinkhorn(jnp.clip(res, *self.clamp), self.iters, self.eps)
        off = jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0), axis=-1),
            jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0), axis=-1))
        self.counted = {"mhc_unbalanced": jnp.sum(off > 1e-3,
                                                  dtype=jnp.int32)}
        return pre, post, res

    def read(self, x):
        with scope("mhc_mix"):
            pre, post, res = self.maps(x)
            # elementwise, so float32 stays float32 (a product on the
            # MXU would round its inputs)
            u = jnp.sum(pre[..., None] * x.astype(jnp.float32), axis=-2)
            return u, (post, res)

    def write(self, x, y, held):
        post, res = held
        with scope("mhc_mix"):
            f32 = jnp.float32
            mixed = jnp.sum(res[..., None] * x.astype(f32)[..., None, :, :],
                            axis=-2)
            out = mixed + post[..., None] * y.astype(f32)[..., None, :]
            return out.astype(self.state_dtype)
