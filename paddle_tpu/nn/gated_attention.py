"""Grouped-query softmax attention with a head width of its own, a
rotary embedding over a leading part of each head, one sigmoid gate a
head and, with a window, a cache that is a ring:
:class:`GatedAttention`, the mixer of decoders that mix window and full
attention layers in one model."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.dtypes import default_dtype
from ..core.enforce import enforce
from ..ops.attention import (_get_flash_decode, decode_flash_ok,
                             rotary_embedding, scaled_dot_product_attention)
from ..telemetry.scopes import scope
from .layer import Layer
from .layers import Linear


class GatedAttention(Layer):
    """``x`` the layer's normed input at position ``t``, query head
    ``h`` of ``num_heads``, each reading key-value head ``h // (num_heads
    / num_kv_heads)``, every head ``head_dim`` wide whatever the hidden
    size, no biases::

        q = x W_q  (H x d);  k = x W_k  (KV x d);  v = x W_v  (KV x d)
        g = sigmoid(x W_g)   (H numbers a position; ``gate``)
        q, k: rotary on the first ``rotary_dim`` numbers of each head
              (``rotary_embedding``: ``theta``, ``yarn`` frequencies over
              those pairs, cosines and sines times ``attention_factor``);
              the rest of the head passes unrotated
        s[t, j] = q_t . k_j / sqrt(d)    for j <= t, and with a
                                         ``window`` W for t - W < j only
        a_h = softmax_j(s) v;   out = concat_h(g_h a_h) W_o

    Softmax in float32; everything else in the inputs' type.

    **The cache is the layer's own size.** Without a window
    :meth:`init_cache` gives keys and values for ``capacity``
    positions, ``(slots, capacity, KV, d)`` twice (``cache_record``
    ``"heads"``). With a window it gives a RING of ``min(capacity,
    window)`` positions (``cache_record`` ``"ring"``): position ``p``
    lives at ``p mod ring``, and there is no other form of it. Rotated
    keys are what is cached, so a ring needs no order: a step writes its
    row's key and value at ``t mod ring`` and reads the whole ring under
    a mask of validity alone, entries ``<= min(t, ring - 1)`` (while
    ``t`` is under the ring's length the entries above it are another
    request's, or none's; from there on every entry is one of this
    sequence's last ``ring`` positions, which are exactly the window). A
    chunk (a prefill, offset 0 alone) attends over itself under the band
    and then writes ONLY the last ``min(valid_len, ring)`` of its
    ``valid_len`` valid positions, each at its own place: a padded
    bucket's tail would land on keys the window still needs.

    The three cached entries are the mixers' convention
    (``models/hybrid.py``); the mixer enters its own scopes around its
    whole self (``gqa_full_step`` / ``gqa_window_step`` one position a
    row, ``gqa_full_prefill`` / ``gqa_window_prefill`` a chunk) and
    counts what a cached call's rows read: ``kv_positions_full`` or
    ``kv_positions_window``, the sum over the rows of ``t + 1`` or of
    ``min(t + 1, ring)`` (int32; every row of the call, an idle slot's
    too: a step is not told which rows are live; a chunk counts its
    valid positions). With ``decode_kernel`` a step's read is the Pallas
    decode kernel (``ops/pallas/flash_decode.py``) where the shape is
    eligible, handed ``min(t, ring - 1)`` as a ring's cursor."""

    state_kind = "kv"
    cached_scope, empty_scope = None, "attn"
    counted = {}

    @property
    def cache_record(self) -> str:
        """``"heads"`` for ``capacity`` positions, ``"ring"`` with a
        window."""
        return "heads" if self.window is None else "ring"

    def __init__(self, hidden: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, window: Optional[int] = None,
                 rope_theta: float = 10000.0,
                 rotary_dim: Optional[int] = None,
                 yarn: Optional[dict] = None, attention_factor: float = 1.0,
                 gate: bool = False, use_flash: bool = True):
        super().__init__()
        enforce(num_heads % num_kv_heads == 0, "%s query heads are not a "
                "multiple of %s key-value heads", num_heads, num_kv_heads)
        enforce(window is None or window >= 1, "window must be >= 1, got "
                "%s", window)
        self.heads, self.kv_heads, self.head_dim = (num_heads, num_kv_heads,
                                                    head_dim)
        self.window = None if window is None else int(window)
        kind = "full" if window is None else "window"
        self._step_scope, self._chunk_scope = (f"gqa_{kind}_step",
                                               f"gqa_{kind}_prefill")
        self._counter = f"kv_positions_{kind}"
        self.rope = dict(theta=float(rope_theta), yarn=yarn and dict(yarn),
                         rotary_dim=rotary_dim,
                         attention_factor=float(attention_factor))
        self.scale = head_dim ** -0.5
        self.use_flash = use_flash
        self.q_proj = Linear(hidden, num_heads * head_dim, bias_attr=False)
        self.k_proj = Linear(hidden, num_kv_heads * head_dim,
                             bias_attr=False)
        self.v_proj = Linear(hidden, num_kv_heads * head_dim,
                             bias_attr=False)
        self.gate_proj = (Linear(hidden, num_heads, bias_attr=False)
                          if gate else None)
        self.out_proj = Linear(num_heads * head_dim, hidden,
                               bias_attr=False)

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """Zeroed keys and values, (B, capacity, KV, d) each; with a
        window (B, min(capacity, window), KV, d): the ring."""
        n = capacity if self.window is None else min(capacity, self.window)
        shape = (batch, n, self.kv_heads, self.head_dim)
        dt = dtype or default_dtype()
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def _project(self, x, positions):
        """``x`` (B, S, hidden) at ``positions`` (S,) or (B, S) -> q (B,
        S, H, d) and k (B, S, KV, d), both rotated, v (B, S, KV, d)."""
        b, s, _ = x.shape
        q = self.q_proj(x).reshape(b, s, self.heads, self.head_dim)
        k = self.k_proj(x).reshape(b, s, self.kv_heads, self.head_dim)
        v = self.v_proj(x).reshape(b, s, self.kv_heads, self.head_dim)
        return (rotary_embedding(q, positions, **self.rope),
                rotary_embedding(k, positions, **self.rope), v)

    def _finish(self, x, a):
        """The gate a head on the heads' outputs ``a`` (B, S, H, d),
        then the output projection."""
        a = a.astype(x.dtype)
        if self.gate_proj is not None:
            g = jax.nn.sigmoid(self.gate_proj(x).astype(jnp.float32))
            a = a * g.astype(x.dtype)[..., None]
        return self.out_proj(a.reshape(*x.shape[:2], -1))

    def _attend_chunk(self, q, k, v):
        return scaled_dot_product_attention(
            q, k, v, causal=True, scale=self.scale, window=self.window,
            use_flash=self.use_flash)

    def forward(self, x):
        """Causal (banded, with a window) self-attention of (B, T,
        hidden) from no cache."""
        q, k, v = self._project(x, jnp.arange(x.shape[1], dtype=jnp.int32))
        return self._finish(x, self._attend_chunk(q, k, v))

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel: bool = False):
        """A prefill: ``x`` (B, S, hidden) at positions [0, S), of which
        the first ``valid_len`` (default all) are a prompt. Every
        position attends over the chunk itself, causal and banded;
        without a window the chunk's keys and values are written at [0,
        S) (a padded tail lands above the cursor), with one the last
        ``min(valid_len, ring)`` valid positions are written into the
        ring and nothing else is. ``t0`` is the static 0: a chunk that
        continues a cache would have to read it, which no serving path
        does for a ring (``serving.BatchedDecoder`` refuses each by
        name)."""
        ck, cv = cache
        enforce(isinstance(t0, int) and t0 == 0, "a chunk of this mixer "
                "starts at the static offset 0, got %r: a chunk that "
                "continues a cache is not written", t0)
        s = x.shape[1]
        n = jnp.asarray(s if valid_len is None else valid_len, jnp.int32)
        with scope(self._chunk_scope):
            q, k, v = self._project(x, jnp.arange(s, dtype=jnp.int32))
            k, v = k.astype(ck.dtype), v.astype(cv.dtype)
            out = self._finish(x, self._attend_chunk(q.astype(ck.dtype),
                                                     k, v))
            if self.window is None:
                enforce(s <= ck.shape[1], "a chunk of %s positions does "
                        "not fit a cache of %s", s, ck.shape[1])
                put = lambda c, new: lax.dynamic_update_slice_in_dim(
                    c, new, 0, axis=1)
                ck, cv = put(ck, k), put(cv, v)
                live = n
            else:
                ring = ck.shape[1]
                # the latest valid position that lives at each entry:
                # the largest p < n with p mod ring == r (none: r >= n)
                r = jnp.arange(ring, dtype=jnp.int32)
                p = r + ring * ((n - 1 - r) // ring)
                at = jnp.clip(p, 0, s - 1)
                keep = (p >= 0)[None, :, None, None]
                ck = jnp.where(keep, jnp.take(k, at, axis=1), ck)
                cv = jnp.where(keep, jnp.take(v, at, axis=1), cv)
                live = jnp.minimum(n, ring)
            self.counted = {self._counter: live * x.shape[0]}
            return out, (ck, cv)

    def forward_step(self, x, cache, t, decode_kernel: bool = False):
        """One decode step at the shared cursor ``t``: ``x`` (B, 1,
        hidden)."""
        return self.forward_step_rows(
            x, cache, jnp.broadcast_to(t, x.shape[:1]), decode_kernel)

    def forward_step_rows(self, x, cache, t_rows,
                          decode_kernel: bool = False):
        """One position PER ROW at per-row cursors ``t_rows`` (B,), the
        continuous-batching step: each row's key and value are written
        at its own cursor (a ring: at ``t mod ring``) and its query
        reads the entries ``<= t`` (a ring: ``<= min(t, ring - 1)``,
        all of them its own window). ``x``: (B, 1, hidden)."""
        ck, cv = cache
        n = ck.shape[1]
        write = jax.vmap(lambda c, u, s: lax.dynamic_update_slice_in_dim(
            c, u, s, axis=0))
        with scope(self._step_scope):
            pos = t_rows.astype(jnp.int32)
            q, k, v = self._project(x, pos[:, None])
            at = pos if self.window is None else pos % n
            ck = write(ck, k.astype(ck.dtype), at)
            cv = write(cv, v.astype(cv.dtype), at)
            top = pos if self.window is None else jnp.minimum(pos, n - 1)
            self.counted = {self._counter: jnp.sum(top + 1, dtype=jnp.int32)}
            q = q.astype(ck.dtype)
            if (decode_kernel and self.use_flash
                    and decode_flash_ok(n, self.head_dim)):
                a = _get_flash_decode()(q, ck, cv, top, scale=self.scale)
            else:
                a = self._read(q, ck, cv, top)
            return self._finish(x, a), (ck, cv)

    def _read(self, q, ck, cv, top):
        """The step's read in plain ``jnp``: ``q`` (B, 1, H, d) over the
        entries ``<= top`` (B,) of ``ck``, ``cv`` (B, n, KV, d), each
        key-value head's entries read once for its group of query
        heads; float32 scores and sums."""
        b, n, f32 = q.shape[0], ck.shape[1], jnp.float32
        qg = q.reshape(b, self.kv_heads, -1, self.head_dim)
        s = jnp.einsum("bkgd,bnkd->bkgn", qg, ck,
                       preferred_element_type=f32) * self.scale
        keep = jnp.arange(n)[None, :] <= top[:, None]
        s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
        a = jnp.einsum("bkgn,bnkd->bkgd", p, cv, preferred_element_type=f32)
        return a.reshape(b, 1, self.heads, self.head_dim)
