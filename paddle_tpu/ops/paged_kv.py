"""Functional paged-KV cache ops (vLLM-style): K/V live in a SHARED
(pages, page_size, kv_heads, head_dim) pool; a request's logical cache
is its page-id sequence. These are the jit-safe array ops — write one
position per row, write a prompt chunk for one row, attend over the
pages (Pallas paged kernel when eligible, gather fallback). The
host-side allocator is paddle_tpu.serving.PagedKVPool.

Pools come in two storage forms, transparent to every caller:

- a plain float array (the original layout), or
- :class:`QuantizedPool` — int8 values + per-(page, position, kv_head)
  float32 scales (the ``quant.ops.absmax_encode`` wire format over each
  head_dim vector). KV bytes set the concurrent-session ceiling per
  chip, so int8 KV ~= 3.7x the pages of fp32 (1 + 4/head_dim bytes per
  element vs 4) at the same HBM. Writes QUANTIZE ON APPEND (each K/V
  vector encoded once, at write time); attention DEQUANTIZES only the
  blocks it touches (never the whole pool), so the working set stays
  O(live tokens). Quantized decode rides the SAME Pallas paged kernel
  as float pools when eligible: int8 blocks stream from HBM with their
  scale blocks prefetched along the same clamped page walk, and dequant
  happens in VMEM as a per-block epilogue (flash_decode_paged's
  k_scale/v_scale form) — O(t) DMA plus ~4x fewer HBM bytes per block.
  The gather path remains the fallback (CPU, ineligible shapes,
  measured use_flash=False verdicts).

This module is the ONE place that branches on the pool storage form —
kernels and serving code take raw arrays (PT-LINT-308 pins it).

Green-field (the modern serving-memory capability; the reference's
serving holds one contiguous buffer per request,
/root/reference/paddle/fluid/inference/api/api_impl.cc role).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp


class QuantizedPool(NamedTuple):
    """int8 paged K or V pool: ``q`` (pages, page_size, kv_heads,
    head_dim) int8 values, ``scale`` (pages, page_size, kv_heads)
    float32 per-vector abs-max scales (dequant = ``q * scale``). A
    pytree — threads through jitted step functions exactly like the
    float pool it replaces; ``shape``/``dtype`` mirror the float pool's
    so shape-driven callers (page_size, OOB page ids) never branch."""

    q: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        """Device bytes of the pool (values + scales) — the serving
        density accounting (`pt_serving_kv_pool_bytes`)."""
        return quantized_pool_nbytes(self.q.shape)


def quantized_pool_nbytes(shape) -> int:
    """Device bytes a :class:`QuantizedPool` with value layout
    ``shape`` = (pages, page_size, kv_heads, head_dim) costs: int8
    values + one f32 scale per (page, position, kv_head) vector. THE
    wire-format byte formula — ``QuantizedPool.nbytes`` and serving's
    ``PagedKVPool.pool_nbytes`` both read it, so the density accounting
    can't drift from the storage layout."""
    pages, page_size, kv_heads, head_dim = shape
    vecs = pages * page_size * kv_heads
    return vecs * head_dim + vecs * 4


def _encode_vectors(x):
    """(..., head_dim) float -> (q int8, scale (...,)) per-vector
    abs-max int8 (the shared quant.ops convention)."""
    from ..quant.ops import absmax_encode

    q, scale = absmax_encode(x, axis=-1)
    return q, scale[..., 0]


def _pool_write(pool, page, off, x):
    """Scatter ``x`` (K/V vectors) into the pool at [page, off] with
    OOB-drop semantics — quantize-on-append for QuantizedPool, plain
    dtype-cast store otherwise. ``page``/``off`` index arrays broadcast
    per the caller's layout."""
    if isinstance(pool, QuantizedPool):
        q, s = _encode_vectors(x)
        return QuantizedPool(pool.q.at[page, off].set(q, mode="drop"),
                             pool.scale.at[page, off].set(s, mode="drop"))
    return pool.at[page, off].set(x.astype(pool.dtype), mode="drop")


def write_rows(kpool, vpool, table, t_rows, k_t, v_t, page_size: int):
    """One position per row at LOGICAL cursors ``t_rows`` (B,): scatter
    k_t/v_t (B, 1, kv, hd) into each row's page. Cursors past the
    row's table capacity DROP (the contiguous cache's OOB-scatter
    semantics) instead of clamp-corrupting the last live page."""
    n_log = table.shape[1]
    rows = jnp.arange(table.shape[0])
    valid = t_rows < n_log * page_size
    col = jnp.minimum(t_rows // page_size, n_log - 1)
    # invalid rows get an out-of-pool page id -> mode="drop"
    page = jnp.where(valid, table[rows, col], kpool.shape[0])
    off = t_rows % page_size
    kpool = _pool_write(kpool, page, off, k_t[:, 0])
    vpool = _pool_write(vpool, page, off, v_t[:, 0])
    return kpool, vpool


def write_chunk(kpool, vpool, table_row, t0, k_c, v_c, page_size: int):
    """S consecutive positions for ONE row starting at logical ``t0``:
    k_c/v_c (1, S, kv, hd). Positions past the table capacity drop
    (see write_rows)."""
    s = k_c.shape[1]
    n_log = table_row.shape[0]
    pos = t0 + jnp.arange(s)
    valid = pos < n_log * page_size
    col = jnp.minimum(pos // page_size, n_log - 1)
    page = jnp.where(valid, table_row[col], kpool.shape[0])
    off = pos % page_size
    kpool = _pool_write(kpool, page, off, k_c[0])
    vpool = _pool_write(vpool, page, off, v_c[0])
    return kpool, vpool


def write_chunk_rows(kpool, vpool, table, t0_rows, k_c, v_c,
                     page_size: int):
    """S consecutive positions PER ROW starting at per-row logical
    cursors ``t0_rows`` (B,): k_c/v_c (B, S, kv, hd) — the speculative
    verify-chunk write (every row lands its gamma+1 candidate K/V at
    its OWN offset). Positions past the table capacity drop (see
    write_rows)."""
    b, s = k_c.shape[:2]
    n_log = table.shape[1]
    pos = t0_rows[:, None] + jnp.arange(s)[None, :]           # (B, S)
    valid = pos < n_log * page_size
    col = jnp.minimum(pos // page_size, n_log - 1)
    rows = jnp.arange(b)[:, None]
    page = jnp.where(valid, table[rows, col], kpool.shape[0])
    off = pos % page_size
    kpool = _pool_write(kpool, page, off, k_c)
    vpool = _pool_write(vpool, page, off, v_c)
    return kpool, vpool


def export_pages(pool, ids):
    """Materialize the CONTENTS of pages ``ids`` (n,) — the
    prefill→decode KV-handoff wire payload: ``(n, page_size, kv_heads,
    head_dim)`` values for a float pool, ``(q, scale)`` arrays for a
    :class:`QuantizedPool` (int8 values + per-vector scales travel
    together, so a handoff never silently dequantizes). Pure gather —
    the caller owns any device→host transfer."""
    if isinstance(pool, QuantizedPool):
        return pool.q[ids], pool.scale[ids]
    return pool[ids]


def import_pages(pool, ids, payload):
    """Write :func:`export_pages` payloads into pages ``ids`` of
    ``pool`` (the decode-side half of the KV handoff). Storage forms
    must match: a quantized payload only lands in a quantized pool —
    re-quantizing a dequantized handoff would double the quantization
    error, so the mismatch is a typed error instead."""
    from ..core.enforce import enforce

    if isinstance(pool, QuantizedPool):
        enforce(isinstance(payload, tuple) and len(payload) == 2,
                "quantized pool needs a (q, scale) payload, got %s",
                type(payload).__name__)
        q, scale = payload
        return QuantizedPool(
            pool.q.at[ids].set(jnp.asarray(q, jnp.int8)),
            pool.scale.at[ids].set(jnp.asarray(scale, jnp.float32)))
    enforce(not isinstance(payload, tuple),
            "float pool cannot import a quantized (q, scale) payload "
            "— kv_dtype must match across the handoff")
    return pool.at[ids].set(jnp.asarray(payload).astype(pool.dtype))


def gather_rows(pool, table, upto: Optional[int] = None,
                full: bool = False):
    """Assemble each row's LOGICAL cache: (B, n_cols*page_size, kv, hd).
    The fallback/prefill view; the decode kernel never materializes
    it. Quantized pools dequantize HERE — only the gathered rows ever
    exist in float.

    ``upto``: STATIC bound on the live positions (the prefill path,
    where the chunk extent t0+S is a Python int) — only the first
    ``ceil(upto / page_size)`` table columns are gathered/dequantized,
    so a short prompt over a long table stops materializing (and for
    quantized pools, dequantizing) the full logical view in float32.
    None (traced cursors: the decode fallback) or ``full=True`` (the
    explicit full-view escape for tests/handoffs) keeps the whole
    view."""
    b, n_log = table.shape
    ps = pool.shape[1]
    if upto is not None and not full:
        n_cols = min(n_log, max(1, -(-int(upto) // ps)))
        table = table[:, :n_cols]
        n_log = n_cols
    if isinstance(pool, QuantizedPool):
        vals = (pool.q[table].astype(jnp.float32)
                * pool.scale[table][..., None])
        return vals.reshape(b, n_log * ps, *pool.shape[2:])
    return pool[table].reshape(b, n_log * ps, *pool.shape[2:])


def attend(q, kpool, vpool, table, t_rows,
           window: Optional[int] = None):
    """Decode attention over the paged cache: the Pallas paged kernel
    when eligible — float AND quantized pools; int8 pools hand the
    kernel their raw (values, scales) planes and dequant runs as the
    kernel's per-block VMEM epilogue — else gather-the-pages + masked
    XLA (dequant on the gathered rows). ``t_rows``: scalar or (B,)
    logical cursors. THE storage-form dispatch boundary: nothing past
    this call branches on :class:`QuantizedPool`."""
    from . import attention as A

    d = q.shape[-1]
    page_size, n_log = kpool.shape[1], table.shape[1]
    # scalar cursor broadcasts on BOTH paths (the kernel already
    # broadcasts; the gather fallback must match)
    t_rows = jnp.broadcast_to(jnp.asarray(t_rows, jnp.int32),
                              (q.shape[0],))
    quantized = isinstance(kpool, QuantizedPool)
    if A.decode_flash_ok(page_size * n_log, d,
                         "int8" if quantized else "f32", page_size):
        from .pallas.flash_decode import flash_decode_paged

        if quantized:
            return flash_decode_paged(
                q, kpool.q, vpool.q, table, t_rows,
                k_scale=kpool.scale, v_scale=vpool.scale,
                window=window)
        return flash_decode_paged(q, kpool, vpool, table, t_rows,
                                  window=window)
    k = gather_rows(kpool, table)
    v = gather_rows(vpool, table)
    pos = jnp.arange(n_log * page_size)[None, :]
    keep = pos <= t_rows[:, None]
    if window is not None:
        keep &= pos > t_rows[:, None] - window
    return A.scaled_dot_product_attention(
        q, k, v, mask=keep[:, None, None, :], use_flash=False)
