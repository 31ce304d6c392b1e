"""Pallas flash-decode: single-position KV-cache attention.

The decode hot loop attends ONE query per (batch, head) against a
pre-allocated (B, capacity, H_kv, D) cache with a ``pos <= t`` mask.
The XLA fallback streams the FULL capacity from HBM every step even
when only t+1 positions are live; decode is bandwidth-bound, so that
over-read is the whole cost. This kernel walks kv blocks on a
(B, capacity/block_k) grid with the block index CLAMPED into the live
range [lo(t), t // block_k] via a scalar-prefetch index map — Mosaic
elides the DMA when consecutive grid steps map to the same block, so
HBM traffic is O(t) (O(window) with sliding-window attention), not
O(capacity).

All H query heads of one batch element ride one program as the row
dimension of the score matrix (a single decode row per head would
waste the 8-sublane tile); GQA/MQA groups take static per-kv-head
slices of those rows, reading each shared K/V block once. Online
softmax carries (m, l, acc) in VMEM scratch across kv blocks exactly
like the training kernel (flash_attention.py).

The paged form has an int8-native variant (ISSUE 15): K/V blocks
stream from HBM as raw int8 with their per-(page, pos, kv_head) f32
scales prefetched along the SAME clamped page walk, and dequant runs
in VMEM as a per-block epilogue before the online-softmax update —
quantized decode keeps the O(t) DMA behavior and moves ~4x fewer HBM
bytes per block. This module only ever sees raw arrays; the
QuantizedPool-vs-float dispatch lives in ops/paged_kv.attend
(PT-LINT-308 pins that boundary).

Inference-only: no VJP (the decode loop never differentiates).
Reference niche: the hand-tuned JIT kernel layer,
/root/reference/paddle/fluid/operators/jit/ — decode attention is the
op XLA leaves the most bandwidth on the table for.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.enforce import enforce
from .flash_attention import (_NEG_INF, _named_call, _scratch,
                              _use_interpret, pltpu)

DEFAULT_DECODE_BLOCK_K = 256


def _decode_core(t_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                 l_ref, ks_ref, vs_ref, *, scale, window, block_k, n_j,
                 nheads, kv_heads):
    """Shared online-softmax decode body. ``ks_ref``/``vs_ref``: the
    int8 variant's per-(position, kv_head) f32 scale blocks — when
    present, K/V blocks arrive as raw int8 and dequantize HERE, in
    VMEM, as an epilogue on each block before the softmax update (the
    pool streams ~4x fewer HBM bytes per block; float never exists
    outside the block working set). None = the float path, bit-for-bit
    the pre-int8 kernel."""
    b, j = pl.program_id(0), pl.program_id(1)
    t = t_ref[b]  # PER-ROW cursor (continuous batching: each slot at
    # its own position; the classic shared-cursor decode broadcasts)
    t_blk = t // block_k
    lo_blk = (jnp.maximum(t - window + 1, 0) // block_k
              if window is not None else 0)
    group = nheads // kv_heads

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((j <= t_blk) & (j >= lo_blk))
    def _body():
        q = q_ref[0]                                  # (H, D)
        parts = []
        for hk in range(kv_heads):
            qg = q[hk * group:(hk + 1) * group]       # (G, D)
            kk = k_ref[0, :, hk]                      # (block_k, D)
            if ks_ref is not None:
                # dequant epilogue: int8 block * per-vector scale, f32
                kk = (kk.astype(jnp.float32)
                      * ks_ref[0, :, hk][:, None])
                qg = qg.astype(jnp.float32)
            parts.append(jax.lax.dot_general(
                qg, kk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        s = jnp.concatenate(parts, axis=0) * scale    # (H, block_k)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        live = cols <= t
        if window is not None:
            live &= cols > t - window
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[:, :1]                         # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, -1, keepdims=True)
        pvs = []
        for hk in range(kv_heads):
            vv = v_ref[0, :, hk]                      # (block_k, D)
            if vs_ref is not None:
                vv = (vv.astype(jnp.float32)
                      * vs_ref[0, :, hk][:, None])
            pg = p[hk * group:(hk + 1) * group]
            pvs.append(jax.lax.dot_general(
                pg.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(pvs, axis=0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_j - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # t<0 would divide by zero
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _decode_kernel(t_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, **kw):
    """Float decode kernel — the core with no scale planes."""
    _decode_core(t_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                 l_ref, None, None, **kw)


def _paged_kernel(t_ref, table_ref, *rest, **kw):
    """The paged variant IS _decode_kernel: page translation happens
    entirely in the specs' index maps (which consume table_ref); the
    kernel body masks by LOGICAL position only, so the online-softmax
    math stays defined once."""
    del table_ref
    _decode_kernel(t_ref, *rest, **kw)


def _paged_kernel_quant(t_ref, table_ref, q_ref, k_ref, ks_ref, v_ref,
                        vs_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    """int8 paged variant: K/V blocks stream raw int8 with their
    per-(page, pos, kv_head) scale blocks prefetched alongside (same
    page walk in the index maps); the core dequantizes per block in
    VMEM."""
    del table_ref
    _decode_core(t_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                 l_ref, ks_ref, vs_ref, **kw)


def flash_decode_paged(q, kpool, vpool, table, t, *,
                       k_scale=None, v_scale=None,
                       window: Optional[int] = None,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None):
    """Paged decode attention (vLLM-style): the KV cache lives in a
    SHARED page pool (pages, page_size, H_kv, D); each row's logical
    cache is the page sequence ``table[b]`` (B, n_logical) of physical
    page ids. One grid step loads one page — the scalar-prefetched
    table drives the DMA, so a row reads ONLY its own live pages and
    the pool can be sized to the live token count instead of
    slots x max-capacity. q: (B, 1, H, D); t: scalar or (B,) per-row
    cursors (LOGICAL positions). Returns (B, 1, H, D).

    int8 pools: pass the RAW int8 value pools as ``kpool``/``vpool``
    and their per-(page, pos, kv_head) f32 scale planes as
    ``k_scale``/``v_scale`` — scale blocks ride the same clamped page
    walk and dequant happens in VMEM per block (the epilogue), so
    quantized decode keeps the O(t) DMA behavior AND streams ~4x fewer
    HBM bytes per block. The storage-form dispatch (QuantizedPool or
    float) stays in ops/paged_kv.attend — this kernel only ever sees
    raw arrays.

    Entries of ``table`` beyond a row's live range may be garbage (the
    index map clamps to the live page walk); pages are block_k-sized by
    construction. The serving-side pool manager is
    paddle_tpu.serving.PagedKVPool."""
    b, tq, h, d = q.shape
    enforce(tq == 1, "flash_decode_paged takes one query position, "
            "got %s", tq)
    enforce(window is None or window >= 1,
            "window must be >= 1, got %s", window)
    enforce((k_scale is None) == (v_scale is None),
            "k_scale and v_scale come together (int8 pools) or not at "
            "all (float pools)")
    pages, block_k, kv_h, dk = kpool.shape
    enforce(dk == d, "pool head_dim %s != q head_dim %s", dk, d)
    enforce(h % kv_h == 0, "heads %s not divisible by kv heads %s", h,
            kv_h)
    quantized = k_scale is not None
    if quantized:
        for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
            enforce(tuple(sc.shape) == (pages, block_k, kv_h),
                    "%s must be the pool's (pages, page_size, "
                    "kv_heads) scale plane %s, got %s",
                    name, (pages, block_k, kv_h), tuple(sc.shape))
    n_log = table.shape[1]
    enforce(table.shape[0] == b,
            "table rows %s != batch %s", table.shape[0], b)
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    qh = q[:, 0]
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    table = table.astype(jnp.int32)

    def _live_page(b_, j, t_, table_):
        jj = jnp.minimum(j, t_[b_] // block_k)
        if window is not None:
            jj = jnp.maximum(
                jj, jnp.maximum(t_[b_] - window + 1, 0) // block_k)
        return jnp.clip(table_[b_, jj], 0, pages - 1)

    def kv_imap(b_, j, t_, table_):
        return (_live_page(b_, j, t_, table_), 0, 0, 0)

    def sc_imap(b_, j, t_, table_):
        # the scale plane walks the SAME clamped live pages
        return (_live_page(b_, j, t_, table_), 0, 0)

    qo_spec = pl.BlockSpec((1, h, d), lambda b_, j, t_, tb_: (b_, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, kv_h, d), kv_imap)
    kw = dict(scale=scale, window=window, block_k=block_k, n_j=n_log,
              nheads=h, kv_heads=kv_h)
    if quantized:
        sc_spec = pl.BlockSpec((1, block_k, kv_h), sc_imap)
        kernel = functools.partial(_paged_kernel_quant, **kw)
        in_specs = [qo_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        operands = (t_arr, table, qh, kpool,
                    k_scale.astype(jnp.float32), vpool,
                    v_scale.astype(jnp.float32))
    else:
        kernel = functools.partial(_paged_kernel, **kw)
        in_specs = [qo_spec, kv_spec, kv_spec]
        operands = (t_arr, table, qh, kpool, vpool)
    out = _named_call(
        "pt_flash_decode_paged",
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_log),
            in_specs=in_specs,
            out_specs=qo_spec,
            scratch_shapes=[
                _scratch((h, d), jnp.float32),
                _scratch((h, 128), jnp.float32),
                _scratch((h, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, None]


def decode_block_k(capacity: int, d: Optional[int] = None) -> Optional[int]:
    """kv block for a cache capacity: the on-chip tuned winner when the
    table has one (tools/pallas_tune.py --decode), else the largest
    supported divisor. None = shape ineligible for the kernel."""
    if d is not None:
        from .tuning import get_tuned_decode

        tuned = get_tuned_decode(capacity, d, "f32")
        if tuned is not None:
            bk = tuned.get("block_k")
            if bk and capacity % bk == 0:
                return bk
    for bk in (DEFAULT_DECODE_BLOCK_K, 128, 64):
        if capacity % bk == 0:
            return bk
    return None


def flash_decode(q, k, v, t, *, window: Optional[int] = None,
                 scale: Optional[float] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """One decode position: q (B, 1, H, D) against caches k/v
    (B, capacity, H_kv, D) with the ``pos <= t`` (and optional
    sliding-``window``) mask applied in-kernel. Returns (B, 1, H, D).
    ``t`` may be a traced scalar (one shared cursor) or a (B,) array
    of PER-ROW cursors (the continuous-batching step); either rides
    scalar prefetch into the index maps. Capacity must be divisible by
    ``block_k``."""
    b, tq, h, d = q.shape
    enforce(tq == 1, "flash_decode takes one query position, got %s",
            tq)
    cap, kv_h = k.shape[1], k.shape[2]
    enforce(h % kv_h == 0, "heads %s not divisible by kv heads %s", h,
            kv_h)
    enforce(window is None or window >= 1,
            "window must be >= 1, got %s", window)
    block_k = block_k or decode_block_k(cap, d)
    enforce(block_k is not None and cap % block_k == 0,
            "capacity %s not divisible by a supported block (%s)", cap,
            block_k)
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    n_j = cap // block_k
    qh = q[:, 0]                                      # (B, H, D)
    t_arr = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))

    def kv_imap(b_, j, t_):
        jj = jnp.minimum(j, t_[b_] // block_k)
        if window is not None:
            jj = jnp.maximum(
                jj, jnp.maximum(t_[b_] - window + 1, 0) // block_k)
        return (b_, jj, 0, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, block_k=block_k,
        n_j=n_j, nheads=h, kv_heads=kv_h)
    qo_spec = pl.BlockSpec((1, h, d), lambda b_, j, t_: (b_, 0, 0))
    out = _named_call(
        "pt_flash_decode",
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_j),
            in_specs=[
                qo_spec,
                pl.BlockSpec((1, block_k, kv_h, d), kv_imap),
                pl.BlockSpec((1, block_k, kv_h, d), kv_imap),
            ],
            out_specs=qo_spec,
            scratch_shapes=[
                _scratch((h, d), jnp.float32),
                _scratch((h, 128), jnp.float32),
                _scratch((h, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(t_arr, qh, k, v)
    return out[:, None]
