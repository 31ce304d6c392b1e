"""Pallas kernels of learned sparse attention over a latent arena (the
"DSA" lightning indexer of DeepSeek-V3.2-Exp in front of multi-head
latent attention): a query attends only the positions a learned scorer
picks for it, so each kernel here takes the pick as a per-(query, key)
keep-mask beside the causal bound.

- :func:`dsa_scores` (``%pt_dsa_scores``): a chunk's index scores
  ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``, float32, one
  (queries, keys) block at a time with the heads' sum kept in VMEM: the
  (queries, heads, keys) product never exists.
- :func:`dsa_prefill` (``%pt_dsa_prefill``): flash attention of a span
  of queries ``[q0, q0 + Sq)`` over the keys ``[0, q0 + Sq)`` under a
  keep-mask (Sq, q0 + Sq) shared by all heads; several heads a grid step,
  so a mask block is fetched once for all of them; key blocks past the
  query block's last position are neither fetched nor run.

A decode step's read under the pick (``%pt_dsa_read``) is
``mla_decode.py``'s kernel with a keep-mask a row.

Inference-only: no VJP.
"""

from __future__ import annotations

import functools
import sys
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.enforce import enforce
from .flash_attention import _NEG_INF, _named_call, _scratch, pltpu

# the module, not the function the package re-exports under its name
_flash = sys.modules[_named_call.__module__]

VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))


def _block(n: int, sizes) -> Optional[int]:
    return next((b for b in sizes if n % b == 0), None)


def _interpret(flag: Optional[bool]) -> bool:
    # through the module, so that what replaces the flash kernels'
    # switch (a compile for a described chip) replaces this one too
    return _flash._use_interpret() if flag is None else flag


# ---------------------------------------------------------------------------
# a chunk's index scores
# ---------------------------------------------------------------------------

SCORE_BLOCKS_Q = (256, 128, 64, 32, 16, 8)
SCORE_BLOCKS_K = (1024, 512, 256, 128)


def scores_ok(sq: int, sk: int, dim: int) -> bool:
    """Shapes :func:`dsa_scores` takes: whole lanes a head, blocks that
    divide both lengths."""
    return (dim % 128 == 0 and _block(sq, SCORE_BLOCKS_Q) is not None
            and _block(sk, SCORE_BLOCKS_K) is not None)


def _scores_kernel(q_ref, w_ref, k_ref, o_ref, *, heads, dim, bq, bk, q0):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j * bk <= q0 + (i + 1) * bq - 1)
    def _body():
        k, w = k_ref[0], w_ref[0]                  # (bk, dim), (bq, H)
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, :, h * dim:(h + 1) * dim], k, _NT,
                preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc


def dsa_scores(q, w, k, *, q0: int = 0, span=None,
               interpret: Optional[bool] = None):
    """``q`` (B, S, H, dim) the index queries, ``w`` (B, S, H) float32
    their head weights, ``k`` (B, S, dim) the index keys, all of
    positions ``[0, S)``; the span ``[q0, q0 + span)`` of queries
    (default: all from ``q0``) against the keys ``[0, q0 + span)`` ->
    (B, span, q0 + span) float32 ``sum_h w_h relu(q_h . k)``. The
    arrays go in whole and the span is the grid's, so no slice is
    copied. A (query, key) block wholly past the causal bound is NOT
    written: the caller masks ``s > t``."""
    b, s, heads, dim = q.shape
    sq = s - q0 if span is None else span
    sk = q0 + sq
    bq, bk = _block(sq, SCORE_BLOCKS_Q), _block(sk, SCORE_BLOCKS_K)
    enforce(bq is not None and bk is not None and q0 % bq == 0
            and sk <= k.shape[1], "index scores of queries [%s, %s) over "
            "%s keys: no block divides them", q0, sk, sk)
    first = q0 // bq
    last = lambda i: (q0 + (i + 1) * bq - 1) // bk
    return _named_call(
        "pt_dsa_scores",
        functools.partial(_scores_kernel, heads=heads, dim=dim, bq=bq,
                          bk=bk, q0=int(q0)),
        grid=(b, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, heads * dim),
                         lambda b_, i, j: (b_, first + i, 0)),
            pl.BlockSpec((1, bq, heads),
                         lambda b_, i, j: (b_, first + i, 0)),
            pl.BlockSpec((1, bk, dim), lambda b_, i, j: (
                b_, jnp.minimum(j, last(i)), 0))],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b_, i, j: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, sq, sk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(q.reshape(b, s, heads * dim).astype(k.dtype),
      w.astype(jnp.float32), k)


# ---------------------------------------------------------------------------
# a chunk's attention under the pick
# ---------------------------------------------------------------------------

PREFILL_BLOCKS = (512, 256, 128)
PREFILL_HEADS = (4, 2, 1)


def prefill_ok(sq: int, sk: int, dq: int, dv: int) -> bool:
    """Shapes :func:`dsa_prefill` takes."""
    return (dq % 128 == 0 and dv % 128 == 0
            and _block(sq, PREFILL_BLOCKS) is not None
            and _block(sk, PREFILL_BLOCKS) is not None)


def _prefill_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, acc_ref, m_ref,
                    l_ref, *, scale, hb, bq, bk, q0, n_j):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= q0 + (i + 1) * bq - 1)
    def _body():
        keep = keep_ref[0].astype(jnp.int32) != 0          # (bq, bk)
        for h in range(hb):
            k, v = k_ref[0, h], v_ref[0, h]
            s = jax.lax.dot_general(
                q_ref[0, h], k, _NT,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to(
                alpha * l_ref[h, :, :1] + jnp.sum(p, -1, keepdims=True),
                l_ref.shape[1:])
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    @pl.when(j == n_j - 1)
    def _finish():
        for h in range(hb):
            l = l_ref[h, :, :1]
            o_ref[0, h] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)
                           ).astype(o_ref.dtype)


def dsa_prefill(q, k, v, keep, *, scale: float, q0: int = 0,
                interpret: Optional[bool] = None):
    """``q`` (B, H, S, dq), ``k`` (B, H, S, dq), ``v`` (B, H, S, dv) of
    positions ``[0, S)``; ``keep`` (B, Sq, q0 + Sq) int8, non-zero where
    the query at ``q0 + i`` attends the key (the causal bound is the
    caller's to put into it; key blocks wholly past a query block's
    last position are skipped whatever it says). The span of queries
    ``[q0, q0 + Sq)`` attends the keys ``[0, q0 + Sq)``: the arrays go
    in whole and the span is the grid's, so no slice is copied. Returns
    (B, H, Sq, dv) in ``v``'s type: ``softmax_{s: keep}(scale q . k_s)
    v_s``."""
    b, heads, s, dq = q.shape
    dv = v.shape[3]
    sq, sk = keep.shape[1], keep.shape[2]
    bq, bk = _block(sq, PREFILL_BLOCKS), _block(sk, PREFILL_BLOCKS)
    hb = _block(heads, PREFILL_HEADS)
    enforce(bq is not None and bk is not None and q0 % bq == 0,
            "masked prefill of queries [%s, %s) over %s keys: no block "
            "divides them", q0, q0 + sq, sk)
    enforce(keep.shape[0] == b and sk == q0 + sq and sk <= s
            and k.shape == (b, heads, s, dq)
            and v.shape[:3] == (b, heads, s),
            "masked prefill shapes disagree: q %s k %s v %s keep %s at %s",
            q.shape, k.shape, v.shape, keep.shape, q0)
    n_j, first = sk // bk, q0 // bq
    last = lambda i: jnp.minimum((q0 + (i + 1) * bq - 1) // bk, n_j - 1)
    kv_at = lambda b_, g, i, j: (b_, g, jnp.minimum(j, last(i)), 0)
    return _named_call(
        "pt_dsa_prefill",
        functools.partial(_prefill_kernel, scale=float(scale), hb=hb,
                          bq=bq, bk=bk, q0=int(q0), n_j=n_j),
        grid=(b, heads // hb, sq // bq, n_j),
        in_specs=[
            pl.BlockSpec((1, hb, bq, dq),
                         lambda b_, g, i, j: (b_, g, first + i, 0)),
            pl.BlockSpec((1, hb, bk, dq), kv_at),
            pl.BlockSpec((1, hb, bk, dv), kv_at),
            pl.BlockSpec((1, bq, bk), lambda b_, g, i, j: (
                b_, i, jnp.minimum(j, last(i))))],
        out_specs=pl.BlockSpec((1, hb, bq, dv),
                               lambda b_, g, i, j: (b_, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, heads, sq, dv), v.dtype),
        scratch_shapes=[_scratch((hb, bq, dv), jnp.float32),
                        _scratch((hb, bq, 128), jnp.float32),
                        _scratch((hb, bq, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(q, k, v, keep.astype(jnp.int8))
