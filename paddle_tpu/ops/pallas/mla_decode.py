"""Pallas decode read of a latent-attention arena: one query position
a row against the compressed records ``c (B, capacity, kv_rank)`` and
``r (B, capacity, rope)`` with the ``pos <= t`` mask.

``flash_decode.py`` reads keys and values by key-value head. Here all
``H`` query heads of a row read the SAME record, for the score (the
absorbed query against ``c``, the rotary query against ``r``) and for
the value (``c`` again): the heads are the rows of one score block, a
record block is fetched once and used for both products, and the walk
over record blocks is clamped to the live range ``[0, t // block_k]``
by a scalar-prefetch index map, so a step moves the live records and
not the capacity. Online softmax carries (m, l, acc) in VMEM across the
blocks, as the other decode kernel does. With a ``keep`` mask a row (a
learned selection's pick, ``ops/latent_attention.py::step_pick``) the
same walk masks the unpicked records out and the call is named
``pt_dsa_read``.

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

BLOCKS = (1024, 512, 256, 128)


def block_k(capacity: int) -> Optional[int]:
    """The records a grid step reads: the largest of :data:`BLOCKS`
    that divides the capacity (1024 records are 1.2 MB of bfloat16, two
    buffers of which sit in VMEM beside a (heads, 1024) float32 score);
    None where none does."""
    return next((b for b in BLOCKS if capacity % b == 0), None)


def _kernel(t_ref, qa_ref, qr_ref, c_ref, r_ref, *refs, scale, bk, n_j):
    # refs: the keep-mask's block where there is one, then the output
    # and the three carries
    *keep_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)
    t = t_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j <= t // bk)
    def _body():
        c, r = c_ref[0], r_ref[0]                  # (bk, L), (bk, R)
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qa_ref[0], c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], r, nt,
                                   preferred_element_type=jnp.float32)
             ) * scale                             # (H, bk)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        seen = cols <= t
        if keep_ref:
            seen = seen & (keep_ref[0][0] != 0)
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, -1, keepdims=True),
            l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == n_j - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)


def mla_decode(qa, qr, c, r, t, *, scale: float, keep=None,
               interpret: Optional[bool] = None):
    """``qa`` (B, H, kv_rank) the queries with ``W^K`` absorbed, ``qr``
    (B, H, rope) their rotary parts, ``c`` (B, capacity, kv_rank) and
    ``r`` (B, capacity, rope) the records, ``t`` (B,) int32 per-row
    cursors: row ``b`` sees records ``<= t[b]``. Returns (B, H,
    kv_rank) float32, ``sum_i softmax_i(scale (qa . c_i + qr . r_i))
    c_i``. With ``keep`` (B, capacity), non-zero where row ``b``'s query
    reads record ``s`` (``s <= t[b]`` is applied besides), the sum is
    over the kept records and the call is ``pt_dsa_read``: every live
    record is still streamed, the unpicked ones masked."""
    b, h, lat = qa.shape
    cap, rope = c.shape[1], r.shape[2]
    bk = block_k(cap)
    enforce(bk is not None, "capacity %s is not a multiple of any of %s",
            cap, BLOCKS)
    enforce(qr.shape == (b, h, rope) and c.shape == (b, cap, lat)
            and r.shape == (b, cap, rope),
            "latent decode shapes disagree: qa %s qr %s c %s r %s",
            qa.shape, qr.shape, c.shape, r.shape)
    enforce(keep is None or keep.shape == (b, cap), "a keep-mask is "
            "(rows, capacity) = %s, got %s", (b, cap),
            None if keep is None else keep.shape)
    if interpret is None:
        # through the module, so that what replaces the flash kernels'
        # switch (a compile for a described chip) replaces this one too
        interpret = _flash._use_interpret()
    n_j = cap // bk
    q_at = lambda b_, j, t_: (b_, 0, 0)
    rec_at = lambda b_, j, t_: (b_, jnp.minimum(j, t_[b_] // bk), 0)
    masks = [] if keep is None else [
        keep.astype(jnp.int32).reshape(b, 1, cap)]
    return _named_call(
        "pt_mla_decode" if keep is None else "pt_dsa_read",
        functools.partial(_kernel, scale=float(scale), bk=bk, n_j=n_j),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_j),
            in_specs=[pl.BlockSpec((1, h, lat), q_at),
                      pl.BlockSpec((1, h, rope), q_at),
                      pl.BlockSpec((1, bk, lat), rec_at),
                      pl.BlockSpec((1, bk, rope), rec_at)] + [
                          pl.BlockSpec((1, 1, bk), lambda b_, j, t_: (
                              b_, 0, jnp.minimum(j, t_[b_] // bk)))
                          for _ in masks],
            out_specs=pl.BlockSpec((1, h, lat), q_at),
            scratch_shapes=[_scratch((h, lat), jnp.float32),
                            _scratch((h, 128), jnp.float32),
                            _scratch((h, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, lat), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(t.astype(jnp.int32), qa.astype(c.dtype), qr.astype(r.dtype), c, r,
      *masks)
