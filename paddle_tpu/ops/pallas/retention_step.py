"""Pallas power-retention decode step: one pass over the state.

A decode step of ``ops/retention.py`` touches every number of the state
``S (slots, kv_heads, D, d)`` twice: the queries read it and the new key
and value are added to it. Written as ``jax.numpy`` the two are two
XLA fusions, each streaming ``S`` from HBM (and the update writing it
back: three passes over 34 MB a layer a slot where two are needed).
This kernel holds one (slot, key-value head) block of ``S`` in VMEM,
reads it for the head's queries, decays it, adds the rank-one term and
writes it back in place (``input_output_aliases``): one read and one
write of the state, which is the least there is.

Layout (``ops.retention.phi``): row ``o * d + i`` of ``S`` belongs to
the product ``x_i x_((i + o) mod d)``, so tile ``o`` of the block is a
(d, d) matrix with ``i`` on sublanes and the value's coordinate on
lanes, and ``phi`` of the key and of the queries is built tile by tile
from one rotation each (``pltpu.roll``): the key's along sublanes (it
arrives broadcast over lanes), the queries' along lanes.

- update, on the VPU in float32, exact: ``S_o = g S_o + (c_o k_i
  k_(i+o)) v``;
- read, on the MXU: ``P += phi(q)_o @ S_o`` with the OLD tile. Both
  sides go in as two bfloat16 halves whose sum is the float32 number
  to 2^-17 (the queries' products as 16 rows of left-hand side for the
  5 to 8 query heads, the tile as two products), so the read is exact
  to float32's own rounding of a sum of 8320 terms: on the chip the
  second product of a tile cost 1% of the step (1.785 -> 1.804 ms a
  layer at 16 slots) and took the difference from the ``jax.numpy``
  body from 1e-2 of the numerators' deviation to 3e-5. The caller
  multiplies ``P`` by the gate and adds the token's own term, which it
  computes exactly; the denominators, which are small, stay with XLA
  in float32.

Inference-only: no VJP.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.enforce import enforce
from .flash_attention import _named_call, pltpu

# the module, not the function the package re-exports under its name
_flash = sys.modules[_named_call.__module__]

ROWS = 8          # query heads a key-value head, padded to a sublane tile


def _halves(x):
    """``x`` (float32) as two bfloat16 arrays whose sum is ``x`` to
    2^-17 of it."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _step_kernel(s_ref, kb_ref, v_ref, q_ref, g_ref, s_out, p_out, *,
                 d: int):
    half = d // 2
    kb = kb_ref[0, 0]                    # (d, d): k_i on sublane i
    v, g = v_ref[0, 0], g_ref[0, 0]      # (1, d) each; g on every lane
    q = q_ref[0, 0]                      # (ROWS, d)
    acc = jnp.zeros((ROWS, d), jnp.float32)
    for o in range(half + 1):
        c = 1.0 if o in (0, half) else math.sqrt(2.0)
        kr = kb if o == 0 else pltpu.roll(kb, d - o, axis=0)
        qr = q if o == 0 else pltpu.roll(q, d - o, axis=1)
        rows = pl.ds(o * d, d)
        tile = s_ref[0, 0, rows, :]
        lhs = jnp.concatenate(_halves((c * q) * qr), axis=0)
        for part in _halves(tile):
            got = jnp.dot(lhs, part, preferred_element_type=jnp.float32)
            acc = acc + got[:ROWS] + got[ROWS:]
        s_out[0, 0, rows, :] = g * tile + ((c * kb) * kr) * v
    p_out[0, 0] = acc


def retention_state_step(S, k, v, q, g, interpret=None):
    """``S`` (B, KV, D, d) float32, consumed; ``k``, ``v`` (B, KV, d)
    and ``q`` (B, KV, R, d) float32, key and queries already scaled;
    ``g`` (B, KV) the decay. Returns (S_new = g S + phi(k) v^T written
    over ``S``, P (B, KV, R, d) = phi(q) . S, the OLD state read)."""
    b, kv, big, d = S.shape
    r = q.shape[2]
    enforce(d % 128 == 0 and big == (d // 2 + 1) * d,
            "the retention step kernel needs a head dimension that is a "
            "multiple of 128 and the state of ops.retention.phi, got %s",
            S.shape)
    enforce(r <= ROWS, "at most %s query heads a key-value head, got %s",
            ROWS, r)
    if interpret is None:
        # through the module, so that what replaces the flash kernels'
        # switch (a compile for a described chip) replaces this one too
        interpret = _flash._use_interpret()
    f32 = jnp.float32
    kb = jnp.broadcast_to(k.astype(f32)[..., None], (b, kv, d, d))
    qp = jnp.pad(q.astype(f32), ((0, 0), (0, 0), (0, ROWS - r), (0, 0)))
    grow = jnp.broadcast_to(g.astype(f32)[..., None, None], (b, kv, 1, d))
    vrow = v.astype(f32)[:, :, None, :]
    at = lambda i, j: (i, j, 0, 0)
    spec = lambda *shape: pl.BlockSpec((1, 1) + shape, at)
    S, P = _named_call(
        "pt_retention_step", functools.partial(_step_kernel, d=d),
        grid=(b, kv),
        in_specs=[spec(big, d), spec(d, d), spec(1, d), spec(ROWS, d),
                  spec(1, d)],
        out_specs=[spec(big, d), spec(ROWS, d)],
        out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct((b, kv, ROWS, d), f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret)(S, kb, vrow, qp, grow)
    return S, P[:, :, :r]
