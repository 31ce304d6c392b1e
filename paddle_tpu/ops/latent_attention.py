"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): what a
position leaves behind is one compressed record for all heads, ``c``
(``kv_rank`` numbers, RMS-normed) and ``r`` (``rope`` numbers, the one
rotary key every head shares), and not keys and values by head.

Two forms compute the same attention:

- **decompressed** (a prefill): each head's keys and values are made
  from the records, ``[k^N_j ; v_j] = (c W_kvb)_j``, and heads of
  ``nope + rope`` (scores) and ``v`` (values) attend as usual:
  :func:`causal_attention`;
- **absorbed** (a decode step): ``W_kvb``'s key half moves into the
  query, ``q'_j = W^K_j q^N_j``, and its value half out of the sum, so
  every head reads the SAME records for the score and for the value
  (the first ``kv_rank`` of the same bytes): :func:`latent_read`. A
  step streams the live records once a layer whatever the head count.

Each has a Pallas body for the TPU and one plain ``jax.numpy`` body
for every other platform and shape (what the CPU tests hold the
kernels to), chosen by static shapes and the platform alone
(:func:`read_kernel_ok`, :func:`prefill_kernel_ok`): the read is
``ops/pallas/mla_decode.py`` (per-row cursors, live blocks only), the
prefill the flash kernel over heads padded to 256, which never holds
an ``S x S`` score. ``tools/mla_bodies.py`` times the read's bodies on
the chip; PERF.md section 3 has the numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30


def _kernels_run() -> bool:
    """On the TPU, or under ``ops.attention.force_flash()`` (where the
    Pallas kernels run interpreted)."""
    from . import attention

    return attention._FORCE_FLASH or jax.default_backend() == "tpu"


def read_kernel_ok(capacity: int, kv_rank: int, rope: int,
                   heads: int) -> bool:
    """Whether a one-position :func:`latent_read` takes the Pallas
    kernel: on the TPU (or under ``ops.attention.force_flash()``,
    interpreted), a latent that fills whole lanes, at most 128 query
    heads (the score block's rows) and a capacity the kernel's block
    divides. Static shapes and the platform alone."""
    from .pallas import mla_decode

    return (_kernels_run() and kv_rank % 128 == 0 and rope % 8 == 0
            and heads <= 128 and mla_decode.block_k(capacity) is not None)


def _read_jnp(qa, qr, c, r, t_rows, scale):
    f32 = jnp.float32
    s = (jnp.einsum("bhl,btl->bht", qa.astype(c.dtype), c,
                    preferred_element_type=f32)
         + jnp.einsum("bhr,btr->bht", qr.astype(r.dtype), r,
                      preferred_element_type=f32)) * scale
    keep = jnp.arange(c.shape[1])[None, :] <= t_rows[:, None]    # (B, T)
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bht,btl->bhl", p.astype(c.dtype), c,
                      preferred_element_type=f32)


def latent_read(qa, qr, c, r, t_rows, scale: float):
    """The absorbed read of the latent records, one query position a
    row.

    ``qa`` (B, H, kv_rank): the queries with ``W^K`` absorbed; ``qr``
    (B, H, rope): their rotary parts; ``c`` (B, T, kv_rank), ``r``
    (B, T, rope): the records of ``T`` positions a row; ``t_rows`` (B,)
    each row's cursor: its query sees records ``<= t_rows[b]``. Returns
    (B, H, kv_rank) float32, ``sum_i p_i c_i``: the caller multiplies
    by ``W^V``."""
    b, h, _ = qa.shape
    t_rows = jnp.broadcast_to(jnp.asarray(t_rows, jnp.int32), (b,))
    if read_kernel_ok(c.shape[1], c.shape[2], r.shape[2], h):
        from .pallas.mla_decode import mla_decode

        return mla_decode(qa, qr, c, r, t_rows, scale=scale)
    return _read_jnp(qa, qr, c, r, t_rows, scale)


# the Pallas flash kernel's blocks for a prefill's padded heads
FLASH_WIDTH, FLASH_BLOCK_Q, FLASH_BLOCK_K = 256, 1024, 512


def prefill_kernel_ok(s: int, dq: int, dv: int) -> bool:
    """Whether :func:`causal_attention` pads its heads to
    :data:`FLASH_WIDTH` and takes the Pallas flash kernel: on the TPU
    (or under ``ops.attention.force_flash()``, interpreted), a length
    the kernel's query block divides, heads no wider than the padded
    width. Static shapes and the platform alone."""
    return (_kernels_run() and s % FLASH_BLOCK_Q == 0
            and max(dq, dv) <= FLASH_WIDTH)


def _flash_padded(q, k, v, scale):
    """Heads of dq / dv as heads of 256 through the flash kernel: zeros
    add nothing to a score, and the padded values' columns are cut."""
    from .pallas.flash_attention import flash_attention

    pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + (
        (0, FLASH_WIDTH - a.shape[-1]),))
    return flash_attention(pad(q), pad(k), pad(v), causal=True,
                           scale=scale, block_q=FLASH_BLOCK_Q,
                           block_k=FLASH_BLOCK_K)[..., :v.shape[-1]]


def causal_attention(q, k, v, scale: float):
    """Causal softmax attention of a sequence over itself with heads
    whose score and value widths differ: ``q``, ``k`` (B, S, H, dq),
    ``v`` (B, S, H, dv) -> (B, S, H, dv). On the TPU, at a length the
    kernel's query block divides (:func:`prefill_kernel_ok`), the
    Pallas flash kernel over heads padded to 256 (it is written for
    equal widths of 64, 128 or 256; the zeros cost 1.6 times the
    operations, ``PERF.md`` section 3); elsewhere the whole masked
    score in ``jax.numpy``, (B, H, S, S) float32: the CPU's body and an
    odd length's, not a long prefill's."""
    if prefill_kernel_ok(q.shape[1], q.shape[-1], v.shape[-1]):
        return _flash_padded(q, k, v, scale)
    f32 = jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=f32) * scale
    at = jnp.arange(q.shape[1])
    p = jax.nn.softmax(jnp.where(at[None, :] <= at[:, None], s, _NEG),
                       axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=f32).astype(v.dtype)
