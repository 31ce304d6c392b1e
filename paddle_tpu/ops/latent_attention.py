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
prefill the flash kernel at a score width and a value width, each
padded to whole lanes and no further, which never holds an ``S x S``
score. ``tools/mla_bodies.py`` times the read's bodies on
the chip; PERF.md section 3 has the numbers.

**Under a learned selection** (the "DSA" lightning indexer of
DeepSeek-V3.2-Exp) a query reads only the ``topk`` positions of largest
index score ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``
(:func:`index_scores`, :func:`step_index_scores`), exactly
(:func:`pick_mask`: a search for the ``topk``-th largest value bit by
bit, ties to the lower position; no approximate top-k). A decode step
then reads every live record under the pick as a mask
(:func:`step_pick`, :func:`latent_read` with ``keep``: ``mla_decode``'s
kernel under a keep-mask a row); a prefill
goes by spans of :data:`QUERY_SPAN` queries, each span's scores, pick
and masked flash attention over the keys up to its end
(:func:`span_pick`, :func:`masked_attention`), so no ``S x S`` float32
exists. ``tools/dsa_bodies.py`` times them on the chip, beside the
forms that gather the picked records.
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


def _read_jnp(qa, qr, c, r, t_rows, scale, keep=None):
    f32 = jnp.float32
    s = (jnp.einsum("bhl,btl->bht", qa.astype(c.dtype), c,
                    preferred_element_type=f32)
         + jnp.einsum("bhr,btr->bht", qr.astype(r.dtype), r,
                      preferred_element_type=f32)) * scale
    live = jnp.arange(c.shape[1])[None, :] <= t_rows[:, None]    # (B, T)
    keep = live if keep is None else live & keep
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bht,btl->bhl", p.astype(c.dtype), c,
                      preferred_element_type=f32)


def latent_read(qa, qr, c, r, t_rows, scale: float, keep=None):
    """The absorbed read of the latent records, one query position a
    row.

    ``qa`` (B, H, kv_rank): the queries with ``W^K`` absorbed; ``qr``
    (B, H, rope): their rotary parts; ``c`` (B, T, kv_rank), ``r``
    (B, T, rope): the records of ``T`` positions a row; ``t_rows`` (B,)
    each row's cursor: its query sees records ``<= t_rows[b]``. Returns
    (B, H, kv_rank) float32, ``sum_i p_i c_i``: the caller multiplies
    by ``W^V``.

    ``keep`` (B, T) bool, a learned selection's pick
    (:func:`step_pick`): every live record is read and the unpicked
    masked out (the kernel's call is then ``pt_dsa_read``). On a v5e at
    16 rows x 32768 positions and a pick of 2048
    (``tools/dsa_bodies.py``; PERF.md section 3) scores, pick and this
    read take 0.50 / 0.71 / 1.12 ms a layer at contexts of 4096 / 12288 /
    28672; the other form, the picked records gathered and read alone,
    6.6 at each in XLA (its gather of 16 x 2048 records of 1152 bytes
    costs twenty times the bytes it moves) and 1.5 with ``lax.top_k``'s
    indices: it waits for a kernel that gathers."""
    b, h, _ = qa.shape
    t_rows = jnp.broadcast_to(jnp.asarray(t_rows, jnp.int32), (b,))
    if read_kernel_ok(c.shape[1], c.shape[2], r.shape[2], h):
        from .pallas.mla_decode import mla_decode

        return mla_decode(qa, qr, c, r, t_rows, scale=scale, keep=keep)
    return _read_jnp(qa, qr, c, r, t_rows, scale, keep)


# the widest head a prefill hands the Pallas flash kernel, and the
# kernel's blocks where the table measured on the chip
# (``ops/pallas/tuned_blocks.json``) has no entry for the call
FLASH_WIDTH, FLASH_BLOCK_Q, FLASH_BLOCK_K = 256, 1024, 512


def prefill_kernel_ok(s: int, dq: int, dv: int) -> bool:
    """Whether :func:`causal_attention` takes the Pallas flash kernel:
    on the TPU (or under ``ops.attention.force_flash()``, interpreted),
    a length the kernel's query block divides, heads no wider than
    :data:`FLASH_WIDTH`. Static shapes and the platform alone."""
    return (_kernels_run() and s % FLASH_BLOCK_Q == 0
            and max(dq, dv) <= FLASH_WIDTH)


def _pad_last(a, to: int):
    """The last axis zero-padded to a multiple of ``to``."""
    extra = -a.shape[-1] % to
    return a if not extra else jnp.pad(
        a, ((0, 0),) * (a.ndim - 1) + ((0, extra),))


def _flash_padded(q, k, v, scale):
    """Heads of dq / dv through the flash kernel, which keeps a score
    width and a value width apart: each operand is padded to ITS OWN
    next multiple of 128 lanes and no further (192 / 128: q and k to
    256, v not at all). Zeros add nothing to a score; a value width of
    whole lanes comes back as it is, another's padded columns are cut.
    The blocks are the tuned table's for the call (length bucket, the
    two widths as padded, the type the operands reach the kernel in),
    :data:`FLASH_BLOCK_Q` x :data:`FLASH_BLOCK_K` where it has none."""
    from .attention import flash_operand_dtype
    from .pallas.flash_attention import flash_attention, resolve_block_sizes

    dv = v.shape[-1]
    q, k, v = (_pad_last(a, 128) for a in (q, k, v))
    s = q.shape[1]
    bq, bk, bq_bwd, bk_bwd = resolve_block_sizes(
        s, s, q.shape[-1], True, dtype=flash_operand_dtype(q.dtype),
        e=v.shape[-1], default_q=FLASH_BLOCK_Q, default_k=FLASH_BLOCK_K)
    return flash_attention(
        q, k, v, causal=True, scale=scale, block_q=bq, block_k=bk,
        block_q_bwd=bq_bwd, block_k_bwd=bk_bwd)[..., :dv]


def causal_attention(q, k, v, scale: float):
    """Causal softmax attention of a sequence over itself with heads
    whose score and value widths differ: ``q``, ``k`` (B, S, H, dq),
    ``v`` (B, S, H, dv) -> (B, S, H, dv). On the TPU, at a length the
    kernel's query block divides (:func:`prefill_kernel_ok`), the
    Pallas flash kernel at the two widths, each padded to whole lanes
    (192 / 128 runs as 256 / 128: on a 128-deep MXU a score product of
    192 is two passes as one of 256 is, and the values are multiplied
    at their own width, ``PERF.md`` section 3); elsewhere the whole masked
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


# --------------------------------------------------------------------------
# a learned selection in front of the read
# --------------------------------------------------------------------------

# the queries one span of a sparse prefill holds: its index scores are
# (QUERY_SPAN, keys) float32, 235 MB at 28672 keys
QUERY_SPAN = 2048


def step_index_scores(qi, wi, ki):
    """One query a row: ``qi`` (B, H, d), ``wi`` (B, H) float32, ``ki``
    (B, T, d) -> (B, T) float32 ``sum_h w_h relu(q_h . k_s)``; the
    weighted sum is elementwise, so float32 stays float32."""
    s = jnp.einsum("bhd,btd->bht", qi.astype(ki.dtype), ki,
                   preferred_element_type=jnp.float32)
    return jnp.sum(wi.astype(jnp.float32)[:, :, None]
                   * jnp.maximum(s, 0.0), axis=1)


def scores_kernel_ok(sq: int, sk: int) -> bool:
    """Whether a chunk's index scores take ``pallas/dsa.py::dsa_scores``
    (heads padded to whole lanes): on the TPU or under ``force_flash``,
    lengths its blocks divide."""
    from .pallas import dsa

    return _kernels_run() and dsa.scores_ok(sq, sk, 128)


def index_scores(qi, wi, ki, q0: int = 0, span=None):
    """A chunk's index scores: ``qi`` (B, S, H, d), ``wi`` (B, S, H)
    float32, ``ki`` (B, S, d), all of positions ``[0, S)``; the queries
    ``[q0, q0 + span)`` (default: all from ``q0``) against the keys
    ``[0, q0 + span)`` -> (B, span, q0 + span) float32. Entries past the
    causal bound are undefined (the kernel skips their blocks): mask
    ``s > t``. The ``jax.numpy`` body adds one head at a time, so the
    (queries, heads, keys) product never exists here either."""
    q1 = qi.shape[1] if span is None else q0 + span
    if scores_kernel_ok(q1 - q0, q1):
        from .pallas.dsa import dsa_scores

        return dsa_scores(_pad_last(qi, 128), wi, _pad_last(ki, 128),
                          q0=q0, span=q1 - q0)
    f32 = jnp.float32
    qi, wi, ki = qi[:, q0:q1], wi[:, q0:q1], ki[:, :q1]

    def one(acc, head):
        q, w = head                                 # (B, Sq, d), (B, Sq)
        s = jnp.einsum("bqd,bkd->bqk", q, ki, preferred_element_type=f32)
        return acc + w[..., None] * jnp.maximum(s, 0.0), None

    acc = jnp.zeros((qi.shape[0], q1 - q0, q1), f32)
    return jax.lax.scan(one, acc, (
        jnp.moveaxis(qi.astype(ki.dtype), 2, 0),
        jnp.moveaxis(wi.astype(f32), 2, 0)))[0]


def _ordered(scores, live):
    """float32 scores as uint32 keys of the same order, 0 where not
    ``live`` (under every live key but that of a NaN with its sign
    set)."""
    scores = scores.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.int32)  # -0.0 is 0.0
    key = jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))
    return jnp.where(live, jax.lax.bitcast_convert_type(key, jnp.uint32),
                     jnp.uint32(0))


def pick_mask(scores, live, k: int):
    """The EXACT selection: ``scores`` (..., T) float32, ``live`` (...,
    T) bool -> (..., T) bool, true at the ``min(k, live count)`` live
    positions of largest score, ties to the lower position (what a
    stable descending sort's first ``k`` are). The ``k``-th largest key
    is found bit by bit, 32 counts over the row, never a sort; the
    prefix sum that breaks ties runs only where some row holds more
    than ``k`` keys at or above its threshold."""
    key = _ordered(scores, live)

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(key.shape[:-1], jnp.uint32))[..., None]
    above = key > thr
    tied = key == thr
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)
    over = jnp.sum(tied, axis=-1, dtype=jnp.int32, keepdims=True) > room
    tied = jax.lax.cond(
        jnp.any(over),
        lambda: tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= room),
        lambda: tied)
    return (above | tied) & live


def step_pick(scores, t_rows, topk: int):
    """A decode step's pick: ``scores`` (B, T) float32 at per-row cursors
    ``t_rows`` (B,) -> (keep (B, T) bool, or None where the capacity is
    within ``topk`` and the pick is every live record; the (B,) records
    each row's attention is given)."""
    b, t = scores.shape
    t_rows = jnp.broadcast_to(jnp.asarray(t_rows, jnp.int32), (b,))
    n = jnp.minimum(t_rows + 1, topk)
    if t <= topk:
        return None, n
    return pick_mask(scores, jnp.arange(t)[None, :] <= t_rows[:, None],
                     topk), n


def sparse_spans(s: int):
    """The (first, end) query spans a sparse prefill of ``s`` positions
    goes by, or None where it is one span (the CPU's body and an odd
    length's, whose index scores and attention scores are whole): on
    the TPU or under ``force_flash``, a length :data:`QUERY_SPAN`
    divides."""
    if not (_kernels_run() and s % QUERY_SPAN == 0):
        return None
    return [(a, a + QUERY_SPAN) for a in range(0, s, QUERY_SPAN)]


def head_group(heads: int) -> int:
    """The heads a sparse prefill decompresses at a time: 16 where that
    divides them (a group's queries, keys and values at 28672 positions
    and widths of 256 are 0.7 GB where all 64 heads' are 2.8)."""
    return 16 if heads % 16 == 0 else heads


def causal_keep(q0: int, q1: int):
    """(1, q1 - q0, q1) bool: query ``q0 + i`` sees keys ``<= q0 + i``."""
    return (jnp.arange(q1)[None, :]
            <= jnp.arange(q0, q1)[:, None])[None]


def span_pick(qi, wi, ki, q0: int, q1: int, topk: int):
    """The pick of the queries ``[q0, q1)`` over the keys ``[0, q1)``:
    (B, q1 - q0, q1) bool. ``qi`` (B, S, H, d), ``wi`` (B, S, H), ``ki``
    (B, S, d) hold the whole chunk."""
    live = causal_keep(q0, q1)
    if q1 <= topk:
        return jnp.broadcast_to(live, (qi.shape[0], *live.shape[1:]))
    return pick_mask(index_scores(qi, wi, ki, q0, q1 - q0), live, topk)


def masked_attention(q, k, v, keep, scale: float, q0: int = 0):
    """Softmax attention of the queries ``[q0, q0 + Sq)`` of ``q`` (B,
    H, S, dq) over the keys ``[0, q0 + Sq)`` of ``k`` (B, H, S, dq),
    ``v`` (B, H, S, dv) where ``keep`` (B, Sq, q0 + Sq) allows -> (B, H,
    Sq, dv). The Pallas kernel on the TPU or under ``force_flash``, at
    lengths its blocks divide (widths padded to whole lanes; the arrays
    go in whole), else the whole score."""
    from .pallas import dsa

    sq, sk = keep.shape[1], keep.shape[2]
    if _kernels_run() and dsa.prefill_ok(sq, sk, 128, 128):
        return dsa.dsa_prefill(
            _pad_last(q, 128), _pad_last(k, 128), _pad_last(v, 128), keep,
            scale=scale, q0=q0)[..., :v.shape[-1]]
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, q0:q0 + sq], k[:, :, :sk],
                   preferred_element_type=f32) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :sk],
                      preferred_element_type=f32).astype(v.dtype)
