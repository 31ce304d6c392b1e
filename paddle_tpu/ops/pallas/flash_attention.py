"""Flash attention — blockwise online-softmax attention as a Pallas TPU
kernel, with a custom VJP (recompute-based backward).

Capability role: the reference has no attention op at all (it composes
matmul+softmax in python, reference: python/paddle/fluid/nets.py:343); its
hand-written-kernel niche is `operators/jit/`. Here the niche is filled
TPU-natively: Q/K/V stream HBM→VMEM block by block, scores never materialize
in HBM, softmax runs online with a running (max, sum), and the MXU sees only
dense (block_q × d) @ (d × block_k) matmuls.

Layout: (batch, seq, heads, head_dim) at the API; internally (batch*heads,
seq, head_dim). Sequence lengths must be divisible by the block sizes (the
framework-level caller pads — ragged semantics are handled one level up, see
ops/sequence.py).

Two widths: q and k share the SCORE width ``d`` (their last dim), v has
the VALUE width ``e`` (its last dim), and every kernel keeps them apart:
q / k / dq / dk blocks and accumulators are ``d`` wide, v / o / do / dv
blocks and accumulators ``e`` wide. Masks, segments, dropout, windows
and GQA act on the (block_q, block_k) score block and see neither. Where
the two are equal the specs, scratch and grid are what one width gave.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30  # safe large-negative (finite: avoids inf-inf NaNs in bwd)


def _vmem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _seed_spec(n_rows):
    """Per-(batch*head) dropout seeds: a (1, B*H) int32 row in SMEM, the
    FULL array per grid step (a (1,1) sub-block would violate the Mosaic
    block-divisibility rule; B*H ints of SMEM are nothing). The kernel
    picks its scalar with the grid row: ``seed_ref[0, bh]``. Addressing
    the seed by (b, h) identity — instead of hashing a single scalar
    with the flattened LOCAL bh index — makes the dropout mask invariant
    to how the call is partitioned: a batch/head shard receives exactly
    the seed rows it owns, so sharded and unsharded runs drop identical
    entries."""
    imap = lambda *_: (0, 0)
    return pl.BlockSpec((1, n_rows), imap, memory_space=pltpu.SMEM)


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _named_call(name, kernel, **kw):
    """``pl.pallas_call`` under a stable device name. ``name=`` names the
    Mosaic kernel; the scope directly around the call names the
    ``custom-call`` instruction, which takes the innermost scope of its
    ``op_name`` — without one a profiler's ``XLA Ops`` event is named
    after the JAX transform around the kernel (``%checkpoint.N``,
    ``%jvp__.N``). Metadata only: the compiled kernel is the same."""
    call = pl.pallas_call(kernel, name=name, **kw)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return run


def _dropout_keep(seed, row0, col0, bq, bk, dropout_p):
    """Deterministic keep-mask for attention-probability dropout, from a
    counter-based integer hash of (per-(b,h) seed, global row, global
    col) — the same mask is rebuilt bit-identically by the backward
    kernels (no RNG state crosses the fwd/bwd boundary) and the ops are
    plain int32 iota/arithmetic, legal in Mosaic AND interpret mode.
    The (batch, head) identity lives in the SEED (one int32 per (b, h),
    see _seed_spec) rather than in the hash, so the mask depends only on
    global coordinates and is identical under any batch/head sharding.
    int32 overflow wraps (two's complement) under XLA, which is exactly
    what a mix function wants."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # rows pass through a NONLINEAR mix before cols join: a single
    # linear combination rows*A + cols*B would make every position pair
    # offset by a fixed lattice vector (A*dr + B*dc == 0 mod 2^32) hash
    # identically for all seeds — correlated dropout along diagonals
    x = rows * jnp.int32(-1640531527) + seed    # 0x9E3779B9
    x = x ^ (x >> 16)
    x = x * jnp.int32(-2048144777)              # 0x85EBCA77 as int32
    x = x ^ (x >> 13)
    x = x + cols * jnp.int32(-1028477379)
    x = x ^ (x >> 16)
    x = x * jnp.int32(-1119713537)
    x = x ^ (x >> 15)
    x = x * jnp.int32(-1640531527)
    x = x ^ (x >> 16)
    u = (x & jnp.int32(0x7FFFFFFF)).astype(jnp.float32) * (1.0 / 2147483648.0)
    return u >= dropout_p


def _band_j_lo(i, *, block_q, block_k, offset, window):
    # leftmost k-block a q-block can see under the window (may be < 0)
    return (i * block_q + offset - (window - 1)) // block_k


def _band_i_lo(j, *, block_q, block_k, offset, window, causal):
    # topmost q-block that can see k-block j under the window
    back = 0 if causal else (window - 1)
    return (j * block_k - offset - back) // block_q


def _band_width_j(*, block_q, block_k, window, causal, n_j):
    # k-blocks a q-block can touch: band span rounded up + alignment slack
    span = block_q - 1 + (window - 1) + (0 if causal else window - 1)
    return min(n_j, span // block_k + 2)


def _band_width_i(*, block_q, block_k, window, causal, n_i):
    span = block_k - 1 + (window - 1) + (0 if causal else window - 1)
    return min(n_i, span // block_q + 2)


def _banded_imap(lo_fn, n, row_fn=lambda b: b, zeros=1):
    """ONE definition of the banded index-map clamp, shared by every
    spec (k/v and q-side, both grid orders; ``zeros`` trailing unit
    coordinates — 2 for the 4-D blocked mask layout): maps (grid row,
    outer block, band step) -> (row_fn(row), clip(lo_fn(outer) + step),
    0...). The kernels recover the same index with the same
    expression — a single source for the band arithmetic."""

    def imap(b, outer, step):
        return (row_fn(b), jnp.clip(lo_fn(outer) + step, 0, n - 1),
                *([0] * zeros))

    return imap


def _causal_j_hi(i, *, block_q, block_k, offset, n_j):
    # last k-block _block_should_run lets causal q-block i touch
    return jnp.clip((i * block_q + block_q - 1 + offset) // block_k,
                    0, n_j - 1)


def _causal_i_lo(j, *, block_q, block_k, offset, n_i):
    # first q-block _block_should_run lets touch causal k-block j
    return jnp.clip((j * block_k - offset) // block_q, 0, n_i - 1)


def _block_should_run(i, j, *, causal, window, offset, block_q, block_k):
    """Block-level skip predicate shared by fwd/dq/dkv: a causal block
    runs iff its lowest row can see its first column; a window adds
    band-overlap limits on both sides (out-of-band blocks skip ALL
    compute — the O(T*window) point of local attention)."""
    run = ((i * block_q + block_q - 1 + offset >= j * block_k)
           if causal else True)
    if window is not None:
        lo = i * block_q + offset - (window - 1)   # leftmost visible col
        run &= j * block_k + block_k - 1 >= lo
        if not causal:
            hi = i * block_q + block_q - 1 + offset + (window - 1)
            run &= j * block_k <= hi
    return run


def _apply_causal_band(s, i, j, *, causal, window, offset, block_q,
                       block_k):
    """Per-entry causal/band mask shared by fwd/dq/dkv (same global
    coordinates in all three — a desync between forward and backward
    masking would corrupt gradients silently)."""
    if not causal and window is None:
        return s
    rows = (i * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0))
    cols = (j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1))
    if causal:
        s = jnp.where(rows >= cols, s, _NEG_INF)
    if window is not None:
        band = rows - cols < window
        if not causal:
            band &= cols - rows < window
        s = jnp.where(band, s, _NEG_INF)
    return s


def _use_interpret() -> bool:
    # keep in sync with ops.attention._flash_ok: the TPU compiles via
    # Mosaic, everything else tests via interpret mode
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, window,
                has_mask, has_segs, dropout_p, offset, block_q, block_k,
                num_k_blocks, banded=False, n_j=None):
    refs = list(refs)
    kvm_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segs else None
    kseg_ref = refs.pop(0) if has_segs else None
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    # program_id is read OUTSIDE pl.when bodies (interpret-mode lowering
    # cannot resolve it inside the conditional)
    bh, i, jj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    if banded:
        # banded (windowed) grid: axis 2 walks only the band; recover
        # the real k-block index (the specs clamp identically, so the
        # loaded block matches; out-of-range steps are skipped)
        j_raw = _band_j_lo(i, block_q=block_q, block_k=block_k,
                           offset=offset, window=window) + jj
        j = jnp.clip(j_raw, 0, n_j - 1)
        in_range = (j_raw >= 0) & (j_raw < n_j)
    else:
        j, in_range = jj, True

    @pl.when(jj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    should_run = in_range & _block_should_run(
        i, j, causal=causal, window=window, offset=offset,
        block_q=block_q, block_k=block_k)

    @pl.when(should_run)
    def _body():
        # matmul inputs stay in the type they arrive in, and the caller
        # decides it: flash_attention() narrows q/k/v to the policy's
        # compute type before the custom_vjp, so under mixed_bf16 these
        # are bf16 x bf16 -> f32 at the full MXU rate. f32 operands
        # (float32 policy) run at the fp32 matmul rate: measured on the
        # v5e at d128 that costs this kernel 1.1 to 1.25 times the bf16
        # time at equal blocks (PERF.md section 7), the score block's
        # vector work being most of it. Accumulators are f32 either way
        q = q_ref[0]                      # (bq, d)
        k = k_ref[0]                      # (bk, d)
        v = v_ref[0]                      # (bk, e)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk) f32
        s = _apply_causal_band(s, i, j, causal=causal, window=window,
                               offset=offset, block_q=block_q,
                               block_k=block_k)
        if has_mask:
            # key-padding keep-mask (1, bk) broadcasting over q rows;
            # the j-th block arrives via the index map (blocked layout)
            kvm = kvm_ref[0, 0]
            s = jnp.where(kvm > 0, s, _NEG_INF)
        if has_segs:
            # packed sequences: attend only within the same segment.
            # q-side ids arrive (bq, 1) via the lse-style layout, kv-side
            # (1, bk) via the blocked index map — broadcast equality
            # gives the (bq, bk) block mask with no in-kernel transpose
            qseg = qseg_ref[0]                       # (bq, 1)
            kseg = kseg_ref[0, 0]                    # (1, bk)
            s = jnp.where(qseg == kseg, s, _NEG_INF)
        m_prev = m_ref[:, :1]                              # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        if causal or window is not None or has_mask or has_segs:
            # a fully-masked row has m_new == _NEG_INF, making the
            # masked exp(s - m_new) = exp(0) = 1 instead of 0
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        # l accumulates the UNdropped p: dropout applies to the softmax
        # probabilities, not to their normalizer
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _dropout_keep(seed_ref[0, bh],
                                 i * block_q + offset, j * block_k,
                                 block_q, block_k, dropout_p)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jj == num_k_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros, not NaN
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(jnp.maximum(l, 1e-37))


def _qseg_spec(nheads, block_q):
    # q-side segment ids (B, Tq, 1) int32; (block_q, 1) last-two dims is
    # the lse layout — legal for any block_q multiple of 8
    return _vmem_spec((1, block_q, 1),
                      lambda b, i, j, _h=nheads: (b // _h, i, 0))


def _kv_row_fold(bh, nheads, kv_heads):
    # k/v may carry FEWER heads than q (GQA/MQA): q-grid row bh maps to
    # kv row batch*kv_heads + (head // group) — the kernel reads the
    # shared K/V block via the index map instead of materializing a
    # head-repeat in HBM. ONE definition: fwd/dq/dkv all fold with it.
    if kv_heads == nheads:
        return bh
    group = nheads // kv_heads
    return (bh // nheads) * kv_heads + (bh % nheads) // group


def _kv_spec(block_k, d, nheads, kv_heads, kv_arg_pos=2, j_hi=None):
    """K/V block spec; ``kv_arg_pos`` names which grid arg is the
    kv-block index (2 for the fwd/dq (b, i, j) grids, 1 for the dkv
    swapped (b, j, i) grid). ``j_hi`` (fwd/dq, causal): q-block -> last
    k-block that runs; a later step is skipped by ``pl.when``, and
    clamped to it re-names the block already in VMEM, so nothing is
    fetched for it (the band's ``_banded_imap`` does the same)."""

    def imap(*args, _h=nheads, _kv=kv_heads, _p=kv_arg_pos):
        j = args[_p]
        if j_hi is not None:
            j = jnp.minimum(j, j_hi(args[1]))
        return (_kv_row_fold(args[0], _h, _kv), j, 0)

    return _vmem_spec((1, block_k, d), imap)


def _causal_kv_spec(block_q, block_k, d, nheads, kv_heads, offset, n_j,
                    causal):
    """Non-banded K/V spec of the fwd/dq (b, i, j) grids."""
    j_hi = (functools.partial(_causal_j_hi, block_q=block_q,
                              block_k=block_k, offset=offset, n_j=n_j)
            if causal else None)
    return _kv_spec(block_k, d, nheads, kv_heads, j_hi=j_hi)


def _mask_block_spec(nheads, block_k, j_pos=2, banded_lo=None,
                     n_j=None):
    """kv-side mask/segment block spec over the (B, n_j, 1, block_k)
    BLOCKED layout (the call sites reshape the (B, 1, Tk) row): the
    grid's k-block index picks the j-th chunk via the INDEX MAP on a
    LEADING (untiled) dim, so the kernel never slices the lane dim at
    a dynamic offset — Mosaic cannot prove ``j * block_k`` is
    lane-aligned when block_k is not a multiple of 128, and the seq-64
    NMT shape (block_k=64) failed TPU compilation exactly there
    ("cannot statically prove that index in dimension 2 is a multiple
    of 128"). The last TWO dims stay (1, block_k) == the array's own
    trailing dims, which satisfies the Mosaic tiling rule for ANY
    block_k; n_j must NOT sit in the sublane slot (a (1-of-n_j) block
    there violates the divisible-by-8-or-full rule whenever n_j > 1 —
    caught by tests/test_pallas_mosaic_lowering.py). ``j_pos`` names
    the grid arg carrying the k-block index (2 for the fwd/dq
    (b, i, j) grids, 1 for the dkv (b, j, i) grid); ``banded_lo``
    switches to the banded clamp (the kernels recover the same
    index)."""
    if banded_lo is not None:
        return _vmem_spec((1, 1, 1, block_k), _banded_imap(
            banded_lo, n_j, lambda b, _h=nheads: b // _h, zeros=2))

    def imap(*args, _h=nheads, _p=j_pos):
        return (args[0] // _h, args[_p], 0, 0)

    return _vmem_spec((1, 1, 1, block_k), imap)


def _block_mask(m, n_j, block_k):
    """(B, 1, Tk) kv-side mask/segment row -> (B, n_j, 1, block_k)
    blocked layout for _mask_block_spec (None passes through)."""
    if m is None:
        return None
    return m.reshape(m.shape[0], n_j, 1, block_k)


def _fwd_call(q, k, v, kvm, qseg, kseg, seed, nheads, kv_heads, causal,
              window, scale, dropout_p, block_q, block_k, interpret):
    bh, tq, d = q.shape
    tk, e = k.shape[1], v.shape[2]
    offset = tk - tq
    n_j = tk // block_k
    n_band = (_band_width_j(block_q=block_q, block_k=block_k,
                            window=window, causal=causal, n_j=n_j)
              if window is not None else n_j)
    banded = window is not None and n_band < n_j
    grid = (bh, tq // block_q, n_band if banded else n_j)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        has_mask=kvm is not None, has_segs=qseg is not None,
        dropout_p=dropout_p, offset=offset, block_q=block_q,
        block_k=block_k, num_k_blocks=grid[2], banded=banded, n_j=n_j)
    # lse carried as (bh, tq, 1): the trailing unit dim keeps the block's
    # last-two-dims (block_q, 1) legal for the Mosaic (8, 128) tiling rule
    out_shape = (
        jax.ShapeDtypeStruct((bh, tq, e), q.dtype),
        jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
    )
    j_lo = functools.partial(_band_j_lo, block_q=block_q,
                             block_k=block_k, offset=offset,
                             window=window)
    if banded:
        # k/v specs walk only the band: jj -> clamp(j_lo(i) + jj); the
        # pipeline then never streams out-of-band K/V blocks from HBM
        kv_imap = _banded_imap(
            j_lo, n_j, lambda b: _kv_row_fold(b, nheads, kv_heads))
        k_spec, v_spec = (_vmem_spec((1, block_k, w), kv_imap)
                          for w in (d, e))
    else:
        k_spec, v_spec = (
            _causal_kv_spec(block_q, block_k, w, nheads, kv_heads, offset,
                            n_j, causal) for w in (d, e))
    mask_spec = _mask_block_spec(
        nheads, block_k, j_pos=2,
        banded_lo=j_lo if banded else None, n_j=n_j)
    in_specs = [
        _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        k_spec,
        v_spec,
    ]
    inputs = (q, k, v)
    if kvm is not None:
        in_specs.append(mask_spec)
        inputs += (_block_mask(kvm, n_j, block_k),)
    if qseg is not None:
        in_specs.append(_qseg_spec(nheads, block_q))
        in_specs.append(mask_spec)  # kv-side: blocked layout
        inputs += (qseg, _block_mask(kseg, n_j, block_k))
    if dropout_p > 0.0:
        in_specs.append(_seed_spec(q.shape[0]))
        inputs += (seed,)
    o, lse = _named_call(
        "pt_flash_fwd",
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            _vmem_spec((1, block_q, e), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[
            _scratch((block_q, e), jnp.float32),
            _scratch((block_q, 128), jnp.float32),
            _scratch((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return o, lse


# ---------------------------------------------------------------------------
# backward (recompute p from q,k + saved lse — no score materialization)
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                scale, causal, window, has_mask, has_segs, dropout_p,
                offset, block_q, block_k, grads, num_steps, banded=False,
                n_inner=None):
    """The backward's one body: p and ds of a score block, recomputed,
    and the sums ``grads`` asks of them.

    ``"dq"``: grid (b, i, j), dq of query block i summed over the key
    blocks (``pt_flash_dq``). ``"dkdv"``: grid (b, j, i), dk and dv of
    key block j summed over the query blocks (the pair's
    ``pt_flash_dkdv``). ``"all"``: that grid and those two, and dq from
    the same ds: ``ds k`` adds into the query block's rows of a float32
    accumulator that spans the (batch, head)'s WHOLE query length and
    stays in VMEM while the key blocks go by. For a fixed query block
    they arrive in ascending order, as under ``"dq"``: the same sum,
    term by term. ``banded``: the inner axis walks a window's band,
    ``num_steps`` of its ``n_inner`` blocks."""
    refs = list(refs)
    kvm_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segs else None
    kseg_ref = refs.pop(0) if has_segs else None
    seed_ref = refs.pop(0) if dropout_p > 0.0 else None
    by_key = grads != "dq"  # kv block outer, q inner
    if grads != "dkdv":
        dq_ref, dq_acc = refs.pop(0), refs.pop()
    if by_key:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    bh, outer, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    inner, in_range = step, True
    if banded:
        band = dict(block_q=block_q, block_k=block_k, offset=offset,
                    window=window)
        raw = (_band_i_lo(outer, causal=causal, **band) if by_key
               else _band_j_lo(outer, **band)) + step
        inner = jnp.clip(raw, 0, n_inner - 1)
        in_range = (raw >= 0) & (raw < n_inner)
    i, j = (inner, outer) if by_key else (outer, inner)

    @pl.when(step == 0)
    def _init():
        for acc in ((dk_acc, dv_acc) if by_key else (dq_acc,)):
            acc[:] = jnp.zeros_like(acc)

    if grads == "all":
        @pl.when((outer == 0) & (step == 0))
        def _init_dq():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    should_run = in_range & _block_should_run(
        i, j, causal=causal, window=window, offset=offset,
        block_q=block_q, block_k=block_k)

    @pl.when(should_run)
    def _body():
        # native-dtype matmul inputs (see _fwd_kernel note): p/ds are
        # quantized back to the input dtype before feeding the MXU —
        # the standard flash-backward precision contract
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]      # (bq, 1)
        delta = delta_ref[0]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _apply_causal_band(s, i, j, causal=causal, window=window,
                               offset=offset, block_q=block_q,
                               block_k=block_k)
        if has_mask:
            kvm = kvm_ref[0, 0]  # j-th block via the index map
            s = jnp.where(kvm > 0, s, _NEG_INF)
        if has_segs:
            qseg = qseg_ref[0]
            kseg = kseg_ref[0, 0]
            s = jnp.where(qseg == kseg, s, _NEG_INF)
        p = jnp.exp(s - lse)                               # (bq, bk) f32
        if causal or window is not None or has_mask or has_segs:
            # fully-masked rows carry lse == _NEG_INF (see fwd _finish)
            p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
        # same counter-based mask as fwd: out = (m ⊙ y / keep) @ v, so
        # dL/dy = (do @ v^T) ⊙ m / keep and ds = y ⊙ (dL/dy − δ)
        keep_mask = lambda: _dropout_keep(
            seed_ref[0, bh], i * block_q + offset, j * block_k, block_q,
            block_k, dropout_p)
        if by_key:
            p_v = p  # dv uses the DROPPED probabilities (out = p_drop @ v)
            if dropout_p > 0.0:
                keep = keep_mask()
                p_v = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
            dv_acc[:] += jax.lax.dot_general(
                p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (bk, e)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, bk)
        if dropout_p > 0.0:
            keep = keep if by_key else keep_mask()
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = (p * (dp - delta) * scale).astype((q if by_key else k).dtype)
        if by_key:
            dk_acc[:] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (bk, d)
        if grads != "dkdv":
            rows = (pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
                    if by_key else slice(None))
            dq_acc[rows] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (bq, d)

    @pl.when(step == num_steps - 1)
    def _finish():
        if by_key:
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        else:
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    if grads == "all":
        @pl.when((outer == pl.num_programs(1) - 1) & (step == num_steps - 1))
        def _finish_dq():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# The one-kernel backward's VMEM ceiling: what that call asks Mosaic for
# (the chip has 128 MiB; the pair and the forward live under the default
# scoped limit), and the rule that says BY SHAPE ALONE whether dq's
# accumulator fits it. Read off the described v5e's compile (PR 54): at
# 256 / 128, 1024 x 1024, bf16 it accepts tq 40960 and refuses 49152; the
# estimate below is over Mosaic's own count everywhere tried (kanana's
# call: 40 MiB for the 26 the compile needs) and stops at tq 36864.
FUSED_BWD_VMEM_LIMIT = 96 * 1024 * 1024


def fused_bwd_vmem_bytes(tq, d, e, block_q, block_k, dtype):
    """VMEM the one-kernel backward holds: the pipeline's two buffers of
    every operand and output block (dq's spans ``tq``; an lse or delta
    row pads to 128 lanes), the three float32 accumulators (dq's spans
    ``tq`` too) and four float32 score-sized blocks live in the body."""
    w = jnp.dtype(dtype).itemsize
    blocks = 2 * w * ((block_q + 2 * block_k) * (d + e) + tq * d)
    rows = 2 * 2 * 4 * block_q * 128
    accs = 4 * (tq * d + block_k * (d + e))
    return blocks + rows + accs + 4 * 4 * block_q * block_k


def bwd_is_fused(tq, d, e, block_q, block_k, dtype):
    """Whether ``_bwd_call`` runs ONE kernel (dq beside dk and dv) or the
    pair ``pt_flash_dq`` + ``pt_flash_dkdv``: the same sums either way."""
    return (fused_bwd_vmem_bytes(tq, d, e, block_q, block_k, dtype)
            <= FUSED_BWD_VMEM_LIMIT)


def _bwd_call(q, k, v, kvm, qseg, kseg, seed, nheads, kv_heads, o, lse,
              do, causal, window, scale, dropout_p, block_q, block_k,
              interpret, delta=None):
    bh, tq, d = q.shape
    tk, e = k.shape[1], v.shape[2]
    offset = tk - tq
    if delta is None:  # ring callers pass the hop-invariant value once
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)  # (bh, tq, 1)
    has_mask = kvm is not None
    has_segs = qseg is not None
    n_j, n_i = tk // block_k, tq // block_q
    band_j = (_band_width_j(block_q=block_q, block_k=block_k,
                            window=window, causal=causal, n_j=n_j)
              if window is not None else n_j)
    banded_j = window is not None and band_j < n_j
    band_i = (_band_width_i(block_q=block_q, block_k=block_k,
                            window=window, causal=causal, n_i=n_i)
              if window is not None else n_i)
    banded_i = window is not None and band_i < n_i

    band = dict(block_q=block_q, block_k=block_k, offset=offset,
                window=window)
    j_lo = functools.partial(_band_j_lo, **band)
    i_lo = functools.partial(_band_i_lo, causal=causal, **band)
    kv_imap_banded = _banded_imap(
        j_lo, n_j, lambda b: _kv_row_fold(b, nheads, kv_heads))
    q_imap_banded = _banded_imap(i_lo, n_i)

    # blocked kv-side mask layout (see _mask_block_spec): the grid's
    # k-block index picks the chunk, shared by dq (j = args[2], banded
    # clamp when windowed) and dkv (j = args[1], never banded over j)
    kvm_b = _block_mask(kvm, n_j, block_k)
    kseg_b = _block_mask(kseg, n_j, block_k)
    fused = bwd_is_fused(tq, d, e, block_q, block_k, q.dtype)
    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, window=window,
        has_mask=has_mask, has_segs=has_segs, dropout_p=dropout_p,
        offset=offset, block_q=block_q, block_k=block_k)
    if not fused:  # dq of a query block, summed over the key blocks
        dq_k_spec, dq_v_spec = (
            _vmem_spec((1, block_k, w), kv_imap_banded)
            if banded_j else _causal_kv_spec(
                block_q, block_k, w, nheads, kv_heads, offset, n_j, causal)
            for w in (d, e))
        dq_in_specs = [
            _vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            dq_k_spec,
            dq_v_spec,
            _vmem_spec((1, block_q, e), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ]
        dq_mask_spec = _mask_block_spec(
            nheads, block_k, j_pos=2,
            banded_lo=j_lo if banded_j else None, n_j=n_j)
        dq_inputs = (q, k, v, do, lse, delta)
        if has_mask:
            dq_in_specs.append(dq_mask_spec)
            dq_inputs += (kvm_b,)
        if has_segs:
            dq_in_specs.append(_qseg_spec(nheads, block_q))
            dq_in_specs.append(dq_mask_spec)
            dq_inputs += (qseg, kseg_b)
        if dropout_p > 0.0:
            dq_in_specs.append(_seed_spec(q.shape[0]))
            dq_inputs += (seed,)
        dq = _named_call(
            "pt_flash_dq",
            functools.partial(
                kernel, grads="dq", banded=banded_j, n_inner=n_j,
                num_steps=band_j if banded_j else n_j),
            grid=(bh, n_i, band_j if banded_j else n_j),
            in_specs=dq_in_specs,
            out_specs=_vmem_spec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            scratch_shapes=[_scratch((block_q, d), jnp.float32)],
            interpret=interpret)(*dq_inputs)
    if causal:
        # dkv's skipped steps come FIRST (q-blocks above the diagonal):
        # clamped up to the first block that runs, they prefetch it
        # once and fetch nothing else (see _kv_spec's j_hi)
        i_first = functools.partial(_causal_i_lo, block_q=block_q,
                                    block_k=block_k, offset=offset,
                                    n_i=n_i)
        q_imap = lambda b, j, i: (b, jnp.maximum(i, i_first(j)), 0)
    else:
        q_imap = lambda b, j, i: (b, i, 0)
    dkv_q_spec, dkv_do_spec, dkv_q1_spec = (
        _vmem_spec((1, block_q, w), q_imap_banded if banded_i else q_imap)
        for w in (d, e, 1))
    dkv_in_specs = [
        dkv_q_spec,
        _kv_spec(block_k, d, nheads, kv_heads, kv_arg_pos=1),
        _kv_spec(block_k, e, nheads, kv_heads, kv_arg_pos=1),
        dkv_do_spec,
        dkv_q1_spec,
        dkv_q1_spec,
    ]
    # dkv grid is (b, j, i): the k-block index is args[1] (plain even
    # when banded — dkv bands over i, not j)
    dkv_mask_spec = _mask_block_spec(nheads, block_k, j_pos=1)
    dkv_inputs = (q, k, v, do, lse, delta)
    if has_mask:
        dkv_in_specs.append(dkv_mask_spec)
        dkv_inputs += (kvm_b,)
    if has_segs:
        # q-side spec must use the SWAPPED grid order: i is program_id(2)
        if banded_i:
            dkv_in_specs.append(_vmem_spec((1, block_q, 1), _banded_imap(
                i_lo, n_i, lambda b, _h=nheads: b // _h)))
        else:
            dkv_in_specs.append(_vmem_spec(
                (1, block_q, 1),
                lambda b, j, i, _h=nheads: (b // _h, i, 0)))
        dkv_in_specs.append(dkv_mask_spec)
        dkv_inputs += (qseg, kseg_b)
    if dropout_p > 0.0:
        dkv_in_specs.append(_seed_spec(q.shape[0]))
        dkv_inputs += (seed,)
    out_specs = [_vmem_spec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                 _vmem_spec((1, block_k, e), lambda b, j, i: (b, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, tk, e), v.dtype)]
    scratch = [_scratch((block_k, d), jnp.float32),
               _scratch((block_k, e), jnp.float32)]
    limit = {}
    if fused:
        # dq leads the outputs: one (tq, d) block a (batch, head) whose
        # index ignores both block axes, so it stays in VMEM beside its
        # float32 accumulator (the last scratch) and is written back once
        out_specs.insert(0, _vmem_spec((1, tq, d), lambda b, j, i: (b, 0, 0)))
        out_shape.insert(0, jax.ShapeDtypeStruct((bh, tq, d), q.dtype))
        scratch.append(_scratch((tq, d), jnp.float32))
        limit = dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_BWD_VMEM_LIMIT))
    *dq_fused, dk, dv = _named_call(
        "pt_flash_dkdv",
        functools.partial(
            kernel, grads="all" if fused else "dkdv", banded=banded_i,
            n_inner=n_i, num_steps=band_i if banded_i else n_i),
        grid=(bh, n_j, band_i if banded_i else n_i), in_specs=dkv_in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, **limit)(*dkv_inputs)
    if fused:
        dq, = dq_fused
    if kv_heads != nheads:
        # dk/dv came back per Q-head; sum each group onto its shared
        # K/V head (h is kv-major: head = kv_head * group + g)
        group = nheads // kv_heads
        b = bh // nheads
        dk = dk.reshape(b, kv_heads, group, tk, d).sum(2).reshape(
            b * kv_heads, tk, d)
        dv = dv.reshape(b, kv_heads, group, tk, e).sum(2).reshape(
            b * kv_heads, tk, e)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# partitioned 4D layer: custom_partitioning INSIDE custom_vjp
#
# XLA's SPMD partitioners (GSPMD and Shardy) have no rule for a Pallas
# custom call: under plain pjit auto-sharding they would ALL-GATHER
# q/k/v and run the kernel replicated (the round-3 flagship gap —
# VERDICT r3 #3). The fix is the pattern production JAX stacks use:
# wrap the forward and backward pallas_call bundles in
# jax.experimental.custom_partitioning (which is NOT differentiable) and
# put the pair under ONE jax.custom_vjp. Attention is embarrassingly
# parallel over batch and heads, so the sharding rule declares batch/head
# dims passthrough and seq/head_dim need-replication; each device then
# runs the kernel on its local (b/dp, t, h/tp, d) shard with no
# collectives and no q/k/v gather.
#
# libtpu does NOT implement custom_partitioning: on more than one TPU
# chip the compiler refuses the call ("Custom emitter for
# CustomSPMDPartitioning not found"). There the same per-shard bodies
# run under jax.shard_map over the ambient mesh instead (_shard_map_mesh
# / _via_shard_map below), with the operand specs fixed by the repo's
# axis vocabulary (batch over dp/fsdp, heads over tp) rather than read
# from the partitioner.
#
# Capability lineage: the reference runs its hand-written jit kernels
# inside graphs parallelized by the multi-device graph pass (reference:
# paddle/fluid/operators/jit/README.en.md,
# framework/ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:450);
# here the "pass" is the SPMD partitioner and this rule teaches it the
# kernel's layout contract.
#
# The boundary arrays are kept unit-dim-free: kvm/qseg/kseg cross as
# (B, T) and lse as (B, H, T); the kernel-layout reshapes ((B,1,Tk),
# (B,Tq,1), (bh,Tq,1)) happen inside the per-shard body.
# ---------------------------------------------------------------------------


def _unpack_opt(args, has_mask, has_segs, has_seed):
    """(q, k, v, *optionals) -> (q, k, v, kvm, seg, seed)."""
    it = iter(args[3:])
    kvm = next(it) if has_mask else None
    seg = next(it) if has_segs else None
    seed = next(it) if has_seed else None
    return args[0], args[1], args[2], kvm, seg, seed


def _rows(x):
    """(B, T, H, W) -> the kernel layout (B*H, T, W), each operand at
    its own width."""
    b, t, h, w = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, w)


def _unrows(x, b):
    """The kernel layout (B*H, T, W) -> (B, T, H, W)."""
    bh, t, w = x.shape
    return x.reshape(b, bh // b, t, w).transpose(0, 2, 1, 3)


def _fwd4(q, k, v, kvm, seg, seed, *, causal, window, scale,
          dropout_p, block_q, block_k, interpret):
    """Forward on (B, T, H, D) arrays (global or per-shard): flatten to
    the kernel layout, run, unflatten. Returns (o (B, Tq, H, E), lse
    (B, H, Tq)): o is as wide as v."""
    b, tq, h, _ = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    kvm3 = None if kvm is None else kvm.astype(jnp.float32).reshape(b, 1, tk)
    # q side reads (block_q, 1) lse-layout blocks, kv side full-row
    # slices — two views of the ONE (B, T) ids array that crossed the
    # partition boundary
    qseg3 = None if seg is None else seg.astype(jnp.int32).reshape(b, tq, 1)
    kseg3 = None if seg is None else seg.astype(jnp.int32).reshape(b, 1, tk)
    seed2 = None if seed is None else seed.reshape(1, b * h)
    o, lse = _fwd_call(_rows(q), _rows(k), _rows(v), kvm3, qseg3, kseg3,
                       seed2, h, hkv, causal, window, scale, dropout_p,
                       block_q, block_k, interpret)
    return _unrows(o, b), lse.reshape(b, h, tq)


def _bwd4(q, k, v, kvm, seg, seed, o, lse, do, *, causal, window,
          scale, dropout_p, block_q_bwd, block_k_bwd, interpret):
    """Backward on (B, T, H, D) arrays; returns (dq, dk, dv) in BTHD,
    each as wide as its primal (dk/dv carry the K/V head count — already
    group-summed under GQA)."""
    b, tq, h, _ = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    lsef = lse.reshape(b * h, tq, 1)
    kvm3 = None if kvm is None else kvm.astype(jnp.float32).reshape(b, 1, tk)
    qseg3 = None if seg is None else seg.astype(jnp.int32).reshape(b, tq, 1)
    kseg3 = None if seg is None else seg.astype(jnp.int32).reshape(b, 1, tk)
    seed2 = None if seed is None else seed.reshape(1, b * h)
    dq, dk, dv = _bwd_call(_rows(q), _rows(k), _rows(v), kvm3, qseg3,
                           kseg3, seed2, h, hkv, _rows(o), lsef, _rows(do),
                           causal, window, scale, dropout_p, block_q_bwd,
                           block_k_bwd, interpret)
    return _unrows(dq, b), _unrows(dk, b), _unrows(dv, b)


def resolve_block_sizes(tq, tk, d, causal, block_q=None, block_k=None,
                        block_q_bwd=None, block_k_bwd=None,
                        dtype=jnp.float32, e=None,
                        default_q=DEFAULT_BLOCK_Q,
                        default_k=DEFAULT_BLOCK_K):
    """Resolve the four kernel block sizes from the autotuned table
    (ops/pallas/tuning.py), falling back pow2-wise to sizes that divide
    the sequence lengths. Shared by flash_attention, the ring-attention
    per-step calls (parallel/context_parallel.py), which see t/sp-sized
    blocks and must resolve against THOSE shapes, and latent
    attention's prefill (ops/latent_attention.py), whose static
    defaults are its own (``default_q`` / ``default_k``).
    ``dtype`` is the type q/k/v reach the kernel in and ``e`` the value
    width where it is not ``d``: the table is keyed by both, so an
    entry measured at bf16 never sizes an f32 call, nor one measured at
    equal widths a call whose value block is narrower."""
    tuned = {}
    if None in (block_q, block_k, block_q_bwd, block_k_bwd):
        from .tuning import attention_key, get_tuned

        tuned = get_tuned(attention_key(tq, tk, d, causal,
                                        dtype=dtype, e=e)) or {}

    def _resolve(given, key, seq, default):
        # pow2 buckets can hold shapes the tuned block doesn't divide
        # (e.g. 384 in the 512 bucket with block 256) — walk a fallback
        # chain (tuned -> default -> 64) and take the first block that
        # divides the seq, rather than trip the divisibility error in
        # flash_attention (the dispatch gate admits any 64-divisible
        # seq, so e.g. 192 must resolve to 64, not crash on the 128
        # default)
        if given is not None:
            return min(given, seq)
        for cand in (tuned.get(key), default, 64):
            if cand and seq % min(cand, seq) == 0:
                return min(cand, seq)
        return min(default, seq)

    block_q = _resolve(block_q, "block_q", tq, default_q)
    block_k = _resolve(block_k, "block_k", tk, default_k)
    # the backward kernels (dq + dkv) have their own arithmetic-intensity
    # sweet spot; tuned independently, defaulting to the forward blocks
    block_q_bwd = _resolve(block_q_bwd, "block_q_bwd", tq, block_q)
    block_k_bwd = _resolve(block_k_bwd, "block_k_bwd", tk, block_k)
    return block_q, block_k, block_q_bwd, block_k_bwd


# ---------------------------------------------------------------------------
# ring-attention per-step entry points (parallel/context_parallel.py)
#
# Ring attention holds the q rows home and rotates K/V blocks around the
# 'sp' mesh axis. Each hop runs the SAME pallas kernels as single-chip
# flash on (q_local, kv_block) — these two wrappers differ from
# _fwd4/_bwd4 only in that (a) the forward RETURNS the logsumexp so the
# ring loop can merge hops flash-decoding style, and (b) the q-side and
# kv-side segment ids are INDEPENDENT arrays (q ids stay home, kv ids
# travel with their block). No GQA/window/dropout (the ring dispatch
# gates those to the einsum path).
# ---------------------------------------------------------------------------


def ring_fwd_block(q, k, v, kvm, qseg, kseg, *, causal, scale, block_q,
                   block_k, interpret):
    """One ring hop's flash forward: local q (B, Tq, H, D) against one
    rotating K/V block (B, Tk, Hkv, D / E; Hkv | H — GQA blocks rotate with
    their FEWER heads, the kernel's index map shares them across each
    group). Returns (o, lse): o is the block-normalized output and
    lse = m + log(l) its per-row logsumexp ((B, H, Tq)) — exactly the
    pair the flash-decoding merge needs. ``causal`` here means THIS
    block is the diagonal one (same global offsets); strictly-past
    blocks are called with causal=False and strictly-future ones are
    skipped by the caller."""
    b, tq, h, _ = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    kvm3 = None if kvm is None else kvm.astype(jnp.float32).reshape(b, 1, tk)
    qseg3 = None if qseg is None else qseg.astype(jnp.int32).reshape(b, tq, 1)
    kseg3 = None if kseg is None else kseg.astype(jnp.int32).reshape(b, 1, tk)
    o, lse = _fwd_call(_rows(q), _rows(k), _rows(v), kvm3, qseg3, kseg3,
                       None, h, hkv, causal, None, scale, 0.0, block_q,
                       block_k, interpret)
    return _unrows(o, b), lse.reshape(b, h, tq)


def ring_bwd_block(q, k, v, kvm, qseg, kseg, o, lse, do, *, causal,
                   scale, block_q, block_k, interpret, delta=None):
    """One ring hop's flash backward under the GLOBAL softmax: p is
    recomputed against the ring-merged lse and delta = rowsum(do * o)
    uses the FINAL output, so the returned (dq, dk, dv) are this
    (q rows, kv block) pair's exact contributions to the global
    gradients — the standard flash backward decomposition, evaluated one
    hop at a time. ``o``/``do``: final output / upstream cotangent
    (B, Tq, H, D); ``lse``: ring-merged (B, H, Tq); ``delta``: optional
    precomputed rowsum(do*o) ((B, Tq, H) — hop-invariant, so the ring
    loop computes it once instead of n times). Under GQA (k/v carry
    Hkv < H heads) dk/dv come back group-summed onto the Hkv heads."""
    b, tq, h, _ = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    lsef = lse.reshape(b * h, tq, 1)
    deltaf = (None if delta is None
              else delta.transpose(0, 2, 1).reshape(b * h, tq, 1))
    kvm3 = None if kvm is None else kvm.astype(jnp.float32).reshape(b, 1, tk)
    qseg3 = None if qseg is None else qseg.astype(jnp.int32).reshape(b, tq, 1)
    kseg3 = None if kseg is None else kseg.astype(jnp.int32).reshape(b, 1, tk)
    dq, dk, dv = _bwd_call(_rows(q), _rows(k), _rows(v), kvm3, qseg3,
                           kseg3, None, h, hkv, _rows(o), lsef, _rows(do),
                           causal, None, scale, 0.0, block_q, block_k,
                           interpret, delta=deltaf)
    return _unrows(dq, b), _unrows(dk, b), _unrows(dv, b)


def _attn_rule(has_mask, has_segs, has_seed, gqa, bwd):
    """Einsum-style Shardy sharding rule + need-replication factors for
    the fwd/bwd custom calls. b (batch) and the head factor are
    passthrough (shardable); tq/tk and both widths, d (scores: q, k and
    their cotangents) and e (values: v, o and theirs), must be
    replicated (the kernel computes full attention rows locally). Under
    GQA the q tensor crosses the boundary as 5-D (b, tq, kv_heads,
    group, d) so the KV-HEAD factor g is SHARED with k/v and shards
    consistently — a head shard then owns whole kv groups (group itself
    is pinned replicated: splitting a group would orphan its shared
    K/V)."""
    if gqa:
        qm, km = "b tq g grp d", "b tk g d"
        om, vm = "b tq g grp e", "b tk g e"
        lse, seed = "b g grp tq", "b g grp"
    else:
        qm, km = "b tq h d", "b tk h d"
        om, vm = "b tq h e", "b tk h e"
        lse, seed = "b h tq", "b h"
    ins = [qm, km, vm]
    if has_mask:
        ins.append("b tk")
    if has_segs:
        ins.append("b tq")
    if has_seed:
        ins.append(seed)
    if bwd:
        ins += [om, lse, om]               # o, lse, do
        outs = [qm, km, vm]                # dq, dk, dv
    else:
        outs = [om, lse]                   # o, lse
    # need_replication must be sorted by factor first-appearance index:
    # non-GQA b=0, tq=1, h=2, d=3, tk=4, e=5; GQA b=0, tq=1, g=2,
    # grp=3, d=4, tk=5, e=6
    need = (("tq", "grp", "d", "tk", "e") if gqa
            else ("tq", "d", "tk", "e"))
    rule = ", ".join(ins) + " -> " + ", ".join(outs)
    return rule, need


def _attn_shardings(mesh, q_sharding, has_mask, has_segs, has_seed, gqa,
                    bwd):
    """Supported NamedShardings for every operand/result, derived from
    the partitioner's suggestion for q: keep its batch/head axes, pin
    everything else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    msh = getattr(q_sharding, "mesh", None) or mesh
    spec = tuple(q_sharding.spec) if q_sharding is not None else ()
    spec = spec + (None,) * ((5 if gqa else 4) - len(spec))
    bax = spec[0]
    hax = spec[2]  # kv-head dim under GQA (q crosses as 5-D), else heads

    def S(*parts):
        return NamedSharding(msh, P(*parts))

    if gqa:
        qs = S(bax, None, hax, None, None)   # (b, tq, kv, group, d)
        ks = S(bax, None, hax, None)         # (b, tk, kv, d)
        lse_s = S(bax, hax, None, None)      # (b, kv, group, tq)
        seed_s = S(bax, hax, None)           # (b, kv, group)
    else:
        qs = ks = S(bax, None, hax, None)
        lse_s = S(bax, hax, None)
        seed_s = S(bax, hax)
    args = [qs, ks, ks]
    if has_mask:
        args.append(S(bax, None))
    if has_segs:
        args.append(S(bax, None))
    if has_seed:
        args.append(seed_s)
    if bwd:
        args += [qs, lse_s, qs]
        results = (qs, ks, ks)
    else:
        results = (qs, lse_s)
    return msh, tuple(args), results


def _shard_map_mesh():
    """The mesh to shard_map the kernel over, or None for the
    custom_partitioning route. Only a multi-chip TPU mesh needs it
    (libtpu has no custom_partitioning), and only outside a manual
    region: inside a shard_map body the arrays are per-shard already
    and custom_partitioning lowers to the plain kernel call. The mesh
    is the ambient one (``core.mesh``; ``parallel.Trainer`` scopes its
    own around every step)."""
    if _use_interpret():
        return None
    from ...core.mesh import get_mesh

    mesh = get_mesh()
    if mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def _via_shard_map(mesh, impl, args, has_mask, has_segs, has_seed, gqa,
                   bwd):
    """Run the per-shard body ``impl`` under a fully-manual shard_map:
    batch over the mesh's dp/fsdp axes, (kv-)heads over tp, everything
    else replicated — the layout _attn_shardings derives from q on the
    custom_partitioning route, here fixed up front. An axis that does
    not divide its dim is left out (the dim stays replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    q = args[0]

    def live(axes, dim):
        axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        return axes if axes and dim % size == 0 else None

    q_sh = NamedSharding(mesh, P(live(("dp", "fsdp"), q.shape[0]), None,
                                 live(("tp",), q.shape[2])))
    _, arg_sh, res_sh = _attn_shardings(mesh, q_sh, has_mask, has_segs,
                                        has_seed, gqa, bwd)
    return jax.shard_map(
        impl, mesh=mesh, in_specs=tuple(s.spec for s in arg_sh),
        out_specs=tuple(s.spec for s in res_sh), check_vma=False)(*args)


@functools.lru_cache(maxsize=None)
def _partitioned(bwd, has_mask, has_segs, has_seed, gqa, causal, window,
                 scale, dropout_p, blk_a, blk_b, interpret):
    """Build (and cache per static config) the partitioned forward or
    backward call: custom_partitioning-wrapped, or shard_map over the
    ambient mesh where :func:`_shard_map_mesh` says so (decided per
    call, at trace time)."""
    from jax.experimental.custom_partitioning import custom_partitioning

    if bwd:
        def impl(*args):
            q, k, v, kvm, seg, seed = _unpack_opt(
                args[:-3], has_mask, has_segs, has_seed)
            o, lse, do = args[-3], args[-2], args[-1]
            if gqa:  # 5-D boundary (see _attn_rule) -> kernel 4-D forms
                b, tq, kv, grp, d = q.shape
                q = q.reshape(b, tq, kv * grp, d)
                o = o.reshape(b, tq, kv * grp, -1)
                do = do.reshape(b, tq, kv * grp, -1)
                lse = lse.reshape(b, kv * grp, tq)
                seed = (None if seed is None
                        else seed.reshape(seed.shape[0], kv * grp))
            dq, dk, dv = _bwd4(q, k, v, kvm, seg, seed, o, lse, do,
                               causal=causal, window=window, scale=scale,
                               dropout_p=dropout_p, block_q_bwd=blk_a,
                               block_k_bwd=blk_b, interpret=interpret)
            if gqa:
                dq = dq.reshape(b, tq, kv, grp, d)
            return dq, dk, dv
    else:
        def impl(*args):
            q, k, v, kvm, seg, seed = _unpack_opt(
                args, has_mask, has_segs, has_seed)
            if gqa:  # 5-D boundary (see _attn_rule) -> kernel 4-D forms
                b, tq, kv, grp, d = q.shape
                q = q.reshape(b, tq, kv * grp, d)
                seed = (None if seed is None
                        else seed.reshape(seed.shape[0], kv * grp))
            o, lse = _fwd4(q, k, v, kvm, seg, seed, causal=causal,
                           window=window, scale=scale, dropout_p=dropout_p,
                           block_q=blk_a, block_k=blk_b,
                           interpret=interpret)
            if gqa:
                o = o.reshape(b, tq, kv, grp, -1)
                lse = lse.reshape(b, kv, grp, tq)
            return o, lse

    wrapped = custom_partitioning(impl)
    rule, need = _attn_rule(has_mask, has_segs, has_seed, gqa, bwd)

    def partition(mesh, arg_shapes, result_shape):
        q_sh = arg_shapes[0].sharding
        if hasattr(q_sh, "spec"):
            msh, arg_sh, res_sh = _attn_shardings(
                mesh, q_sh, has_mask, has_segs, has_seed, gqa, bwd)
        else:
            # inside a partial-manual shard_map region the partitioner
            # hands opaque GSPMDShardings; its suggestion already went
            # through the sdy sharding rule (seq/head_dim pinned
            # replicated), so echo it and lower on the local shards
            msh = mesh
            arg_sh = tuple(a.sharding for a in arg_shapes)
            res_sh = jax.tree_util.tree_map(
                lambda x: x.sharding, result_shape)

        def lower_fn(*args):
            return impl(*args)

        return msh, lower_fn, res_sh, arg_sh

    def infer_sharding_from_operands(mesh, arg_shapes, shape):
        from jax.sharding import NamedSharding, PartitionSpec as P

        q_sh = arg_shapes[0].sharding
        if not hasattr(q_sh, "spec"):
            # GSPMD mode inside a manual region hands opaque shardings
            # (same case the partition callback guards): conservatively
            # replicate the results; partition() still lowers sharded
            return jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), shape)
        return _attn_shardings(mesh, q_sh, has_mask, has_segs, has_seed,
                               gqa, bwd)[2]

    wrapped.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer_sharding_from_operands,
        sharding_rule=rule,
        need_replication_factors=need)

    def call(*args):
        mesh = _shard_map_mesh()
        if mesh is None:
            return wrapped(*args)
        return _via_shard_map(mesh, impl, args, has_mask, has_segs,
                              has_seed, gqa, bwd)

    return call


# ---------------------------------------------------------------------------
# custom_vjp over the partitioned calls, (batch, seq, heads, head_dim)
# ---------------------------------------------------------------------------


def _opt_args(q, k, v, kvm, seg, seed):
    return (q, k, v) + tuple(a for a in (kvm, seg, seed) if a is not None)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, kvm, seg, seed, causal, window, scale, dropout_p,
           block_q, block_k, block_q_bwd, block_k_bwd, interpret):
    o, _ = _flash_fwd(q, k, v, kvm, seg, seed, causal, window, scale,
                      dropout_p, block_q, block_k, block_q_bwd,
                      block_k_bwd, interpret)
    return o


def _gqa_pack(q, seed, hkv):
    """4-D (b, t, h, d) q / (b, h) seed -> the 5-D/3-D GQA boundary
    forms whose kv-head dim shards with k/v (see _attn_rule)."""
    b, tq, h, d = q.shape
    grp = h // hkv
    q5 = q.reshape(b, tq, hkv, grp, d)
    seed3 = None if seed is None else seed.reshape(b, hkv, grp)
    return q5, seed3


def _flash_fwd(q, k, v, kvm, seg, seed, causal, window, scale, dropout_p,
               block_q, block_k, block_q_bwd, block_k_bwd, interpret):
    gqa = k.shape[2] != q.shape[2]
    fwd = _partitioned(False, kvm is not None, seg is not None,
                       seed is not None, gqa, causal, window, scale,
                       dropout_p, block_q, block_k, interpret)
    if gqa:
        b, tq, h, _ = q.shape
        q5, seed3 = _gqa_pack(q, seed, k.shape[2])
        o5, lse = fwd(*_opt_args(q5, k, v, kvm, seg, seed3))
        o = o5.reshape(b, tq, h, -1)
    else:
        o, lse = fwd(*_opt_args(q, k, v, kvm, seg, seed))
    # lse is stored in the call's boundary layout ((b, kv, grp, tq)
    # under GQA) and handed back to the bwd call unchanged
    return o, (q, k, v, kvm, seg, seed, o, lse)


def _flash_bwd(causal, window, scale, dropout_p, block_q, block_k,
               block_q_bwd, block_k_bwd, interpret, res, do):
    q, k, v, kvm, seg, seed, o, lse = res
    gqa = k.shape[2] != q.shape[2]
    bwd = _partitioned(True, kvm is not None, seg is not None,
                       seed is not None, gqa, causal, window, scale,
                       dropout_p, block_q_bwd, block_k_bwd, interpret)
    if gqa:
        b, tq, h, d = q.shape
        hkv = k.shape[2]
        grp = h // hkv
        q5, seed3 = _gqa_pack(q, seed, hkv)
        o5 = o.reshape(b, tq, hkv, grp, -1)
        do5 = do.reshape(b, tq, hkv, grp, -1)
        dq5, dk, dv = bwd(*(_opt_args(q5, k, v, kvm, seg, seed3)
                            + (o5, lse, do5)))
        dq = dq5.reshape(b, tq, h, d)
    else:
        dq, dk, dv = bwd(*(_opt_args(q, k, v, kvm, seg, seed)
                           + (o, lse, do)))
    # the keep-mask, segment ids and dropout seed carry no gradients
    return dq, dk, dv, None, None, None


# ``_flash.defvjp`` is at the end of the file, beside its forward rule


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    kv_mask=None,
                    segment_ids=None,
                    window: Optional[int] = None,
                    dropout_p: float = 0.0,
                    dropout_key=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise attention: q (batch, tq, heads, d), k (batch, tk,
    kv_heads, d), v (batch, tk, kv_heads, e) -> (batch, tq, heads, e);
    the score width d and the value width e are read from the operands
    and may differ (latent: 192 / 128); ``scale`` defaults to ``d ** -0.5``.
    Sequence lengths must divide the block sizes (shrunk for short ones).

    Differentiable: the backward recomputes the scores from o and lse,
    which the forward rule NAMES so that remat keeps them (end of file).

    Operand type: q, k and v are narrowed HERE, before the custom VJP,
    to the active policy's compute type where that is narrower than
    their own (``ops.attention.flash_operand_dtype``; f32 -> bf16 under
    ``mixed_bf16``), and the result is cast back to q's type. The
    residuals, the incoming cotangent and the layout transposes are then
    that type too; lse, delta and every accumulator stay f32. Under the
    ``float32`` and ``bfloat16`` policies nothing is cast.

    Block sizes come from the table measured on the chip
    (ops/pallas/tuned_blocks.json, written by tools/pallas_tune.py,
    keyed by device kind, shape bucket, the two widths and operand
    type) and fall back to 128x128 where it has no entry.

    ``kv_mask``: optional (batch, tk) keep-mask (True/nonzero = attend) —
    the key-padding form every ragged-batch model needs (the LoD
    replacement, ops/sequence.py); masked keys contribute nothing and
    fully-masked rows output zeros, matching ops.attention.xla_attention.
    Arbitrary (B, H, Tq, Tk) masks stay on the XLA path.

    ``segment_ids``: optional (batch, t) int ids for PACKED batches
    (multiple sequences per row, the padding-free pretraining layout):
    positions attend only within their own segment; composes with
    ``causal`` and ``kv_mask``. Self-attention only (tq == tk).

    ``dropout_p``/``dropout_key``: attention-probability dropout INSIDE
    the kernel — scores still never materialize in HBM (the whole point
    at long seq; the XLA fallback with dropout pays the (B,H,T,T)
    tensor). The keep-mask comes from a counter-based hash of the seed
    and global coordinates, so the backward rebuilds it bit-identically
    with no stored mask.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    h_kv = k.shape[2]
    from ..attention import flash_operand_dtype

    if k.shape[-1] != d:
        raise ValueError(
            f"q and k must share the score width, got {d} and "
            f"{k.shape[-1]}")
    out_dtype = q.dtype
    q, k, v = (x.astype(flash_operand_dtype(x.dtype)) for x in (q, k, v))
    if h_kv != h:
        # GQA/MQA: fewer K/V heads than Q heads; the kernel reads the
        # shared block via its index map (no head-repeat in HBM)
        if h % h_kv or v.shape[2] != h_kv:
            raise ValueError(
                f"kv heads ({h_kv}, v={v.shape[2]}) must divide q heads "
                f"({h}) and match each other")
    if scale is None:
        scale = d ** -0.5
    block_q, block_k, block_q_bwd, block_k_bwd = resolve_block_sizes(
        tq, tk, d, causal, block_q, block_k, block_q_bwd, block_k_bwd,
        dtype=q.dtype, e=v.shape[-1])
    if tq % block_q or tk % block_k or tq % block_q_bwd or tk % block_k_bwd:
        raise ValueError(
            f"seq lens ({tq},{tk}) must be divisible by blocks "
            f"({block_q},{block_k}) and bwd blocks "
            f"({block_q_bwd},{block_k_bwd}); pad upstream")
    if interpret is None:
        interpret = _use_interpret()
    kvm = None
    if kv_mask is not None:
        if kv_mask.shape != (b, tk):
            raise ValueError(
                f"kv_mask must be (batch, tk) = ({b},{tk}), got "
                f"{kv_mask.shape}")
        kvm = kv_mask.astype(jnp.float32)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    seed = None
    if dropout_p > 0.0:
        if dropout_key is None:
            raise ValueError("dropout_p > 0 requires dropout_key")
        # one int32 seed per (batch, head): the kernel addresses dropout
        # by global (b, h) identity + global coordinates, so the mask is
        # bit-identical under any batch/head sharding (see _seed_spec)
        seed = jax.random.randint(dropout_key, (b, h), -2 ** 31,
                                  2 ** 31 - 1, dtype=jnp.int32)
    seg = None
    if segment_ids is not None:
        if tq != tk:
            raise ValueError("segment_ids requires self-attention shapes "
                             f"(tq={tq} != tk={tk})")
        if segment_ids.shape != (b, tq):
            raise ValueError(
                f"segment_ids must be (batch, t) = ({b},{tq}), got "
                f"{segment_ids.shape}")
        seg = segment_ids.astype(jnp.int32)
    # 4D boundary: the partitioned fwd/bwd calls shard over batch/head
    # under pjit auto-sharding (no q/k/v all-gather) and flatten to the
    # kernel layout per shard
    return _flash(q, k, v, kvm, seg, seed, causal,
                  None if window is None else int(window), float(scale),
                  float(dropout_p), block_q, block_k, block_q_bwd,
                  block_k_bwd, interpret).astype(out_dtype)


# ---------------------------------------------------------------------------
# what remat keeps of a call
# ---------------------------------------------------------------------------
# The backward kernels take two things from the forward kernel, its
# output ``o`` and the float32 log-sum-exp of every score row ``lse``,
# and recompute the scores from them. A block under ``jax.checkpoint``
# with no policy keeps neither, so its backward pass ran the forward
# KERNEL a second time only to have them again. ``_flash``'s forward
# rule names the two as they are stored in the residual tuple, and a
# policy that saves these names (``nn.remat_policy``, what ``remat=True``
# passes in ``models/gpt.py``, ``models/hybrid.py`` and
# ``nn.TransformerEncoder``) keeps them: one (batch, seq, heads, e)
# activation and one float32 (batch, heads, seq) row a call, for a
# forward kernel a layer a step. Under no such policy a name is the
# identity and lowers to nothing.
#
# This block is the file's LAST, its import is here, and a call that is
# not differentiated (``_flash`` itself: a serving prefill) runs
# ``_flash_fwd`` bare, all on purpose: a program that holds one of these
# kernels carries the line numbers of the kernel's body and of its ten
# innermost call sites (this file's and the line of ``flash_attention``'s
# caller), and the compile cache keys on them and on the private
# functions' numbering, which a name shifted by one in one serving
# prefill. A line added above, or a name in the bare call, moves every
# serving program's key.

from jax.ad_checkpoint import checkpoint_name as _checkpoint_name  # noqa: E402

REMAT_O = "pt_flash_o"
REMAT_LSE = "pt_flash_lse"
REMAT_NAMES = (REMAT_O, REMAT_LSE)


def _flash_fwd_named(*args):
    """``_flash``'s forward rule: ``_flash_fwd`` with ``o`` and ``lse``
    named where they enter the residual tuple (its last two)."""
    o, res = _flash_fwd(*args)
    o = _checkpoint_name(o, REMAT_O)
    return o, res[:-2] + (o, _checkpoint_name(res[-1], REMAT_LSE))


_flash.defvjp(_flash_fwd_named, _flash_bwd)
