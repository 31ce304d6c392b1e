"""Context/sequence parallelism — ring attention and Ulysses all-to-all.

The reference has no sequence-parallel story at all (SURVEY.md §5.7; its long
-sequence mechanism is LoDTensor packing, reference: framework/lod_tensor.h:110).
These are green-field TPU designs:

- **Ring attention**: shard the sequence over the ``sp`` mesh axis; K/V blocks
  rotate around the ring via ``lax.ppermute`` (one ICI hop per step) while each
  device accumulates its Q-block's attention with a running online softmax
  (max/sum carries, exactly the flash-attention recurrence lifted to the mesh
  level). Peak memory per device is O(seq/sp); compute overlaps with the
  collective permute under XLA's async scheduling.

- **Ulysses**: all-to-all swaps sequence sharding for head sharding, runs a
  full (optionally Pallas flash) attention locally over seq with heads/sp heads
  per device, and all-to-alls back. Two a2a hops; requires heads % sp == 0.

Both are differentiable end-to-end: ring via autodiff through the
``lax.scan``+``ppermute`` loop (step compute wrapped in ``jax.checkpoint`` so
backward recomputes scores instead of storing (t×t) blocks), Ulysses via the
flash kernel's custom VJP plus the self-transposing all-to-alls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.enforce import enforce
from ..core.mesh import get_mesh

_NEG_INF = -1e30  # finite: avoids inf-inf NaNs under autodiff


def _shard_with_optional(inner, mesh, spec, mspec, q, k, v, kv_mask,
                         segment_ids):
    """shard_map an ``inner(q, k, v, km, seg)`` with OPTIONAL (B, T)
    inputs: shard_map specs are positional, so each supplied optional
    appends an arg+spec pair and the wrapper re-slots them (None for the
    absent ones) — one place for the plumbing both ring and Ulysses use."""
    args, in_specs = [q, k, v], [spec, spec, spec]
    km_i = seg_i = None
    if kv_mask is not None:
        km_i = len(args)
        args.append(kv_mask)
        in_specs.append(mspec)
    if segment_ids is not None:
        seg_i = len(args)
        args.append(segment_ids)
        in_specs.append(mspec)

    def wrapper(*xs):
        return inner(xs[0], xs[1], xs[2],
                     xs[km_i] if km_i is not None else None,
                     xs[seg_i] if seg_i is not None else None)

    fn = shard_map(wrapper, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=spec, check_vma=False)
    return fn(*args)


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------


def _ring_step_compute(qf, acc, m, l, kc, vc, kmc, qseg, ksegc, src,
                       my_idx, *, t_local, causal, window, scale):
    """One ring step's flash-style accumulation (no collectives; wrapped in
    jax.checkpoint by the caller so backward recomputes the (t×t) scores).
    ``kmc``: the K/V block's key-padding keep-mask (b, t_local) rotating
    around the ring with it, or None. ``qseg``/``ksegc``: packed-batch
    segment ids — q side fixed to this shard, kv side rotating with its
    block; attention stays within a segment. ``window``: sliding-window
    band in GLOBAL positions."""
    # q/k stay in their native dtype (bf16 in production): bf16 inputs
    # with an f32 preferred_element_type run at the full MXU rate, while
    # a pre-cast to f32 would drop to the fp32 matmul rate (4-8x slower
    # on v5e) with no accumulator benefit
    b, t, h, d = qf.shape
    hkv = kc.shape[2]
    if hkv != h:
        # GQA: the K/V blocks rotate the ring with their FEWER heads
        # (h/hkv x less ICI traffic and carry memory than expanding up
        # front); the grouped einsum shares each kv head across its
        # group, kv-major head order matching the kernel/xla paths
        q5 = qf.reshape(b, t, hkv, h // hkv, d)
        s = jnp.einsum("bqegd,bked->begqk", q5, kc,
                       preferred_element_type=jnp.float32)
        s = s.reshape(b, h, t, kc.shape[1]) * scale
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc,
                       preferred_element_type=jnp.float32) * scale
    if causal or window is not None:
        rows = my_idx * t_local + lax.broadcasted_iota(
            jnp.int32, (t_local, t_local), 0)
        cols = src * t_local + lax.broadcasted_iota(
            jnp.int32, (t_local, t_local), 1)
        if causal:
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if window is not None:
            band = rows - cols < window
            if not causal:
                band &= cols - rows < window
            s = jnp.where(band, s, _NEG_INF)
    if kmc is not None:
        s = jnp.where(kmc[:, None, None, :], s, _NEG_INF)
    if qseg is not None:
        s = jnp.where(qseg[:, None, :, None] == ksegc[:, None, None, :],
                      s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)          # (b,h,t,1)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    if kmc is not None or qseg is not None or window is not None:
        # a fully-masked row keeps m_new == _NEG_INF, turning the masked
        # exp(s - m_new) into exp(0) = 1; zero those entries so l stays 0
        # and the final o is 0 (causal alone can't fully mask a row —
        # the diagonal is always visible; a window CAN fully mask a row
        # of an off-diagonal step block)
        p = jnp.where(s <= _NEG_INF * 0.5, 0.0, p)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    if hkv != h:
        p5 = p.astype(vc.dtype).reshape(b, hkv, h // hkv, t, kc.shape[1])
        pv = jnp.einsum("begqk,bked->bqegd", p5, vc,
                        preferred_element_type=jnp.float32)
        pv = pv.reshape(b, t, h, d)
    else:
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
    acc_new = acc * alpha.transpose(0, 2, 1, 3) + pv     # (b,t,h,d)
    return acc_new, m_new, l_new


def _ring_step_gate(src, my_idx, *, t_local, causal, window):
    """Scalar: does this ring step's K/V block contribute at all? False
    for strictly-future blocks (causal) and blocks wholly outside the
    window band — the caller lax.cond's the WHOLE step compute away
    (einsum + softmax + PV), which is what makes causal ring O(T^2/2)
    and windowed ring O(T*W) per device instead of dense cost."""
    gate = jnp.bool_(True)
    if causal:
        gate &= src <= my_idx
    if window is not None:
        # overlap between [src*t, src*t+t-1] cols and the band of
        # [my*t, my*t+t-1] rows
        lo_ok = (src + 1) * t_local - 1 >= my_idx * t_local - (window - 1)
        in_band = lo_ok if causal else (
            lo_ok & (src * t_local <= (my_idx + 1) * t_local - 1
                     + (window - 1)))
        gate &= in_band
    return gate


def _ring_inner(q, k, v, km, seg, *, axis, causal, window, scale, n):
    b, t, h, d = q.shape  # local (sequence-sharded) shapes
    has_mask = km is not None
    has_segs = seg is not None
    my_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qf = q  # native dtype into the MXU (see _ring_step_compute note)
    compute = jax.checkpoint(functools.partial(
        _ring_step_compute, t_local=t, causal=causal, window=window,
        scale=scale))

    def step(carry, t_step):
        acc, m, l, kc, vc, kmc, ksegc = carry
        src = (my_idx - t_step) % n  # origin rank of the K/V block we hold
        gate = _ring_step_gate(src, my_idx, t_local=t, causal=causal,
                               window=window)
        acc, m, l = lax.cond(
            gate,
            lambda a, mm, ll, kcc, vcc: compute(
                qf, a, mm, ll, kcc, vcc,
                kmc if has_mask else None,
                seg if has_segs else None,
                ksegc if has_segs else None, src, my_idx),
            lambda a, mm, ll, kcc, vcc: (a, mm, ll),
            acc, m, l, kc, vc)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        if has_mask:  # the keep-mask block travels with its K/V block
            kmc = lax.ppermute(kmc, axis, perm)
        if has_segs:  # so do the kv-side segment ids
            ksegc = lax.ppermute(ksegc, axis, perm)
        return (acc, m, l, kc, vc, kmc, ksegc), None

    acc0 = jnp.zeros((b, t, h, d), jnp.float32)
    m0 = jnp.full((b, h, t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t, 1), jnp.float32)
    # zeros placeholders keep the scan carry structure static when no
    # mask/ids are supplied (never read: has_* are trace-time consts)
    km0 = km if has_mask else jnp.zeros((b, t), jnp.bool_)
    seg0 = seg if has_segs else jnp.zeros((b, t), jnp.int32)
    # scan the first n-1 steps (compute + rotate); the last block's compute is
    # peeled out so the final rotation — whose result would be discarded —
    # never hits the ICI ring
    (acc, m, l, kc, vc, kmc, ksegc), _ = lax.scan(
        step, (acc0, m0, l0, k, v, km0, seg0), jnp.arange(n - 1))
    last_src = (my_idx - (n - 1)) % n
    acc, m, l = lax.cond(
        _ring_step_gate(last_src, my_idx, t_local=t, causal=causal,
                        window=window),
        lambda a, mm, ll, kcc, vcc: compute(
            qf, a, mm, ll, kcc, vcc,
            kmc if has_mask else None,
            seg if has_segs else None,
            ksegc if has_segs else None, last_src, my_idx),
        lambda a, mm, ll, kcc, vcc: (a, mm, ll),
        acc, m, l, kc, vc)
    o = acc / jnp.maximum(l.transpose(0, 2, 1, 3), 1e-37)
    return o.astype(q.dtype)


# --- ring attention on the flash kernel (VERDICT r4 #3) -------------------
#
# The einsum inner above materializes per-shard-pair (t x t) score blocks
# through XLA every hop — exactly the cost the flash kernel exists to
# kill, and the reason bert_long's SP config was bounded by the fallback.
# This path instead runs the Pallas flash FORWARD per hop (returning the
# block's output + logsumexp) and merges hops flash-decoding style:
#
#   lse' = logaddexp(lse, lse_hop)
#   o'   = o * exp(lse - lse') + o_hop * exp(lse_hop - lse')
#
# which is the online-softmax recurrence carried ACROSS ppermute hops —
# scores never leave VMEM. The backward is its own ring loop: each hop
# calls the flash backward kernel with the GLOBAL (ring-merged) lse and
# the FINAL output (delta = rowsum(do*o)), which makes every hop's
# (dq, dk, dv) the exact contribution of that (q rows, kv block) pair to
# the global gradients; dk/dv accumulators travel the ring with their
# block and arrive home after n hops. Causal runs skip strictly-future
# blocks entirely (lax.cond) and use the causal kernel variant only on
# the diagonal block, keeping the O(T^2/2) ring schedule.
#
# Handles kv_mask/segment_ids/causal AND GQA (kv blocks rotate with
# their fewer heads; the kernel shares them per group). Windowed runs
# stay on the einsum inner; dropout doesn't apply under SP. Dispatch in
# ring_attention.


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13))
def _ring_flash(q, k, v, km, seg, axis, causal, scale, n, block_q,
                block_k, block_q_bwd, block_k_bwd, interpret):
    o, _ = _ring_flash_fwd(q, k, v, km, seg, axis, causal, scale, n,
                           block_q, block_k, block_q_bwd, block_k_bwd,
                           interpret)
    return o


def _ring_flash_fwd(q, k, v, km, seg, axis, causal, scale, n, block_q,
                    block_k, block_q_bwd, block_k_bwd, interpret):
    from ..ops.pallas.flash_attention import ring_fwd_block

    b, t, h, d = q.shape
    my_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_mask = km is not None
    has_segs = seg is not None

    def fwd_block(kc, vc, kmc, ksegc, blk_causal):
        return ring_fwd_block(
            q, kc, vc, kmc if has_mask else None,
            seg if has_segs else None, ksegc if has_segs else None,
            causal=blk_causal, scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret)

    def merge(o_acc, lse_acc, o_s, lse_s):
        lse_new = jnp.logaddexp(lse_acc, lse_s)          # (b, h, t)
        w = lambda x: jnp.exp(x - lse_new).transpose(0, 2, 1)[..., None]
        return (o_acc * w(lse_acc) + o_s.astype(jnp.float32) * w(lse_s),
                lse_new)

    def contribute(o_acc, lse_acc, kc, vc, kmc, ksegc, src):
        if causal:
            o_s, lse_s = lax.cond(
                src == my_idx,
                lambda: fwd_block(kc, vc, kmc, ksegc, True),
                lambda: fwd_block(kc, vc, kmc, ksegc, False))
        else:
            o_s, lse_s = fwd_block(kc, vc, kmc, ksegc, False)
        return merge(o_acc, lse_acc, o_s, lse_s)

    def step_body(o_acc, lse_acc, kc, vc, kmc, ksegc, src):
        if causal:  # strictly-future blocks contribute nothing at all
            return lax.cond(
                src > my_idx,
                lambda: (o_acc, lse_acc),
                lambda: contribute(o_acc, lse_acc, kc, vc, kmc, ksegc,
                                   src))
        return contribute(o_acc, lse_acc, kc, vc, kmc, ksegc, src)

    def step(carry, t_step):
        o_acc, lse_acc, kc, vc, kmc, ksegc = carry
        src = (my_idx - t_step) % n
        o_acc, lse_acc = step_body(o_acc, lse_acc, kc, vc, kmc, ksegc,
                                   src)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        if has_mask:
            kmc = lax.ppermute(kmc, axis, perm)
        if has_segs:
            ksegc = lax.ppermute(ksegc, axis, perm)
        return (o_acc, lse_acc, kc, vc, kmc, ksegc), None

    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t), _NEG_INF, jnp.float32)
    km0 = km if has_mask else jnp.zeros((b, t), jnp.bool_)
    seg0 = seg if has_segs else jnp.zeros((b, t), jnp.int32)
    # scan the first n-1 hops (compute + rotate); the last hop's compute
    # is peeled so the final rotation never hits the ICI ring
    (o_acc, lse_acc, kc, vc, kmc, ksegc), _ = lax.scan(
        step, (o0, lse0, k, v, km0, seg0), jnp.arange(n - 1))
    last_src = (my_idx - (n - 1)) % n
    o_acc, lse_acc = step_body(o_acc, lse_acc, kc, vc, kmc, ksegc,
                               last_src)
    o = o_acc.astype(q.dtype)
    return o, (q, k, v, km, seg, o, lse_acc)


def _ring_flash_bwd(axis, causal, scale, n, block_q, block_k,
                    block_q_bwd, block_k_bwd, interpret, res, do):
    from ..ops.pallas.flash_attention import ring_bwd_block

    q, k, v, km, seg, o, lse = res
    b, t, h, d = q.shape
    my_idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_mask = km is not None
    has_segs = seg is not None

    # hop-invariant: rowsum(do * o) against the FINAL output, computed
    # once here rather than inside each of the n hops' kernel calls
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # (b, t, h)

    def bwd_block(kc, vc, kmc, ksegc, blk_causal):
        return ring_bwd_block(
            q, kc, vc, kmc if has_mask else None,
            seg if has_segs else None, ksegc if has_segs else None,
            o, lse, do, causal=blk_causal, scale=scale,
            block_q=block_q_bwd, block_k=block_k_bwd,
            interpret=interpret, delta=delta)

    def contribute(dq, dkc, dvc, kc, vc, kmc, ksegc, src):
        if causal:
            dq_p, dk_p, dv_p = lax.cond(
                src == my_idx,
                lambda: bwd_block(kc, vc, kmc, ksegc, True),
                lambda: bwd_block(kc, vc, kmc, ksegc, False))
        else:
            dq_p, dk_p, dv_p = bwd_block(kc, vc, kmc, ksegc, False)
        return (dq + dq_p.astype(jnp.float32),
                dkc + dk_p.astype(jnp.float32),
                dvc + dv_p.astype(jnp.float32))

    def step_body(dq, dkc, dvc, kc, vc, kmc, ksegc, src):
        if causal:
            return lax.cond(
                src > my_idx,
                lambda: (dq, dkc, dvc),
                lambda: contribute(dq, dkc, dvc, kc, vc, kmc, ksegc,
                                   src))
        return contribute(dq, dkc, dvc, kc, vc, kmc, ksegc, src)

    def step(carry, t_step):
        dq, kc, vc, kmc, ksegc, dkc, dvc = carry
        src = (my_idx - t_step) % n
        dq, dkc, dvc = step_body(dq, dkc, dvc, kc, vc, kmc, ksegc, src)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        if has_mask:
            kmc = lax.ppermute(kmc, axis, perm)
        if has_segs:
            ksegc = lax.ppermute(ksegc, axis, perm)
        # the block's gradient accumulators travel WITH it
        dkc = lax.ppermute(dkc, axis, perm)
        dvc = lax.ppermute(dvc, axis, perm)
        return (dq, kc, vc, kmc, ksegc, dkc, dvc), None

    dq0 = jnp.zeros((b, t, h, d), jnp.float32)
    # GQA: accumulators match the (possibly fewer-headed) K/V blocks
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    km0 = km if has_mask else jnp.zeros((b, t), jnp.bool_)
    seg0 = seg if has_segs else jnp.zeros((b, t), jnp.int32)
    (dq, kc, vc, kmc, ksegc, dkc, dvc), _ = lax.scan(
        step, (dq0, k, v, km0, seg0, dk0, dv0), jnp.arange(n - 1))
    last_src = (my_idx - (n - 1)) % n
    dq, dkc, dvc = step_body(dq, dkc, dvc, kc, vc, kmc, ksegc, last_src)
    # one final hop brings each block's accumulated dk/dv home (the k/v
    # blocks themselves are already discarded — no need to rotate them)
    dkc = lax.ppermute(dkc, axis, perm)
    dvc = lax.ppermute(dvc, axis, perm)
    return (dq.astype(q.dtype), dkc.astype(k.dtype),
            dvc.astype(v.dtype), None, None)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def _ring_flash_inner(q, k, v, km, seg, *, axis, causal, scale, n,
                      blocks, interpret):
    return _ring_flash(q, k, v, km, seg, axis, causal, scale, n,
                       blocks[0], blocks[1], blocks[2], blocks[3],
                       interpret)


def ring_attention(q, k, v, *, causal: bool = False,
                   scale: Optional[float] = None, axis: str = "sp",
                   batch_axis: Optional[str] = "dp", mesh=None,
                   kv_mask=None, segment_ids=None,
                   window: Optional[int] = None,
                   use_flash: bool = True):
    """Sequence-parallel attention over global (B, T, H, D) arrays.

    ``q``/``k``/``v`` are sharded ``P(batch_axis, axis)`` over the mesh; T must
    divide by the ``axis`` size. Causal masking is in *global* positions.
    ``kv_mask``: optional global (B, T) keep-mask (the ragged-batch
    key-padding form); its blocks rotate around the ring with their K/V.
    ``segment_ids``: optional global (B, T) packed-batch ids (ids global
    per row, so a segment spanning a shard boundary keeps one id); the
    kv-side ids rotate with their block. ``window``: sliding-window band
    in GLOBAL positions (ring steps wholly outside the band keep their
    carries untouched).

    ``use_flash``: route each ring hop through the Pallas flash kernel
    (online-softmax carries merged ACROSS hops — scores never hit HBM)
    when the per-shard block shape is kernel-eligible; windowed runs and
    ineligible shapes keep the einsum inner. Same gating semantics as
    scaled_dot_product_attention's use_flash.

    GQA/MQA (r5): ``k``/``v`` may carry fewer heads than ``q``
    (``h % kv_heads == 0``) — on the flash path the smaller blocks
    rotate as-is and the kernel shares them per group (dk/dv come home
    group-summed); the einsum fallback expands them kv-major up front.
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    b, t, h, d = q.shape
    hkv = k.shape[2]
    enforce(t % n == 0, "seq len %s must divide sp size %s", t, n)
    enforce(k.shape == v.shape and k.shape[0] == b and k.shape[1] == t
            and k.shape[3] == d,
            "ring attention is self-attention shaped: k/v must be "
            "(%s, %s, kv_heads, %s), got k=%s v=%s", b, t, d, k.shape,
            v.shape)
    enforce(h % hkv == 0,
            "q heads %s must be a multiple of kv heads %s (GQA)", h, hkv)
    for name, arr in (("kv_mask", kv_mask), ("segment_ids", segment_ids)):
        if arr is not None:
            enforce(arr.shape == (b, t),
                    "%s must be (batch, seq) = (%s, %s), got %s",
                    name, b, t, arr.shape)
    enforce(window is None or window >= 1,
            "window must be >= 1, got %s", window)
    if scale is None:
        scale = d ** -0.5
    spec = P(batch_axis, axis, None, None)
    mspec = P(batch_axis, axis)
    t_local = t // n
    from ..ops.attention import flash_shape_ok

    if use_flash and window is None and flash_shape_ok(
            t_local, t_local, d, causal=causal, dtype=q.dtype):
        from ..ops.pallas.flash_attention import (_use_interpret,
                                                  resolve_block_sizes)

        blocks = resolve_block_sizes(t_local, t_local, d, causal,
                                     dtype=q.dtype)
        inner = functools.partial(
            _ring_flash_inner, axis=axis, causal=causal,
            scale=float(scale), n=n, blocks=blocks,
            interpret=_use_interpret())
    else:
        # the einsum inner handles GQA natively (grouped score einsum in
        # _ring_step_compute): kv blocks rotate with their fewer heads
        inner = functools.partial(_ring_inner, axis=axis, causal=causal,
                                  window=window, scale=float(scale), n=n)
    return _shard_with_optional(inner, mesh, spec, mspec, q, k, v,
                                kv_mask, segment_ids)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------


def _ulysses_inner(q, k, v, km, seg, *, axis, causal, window, scale,
                   use_flash):
    from ..ops.attention import scaled_dot_product_attention

    # (b, t/sp, h, d) --a2a--> (b, t, h/sp, d): full sequence, head subset
    q = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    mask = None
    if km is not None:
        # each shard holds (b, t/sp) of the keep-mask; after the a2a the
        # local attention sees the FULL sequence, so gather the mask
        # along sp (tiny: bools, no head/dim axes)
        full = lax.all_gather(km, axis, axis=1, tiled=True)  # (b, t)
        mask = full[:, None, None, :]
    seg_full = None
    if seg is not None:  # same gather for packed-batch segment ids
        seg_full = lax.all_gather(seg, axis, axis=1, tiled=True)
    o = scaled_dot_product_attention(q, k, v, mask=mask, causal=causal,
                                     scale=scale, use_flash=use_flash,
                                     segment_ids=seg_full, window=window)
    # back to sequence sharding
    return lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, *, causal: bool = False,
                      scale: Optional[float] = None, axis: str = "sp",
                      batch_axis: Optional[str] = "dp", mesh=None,
                      use_flash: bool = True, kv_mask=None,
                      segment_ids=None, window: Optional[int] = None):
    """DeepSpeed-Ulysses-style SP: a2a seq→head shard, local full attention
    (Pallas flash on TPU), a2a back. Requires heads % sp == 0.
    ``kv_mask``: optional global (B, T) keep-mask; all-gathered over sp
    for the full-sequence local attention (key-padding routes to the
    flash kernel's kv_mask path on TPU). ``segment_ids``: optional global
    (B, T) packed-batch ids, same gather (self-attention only).

    GQA/MQA (r5): supported when ``kv_heads % sp == 0`` — q's kv-major
    head order means each head shard then holds WHOLE groups, so the k/v
    all-to-alls split their own (fewer) heads and the local attention
    stays a valid GQA problem. Fewer kv heads than sp can't shard this
    way; use ``ring`` there."""
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    b, t, h, d = q.shape
    hkv = k.shape[2]
    enforce(t % n == 0, "seq len %s must divide sp size %s", t, n)
    enforce(h % n == 0, "num heads %s must divide sp size %s (Ulysses)", h, n)
    enforce(h % hkv == 0,
            "q heads %s must be a multiple of kv heads %s (GQA)", h, hkv)
    enforce(hkv % n == 0,
            "kv heads %s must divide sp size %s (Ulysses GQA shards "
            "whole groups per device; use seq_parallel='ring' for "
            "kv_heads < sp)", hkv, n)
    if kv_mask is not None:
        # key-padding masks cover the KEY sequence: cross-attention under
        # Ulysses has tk != tq and the mask belongs to k/v, not q
        tk = k.shape[1]
        enforce(kv_mask.shape == (b, tk),
                "kv_mask must be (batch, key_seq) = (%s, %s), got %s",
                b, tk, kv_mask.shape)
    if segment_ids is not None:
        enforce(q.shape[1] == k.shape[1],
                "segment_ids requires self-attention shapes "
                "(tq=%s != tk=%s)", q.shape[1], k.shape[1])
        enforce(segment_ids.shape == (b, t),
                "segment_ids must be (batch, seq) = (%s, %s), got %s",
                b, t, segment_ids.shape)
    if scale is None:
        scale = d ** -0.5
    spec = P(batch_axis, axis, None, None)
    mspec = P(batch_axis, axis)
    enforce(window is None or window >= 1,
            "window must be >= 1, got %s", window)
    inner = functools.partial(_ulysses_inner, axis=axis, causal=causal,
                              window=window, scale=float(scale),
                              use_flash=use_flash)
    return _shard_with_optional(inner, mesh, spec, mspec, q, k, v,
                                kv_mask, segment_ids)


def context_parallel_attention(q, k, v, *, impl: str = "ring", **kw):
    """Dispatch helper: ``impl`` in {"ring", "ulysses"}."""
    if impl == "ring":
        return ring_attention(q, k, v, **kw)
    if impl == "ulysses":
        return ulysses_attention(q, k, v, **kw)
    raise ValueError(f"unknown context-parallel impl {impl!r}")


def sharded_flash_attention(q, k, v, *, mesh=None, batch_axis="dp",
                            head_axis=None, causal=False, scale=None,
                            kv_mask=None, segment_ids=None, window=None,
                            dropout_p=0.0, dropout_key=None):
    """Flash attention partitioned over batch and/or head mesh axes via
    EXPLICIT shard_map. Since round 4 the kernel itself registers a
    partitioning rule (jax.experimental.custom_partitioning, see
    ops/pallas/flash_attention.py) covering dense AND GQA heads (q
    crosses the boundary as (B, T, KV, GROUP, D) so kv heads shard with
    k/v), so plain pjit auto-sharding already runs it on local shards —
    this wrapper remains for explicit control of which axes shard
    independently of the operands' incoming shardings.

    Attention is embarrassingly parallel over batch and heads, so each
    device runs the kernel on its local (b/dp, t, h/tp, d) shard with no
    collectives. kv_mask/segment_ids shard over batch only. Dropout:
    each shard folds its mesh coordinates into the key, so masks are
    DISTINCT across devices (no cross-shard correlation) and
    deterministic per key — unlike the auto-partitioned path, whose
    per-(b,h) seeds make masks bit-identical to the unsharded call.

    The SP paths (ring/ulysses above) already run inside their own
    shard_map.
    """
    from ..ops.pallas.flash_attention import flash_attention

    mesh = mesh or get_mesh()
    b, t, h, d = q.shape
    axes = dict(mesh.shape)
    for name, ax in (("batch_axis", batch_axis), ("head_axis", head_axis)):
        enforce(ax is None or ax in axes,
                "%s %r is not a mesh axis (mesh has %s)", name, ax,
                sorted(axes))
    if batch_axis is not None:
        enforce(b % axes[batch_axis] == 0,
                "batch %s must divide %s axis size %s", b, batch_axis,
                axes[batch_axis])
    if head_axis is not None:
        enforce(h % axes[head_axis] == 0,
                "heads %s must divide %s axis size %s", h, head_axis,
                axes[head_axis])
        # GQA k/v shard with the same head spec: their (fewer) heads
        # must divide the axis too, or shard_map fails opaquely inside
        enforce(k.shape[2] % axes[head_axis] == 0,
                "kv heads %s must divide %s axis size %s (GQA under "
                "head sharding)", k.shape[2], head_axis, axes[head_axis])
    tk = k.shape[1]  # key-padding masks cover the KEY sequence
    for name, arr, length in (("kv_mask", kv_mask, tk),
                              ("segment_ids", segment_ids, t)):
        if arr is not None:
            enforce(arr.shape == (b, length),
                    "%s must be (batch, %s), got %s",
                    name, length, arr.shape)
    spec = P(batch_axis, None, head_axis, None)
    mspec = P(batch_axis, None)

    def inner(q, k, v, km, seg):
        key = dropout_key
        if key is not None:
            # distinct masks per shard: fold the mesh coordinates in
            for ax in (batch_axis, head_axis):
                if ax is not None:
                    key = jax.random.fold_in(key, lax.axis_index(ax))
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               kv_mask=km, segment_ids=seg, window=window,
                               dropout_p=dropout_p, dropout_key=key)

    return _shard_with_optional(inner, mesh, spec, mspec, q, k, v,
                                kv_mask, segment_ids)
