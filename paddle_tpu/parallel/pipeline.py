"""Pipeline parallelism — stage-partitioned microbatch pipeline over 'pp'.

Green-field design (the reference has no pipeline parallelism at all,
SURVEY.md §2.5/§7: its only model-parallel-adjacent feature is PS-sharded
optimizer state, reference: transpiler/distribute_transpiler.py:702).

TPU-native shape: the repeated block's parameters are **stacked** along a
leading layer axis and sharded ``P('pp')`` so each pipeline stage holds a
contiguous chunk of layers in its HBM. One ``shard_map`` + ``lax.scan``
runs the classic GPipe schedule: at tick ``t`` every stage applies its
layers to the activation it holds, then the activations rotate one stage
forward via ``lax.ppermute`` (a single ICI hop — pipeline traffic never
leaves neighbouring chips). Stage 0 injects microbatch ``t``; the last
stage banks its result. ``n + m - 1`` ticks stream ``m`` microbatches
through ``n`` stages (bubble fraction ``(n-1)/(n+m-1)``).

Backward is pure autodiff: the transpose of ``ppermute`` is the reverse
rotation, so the gradient pipeline runs automatically in the opposite
direction — no hand-written 1F1B engine. Each stage application is wrapped
in ``jax.checkpoint`` so the backward recomputes block activations instead
of storing every tick's intermediates.

Two schedules (``schedule=`` on :func:`pipeline_apply`):

- ``"gpipe"`` — each device holds ONE contiguous chunk of ``L/n`` layers;
  ``n + m - 1`` ticks, bubble ``(n-1)/(n+m-1)``.
- ``"interleaved"`` — the Megatron-style virtual-stage schedule: each
  device holds ``v`` round-robin chunks of ``L/(n*v)`` layers (device
  ``d`` owns chunks ``d, n+d, 2n+d, …``) and microbatches circulate the
  ring ``v`` times, injected in bursts of ``n``. A tick now costs
  ``1/v`` of a GPipe tick, so the pipe fills/drains ``v×`` faster:
  bubble ``(n-1)/(m*v + n - 1)`` (for ``n | m``) vs GPipe's
  ``(n-1)/(m+n-1)`` — e.g. 16% vs 27% at n=4, m=8, v=2. The backward
  pipeline inherits the same interleaving through autodiff. Cost: ``v×``
  more ppermute hops of the same total activation traffic, still
  neighbour-only ICI.

Constraints (standard for this schedule): every block maps activations of
one uniform shape to the same shape (transformer blocks qualify); the
stacked layer count must divide ``n * virtual_stages``; microbatches all
share one shape.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.enforce import enforce
from ..core.mesh import get_mesh
from jax import shard_map


def _stack_to_stages(stacked_params, n_stages: int):
    """(L, ...) leaves → (n_stages, L//n_stages, ...)."""

    def reshape(leaf):
        L = leaf.shape[0]
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree_util.tree_map(reshape, stacked_params)


def _interleave_to_stages(stacked_params, n: int, v: int):
    """LOGICAL-order (L, ...) leaves → (n, v, L/(n*v), ...): device ``d``
    slot ``j`` holds chunk ``j*n + d`` — the round-robin layout the
    interleaved schedule walks (a microbatch's j-th ring pass applies
    chunks ``j*n .. j*n + n - 1`` in device order).

    NOTE: on a 'pp'-sharded stack this transpose is a cross-device
    RESHARD (XLA lowers it to all-to-alls of every weight, each step).
    Persistent training state should store the stack in RING ORDER
    (:func:`ring_order_layers`) and pass ``layers_in_ring_order=True`` so
    the per-step reshape stays device-local."""

    def reshape(leaf):
        L = leaf.shape[0]
        k = L // (n * v)
        a = leaf.reshape(v, n, k, *leaf.shape[1:])
        return jnp.swapaxes(a, 0, 1)

    return jax.tree_util.tree_map(reshape, stacked_params)


def _ring_to_stages(stacked_params, n: int, v: int):
    """RING-order (L, ...) leaves → (n, v, k, ...) by pure local reshape
    (ring order stores device d's chunks contiguously: rows
    [d*v*k, (d+1)*v*k) are chunks d, n+d, 2n+d, …)."""

    def reshape(leaf):
        L = leaf.shape[0]
        k = L // (n * v)
        return leaf.reshape(n, v, k, *leaf.shape[1:])

    return jax.tree_util.tree_map(reshape, stacked_params)


def ring_order_layers(stacked_params, n: int, v: int, *,
                      inverse: bool = False):
    """Permute a stacked (L, ...) pytree between LOGICAL layer order and
    the interleaved schedule's RING order (device-contiguous round-robin
    chunks). Apply once at parameter-placement time so each training
    step's stage reshape is local — leaving the stack logical would
    all-to-all every weight on every step. ``inverse=True`` maps ring
    order back to logical (the sequential-oracle path)."""

    def perm(leaf):
        L = leaf.shape[0]
        k = L // (n * v)
        if inverse:  # ring (n, v, k) layout -> logical (v, n, k)
            a = leaf.reshape(n, v, k, *leaf.shape[1:])
        else:        # logical (v, n, k) layout -> ring (n, v, k)
            a = leaf.reshape(v, n, k, *leaf.shape[1:])
        return jnp.swapaxes(a, 0, 1).reshape(L, *leaf.shape[1:])

    return jax.tree_util.tree_map(perm, stacked_params)


def gpipe_ticks(n: int, m: int) -> int:
    """GPipe schedule length in ticks (one tick = one L/n-layer stage)."""
    return n + m - 1


def interleaved_ticks(n: int, m: int, v: int) -> int:
    """Interleaved schedule length in ticks (one tick = one L/(n*v)-layer
    chunk — i.e. 1/v of a GPipe tick). Microbatches are injected in
    bursts of n; burst b starts at tick b*v*n."""
    bursts = -(-m // n)
    o_last = (m - 1) - (bursts - 1) * n
    return (bursts - 1) * v * n + o_last + (v - 1) * n + n


def bubble_fraction(n: int, m: int, schedule: str = "gpipe",
                    virtual_stages: int = 1) -> float:
    """Idle fraction of each device's timeline under the schedule —
    the quantity the interleaved schedule exists to shrink."""
    enforce(schedule in ("gpipe", "interleaved"),
            "schedule must be 'gpipe' or 'interleaved', got %r", schedule)
    if schedule == "interleaved":
        t = interleaved_ticks(n, m, virtual_stages)
        return 1.0 - (m * virtual_stages) / t
    enforce(virtual_stages == 1,
            "gpipe schedule has no virtual stages (got %s)", virtual_stages)
    return 1.0 - m / gpipe_ticks(n, m)


def _aux_block_step(block_fn):
    """Scan body applying one aux-carrying block: the SINGLE definition
    of the aux accumulation (f32, summed over layers) shared by both
    schedule inners and the sequential folds — the microbatch-mean aux
    contract must not be able to diverge between the pipelined paths and
    their loss-match oracles."""
    def one_block(carry, p):
        h, a = carry
        h, al = block_fn(p, h)
        return (h, a + al.astype(jnp.float32)), None

    return one_block


def microbatched_aux_fold(block_fn, stacked_params, x, *,
                          num_microbatches, aux_size, remat=True):
    """Sequential per-MICROBATCH fold of an aux-carrying block stack:
    ``(out (B, ...), aux_mean (aux_size,))`` with aux summed over layers
    per microbatch and averaged over microbatches — numerically the same
    definition every pipelined schedule computes (MoE routing state is
    microbatch-local, so a full-batch fold would differ). Used by the
    n == 1 pipeline short-circuit AND by sequential loss-match oracles
    (parallel/hybrid.py)."""
    body = _aux_block_step(block_fn)
    if remat:
        body = jax.checkpoint(body)
    B, m = x.shape[0], num_microbatches
    x_mb = x.reshape(m, B // m, *x.shape[1:])

    def per_mb(_, mb):
        out = lax.scan(body, (mb, jnp.zeros((aux_size,), jnp.float32)),
                       stacked_params)[0]
        return None, out

    _, (h_mb, a_mb) = lax.scan(per_mb, None, x_mb)
    return h_mb.reshape(B, *h_mb.shape[2:]), jnp.mean(a_mb, axis=0)


def _pipeline_inner(params_nk, x_mb, *, block_fn, axis, n, m, remat,
                    aux_size=0):
    # params_nk leaves: (1, k, ...) — this stage's chunk; squeeze the shard dim
    p_local = jax.tree_util.tree_map(lambda a: a[0], params_nk)
    idx = lax.axis_index(axis)
    has_aux = aux_size > 0
    # x_mb: (m, mb, ...) replicated — stage 0 reads, others ignore

    if has_aux:
        # aux contract: block_fn(p, h) -> (h, aux (A,)); each
        # microbatch's aux vector RIDES THE RING with its activation,
        # summed over the layers it passes through, and is banked by the
        # last stage next to the output (MoE load-balance/z losses —
        # VERDICT r4 #4)
        def stage_fn(p_k, h, a):
            return lax.scan(_aux_block_step(block_fn), (h, a), p_k)[0]
    else:
        def stage_fn(p_k, h):
            def one_block(h, p):
                return block_fn(p, h), None

            return lax.scan(one_block, h, p_k)[0]

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    mb_shape = x_mb.shape[1:]
    fwd_perm = [(i, i + 1) for i in range(n - 1)]

    def tick(carry, t):
        if has_aux:
            state, aux_state, outbuf, auxbuf = carry
        else:
            state, outbuf = carry
        # stage 0 injects microbatch t (clipped: past-the-end ticks feed
        # a dummy that never reaches the output window)
        mb = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, m - 1), 0,
                                      keepdims=False)
        inp = jnp.where(idx == 0, mb, state)
        if has_aux:
            a_in = jnp.where(idx == 0, jnp.zeros_like(aux_state),
                             aux_state)
            out, a_out = stage_fn(p_local, inp, a_in)
        else:
            out = stage_fn(p_local, inp)
        # last stage banks microbatch t-(n-1) once the pipe is full
        pos = t - (n - 1)
        write = jnp.logical_and(idx == n - 1, pos >= 0)
        upd = lax.dynamic_update_index_in_dim(
            outbuf, out.astype(outbuf.dtype), jnp.clip(pos, 0, m - 1), 0)
        outbuf = jnp.where(write, upd, outbuf)
        if has_aux:
            aupd = lax.dynamic_update_index_in_dim(
                auxbuf, a_out, jnp.clip(pos, 0, m - 1), 0)
            auxbuf = jnp.where(write, aupd, auxbuf)
        if n > 1:
            state = lax.ppermute(out, axis, fwd_perm)
            if has_aux:
                aux_state = lax.ppermute(a_out, axis, fwd_perm)
        else:
            state = out
            if has_aux:
                aux_state = a_out
        carry = ((state, aux_state, outbuf, auxbuf) if has_aux
                 else (state, outbuf))
        return carry, None

    state0 = jnp.zeros(mb_shape, x_mb.dtype)
    outbuf0 = jnp.zeros((m,) + mb_shape, jnp.result_type(x_mb.dtype))
    init = ((state0, jnp.zeros((aux_size,), jnp.float32), outbuf0,
             jnp.zeros((m, aux_size), jnp.float32)) if has_aux
            else (state0, outbuf0))
    carry, _ = lax.scan(tick, init, jnp.arange(n + m - 1))
    # only the last stage's buffer is real; mask+psum broadcasts it so the
    # result is replicated over 'pp' (loss/optimizer run identically on all
    # stages — the XLA partitioner then dedups what it can). n == 1 never
    # reaches here: pipeline_apply short-circuits to a sequential fold
    if has_aux:
        _, _, outbuf, auxbuf = carry
        outbuf = jnp.where(idx == n - 1, outbuf, jnp.zeros_like(outbuf))
        auxbuf = jnp.where(idx == n - 1, auxbuf, jnp.zeros_like(auxbuf))
        return lax.psum(outbuf, axis), lax.psum(auxbuf, axis)
    _, outbuf = carry
    outbuf = jnp.where(idx == n - 1, outbuf, jnp.zeros_like(outbuf))
    return lax.psum(outbuf, axis)


def _interleaved_inner(params_nvk, x_mb, *, block_fn, axis, n, m, v,
                       remat, aux_size=0):
    """One device's lockstep loop of the interleaved schedule.

    Tick arithmetic (s = t - device_index ≥ 0 inside the busy window):
    burst b = s // (v*n), r = s % (v*n), ring pass j = r // n, burst
    offset o = r % n, microbatch = b*n + o. Device d applies chunk
    j*n + d (local slot j) to the activation the ring just delivered;
    stage 0 overrides with a fresh injection when j == 0, the last stage
    banks after its j == v-1 application. The full ring permutation
    (n-1 → 0 wrap) carries activations into their next pass. With
    ``aux_size``, each microbatch's (A,) aux vector travels the full
    v-pass ring journey with its activation (see _pipeline_inner)."""
    p_local = jax.tree_util.tree_map(lambda a: a[0], params_nvk)  # (v,k,...)
    idx = lax.axis_index(axis)
    has_aux = aux_size > 0

    if has_aux:
        def chunk_fn(p_vk, j, h, a):
            p_k = jax.tree_util.tree_map(
                lambda arr: lax.dynamic_index_in_dim(arr, j, 0,
                                                     keepdims=False),
                p_vk)
            return lax.scan(_aux_block_step(block_fn), (h, a), p_k)[0]
    else:
        def chunk_fn(p_vk, j, h):
            p_k = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, j, 0,
                                                   keepdims=False),
                p_vk)

            def one_block(h, p):
                return block_fn(p, h), None

            return lax.scan(one_block, h, p_k)[0]

    if remat:
        chunk_fn = jax.checkpoint(chunk_fn)

    mb_shape = x_mb.shape[1:]
    perm = [(i, (i + 1) % n) for i in range(n)]  # full ring: passes wrap

    def tick(carry, t):
        if has_aux:
            state, aux_state, outbuf, auxbuf = carry
        else:
            state, outbuf = carry
        s = jnp.maximum(t - idx, 0)  # pre-window ticks compute garbage
        r = s % (v * n)
        j = r // n
        mb = (s // (v * n)) * n + r % n
        inj = lax.dynamic_index_in_dim(x_mb, jnp.clip(mb, 0, m - 1), 0,
                                       keepdims=False)
        fresh = jnp.logical_and(idx == 0, j == 0)
        inp = jnp.where(fresh, inj, state)
        if has_aux:
            a_in = jnp.where(fresh, jnp.zeros_like(aux_state), aux_state)
            out, a_out = chunk_fn(p_local, j, inp, a_in)
        else:
            out = chunk_fn(p_local, j, inp)
        write = jnp.logical_and(
            jnp.logical_and(idx == n - 1, j == v - 1),
            jnp.logical_and(mb < m, t >= idx))
        upd = lax.dynamic_update_index_in_dim(
            outbuf, out.astype(outbuf.dtype), jnp.clip(mb, 0, m - 1), 0)
        outbuf = jnp.where(write, upd, outbuf)
        if has_aux:
            aupd = lax.dynamic_update_index_in_dim(
                auxbuf, a_out, jnp.clip(mb, 0, m - 1), 0)
            auxbuf = jnp.where(write, aupd, auxbuf)
        state = lax.ppermute(out, axis, perm) if n > 1 else out
        if has_aux:
            aux_state = (lax.ppermute(a_out, axis, perm) if n > 1
                         else a_out)
            return (state, aux_state, outbuf, auxbuf), None
        return (state, outbuf), None

    state0 = jnp.zeros(mb_shape, x_mb.dtype)
    outbuf0 = jnp.zeros((m,) + mb_shape, jnp.result_type(x_mb.dtype))
    T = interleaved_ticks(n, m, v)
    init = ((state0, jnp.zeros((aux_size,), jnp.float32), outbuf0,
             jnp.zeros((m, aux_size), jnp.float32)) if has_aux
            else (state0, outbuf0))
    carry, _ = lax.scan(tick, init, jnp.arange(T))
    # n == 1 never reaches here (pipeline_apply short-circuits)
    if has_aux:
        _, _, outbuf, auxbuf = carry
        outbuf = jnp.where(idx == n - 1, outbuf, jnp.zeros_like(outbuf))
        auxbuf = jnp.where(idx == n - 1, auxbuf, jnp.zeros_like(auxbuf))
        return lax.psum(outbuf, axis), lax.psum(auxbuf, axis)
    _, outbuf = carry
    outbuf = jnp.where(idx == n - 1, outbuf, jnp.zeros_like(outbuf))
    return lax.psum(outbuf, axis)


def pipeline_apply(block_fn: Callable, stacked_params, x, *,
                   num_microbatches: int, axis: str = "pp",
                   mesh=None, remat: bool = True,
                   schedule: str = "gpipe", virtual_stages: int = 1,
                   layers_in_ring_order: bool = False,
                   aux_size: int = 0):
    """Run ``x`` through ``L`` stacked layers as an ``n``-stage pipeline.

    - ``block_fn(params_l, h) -> h``: applies ONE layer (uniform shape).
    - ``stacked_params``: pytree whose leaves have leading dim ``L``
      (``L % n == 0``); stage ``s`` gets layers ``[s*L/n, (s+1)*L/n)``.
    - ``x``: global batch ``(B, ...)`` with ``B % num_microbatches == 0``.
    - ``schedule``: ``"gpipe"`` (contiguous chunks) or ``"interleaved"``
      (``virtual_stages`` round-robin chunks per device — lower bubble,
      see module docstring; requires ``L % (n * virtual_stages) == 0``).
    - ``layers_in_ring_order``: the stacked leaves were pre-permuted with
      :func:`ring_order_layers` (persistent 'pp'-sharded training state
      should be — the per-step stage split is then a LOCAL reshape;
      logical-order sharded stacks pay a weight all-to-all per step).
    - ``aux_size``: when > 0 the block contract widens to
      ``block_fn(params_l, h) -> (h, aux)`` with ``aux`` a float32
      ``(aux_size,)`` vector per layer (MoE load-balance/router-z losses
      — VERDICT r4 #4). Each microbatch's aux rides the pipeline ring
      with its activation, summed over all ``L`` layers, and the return
      becomes ``(out, aux_mean)`` where ``aux_mean`` is the
      MICROBATCH-MEAN of the per-microbatch layer sums — the pipelined
      aux definition (each microbatch routes independently, so a
      full-batch aux would not be computable without materializing every
      microbatch's router state).

    Returns the pipelined equivalent of folding ``block_fn`` over all ``L``
    layers, replicated over the 'pp' axis.
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    m = num_microbatches
    enforce(schedule in ("gpipe", "interleaved"),
            "schedule must be 'gpipe' or 'interleaved', got %r", schedule)
    v = int(virtual_stages)
    enforce(v >= 1, "virtual_stages must be >= 1, got %s", v)
    if schedule == "gpipe":
        enforce(v == 1, "gpipe schedule has no virtual stages; use "
                "schedule='interleaved' with virtual_stages=%s", v)
    leaves = jax.tree_util.tree_leaves(stacked_params)
    enforce(leaves, "stacked_params must be a non-empty pytree")
    L = leaves[0].shape[0]
    enforce(all(l.shape[0] == L for l in leaves),
            "all stacked_params leaves must share leading layer dim %s", L)
    enforce(L % (n * v) == 0,
            "layer count %s must divide pp size x virtual stages (%s x %s)",
            L, n, v)
    B = x.shape[0]
    enforce(B % m == 0,
            "num_microbatches %s must divide batch size %s", m, B)
    enforce(not layers_in_ring_order
            or (schedule == "interleaved" and v > 1),
            "layers_in_ring_order only applies to the interleaved "
            "schedule with virtual_stages > 1")
    if n == 1:
        # a 1-stage pipeline IS the sequential fold; skip the shard_map
        # entirely — the degenerate manual region would still wrap every
        # auto dp/tp collective in a size-1 manual subgroup, which the
        # SPMD partitioner rejects in MULTI-PROCESS compiles (seen with
        # the dcn_dp x dp x tp hybrid mesh, pp = 1)
        fold_params = (ring_order_layers(stacked_params, n, v,
                                         inverse=True)
                       if layers_in_ring_order else stacked_params)

        if aux_size > 0:
            # the pipelined aux is per-MICROBATCH (routing state is
            # microbatch-local); the degenerate fold must microbatch
            # identically, or its MoE capacity/queues — and therefore
            # its loss — would differ from every n > 1 configuration
            h, aux = microbatched_aux_fold(
                block_fn, fold_params, x, num_microbatches=m,
                aux_size=aux_size, remat=remat)
            return h.astype(jnp.result_type(x.dtype)), aux

        def fold(h, p_l):
            return block_fn(p_l, h), None

        body = jax.checkpoint(fold) if remat else fold
        # match the pipelined path's output dtype contract (outbuf is
        # result_type(x.dtype) there, whatever block_fn returns)
        return lax.scan(body, x, fold_params)[0].astype(
            jnp.result_type(x.dtype))
    x_mb = x.reshape(m, B // m, *x.shape[1:])

    if schedule == "interleaved" and v > 1:
        params_staged = (_ring_to_stages(stacked_params, n, v)
                         if layers_in_ring_order
                         else _interleave_to_stages(stacked_params, n, v))
    else:
        params_staged = _stack_to_stages(stacked_params, n)
    # jit is required: remat's closed_call can't evaluate eagerly inside
    # shard_map (and the production path is jitted anyway — no-op there).
    # Cached by configuration so eager per-step callers hit the XLA compile
    # cache instead of retracing a fresh closure every call.
    fn = _jitted_pipeline(block_fn, mesh, axis, n, m, remat, schedule, v,
                          aux_size)
    if aux_size > 0:
        out_mb, aux_mb = fn(params_staged, x_mb)
        return (out_mb.reshape(B, *out_mb.shape[2:]),
                jnp.mean(aux_mb, axis=0))
    out_mb = fn(params_staged, x_mb)
    return out_mb.reshape(B, *out_mb.shape[2:])


@functools.lru_cache(maxsize=64)
def _jitted_pipeline(block_fn, mesh, axis, n, m, remat, schedule="gpipe",
                     v=1, aux_size=0):
    if schedule == "interleaved" and v > 1:
        inner = functools.partial(_interleaved_inner, block_fn=block_fn,
                                  axis=axis, n=n, m=m, v=v, remat=remat,
                                  aux_size=aux_size)
    else:
        inner = functools.partial(_pipeline_inner, block_fn=block_fn,
                                  axis=axis, n=n, m=m, remat=remat,
                                  aux_size=aux_size)
    out_specs = (P(), P()) if aux_size > 0 else P()

    def wrapper(params_staged, x_mb):
        # specs are shape-independent, built from the pytree at trace time
        stage_spec = jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), params_staged)
        # manual ONLY over the pipeline axis: every other mesh axis stays
        # auto, so dp batch sharding and tp weight sharding compose with
        # the pipeline in ONE module (GSPMD inserts their collectives
        # around the manual ppermute ring)
        return shard_map(inner, mesh=mesh,
                         in_specs=(stage_spec, P()),
                         out_specs=out_specs,
                         axis_names=frozenset({axis}),
                         check_vma=False)(params_staged, x_mb)

    return jax.jit(wrapper)


def stage_param_sharding(stacked_params, n_stages: int, axis: str = "pp",
                         mesh=None):
    """NamedShardings that place each stage's layer-chunk on its device —
    apply with jax.device_put to hold only 1/n of the layers per chip."""
    mesh = mesh or get_mesh()

    def spec(leaf):
        return NamedSharding(mesh, P(axis, *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(spec, _stack_to_stages(stacked_params,
                                                         n_stages))


class GPipe:
    """Layer-level convenience: pipeline a uniform stack of blocks.

    ``blocks`` must be structurally identical Layers (same param pytree);
    their params are stacked along a new leading axis and fed to
    :func:`pipeline_apply`.
    """

    def __init__(self, blocks, *, num_microbatches: int, axis: str = "pp",
                 mesh=None, remat: bool = True, schedule: str = "gpipe",
                 virtual_stages: int = 1):
        enforce(len(blocks) > 0, "GPipe needs at least one block")
        self.blocks = list(blocks)
        self.num_microbatches = num_microbatches
        self.axis = axis
        self.mesh = mesh
        self.remat = remat
        self.schedule = schedule
        self.virtual_stages = virtual_stages
        self._template = self.blocks[0]

        # one stable closure for the pipeline compile cache (a fresh
        # closure per __call__ would defeat _jitted_pipeline's lru_cache)
        # Under a mixed policy the call casts the block's declared leaves
        # in the loop's body, once a call (nn.Layer._cast_once). Casting
        # the stack before the loop would be WRONG: a block runs once a
        # microbatch, and its weight's cotangents would meet in the
        # compute type where they now meet in the stored one.
        def _block_fn(p, h, _t=self._template):
            out, _ = _t.functional_call(p, h)
            return out

        self._block_fn = _block_fn

    def stacked_params(self):
        from ..nn.layer import stacked_parameters

        return stacked_parameters(self.blocks)

    def __call__(self, x, stacked_params=None):
        params = (self.stacked_params() if stacked_params is None
                  else stacked_params)
        return pipeline_apply(self._block_fn, params, x,
                              num_microbatches=self.num_microbatches,
                              axis=self.axis, mesh=self.mesh,
                              remat=self.remat, schedule=self.schedule,
                              virtual_stages=self.virtual_stages)
