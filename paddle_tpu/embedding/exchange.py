"""Sparse gradient exchange for ep-sharded tables — (unique ids, rows)
on the wire, never the dense (V, D) gradient.

The reference's trainers push SelectedRows — (row ids, row values)
pairs — to the parameter servers instead of dense table gradients
(reference: framework/selected_rows.h:32, MergeAdd in
operators/math/selected_rows_functor.cc). Under an SPMD ``Plan(ep=N)``
the same traffic shape is hand-written at the JAX level, because GSPMD
left to itself reduces the replicated-table gradient densely — V*D
floats per step for a batch that touched a few thousand rows.

Per step, inside one ``shard_map`` over the plan mesh:

1. **local MergeAdd** — each batch shard dedups its ids and
   segment-sums duplicate rows (``optimizer.sparse.merge_rows``)
   BEFORE anything hits the wire;
2. **int8 wire** — the merged row payload is quantized per-row through
   ``quant.ops.absmax_encode`` (the ``quant/collectives`` wire
   convention: int8 data + f32 scales riding along), all-gathered over
   the batch axis together with the ids; receivers decode to f32.
   Tiny payloads (< ``MIN_COMPRESS_SIZE`` elements, the
   ``quant/collectives`` floor) ride fp32 — scale overhead and noise
   on a toy table buy nothing;
3. **nan-poison** — a non-finite row gradient on ANY shard poisons
   every exchanged row with NaN (4-byte pmin'd finite flag), so the
   train loop's nan-guard keeps firing; a quantizer that laundered inf
   into a finite int8 payload would silently corrupt training;
4. **local scatter** — each ep shard keeps the in-range rows
   (global id - shard offset) and applies them through
   ``optimizer.sparse.apply_rows`` with out-of-bounds drop semantics.
   Update cost stays O(touched rows), flat in vocab.

Byte accounting is host-side per the ``quant/collectives`` convention
(traced code cannot touch counters): shapes are static, so
:func:`exchange_payload_bytes` computes the per-step payload once and
:func:`record_exchange_bytes` advances
``pt_collective_bytes_total{compressed=...}``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.enforce import enforce
from ..core.mesh import get_mesh
from ..optimizer.sparse import apply_rows, find_sparse_embeddings, merge_rows
from ..quant.collectives import MIN_COMPRESS_SIZE, record_payload_bytes
from ..quant.ops import absmax_decode, absmax_encode
from jax import shard_map

PyTree = Any


# ---------------------------------------------------------------------------
# payload-byte accounting (static shapes -> computed once per step fn)
# ---------------------------------------------------------------------------


def exchange_payload_bytes(num_ids: int, dim: int, batch_axis_size: int,
                           *, compressed: bool) -> int:
    """Bytes ONE device moves all-gathering its (ids, rows) payload
    over a ``batch_axis_size`` ring: (n-1) forwarding hops of its own
    chunk — int32 ids + int8 rows + one f32 scale per row when
    compressed, f32 rows otherwise. 0 on a degenerate axis (nothing
    crosses the wire; ep-only plans exchange in-place)."""
    n = int(batch_axis_size)
    if n <= 1:
        return 0
    ids_bytes = int(num_ids) * 4
    if compressed:
        row_bytes = int(num_ids) * (int(dim) + 4)  # int8 rows + f32 scale
    else:
        row_bytes = int(num_ids) * int(dim) * 4
    return (n - 1) * (ids_bytes + row_bytes)


def dense_grad_bytes(vocab: int, dim: int, axis_size: int) -> int:
    """The counterfactual this module exists to avoid: ring-allreducing
    the dense (V, D) fp32 table gradient over ``axis_size`` devices —
    2*(n-1)*ceil(V*D/n)*4 bytes per device per step."""
    n = int(axis_size)
    if n <= 1:
        return 0
    size = int(vocab) * int(dim)
    return 2 * (n - 1) * (-(-size // n)) * 4


def record_exchange_bytes(num_ids: int, dim: int, batch_axis_size: int,
                          *, compressed: bool) -> int:
    """Host-side per-step counter bump on
    ``pt_collective_bytes_total`` (no-op when telemetry is off).
    Returns the bytes recorded."""
    b = exchange_payload_bytes(num_ids, dim, batch_axis_size,
                               compressed=compressed)
    if compressed:
        record_payload_bytes(b, 0)
    else:
        record_payload_bytes(0, b)
    return b


# ---------------------------------------------------------------------------
# the in-shard exchange (call INSIDE a shard_map body)
# ---------------------------------------------------------------------------


def exchange_rows(uids, rows, axis_name: Optional[str], *,
                  compress: bool = True, key=None):
    """All-gather this shard's merged (ids, rows) over ``axis_name`` —
    the SelectedRows wire. Call inside a ``shard_map`` body (like
    ``quant.collectives.quantized_psum``).

    ``uids``: (K,) int ids (out-of-vocab sentinel slots welcome — the
    downstream scatter drops them); ``rows``: (K, D). Returns
    ``(all_ids (n*K,), all_rows (n*K, D) f32)`` identical on every
    device of the axis. ``axis_name=None`` (degenerate batch axis)
    skips the wire but keeps the poison/compress numerics so results
    don't depend on the mesh shape. ``key`` enables stochastic rounding
    of the int8 payload (unbiasedness is per-element; fold a per-device
    key in the caller).
    """
    rows = rows.astype(jnp.float32)
    ok = jnp.isfinite(rows).all().astype(jnp.int32)
    if axis_name is not None:
        ok = lax.pmin(ok, axis_name)
    if compress:
        q, sc = absmax_encode(rows, axis=1, key=key)
        if axis_name is not None:
            q = lax.all_gather(q, axis_name, tiled=True)
            sc = lax.all_gather(sc, axis_name, tiled=True)
        all_rows = absmax_decode(q, sc)
    else:
        all_rows = (lax.all_gather(rows, axis_name, tiled=True)
                    if axis_name is not None else rows)
    all_ids = (lax.all_gather(uids, axis_name, tiled=True)
               if axis_name is not None else uids)
    # non-finite anywhere -> poison every exchanged row (the nan-guard
    # contract shared with quantized_psum)
    all_rows = jnp.where(ok > 0, all_rows, jnp.nan)
    return all_ids, all_rows


# ---------------------------------------------------------------------------
# the sharded sparse update (global-level entry; composes under pjit)
# ---------------------------------------------------------------------------


def _resolve_batch_axis(mesh, batch_axis, leading):
    if batch_axis is not None and batch_axis not in mesh.shape:
        return None
    if batch_axis is not None and leading % int(mesh.shape[batch_axis]):
        return None  # odd batch (eval tail): replicate, still exact
    return batch_axis


def should_compress(ids_size: int, batch_axis_size: int, dim: int,
                    *, min_size: int = MIN_COMPRESS_SIZE) -> bool:
    """The tiny-table fp32 fallback gate (the ``quant/collectives``
    floor applied to the per-shard row payload)."""
    per_shard = -(-int(ids_size) // max(1, int(batch_axis_size)))
    return per_shard * int(dim) >= min_size


def sparse_ep_update(optimizer, table, ids, row_grads, leaf_state,
                     lr, step, *, mesh=None, table_axis: str = "ep",
                     batch_axis: Optional[str] = "dp",
                     compress: Optional[bool] = None, key=None
                     ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One row-sparse update of an ep-sharded ``table`` — local
    MergeAdd, int8 (ids, rows) exchange over the batch axis, per-shard
    scatter through the optimizer's ordinary ``update_leaf`` rule.

    - ``table``: (V, D), placed ``P(table_axis, None)``;
    - ``ids``: any int shape, batch-sharded over ``batch_axis``
      (replicated across ``table_axis``); ``row_grads``:
      ``ids.shape + (D,)``;
    - ``leaf_state``: the table's per-leaf optimizer state
      (``optimizer.init_leaf``) — leaves with a V leading dim are
      treated per-row and must be placed like the table;
    - ``compress=None`` auto-applies the tiny-payload fp32 fallback.

    Returns ``(new_table, new_leaf_state)`` with the same placements.
    The dense (V, D) gradient is never materialized on any device or
    wire.
    """
    mesh = mesh or get_mesh()
    enforce(table_axis in mesh.shape,
            "mesh has no %r axis (axes: %s)", table_axis,
            tuple(mesh.shape))
    n_ep = int(mesh.shape[table_axis])
    V, D = table.shape
    enforce(V % n_ep == 0,
            "vocab %s must divide %s axis size %s (pad the table)", V,
            table_axis, n_ep)
    rows_per_shard = V // n_ep
    batch_axis = _resolve_batch_axis(mesh, batch_axis, ids.shape[0])
    n_b = int(mesh.shape[batch_axis]) if batch_axis else 1
    if compress is None:
        compress = should_compress(ids.size, n_b, D)

    rowwise = {k: (hasattr(v, "ndim") and v.ndim >= 1
                   and v.shape[0] == V)
               for k, v in leaf_state.items()}
    state_specs = {k: P(table_axis, *([None] * (leaf_state[k].ndim - 1)))
                   if rw else P() for k, rw in rowwise.items()}
    ids_spec = P(batch_axis, *([None] * (ids.ndim - 1)))
    rows_spec = P(batch_axis, *([None] * (row_grads.ndim - 1)))

    def body(table_l, state_l, ids_l, rows_l, lr_, step_):
        # 1. local MergeAdd before the wire (fill slots carry id == V:
        #    out of every shard's range, dropped by the scatter)
        uids, merged = merge_rows(ids_l, rows_l, V)
        k = None
        if key is not None:
            k = jax.random.fold_in(key, lax.axis_index(table_axis))
            if batch_axis is not None:
                k = jax.random.fold_in(k, lax.axis_index(batch_axis))
        # 2./3. int8 exchange + nan-poison
        all_ids, all_rows = exchange_rows(uids, merged, batch_axis,
                                          compress=compress, key=k)
        # 4. localize to this shard's row window and scatter-update
        off = lax.axis_index(table_axis) * rows_per_shard
        loc = all_ids - off
        loc = jnp.where((loc >= 0) & (loc < rows_per_shard), loc,
                        rows_per_shard)
        return apply_rows(optimizer, table_l, loc, all_rows, state_l,
                          lr_, step_)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(table_axis, None), state_specs, ids_spec, rows_spec,
                  P(), P()),
        out_specs=(P(table_axis, None), state_specs),
        check_vma=False)
    return fn(table, leaf_state, ids, row_grads,
              jnp.asarray(lr, jnp.float32), jnp.asarray(step))


# ---------------------------------------------------------------------------
# the full train-step builder (the ep counterpart of
# optimizer.sparse.sparse_minimize_fn)
# ---------------------------------------------------------------------------


def sparse_ep_minimize_fn(model, forward_loss: Callable, optimizer, *,
                          plan=None, mesh=None, table_axis: str = "ep",
                          batch_axis: Optional[str] = "dp",
                          emb_optimizer=None,
                          compress: Optional[bool] = None, key=None):
    """Build ``(init_fn, step_fn)`` where every ``is_sparse`` embedding
    table updates through :func:`sparse_ep_update` (sparse exchange over
    the plan mesh) and the dense remainder follows the ordinary
    ``optimizer.apply``. Same contract as
    ``optimizer.sparse.sparse_minimize_fn``::

        state = init_fn(params)
        loss, params, state = compiled(params, state, *batch)

    Compile the step through ``parallel.compile_step(plan, step_fn,
    in_shardings=..., out_shardings=...)`` — the one-compile path; the
    exchange's ``shard_map`` composes inside the pjit trace exactly
    like ``sharded_embedding_lookup`` does in the forward.
    """
    from ..nn.sparse import Capture, Inject

    mesh_ = plan.mesh if plan is not None else (mesh or None)

    embs = find_sparse_embeddings(model)
    enforce(embs, "sparse_ep_minimize_fn: model has no is_sparse "
            "embeddings — use optimizer.minimize_fn / "
            "sparse_minimize_fn instead")
    emb_names = set(embs)
    eopt = emb_optimizer or optimizer
    layer_ids = {id(l) for l in embs.values()}
    by_layer = {id(l): n for n, l in embs.items()}

    def init_fn(params: Dict[str, Any]) -> Dict[str, Any]:
        dense = {k: v for k, v in params.items() if k not in emb_names}
        return {
            "dense": optimizer.init(dense),
            "sparse": {n: eopt.init_leaf(params[n]) for n in emb_names},
        }

    def step_fn(params, state, *args, **kwargs):
        tables = {n: params[n] for n in emb_names}
        dense = {k: v for k, v in params.items() if k not in emb_names}

        # phase 1: capture the ids each sparse layer consumes
        cap = Capture(layer_ids)
        with cap:
            forward_loss(params, *args, **kwargs)
        # phase 2: gather rows OUTSIDE the differentiated function
        rows = {slot: jnp.take(tables[by_layer[owner]], cap.ids[slot],
                               axis=0)
                for slot, owner in cap.owner.items()}

        def inner(dense_p, rows_map):
            inj = Inject(layer_ids, rows_map)
            with inj:
                return forward_loss({**dense_p, **tables}, *args,
                                    **kwargs)

        loss, (g_dense, g_rows) = jax.value_and_grad(
            inner, argnums=(0, 1))(dense, rows)

        step = state["dense"]["step"]
        new_dense, new_dense_state = optimizer.apply(
            dense, g_dense, state["dense"])

        lr = eopt.schedule(step)
        new_sparse_state = {}
        new_tables = dict(tables)
        for name in emb_names:
            slots = [s for s, o in cap.owner.items()
                     if by_layer[o] == name]
            tbl, st = new_tables[name], state["sparse"][name]
            for slot in slots:
                tbl, st = sparse_ep_update(
                    eopt, tbl, cap.ids[slot], g_rows[slot], st, lr,
                    step, mesh=mesh_, table_axis=table_axis,
                    batch_axis=batch_axis, compress=compress, key=key)
            new_tables[name] = tbl
            new_sparse_state[name] = st

        new_params = {**new_dense, **new_tables}
        return loss, new_params, {"dense": new_dense_state,
                                  "sparse": new_sparse_state}

    return init_fn, step_fn
