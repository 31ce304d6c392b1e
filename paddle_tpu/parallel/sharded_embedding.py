"""Sharded embedding tables — the TPU-native successor of the reference's
parameter-server sparse tables.

Capability lineage (SURVEY.md §2.5): the reference shards giant embedding
tables across parameter servers and prefetches rows over RPC
(reference: operators/distributed/parameter_prefetch.cc,
transpiler/distribute_lookup_table.py, framework/fleet/fleet_wrapper.h:55
PullSparseVarsSync) with SelectedRows sparse gradients
(reference: framework/selected_rows.h:32). On TPU the table is a dense
array row-sharded over a mesh axis ('ep'); lookup is a *local* gather of
the in-shard rows plus one ``psum`` over the axis (XLA lowers it onto the
ICI ring), and the "sparse gradient" is the transpose — a local
scatter-add into each shard — handled entirely by autodiff. No RPC, no
row cache, no id-dedup protocol.

Memory: each chip holds V/ep rows. Compute: every chip gathers B ids
against its shard (out-of-shard rows contribute zeros) — bandwidth-bound
on the (B, D) psum, the standard SPMD embedding recipe.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.enforce import InvalidArgumentError, enforce
from ..core.mesh import get_mesh
from ..nn.layer import Layer
from .. import initializer as I
from jax import shard_map


def _lookup_inner(ids, table, *, axis, rows_per_shard):
    idx = lax.axis_index(axis)
    offset = idx * rows_per_shard
    local = ids - offset
    valid = (local >= 0) & (local < rows_per_shard)
    safe = jnp.clip(local, 0, rows_per_shard - 1)
    rows = jnp.take(table, safe, axis=0)
    rows = jnp.where(valid[..., None], rows, 0)
    return lax.psum(rows, axis)


def _check_ids_in_vocab(ids, vocab: int,
                        padding_idx: Optional[int] = None) -> None:
    """Typed out-of-vocab enforcement on CONCRETE ids (eager calls and
    the op-construction path). An id outside [0, V) used to psum to a
    silent all-zeros row — indistinguishable from a real zero embedding
    and the classic off-by-one-vocab data bug; now it raises
    :class:`..core.enforce.InvalidArgumentError`. ``padding_idx`` ids
    are exempt (an out-of-range pad like -1 is a legitimate
    convention). Traced ids (inside jit/pjit, shapes only) skip the
    check — the in-shard mask still yields zeros there, and the data
    pipeline owns validation."""
    if isinstance(ids, jax.core.Tracer) or getattr(ids, "size", 0) == 0:
        return
    import numpy as np

    # host-side numpy on the concrete ids: jnp ops here would STAGE
    # under an enclosing jit trace (constants become tracers) and the
    # int() coercion would blow up mid-trace
    check = np.asarray(ids)
    if padding_idx is not None:
        check = np.where(check == padding_idx, 0, check)
    lo, hi = int(check.min()), int(check.max())
    if lo < 0 or hi >= vocab:
        raise InvalidArgumentError(
            f"embedding ids span [{lo}, {hi}] but the table has "
            f"{vocab} rows — out-of-vocab ids are an error, not a "
            f"clip (hash or bucket ids upstream, or grow the table)")


def sharded_embedding_lookup(ids, table, *, axis: str = "ep",
                             batch_axis: Optional[str] = "dp", mesh=None,
                             padding_idx: Optional[int] = None):
    """Gather rows of a globally (V, D) table row-sharded over ``axis``.

    ``ids``: any int shape, batch-sharded over ``batch_axis`` (or
    replicated with ``batch_axis=None``). Returns ids.shape + (D,).
    ``padding_idx`` rows come back as exact zeros; concrete
    out-of-vocab ids raise :class:`..core.enforce.InvalidArgumentError`
    (see :func:`_check_ids_in_vocab`).
    """
    mesh = mesh or get_mesh()
    enforce(axis in mesh.shape, "mesh has no %r axis (axes: %s)", axis,
            tuple(mesh.shape))
    n = mesh.shape[axis]
    V, D = table.shape
    enforce(V % n == 0,
            "vocab %s must divide %s axis size %s (pad the table)", V, axis, n)
    _check_ids_in_vocab(ids, V, padding_idx)
    if batch_axis is not None and batch_axis not in mesh.shape:
        batch_axis = None  # user mesh without a batch axis: replicate ids
    if batch_axis is not None and ids.shape[0] % mesh.shape[batch_axis]:
        batch_axis = None  # odd batch (e.g. eval tail): replicate, still exact
    ids_spec = P(batch_axis, *([None] * (ids.ndim - 1)))
    inner = functools.partial(_lookup_inner, axis=axis,
                              rows_per_shard=V // n)
    fn = shard_map(inner, mesh=mesh,
                   in_specs=(ids_spec, P(axis, None)),
                   out_specs=P(batch_axis, *([None] * ids.ndim)),
                   check_vma=False)
    out = fn(ids, table)
    if padding_idx is not None:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return out


class ShardedEmbedding(Layer):
    """Embedding whose table is row-sharded over a mesh axis ('ep').

    Drop-in for :class:`paddle_tpu.nn.Embedding` at vocab sizes that don't
    fit one chip's HBM — the PSLib/Downpour giant-table capability
    (reference: distributed/downpour.py:24) without a parameter server.
    """

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 axis: str = "ep", padding_idx: Optional[int] = None,
                 weight_init=None, dtype=None, mesh=None,
                 batch_axis: Optional[str] = "dp",
                 is_sparse: bool = False):
        super().__init__()
        self.axis = axis
        self.batch_axis = batch_axis
        self.padding_idx = padding_idx
        self._mesh = mesh
        # row-sparse gradient updates (see nn.Embedding.is_sparse): the
        # sparse step's scatter composes with the P(axis, None) placement
        # — GSPMD routes each unique row's update to its owning shard
        self.is_sparse = is_sparse
        self.create_parameter("weight", (num_embeddings, embedding_dim),
                              dtype, weight_init or I.XavierNormal())

    def weight_sharding(self, mesh=None) -> NamedSharding:
        """Row-sharded placement — device_put the weight with this (and use
        it as the param's sharding rule in the trainer)."""
        return NamedSharding(self._mesh or mesh or get_mesh(),
                             P(self.axis, None))

    def forward(self, ids):
        from ..nn.sparse import Capture, Inject, active

        ctx = active()
        if ctx is not None and ctx.handles(self):
            if isinstance(ctx, Capture):
                ctx.record(self, ids)
            else:
                assert isinstance(ctx, Inject)
                rows = ctx.pop(self)
                if self.padding_idx is not None:
                    rows = jnp.where((ids == self.padding_idx)[..., None],
                                     0.0, rows)
                return rows
        return sharded_embedding_lookup(
            ids, self.weight, axis=self.axis, mesh=self._mesh,
            batch_axis=self.batch_axis, padding_idx=self.padding_idx)


def embedding_ep_rules(model: Layer, axis: str = "ep"):
    """Sharding rules placing every ShardedEmbedding table in ``model`` on
    the ep axis (compose with transformer_tp_rules/zero_dp_rules in the
    trainer)."""
    import re

    rules = []
    for name, sub in model.named_sublayers():
        if isinstance(sub, ShardedEmbedding):
            rules.append((re.escape(f"{name}.weight") + "$", P(axis, None)))
    return rules
