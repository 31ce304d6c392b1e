"""Geo-async training — the communicator capability (reference:
paddle/fluid/operators/distributed/communicator.h:160 Communicator — a
background thread batching gradient pushes to the parameter server every
``geo_sgd_need_push_nums`` steps, with trainers running on stale local
params between pushes).

TPU-native redesign: no RPC, no parameter server. Each data-parallel
worker holds its OWN param/optimizer replica (leaves stacked along a
leading worker axis, sharded ``P('dp')`` so every replica lives on its
own chips) and trains independently; every ``sync_every`` steps the
replicas synchronize by parameter averaging — one compiler-emitted
``pmean`` over ICI. This is local SGD / federated averaging, the
synchronous-hardware form of the reference's geo mode (push deltas every
K steps, train on stale params in between): communication drops to 1/K
of per-step DP traffic, exactly the reference's bandwidth contract,
without a server round trip.

Use::

    geo = GeoSGDTrainer(trainer, sync_every=16)
    for batch in loader:                 # batch sharded P('dp')
        loss = geo.train_step(batch)     # local step; auto-sync every 16
    geo.sync()                           # flush + write averaged params
                                         # back into the wrapped trainer
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.enforce import enforce
from jax import shard_map


class GeoSGDTrainer:
    """Wrap a ``parallel.Trainer`` with per-worker replicas and K-step
    deferred parameter averaging over ``axis``."""

    def __init__(self, trainer, sync_every: int = 16, axis: str = "dp"):
        enforce(sync_every >= 1, "sync_every must be >= 1, got %s",
                sync_every)
        self.trainer = trainer
        self.sync_every = sync_every
        self.axis = axis
        self.mesh = trainer.mesh
        n = int(self.mesh.shape.get(axis, 0))
        enforce(n >= 1, "mesh has no %r axis", axis)
        self._n = n
        self._since_sync = 0

        def stack(tree):
            def put(x):
                y = jnp.broadcast_to(x[None], (n,) + x.shape)
                spec = P(axis, *([None] * x.ndim))
                return jax.device_put(y, NamedSharding(self.mesh, spec))

            return jax.tree_util.tree_map(put, tree)

        # per-worker replicas (the reference's per-trainer stale params)
        self._params = stack(trainer.params)
        self._buffers = stack(trainer.buffers)
        self._opt_state = stack(trainer.opt_state)
        self._jit_local = None
        self._jit_avg = None

    # -- jitted pieces ------------------------------------------------------

    def _specs(self, stacked):
        return jax.tree_util.tree_map(
            lambda x: P(self.axis, *([None] * (x.ndim - 1))), stacked)

    def _build(self, batch):
        tr, axis = self.trainer, self.axis

        def local(params, buffers, opt_state, rng, batch):
            """One UNSYNCED step per worker: inside shard_map over dp,
            each shard squeezes its replica and updates it with its own
            local batch — no cross-worker gradient traffic."""
            def inner(p, b, s, rng, bt):
                # state replicas carry a size-1 stacked dim per shard —
                # squeeze them; the batch shard does NOT (its leading dim
                # is this worker's B/n samples, all of which train)
                one = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
                p, b, s = one(p), one(b), one(s)
                sub = jax.random.fold_in(rng, lax.axis_index(axis))
                loss, _m, p, b, s = tr._step(p, b, s, sub, bt)
                ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
                return loss[None], ex(p), ex(b), ex(s)

            pspec, bspec, sspec = (self._specs(params),
                                   self._specs(buffers),
                                   self._specs(opt_state))
            batch_spec = jax.tree_util.tree_map(lambda _: P(axis), batch)
            return shard_map(
                inner, mesh=self.mesh,
                in_specs=(pspec, bspec, sspec, P(), batch_spec),
                out_specs=(P(axis), pspec, bspec, sspec),
                check_vma=False)(params, buffers, opt_state, rng, batch)

        def avg(params):
            """The geo sync: average replicas over dp (one ICI
            all-reduce — the batched-push replacement)."""
            def inner(p):
                return jax.tree_util.tree_map(
                    lambda x: lax.pmean(x, axis), p)

            spec = self._specs(params)
            return shard_map(inner, mesh=self.mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False)(params)

        self._jit_local = jax.jit(local)
        self._jit_avg = jax.jit(avg)

    # -- driver -------------------------------------------------------------

    def train_step(self, batch) -> Tuple[Any, dict]:
        """One local step per worker; every ``sync_every``-th call
        averages the replicas (the geo push/pull). Returns the mean of
        the per-worker losses."""
        if self._jit_local is None:
            self._build(batch)
        tr = self.trainer
        tr._rng, sub = jax.random.split(tr._rng)
        losses, self._params, self._buffers, self._opt_state = \
            self._jit_local(self._params, self._buffers, self._opt_state,
                            sub, batch)
        self._since_sync += 1
        if self._since_sync >= self.sync_every:
            self._params = self._jit_avg(self._params)
            self._since_sync = 0
        return jnp.mean(losses), {}

    def sync(self) -> None:
        """Flush: average now and write the consensus params, buffers,
        AND optimizer state back into the wrapped trainer so eval/resume
        see trained running stats and moments (reference: Communicator
        flush on barrier/save)."""
        if self._jit_avg is None and self._jit_local is None:
            return
        self._params = self._jit_avg(self._params)
        self._buffers = self._jit_avg(self._buffers)
        self._opt_state = self._jit_avg(self._opt_state)
        self._since_sync = 0
        rep = NamedSharding(self.mesh, P())
        unstack = lambda t: jax.tree_util.tree_map(
            lambda x: jax.device_put(x[0], rep), t)
        self.trainer.params = unstack(self._params)
        self.trainer.buffers = unstack(self._buffers)
        self.trainer.opt_state = unstack(self._opt_state)

    @property
    def divergence(self):
        """Max abs spread across replicas (0 right after a sync) — a
        staleness observability hook."""
        def spread(x):
            return jnp.max(jnp.abs(x - jnp.mean(x, axis=0, keepdims=True)))

        leaves = [spread(x) for x in
                  jax.tree_util.tree_leaves(self._params)]
        return jnp.max(jnp.stack(leaves))
