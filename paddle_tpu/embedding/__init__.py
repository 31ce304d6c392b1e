"""Sharded embedding plane — giant tables on the Plan substrate.

The reference Fluid's signature production capability is distributed
sparse embedding over parameter servers (PAPER.md layer 5: SelectedRows
+ parameter_prefetch + distribute_lookup_table). This package is the
TPU-native rebuild, three planes over one table:

- **on-chip, sharded** — ``Plan(ep=N, tables=[...])`` row-shards
  registered tables over the ``ep`` mesh axis; the forward is
  ``parallel.sharded_embedding_lookup`` (local gather + one psum) and
  the backward is :func:`exchange.sparse_ep_update`: (unique ids, int8
  rows) on the wire, never the dense (V, D) gradient.
- **host-backed** — :class:`host_table.HostBackedTable` keeps
  authoritative rows in host RAM at scales no chip (or pod) holds,
  with an on-chip hot-row working set governed by
  :class:`cache.RowCache` (clock/second-chance LRU) and prefetched by
  the data plane (``DevicePrefetcher(prefetch_rows=...)``).
- **durable** — tables checkpoint through ``paddle_tpu.checkpoint``'s
  globally-committed two-phase path (per-shard files + checksums) and
  restore across ``ep`` shapes via the cross-plan-shape restore.

The README's "Sharded embeddings" section is the user-facing tour.
"""

from .cache import RowCache
from .host_table import HostBackedTable
from .exchange import (dense_grad_bytes, exchange_payload_bytes,
                       exchange_rows, record_exchange_bytes,
                       should_compress, sparse_ep_minimize_fn,
                       sparse_ep_update)
from ..parallel.sharded_embedding import (ShardedEmbedding,
                                          embedding_ep_rules,
                                          sharded_embedding_lookup)

__all__ = [
    "RowCache",
    "HostBackedTable",
    "ShardedEmbedding",
    "dense_grad_bytes",
    "embedding_ep_rules",
    "exchange_payload_bytes",
    "exchange_rows",
    "record_exchange_bytes",
    "sharded_embedding_lookup",
    "should_compress",
    "sparse_ep_minimize_fn",
    "sparse_ep_update",
]
