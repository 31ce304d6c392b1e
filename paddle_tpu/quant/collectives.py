"""Compressed gradient collectives — int8 allreduce for the sharding
plan's dp/fsdp axes (EQuARX, PAPERS.md: quantized AllReduce inside the
collective at ~2x speedup; here the same design hand-written at the JAX
level for the plan's ``shard_map`` pure-DP path).

:func:`quantized_psum` is a hand-written ring allreduce over a named
mesh axis — reduce-scatter then all-gather via ``lax.ppermute`` — whose
per-hop payload is the int8 ``quant.ops.absmax_encode`` wire format
(per-``group`` abs-max scales ride along as float32, a ``4/group``
overhead). Partial sums are dequantized, accumulated in float32, and
requantized at each reduce-scatter hop exactly like EQuARX's in-XLA
pipeline; the all-gather phase forwards received payloads unchanged so
every device decodes bit-identical chunks — the replicated-update
invariant the shard_map trainer step relies on. Wire bytes per device:
``2*(n-1)/n * (size + 4*size/group)`` vs ``2*(n-1)/n * 4*size`` for the
fp32 ring — a ~3.98x payload reduction at the default group.

Safety rails baked in (the ``amp``-style contract — opt-in, parity
gated, never silently lossy in the failure modes that matter):

- **tiny leaves** (< ``MIN_COMPRESS_SIZE`` elements) and non-float
  leaves ride the plain fp32 ``lax.psum`` — scale overhead and
  quantization noise on a 10-element bias buys nothing;
- **scale-degenerate leaves**: an all-zero chunk encodes exactly (the
  eps floor), and a NON-FINITE leaf (inf/nan gradients) poisons the
  whole output with NaN via a 4-byte ``pmin``-reduced finite flag — the
  train loop's nan-guard must keep firing; a quantizer that launders
  inf into a finite int8 payload would silently corrupt training;
- **stochastic rounding** (``key=``): unbiased ``floor(y + u)``
  rounding so quantization bias cannot accumulate across steps.

:func:`quantized_psum_partitioned` is the same ring rebuilt as a
``jax.custom_partitioning``-wrapped collective for PJIT-LEVEL callers:
the stacked per-shard partials stay sharded over the named axis and the
int8 encode/exchange/accumulate lowers INSIDE the partitioned
computation (bit-identical to the shard_map form on the same mesh) —
no shard_map body to write, and GSPMD composes the op with everything
around it. Both forms funnel their dispatch through
``utils.compat.native_int8_allreduce()``: the moment the runtime
exposes a native int8 AllReduce (EQuARX proper), it swaps in under
both spellings with zero call-site changes.

The explicit (fsdp/tp) pjit path has no user-visible collective — GSPMD
owns the reduce schedule — so :func:`compress_grads` applies the SAME
int8 wire-format round-trip at the reduce boundary instead: numerics
(and therefore the parity gate) match the quantized wire exactly, and
the native-AllReduce seam above slots in underneath without an API
change when the backend grows one.

Byte accounting is host-side (``pt_collective_bytes_total{compressed=}``
— traced code cannot touch counters): leaf shapes are static, so the
per-step payload is computed once (:func:`tree_payload_bytes`) and the
trainer increments the counter per dispatched step.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from ..core.enforce import enforce
from ..utils import compat
from .ops import absmax_decode, absmax_encode

# per-group quantization granularity of the wire format (elements per
# f32 scale — 4/GROUP_SIZE relative overhead on the payload)
GROUP_SIZE = 1024
# leaves below this many elements ride the fp32 psum (biases, scalars:
# noise for no bandwidth win)
MIN_COMPRESS_SIZE = 2048

COMPRESSION_MODES = (None, "int8", "int8_sr")


def check_mode(mode: Optional[str]) -> Optional[str]:
    """Validate a ``grad_compression`` knob value (None | "int8" |
    "int8_sr" — the stochastic-rounding variant)."""
    enforce(mode in COMPRESSION_MODES,
            "grad_compression must be one of %s, got %r",
            COMPRESSION_MODES, mode)
    return mode


@telemetry.cached_instruments
def _comm_metrics(reg):
    """Collective byte counters (only reached when telemetry is on)."""
    return {
        "bytes_int8": reg.counter(
            "pt_collective_bytes_total",
            "per-device gradient-allreduce payload bytes moved by the "
            "hand-written plan collectives (int8 wire format incl. "
            "scales)", labels={"compressed": "int8"}),
        "bytes_fp32": reg.counter(
            "pt_collective_bytes_total",
            "per-device gradient-allreduce payload bytes moved by the "
            "hand-written plan collectives (fp32 payload)",
            labels={"compressed": "fp32"}),
    }


def record_payload_bytes(int8_bytes: int, fp32_bytes: int) -> None:
    """Host-side per-step counter bump (no-op when telemetry is off)."""
    if not telemetry.enabled():
        return
    m = _comm_metrics()
    if int8_bytes:
        m["bytes_int8"].inc(int8_bytes)
    if fp32_bytes:
        m["bytes_fp32"].inc(fp32_bytes)


# ---------------------------------------------------------------------------
# payload-byte accounting (static shapes -> computed once per trainer)
# ---------------------------------------------------------------------------


def _ring_chunk(size: int, n: int, group: int) -> int:
    """Per-device ring chunk in elements, padded to the group grid."""
    chunk = -(-size // n)
    return -(-chunk // group) * group


def leaf_payload_bytes(size: int, axis_size: int, *, compressed: bool,
                       group: int = GROUP_SIZE,
                       dtype_bytes: int = 4) -> int:
    """Ring-allreduce payload bytes ONE device moves (sends) for one
    leaf: 2*(n-1) hops of one chunk each (reduce-scatter + all-gather),
    int8 data + f32 per-group scales when compressed."""
    n = int(axis_size)
    if n <= 1:
        return 0
    if not compressed:
        # plain lax.pmean: ring chunk is ceil(size/n), no group grid
        return 2 * (n - 1) * (-(-int(size) // n)) * dtype_bytes
    chunk = _ring_chunk(int(size), n, group)
    return 2 * (n - 1) * (chunk + 4 * (chunk // group))


def tree_payload_bytes(tree, axis_size: int, *, compression: Optional[str],
                       min_size: int = MIN_COMPRESS_SIZE,
                       group: int = GROUP_SIZE) -> Tuple[int, int]:
    """(int8_bytes, fp32_bytes) one device moves per step reducing
    ``tree`` over an ``axis_size`` ring — the numbers
    ``pt_collective_bytes_total`` advances by. Compression applies per
    leaf exactly where :func:`quantized_pmean_tree` would compress."""
    i8 = f32 = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = int(leaf.size) if hasattr(leaf, "size") else 1
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        if compression and _compressible(leaf, min_size):
            i8 += leaf_payload_bytes(size, axis_size, compressed=True,
                                     group=group)
        else:
            f32 += leaf_payload_bytes(size, axis_size, compressed=False,
                                      dtype_bytes=itemsize)
    return i8, f32


def _compressible(leaf, min_size: int) -> bool:
    dt = getattr(leaf, "dtype", None)
    return (dt is not None and jnp.issubdtype(dt, jnp.floating)
            and int(leaf.size) >= min_size)


# ---------------------------------------------------------------------------
# the hand-written quantized ring psum (shard_map bodies only)
# ---------------------------------------------------------------------------


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _encode_chunk(chunk, group: int, key=None):
    """Chunk -> (q (gpc, group) int8, scale (gpc, 1) f32)."""
    return absmax_encode(chunk.reshape(-1, group), axis=1, key=key)


def quantized_psum(x, axis_name: str, axis_size: int, *,
                   group: int = GROUP_SIZE, key=None):
    """int8 ring allreduce of ``x`` over ``axis_name`` — call inside a
    ``shard_map`` body (the plan's pure-DP step). Returns the summed
    array in ``x``'s dtype, identical on every device. ``key``: enables
    stochastic rounding of each hop's payload (per-device independent
    keys are fine — unbiasedness is per-element).

    The mean-loss gradient tolerance: each chunk's running sum is
    requantized per reduce-scatter hop, so worst-case error grows
    ~linearly in ``axis_size`` quantization steps (the EQuARX regime,
    <1% on gradient-scale data); the trajectory parity gate in
    ``tests/test_quant_comm.py`` pins the training-level consequence.
    """
    n = int(axis_size)
    enforce(n >= 2, "quantized_psum needs axis_size >= 2, got %s", n)
    native = compat.native_int8_allreduce()
    if native is not None and (
            key is None or not getattr(native, "partial_contract",
                                       False)):
        # the runtime grew an in-XLA int8 AllReduce (EQuARX proper):
        # route through it — same contract, the ring below becomes the
        # reference implementation. A partial-contract adapter (no
        # stochastic-rounding support) is refused for key= calls: SR
        # numerics must never silently degrade to nearest rounding.
        return native(x, axis_name=axis_name, axis_size=n, group=group,
                      key=key)
    shape, dt = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    size = flat.size
    chunk = _ring_chunk(size, n, group)
    gpc = chunk // group
    flat = jnp.pad(flat, (0, n * chunk - size))
    parts = flat.reshape(n, chunk)
    idx = lax.axis_index(axis_name)
    perm = _ring_perm(n)
    # non-finite leaves must POISON the result (nan-guard contract):
    # quantizing inf/nan would launder it into a finite payload
    ok_all = lax.pmin(jnp.isfinite(x).all().astype(jnp.int32), axis_name)

    # reduce-scatter: n-1 hops; hop s sends chunk (idx-s) mod n as int8
    # + scales, receiver dequantizes and accumulates in f32
    for s in range(n - 1):
        hop_key = None if key is None else jax.random.fold_in(key, s)
        q, sc = _encode_chunk(jnp.take(parts, (idx - s) % n, axis=0),
                              group, key=hop_key)
        q = lax.ppermute(q, axis_name, perm)
        sc = lax.ppermute(sc, axis_name, perm)
        recv = (idx - s - 1) % n
        upd = jnp.take(parts, recv, axis=0) \
            + absmax_decode(q, sc).reshape(chunk)
        parts = parts.at[recv].set(upd)

    # device idx now owns the fully-reduced chunk (idx+1) mod n; encode
    # it ONCE and all-gather the payload unchanged — every device
    # (owner included) decodes the same bytes, so outputs replicate
    # bit-identically
    own = (idx + 1) % n
    own_key = None if key is None else jax.random.fold_in(key, n - 1)
    q_own, s_own = _encode_chunk(jnp.take(parts, own, axis=0), group,
                                 key=own_key)
    out_q = jnp.zeros((n, gpc, group), jnp.int8).at[own].set(q_own)
    out_s = jnp.zeros((n, gpc, 1), jnp.float32).at[own].set(s_own)
    cur_q, cur_s = q_own, s_own
    for s in range(n - 1):
        cur_q = lax.ppermute(cur_q, axis_name, perm)
        cur_s = lax.ppermute(cur_s, axis_name, perm)
        recv = (idx - s) % n
        out_q = out_q.at[recv].set(cur_q)
        out_s = out_s.at[recv].set(cur_s)
    out = absmax_decode(out_q.reshape(-1, group),
                        out_s.reshape(-1, 1)).reshape(-1)[:size]
    out = jnp.where(ok_all > 0, out, jnp.nan)
    return out.reshape(shape).astype(dt)


def quantized_pmean(x, axis_name: str, axis_size: int, *,
                    group: int = GROUP_SIZE, key=None):
    """Mean form of :func:`quantized_psum` (what gradient reduction
    wants: mean over batch shards == grad of the global-mean loss)."""
    return quantized_psum(x, axis_name, axis_size, group=group,
                          key=key) / axis_size


# ---------------------------------------------------------------------------
# the custom-partitioned form (pjit-level callers — no shard_map body)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _partitioned_psum(axis_name: str, group: int, has_key: bool):
    """Build (and cache per static config) the custom_partitioning
    wrapper around the int8 ring. The SPMD partitioners have no rule
    for a quantized collective — under plain pjit the stacked partials
    would all-gather and reduce in fp32, erasing the byte win. The
    registered partition keeps the input sharded over ``axis_name`` and
    lowers to a per-shard body that runs :func:`quantized_psum` over
    the SAME named axis: the int8 encode/exchange/accumulate executes
    INSIDE the partitioned computation (per-shard ring, fp32
    accumulation), not at its edges."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ref(x, *maybe_key):
        # global semantics (abstract eval + the no-mesh eager fallback):
        # the exact fp32 sum over the stacked partials. The partitioned
        # lowering replaces this with the quantized ring — single-shard
        # (and eager) calls are exact, multi-shard calls carry the
        # documented quantization-step bound.
        return x.astype(jnp.float32).sum(0).astype(x.dtype)

    wrapped = custom_partitioning(ref)

    def _arg_shardings(msh, ndim):
        xs = NamedSharding(msh, P(axis_name, *([None] * (ndim - 1))))
        if has_key:
            return (xs, NamedSharding(msh, P()))
        return (xs,)

    def partition(mesh, arg_shapes, result_shape):
        a_sh = arg_shapes[0].sharding
        msh = getattr(a_sh, "mesh", None) or mesh
        n = int(msh.shape[axis_name])
        ndim = len(arg_shapes[0].shape)

        def lower_fn(x_local, *maybe_key):
            # local partials fold first (any even sharding of the
            # leading dim is correct: sum of local sums == global sum),
            # then ONE ring over the named axis
            part = x_local.astype(jnp.float32).sum(0)
            k = maybe_key[0] if maybe_key else None
            if k is not None:
                # per-device independent draws (unbiasedness is
                # per-element; see quantized_psum's key contract)
                k = jax.random.fold_in(k, lax.axis_index(axis_name))
            if n < 2:
                out = part
            else:
                out = quantized_psum(part, axis_name, n, group=group,
                                     key=k)
            return out.astype(x_local.dtype)

        return (msh, lower_fn, NamedSharding(msh, P()),
                _arg_shardings(msh, ndim))

    def infer_sharding_from_operands(mesh, arg_shapes, shape):
        a_sh = arg_shapes[0].sharding
        msh = getattr(a_sh, "mesh", None) or mesh
        return NamedSharding(msh, P())

    def sharding_rule(mesh, value_types, result_types):
        # Shardy's view of the call: the stacked dim ``n`` is summed
        # away, every other dim of x passes through; the key (when
        # given) shares no factor with the result
        def dims(prefix, rank):
            return " ".join(f"{prefix}{i}" for i in range(rank))

        rest = dims("d", len(value_types[0].shape) - 1)
        key = ", " + dims("k", len(value_types[1].shape)) if has_key else ""
        return f"n {rest}{key} -> {rest}"

    wrapped.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer_sharding_from_operands,
        sharding_rule=sharding_rule)
    return wrapped


def quantized_psum_partitioned(x, axis_name: str, *,
                               group: int = GROUP_SIZE, key=None):
    """:func:`quantized_psum` as a ``jax.custom_partitioning``-wrapped
    collective — the pjit-level spelling (no shard_map body to write).
    ``x`` (n, ...) stacks the per-shard partials on dim 0, sharded over
    mesh axis ``axis_name``; returns the REPLICATED sum (...) in ``x``'s
    dtype. The lowered computation runs the identical hand-written int8
    ring (same wire format, same per-hop payload — byte accounting via
    :func:`leaf_payload_bytes` applies unchanged; same nan-poison and
    stochastic-rounding ``key=`` contracts), so results are
    bit-identical to the shard_map form on the same mesh. Outside a
    mesh/jit the exact fp32 sum runs instead (nothing to compress
    across). The runtime-native int8 AllReduce seam
    (``utils.compat.native_int8_allreduce``) applies inside the
    partitioned body exactly as it does inside shard_map bodies."""
    enforce(x.ndim >= 1,
            "quantized_psum_partitioned stacks per-shard partials on "
            "dim 0 — got a scalar")
    wrapped = _partitioned_psum(axis_name, int(group), key is not None)
    out = wrapped(x, key) if key is not None else wrapped(x)
    return out.astype(x.dtype)


def quantized_pmean_tree(tree, axis_name: str, axis_size: int, *,
                         min_size: int = MIN_COMPRESS_SIZE,
                         group: int = GROUP_SIZE, key=None):
    """Gradient-tree reduce for the shard_map step: float leaves >=
    ``min_size`` elements ride the int8 ring; everything else (tiny
    biases, int counters) the plain fp32 ``lax.pmean``. Each compressed
    leaf folds its flattened tree index into ``key`` so stochastic
    draws never repeat across leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        if _compressible(leaf, min_size):
            k = None if key is None else jax.random.fold_in(key, i)
            out.append(quantized_pmean(leaf, axis_name, axis_size,
                                       group=group, key=k))
        else:
            out.append(lax.pmean(leaf, axis_name))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the pjit/GSPMD boundary: wire-format round-trip (fsdp/tp plans)
# ---------------------------------------------------------------------------


def compress_grads(tree, *, min_size: int = MIN_COMPRESS_SIZE,
                   group: int = GROUP_SIZE, key=None):
    """int8 wire-format round-trip (encode -> decode, same per-group
    abs-max convention) over a gradient tree whose allreduce GSPMD owns
    (explicit fsdp/tp plans — no user-level collective to rewrite at
    the JAX level). Numerics match the quantized wire exactly, so the
    parity gate and the opt-in surface are uniform across plan shapes;
    the in-collective byte win lands when the runtime exposes an int8
    AllReduce (EQuARX) under the same boundary. Non-finite leaves pass
    through untouched — the nan-guard sees the original values."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        if not _compressible(leaf, min_size):
            out.append(leaf)
            continue
        k = None if key is None else jax.random.fold_in(key, i)
        flat = leaf.reshape(-1).astype(jnp.float32)
        size = flat.size
        pad = -(-size // group) * group - size
        g = jnp.pad(flat, (0, pad)).reshape(-1, group)
        q, sc = absmax_encode(g, axis=1, key=k)
        deq = absmax_decode(q, sc).reshape(-1)[:size]
        ok = jnp.isfinite(leaf).all()
        deq = jnp.where(ok, deq, flat[:size])
        out.append(deq.reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
