"""Power retention of degree 2: linear attention whose score is the
SQUARE of a scaled dot product, so that what a sequence has seen is a
fixed-size state a key-value head and not keys and values by position
(Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239).

The attention form, per query head ``j`` reading key-value head
``c(j)`` (``h / kv`` query heads share one), ``d`` the head dimension
and ``log g <= 0`` one gate a position and key-value head::

    w[t, i] = (q_t . k_i / sqrt(d))^2 * exp(sum_{s=i+1..t} log g_s)   i <= t
    y_t = sum_i w[t, i] v_i / (sum_i w[t, i] + eps)

No softmax and no exponent of a score. Because ``(a . b)^2 = phi(a) .
phi(b)`` for the symmetric square :func:`phi`, the same numbers come
from a state ``S (D, d)``, ``z (D,)`` a key-value head::

    S_t = g_t S_{t-1} + phi(k_t / d^(1/4)) v_t^T
    z_t = g_t z_{t-1} + phi(k_t / d^(1/4))
    y_t = phi(q_t / d^(1/4))^T S_t / (phi(q_t / d^(1/4))^T z_t + eps)

:func:`retention_step` is that update and read once (a decode step);
:func:`retention_chunked` runs a sequence chunk by chunk: inside a
chunk the attention form with its decay mask, across chunks the state.
The state, the decays and the denominators are float32 whatever the
inputs are. ``valid_len`` is how a padded sequence is run: positions at
or beyond it get ``log g = 0`` and a zero key, so they leave ``S`` and
``z`` untouched (their outputs are finite and meaningless).

**The layout of ``phi``.** The products ``x_a x_b`` are kept by their
circular offset ``o = (b - a) mod d``: ``phi(x)[o * d + i] = c_o x_i
x_((i + o) mod d)`` for ``o = 0 .. d / 2``, with ``c_0 = 1`` (the
squares), ``c_o = sqrt(2)`` for ``0 < o < d / 2`` (offsets ``o`` and
``d - o`` hold the same pairs, so one of them is kept and counted
twice) and ``c_(d/2) = 1`` (that offset is its own mirror: each of its
pairs stands twice in the row). ``D = (d / 2 + 1) d``: 8320 at ``d`` =
128, 64 entries over the least there is (``d (d + 1) / 2`` = 8256), and
every row of ``phi`` is a whole ``d``-wide rotation of ``x``: no gather,
no triangular bookkeeping, and ``D`` is a multiple of ``d``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["phi", "phi_dim", "retention_chunked", "retention_step",
           "retention_step_parts", "step_kernel_ok", "zero_state"]

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def phi_dim(d: int) -> int:
    """The length of :func:`phi` of a ``d``-vector."""
    return (d // 2 + 1) * d


def phi(x):
    """The symmetric square of ``x`` (..., d), ``d`` even: (...,
    :func:`phi_dim`) float32 with ``phi(a) . phi(b) == (a . b)^2``."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"phi needs an even width, got {d}")
    x = x.astype(_F32)
    half = d // 2
    twice = jnp.concatenate([x, x[..., :half]], axis=-1)
    rolled = jnp.stack([twice[..., o:o + d] for o in range(half + 1)],
                       axis=-2)                       # (..., half+1, d)
    c = np.full((half + 1, 1), math.sqrt(2.0), np.float32)
    c[0] = c[half] = 1.0
    out = rolled * x[..., None, :] * c
    return out.reshape(*x.shape[:-1], phi_dim(d))


def zero_state(b: int, kv: int, d: int):
    """(S (b, kv, D, d), z (b, kv, D)) float32 of sequences that have
    seen no token."""
    return (jnp.zeros((b, kv, phi_dim(d), d), _F32),
            jnp.zeros((b, kv, phi_dim(d)), _F32))


def step_kernel_ok(d: int, per_kv: int) -> bool:
    """Whether a step goes through the Pallas kernel
    (``pallas/retention_step.py``: one pass over the state): on the TPU
    (or under ``ops.attention.force_flash``, interpreted), where the
    head dimension is a multiple of the 128 lanes and a key-value
    head's queries fit one sublane tile. Static shapes alone."""
    from . import attention
    from .pallas.retention_step import ROWS

    if not attention._FORCE_FLASH and jax.default_backend() != "tpu":
        return False
    return d % 128 == 0 and per_kv <= ROWS


def retention_step_parts(q, k, v, log_g, state):
    """One update of the state and one read, unnormalised.

    ``q`` (B, H, d); ``k``, ``v`` (B, KV, d); ``log_g`` (B, KV), <= 0;
    ``state`` = (S (B, KV, D, d), z (B, KV, D)) float32. Returns (num
    (B, H, d), den (B, H), new state), all float32: the output is
    ``num / (den + eps)``. The token's own term is computed as the
    attention form has it, ``(q . k)^2 / d`` exactly, and only what the
    state kept goes through ``phi`` (whose ``D`` products are of either
    sign and cancel down to the weight: float32 rounding there is
    absolute, and small against a denominator that holds its own term).

    Two bodies for the pass over ``S``, chosen by :func:`step_kernel_ok`:
    the Pallas kernel (reads the old ``S`` for the queries on the MXU
    and writes the new one in place, one read and one write), and plain
    ``jax.numpy`` (the read as a product and a sum over ``D``, exact in
    float32, and the update beside it: XLA makes them two fusions that
    each stream ``S``)."""
    S, z = state
    b, h, d = q.shape
    kv = k.shape[1]
    scale = d ** -0.25
    g = jnp.exp(log_g.astype(_F32))                          # (B, KV)
    k, v = k.astype(_F32) * scale, v.astype(_F32)
    q = q.astype(_F32).reshape(b, kv, h // kv, d) * scale
    pk, pq = phi(k), phi(q)                  # (B, KV, D), (B, KV, R, D)
    own = jnp.square(jnp.sum(q * k[:, :, None], axis=-1))    # (B, KV, R)
    if step_kernel_ok(d, h // kv):
        from .pallas.retention_step import retention_state_step

        S, kept = retention_state_step(S, k, v, q, g)
    else:
        # both readers of the old state, side by side: what it gives
        # the queries and what it becomes
        kept = jnp.sum(pq[..., None] * S[:, :, None], axis=3)
        S = g[..., None, None] * S + pk[..., None] * v[:, :, None, :]
    # the gate is one number a head, so it multiplies the sums
    num = g[..., None, None] * kept + own[..., None] * v[:, :, None]
    den = g[..., None] * jnp.sum(pq * z[:, :, None], axis=3) + own
    z = g[..., None] * z + pk
    return num.reshape(b, h, d), den.reshape(b, h), (S, z)


def retention_step(q, k, v, log_g, state, eps: float = 1e-6):
    """One position for every row: ``q`` (B, H, d), ``k``, ``v`` (B,
    KV, d), ``log_g`` (B, KV), ``state`` as :func:`retention_chunked`
    returns it. Returns (y (B, H, d) float32, new state). A zero state
    and a zero key give ``y = 0``."""
    num, den, state = retention_step_parts(q, k, v, log_g, state)
    return num / (den[..., None] + eps), state


def retention_chunked(q, k, v, log_g, chunk: int, state0=None,
                      valid_len=None, eps: float = 1e-6):
    """A sequence, chunk by chunk.

    ``q`` (B, T, H, d); ``k``, ``v`` (B, T, KV, d) with ``KV`` dividing
    ``H``; ``log_g`` (B, T, KV), <= 0; ``state0`` = (S (B, KV, D, d), z
    (B, KV, D)) or None (zeros); ``valid_len`` a scalar: only the first
    ``valid_len`` positions advance the state. T need not be a multiple
    of ``chunk``. Returns (y (B, T, H, d) float32, (S, z) float32).

    With ``a`` the running sum of ``log g`` inside a chunk (every
    difference read is <= 0, so nothing overflows), position ``i`` reads
    position ``j <= i`` of its chunk with ``(q_i . k_j)^2 / d * exp(a_i
    - a_j)`` and the state entering the chunk through ``phi(q_i)``
    times ``exp(a_i)``; the chunk leaves ``exp(a_last) S + sum_j
    exp(a_last - a_j) phi(k_j) v_j^T``. One chunk is worked at a time (a
    ``lax.scan``): ``phi`` of a chunk's queries is ``chunk x H x D``
    numbers."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    r = h // kv
    scale = d ** -0.25
    q, k, v = (a.astype(_F32) for a in (q, k, v))
    q, k = q * scale, k * scale
    log_g = log_g.astype(_F32)
    if valid_len is not None:
        live = jnp.arange(t) < valid_len
        log_g = jnp.where(live[None, :, None], log_g, 0.0)
        k = jnp.where(live[None, :, None, None], k, 0.0)
    pad = -t % chunk
    if pad:
        # log g = 0 and a zero key in the padding: the state passes
        # through it unchanged
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (a.ndim - 2)) for a in (q, k, v, log_g))
    nc = (t + pad) // chunk
    by_chunk = lambda a: jnp.moveaxis(
        a.reshape(b, nc, chunk, *a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, inp):
        S, z = state
        qc, kc, vc, gc = inp                  # (b, L, ...) of one chunk
        qc = qc.reshape(b, chunk, kv, r, d)
        a = jnp.cumsum(gc, axis=1)                           # (b, L, kv)
        at = jnp.moveaxis(a, 1, 2)                           # (b, kv, L)
        diff = at[:, :, :, None] - at[:, :, None, :]       # (b, kv, i, j)
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)),
                          0.0)
        s = jnp.einsum("bikrd,bjkd->bkrij", qc, kc)
        w = jnp.square(s) * decay[:, :, None]
        num = jnp.einsum("bkrij,bjkd->bikrd", w, vc)
        den = jnp.moveaxis(jnp.sum(w, axis=-1), 3, 1)     # (b, L, kv, r)
        pq = phi(qc)                                   # (b, L, kv, r, D)
        ea = jnp.exp(a)
        num = num + jnp.einsum("bikrD,bkDd->bikrd", pq, S) * ea[
            ..., None, None]
        den = den + jnp.einsum("bikrD,bkD->bikr", pq, z,
                               precision=_HIGHEST) * ea[..., None]
        y = num / (den[..., None] + eps)
        pk = phi(kc)                                      # (b, L, kv, D)
        to_end = jnp.exp(a[:, -1:, :] - a)                   # (b, L, kv)
        whole = jnp.exp(at[:, :, -1])                           # (b, kv)
        S = whole[..., None, None] * S + jnp.einsum(
            "bjkD,bjkd->bkDd", pk * to_end[..., None], vc)
        z = whole[..., None] * z + jnp.einsum("bjkD,bjk->bkD", pk, to_end,
                                              precision=_HIGHEST)
        return (S, z), y.reshape(b, chunk, h, d)

    state0 = (zero_state(b, kv, d) if state0 is None else
              tuple(a.astype(_F32) for a in state0))
    state, y = lax.scan(one, state0, tuple(
        by_chunk(a) for a in (q, k, v, log_g)))
    return jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, d)[:, :t], state
