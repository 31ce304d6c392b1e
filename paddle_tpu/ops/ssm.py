"""State-space (Mamba-2 / SSD) operations: the selective scan whose
recurrent state is a fixed-size (heads, head_dim, state) matrix a
sequence, and the short depthwise causal convolution in front of it.

The recurrence, per head ``h`` with a scalar decay (Dao & Gu 2024,
"Transformers are SSMs", the SSD form; one group of B and C shared by
all heads)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: (P, N)
    y_t = S_t C_t + D x_t

:func:`ssd_step` is that update once (a decode step);
:func:`ssd_chunked` computes it over a sequence as the chunked form: a
quadratic attention-like product inside each chunk and a state passed
from chunk to chunk, both as einsums. Decays, cumulative sums and the
state are float32 whatever the inputs are; the two agree to rounding
(``tests/test_ssm.py`` holds both to a plain ``lax.scan`` of the
recurrence).

``valid_len`` is how a padded sequence is run: positions at or beyond it
get ``dt = 0``, which is decay 1 and input 0, so they leave the state
untouched (their outputs are finite and meaningless). The convolution's
tail is likewise taken at ``valid_len``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv1d", "causal_conv1d_step", "ssd_chunked",
           "ssd_step"]

_F32 = jnp.float32


def causal_conv1d(x, weight, bias, tail, valid_len=None):
    """Depthwise causal convolution along time with a carried tail.

    ``x`` (B, T, C); ``weight`` (K, C), ``weight[K-1]`` multiplying the
    current position; ``bias`` (C,) or None; ``tail`` (B, K-1, C), the
    K-1 inputs before ``x[:, 0]`` (zeros at the start of a sequence).
    Returns (y (B, T, C), new tail): the K-1 inputs before position
    ``valid_len`` (default T), so that a later call continues there."""
    k = weight.shape[0]
    t = x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + t] * weight[i] for i in range(k))
    if bias is not None:
        y = y + bias
    at = t if valid_len is None else valid_len
    return y, lax.dynamic_slice_in_dim(xp, at, k - 1, axis=1)


def causal_conv1d_step(x, weight, bias, tail):
    """One position of :func:`causal_conv1d`: ``x`` (B, C) -> (y (B, C),
    new tail)."""
    y, tail = causal_conv1d(x[:, None], weight, bias, tail)
    return y[:, 0], tail


def ssd_step(x, dt, A, B, C, D, state):
    """One update of the recurrence for every row.

    ``x`` (B, H, P); ``dt`` (B, H), already positive (after softplus);
    ``A`` (H,), negative; ``B``, ``C`` (B, N); ``D`` (H,); ``state``
    (B, H, P, N) float32. Returns (y (B, H, P) float32, new state)."""
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    A, D = A.astype(_F32), D.astype(_F32)
    decay = jnp.exp(dt * A)                                   # (B, H)
    state = (state * decay[..., None, None]
             + (dt[..., None] * x)[..., None] * B[:, None, None, :])
    y = jnp.sum(state * C[:, None, None, :], axis=-1)
    return y + D[:, None] * x, state


def ssd_chunked(x, dt, A, B, C, D, chunk: int, state0=None,
                valid_len=None):
    """The recurrence over a sequence, chunk by chunk.

    ``x`` (B, T, H, P); ``dt`` (B, T, H) positive; ``A`` (H,) negative;
    ``B``, ``C`` (B, T, N); ``D`` (H,); ``state0`` (B, H, P, N) or None
    (zeros); ``valid_len`` a scalar: only the first ``valid_len``
    positions advance the state. T need not be a multiple of ``chunk``.
    Returns (y (B, T, H, P) float32, final state (B, H, P, N) float32).

    Within a chunk, position i reads position j <= i with weight
    ``exp(a_i - a_j) dt_j (C_i . B_j)`` where ``a`` is the running sum
    of ``dt A`` inside the chunk (all differences are <= 0, so nothing
    overflows); the state entering a chunk is read with ``exp(a_i)`` and
    leaves it as ``exp(a_last) S + sum_j exp(a_last - a_j) dt_j x_j (x)
    B_j``."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    x, dt, B, C = (a.astype(_F32) for a in (x, dt, B, C))
    A, D = A.astype(_F32), D.astype(_F32)
    if valid_len is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] < valid_len, dt, 0.0)
    pad = -t % chunk
    if pad:
        # dt = 0 in the padding: the state passes through it unchanged
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                               (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (t + pad) // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)
    a = jnp.cumsum(dtc * A, axis=2)                      # (b, nc, L, h)
    # inside a chunk: a masked, decayed (C_i . B_j) score a head
    diff = a[:, :, :, None, :] - a[:, :, None, :, :]     # (b, nc, i, j, h)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :,
                                                       None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)
    w = decay * cb[..., None] * dtc[:, :, None, :, :]    # (b, nc, i, j, h)
    y = jnp.einsum("bcijh,bcjhp->bcihp", w, xc)
    # what each chunk adds to the state, and the pass from chunk to chunk
    to_end = jnp.exp(a[:, :, -1:, :] - a)                # (b, nc, L, h)
    added = jnp.einsum("bcjh,bcjhp,bcjn->bchpn", to_end * dtc, xc, Bc)
    whole = jnp.exp(a[:, :, -1, :])                      # (b, nc, h)
    s0 = (jnp.zeros((b, h, p, n), _F32) if state0 is None
          else state0.astype(_F32))

    def carry(s, inp):
        add, keep = inp
        return s * keep[..., None, None] + add, s        # emit the entry

    final, entering = lax.scan(
        carry, s0, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)              # (b, nc, h, p, n)
    y = y + jnp.einsum("bcin,bchpn->bcihp", Cc, entering) * jnp.exp(
        a)[..., None]
    y = y.reshape(b, nc * chunk, h, p)[:, :t] + D[:, None] * x[:, :t]
    return y, final
