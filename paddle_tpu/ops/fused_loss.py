"""Fused linear + softmax-cross-entropy over a chunked vocabulary.

The classifier head ``loss = CE(h @ W + b, labels)`` materializes a
``(B*T, V)`` logits tensor — at BERT scale (32x128 tokens, 30k vocab,
fp32) that is ~0.5 GB live twice (fwd activation + bwd softmax), pure HBM
traffic. This op computes the SAME loss by scanning vocabulary chunks:
per chunk one ``(N, C)`` logits tile feeds an online logsumexp (forward)
and the softmax-weighted matmuls (backward), so peak memory is
``O(N*C + D*C)`` instead of ``O(N*V)`` while every FLOP stays an MXU
matmul. This is the capability slot of the reference's hand-fused
CPU kernels (fused_embedding_seq_pool / jit kernel niche — SURVEY §2.2)
applied to the modern transformer hot spot.

Numerics match ops.loss.softmax_with_cross_entropy to fp32 roundoff; the
custom VJP recomputes chunk logits in the backward pass (rematerialize >
store — HBM is the bottleneck, MXU has headroom).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import enforce
from ..telemetry.scopes import scope


def _chunk_w(weight, bias, num_chunks, chunk):
    """(D, V) → (num_chunks, D, C) [+ bias (num_chunks, C)], zero-padded."""
    d, v = weight.shape
    pad = num_chunks * chunk - v
    wp = jnp.pad(weight, ((0, 0), (0, pad)))
    wc = jnp.transpose(wp.reshape(d, num_chunks, chunk), (1, 0, 2))
    if bias is None:
        bc = jnp.zeros((num_chunks, chunk), weight.dtype)
    else:
        bc = jnp.pad(bias, (0, pad)).reshape(num_chunks, chunk)
    return wc, bc


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def linear_cross_entropy(hidden, weight, bias, labels, chunk: int = 4096,
                         ignore_index: int = -100):
    """Per-row CE of ``hidden @ weight + bias`` against ``labels`` without
    materializing the full logits.

    hidden (N, D) float; weight (D, V); bias (V,) or None; labels (N,) int.
    Rows with ``labels == ignore_index`` contribute 0. Returns (N,) losses.
    """
    loss, _ = _lce_fwd_impl(hidden, weight, bias, labels, chunk,
                            ignore_index)
    return loss


@scope("linear_ce")
def _lce_fwd_impl(hidden, weight, bias, labels, chunk, ignore_index):
    n, d = hidden.shape
    d2, v = weight.shape
    enforce(d == d2, "hidden dim %s != weight dim %s", d, d2)
    num_chunks = -(-v // chunk)
    wc, bc = _chunk_w(weight, bias, num_chunks, chunk)
    valid_cols = jnp.arange(num_chunks * chunk).reshape(num_chunks, chunk) < v

    def body(carry, xs):
        m, s = carry                       # running max (N,), sumexp (N,)
        w_c, b_c, mask_c = xs
        # bf16 inputs on the MXU, fp32 accumulation — MUST match t_logit's
        # precision or confident rows go negative (lse < target logit)
        logits = jnp.matmul(hidden, w_c,
                            preferred_element_type=jnp.float32) \
            + b_c.astype(jnp.float32)
        logits = jnp.where(mask_c[None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=1)
        return (m_new, s), None

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((n,), jnp.float32)
    (m, s), _ = lax.scan(body, (m0, s0), (wc, bc, valid_cols))
    lse = m + jnp.log(s)                   # (N,)

    safe = jnp.clip(labels, 0, v - 1)
    w_t = jnp.take(weight, safe, axis=1).T          # (N, D) target columns
    # fp32 products + fp32 sum, EXACTLY like the preferred_element_type
    # matmul tiles — a bf16-rounded product here would make lse < t_logit
    # (negative loss) on confident rows
    t_logit = jnp.sum(hidden.astype(jnp.float32)
                      * w_t.astype(jnp.float32), axis=1)
    if bias is not None:
        t_logit = t_logit + jnp.take(bias, safe).astype(jnp.float32)
    valid = labels != ignore_index
    loss = jnp.where(valid, lse - t_logit, 0.0)
    return loss, (hidden, weight, bias, labels, lse)


@scope("linear_ce")
def _lce_bwd(chunk, ignore_index, res, g):
    hidden, weight, bias, labels, lse = res
    n, d = hidden.shape
    v = weight.shape[1]
    num_chunks = -(-v // chunk)
    wc, bc = _chunk_w(weight, bias, num_chunks, chunk)
    valid = (labels != ignore_index)
    gv = jnp.where(valid, g, 0.0)          # (N,) upstream per-row grads
    safe = jnp.clip(labels, 0, v - 1)

    def body(dh, xs):
        w_c, b_c, idx0 = xs
        logits = jnp.matmul(hidden, w_c,
                            preferred_element_type=jnp.float32) \
            + b_c.astype(jnp.float32)
        col = idx0 + jnp.arange(chunk)
        p = jnp.where(col[None, :] < v,
                      jnp.exp(logits - lse[:, None]), 0.0)  # softmax tile
        # dlogits = gv * (p - onehot)
        onehot = (col[None, :] == safe[:, None]).astype(p.dtype)
        dl = (gv[:, None] * (p - onehot)).astype(hidden.dtype)  # (N, C)
        dh = dh + (dl @ w_c.T).astype(jnp.float32)  # fp32 accumulator
        dw_c = hidden.T @ dl               # (D, C)
        db_c = jnp.sum(dl.astype(jnp.float32), axis=0)
        return dh, (dw_c, db_c)

    idx0s = jnp.arange(num_chunks) * chunk
    dh0 = jnp.zeros(hidden.shape, jnp.float32)
    dh, (dw_chunks, db_chunks) = lax.scan(body, dh0, (wc, bc, idx0s))
    dw = jnp.transpose(dw_chunks, (1, 0, 2)).reshape(d, num_chunks * chunk)
    dw = dw[:, :v].astype(weight.dtype)
    dh = dh.astype(hidden.dtype)
    db = (db_chunks.reshape(-1)[:v].astype(bias.dtype)
          if bias is not None else None)
    return dh, dw, db, None


linear_cross_entropy.defvjp(_lce_fwd_impl, _lce_bwd)


def mean_linear_cross_entropy(hidden, weight, bias, labels,
                              chunk: int = 4096, ignore_index: int = -100):
    """Mean over non-ignored rows (the training-loss form)."""
    losses = linear_cross_entropy(hidden, weight, bias, labels, chunk,
                                  ignore_index)
    count = jnp.maximum(jnp.sum((labels != ignore_index)
                                .astype(losses.dtype)), 1.0)
    return jnp.sum(losses) / count
