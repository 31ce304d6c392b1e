"""Gradient compression — DGC + quantized collectives (reference:
paddle/fluid/operators/dgc_op.cc, framework/details/
sparse_all_reduce_op_handle.h:30 sparse allreduce, python
optimizer.py:640 DGCMomentumOptimizer; quantized allreduce follows the
EQuARX-style design referenced in PAPERS.md).

Deep Gradient Compression (Lin et al.): send only the top-k fraction of
gradient magnitudes each step; the rest accumulates locally (error
feedback) with momentum correction, preserving convergence at 100-1000x
compression.

TPU-native notes: the reference ships sparse (index, value) pairs over
NCCL. On TPU, dynamic sparse shapes fight XLA, so:
  - ``top_k_sparsify`` produces a *dense masked* tensor (static shape) —
    the error-feedback/momentum-correction math is identical;
  - the bandwidth win comes from ``quantized_allreduce``: int8
    reduce-scatter + all-gather over the dp axis (~4x less ICI traffic),
    composable with DGC's sparsification (zeros quantize to zero).
Both are shard_map-level tools: use inside a manually-sharded train step
where the gradient exchange is explicit.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import enforce
from ..optimizer.optimizers import Momentum, Optimizer, tree_map


def top_k_sparsify(g, sparsity: float = 0.999) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Keep the top-(1-sparsity) fraction of |g|; return (kept, residual)
    as dense tensors (kept + residual == g). reference: dgc_op.cc top-k
    threshold selection."""
    flat = jnp.abs(g.reshape(-1))
    k = max(int(round(flat.size * (1.0 - sparsity))), 1)
    # threshold = k-th largest |g|; lax.top_k is TPU-friendly
    thresh = lax.top_k(flat, k)[0][-1]
    mask = (jnp.abs(g) >= thresh).astype(g.dtype)
    kept = g * mask
    return kept, g - kept


class DGCMomentum(Optimizer):
    """Momentum with deep gradient compression (reference:
    optimizer.py:640 DGCMomentumOptimizer: momentum correction + local
    gradient accumulation + top-k sparsification, with a dense warmup
    period [rampup_begin_step]).

    Per-leaf state: velocity ``u`` (momentum-corrected accumulator) and
    error accumulator ``v``. Each step the locally-accumulated
    momentum-corrected gradient is sparsified; kept entries update the
    params, the residual stays local.
    """

    def __init__(self, learning_rate, momentum: float = 0.9,
                 sparsity: float = 0.999, rampup_begin_step: int = 0,
                 use_nesterov: bool = False, grad_clip=None,
                 regularization=None):
        super().__init__(learning_rate, grad_clip, regularization)
        self.momentum = momentum
        self.sparsity = sparsity
        self.rampup_begin_step = rampup_begin_step
        self.use_nesterov = use_nesterov

    def init_leaf(self, p):
        return {"u": jnp.zeros_like(p), "v": jnp.zeros_like(p)}

    def update_leaf(self, p, g, s, lr, step):
        # momentum correction (DGC paper alg. 1): accumulate velocity
        # locally, THEN sparsify the accumulated update; BOTH accumulators
        # are cleared at sent coordinates
        u = self.momentum * s["u"] + g
        if self.use_nesterov:
            u = self.momentum * u + g
        acc = s["v"] + u
        kept, residual = top_k_sparsify(acc, self.sparsity)
        sent = (kept != 0).astype(u.dtype)
        new_u = u * (1.0 - sent)
        # dense warmup: send everything, keep plain momentum, no residual
        dense = step < self.rampup_begin_step
        kept = jnp.where(dense, acc, kept)
        residual = jnp.where(dense, jnp.zeros_like(acc), residual)
        new_u = jnp.where(dense, u, new_u)
        new_p = p - lr * kept
        return new_p, {"u": new_u, "v": residual}


def quantized_allreduce(x, axis_name: str = "dp", bits: int = 8):
    """Bandwidth-reduced allreduce: int8 reduce-scatter + int8 all-gather
    (each phase quantized with a per-shard scale). ~4x less traffic than
    fp32 allreduce; error is bounded by the two quantization steps.

    Call inside shard_map with ``axis_name`` live. x must have a leading
    dim divisible by the axis size (pad first if needed)."""
    n = lax.axis_size(axis_name)
    qmax = float(2 ** (bits - 1) - 1)
    orig_shape = x.shape
    flat = x.reshape(-1)
    enforce(flat.size % n == 0,
            "quantized_allreduce needs size %% axis_size == 0 "
            "(got %s %% %s)", flat.size, n)
    chunks = flat.reshape(n, -1)  # row i -> destination device i

    def quant(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-12)
        q = jnp.round(v * (qmax / scale)).astype(jnp.int8)
        return q, scale

    # phase 1: quantize chunks, exchange so device i holds every shard's
    # chunk i (reduce-scatter in int8): split rows across peers, row p of
    # the result is peer p's chunk destined for me
    q, scale = quant(chunks)  # (n, c) int8 + scalar scale
    recv = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    scales = lax.all_gather(scale, axis_name)  # (n,)
    partial = jnp.sum(recv.astype(x.dtype) *
                      (scales / qmax)[:, None], axis=0)  # (c,) my chunk sum
    # phase 2: quantize the reduced chunk, all-gather back
    q2, scale2 = quant(partial)
    gathered = lax.all_gather(q2, axis_name)        # (n, c) int8
    scales2 = lax.all_gather(scale2, axis_name)     # (n,)
    out = (gathered.astype(x.dtype) * (scales2 / qmax)[:, None]).reshape(-1)
    return out.reshape(orig_shape)


def dgc_allreduce(grads, axis_name: str = "dp", sparsity: float = 0.999,
                  quantize: bool = True):
    """Compressed gradient exchange for shard_map DP steps: sparsify each
    leaf locally (caller owns the residual bookkeeping via DGCMomentum) and
    sum across the axis, optionally with the quantized path. Returns the
    summed (dense) gradients."""
    def reduce_leaf(g):
        kept, _ = top_k_sparsify(g, sparsity)
        if quantize and kept.size % lax.axis_size(axis_name) == 0:
            return quantized_allreduce(kept, axis_name)
        return lax.psum(kept, axis_name)

    return tree_map(reduce_leaf, grads)
