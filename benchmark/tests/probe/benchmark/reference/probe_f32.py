"""The plain reference of the ``probe`` family, in jax.numpy: float32,
one expert at a time, no batching tricks. Imports nothing of the program
and nothing of the harness; weights come from the harness's generator.
``mode`` is ``"f32"`` or, for the control, ``"fp8"`` (matmul inputs
scaled per tensor and rounded to float8 e4m3)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def matmul(x, w, mode: str):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    elif mode != "f32":
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, weight, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def layer(x, w, i: int, dims, mode: str):
    """Block ``i`` on one sequence ``x`` (T, hidden)."""
    p = f"blocks.{i}."
    y = rms_norm(x, w[p + "norm.weight"], dims.eps)
    if i < dims.dense_layers:
        return x + matmul(jax.nn.silu(matmul(y, w[p + "up"], mode)),
                          w[p + "down"], mode)
    gate = jax.nn.softmax(
        matmul(y, w[p + "router"], mode) + w[p + "router_bias"], axis=-1)
    for e in range(dims.experts):
        z = jax.nn.silu(matmul(y, w[p + "up"][e], mode))
        x = x + gate[:, e, None] * matmul(z, w[p + "down"][e], mode)
    return x


def loss(w, batch, dims, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of (B, T) ``batch``; a mean over
    rows, as the harness's row-at-a-time reference needs."""
    def row(tokens):
        x = w["embed.weight"].astype(jnp.float32)[tokens]
        for i in range(dims.layers):
            x = layer(x, w, i, dims, mode)
        x = rms_norm(x, w["norm_f.weight"], dims.eps)
        lp = jax.nn.log_softmax(matmul(x, w["lm_head"], mode)[:-1])
        return -jnp.sum(jnp.take_along_axis(lp, tokens[1:, None], 1))
    total = jnp.sum(jax.lax.map(row, batch))
    return total / (batch.shape[0] * (batch.shape[1] - 1))
