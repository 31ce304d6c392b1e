"""The plain reference: the published decoder block in jax.numpy.

RMSNorm pre-norm, rotary (rotate-half) GQA causal attention with an
optional sliding window, SwiGLU, no biases, tied or untied head — the
block of Mistral-7B-v0.1 and InternLM2 (whose fused ``wqkv`` is a
storage layout of the same three projections). Float32 throughout with
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching tricks. Imports nothing of the program and takes nothing the
program made: weights come from ``benchmark/harness/weights.py``.

``mode`` selects the arithmetic of every matrix multiplication:

- ``"f32"``  the reference;
- ``"bf16"`` inputs rounded to bfloat16, float32 accumulation (what the
  configurations state; informational);
- ``"fp8"``  inputs scaled per tensor and rounded to float8 e4m3: the
  precision below bfloat16, used only as the *control* that the
  comparison deciding ``correct`` has to fail.

Departure from the published configs: ``eps`` is whatever the
configuration file says it runs with (the program's RMSNorm fixes 1e-6;
both models publish 1e-5) — listed under ``reduced`` in each file.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    theta: float
    eps: float
    tied: bool
    window: Optional[int] = None

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(hidden=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ffn=c["intermediate_size"],
                   vocab=c["vocab_size"], theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]),
                   window=c.get("sliding_window"))


def _round_fp8(x):
    """``x`` rounded to float8 e4m3 after scaling its largest magnitude
    to the format's 448; gradients pass straight through the rounding."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def matmul(x, w, mode: str):
    """``x @ w`` in the arithmetic ``mode`` names, float32 out."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    elif mode == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode != "f32":
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, weight, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding of (T, H, D) at ``positions`` (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: Optional[int]):
    """Causal softmax attention of one sequence: q (T, H, D), k and v
    (T, Hkv, D); query head h reads kv head h // (H / Hkv)."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.float32(d))
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = ki <= qi
    if window is not None:
        keep &= ki > qi - window
    s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """One decoder block on one sequence ``x`` (T, hidden)."""
    p = f"blocks.{i}."
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, w[p + "norm1.weight"], dims.eps)
    q = matmul(h, w[p + "self_attn.q_proj.weight"], mode)
    k = matmul(h, w[p + "self_attn.k_proj.weight"], mode)
    v = matmul(h, w[p + "self_attn.v_proj.weight"], mode)
    q = rope(q.reshape(t, dims.heads, dims.head_dim), pos, dims.theta)
    k = rope(k.reshape(t, dims.kv_heads, dims.head_dim), pos, dims.theta)
    v = v.reshape(t, dims.kv_heads, dims.head_dim)
    a = attention(q, k, v, dims.window).reshape(t, -1)
    x = x + matmul(a, w[p + "self_attn.out_proj.weight"], mode)
    h = rms_norm(x, w[p + "norm2.weight"], dims.eps)
    g = matmul(h, w[p + "ffn.gate.weight"], mode)
    u = matmul(h, w[p + "ffn.up.weight"], mode)
    return x + matmul(jax.nn.silu(g) * u, w[p + "ffn.down.weight"], mode)


def head_weight(w, dims: Dims):
    return w["embed.weight"].T if dims.tied else w["lm_head"]


def logits(tokens, w, dims: Dims, mode: str = "f32", remat: bool = False):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``. ``remat``
    recomputes each block in a backward pass instead of keeping its
    activations: the same mathematics in less memory."""
    x = w["embed.weight"].astype(jnp.float32)[tokens]
    step = (jax.checkpoint(layer, static_argnums=(2, 3, 4)) if remat
            else layer)
    for i in range(dims.layers):
        x = step(x, w, i, dims, mode)
    x = rms_norm(x, w["norm_f.weight"], dims.eps)
    return matmul(x, head_weight(w, dims), mode)


def loss(w, batch, dims: Dims, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of (B, T) ``batch``: position t
    predicts token t+1, the last position of each row predicts nothing."""
    def row(tokens):
        lp = jax.nn.log_softmax(
            logits(tokens, w, dims, mode, remat)[:-1])
        return -jnp.sum(jnp.take_along_axis(lp, tokens[1:, None], 1))
    total = jnp.sum(jax.lax.map(row, batch))
    return total / (batch.shape[0] * (batch.shape[1] - 1))


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple]):
    """Logits at ``positions`` (B, P) of (B, T) ``tokens``, holding one
    layer's weights at a time: ``get(shapes)`` makes the named leaves.
    Sequences are independent, so padding a row's tail changes nothing
    at earlier positions (causal)."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    x = _embed(tokens, emb["embed.weight"])
    if not dims.tied:
        del emb
    for i in range(dims.layers):
        # one program serves every layer: its leaves go in as block 0's
        w = {k.replace(f"blocks.{i}.", "blocks.0."): a
             for k, a in get(shapes_of_layer(i)).items()}
        x = _layer_rows(x, w, dims, mode)
        del w
    rest = {k: s for k, s in top_shapes.items() if k != "embed.weight"}
    w = get(rest)
    if dims.tied:
        w.update(emb)
    return _head_rows(x, positions, w, dims, mode)


@jax.jit
def _embed(tokens, table):
    return table.astype(jnp.float32)[tokens]


def _layer_rows_impl(x, w, dims, mode):
    return jax.lax.map(lambda r: layer(r, w, 0, dims, mode), x)


_layer_rows = jax.jit(_layer_rows_impl, static_argnums=(2, 3))


def _head_rows_impl(x, positions, w, dims, mode):
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    picked = rms_norm(picked, w["norm_f.weight"], dims.eps)
    return matmul(picked, head_weight(w, dims), mode)


_head_rows = jax.jit(_head_rows_impl, static_argnums=(3, 4))
