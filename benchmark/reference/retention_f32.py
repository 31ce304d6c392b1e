"""The plain reference of the ``retention_decoder`` family: a dense
pre-norm decoder whose token mixer is power retention of degree 2
(Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; the Brumby-14B-Base release notes), written in its
ATTENTION form in jax.numpy: every query against every earlier key, no
state, no chunks, no cache.

The equations (``h`` the residual stream, ``d`` the head dimension,
``c(j)`` the key-value head of query head ``j``)::

    h = E[ids];  logits = RMSNorm(h) @ W_head
    h = h + Mixer(RMSNorm(h))
    h = h + W_d (silu(u W_g) * (u W_u)),  u = RMSNorm(h)

    Mixer, x = RMSNorm(h) at position t:
      q_j = rope(RMSNorm_d(x W_q)_j, t)      k_c = rope(RMSNorm_d(x W_k)_c, t)
      v_c = (x W_v)_c                        log g_{t,c} = logsigmoid((x W_gate)_c)
      w_{t,i,j} = (q_{t,j} . k_{i,c(j)} / sqrt(d))^2 * exp(sum_{s=i+1..t} log g_{s,c(j)})
      y_{t,j} = sum_{i<=t} w_{t,i,j} v_{i,c(j)} / (sum_{i<=t} w_{t,i,j} + eps)
      out = concat_j(y_{t,j}) W_o

No softmax and no exponent of a score; the token's own term has decay 1.
Queries are taken in blocks of :data:`QUERY_BLOCK` so that a 2048-token
row's (heads, block, T) weights fit beside the layer; that changes no
number.

Float32 throughout with ``jax.default_matmul_precision("highest")``
semantics (every product names ``Precision.HIGHEST``). Imports nothing
of the program. ``mode`` selects the arithmetic of every matrix
multiplication by a weight, as in ``decoder_f32``: ``"f32"`` the
reference, ``"fp8"`` the control (inputs rounded to float8 e4m3); the
scores, the gates' running sum and the normalisation stay float32 in
both.

Departures from the published description, each an assumption the
configuration file lists under ``assumed`` (the public ``config.json``
is a Qwen3-14B-shaped decoder's and does not carry the mixer's own
settings): the degree is 2; one gate a key-value head from a bias-free
projection ``hidden -> kv_heads``, ``log g = logsigmoid(.)``; the
output is normalised by the sum of the weights plus ``eps`` = 1e-6;
queries and keys get a per-head RMSNorm before the rotary embedding
(Qwen3's, which the ``head_dim`` / ``rope_theta`` keys are left from);
the published kernels keep keys and values below a crossover length and
a state above it, which changes no output and is not written here.
Leaf layout: linear weights are (in, out), the head is (hidden, vocab).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


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
    degree: int = 2
    retention_eps: float = 1e-6

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c["tie_word_embeddings"]:
            raise ValueError("the head is a matrix of its own")
        mixer = c.get("retention", {})
        if mixer.get("degree", 2) != 2:
            raise ValueError("degree 2 is what is written")
        return cls(hidden=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ffn=c["intermediate_size"],
                   vocab=c["vocab_size"], theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   retention_eps=float(mixer.get("eps", 1e-6)))


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


def matmul(x, w, mode: str):
    """``x @ w`` in the arithmetic ``mode`` names, float32 out."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    elif mode != "f32":
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


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


def retention(q, k, v, log_g, eps: float, block: int = QUERY_BLOCK):
    """The attention form on one sequence: ``q`` (T, H, d), ``k``, ``v``
    (T, KV, d), ``log_g`` (T, KV) -> (T, H, d). Query head ``j`` reads
    key-value head ``j // (H / KV)``."""
    t, h, d = q.shape
    kv = k.shape[1]
    cum = jnp.cumsum(log_g, axis=0)                              # (T, KV)
    block = min(block, t)
    pad = -t % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, kv, h // kv, d)
    cp = jnp.pad(cum, ((0, pad), (0, 0))).reshape(-1, block, kv)
    at = jnp.arange(t + pad).reshape(-1, block)
    keys = jnp.arange(t)

    def one(inp):
        qb, cb, ib = inp
        s = jnp.einsum("ikrd,jkd->krij", qb, k,
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        keep = keys[None, :] <= ib[:, None]                     # (i, j)
        diff = cb.T[:, :, None] - cum.T[:, None, :]          # (kv, i, j)
        decay = jnp.where(keep, jnp.exp(jnp.where(keep, diff, 0.0)), 0.0)
        w = jnp.square(s) * decay[:, None]
        num = jnp.einsum("krij,jkd->ikrd", w, v, precision=HIGHEST)
        den = jnp.moveaxis(jnp.sum(w, axis=-1), 2, 0)      # (i, kv, r)
        return num / (den[..., None] + eps)

    y = jax.lax.map(one, (qp, cp, at))
    return y.reshape(t + pad, h, d)[:t]


def mixer(x, w, p: str, dims: Dims, mode: str):
    """The power-retention mixer on one sequence ``x`` (T, hidden)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    hd = dims.head_dim
    q = matmul(x, w[p + "q_proj.weight"], mode).reshape(t, dims.heads, hd)
    k = matmul(x, w[p + "k_proj.weight"], mode).reshape(
        t, dims.kv_heads, hd)
    v = matmul(x, w[p + "v_proj.weight"], mode).reshape(
        t, dims.kv_heads, hd)
    q = rope(rms_norm(q, w[p + "q_norm.weight"], dims.eps), pos,
             dims.theta)
    k = rope(rms_norm(k, w[p + "k_norm.weight"], dims.eps), pos,
             dims.theta)
    log_g = jax.nn.log_sigmoid(matmul(x, w[p + "gate_proj.weight"], mode))
    y = retention(q, k, v, log_g, dims.retention_eps)
    return matmul(y.reshape(t, -1), w[p + "out_proj.weight"], mode)


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """Block ``i`` on one sequence ``x`` (T, hidden); its leaves are
    named ``blocks.<i>.*``."""
    p = f"blocks.{i}."
    x = x + mixer(rms_norm(x, w[p + "norm1.weight"], dims.eps), w,
                  p + "mixer.", dims, mode)
    u = rms_norm(x, w[p + "norm2.weight"], dims.eps)
    g = matmul(u, w[p + "mlp.gate.weight"], mode)
    up = matmul(u, w[p + "mlp.up.weight"], mode)
    return x + matmul(jax.nn.silu(g) * up, w[p + "mlp.down.weight"], mode)


def head(x, w, dims: Dims, mode: str):
    return matmul(rms_norm(x, w["norm_f.weight"], dims.eps), w["lm_head"],
                  mode)


def logits(tokens, w, dims: Dims, mode: str = "f32"):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``."""
    x = w["embed.weight"].astype(jnp.float32)[tokens]
    for i in range(dims.layers):
        x = layer(x, w, i, dims, mode)
    return head(x, w, dims, mode)


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple]):
    """Logits at ``positions`` (B, P) of (B, T) ``tokens``, holding one
    layer's leaves at a time: ``get(shapes)`` makes the named leaves.
    Sequences are independent and every layer is causal, so padding a
    row's tail changes nothing at earlier positions."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    x = _embed(tokens, emb["embed.weight"])
    del emb
    for i in range(dims.layers):
        # one program serves every layer: its leaves go in as block 0's
        w = {k.replace(f"blocks.{i}.", "blocks.0."): a
             for k, a in get(shapes_of_layer(i)).items()}
        x = _layer_rows(x, w, dims, mode)
        del w
    w = get({k: s for k, s in top_shapes.items() if k != "embed.weight"})
    return _head_rows(x, positions, w, dims, mode)


@jax.jit
def _embed(tokens, table):
    # the rows first, then float32: the table whole in float32 is 3 GB
    return table[tokens].astype(jnp.float32)


def _layer_rows_impl(x, w, dims, mode):
    return jax.lax.map(lambda r: layer(r, w, 0, dims, mode), x)


_layer_rows = jax.jit(_layer_rows_impl, static_argnums=(2, 3))


def _head_rows_impl(x, positions, w, dims, mode):
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return head(picked, w, dims, mode)


_head_rows = jax.jit(_head_rows_impl, static_argnums=(3, 4))
