"""The plain reference of the ``hybrid_moe`` family: a decoder whose
layers mix tokens by a Mamba-2 state-space recurrence or by attention
without positions, each followed by top-k routed experts and a shared
gated MLP, in jax.numpy.

The equations are those of the published ``granitemoehybrid`` modelling
code (``h`` the residual stream, ``m`` = ``residual_multiplier``)::

    h = E[ids] * embedding_multiplier
    h = h + m * Mixer(RMSNorm(h));  u = RMSNorm(h)
    h = h + m * (MoE(u) + Shared(u))
    logits = RMSNorm(h) @ E^T / logits_scaling

- attention: GQA, no positional encoding, scores times
  ``attention_multiplier``, causal;
- Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC) + b)``,
  a depthwise causal convolution of kernel ``conv``; ``xBC`` split into
  ``x`` (H, P), ``B`` (N), ``C`` (N) (one group); ``dt = softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t (x) B_t``; ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))``
  over the whole inner width; ``out = y W_out``. Written as a
  ``lax.scan`` over positions: no chunked form, no cache;
- experts: 72-wide router logits in float32, the ``top_k`` largest
  (ties to the lowest index, as ``lax.top_k``), gates = softmax over
  those; ``e(u) = (silu(u Wg) * (u Wu)) Wd``; a masked loop over the
  experts held here, ``(first, count)`` of the router's width: what the
  absent experts would add is left out, as in the program.

Float32 throughout with ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching tricks. Imports nothing of the program.
``mode`` selects the arithmetic of every matrix multiplication, as in
``decoder_f32``: ``"f32"`` the reference, ``"fp8"`` the control (inputs
rounded to float8 e4m3); the recurrence itself stays float32 in both.

Departures from the published code: leaf layout (linear weights are
(in, out); the fused ``input_linear`` of experts and shared MLP is kept
as separate gate and up matrices; the convolution's weight is (kernel,
channels)); ``A_log``, ``dt_bias``, ``D``, the convolution's bias and
the norm scales hold what the harness's three leaf rules give them
(``families/hybrid_moe.py::leaf_rule``), not the published
initialisation; ``time_step_limit`` (0, inf) clamps nothing and is left
out.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layer_types: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    shared_width: int
    experts: int                 # the router's width
    top_k: int
    held: Tuple[int, int]        # (first, count) of the experts held
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_conv: int
    ssm_chunk: int
    vocab: int
    eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.ssm_state

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        """``layer_types`` as the file lists them; the router's width is
        the published ``num_local_experts`` where ``reduced`` names the
        key (the file then gives the number held and ``first``)."""
        cut = {r["key"]: r for r in c.get("reduced", [])}
        row = cut.get("num_local_experts")
        held = c["num_local_experts"]
        experts = row["published"] if row else held
        first = row.get("first", 0) if row else 0
        if c["mamba_n_groups"] != 1:
            raise ValueError("one group of B and C is what is written")
        if c["mamba_expand"] * c["hidden_size"] != (
                c["mamba_n_heads"] * c["mamba_d_head"]):
            raise ValueError("mamba_expand x hidden_size differs from "
                             "mamba_n_heads x mamba_d_head")
        if not c["tie_word_embeddings"]:
            raise ValueError("the head is the embedding, transposed")
        return cls(
            hidden=c["hidden_size"],
            layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            expert_width=c["intermediate_size"],
            shared_width=c["shared_intermediate_size"],
            experts=experts, top_k=c["num_experts_per_tok"],
            held=(first, held), ssm_heads=c["mamba_n_heads"],
            ssm_head_dim=c["mamba_d_head"], ssm_state=c["mamba_d_state"],
            ssm_conv=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
            vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
            embedding_multiplier=float(c["embedding_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            logits_scaling=float(c["logits_scaling"]))


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


def attention(x, w, p: str, dims: Dims, mode: str):
    """Causal GQA attention of one sequence ``x`` (T, hidden), no
    positional encoding, scores times ``attention_multiplier``."""
    t = x.shape[0]
    q = matmul(x, w[p + "q_proj.weight"], mode).reshape(
        t, dims.heads, dims.head_dim)
    k = matmul(x, w[p + "k_proj.weight"], mode).reshape(
        t, dims.kv_heads, dims.head_dim)
    v = matmul(x, w[p + "v_proj.weight"], mode).reshape(
        t, dims.kv_heads, dims.head_dim)
    rep = dims.heads // dims.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=HIGHEST) * dims.attention_multiplier
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    pr = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    return matmul(a.reshape(t, -1), w[p + "out_proj.weight"], mode)


def mamba(x, w, p: str, dims: Dims, mode: str):
    """The Mamba-2 mixer on one sequence ``x`` (T, hidden): the
    recurrence as a scan over positions from a zero state."""
    t = x.shape[0]
    hh, pp, n, k = (dims.ssm_heads, dims.ssm_head_dim, dims.ssm_state,
                    dims.ssm_conv)
    zxd = matmul(x, w[p + "in_proj.weight"], mode)
    z = zxd[:, :dims.inner]
    xbc = zxd[:, dims.inner:dims.inner + dims.conv_dim]
    dt = jax.nn.softplus(zxd[:, dims.inner + dims.conv_dim:]
                         + w[p + "dt_bias"].astype(jnp.float32))
    cw = w[p + "conv_weight"].astype(jnp.float32)            # (k, C)
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, dims.conv_dim), jnp.float32), xbc])
    xbc = sum(padded[i:i + t] * cw[i] for i in range(k)) + w[
        p + "conv_bias"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :dims.inner].reshape(t, hh, pp)
    B = xbc[:, dims.inner:dims.inner + n]
    C = xbc[:, dims.inner + n:]
    A = -jnp.exp(w[p + "A_log"].astype(jnp.float32))          # (H,)
    D = w[p + "D"].astype(jnp.float32)

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", S, c_t, precision=HIGHEST)
        return S, y + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((hh, pp, n), jnp.float32),
                        (xs, dt, B, C))
    y = rms_norm(y.reshape(t, dims.inner) * jax.nn.silu(z),
                 w[p + "norm.weight"], dims.eps)
    return matmul(y, w[p + "out_proj.weight"], mode)


def gated(u, gate, up, down, mode: str):
    return matmul(jax.nn.silu(matmul(u, gate, mode)) * matmul(u, up, mode),
                  down, mode)


def experts(u, w, p: str, dims: Dims, mode: str):
    """What the held experts add for tokens ``u`` (T, hidden): route
    over the router's whole width, then one expert at a time, each on
    every token and weighted by the gate of the tokens that picked it
    (zero for the others)."""
    logits = matmul(u, w[p + "router.weight"], mode)
    top_l, top_i = jax.lax.top_k(logits, dims.top_k)
    gates = jax.nn.softmax(top_l, axis=-1)
    first, count = dims.held
    out = jnp.zeros_like(u)
    for e in range(count):
        g = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), axis=-1)
        out = out + g[:, None] * gated(
            u, w[p + "w_gate"][e], w[p + "w_up"][e], w[p + "w_down"][e],
            mode)
    return out


def layer(x, w: Dict[str, jax.Array], i: int, dims: Dims, mode: str):
    """Block ``i`` on one sequence ``x`` (T, hidden); its kind is
    ``dims.layer_types[i]``, its leaves are named ``blocks.<i>.*``."""
    p = f"blocks.{i}."
    h = rms_norm(x, w[p + "norm1.weight"], dims.eps)
    mix = (mamba if dims.layer_types[i] == "mamba" else attention)(
        h, w, p + "mixer.", dims, mode)
    x = x + dims.residual_multiplier * mix
    u = rms_norm(x, w[p + "norm2.weight"], dims.eps)
    add = experts(u, w, p + "moe.", dims, mode) + gated(
        u, w[p + "shared.gate.weight"], w[p + "shared.up.weight"],
        w[p + "shared.down.weight"], mode)
    return x + dims.residual_multiplier * add


def embed(tokens, table, dims: Dims):
    return table.astype(jnp.float32)[tokens] * dims.embedding_multiplier


def head(x, w, dims: Dims, mode: str):
    x = rms_norm(x, w["norm_f.weight"], dims.eps)
    return matmul(x, w["embed.weight"].T, mode) / dims.logits_scaling


def logits(tokens, w, dims: Dims, mode: str = "f32", remat: bool = False):
    """(T,) tokens -> (T, vocab) logits, all weights in ``w``."""
    x = embed(tokens, w["embed.weight"], dims)
    step = (jax.checkpoint(layer, static_argnums=(2, 3, 4)) if remat
            else layer)
    for i in range(dims.layers):
        x = step(x, w, i, dims, mode)
    return head(x, w, dims, mode)


def loss(w, batch, dims: Dims, mode: str = "f32", remat: bool = False):
    """Mean next-token cross-entropy of (B, T) ``batch``, a mean over
    rows that do not see each other (no balance term, no capacity)."""
    def row(tokens):
        lp = jax.nn.log_softmax(logits(tokens, w, dims, mode, remat)[:-1])
        return -jnp.sum(jnp.take_along_axis(lp, tokens[1:, None], 1))
    total = jnp.sum(jax.lax.map(row, batch))
    return total / (batch.shape[0] * (batch.shape[1] - 1))


def layerwise_logits(tokens, positions, dims: Dims, mode: str,
                     get: Callable[[Dict[str, tuple]], Dict[str, jax.Array]],
                     shapes_of_layer: Callable[[int], Dict[str, tuple]],
                     top_shapes: Dict[str, tuple]):
    """Logits at ``positions`` (B, P) of (B, T) ``tokens``, holding one
    layer's leaves at a time: ``get(shapes)`` makes the named leaves.
    Sequences are independent and every layer is causal, so padding a
    row's tail changes nothing at earlier positions. One program a kind
    of layer: a layer's leaves go in under the first index of its kind."""
    emb = get({"embed.weight": top_shapes["embed.weight"]})
    x = _embed(tokens, emb["embed.weight"], dims)
    for i in range(dims.layers):
        j = dims.layer_types.index(dims.layer_types[i])
        w = {k.replace(f"blocks.{i}.", f"blocks.{j}."): a
             for k, a in get(shapes_of_layer(i)).items()}
        x = _layer_rows(x, w, j, dims, mode)
        del w
    w = get({k: s for k, s in top_shapes.items() if k != "embed.weight"})
    w.update(emb)
    return _head_rows(x, positions, w, dims, mode)


_embed = jax.jit(embed, static_argnums=2)


def _layer_rows_impl(x, w, j, dims, mode):
    return jax.lax.map(lambda r: layer(r, w, j, dims, mode), x)


_layer_rows = jax.jit(_layer_rows_impl, static_argnums=(2, 3, 4))


def _head_rows_impl(x, positions, w, dims, mode):
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    return head(picked, w, dims, mode)


_head_rows = jax.jit(_head_rows_impl, static_argnums=(3, 4))
