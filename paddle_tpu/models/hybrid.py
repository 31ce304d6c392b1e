"""Hybrid state-space / attention causal LM with routed experts.

A decoder whose blocks differ by index: ``layer_types[i]`` says whether
block ``i`` mixes tokens by a Mamba-2 (SSD) state-space layer or by GQA
attention without positional encoding, and every block, whichever its
mixer, is followed by a dropless top-k expert layer plus one shared
gated MLP. Three constant multipliers scale the embedding, each residual
branch and the attention scores, and the logits are divided by a fourth
(the muP-style parametrisation published with such models)::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    u = RMSNorm(h)
    h = h + residual_multiplier * (Experts(u) + Shared(u))
    logits = RMSNorm(h) @ E^T / logits_scaling

What decoding keeps a sequence differs by block: an attention block
keeps keys and values by position, (K, V) of shape (slots, capacity,
kv_heads, head_dim); a state-space block keeps a state of fixed size,
(convolution tail (slots, conv - 1, channels), S (slots, heads,
head_dim, state) float32). :meth:`HybridForCausalLM.init_cache` gives
the list, one entry a block, and ``cache_kinds`` says which is which;
``serving.BatchedDecoder`` holds it as its arena. The expert layer is
told which experts it holds (``experts_held``), as one chip of an
expert-parallel deployment is (``nn.DroplessMoE``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dtypes import default_dtype
from ..core.enforce import enforce
from ..nn.layer import Layer
from ..ops import ssm


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 32000
    hidden_size: int = 1024
    layer_types: Tuple[str, ...] = ("mamba", "attention")
    num_heads: int = 8
    num_kv_heads: Optional[int] = None
    expert_width: int = 256              # one routed expert's gated width
    shared_width: int = 512              # the always-on gated MLP's width
    num_experts: int = 8                 # the router's width
    experts_per_token: int = 2
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    ssm_heads: int = 32
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None     # None: 1/sqrt(hd)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    use_flash: bool = True

    @classmethod
    def tiny(cls, periods: int = 1):
        """For tests: ``periods`` x (mamba, mamba, attention), hidden
        64, 4 q / 2 kv heads of 16, 8 state-space heads of 16 with
        state 16, 12 experts of width 32, 4 a token."""
        return cls(vocab_size=256, hidden_size=64,
                   layer_types=("mamba", "mamba", "attention") * periods,
                   num_heads=4, num_kv_heads=2, expert_width=32,
                   shared_width=48, num_experts=12, experts_per_token=4,
                   ssm_heads=8, ssm_head_dim=16, ssm_state=16,
                   ssm_chunk=8, embedding_multiplier=12.0,
                   attention_multiplier=1.0 / 16, residual_multiplier=0.22,
                   logits_scaling=16.0)


class GatedMLP(Layer):
    """down(silu(gate(x)) * up(x)), no biases."""

    def __init__(self, d_model: int, d_ff: int):
        super().__init__()
        self.gate = nn.Linear(d_model, d_ff, bias_attr=False)
        self.up = nn.Linear(d_model, d_ff, bias_attr=False)
        self.down = nn.Linear(d_ff, d_model, bias_attr=False)

    def forward(self, x):
        return self.down(jax.nn.silu(self.gate(x)) * self.up(x))


class SSDMixer(Layer):
    """Mamba-2 mixer: one input projection to a gate ``z``, the
    convolved stream ``xBC`` and the step sizes ``dt``; a depthwise
    causal convolution (kernel ``conv``, with bias) and SiLU on ``xBC``;
    the selective state-space recurrence (``ops/ssm.py``) with one group
    of B and C; RMSNorm of ``y * silu(z)`` over the whole inner width;
    an output projection. Decays and the state are float32."""

    state_kind = "recurrent"

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        self.heads, self.head_dim, self.state = h, p, n
        self.inner = h * p
        self.conv_dim = self.inner + 2 * n
        self.conv, self.chunk = cfg.ssm_conv, cfg.ssm_chunk
        self.in_proj = nn.Linear(cfg.hidden_size,
                                 self.inner + self.conv_dim + h,
                                 bias_attr=False)
        steps = jnp.linspace(0.0, 1.0, h)
        self.create_parameter(
            "conv_weight", (self.conv, self.conv_dim), None)
        self.create_parameter("conv_bias", (self.conv_dim,), None,
                              is_bias=True)
        # dt in 0.001 .. 0.1 at a zero projection, A in -1 .. -16
        self.create_parameter(
            "dt_bias", (h,), None, lambda k, s, d: jnp.log(jnp.expm1(
                jnp.exp(jnp.log(1e-3) + steps * jnp.log(100.0)))).astype(d))
        self.create_parameter(
            "A_log", (h,), None,
            lambda k, s, d: jnp.log(1.0 + 15.0 * steps).astype(d))
        self.create_parameter("D", (h,), None,
                              lambda k, s, d: jnp.ones(s, d))
        self.norm = nn.RMSNorm(self.inner, epsilon=cfg.rms_norm_eps)
        self.out_proj = nn.Linear(self.inner, cfg.hidden_size,
                                  bias_attr=False)

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """(convolution tail, state) of a sequence that has seen no
        token; ``capacity`` does not size it."""
        return (jnp.zeros((batch, self.conv - 1, self.conv_dim),
                          dtype or default_dtype()),
                jnp.zeros((batch, self.heads, self.head_dim, self.state),
                          jnp.float32))

    def _project(self, x):
        zxd = self.in_proj(x)
        z = zxd[..., :self.inner]
        xbc = zxd[..., self.inner:self.inner + self.conv_dim]
        dt = jax.nn.softplus(zxd[..., self.inner + self.conv_dim:].astype(
            jnp.float32) + self.dt_bias.astype(jnp.float32))
        return z, xbc, dt

    def _split(self, xbc):
        lead = xbc.shape[:-1]
        return (xbc[..., :self.inner].reshape(*lead, self.heads,
                                              self.head_dim),
                xbc[..., self.inner:self.inner + self.state],
                xbc[..., self.inner + self.state:])

    def _finish(self, y, z):
        y = y.reshape(*z.shape).astype(z.dtype) * jax.nn.silu(z)
        return self.out_proj(self.norm(y))

    def forward_chunk(self, x, cache, valid_len=None):
        """``x`` (B, S, D) continuing ``cache``; only the first
        ``valid_len`` positions (default all) advance it. Returns
        (out (B, S, D), new cache)."""
        tail, state = cache
        with jax.named_scope("ssm_scan"):
            z, xbc, dt = self._project(x)
            xbc, new_tail = ssm.causal_conv1d(
                xbc, self.conv_weight, self.conv_bias, tail, valid_len)
            tail = new_tail.astype(tail.dtype)
            xs, B, C = self._split(jax.nn.silu(xbc))
            y, state = ssm.ssd_chunked(
                xs, dt, -jnp.exp(self.A_log.astype(jnp.float32)), B, C,
                self.D, self.chunk, state, valid_len)
            return self._finish(y, z), (tail, state)

    def forward_step(self, x, cache):
        """One position a row: ``x`` (B, 1, D) -> (out (B, 1, D), new
        cache)."""
        tail, state = cache
        with jax.named_scope("ssm_step"):
            z, xbc, dt = self._project(x[:, 0])
            xbc, new_tail = ssm.causal_conv1d_step(
                xbc, self.conv_weight, self.conv_bias, tail)
            tail = new_tail.astype(tail.dtype)
            xs, B, C = self._split(jax.nn.silu(xbc))
            y, state = ssm.ssd_step(
                xs, dt, -jnp.exp(self.A_log.astype(jnp.float32)), B, C,
                self.D, state)
            return self._finish(y, z)[:, None], (tail, state)

    def forward(self, x):
        return self.forward_chunk(x, self.init_cache(x.shape[0], 0,
                                                     x.dtype))[0]


class HybridBlock(Layer):
    """h + m Mixer(norm(h)); then + m (Experts(u) + Shared(u)), u the
    second norm. ``kind`` chooses the mixer."""

    def __init__(self, cfg: HybridConfig, kind: str):
        super().__init__()
        enforce(kind in ("mamba", "attention"),
                "layer type %r is neither 'mamba' nor 'attention'", kind)
        self.kind, self.m = kind, float(cfg.residual_multiplier)
        self.norm1 = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if kind == "mamba":
            self.mixer = SSDMixer(cfg)
        else:
            self.mixer = nn.MultiHeadAttention(
                cfg.hidden_size, cfg.num_heads, bias=False,
                use_flash=cfg.use_flash,
                num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
                rotary=False, scale=cfg.attention_multiplier)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.moe = nn.DroplessMoE(
            cfg.hidden_size, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, experts_held=cfg.experts_held)
        self.shared = GatedMLP(cfg.hidden_size, cfg.shared_width)

    @property
    def state_kind(self) -> str:
        return getattr(self.mixer, "state_kind", "kv")

    def channel_mix(self, x):
        """(x + m (Experts(u) + Shared(u)), the (held,) tokens each held
        expert got)."""
        u = self.norm2(x)
        routed, tokens = self.moe.forward_counted(u)
        with jax.named_scope("moe_shared"):
            shared = self.shared(u)
        return x + self.m * (routed + shared), tokens

    def forward(self, x):
        h = self.norm1(x)
        a = (self.mixer(h) if self.kind == "mamba"
             else self.mixer(h, causal=True))
        return self.channel_mix(x + self.m * a)[0]


class HybridForCausalLM(Layer):
    """Embedding -> blocks by ``cfg.layer_types`` -> RMSNorm -> tied
    head. ``forward(ids)`` gives (B, T, V) logits from empty state; the
    ``_chunk_logits`` / ``_step_logits`` / ``_step_logits_rows`` entries
    are what ``serving.BatchedDecoder`` calls, over the cache list
    :meth:`init_cache` gives."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([HybridBlock(cfg, kind)
                                    for kind in cfg.layer_types])
        self.norm_f = nn.RMSNorm(cfg.hidden_size,
                                 epsilon=cfg.rms_norm_eps)
        self.cache_kinds = [blk.state_kind for blk in self.blocks]
        self._expert_tokens = self._expert_dense_layers = None

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """One pytree a block, every leaf with the sequence (slot) axis
        first: (K, V) for attention, (tail, S) for a state-space block."""
        return [blk.mixer.init_cache(batch, capacity, dtype)
                for blk in self.blocks]

    def step_counters(self):
        """What the latest cached call counted, for the program that
        made the call to return: ``expert_tokens`` (held,) int32, the
        (token, pick) pairs each held expert got, summed over blocks;
        ``expert_dense_layers`` int32, the expert layers of the call
        whose rows took the dense body of ``nn.moe.dropless_moe`` (the
        trace fixes it). Valid only inside the trace of that call."""
        return {"expert_tokens": self._expert_tokens,
                "expert_dense_layers": self._expert_dense_layers}

    def _embed(self, ids):
        e = self.embed(ids)
        return e * jnp.asarray(self.cfg.embedding_multiplier, e.dtype)

    def _head(self, x):
        logits = self.norm_f(x) @ self.embed.weight.T
        return logits / jnp.asarray(self.cfg.logits_scaling, logits.dtype)

    def forward(self, ids):
        x = self._embed(ids)
        for blk in self.blocks:
            x = blk(x)
        return self._head(x)

    def forward_loss(self, ids, labels=None, ignore_index: int = -100):
        """Mean next-token cross-entropy (plain, unfused: the training
        path of this model is not tuned)."""
        from .gpt import loss_fn

        if labels is None:
            labels = jnp.concatenate(
                [ids[:, 1:],
                 jnp.full((ids.shape[0], 1), ignore_index, ids.dtype)],
                axis=1)
        return loss_fn(self.forward(ids), labels, ignore_index)

    def _cached_blocks(self, x, caches, attn_step, ssm_step,
                       head: bool = True):
        """The cached block composition, written once over the mixed
        block list: ``attn_step(mixer, h, k, v) -> (a, k, v)`` and
        ``ssm_step(mixer, h, cache) -> (a, cache)`` are all that vary
        between the chunk, single-step and per-row entries."""
        new_caches, tokens = [], 0
        for blk, cache in zip(self.blocks, caches):
            h = blk.norm1(x)
            if blk.kind == "mamba":
                a, cache = ssm_step(blk.mixer, h, cache)
            else:
                a, ck, cv = attn_step(blk.mixer, h, *cache)
                cache = (ck, cv)
            x, got = blk.channel_mix(x + blk.m * a)
            tokens = tokens + got
            new_caches.append(cache)
        self._expert_tokens = tokens
        rows = x.shape[0] * x.shape[1]
        self._expert_dense_layers = jnp.int32(sum(
            blk.moe.streams_densely(rows) for blk in self.blocks))
        return (self._head(x) if head else None), new_caches

    def _chunk_logits(self, toks, caches, t0, head: bool = True,
                      decode_kernel: bool = False, valid_len=None):
        """S cached positions in one pass at cache indices [t0, t0+S):
        keys and values are written for the whole chunk, a recurrence
        advances over its first ``valid_len`` positions only."""
        return self._cached_blocks(
            self._embed(toks), caches,
            lambda sa, h, ck, cv: sa.forward_chunk(
                h, ck, cv, t0, decode_kernel=decode_kernel),
            lambda mx, h, c: mx.forward_chunk(h, c, valid_len),
            head=head)

    def _step_logits(self, tok, caches, t, decode_kernel: bool = False):
        """One cached position: ``tok`` (B,) -> ((B, V), caches)."""
        logits, caches = self._cached_blocks(
            self._embed(tok[:, None]), caches,
            lambda sa, h, ck, cv: sa.forward_step(
                h, ck, cv, t, decode_kernel=decode_kernel),
            lambda mx, h, c: mx.forward_step(h, c))
        return logits[:, 0], caches

    def _step_logits_rows(self, tok, caches, t_rows,
                          decode_kernel: bool = False):
        """One cached position PER ROW at per-row cursors ``t_rows``
        (the continuous-batching step): a state has no cursor, so only
        the attention blocks read ``t_rows``."""
        logits, caches = self._cached_blocks(
            self._embed(tok[:, None]), caches,
            lambda sa, h, ck, cv: sa.forward_step_rows(
                h, ck, cv, t_rows, decode_kernel=decode_kernel),
            lambda mx, h, c: mx.forward_step(h, c))
        return logits[:, 0], caches
