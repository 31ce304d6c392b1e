"""A causal-LM shell whose blocks are built from kinds: a token mixer
and a channel mix a block, chosen by the configuration.

``layer_types[i]`` names block ``i``'s MIXER, a key of :data:`MIXERS`:

- ``"mamba"``: :class:`SSDMixer`, a Mamba-2 (SSD) state-space layer;
- ``"attention"``: :class:`SoftmaxMixer`, GQA softmax attention without
  positional encoding (an ``nn.MultiHeadAttention``);
- ``"retention"``: :class:`RetentionMixer`, power retention of degree
  2, linear attention whose score is the square of a dot product;
- ``"latent"``: ``nn.LatentAttention``, multi-head latent attention:
  one compressed record a position for all heads, decompressed in a
  prefill and read absorbed by a decode step; with ``index_topk`` a
  learned indexer picks the positions each query attends;
- ``"full_attention"`` / ``"sliding_attention"``: ``nn.GatedAttention``,
  GQA softmax attention with a head width of its own (``attn_head_dim``),
  query heads and a rotary embedding by kind (``attn_heads``,
  ``attn_rope``), one sigmoid gate a head (``attn_gate``); the sliding
  kind attends the last ``sliding_window`` positions, and its cache is
  a ring that long whatever the capacity.

**A mixer is one convention**, and the shell knows no kind by name. A
mixer is built from the configuration (``MIXERS[kind](cfg)``, reading
its own fields) and answers, whether or not it reads an argument:

- ``init_cache(batch, capacity, dtype) -> cache``: ONE pytree, every
  leaf with the sequence (slot) axis first, handed to the entries below
  whole and handed back whole;
- ``mixer(h) -> a``: causal, from empty state (training, ``forward``);
- ``forward_chunk(h, cache, t0, valid_len, decode_kernel) -> (a,
  cache)``: S positions at cache indices [t0, t0 + S), of which a
  recurrence advances over the first ``valid_len`` only;
- ``forward_step(h, cache, t, decode_kernel)`` and
  ``forward_step_rows(h, cache, t_rows, decode_kernel) -> (a, cache)``:
  one position a row at one cursor, or at per-row cursors (B,);
- class attributes: ``state_kind`` (``"kv"``, addressed by position, or
  ``"recurrent"``, a state of fixed size), ``cache_record`` (what a
  ``"kv"`` cache holds: ``"heads"``, keys and values by head for
  ``capacity`` positions, ``"ring"``, the same for a window's positions
  alone, written round and round, or ``"latent"``; None for a state),
  ``cached_scope`` / ``empty_scope``
  (the scope its whole sublayer runs under in a cached call and from
  empty state, or None: the mixer then enters scopes of its own around
  itself alone, and the norms and adds beside it are under none);
- ``counted``: the names and int32 values its latest cached call
  counted ({} for a mixer that counts nothing). The residual path
  reports the same way, and the shell sums by name
  (:meth:`HybridForCausalLM.step_counters`).

``channel_mix`` names what follows the mixer, in every block (one
name) or block by block (one name a block, as ``layer_types``):

- ``"experts"``: a dropless top-k expert layer plus one shared gated
  MLP (``nn.DroplessMoE`` + :class:`GatedMLP`), told which experts it
  holds (``experts_held``), as one chip of an expert-parallel
  deployment is, under the routing rule ``routing``
  (``nn.DroplessMoE.ROUTING``); with ``router_bias_update_rate`` > 0
  a training call moves the router's selection bias by the
  auxiliary-loss-free rule (``nn.DroplessMoE.bias_update``);
- ``"mlp"``: one gated MLP of width ``mlp_width`` (SwiGLU).

The RESIDUAL PATH a block's two sublayers are written over is an
object with ``read(X) -> (u, held)`` and ``write(X, y, held) -> X'``:
``nn.latent.PlainResidual`` (``hc_mult`` 1: one stream, ``x + m
F(norm(x))``) or, with ``hc_mult`` > 1, ``nn.latent.HyperConnection``
(manifold-constrained hyper-connections: the state of a position is
``hc_mult`` streams, (B, S, hc_mult, hidden), read in as copies of the
embedding and read out as their sum; each sublayer has maps of its
own, computed in float32, and the state stays float32 between
sublayers).

Three constant multipliers scale the embedding, each residual branch
and the attention scores, and the logits are divided by a fourth (the
muP-style parametrisation published with some such models; all 1 by
default); the head is the embedding transposed or, with
``tie_embeddings=False``, a matrix of its own (``lm_head``)::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    u = RMSNorm(h)
    h = h + residual_multiplier * ChannelMix(u)
    logits = RMSNorm(h) @ W_head / logits_scaling

(the plain path; with hyper-connections ``u, held = read(X)``, ``X =
write(X, F(RMSNorm(u)), held)`` around each sublayer).

What decoding keeps a sequence is the mixer's own, and its
``init_cache`` says what: keys and values by position, a state of fixed
size (retention's is 34 MB a slot at head dimension 128 whatever the
context), or one compressed record a position.
:meth:`HybridForCausalLM.init_cache` gives the list, one entry a block,
``cache_kinds`` and ``cache_records`` are the blocks' ``state_kind``
and ``cache_record``, and ``serving.BatchedDecoder`` holds the list as
its arena.

TRAINING goes through :meth:`HybridForCausalLM.forward_loss` under the
one ``parallel.Trainer``, as ``models/gpt.py``'s does: the blocks from
empty state (``remat``: each under ``jax.checkpoint``, which keeps a
block's input and its flash kernel's ``o`` and ``lse`` and recomputes the
rest), the fused linear-CE head, the routers' new state as buffers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from .. import initializer as I
from .. import nn
from ..core.dtypes import default_dtype
from ..core.enforce import enforce
from ..nn.gated_attention import GatedAttention
from ..nn.latent import HyperConnection, LatentAttention, PlainResidual
from ..nn.layer import Layer
from ..ops import retention, ssm
from ..ops.attention import rotary_embedding
from ..telemetry.scopes import scope
from .gpt import next_token_loss

CHANNEL_MIXES = ("experts", "mlp")


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 32000
    hidden_size: int = 1024
    layer_types: Tuple[str, ...] = ("mamba", "attention")
    num_heads: int = 8
    num_kv_heads: Optional[int] = None
    # "experts" or "mlp": every block's, or one name a block
    channel_mix: Union[str, Tuple[str, ...]] = "experts"
    mlp_width: int = 0                   # the "mlp" channel mix's width
    expert_width: int = 256              # one routed expert's gated width
    shared_width: int = 512              # the always-on gated MLP's width
    num_experts: int = 8                 # the router's width
    experts_per_token: int = 2
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    routing: str = "topk_softmax"        # nn.DroplessMoE.ROUTING
    routed_scaling_factor: float = 1.0   # "sigmoid_noaux_tc" gates' sum
    # "sigmoid_noaux_tc": how far a training call moves each selection
    # bias towards the mean load (0: the bias stays where it is)
    router_bias_update_rate: float = 0.0
    ssm_heads: int = 32
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    rope_theta: float = 10000.0          # retention's, latent's rotary
    # the latent mixer: ranks and head widths, YaRN (the keyword
    # arguments of ops.attention.yarn_frequencies) and its mscale_all_dim
    q_lora_rank: Optional[int] = 0       # 0 / None: queries from x directly
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[dict] = None
    rope_mscale_all_dim: float = 1.0
    # the latent mixer's indexer (all 0: none): a query attends the
    # index_topk positions its index_n_heads heads of index_head_dim pick
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the "full_attention" / "sliding_attention" mixers: the head width
    # (0: hidden / heads), query heads by kind (None: num_heads), the
    # sliding kind's window, the rotary embedding by kind (the keyword
    # arguments of nn.GatedAttention after ``window``: rope_theta,
    # rotary_dim, yarn, attention_factor) and the gate a head
    attn_head_dim: int = 0
    attn_heads: Optional[dict] = None
    sliding_window: int = 0
    attn_rope: Optional[dict] = None
    attn_gate: bool = False
    # hyper-connections: streams of the residual state (1: the plain
    # path), Sinkhorn rounds and epsilon, the clamp on H_res's logits
    hc_mult: int = 1
    settle_residual: bool = False        # nn.latent.PlainResidual's
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    retention_degree: int = 2            # the power of the score
    retention_eps: float = 1e-6          # added to the sum of weights
    retention_chunk: int = 128
    tie_embeddings: bool = True          # False: a head of its own
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None     # None: 1/sqrt(hd)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    use_flash: bool = True
    remat: bool = False  # keep a block's input + flash o, lse; recompute rest

    def channel_mixes(self) -> Tuple[str, ...]:
        """The channel mix of each block."""
        if isinstance(self.channel_mix, str):
            return (self.channel_mix,) * len(self.layer_types)
        enforce(len(self.channel_mix) == len(self.layer_types),
                "channel_mix names %s blocks, layer_types %s",
                len(self.channel_mix), len(self.layer_types))
        return tuple(self.channel_mix)

    @classmethod
    def tiny_latent(cls, layers: int = 3, dense: int = 1):
        """For tests: ``layers`` latent-attention blocks over 4 residual
        streams, the first ``dense`` with a gated MLP of 96 and the
        rest with 16 sigmoid-routed experts of width 24, 4 a token,
        scaled by 2, plus a shared MLP of 24; hidden 64, 4 heads of
        16 + 8 (scores) / 16 (values), ranks 24 and 32, YaRN by 4 over
        32 positions, an untied head."""
        return cls(vocab_size=256, hidden_size=64,
                   layer_types=("latent",) * layers, num_heads=4,
                   channel_mix=("mlp",) * dense
                   + ("experts",) * (layers - dense),
                   mlp_width=96, expert_width=24, shared_width=24,
                   num_experts=16, experts_per_token=4,
                   routing="sigmoid_noaux_tc", routed_scaling_factor=2.0,
                   q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16,
                   rope_yarn=dict(factor=4.0, original_max_position=32,
                                  beta_fast=32.0, beta_slow=1.0),
                   hc_mult=4, tie_embeddings=False, rms_norm_eps=1e-6)

    @classmethod
    def tiny_sparse_latent(cls, layers: int = 3, dense: int = 1,
                           topk: int = 8):
        """For tests: :meth:`tiny_latent`'s blocks over the plain
        residual path, without YaRN, with an indexer of 4 heads of 16
        that picks ``topk`` positions a query; heads of 16 + 8 (scores)
        / 24 (values)."""
        return dataclasses.replace(
            cls.tiny_latent(layers, dense), rope_yarn=None, hc_mult=1,
            v_head_dim=24, index_n_heads=4, index_head_dim=16,
            index_topk=topk)

    @classmethod
    def tiny_window(cls, periods: int = 2, held=(0, 4)):
        """For tests: ``periods`` x (full, sliding, sliding, sliding)
        attention, hidden 64, heads of 16 (6 query heads on a full
        layer, 8 on a sliding one, 2 key-value heads), a window of 8, a
        gate a head; the full layers turn the first 8 numbers of a head
        at YaRN's frequencies (by 4 over 16 positions, cosines times
        1.2), the sliding ones all 16 plainly; a gated MLP of 96 in the
        first block, then 16 sigmoid-routed experts of width 24, 4 a
        token, scaled by 2.5, of which ``held`` are here, plus a shared
        MLP of 24; an untied head."""
        kinds = ("full_attention",) + ("sliding_attention",) * 3
        return cls(vocab_size=256, hidden_size=64,
                   layer_types=kinds * periods, num_kv_heads=2,
                   channel_mix=("mlp",) + ("experts",) * (4 * periods - 1),
                   mlp_width=96, expert_width=24, shared_width=24,
                   num_experts=16, experts_per_token=4, experts_held=held,
                   routing="sigmoid_noaux_tc", routed_scaling_factor=2.5,
                   attn_head_dim=16, sliding_window=8, attn_gate=True,
                   attn_heads={"full_attention": 6, "sliding_attention": 8},
                   attn_rope={
                       "full_attention": dict(
                           rope_theta=500000.0, rotary_dim=8,
                           attention_factor=1.2,
                           yarn=dict(factor=4.0, original_max_position=16,
                                     beta_fast=8.0, beta_slow=1.0)),
                       "sliding_attention": dict(rope_theta=10000.0)},
                   tie_embeddings=False, rms_norm_eps=1e-6)

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

    @classmethod
    def tiny_retention(cls, layers: int = 3):
        """For tests: ``layers`` retention blocks with a gated MLP,
        hidden 80, 10 q / 2 kv heads of 8 (five query heads a key-value
        head, a state of 40 x 8 a head), MLP 96, an untied head."""
        return cls(vocab_size=256, hidden_size=80,
                   layer_types=("retention",) * layers, num_heads=10,
                   num_kv_heads=2, channel_mix="mlp", mlp_width=96,
                   retention_chunk=8, tie_embeddings=False,
                   rms_norm_eps=1e-6)


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
    an output projection. Decays and the state are float32. A state
    has no cursor: ``t0``, ``t`` and ``decode_kernel`` are not read."""

    state_kind, cache_record = "recurrent", None
    cached_scope = empty_scope = None
    counted = {}

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

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel: bool = False):
        """``x`` (B, S, D) continuing ``cache``; only the first
        ``valid_len`` positions (default all) advance it. Returns
        (out (B, S, D), new cache)."""
        tail, state = cache
        with scope("ssm_scan"):
            z, xbc, dt = self._project(x)
            xbc, new_tail = ssm.causal_conv1d(
                xbc, self.conv_weight, self.conv_bias, tail, valid_len)
            tail = new_tail.astype(tail.dtype)
            xs, B, C = self._split(jax.nn.silu(xbc))
            y, state = ssm.ssd_chunked(
                xs, dt, -jnp.exp(self.A_log.astype(jnp.float32)), B, C,
                self.D, self.chunk, state, valid_len)
            return self._finish(y, z), (tail, state)

    def forward_step(self, x, cache, t=None, decode_kernel: bool = False):
        """One position a row: ``x`` (B, 1, D) -> (out (B, 1, D), new
        cache)."""
        tail, state = cache
        with scope("ssm_step"):
            z, xbc, dt = self._project(x[:, 0])
            xbc, new_tail = ssm.causal_conv1d_step(
                xbc, self.conv_weight, self.conv_bias, tail)
            tail = new_tail.astype(tail.dtype)
            xs, B, C = self._split(jax.nn.silu(xbc))
            y, state = ssm.ssd_step(
                xs, dt, -jnp.exp(self.A_log.astype(jnp.float32)), B, C,
                self.D, state)
            return self._finish(y, z)[:, None], (tail, state)

    forward_step_rows = forward_step

    def forward(self, x):
        return self.forward_chunk(x, self.init_cache(x.shape[0], 0,
                                                     x.dtype))[0]


class RetentionMixer(Layer):
    """Power retention of degree 2 (``ops/retention.py``): q, k, v and
    gate projections without biases; RMSNorm over each head of queries
    and keys, then the rotary embedding; ``log g = logsigmoid(gate)``,
    one a key-value head, float32; the state's update and read
    (``retention_step``) or the chunked form over a sequence; an output
    projection. The state and the denominators are float32. A step
    counts ``retention_small_norm``: how many of its (row, head)
    denominators fell under ``10 eps`` (an idle slot's junk row counted
    too); a chunk counts none."""

    state_kind, cache_record = "recurrent", None
    cached_scope = empty_scope = None
    counted = {}

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        enforce(cfg.retention_degree == 2,
                "power retention of degree %s is not written: the state "
                "holds the symmetric square", cfg.retention_degree)
        h, self.heads = cfg.hidden_size, cfg.num_heads
        self.kv_heads = cfg.num_kv_heads or cfg.num_heads
        self.head_dim = hd = h // self.heads
        self.theta, self.eps = float(cfg.rope_theta), cfg.retention_eps
        self.chunk = cfg.retention_chunk
        self.q_proj = nn.Linear(h, self.heads * hd, bias_attr=False)
        self.k_proj = nn.Linear(h, self.kv_heads * hd, bias_attr=False)
        self.v_proj = nn.Linear(h, self.kv_heads * hd, bias_attr=False)
        self.gate_proj = nn.Linear(h, self.kv_heads, bias_attr=False)
        self.q_norm = nn.RMSNorm(hd, epsilon=cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(hd, epsilon=cfg.rms_norm_eps)
        self.out_proj = nn.Linear(self.heads * hd, h, bias_attr=False)

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """(S (B, kv_heads, D, head_dim), z (B, kv_heads, D)), ``D`` =
        ``ops.retention.phi_dim(head_dim)``, of a sequence that has seen
        no token: float32 whatever ``dtype``, and not sized by
        ``capacity``."""
        return retention.zero_state(batch, self.kv_heads, self.head_dim)

    def _project(self, x, positions):
        """``x`` (B, S, hidden) at ``positions`` (S,) or (B, S) -> q (B,
        S, H, d), k, v (B, S, KV, d), log g (B, S, KV) float32."""
        b, s, _ = x.shape
        q = self.q_norm(self.q_proj(x).reshape(b, s, self.heads,
                                               self.head_dim))
        k = self.k_norm(self.k_proj(x).reshape(b, s, self.kv_heads,
                                               self.head_dim))
        v = self.v_proj(x).reshape(b, s, self.kv_heads, self.head_dim)
        log_g = jax.nn.log_sigmoid(self.gate_proj(x).astype(jnp.float32))
        return (rotary_embedding(q, positions, self.theta),
                rotary_embedding(k, positions, self.theta), v, log_g)

    def _finish(self, y, x):
        return self.out_proj(y.astype(x.dtype).reshape(*x.shape[:2], -1))

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel: bool = False):
        """``x`` (B, S, D) at positions [t0, t0 + S) continuing
        ``cache``; only the first ``valid_len`` positions (default all)
        advance it. Returns (out (B, S, D), new cache)."""
        with scope("retention_scan"):
            q, k, v, log_g = self._project(
                x, t0 + jnp.arange(x.shape[1]))
            y, cache = retention.retention_chunked(
                q, k, v, log_g, self.chunk, cache, valid_len, self.eps)
            self.counted = {"retention_small_norm": jnp.int32(0)}
            return self._finish(y, x), cache

    def forward_step(self, x, cache, t, decode_kernel: bool = False):
        """:meth:`forward_step_rows` with every row at the cursor ``t``."""
        return self.forward_step_rows(
            x, cache, jnp.broadcast_to(t, x.shape[:1]))

    def forward_step_rows(self, x, cache, t_rows,
                          decode_kernel: bool = False):
        """One position a row at per-row positions ``t_rows`` (B,):
        ``x`` (B, 1, D) -> (out (B, 1, D), new cache)."""
        with scope("retention_step"):
            q, k, v, log_g = self._project(x, t_rows[:, None])
            num, den, cache = retention.retention_step_parts(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], cache)
            self.counted = {"retention_small_norm": jnp.sum(
                den < 10 * self.eps, dtype=jnp.int32)}
            y = num / (den[..., None] + self.eps)
            return self._finish(y[:, None], x), cache

    def forward(self, x):
        return self.forward_chunk(x, self.init_cache(x.shape[0], 0))[0]


class SoftmaxMixer(nn.MultiHeadAttention):
    """GQA softmax attention without positional encoding or biases, the
    scores times ``cfg.attention_multiplier``: ``nn.MultiHeadAttention``
    answering the mixers' convention, its cache the pair (K, V). The
    whole sublayer (norm1, the mixer, the residual add) runs under
    ``attn``, cached or not."""

    state_kind, cache_record = "kv", "heads"
    cached_scope = empty_scope = "attn"
    counted = {}

    def __init__(self, cfg: HybridConfig):
        super().__init__(
            cfg.hidden_size, cfg.num_heads, bias=False,
            use_flash=cfg.use_flash,
            num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
            rotary=False, scale=cfg.attention_multiplier)

    def forward(self, x):
        return super().forward(x, causal=True)

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel: bool = False):
        a, ck, cv = super().forward_chunk(x, *cache, t0,
                                          decode_kernel=decode_kernel)
        return a, (ck, cv)

    def forward_step(self, x, cache, t, decode_kernel: bool = False):
        return self.forward_chunk(x, cache, t, None, decode_kernel)

    def forward_step_rows(self, x, cache, t_rows,
                          decode_kernel: bool = False):
        a, ck, cv = super().forward_step_rows(x, *cache, t_rows,
                                              decode_kernel=decode_kernel)
        return a, (ck, cv)


def _latent(cfg: HybridConfig):
    return LatentAttention(
        cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank or 0,
        cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.rope_theta, cfg.rope_yarn,
        cfg.rope_mscale_all_dim, cfg.rms_norm_eps, cfg.index_n_heads,
        cfg.index_head_dim, cfg.index_topk)


def _gated(kind: str, cfg: HybridConfig):
    sliding = kind == "sliding_attention"
    enforce(not sliding or cfg.sliding_window >= 1, "a sliding layer "
            "needs a sliding_window, got %s", cfg.sliding_window)
    heads = (cfg.attn_heads or {}).get(kind, cfg.num_heads)
    return GatedAttention(
        cfg.hidden_size, heads, cfg.num_kv_heads or heads,
        cfg.attn_head_dim or cfg.hidden_size // heads,
        cfg.sliding_window if sliding else None,
        gate=cfg.attn_gate, use_flash=cfg.use_flash,
        **(cfg.attn_rope or {}).get(kind, {}))


# a kind's name -> what builds its mixer from the configuration; a new
# kind is a class that answers the convention and one line here
MIXERS = {"mamba": SSDMixer, "attention": SoftmaxMixer,
          "retention": RetentionMixer, "latent": _latent,
          "full_attention": functools.partial(_gated, "full_attention"),
          "sliding_attention": functools.partial(_gated,
                                                 "sliding_attention")}


def _under(name):
    return scope(name) if name else contextlib.nullcontext()


def tally(totals: dict, counted: dict):
    """Add what a part of a block counted to the call's, by name and in
    the names' order (not the order a part built its dictionary in)."""
    for name in sorted(counted):
        totals[name] = totals.get(name, 0) + counted[name]


class HybridBlock(Layer):
    """Two sublayers over one residual path (``res1``, ``res2``:
    ``u, held = read(X)``, ``X = write(X, F(norm(u)), held)``): the
    mixer ``MIXERS[kind]`` builds, then the channel mix ``mix`` names,
    routed experts plus a shared MLP (``moe``, ``shared``) or one gated
    MLP (``mlp``). The path is the plain ``X + m F(norm(X))`` or, with
    ``cfg.hc_mult`` > 1, hyper-connections over that many streams, each
    sublayer with maps of its own."""

    def __init__(self, cfg: HybridConfig, kind: str,
                 mix: Optional[str] = None):
        super().__init__()
        mix = cfg.channel_mix if mix is None else mix
        enforce(kind in MIXERS, "layer type %r is none of %s", kind,
                tuple(MIXERS))
        enforce(mix in CHANNEL_MIXES, "channel mix %r is none of %s",
                mix, CHANNEL_MIXES)
        self.m = float(cfg.residual_multiplier)
        if cfg.hc_mult > 1:
            enforce(self.m == 1.0, "hyper-connections carry no residual "
                    "multiplier, got %s", self.m)
            self.res1, self.res2 = (HyperConnection(
                cfg.hidden_size, cfg.hc_mult, cfg.hc_sinkhorn_iters,
                cfg.hc_eps, cfg.hc_clamp, cfg.rms_norm_eps)
                for _ in range(2))
        else:
            self.res1 = self.res2 = PlainResidual(self.m,
                                                  cfg.settle_residual)
        self.norm1 = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mixer = MIXERS[kind](cfg)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.moe = None
        if mix == "mlp":
            self.mlp = GatedMLP(cfg.hidden_size, cfg.mlp_width)
            return
        self.moe = nn.DroplessMoE(
            cfg.hidden_size, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, experts_held=cfg.experts_held,
            routing=cfg.routing, scaling=cfg.routed_scaling_factor,
            bias_update_rate=cfg.router_bias_update_rate)
        self.shared = GatedMLP(cfg.hidden_size, cfg.shared_width)

    def channel_mix(self, x, with_load: bool = False):
        """(the state after the channel mix's sublayer, what it counted):
        with experts ``expert_tokens``, the (held,) tokens each held
        expert got, and, ``with_load``, ``expert_load``, the
        (num_experts,) pairs each router output got; else nothing."""
        u, held = self.res2.read(x)
        if self.moe is None:
            with scope("mlp"):
                y = self.mlp(self.norm2(u))
            return self.res2.write(x, y, held), {}
        u = self.norm2(u)
        routed, *counts = self.moe.forward_counted(u, with_load)
        with scope("moe_shared"):
            shared = self.shared(u)
        return (self.res2.write(x, routed + shared, held),
                dict(zip(("expert_tokens", "expert_load"), counts)))

    def forward_counted(self, x):
        """The block from empty state, a pure function of ``x`` (what
        ``jax.checkpoint`` wraps): (the state after both sublayers, the
        (held,) tokens each held expert got or None, the (num_experts,)
        load of every router output or None where the block has no bias
        rule). The mixer's sublayer (norm1, the mixer, the residual add)
        runs under the mixer's ``empty_scope`` (``attn`` for both
        attentions: a training step's table reads it as the dense
        decoder's); what the mixer and the residual path count stays
        inside."""
        with _under(self.mixer.empty_scope):
            u, held = self.res1.read(x)
            x = self.res1.write(x, self.mixer(self.norm1(u)), held)
        with_load = self.moe is not None and bool(self.moe.bias_update_rate)
        x, counted = self.channel_mix(x, with_load)
        return x, counted.get("expert_tokens"), counted.get("expert_load")

    def forward(self, x):
        return self.forward_counted(x)[0]


class HybridForCausalLM(Layer):
    """Embedding -> blocks by ``cfg.layer_types`` -> RMSNorm -> head
    (the embedding's transpose, or ``lm_head``). ``forward(ids)`` gives
    (B, T, V) logits from empty state; the ``_chunk_logits`` /
    ``_step_logits`` / ``_step_logits_rows`` entries are what
    ``serving.BatchedDecoder`` calls, over the cache list
    :meth:`init_cache` gives."""

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([
            HybridBlock(cfg, kind, mix) for kind, mix in
            zip(cfg.layer_types, cfg.channel_mixes())])
        self.norm_f = nn.RMSNorm(cfg.hidden_size,
                                 epsilon=cfg.rms_norm_eps)
        if not cfg.tie_embeddings:
            self.create_parameter(
                "lm_head", (cfg.hidden_size, cfg.vocab_size), None,
                I.XavierUniform())
        self.cache_kinds = [blk.mixer.state_kind for blk in self.blocks]
        self.cache_records = [blk.mixer.cache_record for blk in self.blocks]
        self._counted = {}

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """One pytree a block, its mixer's own, every leaf with the
        sequence (slot) axis first."""
        return [blk.mixer.init_cache(batch, capacity, dtype)
                for blk in self.blocks]

    def step_counters(self):
        """What the latest cached call, or the latest call from empty
        state (:meth:`forward`, :meth:`forward_loss`: a training step),
        counted, for the program that made the call to return: int32
        sums over the blocks, by the name each part reports under (its
        own docstring says what it counts). A cached call: each mixer's
        ``counted`` (``RetentionMixer``: ``retention_small_norm``;
        ``nn.LatentAttention`` with an indexer: ``dsa_positions_live`` /
        ``dsa_positions_read``), the residual path's
        (``nn.latent.HyperConnection``: ``mhc_unbalanced``), the channel
        mix's (``expert_tokens`` (held,)) and, with routed experts, the
        shell's own ``expert_dense_layers``, the expert layers of the
        call whose rows took the dense body of ``nn.moe.dropless_moe``
        (the trace fixes it: :meth:`expert_layers`). A call from empty
        state: the last two and, where the routers have the bias rule
        (``router_bias_update_rate``), ``expert_load`` (expert layers,
        num_experts); what its mixers and residual paths count is left
        inside ``jax.checkpoint``. Valid only inside the trace of that
        call."""
        return dict(self._counted)

    @scope("embed")
    def _embed(self, ids):
        e = self.embed(ids)
        e = e * jnp.asarray(self.cfg.embedding_multiplier, e.dtype)
        if self.cfg.hc_mult > 1:    # every stream starts as the embedding
            e = jnp.broadcast_to(e[..., None, :], (
                *e.shape[:-1], self.cfg.hc_mult, e.shape[-1])).astype(
                    HyperConnection.state_dtype)
        return e

    def _head_weight(self):
        return (self.embed.weight.T if self.cfg.tie_embeddings
                else self.lm_head)

    def _final_hidden(self, x):
        """The final norm over the state (its streams read out as their
        sum): what the head's product reads."""
        if self.cfg.hc_mult > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=-2)
        return self.norm_f(x)

    @scope("head")
    def _head(self, x):
        logits = self._final_hidden(x) @ self._head_weight()
        return logits / jnp.asarray(self.cfg.logits_scaling, logits.dtype)

    def _trunk(self, ids):
        """The blocks over ``ids`` from empty state, each under
        ``jax.checkpoint`` with ``cfg.remat`` (kept: ``nn.remat_policy``).
        In training mode a block whose router has the bias rule then moves
        its bias by the load of its batch (``nn.DroplessMoE.bias_update``,
        scope ``moe_bias_update``): a buffer update made HERE, outside the
        checkpoint, for ``new_buffers``; the call routes by the bias given."""
        x = self._embed(ids)
        tokens, loads = 0, []
        for blk in self.blocks:
            run = (jax.checkpoint(blk.forward_counted,
                                  policy=nn.remat_policy())
                   if self.cfg.remat else blk.forward_counted)
            x, got, load = run(x)
            if got is not None:
                tokens = tokens + got
            if load is not None:
                loads.append(load)
                if self.training:
                    with scope("moe_bias_update"):
                        blk.moe.bias_update(load)
        self._counted = {}
        layers, dense = self.expert_layers(ids.shape[0] * ids.shape[1])
        if layers:
            self._counted.update(expert_tokens=tokens,
                                 expert_dense_layers=jnp.int32(dense))
        if loads:
            self._counted["expert_load"] = jnp.stack(loads)
        return x

    def forward(self, ids):
        return self._head(self._trunk(ids))

    def forward_loss(self, ids, labels=None, vocab_chunk: int = 1024,
                     ignore_index: int = -100):
        """Mean next-token cross-entropy, the training entry
        (``parallel.Trainer`` through ``functional_call(...,
        method="forward_loss")``, as ``GPTForCausalLM.forward_loss``):
        the blocks from empty state (:meth:`_trunk`: remat, the routers'
        bias rule), the final norm under ``head``, then the tail both
        shells share (``models.gpt.next_token_loss``); equal to
        ``models.gpt.loss_fn`` over :meth:`forward`'s logits
        (``tests/test_hybrid_train.py``). The routers' new state comes
        back as the call's buffers."""
        x = self._trunk(ids)
        with scope("head"):     # the head itself is ``linear_ce``
            h = self._final_hidden(x)
            h = h / jnp.asarray(self.cfg.logits_scaling, h.dtype)
        return next_token_loss(h, self._head_weight(), ids, labels,
                               vocab_chunk, ignore_index)

    def expert_layers(self, rows: int, last_rows=None):
        """(the expert layers of a call over ``rows`` positions, cached
        or from empty state (a training step's ``rows`` are its batch's
        tokens), those of them that take the dense body of
        ``nn.moe.dropless_moe``): static counts, so a caller that knows
        a program's shape knows them without running it; the second is
        the ``expert_dense_layers`` both kinds of call count.
        ``last_rows``: the rows of the last block's channel mix where
        the call is cut to the head's position before it (``head_at``: a
        prefill of one prompt leaves it 1 row)."""
        n = dense = 0
        for i, blk in enumerate(self.blocks):
            if blk.moe is None:
                continue
            cut = last_rows is not None and i == len(self.blocks) - 1
            n += 1
            dense += blk.moe.streams_densely(last_rows if cut else rows)
        return n, dense

    def _cached_blocks(self, x, caches, call, head: bool = True,
                       head_at=None):
        """The cached block composition, written once: ``call(mixer, h,
        cache) -> (a, cache)``, the entry of the mixers' convention a
        caller wants with its cursor bound, is all that varies between
        the chunk, single-step and per-row entries; each mixer's
        sublayer runs under its ``cached_scope``. It ends in the head
        over every position (``head=True``), in none (``head=False``),
        or in the head at the one position ``head_at`` (may be traced):
        ``x`` is cut to that row as soon as only that row is wanted,
        after the last block's mixer (whose cache every position
        writes), so the last block's channel mix and the head have one
        row, and the logits are (B, V)."""
        new_caches, totals = [], {}
        rows, last = x.shape[0] * x.shape[1], len(self.blocks) - 1
        for i, (blk, cache) in enumerate(zip(self.blocks, caches)):
            with _under(blk.mixer.cached_scope):
                u, held = blk.res1.read(x)
                a, cache = call(blk.mixer, blk.norm1(u), cache)
                tally(totals, blk.mixer.counted)
                x = blk.res1.write(x, a, held)
            if head_at is not None and i == last:
                x = lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
            x, counted = blk.channel_mix(x)
            for part in (counted, blk.res1.counted, blk.res2.counted):
                tally(totals, part)
            new_caches.append(cache)
        layers, dense = self.expert_layers(
            rows, None if head_at is None else x.shape[0])
        if layers:
            totals["expert_dense_layers"] = jnp.int32(dense)
        self._counted = totals
        if head_at is not None:
            return self._head(x)[:, 0], new_caches
        return (self._head(x) if head else None), new_caches

    def _chunk_logits(self, toks, caches, t0, head: bool = True,
                      decode_kernel: bool = False, valid_len=None,
                      head_at=None):
        """S cached positions in one pass at cache indices [t0, t0+S):
        keys and values are written for the whole chunk, a recurrence
        advances over its first ``valid_len`` positions only and stays
        there, while every position's output is that position's own
        (so ``head_at=valid_len - 1`` gives a padded prompt's next-token
        logits, (B, V), from the same pass that leaves the state after
        the whole prompt)."""
        return self._cached_blocks(
            self._embed(toks), caches, lambda mx, h, c: mx.forward_chunk(
                h, c, t0, valid_len, decode_kernel),
            head=head, head_at=head_at)

    def _step_logits(self, tok, caches, t, decode_kernel: bool = False):
        """One cached position: ``tok`` (B,) -> ((B, V), caches)."""
        logits, caches = self._cached_blocks(
            self._embed(tok[:, None]), caches,
            lambda mx, h, c: mx.forward_step(h, c, t, decode_kernel))
        return logits[:, 0], caches

    def _step_logits_rows(self, tok, caches, t_rows,
                          decode_kernel: bool = False):
        """One cached position PER ROW at per-row cursors ``t_rows``
        (the continuous-batching step): a state has no cursor, so only
        attention and a mixer with a rotary embedding read ``t_rows``."""
        logits, caches = self._cached_blocks(
            self._embed(tok[:, None]), caches,
            lambda mx, h, c: mx.forward_step_rows(h, c, t_rows,
                                                  decode_kernel))
        return logits[:, 0], caches
