"""Transformer layers — encoder/decoder stacks over MultiHeadAttention.

The reference assembles transformers in model code from primitives
(reference: benchmark/fluid/models/machine_translation.py,
python/paddle/fluid/nets.py:343 scaled_dot_product_attention); here the
stack is first-class so the flash/ring-attention kernel paths and TP/SP
sharding rules have a single home.

TPU notes: pre-norm by default (stable in bf16), GELU FFN, static shapes
(padding/masking handles ragged batches — see ops/sequence.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..core.enforce import enforce
from .layer import Layer, LayerList, remat_policy
from .layers import Dropout, Embedding, LayerNorm, Linear, MultiHeadAttention


class FeedForward(Layer):
    """Position-wise FFN: Linear → act → dropout → Linear."""

    def __init__(self, d_model: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "gelu"):
        super().__init__()
        self.fc1 = Linear(d_model, dim_feedforward, act=activation)
        self.fc2 = Linear(dim_feedforward, d_model)
        self.drop = Dropout(dropout)

    def forward(self, x):
        return self.fc2(self.drop(self.fc1(x)))


class TransformerEncoderLayer(Layer):
    """``moe_experts > 0`` swaps the dense FFN for a Switch-MoE FFN
    (:class:`~paddle_tpu.nn.moe.SwitchFFN`) — experts shard over the
    'ep' mesh axis; the load-balance aux loss rides the layer's buffers
    (collect ``*.ffn.aux_loss`` from functional_call's new_buffers)."""

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "gelu",
                 normalize_before: bool = True, use_flash: bool = True,
                 seq_parallel=None, attn_window=None,
                 moe_experts: int = 0,
                 moe_capacity_factor: float = 1.25):
        super().__init__()
        self.normalize_before = normalize_before
        # sliding-window/local attention width (None = full)
        self.attn_window = attn_window
        # attention-probability dropout is unsupported under SP (the ring/
        # a2a paths have no per-probability RNG plan yet); residual/FFN
        # dropout below stays active, so regularization is not silently lost
        self.self_attn = MultiHeadAttention(
            d_model, nhead, dropout=0.0 if seq_parallel else dropout,
            use_flash=use_flash, seq_parallel=seq_parallel)
        if moe_experts:
            from .moe import SwitchFFN

            self.ffn = SwitchFFN(d_model, dim_feedforward, moe_experts,
                                 capacity_factor=moe_capacity_factor)
        else:
            self.ffn = FeedForward(d_model, dim_feedforward, dropout,
                                   activation)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.drop1 = Dropout(dropout)
        self.drop2 = Dropout(dropout)

    def forward(self, x, mask=None, segment_ids=None):
        if self.normalize_before:
            x = x + self.drop1(self.self_attn(self.norm1(x), attn_mask=mask,
                                              segment_ids=segment_ids,
                                              window=self.attn_window))
            x = x + self.drop2(self.ffn(self.norm2(x)))
        else:
            x = self.norm1(x + self.drop1(self.self_attn(
                x, attn_mask=mask, segment_ids=segment_ids,
                window=self.attn_window)))
            x = self.norm2(x + self.drop2(self.ffn(x)))
        return x


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "gelu",
                 normalize_before: bool = True, use_flash: bool = True,
                 seq_parallel=None, attn_window=None):
        super().__init__()
        self.normalize_before = normalize_before
        # sliding-window width for the causal SELF-attention (the
        # Mistral-style decoder pattern); cross-attention stays full
        self.attn_window = attn_window
        # attention-probability dropout off under SP (see EncoderLayer note)
        self.self_attn = MultiHeadAttention(
            d_model, nhead, dropout=0.0 if seq_parallel else dropout,
            use_flash=use_flash, seq_parallel=seq_parallel)
        # cross-attention keeps the standard path: its K/V length is the
        # (short) memory length, not the SP-sharded decoder length
        self.cross_attn = MultiHeadAttention(d_model, nhead, dropout=dropout,
                                             use_flash=use_flash)
        self.ffn = FeedForward(d_model, dim_feedforward, dropout, activation)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.drop1 = Dropout(dropout)
        self.drop2 = Dropout(dropout)
        self.drop3 = Dropout(dropout)

    def forward(self, x, memory, self_mask=None, cross_mask=None,
                causal: bool = True):
        if self.normalize_before:
            x = x + self.drop1(self.self_attn(self.norm1(x),
                                              attn_mask=self_mask,
                                              causal=causal,
                                              window=self.attn_window))
            x = x + self.drop2(self.cross_attn(self.norm2(x), memory, memory,
                                               attn_mask=cross_mask))
            x = x + self.drop3(self.ffn(self.norm3(x)))
        else:
            x = self.norm1(x + self.drop1(self.self_attn(
                x, attn_mask=self_mask, causal=causal,
                window=self.attn_window)))
            x = self.norm2(x + self.drop2(self.cross_attn(
                x, memory, memory, attn_mask=cross_mask)))
            x = self.norm3(x + self.drop3(self.ffn(x)))
        return x


@functools.lru_cache(maxsize=None)
def _dots_policy():
    """``remat_policy="dots"``: the matmul outputs beside what
    ``remat_policy()`` keeps; one object, as ``jax.checkpoint`` caches
    its traces by the policy's identity."""
    import jax

    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        remat_policy())


class TransformerEncoder(Layer):
    """``remat=True`` wraps each block in ``jax.checkpoint`` so backward
    recomputes block activations instead of storing every layer's — the
    HBM-for-FLOPs trade that makes long-sequence training fit (TPU
    guidance: rematerialize at block boundaries). What a block keeps is
    its input and, under either ``remat_policy``, its flash kernel's
    ``o`` and ``lse`` (``nn.remat_policy``, the rule the model shells
    share: the backward pass does not run the forward kernel again);
    ``"dots"`` keeps the matmul outputs besides. Applies on every call
    when enabled; meant for the jitted training path (eager callers
    should leave the default False)."""

    def __init__(self, num_layers: int, d_model: int, nhead: int,
                 dim_feedforward: int, dropout: float = 0.1,
                 activation: str = "gelu", normalize_before: bool = True,
                 use_flash: bool = True, seq_parallel=None,
                 remat: bool = False, scan_layers: bool = False,
                 attn_window=None, remat_policy: Optional[str] = None,
                 moe_experts: int = 0, moe_capacity_factor: float = 1.25):
        super().__init__()
        self.layers = LayerList([
            TransformerEncoderLayer(d_model, nhead, dim_feedforward, dropout,
                                    activation, normalize_before, use_flash,
                                    seq_parallel, attn_window=attn_window,
                                    moe_experts=moe_experts,
                                    moe_capacity_factor=moe_capacity_factor)
            for _ in range(num_layers)])
        self.final_norm = LayerNorm(d_model) if normalize_before else None
        self.remat = remat
        # None = keep the flash kernel's o and lse alone (recompute
        # everything else); "dots" = save matmul outputs too and
        # recompute only the elementwise tail — less recompute FLOPs for
        # a bit more HBM (the standard policy sweep for MFU at long
        # sequence). Validated HERE so a policy on a non-remat encoder
        # fails loudly instead of silently not running
        enforce(remat_policy in (None, "dots"),
                "remat_policy must be None or 'dots', got %r", remat_policy)
        enforce(remat_policy is None or remat,
                "remat_policy=%r requires remat=True", remat_policy)
        self.remat_policy = remat_policy
        # scan-over-layers: one traced block applied via lax.scan over
        # stacked per-layer params — the compiled module stays O(1) in
        # depth (compile time + HLO size for 24/48-layer stacks) and the
        # scan body is the natural remat boundary. Dropout must be 0:
        # the scan body shares one RNG stream, which would correlate
        # masks across layers (checked per-call: scan_layers is a plain
        # attribute).
        self._dropout_p = dropout
        self.scan_layers = scan_layers

    def _ckpt_policy(self):
        return remat_policy() if self.remat_policy is None else _dots_policy()

    def forward(self, x, mask=None, segment_ids=None):
        import jax
        from jax import lax

        if self.scan_layers and len(self.layers) > 1:
            enforce(self._dropout_p == 0.0 or not self.training,
                    "scan_layers needs dropout == 0 in training (one "
                    "traced body would reuse its RNG across layers); "
                    "unroll instead")
            from .layer import stacked_parameters

            stacked = stacked_parameters(self.layers)
            template = self.layers[0]

            def body(h, pl):
                out, _ = template.functional_call(
                    pl, h, mask=mask, segment_ids=segment_ids,
                    training=self.training)
                return out, None

            if self.remat:
                # prevent_cse is unnecessary inside scan (JAX docs) and
                # would insert optimization barriers per iteration
                body = jax.checkpoint(body, prevent_cse=False,
                                      policy=self._ckpt_policy())
            x = lax.scan(body, x, stacked)[0]
        else:
            for layer in self.layers:
                if self.remat:
                    x = jax.checkpoint(
                        lambda h, _l=layer: _l(h, mask=mask,
                                               segment_ids=segment_ids),
                        policy=self._ckpt_policy())(x)
                else:
                    x = layer(x, mask=mask, segment_ids=segment_ids)
        if self.final_norm is not None:
            x = self.final_norm(x)
        return x


class TransformerDecoder(Layer):
    def __init__(self, num_layers: int, d_model: int, nhead: int,
                 dim_feedforward: int, dropout: float = 0.1,
                 activation: str = "gelu", normalize_before: bool = True,
                 use_flash: bool = True, seq_parallel=None,
                 attn_window=None):
        super().__init__()
        self.layers = LayerList([
            TransformerDecoderLayer(d_model, nhead, dim_feedforward, dropout,
                                    activation, normalize_before, use_flash,
                                    seq_parallel, attn_window=attn_window)
            for _ in range(num_layers)])
        self.final_norm = LayerNorm(d_model) if normalize_before else None

    def forward(self, x, memory, self_mask=None, cross_mask=None,
                causal: bool = True):
        for layer in self.layers:
            x = layer(x, memory, self_mask=self_mask, cross_mask=cross_mask,
                      causal=causal)
        if self.final_norm is not None:
            x = self.final_norm(x)
        return x


class PositionalEncoding(Layer):
    """Sinusoidal position signal (reference: the NMT model's
    position_encoding_init, benchmark/fluid/models/machine_translation.py)."""

    def __init__(self, d_model: int, max_len: int = 4096,
                 dropout: float = 0.0, scale_embedding: bool = True):
        super().__init__()
        enforce(d_model % 2 == 0, "d_model must be even, got %s", d_model)
        pos = np.arange(max_len)[:, None]
        div = np.exp(np.arange(0, d_model, 2) * (-math.log(10000.0) / d_model))
        pe = np.zeros((max_len, d_model), np.float32)
        pe[:, 0::2] = np.sin(pos * div)
        pe[:, 1::2] = np.cos(pos * div)
        self.register_buffer("pe", pe)
        self.scale = math.sqrt(d_model) if scale_embedding else 1.0
        self.drop = Dropout(dropout)

    def forward(self, x):
        t = x.shape[1]
        out = x * self.scale + self.pe[None, :t].astype(x.dtype)
        return self.drop(out)


class LearnedPositionalEmbedding(Layer):
    """BERT-style learned positions."""

    def __init__(self, max_len: int, d_model: int):
        super().__init__()
        self.emb = Embedding(max_len, d_model)

    def forward(self, x):
        t = x.shape[1]
        positions = jnp.arange(t)[None, :]
        return x + self.emb(positions)


def decoder_layer_step(layer, x_t, mem_k, mem_v, cache_k, cache_v, t,
                       cross_mask=None, decode_kernel: bool = False):
    """One incremental-decode step of a TransformerDecoderLayer: the
    self-attention runs against the layer's K/V cache (O(T) per step —
    the transformer analog of the reference RNN decoder's O(1) state),
    cross-attention against PRE-PROJECTED memory K/V. ``x_t``: (B, 1, D).
    Returns (out_t, cache_k, cache_v). Mirrors
    TransformerDecoderLayer.forward's pre/post-norm residual layout
    (eval mode: dropout is identity)."""
    w = layer.attn_window
    if layer.normalize_before:
        h, cache_k, cache_v = layer.self_attn.forward_step(
            layer.norm1(x_t), cache_k, cache_v, t, window=w,
            decode_kernel=decode_kernel)
        x_t = x_t + h
        x_t = x_t + layer.cross_attn.attend_kv(layer.norm2(x_t), mem_k,
                                               mem_v, attn_mask=cross_mask)
        x_t = x_t + layer.ffn(layer.norm3(x_t))
    else:
        h, cache_k, cache_v = layer.self_attn.forward_step(
            x_t, cache_k, cache_v, t, window=w,
            decode_kernel=decode_kernel)
        x_t = layer.norm1(x_t + h)
        x_t = layer.norm2(x_t + layer.cross_attn.attend_kv(
            x_t, mem_k, mem_v, attn_mask=cross_mask))
        x_t = layer.norm3(x_t + layer.ffn(x_t))
    return x_t, cache_k, cache_v
