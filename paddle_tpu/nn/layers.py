"""Standard layers — capability parity with fluid.dygraph.nn
(reference: python/paddle/fluid/dygraph/nn.py:35-2332 — Conv2D, Pool2D, FC,
BatchNorm, Embedding, LayerNorm, GRUUnit, NCE, PRelu, BilinearTensorProduct,
Conv2DTranspose, GroupNorm, SpectralNorm, TreeConv) plus the transformer
layers the model zoo needs (MultiHeadAttention etc. — assembled in the
reference from primitives, see nets.py:343 scaled_dot_product_attention).
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from .. import initializer as I
from ..core.dtypes import default_dtype, get_policy
from ..core.enforce import enforce
from ..ops import math as OM
from ..ops import nn as ON
from .layer import Layer, LayerList


class Linear(Layer):
    """FC layer (reference: dygraph/nn.py FC / layers/nn.py fc:210)."""

    def __init__(self, in_features: int, out_features: int,
                 bias_attr: bool = True, act: Optional[str] = None,
                 weight_init=None, bias_init=None, dtype=None):
        super().__init__()
        self.in_features, self.out_features = in_features, out_features
        self.act = act
        self.create_parameter("weight", (in_features, out_features), dtype,
                              weight_init or I.XavierUniform(),
                              compute_cast=True)
        self.has_bias = bias_attr
        if bias_attr:
            self.create_parameter("bias", (out_features,), dtype,
                                  bias_init or I.Constant(0.0), is_bias=True,
                                  compute_cast=True)

    def forward(self, x):
        pol = get_policy()
        w = pol.cast_to_compute(self.weight)
        out = jnp.matmul(pol.cast_to_compute(x), w)
        if self.has_bias:
            out = out + pol.cast_to_compute(self.bias)
        out = pol.cast_to_output(out)
        return _apply_act(out, self.act)


class Conv2D(Layer):
    """reference: dygraph/nn.py Conv2D (NCHW, OIHW weights)."""

    def __init__(self, in_channels: int, out_channels: int,
                 kernel_size: Union[int, Sequence[int]], stride=1, padding=0,
                 dilation=1, groups: int = 1, bias_attr: bool = True,
                 act: Optional[str] = None, weight_init=None, dtype=None,
                 data_format: str = "NCHW"):
        super().__init__()
        k = (kernel_size,) * 2 if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride, self.padding, self.dilation, self.groups = stride, padding, dilation, groups
        self.act = act
        self.data_format = data_format
        self.create_parameter(
            "weight", (out_channels, in_channels // groups) + k, dtype,
            weight_init or I.MSRA(uniform=False), compute_cast=True)
        self.has_bias = bias_attr
        if bias_attr:
            self.create_parameter("bias", (out_channels,), dtype,
                                  I.Constant(0.0), is_bias=True,
                                  compute_cast=True)

    def forward(self, x):
        pol = get_policy()
        out = ON.conv2d(pol.cast_to_compute(x), pol.cast_to_compute(self.weight),
                        self.stride, self.padding, self.dilation, self.groups,
                        data_format=self.data_format)
        if self.has_bias:
            bshape = ((1, -1, 1, 1) if self.data_format == "NCHW"
                      else (1, 1, 1, -1))
            out = out + pol.cast_to_compute(self.bias).reshape(bshape)
        return _apply_act(pol.cast_to_output(out), self.act)


class Conv2DTranspose(Layer):
    """reference: dygraph/nn.py Conv2DTranspose (IOHW weights)."""

    def __init__(self, in_channels: int, out_channels: int, kernel_size,
                 stride=1, padding=0, dilation=1, groups: int = 1,
                 bias_attr: bool = True, act: Optional[str] = None, dtype=None):
        super().__init__()
        k = (kernel_size,) * 2 if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride, self.padding, self.dilation, self.groups = stride, padding, dilation, groups
        self.act = act
        self.create_parameter("weight",
                              (in_channels, out_channels // groups) + k, dtype,
                              I.XavierUniform(), compute_cast=True)
        self.has_bias = bias_attr
        if bias_attr:
            self.create_parameter("bias", (out_channels,), dtype,
                                  I.Constant(0.0), is_bias=True,
                                  compute_cast=True)

    def forward(self, x):
        pol = get_policy()
        out = ON.conv2d_transpose(pol.cast_to_compute(x),
                                  pol.cast_to_compute(self.weight),
                                  self.stride, self.padding,
                                  self.dilation, self.groups)
        if self.has_bias:
            out = out + pol.cast_to_compute(self.bias).reshape(1, -1, 1, 1)
        return _apply_act(pol.cast_to_output(out), self.act)


class Pool2D(Layer):
    """reference: dygraph/nn.py Pool2D."""

    def __init__(self, kernel_size, pool_type: str = "max", stride=None,
                 padding=0, global_pooling: bool = False,
                 ceil_mode: bool = False, data_format: str = "NCHW"):
        super().__init__()
        self.kernel_size, self.pool_type = kernel_size, pool_type
        self.stride, self.padding = stride, padding
        self.global_pooling, self.ceil_mode = global_pooling, ceil_mode
        self.data_format = data_format

    def forward(self, x):
        return ON.pool2d(x, self.kernel_size, self.pool_type, self.stride,
                         self.padding, ceil_mode=self.ceil_mode,
                         global_pooling=self.global_pooling,
                         data_format=self.data_format)


class BatchNorm(Layer):
    """reference: dygraph/nn.py BatchNorm — running stats live in buffers;
    functional_call returns them updated."""

    def __init__(self, num_channels: int, momentum: float = 0.9,
                 epsilon: float = 1e-5, act: Optional[str] = None,
                 data_layout: str = "NCHW", dtype=None):
        super().__init__()
        self.momentum, self.epsilon = momentum, epsilon
        self.act, self.data_layout = act, data_layout
        self.create_parameter("weight", (num_channels,), dtype, I.Constant(1.0))
        self.create_parameter("bias", (num_channels,), dtype, I.Constant(0.0),
                              is_bias=True)
        self.register_buffer("mean", jnp.zeros((num_channels,)))
        self.register_buffer("variance", jnp.ones((num_channels,)))

    def forward(self, x):
        y, new_mean, new_var = ON.batch_norm(
            x, self.weight, self.bias, self.mean, self.variance,
            training=self.training, momentum=self.momentum,
            epsilon=self.epsilon, data_layout=self.data_layout)
        if self.training:
            self.update_buffer("mean", new_mean)
            self.update_buffer("variance", new_var)
        return _apply_act(y, self.act)


class LayerNorm(Layer):
    """reference: dygraph/nn.py LayerNorm."""

    def __init__(self, normalized_shape, epsilon: float = 1e-5,
                 scale: bool = True, shift: bool = True, dtype=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        self.has_scale, self.has_shift = scale, shift
        if scale:
            self.create_parameter("weight", self.normalized_shape, dtype,
                                  I.Constant(1.0))
        if shift:
            self.create_parameter("bias", self.normalized_shape, dtype,
                                  I.Constant(0.0), is_bias=True)

    def forward(self, x):
        begin = x.ndim - len(self.normalized_shape)
        return ON.layer_norm(
            x, self.weight if self.has_scale else None,
            self.bias if self.has_shift else None,
            begin_norm_axis=begin, epsilon=self.epsilon)


class GroupNorm(Layer):
    """reference: dygraph/nn.py GroupNorm."""

    def __init__(self, num_groups: int, num_channels: int,
                 epsilon: float = 1e-5, dtype=None):
        super().__init__()
        self.num_groups, self.epsilon = num_groups, epsilon
        self.create_parameter("weight", (num_channels,), dtype, I.Constant(1.0))
        self.create_parameter("bias", (num_channels,), dtype, I.Constant(0.0),
                              is_bias=True)

    def forward(self, x):
        return ON.group_norm(x, self.weight, self.bias,
                             groups=self.num_groups, epsilon=self.epsilon)


class RMSNorm(Layer):
    """Modern-transformer norm (no direct reference analog)."""

    def __init__(self, dim: int, epsilon: float = 1e-6, dtype=None):
        super().__init__()
        self.epsilon = epsilon
        self.create_parameter("weight", (dim,), dtype, I.Constant(1.0))

    def forward(self, x):
        return ON.rms_norm(x, self.weight, epsilon=self.epsilon)


class Embedding(Layer):
    """reference: dygraph/nn.py Embedding (lookup_table_op).

    ``is_sparse=True`` (reference lookup_table's is_sparse attr) marks the
    table for row-sparse gradient updates: a train step built with
    :func:`paddle_tpu.optimizer.sparse.sparse_minimize_fn` differentiates
    w.r.t. the gathered rows instead of the table, and the optimizer
    touches O(batch * seq) rows per step, not O(vocab) — the SelectedRows
    capability (reference: framework/selected_rows.h:32). Outside such a
    step the flag is inert (plain dense gather). The giant-table sharded
    variant lives in paddle_tpu.parallel.sharded_embedding."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 padding_idx: Optional[int] = None, weight_init=None,
                 dtype=None, is_sparse: bool = False):
        super().__init__()
        self.padding_idx = padding_idx
        self.is_sparse = is_sparse
        self.create_parameter("weight", (num_embeddings, embedding_dim), dtype,
                              weight_init or I.XavierNormal())

    def forward(self, ids):
        from .sparse import Capture, Inject, active

        ctx = active()
        if ctx is not None and ctx.handles(self):
            if isinstance(ctx, Capture):
                ctx.record(self, ids)
            else:
                assert isinstance(ctx, Inject)
                rows = ctx.pop(self)
                if self.padding_idx is not None:
                    rows = jnp.where((ids == self.padding_idx)[..., None],
                                     0.0, rows)
                return rows
        return ON.embedding(ids, self.weight, self.padding_idx)


class Dropout(Layer):
    """reference: dropout layer (dropout_op)."""

    def __init__(self, p: float = 0.5, mode: str = "upscale_in_train"):
        super().__init__()
        self.p, self.mode = p, mode

    def forward(self, x):
        if not self.training or self.p == 0.0:
            return ON.dropout(x, self.p, training=False, mode=self.mode)
        return ON.dropout(x, self.p, key=self.rng("dropout"), training=True,
                          mode=self.mode)


class PRelu(Layer):
    """reference: dygraph/nn.py PRelu."""

    def __init__(self, mode: str = "all", channel: Optional[int] = None,
                 init: float = 0.25, dtype=None):
        super().__init__()
        self.mode = mode
        shape = (1,) if mode == "all" else (channel,)
        self.create_parameter("alpha", shape, dtype, I.Constant(init))

    def forward(self, x):
        return OM.prelu(x, self.alpha, self.mode)


class BilinearTensorProduct(Layer):
    """reference: dygraph/nn.py BilinearTensorProduct."""

    def __init__(self, in1_features: int, in2_features: int, out_features: int,
                 bias_attr: bool = True, dtype=None):
        super().__init__()
        self.create_parameter("weight",
                              (out_features, in1_features, in2_features), dtype,
                              I.XavierUniform())
        self.has_bias = bias_attr
        if bias_attr:
            self.create_parameter("bias", (out_features,), dtype,
                                  I.Constant(0.0), is_bias=True)

    def forward(self, x, y):
        return OM.bilinear_tensor_product(
            x, y, self.weight, self.bias if self.has_bias else None)


class SpectralNorm(Layer):
    """reference: dygraph/nn.py SpectralNorm — power-iteration weight norm.
    The u/v vectors are buffers updated each forward."""

    def __init__(self, weight_shape, dim: int = 0, power_iters: int = 1,
                 eps: float = 1e-12, dtype=None):
        super().__init__()
        self.dim, self.power_iters, self.eps = dim, power_iters, eps
        h = weight_shape[dim]
        w = math.prod(weight_shape) // h
        self.register_buffer("u", jax.random.normal(jax.random.key(0), (h,)))
        self.register_buffer("v", jax.random.normal(jax.random.key(1), (w,)))

    def forward(self, weight):
        h = weight.shape[self.dim]
        wmat = jnp.moveaxis(weight, self.dim, 0).reshape(h, -1)
        u, v = self.u, self.v
        for _ in range(self.power_iters):
            v = wmat.T @ u
            v = v / (jnp.linalg.norm(v) + self.eps)
            u = wmat @ v
            u = u / (jnp.linalg.norm(u) + self.eps)
        if self.training:
            self.update_buffer("u", u)
            self.update_buffer("v", v)
        sigma = u @ wmat @ v
        return weight / sigma


class GRUCell(Layer):
    """GRU step (reference: dygraph/nn.py GRUUnit / operators/gru_unit_op)."""

    def __init__(self, input_size: int, hidden_size: int, dtype=None):
        super().__init__()
        self.hidden_size = hidden_size
        self.create_parameter("w_ih", (input_size, 3 * hidden_size), dtype,
                              I.XavierUniform())
        self.create_parameter("w_hh", (hidden_size, 3 * hidden_size), dtype,
                              I.XavierUniform())
        self.create_parameter("bias", (3 * hidden_size,), dtype,
                              I.Constant(0.0), is_bias=True)

    def forward(self, x, h):
        gates = x @ self.w_ih + self.bias
        hh = h @ self.w_hh
        hs = self.hidden_size
        r = jax.nn.sigmoid(gates[..., :hs] + hh[..., :hs])
        z = jax.nn.sigmoid(gates[..., hs:2 * hs] + hh[..., hs:2 * hs])
        n = jnp.tanh(gates[..., 2 * hs:] + r * hh[..., 2 * hs:])
        new_h = (1.0 - z) * n + z * h
        return new_h, new_h


class LSTMCell(Layer):
    """LSTM step (reference: operators/lstm_unit_op / cudnn_lstm capability)."""

    def __init__(self, input_size: int, hidden_size: int,
                 forget_bias: float = 1.0, dtype=None):
        super().__init__()
        self.hidden_size, self.forget_bias = hidden_size, forget_bias
        self.create_parameter("w_ih", (input_size, 4 * hidden_size), dtype,
                              I.XavierUniform())
        self.create_parameter("w_hh", (hidden_size, 4 * hidden_size), dtype,
                              I.XavierUniform())
        self.create_parameter("bias", (4 * hidden_size,), dtype,
                              I.Constant(0.0), is_bias=True)

    def forward(self, x, state):
        h, c = state
        gates = x @ self.w_ih + h @ self.w_hh + self.bias
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        f = jax.nn.sigmoid(f + self.forget_bias)
        i = jax.nn.sigmoid(i)
        o = jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        new_c = f * c + i * g
        new_h = o * jnp.tanh(new_c)
        return new_h, (new_h, new_c)


class RNN(Layer):
    """Run a cell over time via lax.scan (recurrent_op / DynamicRNN analog on
    padded batches; masking respects `lengths` like LoD did)."""

    def __init__(self, cell: Layer, time_major: bool = False):
        super().__init__()
        self.cell = cell
        self.time_major = time_major

    def forward(self, x, initial_state, lengths=None):
        from ..ops.control_flow import scan

        if not self.time_major:
            x = jnp.swapaxes(x, 0, 1)  # (T, B, D)
        t = x.shape[0]

        def step(carry, inp):
            state, pos = carry
            t_x, = inp
            out, new_state = self.cell(t_x, state)
            if lengths is not None:
                active = (pos < lengths).reshape((-1,) + (1,) * (out.ndim - 1))
                new_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(active, n, o), new_state, state)
                out = out * active.astype(out.dtype)
            return (new_state, pos + 1), out

        (final_state, _), outs = scan(step, (initial_state, 0), (x,))
        if not self.time_major:
            outs = jnp.swapaxes(outs, 0, 1)
        return outs, final_state


class _MHADecodeMixin:
    """Incremental-decode pieces for MultiHeadAttention (KV cache).

    The reference era decodes with an RNN whose state is O(1) per step;
    the transformer analog needs the K/V of every past position. These
    methods keep decode O(T) per step instead of re-running the stack
    over the whole prefix (O(T^2) per step) the way naive scan decode
    does.
    """

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """Zeroed (B, capacity, h_kv, hd) K and V caches."""
        dt = dtype or default_dtype()
        shape = (batch, capacity, self.num_kv_heads, self.head_dim)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def project_kv(self, key, value=None):
        """One-time K/V projection (cross-attention over fixed memory)."""
        value = key if value is None else value
        b, tk, _ = key.shape
        k = self.k_proj(key).reshape(b, tk, self.num_kv_heads,
                                     self.head_dim)
        v = self.v_proj(value).reshape(b, tk, self.num_kv_heads,
                                       self.head_dim)
        return k, v

    def attend_kv(self, query, k, v, attn_mask=None, q_positions=None,
                  decode_t=None, window=None):
        """Attention of ``query`` (B, Tq, D) against PRE-PROJECTED k/v.
        ``q_positions``: absolute positions for rotary queries (the
        cached K was rotated at write time — the RoPE cache
        convention). ``decode_t`` (with Tq == 1): the cache cursor —
        eligible shapes ride the Pallas flash-decode kernel, which
        applies the pos <= decode_t (and ``window``) mask in-kernel and
        reads only live cache blocks from HBM; ineligible shapes fall
        back to ``attn_mask`` (callers pass both)."""
        from ..ops.attention import (_get_flash_decode, decode_flash_ok,
                                     rotary_embedding,
                                     scaled_dot_product_attention)

        b, tq, d = query.shape
        q = self.q_proj(query).reshape(b, tq, self.num_heads,
                                       self.head_dim)
        if q_positions is not None:
            q = rotary_embedding(q, q_positions,
                                 theta=self.rotary_theta)
        if (decode_t is not None and tq == 1 and self.use_flash
                and decode_flash_ok(k.shape[1], self.head_dim)):
            out = _get_flash_decode()(q, k, v, decode_t, window=window,
                                      scale=self.scale)
        else:
            out = scaled_dot_product_attention(
                q, k, v, mask=attn_mask, use_flash=self.use_flash,
                scale=self.scale)
        return self.out_proj(out.reshape(b, tq, d))

    def forward_chunk(self, x_chunk, cache_k, cache_v, t0, window=None,
                      decode_kernel: bool = False):
        """S decode positions in ONE call: project the chunk's K/V into
        the caches at [t0, t0+S) and attend each position i over cache
        positions <= t0+i (optionally only the last ``window``).
        ``x_chunk``: (B, S, D); returns (out (B, S, D), cache_k,
        cache_v). One speculative-decoding target-scoring pass over
        gamma drafts = one forward_chunk; S=1 is the classic decode
        step. Caller guarantees t0+S <= capacity (dynamic_update_slice
        would silently clamp the write window otherwise)."""
        from jax import lax

        b, s, _ = x_chunk.shape
        cap = cache_k.shape[1]
        # one positions array shared by the k rotation here and the q
        # rotation inside attend_kv — they must never desynchronize
        pos_chunk = t0 + jnp.arange(s, dtype=jnp.int32)       # (S,)
        k_c, v_c = self._project_kv_t(x_chunk, pos_chunk)
        cache_k = lax.dynamic_update_slice_in_dim(
            cache_k, k_c.astype(cache_k.dtype), t0, axis=1)
        cache_v = lax.dynamic_update_slice_in_dim(
            cache_v, v_c.astype(cache_v.dtype), t0, axis=1)
        pos = jnp.arange(cap)
        keep = pos[None, :] <= pos_chunk[:, None]             # (S, cap)
        if window is not None:
            keep &= pos[None, :] > pos_chunk[:, None] - window
        out = self.attend_kv(
            x_chunk, cache_k, cache_v, attn_mask=keep[None, None],
            q_positions=pos_chunk if self.rotary else None,
            # the decode kernel is an OPT-IN (plain jit decode loops):
            # its scalar-prefetch pallas_call must not be dragged under
            # an outer vmap (the speculative per-row loop) where the
            # batching rule would reject it
            decode_t=(t0 if decode_kernel and s == 1 else None),
            window=window)
        return out, cache_k, cache_v

    def _project_kv_t(self, x_t, positions):
        """Project (and rotate) this step's K/V: x_t (B, S, D) ->
        (B, S, kv_heads, head_dim) each; ``positions`` (S,) or (B, S)
        absolute positions for the rotary K convention."""
        b, s, _ = x_t.shape
        k_t = self.k_proj(x_t).reshape(b, s, self.num_kv_heads,
                                       self.head_dim)
        v_t = self.v_proj(x_t).reshape(b, s, self.num_kv_heads,
                                       self.head_dim)
        if self.rotary:
            from ..ops.attention import rotary_embedding

            k_t = rotary_embedding(k_t, positions,
                                   theta=self.rotary_theta)
        return k_t, v_t

    def forward_step_paged(self, x_t, kpool, vpool, table, t_rows,
                           window=None):
        """One decode position PER ROW against a PAGED cache
        (ops/paged_kv.py): project+rotate this position's K/V, scatter
        into each row's page at its logical cursor, attend over the
        row's pages (paged kernel when eligible, gather fallback).
        ``x_t``: (B, 1, D); returns (out, kpool, vpool)."""
        from ..ops import paged_kv

        enforce(self.scale is None, "the paged decode step has no "
                "score scale argument (scale=%s)", self.scale)
        pos_rows = t_rows.astype(jnp.int32)[:, None]          # (B, 1)
        k_t, v_t = self._project_kv_t(x_t, pos_rows)
        kpool, vpool = paged_kv.write_rows(
            kpool, vpool, table, pos_rows[:, 0], k_t, v_t,
            kpool.shape[1])
        out = paged_kv.attend(
            self._rotated_q(x_t, pos_rows), kpool, vpool, table,
            pos_rows[:, 0], window=window)
        b, tq, d = x_t.shape
        return (self.out_proj(out.reshape(b, tq, d)), kpool, vpool)

    def forward_chunk_paged(self, x_chunk, kpool, vpool, table_row,
                            t0, window=None):
        """S prefill positions for ONE row (batch 1) against the paged
        cache: chunk-write, then attend each position i over pages up
        to t0+i (gather path — prefill runs once per request).
        ``x_chunk``: (1, S, D); returns (out, kpool, vpool)."""
        from ..ops import paged_kv
        from ..ops.attention import scaled_dot_product_attention

        b, s, d = x_chunk.shape
        pos_chunk = t0 + jnp.arange(s, dtype=jnp.int32)       # (S,)
        k_c, v_c = self._project_kv_t(x_chunk, pos_chunk)
        kpool, vpool = paged_kv.write_chunk(
            kpool, vpool, table_row, t0, k_c, v_c, kpool.shape[1])
        # static chunk extent (the bucketed-prefill case: t0 is a
        # Python int) -> gather/dequantize only the live page columns
        # instead of the row's full logical view
        upto = t0 + s if isinstance(t0, int) else None
        k = paged_kv.gather_rows(kpool, table_row[None], upto=upto)
        v = paged_kv.gather_rows(vpool, table_row[None], upto=upto)
        cap = k.shape[1]
        pos = jnp.arange(cap)
        keep = pos[None, :] <= pos_chunk[:, None]             # (S, cap)
        if window is not None:
            keep &= pos[None, :] > pos_chunk[:, None] - window
        out = scaled_dot_product_attention(
            self._rotated_q(x_chunk, pos_chunk), k, v,
            mask=keep[None, None], use_flash=False, scale=self.scale)
        return (self.out_proj(out.reshape(b, s, d)), kpool, vpool)

    def _rotated_q(self, query, positions):
        """Projected (and rotated) q for the paged paths — the same
        prologue attend_kv applies."""
        from ..ops.attention import rotary_embedding

        b, tq, d = query.shape
        q = self.q_proj(query).reshape(b, tq, self.num_heads,
                                       self.head_dim)
        if self.rotary:
            q = rotary_embedding(q, positions,
                                 theta=self.rotary_theta)
        return q

    def forward_step(self, x_t, cache_k, cache_v, t, window=None,
                     decode_kernel: bool = False):
        """One decode step (``x_t``: (B, 1, D)) — forward_chunk S=1."""
        return self.forward_chunk(x_t, cache_k, cache_v, t,
                                  window=window,
                                  decode_kernel=decode_kernel)

    def forward_step_rows(self, x_t, cache_k, cache_v, t_rows,
                          window=None, decode_kernel: bool = False):
        """One decode position PER ROW at per-row cursors ``t_rows``
        (B,) — the continuous-batching step (each serving slot at its
        own position). Cache writes land at each row's own index
        (vmapped dynamic_update_slice); attention rides the
        flash-decode kernel's per-row-cursor form when eligible, else
        a per-row masked XLA path. ``x_t``: (B, 1, D)."""
        from jax import lax

        b = x_t.shape[0]
        cap = cache_k.shape[1]
        pos_rows = t_rows.astype(jnp.int32)[:, None]          # (B, 1)
        k_t, v_t = self._project_kv_t(x_t, pos_rows)
        write = jax.vmap(lambda c, u, s: lax.dynamic_update_slice_in_dim(
            c, u, s, axis=0))
        cache_k = write(cache_k, k_t.astype(cache_k.dtype),
                        pos_rows[:, 0])
        cache_v = write(cache_v, v_t.astype(cache_v.dtype),
                        pos_rows[:, 0])
        pos = jnp.arange(cap)[None, :]
        keep = pos <= pos_rows
        if window is not None:
            keep &= pos > pos_rows - window
        out = self.attend_kv(
            x_t, cache_k, cache_v,
            attn_mask=keep[:, None, None, :],
            q_positions=pos_rows if self.rotary else None,
            decode_t=(pos_rows[:, 0] if decode_kernel else None),
            window=window)
        return out, cache_k, cache_v

    def forward_chunk_rows(self, x_chunk, cache_k, cache_v, t0_rows,
                           window=None):
        """S decode positions PER ROW at per-row chunk starts
        ``t0_rows`` (B,) — the speculative verify chunk over a
        continuous-batching arena (each slot scores its gamma+1
        candidates at its OWN cursor). ``x_chunk``: (B, S, D); returns
        (out (B, S, D), cache_k, cache_v). Caller contract matches
        forward_chunk: position i of row b attends cache positions
        <= t0_rows[b]+i; writes at t0+S past capacity clamp (retired
        rows park past capacity — junk at the clamped tail is
        overwritten by a later real write before any query attends
        it)."""
        from jax import lax

        b, s, _ = x_chunk.shape
        cap = cache_k.shape[1]
        pos_chunk = (t0_rows.astype(jnp.int32)[:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None, :])  # (B, S)
        k_c, v_c = self._project_kv_t(x_chunk, pos_chunk)
        write = jax.vmap(lambda c, u, t: lax.dynamic_update_slice_in_dim(
            c, u, t, axis=0))
        cache_k = write(cache_k, k_c.astype(cache_k.dtype),
                        t0_rows.astype(jnp.int32))
        cache_v = write(cache_v, v_c.astype(cache_v.dtype),
                        t0_rows.astype(jnp.int32))
        pos = jnp.arange(cap)
        keep = pos[None, None, :] <= pos_chunk[:, :, None]   # (B, S, cap)
        if window is not None:
            keep &= pos[None, None, :] > pos_chunk[:, :, None] - window
        out = self.attend_kv(
            x_chunk, cache_k, cache_v, attn_mask=keep[:, None],
            q_positions=pos_chunk if self.rotary else None,
            window=window)
        return out, cache_k, cache_v

    def forward_chunk_paged_rows(self, x_chunk, kpool, vpool, table,
                                 t0_rows, window=None):
        """S decode positions PER ROW against the PAGED cache at
        per-row chunk starts (the paged-arena speculative verify
        chunk): chunk-write every row's candidates at its own logical
        offset (OOB rows drop — parked cursors), attend over each
        row's pages via the gather path (S is gamma+1-small; the paged
        decode kernel stays the S=1 hot loop). ``x_chunk``: (B, S, D);
        returns (out, kpool, vpool)."""
        from ..ops import paged_kv
        from ..ops.attention import scaled_dot_product_attention

        b, s, d = x_chunk.shape
        pos_chunk = (t0_rows.astype(jnp.int32)[:, None]
                     + jnp.arange(s, dtype=jnp.int32)[None, :])  # (B, S)
        k_c, v_c = self._project_kv_t(x_chunk, pos_chunk)
        kpool, vpool = paged_kv.write_chunk_rows(
            kpool, vpool, table, t0_rows.astype(jnp.int32), k_c, v_c,
            kpool.shape[1])
        k = paged_kv.gather_rows(kpool, table)
        v = paged_kv.gather_rows(vpool, table)
        cap = k.shape[1]
        pos = jnp.arange(cap)
        keep = pos[None, None, :] <= pos_chunk[:, :, None]   # (B, S, cap)
        if window is not None:
            keep &= pos[None, None, :] > pos_chunk[:, :, None] - window
        out = scaled_dot_product_attention(
            self._rotated_q(x_chunk, pos_chunk), k, v,
            mask=keep[:, None], use_flash=False, scale=self.scale)
        return (self.out_proj(out.reshape(b, s, d)), kpool, vpool)


class MultiHeadAttention(_MHADecodeMixin, Layer):
    """Transformer attention. The reference builds this from primitives
    (nets.py:343 scaled_dot_product_attention); here it's a first-class layer
    with an optional Pallas flash-attention path on TPU."""

    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 bias: bool = True, use_flash: bool = True,
                 seq_parallel: Optional[str] = None, dtype=None,
                 num_kv_heads: Optional[int] = None,
                 rotary: bool = False, rotary_theta: float = 10000.0,
                 scale: Optional[float] = None):
        super().__init__()
        enforce(embed_dim % num_heads == 0,
                "embed_dim %s not divisible by heads %s", embed_dim, num_heads)
        # RoPE on q/k after projection (self-attention decoder blocks);
        # applied on the GLOBAL arrays before any SP sharding, so ring/
        # Ulysses see position-correct rotations
        self.rotary = rotary
        self.rotary_theta = float(rotary_theta)
        # what the scores are multiplied by; None = 1/sqrt(head_dim)
        self.scale = None if scale is None else float(scale)
        enforce(scale is None or seq_parallel is None,
                "scale= is not carried through seq_parallel=%s",
                seq_parallel)
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        # GQA/MQA: fewer K/V heads than Q heads (the flash kernel reads
        # shared K/V blocks via its index map; XLA repeats heads)
        self.num_kv_heads = num_kv_heads or num_heads
        enforce(num_heads % self.num_kv_heads == 0,
                "num_heads %s not divisible by num_kv_heads %s",
                num_heads, self.num_kv_heads)
        self.dropout_p = dropout
        self.use_flash = use_flash
        # None | "ring" | "ulysses": shard attention over the 'sp' mesh axis
        self.seq_parallel = seq_parallel
        # GQA under SP (r5): ring rotates kv blocks with their fewer
        # heads; Ulysses shards whole groups and enforces
        # kv_heads % sp == 0 at CALL time (the mesh isn't known here) —
        # its typed error points at ring for kv_heads < sp
        kv_dim = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(embed_dim, embed_dim, bias_attr=bias)
        self.k_proj = Linear(embed_dim, kv_dim, bias_attr=bias)
        self.v_proj = Linear(embed_dim, kv_dim, bias_attr=bias)
        self.out_proj = Linear(embed_dim, embed_dim, bias_attr=bias)

    def forward(self, query, key=None, value=None, attn_mask=None,
                causal: bool = False, segment_ids=None,
                window: Optional[int] = None):
        key = query if key is None else key
        value = key if value is None else value
        b, tq, d = query.shape
        tk = key.shape[1]
        h, hd = self.num_heads, self.head_dim
        q = self.q_proj(query).reshape(b, tq, h, hd)
        k, v = self.project_kv(key, value)
        if self.rotary:
            from ..ops.attention import rotary_embedding

            enforce(tk == tq, "rotary MHA is self-attention shaped "
                    "(tq=%s != tk=%s)", tq, tk)
            pos = jnp.arange(tq)
            q = rotary_embedding(q, pos, theta=self.rotary_theta)
            k = rotary_embedding(k, pos, theta=self.rotary_theta)

        if self.seq_parallel is not None:
            # key-padding masks ((B, Tk) or (B, 1, 1, Tk)) ride the SP
            # paths (ring rotates the mask block with its K/V; Ulysses
            # all-gathers it); anything per-head/per-query is an explicit
            # error, never a silent fall-back to full attention — the
            # full path materializes (B,H,T,T) scores and would OOM on
            # exactly the sequence lengths SP exists for
            kv_mask = None
            if attn_mask is not None:
                from ..ops.attention import _as_kv_mask

                kv_mask = _as_kv_mask(attn_mask, b, tk)
                enforce(kv_mask is not None,
                        "seq_parallel=%s supports only key-padding masks "
                        "((B, Tk) or (B, 1, 1, Tk)); got shape %s",
                        self.seq_parallel, attn_mask.shape)
            enforce(not (self.training and self.dropout_p > 0),
                    "seq_parallel attention does not support attention "
                    "dropout; set dropout=0 on MultiHeadAttention")
            if self.seq_parallel == "ring":
                enforce(tk == tq, "ring attention requires self-attention "
                        "shapes (tq=%s != tk=%s); use 'ulysses' for "
                        "cross-attention", tq, tk)
            from ..parallel.context_parallel import context_parallel_attention

            kw = ({"use_flash": self.use_flash}
                  if self.seq_parallel in ("ulysses", "ring") else {})
            out = context_parallel_attention(
                q, k, v, impl=self.seq_parallel, causal=causal,
                kv_mask=kv_mask, segment_ids=segment_ids, window=window,
                **kw)
        else:
            from ..ops.attention import scaled_dot_product_attention

            out = scaled_dot_product_attention(
                q, k, v, mask=attn_mask, causal=causal,
                dropout_p=self.dropout_p if self.training else 0.0,
                dropout_key=self.rng("attn_dropout") if (self.training and self.dropout_p > 0) else None,
                use_flash=self.use_flash, segment_ids=segment_ids,
                window=window, scale=self.scale)
        out = out.reshape(b, tq, d)
        return self.out_proj(out)


def _apply_act(x, act: Optional[str]):
    if act is None:
        return x
    fn = getattr(OM, act, None) or getattr(jax.nn, act, None)
    enforce(fn is not None, "unknown activation %s", act)
    return fn(x)


# Activation layers (paddle-style class wrappers)
class ReLU(Layer):
    def forward(self, x):
        return OM.relu(x)


class GELU(Layer):
    def __init__(self, approximate: bool = False):
        super().__init__()
        self.approximate = approximate

    def forward(self, x):
        return OM.gelu(x, self.approximate)


class Sigmoid(Layer):
    def forward(self, x):
        return OM.sigmoid(x)


class Tanh(Layer):
    def forward(self, x):
        return OM.tanh(x)


class Softmax(Layer):
    def __init__(self, axis: int = -1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return ON.softmax(x, self.axis)


class Flatten(Layer):
    def __init__(self, start_axis: int = 1):
        super().__init__()
        self.start_axis = start_axis

    def forward(self, x):
        from ..ops.tensor import flatten

        return flatten(x, self.start_axis)


class MultiBoxHead(Layer):
    """SSD detection head over multiple feature maps (reference:
    python/paddle/fluid/layers/detection.py multi_box_head): a 3x3 conv
    per map predicts box deltas (4A channels) and class logits (CA
    channels); priors come from ops.detection.prior_box per map.

    ``in_channels``: channel count of each input feature map (the fluid
    version infers these from the graph; eager layers declare them).
    min/max sizes follow the fluid ratio derivation when not given.
    """

    def __init__(self, in_channels: Sequence[int], image_size,
                 num_classes: int, *, base_size: Optional[int] = None,
                 aspect_ratios: Sequence[Sequence[float]] = (),
                 min_ratio: int = 20, max_ratio: int = 90,
                 min_sizes: Optional[Sequence[float]] = None,
                 max_sizes: Optional[Sequence[float]] = None,
                 steps: Optional[Sequence[float]] = None,
                 variances: Sequence[float] = (0.1, 0.1, 0.2, 0.2),
                 flip: bool = True, clip: bool = False,
                 offset: float = 0.5, dtype=None):
        super().__init__()
        from ..ops import detection as _D

        n_maps = len(in_channels)
        self.image_size = ((image_size, image_size)
                           if isinstance(image_size, int) else
                           tuple(image_size))
        base = base_size or self.image_size[0]
        if min_sizes is None:
            # fluid derivation: first map at base*10%%, the rest spread
            # min_ratio..max_ratio evenly (layers/detection.py)
            min_sizes, max_sizes = [base * 0.1], [base * 0.2]
            if n_maps > 1:
                step = int(math.floor((max_ratio - min_ratio)
                                      / max(n_maps - 2, 1)))
                for r in range(min_ratio, max_ratio + 1, max(step, 1)):
                    min_sizes.append(base * r / 100.0)
                    max_sizes.append(base * (r + step) / 100.0)
                min_sizes = min_sizes[:n_maps]
                max_sizes = max_sizes[:n_maps]
        self.min_sizes = [([s] if not isinstance(s, (list, tuple)) else
                           list(s)) for s in min_sizes]
        self.max_sizes = [([s] if not isinstance(s, (list, tuple)) else
                           list(s)) for s in (max_sizes or [])]
        if not aspect_ratios:
            aspect_ratios = [[2.0]] * n_maps
        self.aspect_ratios = [list(a) for a in aspect_ratios]
        self.steps = steps
        self.variances = tuple(variances)
        self.flip, self.clip, self.offset = flip, clip, offset
        self.num_classes = num_classes

        self.num_priors = []
        self.loc_convs = LayerList()
        self.conf_convs = LayerList()
        for i, c_in in enumerate(in_channels):
            a = _D.prior_box_count(
                self.min_sizes[i],
                self.max_sizes[i] if self.max_sizes else (),
                self.aspect_ratios[i], flip)
            self.num_priors.append(a)
            self.loc_convs.append(Conv2D(c_in, a * 4, 3, padding=1,
                                         dtype=dtype))
            self.conf_convs.append(Conv2D(c_in, a * num_classes, 3,
                                          padding=1, dtype=dtype))

    def forward(self, inputs):
        from ..ops import detection as _D

        locs, confs, boxes, variances = [], [], [], []
        for i, x in enumerate(inputs):
            n = x.shape[0]
            loc = self.loc_convs[i](x)          # (N, 4A, H, W)
            conf = self.conf_convs[i](x)        # (N, CA, H, W)
            h, w = x.shape[2], x.shape[3]
            locs.append(jnp.transpose(loc, (0, 2, 3, 1))
                        .reshape(n, -1, 4))
            confs.append(jnp.transpose(conf, (0, 2, 3, 1))
                         .reshape(n, -1, self.num_classes))
            step = ((self.steps[i], self.steps[i])
                    if self.steps else (0.0, 0.0))
            b, v = _D.prior_box(
                (h, w), self.image_size, self.min_sizes[i],
                self.max_sizes[i] if self.max_sizes else (),
                self.aspect_ratios[i], variances=self.variances,
                flip=self.flip, clip=self.clip, step=step,
                offset=self.offset)
            boxes.append(b.reshape(-1, 4))
            variances.append(v.reshape(-1, 4))
        return (jnp.concatenate(locs, 1), jnp.concatenate(confs, 1),
                jnp.concatenate(boxes, 0), jnp.concatenate(variances, 0))
