"""Decoder-only causal LM (GPT/Llama-style) — the modern long-context
flagship workload, assembled from this framework's own pieces: RoPE
(ops.attention.rotary_embedding), GQA MultiHeadAttention on the Pallas
flash path, RMSNorm pre-norm blocks, SwiGLU (or Switch-MoE) FFNs,
KV-cached greedy decode, and a fused linear-CE training head.

Green-field relative to the reference (its transformer story is the
encoder-decoder NMT model, reference:
benchmark/fluid/models/machine_translation.py); this family exists so a
user scaling a decoder LM finds the whole recipe — causal flash
attention, sequence parallelism (seq_parallel='ring' supports GQA),
pipeline-able uniform blocks, MoE FFNs — in one model.

Geometry notes (TPU-first): head_dim 64/128 keeps the flash dispatch
gate open; hidden sizes stay multiples of 128 for MXU tiling; the block
is uniform h -> h so parallel.pipeline_apply and scan_layers both apply.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import initializer as I
from .. import nn
from ..core.enforce import enforce
from ..nn.layer import Layer
from ..telemetry.scopes import scope


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None   # < num_heads = GQA/MQA
    intermediate_size: int = 2048        # SwiGLU width
    max_position: int = 2048             # decode-cache capacity default
    rope_theta: float = 10000.0
    dropout: float = 0.0                 # residual/FFN dropout
    use_flash: bool = True
    remat: bool = False  # keep a block's input + flash o, lse; recompute rest
    # None | 'ring' | 'ulysses' — shard attention over the 'sp' axis
    # (ring supports GQA; see parallel.context_parallel)
    seq_parallel: Optional[str] = None
    attn_window: Optional[int] = None    # sliding-window local attention
    moe_experts: int = 0                 # > 0: Switch-MoE FFN over 'ep'
    moe_capacity_factor: float = 1.25
    tie_embeddings: bool = True          # LM head = embedding^T

    @classmethod
    def tiny(cls):
        """For tests: 2 layers, hidden 128, GQA 4q/2kv, head_dim 32."""
        return cls(vocab_size=512, hidden_size=128, num_layers=2,
                   num_heads=4, num_kv_heads=2, intermediate_size=256,
                   max_position=128)

    @classmethod
    def small(cls):
        """A llama-ish small config: head_dim 64 (flash-eligible)."""
        return cls(vocab_size=32000, hidden_size=768, num_layers=12,
                   num_heads=12, num_kv_heads=4, intermediate_size=2048,
                   max_position=2048)


class _SwiGLU(Layer):
    """Gated FFN: down(silu(gate(x)) * up(x)) — the Llama MLP."""

    def __init__(self, d_model: int, d_ff: int, dropout: float = 0.0):
        super().__init__()
        self.gate = nn.Linear(d_model, d_ff, bias_attr=False)
        self.up = nn.Linear(d_model, d_ff, bias_attr=False)
        self.down = nn.Linear(d_ff, d_model, bias_attr=False)
        self.drop = nn.Dropout(dropout)

    def forward(self, x):
        return self.drop(self.down(jax.nn.silu(self.gate(x)) * self.up(x)))


class GPTBlock(Layer):
    """Pre-norm decoder block: x + attn(rms(x)); x + ffn(rms(x)).
    Uniform h -> h (pipeline_apply / scan_layers compatible)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.attn_window = cfg.attn_window
        self.norm1 = nn.RMSNorm(cfg.hidden_size)
        self.self_attn = nn.MultiHeadAttention(
            cfg.hidden_size, cfg.num_heads, dropout=cfg.dropout,
            bias=False, use_flash=cfg.use_flash,
            seq_parallel=cfg.seq_parallel,
            num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
            rotary=True, rotary_theta=cfg.rope_theta)
        self.norm2 = nn.RMSNorm(cfg.hidden_size)
        if cfg.moe_experts:
            self.ffn = nn.SwitchFFN(
                cfg.hidden_size, cfg.intermediate_size, cfg.moe_experts,
                capacity_factor=cfg.moe_capacity_factor)
        else:
            self.ffn = _SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                               cfg.dropout)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, kv_mask=None):
        with scope("attn"):
            x = x + self.drop(self.self_attn(
                self.norm1(x), causal=True, window=self.attn_window,
                attn_mask=None if kv_mask is None
                else kv_mask[:, None, None, :]))
        with scope("mlp"):
            return x + self.ffn(self.norm2(x))


class GPTForCausalLM(Layer):
    """Token embedding -> N GPTBlocks -> final RMSNorm -> LM head.

    ``forward(ids)`` returns (B, T, V) logits (tied head when
    cfg.tie_embeddings). ``forward_loss(ids, labels)`` is the training
    entry: next-token shift + fused chunked linear-CE (the logits
    matrix never materializes; ops/fused_loss.py). ``greedy_decode``
    runs the KV-cached incremental loop (RoPE applied at each cache
    position — MultiHeadAttention.forward_step).
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        enforce((cfg.hidden_size // cfg.num_heads) % 2 == 0,
                "rotary needs an even head_dim, got %s",
                cfg.hidden_size // cfg.num_heads)
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size)
        if not cfg.tie_embeddings:
            self.create_parameter(
                "lm_head", (cfg.hidden_size, cfg.vocab_size), None,
                I.XavierUniform())

    def _head_weight(self):
        return (self.embed.weight.T if self.cfg.tie_embeddings
                else self.lm_head)

    @scope("embed")
    def _embed(self, ids):
        return self.embed(ids)

    @scope("head")
    def _head(self, x):
        return self.norm_f(x) @ self._head_weight()

    def _trunk(self, ids, kv_mask=None):
        x = self._embed(ids)
        for blk in self.blocks:
            if self.cfg.remat:
                x = jax.checkpoint(lambda h, b=blk: b(h, kv_mask=kv_mask),
                                   policy=nn.remat_policy())(x)
            else:
                x = blk(x, kv_mask=kv_mask)
        return x

    def forward(self, ids, kv_mask=None):
        return self._head(self._trunk(ids, kv_mask=kv_mask))

    def forward_loss(self, ids, labels=None, kv_mask=None,
                     vocab_chunk: int = 1024, ignore_index: int = -100):
        """Mean next-token CE. ``labels`` default to ids shifted left
        (standard causal-LM training); pass explicit labels with
        ``ignore_index`` holes for masked/padded positions."""
        x = self._trunk(ids, kv_mask=kv_mask)
        with scope("head"):     # the head itself is ``linear_ce``
            h = self.norm_f(x)
        return next_token_loss(h, self._head_weight(), ids, labels,
                               vocab_chunk, ignore_index)

    def init_cache(self, batch: int, capacity: int, dtype=None):
        """The decode cache ``serving.BatchedDecoder`` holds as its
        arena: one (K, V) pair a block, (batch, capacity, kv_heads,
        head_dim) each."""
        return [blk.self_attn.init_cache(batch, capacity, dtype)
                for blk in self.blocks]

    def _cached_blocks(self, x, caches, attn_step, head: bool = True,
                       head_at=None):
        """ONE definition of the cached-decode block composition
        (norm1 -> attn -> residual -> ffn -> norm_f@head) shared by the
        chunk, single-step, and per-row-cursor entries — the attention
        flavor is the only thing that varies. ``head=False`` skips the
        (S, V) head projection (cache-only prefill; XLA would DCE the
        dead matmul under jit, but eager callers pay it for real).
        ``head_at`` (a position of the chunk, may be traced) applies the
        head at that one position: ``x`` is cut to its row as soon as
        only that row is wanted, after the last block's attention (whose
        keys and values every position writes), so the last block's MLP
        and the head's product have one row and the logits are (B, V)."""
        new_caches = []
        last = len(self.blocks) - 1
        for i, (blk, (ck, cv)) in enumerate(zip(self.blocks, caches)):
            with scope("attn"):
                h = blk.norm1(x)
                a, ck, cv = attn_step(blk.self_attn, h, ck, cv)
                x = x + a
            if head_at is not None and i == last:
                x = lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
            with scope("mlp"):
                x = x + blk.ffn(blk.norm2(x))
            new_caches.append((ck, cv))
        if head_at is not None:
            return self._head(x)[:, 0], new_caches
        if not head:
            return None, new_caches
        return self._head(x), new_caches

    def _chunk_logits(self, toks, caches, t0, head: bool = True,
                      decode_kernel: bool = False, valid_len=None,
                      head_at=None):
        """S KV-cached positions in one pass: embed ``toks`` (B, S), run
        every block's forward_chunk at cache indices [t0, t0+S), return
        ((B, S, V) logits, new caches), or (B, V) logits of the chunk's
        position ``head_at`` alone (a whole-prompt prefill's first
        token). The speculative-decoding target scores its gamma+1
        candidates with one call. ``valid_len`` (how many of the S
        tokens are the sequence, the rest padding) is taken and not
        read: keys and values written past it sit above the cursor,
        which masks them, and no position before it attends to them."""
        return self._cached_blocks(
            self._embed(toks), caches,
            lambda sa, h, ck, cv: sa.forward_chunk(
                h, ck, cv, t0, window=self.cfg.attn_window,
                decode_kernel=decode_kernel),
            head=head, head_at=head_at)

    def _step_logits(self, tok, caches, t, decode_kernel: bool = False):
        """One KV-cached position: ``tok`` (B,) -> ((B, V), caches)."""
        logits, caches = self._chunk_logits(
            tok[:, None], caches, t, decode_kernel=decode_kernel)
        return logits[:, 0], caches

    def _step_logits_rows(self, tok, caches, t_rows,
                          decode_kernel: bool = False):
        """One KV-cached position PER ROW at per-row cursors ``t_rows``
        (B,) — the continuous-batching step (serving.BatchedDecoder).
        ``tok`` (B,) -> ((B, V) logits, caches)."""
        logits, caches = self._cached_blocks(
            self._embed(tok[:, None]), caches,
            lambda sa, h, ck, cv: sa.forward_step_rows(
                h, ck, cv, t_rows, window=self.cfg.attn_window,
                decode_kernel=decode_kernel))
        return logits[:, 0], caches

    def _chunk_logits_rows(self, toks, caches, t0_rows):
        """S KV-cached positions PER ROW at per-row chunk starts
        ``t0_rows`` (B,) — the arena speculative verify: every slot
        scores its gamma+1 candidates at its OWN cursor in ONE pass.
        ``toks`` (B, S) -> ((B, S, V) logits, caches)."""
        return self._cached_blocks(
            self._embed(toks), caches,
            lambda sa, h, ck, cv: sa.forward_chunk_rows(
                h, ck, cv, t0_rows, window=self.cfg.attn_window))

    def _chunk_logits_paged_rows(self, toks, pools, table, t0_rows):
        """S positions PER ROW against PAGED caches at per-row chunk
        starts (see _chunk_logits_rows). ``toks`` (B, S)."""
        return self._cached_blocks(
            self._embed(toks), pools,
            lambda sa, h, kp, vp: sa.forward_chunk_paged_rows(
                h, kp, vp, table, t0_rows,
                window=self.cfg.attn_window))

    def _step_logits_paged(self, tok, pools, table, t_rows):
        """One position PER ROW against PAGED caches: ``pools`` is the
        per-block [(kpool, vpool), ...] list, ``table`` the shared
        (B, n_log) page table. ``tok`` (B,) -> ((B, V) logits, pools)."""
        logits, pools = self._cached_blocks(
            self._embed(tok[:, None]), pools,
            lambda sa, h, kp, vp: sa.forward_step_paged(
                h, kp, vp, table, t_rows,
                window=self.cfg.attn_window))
        return logits[:, 0], pools

    def _chunk_logits_paged(self, toks, pools, table_row, t0,
                            head: bool = True):
        """S prefill positions for ONE row against paged caches (see
        _step_logits_paged). ``toks`` (1, S)."""
        return self._cached_blocks(
            self._embed(toks), pools,
            lambda sa, h, kp, vp: sa.forward_chunk_paged(
                h, kp, vp, table_row, t0,
                window=self.cfg.attn_window),
            head=head)

    def generate(self, prompt_ids, max_len: int, *, key=None,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 capacity: Optional[int] = None):
        """KV-cached continuation of ``prompt_ids`` (B, Tp) to total
        length ``max_len``; returns (B, max_len) token ids.

        ``temperature == 0`` is exact greedy (argmax, no key needed);
        otherwise tokens are drawn via ops.sampling.sample_from_logits
        (temperature scaling, then top-k, then nucleus top-p), with a
        per-position key derived by ``fold_in`` so the draw stream is
        independent of batch size and prompt length. ``eos_id`` freezes
        a row once it emits eos (every later token is eos_id).

        O(T) per step via per-block K/V caches; RoPE rotates each
        cached K at its absolute position and each query at its own.
        Green-field vs the reference (its decoding story is beam search
        over the NMT encoder-decoder, reference:
        benchmark/fluid/models/machine_translation.py)."""
        from ..ops.sampling import sample_from_logits

        enforce(not self.training,
                "generate runs in eval mode (call .eval()); live "
                "dropout would break the token-identical-to-forward "
                "contract")
        b, tp = prompt_ids.shape
        cap = capacity or max(self.cfg.max_position, max_len)
        enforce(max_len > tp, "max_len %s must exceed prompt %s",
                max_len, tp)
        enforce(cap >= max_len, "cache capacity %s < max_len %s", cap,
                max_len)
        sampled = float(temperature) != 0.0
        if sampled:
            enforce(key is not None,
                    "temperature > 0 samples and needs a PRNG key; "
                    "pass temperature=0 for greedy decoding")
        caches = [blk.self_attn.init_cache(b, cap)
                  for blk in self.blocks]

        # prefill: teacher-force the prompt through the step loop (the
        # scan keeps ONE compiled block body for prefill + generation)
        tokens = jnp.concatenate(
            [prompt_ids,
             jnp.zeros((b, max_len - tp), prompt_ids.dtype)], axis=1)

        def scan_step(carry, t):
            tok_prev, caches, done = carry
            # the flash-decode kernel masks pos <= t in-kernel and reads
            # only live cache blocks (eligible shapes; XLA mask path
            # otherwise) — safe here: generate() never runs under vmap
            logits, caches = self._step_logits(tok_prev, caches, t,
                                               decode_kernel=True)
            if sampled:
                nxt = sample_from_logits(
                    logits, jax.random.fold_in(key, t), temperature,
                    top_k, top_p)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(prompt_ids.dtype)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.asarray(eos_id, nxt.dtype),
                                nxt)
            # while still inside the prompt, feed the real next token
            inside = t + 1 < tp
            forced = lax.dynamic_index_in_dim(
                tokens, jnp.clip(t + 1, 0, max_len - 1), 1,
                keepdims=False)
            tok = jnp.where(inside, forced, nxt)
            if eos_id is not None:
                done = done | ((tok == eos_id) & jnp.logical_not(inside))
            return (tok, caches, done), tok

        (_, _, _), outs = lax.scan(
            scan_step,
            (tokens[:, 0], caches, jnp.zeros((b,), bool)),
            jnp.arange(max_len - 1))
        outs = jnp.swapaxes(outs, 0, 1)           # (B, max_len - 1)
        return jnp.concatenate([tokens[:, :1], outs], axis=1)

    def greedy_decode(self, prompt_ids, max_len: int,
                      capacity: Optional[int] = None):
        """KV-cached greedy continuation — generate(temperature=0)."""
        return self.generate(prompt_ids, max_len, temperature=0.0,
                             capacity=capacity)


def next_token_loss(h, head_weight, ids, labels=None,
                    vocab_chunk: int = 1024, ignore_index: int = -100):
    """The training tail of both causal-LM shells: mean cross-entropy of
    the final hidden states ``h`` (B, T, D) under ``head_weight`` (D, V)
    against ``labels`` (default: ``ids`` shifted left, the last position
    ignored), by the fused chunked head (``ops/fused_loss.py``, scope
    ``linear_ce``): the (B, T, V) logits never exist."""
    from ..ops.fused_loss import mean_linear_cross_entropy

    if labels is None:
        labels = jnp.concatenate(
            [ids[:, 1:],
             jnp.full((ids.shape[0], 1), ignore_index, ids.dtype)],
            axis=1)
    b, t, d = h.shape
    return mean_linear_cross_entropy(
        h.reshape(b * t, d), head_weight, None, labels.reshape(-1),
        chunk=vocab_chunk, ignore_index=ignore_index)


def loss_fn(logits, labels, ignore_index: int = -100):
    """Plain (unfused) next-token CE over (B, T, V) logits — the test
    oracle for forward_loss."""
    b, t, v = logits.shape
    flat = logits.reshape(b * t, v).astype(jnp.float32)
    lbl = labels.reshape(-1)
    keep = lbl != ignore_index
    lp = jax.nn.log_softmax(flat)
    picked = jnp.take_along_axis(
        lp, jnp.clip(lbl, 0, v - 1)[:, None], axis=1)[:, 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.maximum(
        jnp.sum(keep), 1)
