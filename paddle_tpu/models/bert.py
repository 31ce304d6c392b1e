"""BERT — BASELINE config 3 (bert-base pretraining: MLM + NSP).

The reference era has no in-tree BERT; this model is the framework's
transformer-encoder flagship, built on nn.transformer with the Pallas flash
attention path and TP-ready parameter names (see parallel/sharding.py rules).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from .. import nn
from ..metrics import accuracy
from ..nn.transformer import TransformerEncoder
from ..ops import loss as L


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    use_flash: bool = True
    # None | 'ring' | 'ulysses' — shard attention over the 'sp' mesh axis
    seq_parallel: Optional[str] = None
    remat: bool = False        # jax.checkpoint per block (HBM for FLOPs)
    remat_policy: Optional[str] = None  # None (flash o, lse only) | "dots"
    # sliding-window/local attention width (None = full; the flash
    # kernel skips out-of-band blocks — O(T*window) long-context mode)
    attn_window: Optional[int] = None
    scan_layers: bool = False  # lax.scan over stacked layers (needs
    #                            dropout == 0 while training)
    # > 0 swaps each block's dense FFN for a Switch-MoE FFN (nn.moe);
    # experts shard over the 'ep' mesh axis, per-layer load-balance aux
    # losses ride functional_call's new_buffers (*.ffn.aux_loss)
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @classmethod
    def base(cls):
        return cls()

    @classmethod
    def tiny(cls):
        """For tests: 2 layers, hidden 64."""
        return cls(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                   intermediate_size=128, max_position=128, dropout=0.0)

    @classmethod
    def moe_smoke(cls, layers: int = 4):
        """The ONE bert_moe smoke configuration shared by the test suite
        and the multichip dryrun (capacity 2.0 keeps routing drops out of
        loss-match tolerances) — tune it in one place."""
        return cls(vocab_size=256, hidden_size=64, num_layers=layers,
                   num_heads=4, intermediate_size=128, max_position=32,
                   dropout=0.0, moe_experts=4, moe_capacity_factor=2.0)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.tok = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.pos = nn.Embedding(cfg.max_position, cfg.hidden_size)
        self.seg = nn.Embedding(cfg.type_vocab_size, cfg.hidden_size)
        self.norm = nn.LayerNorm(cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        t = input_ids.shape[1]
        if position_ids is None:
            position_ids = jnp.arange(t)[None, :]
        x = self.tok(input_ids) + self.pos(position_ids)
        if token_type_ids is not None:
            x = x + self.seg(token_type_ids)
        return self.drop(self.norm(x))


class BertModel(nn.Layer):
    def __init__(self, cfg: Optional[BertConfig] = None):
        super().__init__()
        self.cfg = cfg = cfg or BertConfig.base()
        self.embeddings = BertEmbeddings(cfg)
        self.encoder = TransformerEncoder(
            cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.intermediate_size, cfg.dropout, activation="gelu",
            normalize_before=False, use_flash=cfg.use_flash,
            seq_parallel=cfg.seq_parallel, remat=cfg.remat,
            remat_policy=cfg.remat_policy,
            scan_layers=cfg.scan_layers, attn_window=cfg.attn_window,
            moe_experts=cfg.moe_experts,
            moe_capacity_factor=cfg.moe_capacity_factor)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size, act="tanh")

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None, segment_ids=None):
        """``segment_ids``/``position_ids``: the PACKED-batch form
        (data.bucketing.pack_sequences) — attention confined to each
        packed segment, positions restarting per segment."""
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        mask = None
        if attention_mask is not None:
            # (B, T) keep-mask → broadcastable (B, 1, 1, T)
            mask = attention_mask[:, None, None, :]
        h = self.encoder(x, mask=mask, segment_ids=segment_ids)
        pooled = self.pooler(h[:, 0])
        return h, pooled


class BertForPretraining(nn.Layer):
    """MLM head (tied decoder weight not required for parity) + NSP head."""

    def __init__(self, cfg: Optional[BertConfig] = None):
        super().__init__()
        cfg = cfg or BertConfig.base()
        self.bert = BertModel(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                                       act="gelu")
        self.mlm_norm = nn.LayerNorm(cfg.hidden_size)
        self.mlm_decoder = nn.Linear(cfg.hidden_size, cfg.vocab_size)
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        h, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        mlm_logits = self.mlm_decoder(self.mlm_norm(self.mlm_transform(h)))
        nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits

    def forward_fused_loss(self, input_ids, mlm_labels, nsp_label,
                           token_type_ids=None, attention_mask=None,
                           vocab_chunk: int = 4096):
        """Pretrain loss WITHOUT materializing (B, T, V) logits: the MLM
        head goes through ops.fused_loss.linear_cross_entropy (chunked
        vocab scan — the HBM hot spot of MLM training; fused_loss.py
        docstring has the numbers)."""
        from ..core.dtypes import get_policy
        from ..ops.fused_loss import mean_linear_cross_entropy

        h, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        h_mlm = self.mlm_norm(self.mlm_transform(h))
        b, t, d = h_mlm.shape
        # the vocab matmuls honor the AMP compute dtype (bf16 on the MXU),
        # exactly like the Linear head they replace; the op's logsumexp
        # accumulators stay fp32 internally
        pol = get_policy()
        mlm_loss = mean_linear_cross_entropy(
            pol.cast_to_compute(h_mlm.reshape(b * t, d)),
            pol.cast_to_compute(self.mlm_decoder.weight),
            pol.cast_to_compute(self.mlm_decoder.bias),
            mlm_labels.reshape(-1), chunk=vocab_chunk, ignore_index=-100)
        nsp_logits = self.nsp(pooled)
        nsp_loss = jnp.mean(L.softmax_with_cross_entropy(nsp_logits,
                                                         nsp_label))
        return mlm_loss + nsp_loss

    def forward_packed_loss(self, tokens, positions, segment_ids,
                            mlm_labels, vocab_chunk: int = 4096):
        """MLM loss over a PACKED batch (data.bucketing.pack_sequences
        layout: multiple sequences per row, segment id 0 = padding tail).
        Attention is confined to each segment via the Pallas packed-batch
        path, positions restart per segment, and padding tokens are
        excluded from the loss (ignore_index). NSP is skipped — a packed
        row holds many unrelated documents, so next-sentence pairing has
        no meaning there."""
        from ..core.dtypes import get_policy
        from ..ops.fused_loss import mean_linear_cross_entropy

        h, _ = self.bert(tokens, position_ids=positions,
                         segment_ids=segment_ids)
        h_mlm = self.mlm_norm(self.mlm_transform(h))
        b, t, d = h_mlm.shape
        labels = jnp.where(segment_ids > 0, mlm_labels, -100)
        pol = get_policy()
        return mean_linear_cross_entropy(
            pol.cast_to_compute(h_mlm.reshape(b * t, d)),
            pol.cast_to_compute(self.mlm_decoder.weight),
            pol.cast_to_compute(self.mlm_decoder.bias),
            labels.reshape(-1), chunk=vocab_chunk, ignore_index=-100)


def pretrain_loss(outputs, labels):
    """labels: dict(mlm_labels (B,T) with -100 = unmasked, nsp_label (B,))."""
    mlm_logits, nsp_logits = outputs
    mlm_labels = labels["mlm_labels"]
    valid = (mlm_labels >= 0)
    safe_labels = jnp.where(valid, mlm_labels, 0)
    tok_loss = L.softmax_with_cross_entropy(mlm_logits,
                                            safe_labels).squeeze(-1)
    mlm_loss = jnp.sum(tok_loss * valid) / jnp.maximum(jnp.sum(valid), 1)
    nsp_loss = jnp.mean(
        L.softmax_with_cross_entropy(nsp_logits, labels["nsp_label"]))
    return mlm_loss + nsp_loss


def pretrain_metrics(outputs, labels):
    mlm_logits, nsp_logits = outputs
    valid = (labels["mlm_labels"] >= 0)
    pred = jnp.argmax(mlm_logits, -1)
    mlm_acc = jnp.sum((pred == labels["mlm_labels"]) * valid) / \
        jnp.maximum(jnp.sum(valid), 1)
    return {"mlm_acc": mlm_acc,
            "nsp_acc": accuracy(nsp_logits, labels["nsp_label"])}
