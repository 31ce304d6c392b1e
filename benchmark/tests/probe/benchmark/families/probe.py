"""The ``probe`` family: what a family that is not the dense decoder
asks of the seam, at a size a CPU test holds. One leading dense MLP
block, then blocks that mix ``experts`` small MLPs by a softmax router
with a bias: leaves of rank 3 (the stacked experts), a rank-1 leaf that
starts at zero (the router's bias), and layers whose shapes depend on
their index. No attention: a token sees itself only, which is all the
loss contract needs (a mean over rows). It is no cell of the benchmark;
``tests/test_family_probe.py`` copies these files into a root of its
own and drives the unedited harness over them.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from benchmark.harness.manifest import load_reference

reference = load_reference(__file__, "probe_f32")


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden: int
    layers: int
    dense_layers: int
    ffn: int
    experts: int
    expert_ffn: int
    vocab: int
    eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(hidden=c["hidden_size"], layers=c["num_hidden_layers"],
                   dense_layers=c["num_dense_layers"],
                   ffn=c["intermediate_size"], experts=c["num_experts"],
                   expert_ffn=c["moe_intermediate_size"],
                   vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]))


def top_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    return {"embed.weight": (dims.vocab, dims.hidden),
            "norm_f.weight": (dims.hidden,),
            "lm_head": (dims.hidden, dims.vocab)}


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    h, p = dims.hidden, f"blocks.{i}."
    if i < dims.dense_layers:
        return {p + "norm.weight": (h,), p + "up": (h, dims.ffn),
                p + "down": (dims.ffn, h)}
    e, f = dims.experts, dims.expert_ffn
    return {p + "norm.weight": (h,), p + "router": (h, e),
            p + "router_bias": (e,), p + "up": (e, h, f),
            p + "down": (e, f, h)}


def leaf_rule(name: str, shape) -> str:
    if name.endswith("router_bias"):
        return "zeros"
    return "ones" if len(shape) == 1 else "uniform"


def train_flops_per_token(dims, seq: int) -> float:
    """6 x every matmul weight a token meets: every expert runs on every
    token here (a dense mixture), so all of them count."""
    h = dims.hidden
    mix = dims.experts * (h + 2 * h * dims.expert_ffn)
    return 6.0 * (dims.dense_layers * 2 * h * dims.ffn
                  + (dims.layers - dims.dense_layers) * mix
                  + h * dims.vocab)


def build_model(config: dict, dims, dtype: str, max_position: int,
                remat: bool):
    """The program's side: a small ``paddle_tpu.nn.Layer`` with
    ``forward_loss``, matmul operands in the active policy's compute
    type as ``nn.Linear`` has them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.core import get_policy

    def mm(spec, a, b):
        pol = get_policy()
        return pol.cast_to_output(jnp.einsum(
            spec, pol.cast_to_compute(a), pol.cast_to_compute(b)))

    class DenseBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            self.norm = nn.RMSNorm(dims.hidden, epsilon=dims.eps)
            self.create_parameter("up", (dims.hidden, dims.ffn))
            self.create_parameter("down", (dims.ffn, dims.hidden))

        def forward(self, x):
            y = mm("bth,hf->btf", self.norm(x), self.up)
            return x + mm("btf,fh->bth", jax.nn.silu(y), self.down)

    class MixBlock(nn.Layer):
        def __init__(self):
            super().__init__()
            e, f = dims.experts, dims.expert_ffn
            self.norm = nn.RMSNorm(dims.hidden, epsilon=dims.eps)
            self.create_parameter("router", (dims.hidden, e))
            self.create_parameter("router_bias", (e,), is_bias=True)
            self.create_parameter("up", (e, dims.hidden, f))
            self.create_parameter("down", (e, f, dims.hidden))

        def forward(self, x):
            y = self.norm(x)
            gate = jax.nn.softmax(
                mm("bth,he->bte", y, self.router).astype(jnp.float32)
                + self.router_bias, axis=-1)
            z = jax.nn.silu(mm("bth,ehf->btef", y, self.up))
            out = mm("btef,efh->bteh", z, self.down)
            return x + jnp.sum(gate[..., None] * out, axis=2)

    class ProbeLM(nn.Layer):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(dims.vocab, dims.hidden)
            self.blocks = nn.LayerList([
                DenseBlock() if i < dims.dense_layers else MixBlock()
                for i in range(dims.layers)])
            self.norm_f = nn.RMSNorm(dims.hidden, epsilon=dims.eps)
            self.create_parameter("lm_head", (dims.hidden, dims.vocab))

        def forward_loss(self, ids):
            x = self.embed(ids)
            for blk in self.blocks:
                # a new lambda each trace: checkpoint caches by function,
                # and a block closes over this trace's parameters
                x = (jax.checkpoint(lambda h, b=blk: b(h))(x) if remat
                     else blk(x))
            logits = mm("bth,hv->btv", self.norm_f(x), self.lm_head)
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                lp, ids[:, 1:, None], axis=-1))

    return ProbeLM()
