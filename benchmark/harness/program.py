"""The only place the benchmark touches the program: build the system
under test the way its users do, with the benchmark's seeded weights.

``chip_smoke.py``'s constructors are the pattern: ``parallel.Trainer``
with ``amp="mixed_bf16"`` for training, ``BatchedDecoder`` behind an
in-process ``Router`` + ``LocalReplica`` for serving.
"""

from __future__ import annotations

import jax

from . import weights as W


def build_model(config: dict, dims, seed: int, dtype: str,
                max_position: int, remat: bool):
    """``GPTForCausalLM`` at the configuration's sizes holding the
    benchmark's weights. The constructor runs under ``jax.eval_shape``
    (nothing is allocated by the program's own initialisers, no 7 GB
    made leaf by leaf and thrown away); the weights then come from one
    jitted call, on the device, in ``dtype``."""
    import paddle_tpu as pt
    from paddle_tpu.core.config import FLAGS
    from paddle_tpu.models import gpt as G

    FLAGS.set("default_dtype", dtype)
    cfg = G.GPTConfig(
        vocab_size=dims.vocab, hidden_size=dims.hidden,
        num_layers=dims.layers, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, intermediate_size=dims.ffn,
        max_position=max_position, rope_theta=dims.theta, remat=remat,
        attn_window=dims.window, tie_embeddings=dims.tied)
    if dims.hidden // dims.heads != dims.head_dim:
        raise ValueError("GPTConfig derives head_dim as hidden / heads")
    box = {}

    def construct():
        box["model"] = G.GPTForCausalLM(cfg)
        return dict(box["model"].named_parameters())

    pt.seed(0)
    shapes = jax.eval_shape(construct)
    pt.seed(0)      # the global key held a tracer: make it concrete again
    W.check_names(W.leaf_shapes(dims),
                  ((k, v.shape) for k, v in shapes.items()))
    model = box["model"]
    model.set_parameters(W.make_all(seed, dims, dtype))
    return model


def build_trainer(model, lr: float, amp: str):
    """``parallel.Trainer`` over a one-chip mesh with Adam and the fused
    linear-CE loss (``forward_loss``)."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel

    def loss_builder(params, buffers, rng, ids):
        loss, new_buffers = model.functional_call(
            params, ids, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    return parallel.Trainer(model, optimizer.Adam(lr), loss_builder,
                            mesh=mesh, amp=amp)


def build_serving(model, serve: dict):
    """(decoder, replica, router): the contiguous arena behind one
    in-process replica and the router, all defaults but the arena's
    sizes."""
    from paddle_tpu.serving import BatchedDecoder
    from paddle_tpu.serving_router import LocalReplica, Router

    dec = BatchedDecoder(model.eval(), slots=serve["slots"],
                         capacity=serve["capacity"],
                         prompt_bucket=serve["prompt_bucket"],
                         decode_steps=serve["decode_steps"])
    replica = LocalReplica(dec).start()
    return dec, replica, Router([replica])
