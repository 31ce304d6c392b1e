"""Where the harness touches the program: build the system under test
the way its users do, with the benchmark's seeded weights.

The model is the cell's family's (``manifest.load_family``); what runs
it is common to every family and no family's choice: ``parallel.Trainer``
over ``forward_loss`` with the configuration's ``amp`` for training,
``BatchedDecoder`` behind an in-process ``Router`` + ``LocalReplica``
for serving (``chip_smoke.py``'s constructors are the pattern).
"""

from __future__ import annotations

import jax

from . import weights as W


def build_model(fam, config: dict, dims, seed: int, dtype: str,
                max_position: int, remat: bool):
    """The family's model at the configuration's sizes holding the
    benchmark's weights. The family's constructor runs under
    ``jax.eval_shape`` (nothing is allocated by the program's own
    initialisers, no 7 GB made leaf by leaf and thrown away); the
    weights then come from one jitted call, on the device, in
    ``dtype``."""
    import paddle_tpu as pt
    from paddle_tpu.core.config import FLAGS

    FLAGS.set("default_dtype", dtype)
    box = {}

    def construct():
        box["model"] = fam.build_model(config, dims, dtype, max_position,
                                       remat)
        return dict(box["model"].named_parameters())

    pt.seed(0)
    shapes = jax.eval_shape(construct)
    pt.seed(0)      # the global key held a tracer: make it concrete again
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in shapes.items()))
    model = box["model"]
    model.set_parameters(W.make_all(seed, fam, dims, dtype))
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
