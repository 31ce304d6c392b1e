#!/usr/bin/env python3
"""Rehearsal 3: compile each cell's programs at the real sizes for a
described TPU v5e (``v5e:2x2`` topology, one described chip), with no
chip attached. Prints ``memory_analysis()``, the ``tpu_custom_call``
count and a hash of the lowered program's text of each (equal hashes on
two commits: the same program goes to the compiler). A compile that
passes is not a chip run: nothing here is a time or a result.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [cell ...]
"""

import hashlib
import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def report(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    gb = lambda b: f"{b / 1e9:.2f} GB"
    print(f"{name}: compiled in {time.perf_counter() - t0:.0f} s; "
          f"arguments {gb(m.argument_size_in_bytes)}, outputs "
          f"{gb(m.output_size_in_bytes)}, aliased "
          f"{gb(m.alias_size_in_bytes)}, temporaries "
          f"{gb(m.temp_size_in_bytes)}; "
          f"{compiled.as_text().count('tpu_custom_call')} tpu_custom_call; "
          f"lowered text sha256 "
          f"{hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]}",
          flush=True)


def main(argv):
    from jax.experimental import topologies

    from benchmark.harness import loadgen, manifest, program
    import paddle_tpu.ops.attention as attn
    from paddle_tpu.core.mesh import mesh_scope

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)
    flash = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    flash._use_interpret = lambda: False
    importlib.import_module(
        "paddle_tpu.ops.pallas.flash_decode")._use_interpret = lambda: False

    man = manifest.load_manifest()
    names = argv or [w["name"] for w in man["workloads"]]
    for name in names:
        cell = manifest.Cell(man, name)
        cfg, mix, fam = cell.config, cell.traffic, cell.family
        dims = fam.Dims.from_config(cfg)
        with attn.force_flash():
            if cell.kind == "train":
                model = _abstract_model(program, fam, cfg, dims,
                                        mix["seq"], cfg["train"]["remat"])
                import paddle_tpu as pt
                from paddle_tpu import optimizer, parallel

                tr = object.__new__(parallel.Trainer)
                tr.amp_policy = cfg["train"]["amp"]
                tr.optimizer = optimizer.Adam(mix["lr"])
                tr._pmean_axes, tr.grad_compression, tr.plan = (), None, None

                def loss_builder(params, buffers, rng, ids):
                    loss, nb = model.functional_call(
                        params, ids, buffers=buffers, rng=rng,
                        training=True, method="forward_loss")
                    return loss, ({}, nb)

                tr.loss_builder = loss_builder
                params = model.shapes
                args = on_chip((
                    params, {}, jax.eval_shape(tr.optimizer.init, params),
                    jax.eval_shape(lambda: jax.random.key(0)),
                    jax.ShapeDtypeStruct((mix["rows"], mix["seq"]),
                                         jnp.int32)))
                mesh = jax.sharding.Mesh(topo.devices[:1], ("dp",))
                with mesh_scope(mesh):
                    report(f"{name} train step", jax.jit(
                        tr._step, donate_argnums=(0, 1, 2)).lower(*args))
            else:
                from paddle_tpu.serving import BatchedDecoder

                serve = cfg["serve"]
                model = _abstract_model(program, fam, cfg, dims,
                                        serve["capacity"], False).eval()
                dec = BatchedDecoder(
                    model, slots=serve["slots"],
                    capacity=serve["capacity"],
                    prompt_bucket=serve["prompt_bucket"],
                    decode_steps=serve["decode_steps"])
                mstate = on_chip((model.shapes, {}))
                caches = on_chip(dec.caches)
                i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,
                                                      sharding=chip)
                report(f"{name} decode step", dec._build_multi_step(
                    serve["decode_steps"]).lower(
                        mstate, caches, i32(serve["slots"]),
                        i32(serve["slots"]), jax.ShapeDtypeStruct(
                            (serve["slots"],), jnp.uint32, sharding=chip)))
                buckets = loadgen.prompt_buckets(
                    mix, serve["prompt_bucket"], serve["capacity"])
                for lb in (buckets[0], buckets[-1]):
                    report(f"{name} prefill[{lb}]", dec._prefill_fn(
                        lb).lower(mstate, caches, i32(lb), 7, 0))
    return 0


def _abstract_model(program, fam, cfg, dims, max_position, remat):
    """The model object with no weights behind it, and the shapes of
    its parameters on ``.shapes``."""
    import paddle_tpu.nn.layer as L

    from benchmark.harness import weights as W

    keep = L.Layer.set_parameters, W.make_all
    L.Layer.set_parameters = lambda self, flat: None
    W.make_all = lambda seed, fam, d, dtype: {}
    try:
        model = program.build_model(fam, cfg, dims, 0, cfg["dtype"],
                                    max_position, remat)
    finally:
        L.Layer.set_parameters, W.make_all = keep
    model.shapes = {k: jax.ShapeDtypeStruct(s, jnp.dtype(cfg["dtype"]))
                    for k, s in W.leaf_shapes(fam, dims).items()}
    return model


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
