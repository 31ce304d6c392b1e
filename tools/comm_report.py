"""Collective-traffic report for parallel configs — the scaling-book
"pick a mesh, annotate shardings, let XLA insert collectives, profile,
iterate" loop, runnable WITHOUT hardware: compile the hybrid BERT train
step on the virtual CPU mesh per config and tally every collective the
SPMD partitioner inserted (kind, count, bytes) next to the module's
compute FLOPs. The communication:compute ratio is the quantity mesh
layouts are chosen to minimize (SURVEY §5.8; reference analog: the
multi-device graph pass's inserted allreduce op-handles,
framework/details/all_reduce_op_handle.cc, which the reference could
only count by reading timeline traces).

    python tools/comm_report.py                       # the default sweep
    python tools/comm_report.py --config dp2tp2pp2    # one config

Prints one JSON line per config:
  {"config", "collectives": {kind: {"count", "mbytes"}}, "gflops",
   "comm_mbytes_total", "bytes_per_flop"}
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

# `%x = <result type> all-reduce(...` — the result type may be a TUPLE
# of shapes (grad-bucket all-reduces are). Async pairs are counted at
# the -done op, whose result IS the output payload; a -start's tuple
# also carries the operand alias + context scalars and would inflate
# the tally ~2x
_LINE_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_traffic(hlo_text: str):
    """Tally collectives in compiled HLO text: {kind: (count, bytes)}.
    Bytes are per-device result payload per execution of the op (tuple
    results sum their elements; fusion/while bodies count once —
    multiply by trip counts externally if needed)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        typ, kind, suffix = m.groups()
        if suffix == "-start":
            continue  # counted at the matching -done (see _LINE_RE note)
        b = sum(_bytes_of(dt, dims)
                for dt, dims in _SHAPE_RE.findall(typ))
        cnt, byt = out.get(kind, (0, 0))
        out[kind] = (cnt + 1, byt + b)
    return out


CONFIGS = {
    "dp8": dict(dp=8, tp=1, pp=1),
    "dp4tp2": dict(dp=4, tp=2, pp=1),
    "dp2tp4": dict(dp=2, tp=4, pp=1),
    "dp2tp2pp2": dict(dp=2, tp=2, pp=2),
    "dp2tp2pp2_interleaved": dict(dp=2, tp=2, pp=2,
                                  pipeline_schedule="interleaved",
                                  virtual_stages=2, layers=4),
    # r5 additions (VERDICT r4 #6): the non-BERT traffic profiles the
    # CI budget gate covers — pure-DP conv grads, EP embedding
    # dispatch, and the MoE dp x pp x ep composition
    "resnet20_dp8": dict(model="resnet_dp", dp=8),
    "deepfm_ep4": dict(model="deepfm_ep", dp=2, ep=4),
    "bert_moe_ep": dict(model="bert_moe", dp=2, tp=1, pp=2, ep=2),
    # the GPT 3D flagship (r5): same structural expectations as the
    # BERT hybrid (dp grad all-reduce, tp activation all-reduces, pp
    # neighbour permutes) over the decoder stack + tied vocab head
    "gpt_dp2tp2pp2": dict(model="gpt", dp=2, tp=2, pp=2),
}


def _compile_resnet_dp(mesh, batch):
    """resnet20-cifar momentum train step, batch P('dp'): the expected
    profile is grad all-reduce ONLY (reference analog: the dp graph
    pass's inserted allreduce handles)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.models import resnet

    pt.seed(0)
    model = resnet.resnet20_cifar(num_classes=10)
    params, buffers = model.named_parameters(), model.named_buffers()
    opt = optimizer.Momentum(0.05, 0.9)
    state = opt.init(params)
    rng = np.random.default_rng(0)
    dsh = NamedSharding(mesh, P("dp"))
    x = jax.device_put(
        jnp.asarray(rng.normal(size=(batch, 3, 16, 16)).astype("float32")),
        dsh)
    y = jax.device_put(jnp.asarray(rng.integers(0, 10, batch)), dsh)

    def step(params, buffers, state, x, y):
        def loss(p):
            logits, new_buf = model.functional_call(
                p, x, buffers=buffers, training=True)
            return resnet.loss_fn(logits, y), new_buf

        (l, new_buf), g = jax.value_and_grad(loss, has_aux=True)(params)
        params, state = opt.apply(params, g, state)
        return l, params, new_buf, state

    compiled = jax.jit(step).lower(params, buffers, state, x, y).compile()
    from paddle_tpu.utils.memory import bytes_of_tree

    return compiled, {"param_bytes": bytes_of_tree(params)}


def _compile_deepfm_ep(mesh, batch):
    """DeepFM grad step with ep-sharded embedding tables and dp-sharded
    ids: the PSLib sparse-dispatch profile (tokens cross between the dp
    and ep layouts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.models import deepfm as DF
    from paddle_tpu.parallel import embedding_ep_rules, shard_params

    pt.seed(0)
    with pt.core.mesh.mesh_scope(mesh):
        cfg = DF.DeepFMConfig(total_vocab=1024, num_fields=8, dense_dim=4,
                              embed_dim=16, mlp_dims=(32,))
        model = DF.DeepFM(cfg)
        params = shard_params(model.named_parameters(),
                              embedding_ep_rules(model), mesh=mesh)
        rng = np.random.default_rng(0)
        dsh = NamedSharding(mesh, P("dp"))
        ids = jax.device_put(jnp.asarray(
            rng.integers(0, cfg.total_vocab, size=(batch, 8))), dsh)
        dense = jax.device_put(jnp.asarray(
            rng.normal(size=(batch, 4)).astype("float32")), dsh)
        lbl = jax.device_put(jnp.asarray(
            rng.integers(0, 2, batch).astype("float32")), dsh)

        def loss(p, ids, dense, lbl):
            logits, _ = model.functional_call(p, ids, dense)
            return DF.loss_fn(logits, lbl)

        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            params, ids, dense, lbl).compile()
    return compiled, {}


def report(config_name: str, *, batch: int = 8, seq_len: int = 32,
           layers: int = 2):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.parallel.hybrid import build_bert_hybrid_step

    spec = dict(CONFIGS[config_name])
    model_kind = spec.pop("model", "bert")
    sched = spec.pop("pipeline_schedule", "gpipe")
    v = spec.pop("virtual_stages", 1)
    layers = spec.pop("layers", layers)
    mesh = pt.build_mesh(devices=jax.devices()[:8], **spec)
    extra = {}
    if model_kind == "resnet_dp":
        compiled, extra = _compile_resnet_dp(mesh, batch)
    elif model_kind == "deepfm_ep":
        compiled, extra = _compile_deepfm_ep(mesh, batch)
    else:
        # tiny stack: collective STRUCTURE (which kinds, how the bytes
        # scale with the axes) is what matters; absolute sizes scale with
        # the model and are reported per-config for ratio comparisons
        if model_kind == "gpt":
            from paddle_tpu.models.gpt import GPTConfig
            from paddle_tpu.parallel.hybrid import build_gpt_hybrid_step

            gcfg = GPTConfig(vocab_size=256, hidden_size=64,
                             num_layers=layers, num_heads=4,
                             num_kv_heads=2, intermediate_size=128,
                             max_position=64)
            step, _, params, feed = build_gpt_hybrid_step(
                mesh, cfg=gcfg, batch=batch, seq_len=seq_len,
                num_microbatches=2, pipeline_schedule=sched,
                virtual_stages=v)
        else:
            cfg = (BertConfig.moe_smoke(layers=4)
                   if model_kind == "bert_moe"
                   else BertConfig(vocab_size=256, hidden_size=64,
                                   num_layers=layers, num_heads=4,
                                   intermediate_size=128,
                                   max_position=64, dropout=0.0))
            seq_len = min(seq_len, cfg.max_position)
            step, _, params, feed = build_bert_hybrid_step(
                mesh, cfg=cfg, batch=batch, seq_len=seq_len,
                num_microbatches=2 if spec.get("pp", 1) > 1 else 1,
                pipeline_schedule=sched, virtual_stages=v)
        compiled = jax.jit(step).lower(params, *feed).compile()
    traffic = collective_traffic(compiled.as_text())
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    total = sum(b for _, b in traffic.values())
    out = {
        "config": config_name,
        "collectives": {k: {"count": c, "mbytes": round(b / 1e6, 3)}
                        for k, (c, b) in sorted(traffic.items())},
        "gflops": round(flops / 1e9, 3),
        "comm_mbytes_total": round(total / 1e6, 3),
        "bytes_per_flop": round(total / flops, 6) if flops else None,
    }
    out.update(extra)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(CONFIGS), default=None)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args(argv)
    names = [args.config] if args.config else list(CONFIGS)
    for name in names:
        print(json.dumps(report(name, batch=args.batch)), flush=True)
    return 0


if __name__ == "__main__":
    import jax

    # virtual-mesh analysis tool: never takes the chip
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < 8:
        print("comm_report needs 8 virtual devices: run with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8",
              file=sys.stderr)
        sys.exit(2)
    sys.exit(main())
