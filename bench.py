#!/usr/bin/env python
"""Benchmark harness — fluid_benchmark.py analog (reference:
benchmark/fluid/fluid_benchmark.py:296-300 examples/sec metric).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``vs_baseline`` compares against the last recorded value in BENCH_HISTORY.json
(the reference publishes no numbers, so the baseline is our own
trajectory; >1.0 means faster than the previous record).

Usage: python bench.py [--smoke] [--model mnist_mlp]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _ledger_flops(program, fn, *args, n_partitions=1, **kwargs):
    """FLOPs of one dispatch of ``fn(*args)`` — the same XLA cost-model
    number ``utils.flops.lowered_flops`` reads, but REGISTERED in the
    telemetry cost ledger under ``program`` so report_line can audit
    the emitted mfu against the registry record (ride the name along as
    ``extras["ledger_program"]`` plus ``ledger_dispatches`` /
    ``ledger_window_s``). None when the backend won't cost the module
    (the provenance-only record still registers)."""
    from paddle_tpu.telemetry import costs as _tcosts

    try:
        return _tcosts.analyze_callable(
            program, fn, *args, n_partitions=n_partitions,
            **kwargs).get("flops")
    except Exception:
        return None


def bench_mnist_mlp(steps: int, batch_size: int, warmup: int = 5,
                    steps_per_call: int = 8, dp: int = 1, amp=None):
    """BASELINE config 1. ``steps_per_call`` fuses K optimizer steps into
    one dispatch (Trainer.train_steps lax.scan) — the per-dispatch
    overhead dominates a step this small.
    ``dp``: data-parallel device count (fluid_benchmark's --gpus analog);
    the batch shards over the dp mesh axis and XLA inserts the gradient
    all-reduce."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models import mnist as M

    pt.seed(0)
    assert batch_size >= dp > 0, f"batch {batch_size} must be >= dp {dp}"
    mesh = pt.build_mesh(dp=dp, devices=jax.devices()[:dp])
    model = M.MnistMLP(hidden1=512, hidden2=256)
    if _MODE == "infer":
        _rng = np.random.default_rng(0)
        return _infer_bench(
            model, lambda bs: (jnp.asarray(
                _rng.normal(size=(bs, 784)).astype(np.float32)),),
            steps, batch_size, amp=amp)
    trainer = parallel.Trainer.supervised(
        model, optimizer.Adam(1e-3), M.loss_fn, mesh=mesh, amp=amp)
    rng = np.random.default_rng(0)
    batch_size -= batch_size % max(dp, 1)
    x = jnp.asarray(rng.normal(size=(batch_size, 784)).astype(np.float32))
    label = jnp.asarray(rng.integers(0, 10, batch_size))
    batch = {"x": x, "label": label}
    if dp > 1:
        sh = trainer.data_sharding()
        batch = {k: jax.device_put(v, sh) for k, v in batch.items()}
    k = max(1, steps_per_call)
    outer = max(1, steps // k)
    # FLOPs of the module that is ACTUALLY dispatched (the k-step scan
    # when k>1) — lowered before any call donates buffers, and the AOT
    # compile inside the fallback is the same executable the timed loop
    # reuses via the persistent cache. Registered in the telemetry cost
    # ledger so the emitted mfu is auditable against the registry.
    ledger_program = "bench.mnist_mlp.step"
    step_flops = _ledger_flops(
        ledger_program, trainer.steps_jit(k) if k > 1 else
        trainer._jit_step, trainer.params, trainer.buffers,
        trainer.opt_state, trainer._rng, batch, n_partitions=dp)
    if step_flops and k > 1:
        step_flops /= k
    for _ in range(warmup):
        loss, _ = (trainer.train_steps(batch, k) if k > 1
                   else trainer.train_step(batch))
    float(loss)  # host fetch = the only reliable fence (see _train_bench)
    t0 = time.perf_counter()
    for i in range(outer):
        loss, _ = (trainer.train_steps(batch, k) if k > 1
                   else trainer.train_step(batch))
        if i % 4 == 3:
            float(loss)
    float(loss)
    dt = time.perf_counter() - t0
    extras = {"step_time_ms": round(dt / (outer * k) * 1e3, 3)}
    if step_flops:
        extras["flops_per_sec"] = step_flops * outer * k / dt
        extras.update(ledger_program=ledger_program,
                      ledger_dispatches=outer, ledger_window_s=dt)
    return outer * k * batch_size / dt, "examples/sec", extras


HEADLINE_STEPS = 100  # the full-length measurement; shorter runs (fast
# sweep) fork the workload fingerprint and never claim headline records

_STEPS_PER_CALL = None  # CLI override consumed by _train_bench
_EXPLICIT_BATCH = False  # set by main() when --batch-size is given
_MODE = "train"  # "train" | "infer" (--infer): per-model bench fns keep
# their model/batch construction; _train_bench routes to _infer_bench


def _cap(batch_size: int, cap: int) -> int:
    """Clamp the harness-wide default batch (8192) to the model's
    headline config; an EXPLICIT --batch-size is honored as given so
    knob sweeps (e.g. bert_base --batch-size 64) actually run what the
    label says."""
    return batch_size if _EXPLICIT_BATCH else min(batch_size, cap)


def _train_bench(model, loss_fn, make_batch, steps, batch_size, warmup=3,
                 lr=1e-3, amp=None, method="forward", steps_per_call=None,
                 infer_batch=None, aux_loss_fn=None,
                 flops_scale: float = 1.0):
    """Shared harness: jitted value_and_grad+Adam step, timed post-warmup.

    Timing blocks on the FULL output state, not just the loss scalar — the
    device queue can resolve a scalar d2h long before the update chain
    drains, which inflates throughput ~30x.

    ``amp``: dtype policy name (e.g. "mixed_bf16") applied at trace time;
    params/opt state stay fp32 masters. Buffers donate so param/opt updates
    are in-place in HBM. ``steps_per_call`` fuses K update steps into one
    dispatch via lax.scan (identical math — the Trainer.train_steps
    pattern), amortizing the per-dispatch overhead.
    ``aux_loss_fn(new_buffers) -> scalar`` adds buffer-carried auxiliary
    objectives (the MoE load-balance loss) to the optimized loss.
    """
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax import lax
    import paddle_tpu as pt
    from paddle_tpu.core.dtypes import policy_scope

    from paddle_tpu import optimizer

    if _MODE == "infer":
        # the fused-loss training method needs labels; inference runs the
        # plain forward (real serving materializes the logits). The train
        # batch tuple may carry trailing label args the forward doesn't
        # take — truncate to the forward's positional arity. A model
        # whose label args would ALIAS optional forward params (BERT:
        # nsp_label landing in attention_mask) must pass ``infer_batch``
        # explicitly instead.
        import inspect as _inspect

        infer_method = ("forward" if method.endswith("_loss") else method)
        if infer_batch is None:
            fwd_params = list(_inspect.signature(
                getattr(type(model), infer_method)).parameters.values())[1:]
            n_pos = sum(1 for p in fwd_params
                        if p.kind in (p.POSITIONAL_ONLY,
                                      p.POSITIONAL_OR_KEYWORD))
            infer_batch = lambda bs: make_batch(bs)[:n_pos]
        return _infer_bench(model, infer_batch, steps, batch_size,
                            amp=amp, method=infer_method)

    params = model.named_parameters()
    buffers = model.named_buffers()
    opt = optimizer.Adam(lr)
    state = opt.init(params)
    batch = make_batch(batch_size)
    k = max(1, steps_per_call or _STEPS_PER_CALL or 1)

    def one_step(params, buffers, state, batch):
        scope = policy_scope(amp) if amp else contextlib.nullcontext()

        def loss(p):
            with scope:
                out, new_buf = model.functional_call(
                    p, *batch, buffers=buffers, training=True,
                    method=method)
                l = loss_fn(out, batch)
                if aux_loss_fn is not None:
                    l = l + aux_loss_fn(new_buf)
                return l, new_buf

        (l, new_buf), g = jax.value_and_grad(loss, has_aux=True)(params)
        params, state = opt.apply(params, g, state)
        return params, new_buf, state, l

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, buffers, state, batch):
        if k == 1:
            return one_step(params, buffers, state, batch)

        def body(carry, _):
            p, b, st = carry
            p, b, st, l = one_step(p, b, st, batch)
            return (p, b, st), l

        (params, buffers, state), ls = lax.scan(
            body, (params, buffers, state), None, length=k)
        return params, buffers, state, ls[-1]

    from paddle_tpu.core.profiler import RecordEvent

    # model FLOPs per STEP from XLA's cost model, measured on a k=1
    # probe (lower-only, never executed) and scaled by k explicitly:
    # the cost analysis counts a lax.scan/while BODY ONCE regardless of
    # trip count, so analyzing the fused k-step dispatch under-reports
    # by k (observed on-chip: rn50 spc8 printed 2.8% MFU at a true
    # ~22.7%). ``flops_scale`` is the same correction for bodies the
    # MODEL scans internally (scan_layers -> num_layers). Must happen
    # BEFORE the first call donates these buffers.
    # k == 1: analyze ``step`` itself — its AOT fallback compile is the
    # same program the first dispatch reuses from the cache; a separate
    # donation-free probe jit would pay a second full (remote) compile
    ledger_program = f"bench.{type(model).__name__}.step"
    dispatch_flops = _ledger_flops(
        ledger_program, step if k == 1 else jax.jit(one_step), params,
        buffers, state, batch)
    if dispatch_flops:
        dispatch_flops *= k * flops_scale

    outer = max(1, steps // k)
    for _ in range(warmup):
        params, buffers, state, l = step(params, buffers, state, batch)
    float(l)  # host fetch = the only reliable fence on this backend
    t0 = time.perf_counter()
    for i in range(outer):
        with RecordEvent(f"train_step[{k}]"):  # --profile span per dispatch
            params, buffers, state, l = step(params, buffers, state, batch)
        # fence every few steps: a loss fetch serializes the whole update
        # chain (honest timing) while keeping the dispatch queue shallow
        if i % 4 == 3:
            float(l)
    float(l)
    dt = time.perf_counter() - t0
    extras = {"step_time_ms": round(dt / (outer * k) * 1e3, 3)}
    if dispatch_flops:
        extras["flops_per_sec"] = dispatch_flops * outer / dt
        extras.update(ledger_program=ledger_program,
                      ledger_scale=k * flops_scale,
                      ledger_dispatches=outer, ledger_window_s=dt)
    return outer * k * batch_size / dt, "examples/sec", extras


def _infer_bench(model, make_batch, steps, batch_size, warmup=5, amp=None,
                 method="forward"):
    """Inference harness (reference: the per-model inference latency
    analyzer tests, inference/tests/api/): jitted forward only, no
    grads/optimizer.

    Two numbers, two disciplines:
    - latency_ms_p50/p99: one dispatch at a time, host-fenced per call —
      end-to-end serving latency including the device round trip;
    - value (examples/sec): pipelined dispatches fenced every few calls —
      saturated-server throughput.
    """
    import contextlib

    import jax
    from paddle_tpu.core.dtypes import policy_scope

    params = model.named_parameters()
    buffers = model.named_buffers()
    batch = make_batch(batch_size)

    @jax.jit
    def fwd(params, buffers, batch):
        scope = policy_scope(amp) if amp else contextlib.nullcontext()
        with scope:
            out, _ = model.functional_call(
                params, *batch, buffers=buffers, training=False,
                method=method)
        return out

    def _fence(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        idx = (0,) * getattr(leaf, "ndim", 0)
        float(jax.device_get(leaf[idx] if idx else leaf).real
              if hasattr(leaf, "real") else leaf)

    for _ in range(warmup):
        out = fwd(params, buffers, batch)
    _fence(out)

    # latency: serialize every dispatch
    lats = []
    for _ in range(min(steps, 50)):
        t0 = time.perf_counter()
        out = fwd(params, buffers, batch)
        _fence(out)
        lats.append(time.perf_counter() - t0)
    lats.sort()
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]

    # throughput: keep the queue full, fence periodically
    t0 = time.perf_counter()
    for i in range(steps):
        out = fwd(params, buffers, batch)
        if i % 8 == 7:
            _fence(out)
    _fence(out)
    dt = time.perf_counter() - t0
    extras = {"latency_ms_p50": round(p50 * 1e3, 3),
              "latency_ms_p99": round(p99 * 1e3, 3),
              "step_time_ms": round(dt / steps * 1e3, 3)}
    return steps * batch_size / dt, "examples/sec", extras


def bench_resnet50(steps: int, batch_size: int, smoke: bool = False,
                   amp=None, layout: str = "NHWC"):
    """BASELINE config 2 (image 224 is the headline; smoke uses 64).
    NHWC is the TPU-native layout default; pass layout=NCHW to compare."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import resnet

    pt.seed(0)
    size = 64 if smoke else 224
    batch_size = _cap(batch_size, 8 if smoke else 128)
    model = resnet.resnet50(num_classes=1000, data_format=layout)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        return (jnp.asarray(rng.normal(size=(bs, 3, size, size))
                            .astype(np.float32)),)

    def loss_fn(logits, batch):
        labels = jnp.zeros((logits.shape[0],), jnp.int32)
        return resnet.loss_fn(logits, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_bert_base(steps: int, batch_size: int, amp=None,
                    fused_ce: bool = True, remat=False,
                    scan_layers: bool = False):
    """BASELINE config 3: BERT-base MLM pretrain step, seq 128.

    ``fused_ce`` routes the MLM head through the chunked
    linear-cross-entropy (ops/fused_loss.py) so the (B, T, 30k) logits
    tensor never materializes — the HBM-bound hot spot of this config.
    ``remat`` checkpoints each block (False | "full" | "dots" — "dots"
    saves matmul outputs, recomputing only the elementwise tail);
    ``scan_layers`` folds the stack
    into one lax.scan body (forces dropout 0 — noted so numbers stay
    comparable)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import bert as B

    pt.seed(0)
    batch_size = _cap(batch_size, 32)
    cfg = B.BertConfig.base()
    cfg.remat, cfg.scan_layers = bool(remat), scan_layers
    cfg.remat_policy = "dots" if remat == "dots" else None
    if scan_layers:
        cfg.dropout = 0.0  # scan body shares one RNG stream
    model = B.BertForPretraining(cfg)
    rng = np.random.default_rng(0)
    T = 128

    if fused_ce:
        def make_batch(bs):
            ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, T)))
            nsp = jnp.asarray(rng.integers(0, 2, (bs,)))
            return (ids, ids, nsp)  # MLM over every position: predict ids

        def loss_fn(out, batch):
            return out  # forward_fused_loss returns the scalar loss

        return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                            amp=amp, method="forward_fused_loss",
                            infer_batch=lambda bs: make_batch(bs)[:1],
                            flops_scale=(cfg.num_layers
                                         if scan_layers else 1))

    def make_batch(bs):
        return (jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, T))),)

    def loss_fn(out, batch):
        from paddle_tpu.ops import loss as L

        mlm_logits, _ = out  # MLM over every position: predict input ids
        return jnp.mean(L.softmax_with_cross_entropy(mlm_logits, batch[0]))

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_gpt(steps: int, batch_size: int, smoke: bool = False,
              amp=None, seq_len: int = 1024):
    """Decoder-only causal LM (models/gpt.py — RoPE + GQA 12q/4kv +
    SwiGLU, head_dim 64 so the causal flash kernel engages, fused
    linear-CE head): the modern long-context training workload the
    reference era lacks. Next-token loss over random ids; remat per
    block keeps seq 1024 activations in HBM."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G

    pt.seed(0)
    batch_size = _cap(batch_size, 2 if smoke else 8)
    cfg = G.GPTConfig.small()
    if smoke:
        cfg.vocab_size, cfg.num_layers = 1024, 2
        seq_len = min(seq_len, 128)
    cfg.max_position = seq_len
    cfg.remat = True
    model = G.GPTForCausalLM(cfg)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        ids = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (bs, seq_len)))
        return (ids,)

    return _train_bench(model, lambda out, batch: out, make_batch,
                        steps, batch_size, amp=amp,
                        method="forward_loss", infer_batch=make_batch)


def bench_bert_moe(steps: int, batch_size: int, amp=None,
                   experts: int = 8):
    """Switch-MoE BERT (green-field config — the reference has no MoE):
    bert_base geometry with each block's FFN replaced by an
    ``experts``-way Switch FFN (top-1, cf 1.25); the optimized loss adds
    0.01 x the per-layer load-balance aux. Single-chip this measures the
    dense dispatch/combine einsum cost; on a mesh the experts shard over
    'ep' (tests/test_moe.py golden HLO)."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import bert as B

    pt.seed(0)
    batch_size = _cap(batch_size, 16)
    cfg = B.BertConfig.base()
    cfg.dropout = 0.0
    cfg.moe_experts = experts
    model = B.BertForPretraining(cfg)
    rng = np.random.default_rng(0)
    T = 128

    def make_batch(bs):
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, T)))
        mlm = jnp.asarray(np.where(
            rng.random((bs, T)) < 0.15,
            rng.integers(0, cfg.vocab_size, (bs, T)), -100))
        nsp = jnp.asarray(rng.integers(0, 2, (bs,)))
        return (ids, mlm, nsp)

    def aux(new_buf):
        return 0.01 * sum(v for k, v in new_buf.items()
                          if k.endswith("ffn.aux_loss"))

    # --infer: only input_ids reaches the forward (mlm/nsp labels would
    # alias token_type_ids/attention_mask — the _train_bench docstring
    # hazard bench_bert_base guards the same way)
    return _train_bench(model, lambda out, batch: out, make_batch, steps,
                        batch_size, amp=amp, method="forward_fused_loss",
                        aux_loss_fn=aux,
                        infer_batch=lambda bs: make_batch(bs)[:1])


def bench_transformer_nmt(steps: int, batch_size: int, amp=None,
                          fused_ce: bool = True):
    """BASELINE config 4: Transformer NMT train step, seq 64. ``fused_ce``
    routes the generator head through the chunked linear-cross-entropy."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as TR

    pt.seed(0)
    batch_size = _cap(batch_size, 64)
    cfg = TR.NMTConfig.base()
    model = TR.TransformerNMT(cfg)
    rng = np.random.default_rng(0)
    T = 64

    if fused_ce:
        def make_batch(bs):
            src = jnp.asarray(rng.integers(3, cfg.src_vocab, (bs, T)))
            tgt = jnp.asarray(rng.integers(3, cfg.tgt_vocab, (bs, T)))
            return (src, tgt, tgt)

        return _train_bench(model, lambda out, batch: out, make_batch,
                            steps, batch_size, amp=amp,
                            method="forward_fused_loss")

    def make_batch(bs):
        src = jnp.asarray(rng.integers(3, cfg.src_vocab, (bs, T)))
        tgt = jnp.asarray(rng.integers(3, cfg.tgt_vocab, (bs, T)))
        return (src, tgt)

    def loss_fn(out, batch):
        logits = out[0] if isinstance(out, tuple) else out
        from paddle_tpu.ops import loss as L

        return jnp.mean(L.softmax_with_cross_entropy(logits, batch[1]))

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_bert_long(steps: int, batch_size: int, amp=None,
                    seq_len: int = 2048, window: int = None):
    """Long-context BERT MLM step at seq 2048 — the SURVEY §5.7
    long-sequence showcase: attention cost is O(T^2), so this is where
    the flash-attention kernel path engages on TPU (T % 128 == 0, head
    dim 64) and remat at block boundaries keeps activations inside HBM.
    Compare against --model bert_base (seq 128) for the scaling story."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import bert as B

    pt.seed(0)
    batch_size = _cap(batch_size, 4)
    cfg = B.BertConfig.base()
    cfg.max_position = seq_len
    cfg.remat = True
    cfg.attn_window = window  # --window: O(T*W) local attention
    model = B.BertForPretraining(cfg)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (bs, seq_len)))
        nsp = jnp.asarray(rng.integers(0, 2, (bs,)))
        return (ids, ids, nsp)

    def loss_fn(out, batch):
        return out  # forward_fused_loss returns the scalar loss

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp, method="forward_fused_loss",
                        infer_batch=lambda bs: make_batch(bs)[:1])


def bench_bert_packed(steps: int, batch_size: int, amp=None,
                      seq_len: int = 128):
    """BERT MLM over PACKED batches (data.bucketing.pack_sequences):
    variable-length documents share fixed (B, T) rows with segment-ids
    attention (the Pallas packed-batch kernel path) and per-segment
    positions — zero padding waste vs the padded bert_base config. Same
    row shape as bert_base, so examples/sec is directly comparable; at
    this config's doc-length distribution (uniform 16..128) packed rows
    carry ~1.6-1.8x the real tokens a padded ragged batch of the same
    documents would."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.data.bucketing import pack_sequences
    from paddle_tpu.models import bert as B

    pt.seed(0)
    batch_size = _cap(batch_size, 32)
    cfg = B.BertConfig.base()
    model = B.BertForPretraining(cfg)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        # documents: lengths 16..seq_len, enough to fill bs rows
        def docs():
            while True:
                n = int(rng.integers(16, seq_len + 1))
                yield rng.integers(3, cfg.vocab_size, n)

        gen = pack_sequences(docs, capacity=seq_len, batch_size=bs)
        batch = next(iter(gen()))
        tokens = jnp.asarray(batch["tokens"])
        return (tokens, jnp.asarray(batch["positions"]),
                jnp.asarray(batch["segment_ids"]), tokens)

    return _train_bench(model, lambda out, batch: out, make_batch, steps,
                        batch_size, amp=amp, method="forward_packed_loss")


def bench_nmt_decode(steps: int, batch_size: int, amp=None,
                     cached: bool = True, max_len: int = 64):
    """Autoregressive decode throughput (tokens/sec) for the NMT
    transformer — the serving-side counterpart of --infer. ``cached``
    uses the per-layer K/V caches (O(T) per step); --no-kv-cache runs
    the full-prefix re-run greedy_decode for the honest comparison
    (identical tokens, pinned by tests)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as TR

    import contextlib

    from paddle_tpu.core.dtypes import policy_scope

    pt.seed(0)
    batch_size = _cap(batch_size, 32)
    cfg = TR.NMTConfig.base()
    model = TR.TransformerNMT(cfg).eval()
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(3, cfg.src_vocab, (batch_size, 64)))

    from paddle_tpu.nn.layer import inject_state

    decode = (model.greedy_decode_cached if cached
              else model.greedy_decode)
    # params ride as jit ARGUMENTS (inject_state): a closure over the
    # model would bake every weight into the program as constants
    params = dict(model.named_parameters())

    def _decode(p, s):
        scope = policy_scope(amp) if amp else contextlib.nullcontext()
        with scope, inject_state((model, p)):
            return decode(s, max_len=max_len)

    fn = jax.jit(_decode)

    def _fence(out):
        float(jax.device_get(out[0, 0]))

    for _ in range(2):
        out = fn(params, src)
    _fence(out)
    outer = max(1, steps // 4)
    t0 = time.perf_counter()
    for i in range(outer):
        out = fn(params, src)
        _fence(out)
    dt = time.perf_counter() - t0
    return (outer * batch_size * max_len / dt, "tokens/sec",
            {"step_time_ms": round(dt / outer * 1e3, 3)})


def bench_vit(steps: int, batch_size: int, smoke: bool = False,
              amp=None, layout: str = "NHWC"):
    """ViT-B/16 @224 (models/vit.py — green-field next to the conv zoo;
    ~17.6 GFLOP fwd/img lands almost entirely on the MXU as big
    matmuls): supervised CE over random images. remat per block keeps
    b128 activations in HBM."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import vit as V

    pt.seed(0)
    batch_size = _cap(batch_size, 8 if smoke else 128)
    cfg = V.ViTConfig.tiny() if smoke else V.ViTConfig.base()
    cfg.layout = layout
    cfg.remat = not smoke
    model = V.ViT(cfg)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        if layout == "NHWC":
            shape = (bs, cfg.image_size, cfg.image_size,
                     cfg.num_channels)
        else:
            shape = (bs, cfg.num_channels, cfg.image_size,
                     cfg.image_size)
        return (jnp.asarray(rng.normal(size=shape).astype(np.float32)),)

    def loss_fn(logits, batch):
        labels = jnp.asarray(
            np.arange(logits.shape[0]) % cfg.num_classes)
        return V.loss_fn(logits, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_gpt_decode(steps: int, batch_size: int, amp=None,
                     max_len: int = 128, gamma: int = 0,
                     weight_only: bool = False, smoke: bool = False):
    """GPT KV-cached decode throughput (tokens/sec, generated positions
    only). Default is greedy decode on the 12-layer small config.
    ``--gamma g`` > 0 switches to speculative decoding against a
    2-layer draft sharing the target's geometry (fresh init): the
    output distribution is the target's regardless of the draft, so
    this measures the MACHINERY cost honestly — the emitted
    accept-per-round extra turns the number into the real speedup
    formula (tokens per target pass = 1 + accepted/round) for any
    better-trained draft pair."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.dtypes import policy_scope
    from paddle_tpu.models import gpt as G
    from paddle_tpu.models.speculative import speculative_generate

    pt.seed(0)
    batch_size = _cap(batch_size, 2 if smoke else 16)
    cfg = G.GPTConfig.small()
    if smoke:
        cfg.vocab_size, cfg.num_layers = 1024, 2
        max_len = min(max_len, 32)
    cfg.max_position = max_len + max(gamma, 0)
    model = G.GPTForCausalLM(cfg).eval()
    if weight_only:
        # W8A16: halve the weight HBM stream of the bandwidth-bound
        # decode loop (logit accuracy pinned in tests/test_weight_only)
        from paddle_tpu.quant import apply_weight_only_int8

        apply_weight_only_int8(model)
    rng = np.random.default_rng(0)
    prompt_len = min(16, max_len // 2)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch_size, prompt_len)))

    from paddle_tpu.nn.layer import inject_state

    # params/buffers ride as jit ARGUMENTS (inject_state): closures
    # would bake the weights into the program as constants. Buffers
    # matter too: --weight-only stores the int8 weights AS buffers.
    tstate = (dict(model.named_parameters()),
              dict(model.named_buffers()))
    if gamma > 0:
        dcfg = dataclasses.replace(cfg, num_layers=2)
        pt.seed(1)
        draft = G.GPTForCausalLM(dcfg).eval()
        dstate = (dict(draft.named_parameters()),
                  dict(draft.named_buffers()))

        def _decode(tp, tb, dp, db, p):
            scope = policy_scope(amp) if amp else contextlib.nullcontext()
            with scope, inject_state((model, tp, tb), (draft, dp, db)):
                return speculative_generate(
                    model, draft, p, max_len, gamma=gamma,
                    temperature=0.0, return_stats=True)

        fn = jax.jit(_decode)
        args = (*tstate, *dstate, prompt)
    else:
        def _decode(tp, tb, p):
            scope = policy_scope(amp) if amp else contextlib.nullcontext()
            with scope, inject_state((model, tp, tb)):
                return model.greedy_decode(p, max_len), None

        fn = jax.jit(_decode)
        args = (*tstate, prompt)

    def _fence(out):
        float(jax.device_get(out[0][0, 0]))

    for _ in range(2):
        out = fn(*args)
    _fence(out)
    outer = max(1, steps // 4)
    t0 = time.perf_counter()
    for i in range(outer):
        out = fn(*args)
        _fence(out)
    dt = time.perf_counter() - t0
    extras = {"step_time_ms": round(dt / outer * 1e3, 3)}
    if gamma > 0:
        stats = jax.device_get(out[1])
        rounds = float(np.mean(stats["rounds"]))
        extras = {"accept_per_round":
                  round(float(np.mean(stats["accepted_drafts"])) /
                        max(rounds, 1.0), 3),
                  "rounds": round(rounds, 1)}
    gen = max_len - prompt_len
    return outer * batch_size * gen / dt, "tokens/sec", extras


def bench_gpt_serve(steps: int, batch_size: int, amp=None,
                    max_new: int = 64, smoke: bool = False,
                    weight_only: bool = False, paged: bool = False,
                    gamma: int = 0, prefill_chunk=None,
                    decode_steps: int = 1, kv_dtype=None):
    """Continuous-batching serving throughput (serving.BatchedDecoder):
    2x``batch_size`` requests with MIXED prompt lengths over a
    ``batch_size``-slot arena — generated tokens/sec across the whole
    workload, admission/refill included (the slot machinery's win over
    pad-to-slowest static batching). --weight-only composes W8A16;
    --gamma g serves SPECULATIVELY (per-row drafts + one per-row verify
    chunk per round, 2-layer draft — accept_per_round extra gives the
    real-pair speedup formula); --prefill-chunk C smooths admission by
    prefilling C tokens per serving tick instead of a whole prompt;
    --kv-dtype int8 serves over the QUANTIZED page pool (implies
    --paged) and additionally measures the serving-DENSITY A/B: max
    concurrent sessions before admission backpressure at ONE page-pool
    HBM budget, fp32 KV vs int8 KV, plus the greedy-decode parity
    agreement (the density acceptance gate's evidence)."""
    import contextlib

    import paddle_tpu as pt
    from paddle_tpu.core.dtypes import policy_scope
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder

    if kv_dtype is not None:
        paged = True  # quantized KV lives in the page pool
    pt.seed(0)
    slots = _cap(batch_size, 2 if smoke else 8)
    cfg = G.GPTConfig.small()
    if smoke:
        cfg.vocab_size, cfg.num_layers = 1024, 2
        max_new = min(max_new, 8)
    cap = 256 if not smoke else 64
    cfg.max_position = cap
    model = G.GPTForCausalLM(cfg).eval()
    if weight_only:
        from paddle_tpu.quant import apply_weight_only_int8

        apply_weight_only_int8(model)
    rng = np.random.default_rng(0)
    n_req = 2 * slots
    lens = [int(8 + (i * 7) % 24) for i in range(n_req)]  # mixed
    # ONE decoder across warmup + timed runs: its jitted step and
    # prefill-bucket functions cache per-instance, so a fresh decoder
    # per run would re-trace inside the timed loop. --paged serves over
    # the shared page pool (memory ~ live tokens) instead of the
    # slots x capacity arena.
    kw = {}
    if paged:
        kw = dict(pages=max(slots * (cap // 64) // 2, slots),
                  page_size=64)
        if kv_dtype is not None:
            kw["kv_dtype"] = kv_dtype
    if gamma > 0:
        dcfg = dataclasses.replace(cfg, num_layers=2)
        pt.seed(1)
        kw["draft"] = G.GPTForCausalLM(dcfg).eval()
        kw["gamma"] = gamma
    if prefill_chunk:
        kw["prefill_chunk"] = prefill_chunk
    if decode_steps > 1:
        kw["decode_steps"] = decode_steps
    dec = BatchedDecoder(model, slots=slots, capacity=cap, **kw)

    def run_all():
        scope = policy_scope(amp) if amp else contextlib.nullcontext()
        with scope:  # trace-time policy, same contract as gpt_decode
            for n in lens:
                dec.submit(rng.integers(1, cfg.vocab_size, (n,))
                           .astype(np.int32), max_new)
            return dec.run()

    # warmup compiles the step + prefill buckets — with telemetry on
    # for just this run so the serving dispatch sites register their
    # programs in the cost ledger (the serve row's mfu/roofline source)
    from paddle_tpu.telemetry import costs as _tcosts
    from paddle_tpu.telemetry import metrics as _tmetrics

    telem_was_on = _tmetrics.enabled()
    _tmetrics.enable()
    try:
        run_all()
    finally:
        if not telem_was_on:
            _tmetrics.disable()
    step_rec = next((r for name, r in sorted(_tcosts.ledger().items())
                     if name.startswith("serving.step[")), None)
    ticks0, tok0, cap0 = dec.tick_count, dec.tick_tokens, \
        dec.tick_capacity
    outer = max(1, steps // 50)
    t0 = time.perf_counter()
    total = 0
    for _ in range(outer):
        outs = run_all()
        total += sum(len(v) for v in outs.values())
    dt = time.perf_counter() - t0
    extras = {"requests": n_req, "slots": slots,
              "step_time_ms": round(dt / outer * 1e3, 3)}
    # goodput: tokens emitted / slot-token capacity over the timed
    # ticks, from the decoder's unconditional tick counters
    cap_delta = dec.tick_capacity - cap0
    if cap_delta > 0:
        extras["goodput_ratio"] = round(
            (dec.tick_tokens - tok0) / cap_delta, 4)
    if step_rec is not None and step_rec.get("flops"):
        # decode-dispatch FLOPs only (prefill excluded): a lower bound,
        # audited in report_line against the same ledger record
        n_ticks = dec.tick_count - ticks0
        if n_ticks > 0:
            extras["flops_per_sec"] = \
                step_rec["flops"] * n_ticks / dt
            extras.update(ledger_program=step_rec["program"],
                          ledger_dispatches=n_ticks,
                          ledger_window_s=dt)
    if gamma > 0:
        extras["accept_per_round"] = round(
            dec.spec_accepted / max(1, dec.spec_row_rounds), 3)
    if kv_dtype is not None:
        extras["kv_dtype"] = kv_dtype
        extras.update(_kv_serve_density(model, cap, smoke))
        extras.update(_kv_decode_step_time(model, cap, smoke))
    return total / dt, "tokens/sec", extras


def _router_replica_spec(smoke=False, kv_dtype=None, slots=4,
                         seed=0, prefill_chunk=None):
    """Replica model contract for the router bench + worker processes
    (``python -m paddle_tpu.serving_router --worker --spec
    bench:_router_replica_spec``): every replica builds the SAME
    weights (fixed seed), so placement is invisible in the output."""
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder

    pt.seed(seed)
    cfg = G.GPTConfig.small()
    cap = 256
    if smoke:
        # 3 layers (not the usual smoke 2): the router A/B's signal is
        # the absolute ms a monolithic long-prompt prefill steals from
        # decode — one extra layer grows that effect past CI timing
        # noise at still-smoke cost
        cfg.vocab_size, cfg.num_layers = 1024, 3
        cap, slots = 128, max(2, slots // 2)
    cfg.max_position = cap
    model = G.GPTForCausalLM(cfg).eval()
    kw = {}
    if prefill_chunk:
        kw["prefill_chunk"] = prefill_chunk
    return BatchedDecoder(
        model, slots=slots, capacity=cap,
        pages=slots * (cap // 64) + 8, page_size=64,
        kv_dtype=kv_dtype, **kw)


def _router_aot_ttfr_ab(spec_kw):
    """TTFR (time-to-first-ready) A/B for the aot compiled-program
    plane: boot the SAME replica twice — once through the ordinary
    trace path (construct model, trace, compile, warm) and once
    trace-free from the serialized artifact the first boot exported —
    and gate ``ttfr_aot_ms < ttfr_traced_ms`` (the artifact exists to
    delete trace+compile from elastic scale-up; if it doesn't, the
    plane is a regression and the bench must say so). The AOT replica
    then serves a real request end-to-end, so the number is a SERVING
    boot, not a load microbenchmark. Artifact export/load failures
    raise :class:`_SkipBench` (skipped row, cause
    ``artifact_load_failed``) — never a fake 0.0 TTFR."""
    import shutil
    import tempfile

    from paddle_tpu import aot
    from paddle_tpu.core.enforce import enforce
    from paddle_tpu.serving_router import LocalReplica

    def boot(mk):
        t0 = time.perf_counter()
        rep = LocalReplica(mk(), name="ttfr").start()
        rep.warmup()
        return rep, (time.perf_counter() - t0) * 1e3

    rep, ttfr_traced = boot(lambda: _router_replica_spec(**spec_kw))
    tmp = tempfile.mkdtemp(prefix="pt-aot-bench-")
    art = os.path.join(tmp, "artifact")
    try:
        try:
            aot.export_decoder(rep.decoder, art)
        except aot.AotError as e:
            raise _SkipBench(f"aot artifact export failed: {e}",
                             cause="artifact_load_failed")
        finally:
            rep.close()

        def load():
            try:
                return aot.load_decoder(art)
            except aot.AotError as e:
                raise _SkipBench(f"aot artifact load failed: {e}",
                                 cause="artifact_load_failed")

        rep2, ttfr_aot = boot(load)
        try:
            # end-to-end through the trace-free replica: the stub
            # booby-traps every trace entry point, so tokens coming
            # back prove the serialized programs served the request
            rid = rep2.submit(np.asarray([1, 2], np.int32), 4)
            deadline = time.time() + 300.0
            done = {}
            while rid not in done and time.time() < deadline:
                done.update(rep2.drain_results())
                time.sleep(0.01)
            enforce(rid in done and len(done[rid]["tokens"]) > 0,
                    "aot-booted replica served no tokens")
            info = getattr(rep2.decoder, "aot_info", {})
        finally:
            rep2.close()
        enforce(ttfr_aot < ttfr_traced,
                "aot cold start (%.0f ms) must beat the traced boot "
                "(%.0f ms) — the artifact plane exists to delete "
                "trace+compile from scale-up", ttfr_aot, ttfr_traced)
        return {"ttfr_traced_ms": round(ttfr_traced, 1),
                "ttfr_aot_ms": round(ttfr_aot, 1),
                "aot_artifact_id": info.get("artifact_id")}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _open_loop(router, prompts, max_new: int, rate_rps: float,
               rng, timeout_s: float = 900.0, stream: bool = False):
    """Seeded Poisson OPEN-loop load: arrivals are exponential gaps at
    ``rate_rps`` independent of completions (the closed-loop bench
    hides queueing collapse; open-loop is how serving studies measure
    TTFT under load). Returns (tickets, wall_s) with wall measured
    submit-of-first to completion-of-last non-shed request.
    ``stream=True`` submits streaming tickets — TTFT is then the
    router-side FIRST-TOKEN stamp, and the client-side inter-token
    gaps land on the tickets via :func:`_drain_streams`."""
    gaps = rng.exponential(1.0 / rate_rps, size=len(prompts))
    arrivals = np.cumsum(gaps)
    t0 = time.perf_counter()
    tickets = []
    for i, p in enumerate(prompts):
        while time.perf_counter() - t0 < arrivals[i]:
            time.sleep(0.0005)
        tickets.append(router.submit(p, max_new, session=f"s{i}",
                                     stream=stream))
    router.wait(tickets, timeout=timeout_s)
    wall = time.perf_counter() - t0
    if stream:
        _drain_streams(tickets)
    return tickets, wall


def _drain_streams(tickets):
    """Read each streamed ticket's client records and REPLACE its
    ``itl_p99_s`` with the CLIENT-side inter-token gap p99 (arrival
    stamps at the router fan-in — the latency a streaming consumer
    actually experiences, network hop included), so ``_arm_stats``
    reports streaming ITL from the same field."""
    for t in tickets:
        if t.shed or t.stream is None:
            continue
        stamps = [r["t"] for r in t.stream
                  if r.get("t") is not None and "i" in r]
        gaps = (np.diff(np.asarray(stamps)) if len(stamps) > 1
                else np.asarray([0.0]))
        t.itl_p99_s = float(np.quantile(gaps, 0.99))


def _arm_stats(tickets, wall_s: float, short_lt=None):
    served = [t for t in tickets if not t.shed]
    ttfts = np.asarray([t.ttft_s for t in served])
    toks = sum(len(t.tokens) for t in served)
    itls = np.asarray([t.itl_p99_s for t in served])
    out = {
        "ttft_p50_ms": round(float(np.quantile(ttfts, 0.5)) * 1e3, 2),
        "ttft_p99_ms": round(float(np.quantile(ttfts, 0.99)) * 1e3, 2),
        "itl_p99_ms": round(float(np.quantile(itls, 0.99)) * 1e3, 2),
        "tokps": round(toks / wall_s, 2),
        "shed_rate": round(1.0 - len(served) / len(tickets), 4),
        "requests": len(tickets),
    }
    if short_lt is not None:
        # the interactive tail: TTFT of SHORT prompts only. A long
        # prompt's own TTFT is prefill-dominated either way; what
        # disaggregation structurally removes is shorts waiting behind
        # someone ELSE's monolithic prefill
        s = np.asarray([t.ttft_s for t in served
                        if len(t.prompt) < short_lt])
        if len(s):
            out["ttft_short_p99_ms"] = round(
                float(np.quantile(s, 0.99)) * 1e3, 2)
            # the gate statistic: a mean over all shorts averages
            # scheduler noise that a 12-sample p99 (= max) cannot
            out["ttft_short_mean_ms"] = round(
                float(s.mean()) * 1e3, 2)
    return out


def _piecewise_open_loop(router, prompts, max_new: int, phases, rng,
                         timeout_s: float = 900.0):
    """:func:`_open_loop` over a piecewise-rate schedule — the
    diurnal/spiky traffic trace the autoscale A/B drives. ``phases``
    is ``[(rate_rps, n_requests), ...]``; arrivals inside each phase
    are seeded-Poisson at that phase's rate, so the whole arrival
    vector is a deterministic function of (rng seed, phases)."""
    gaps = np.concatenate([rng.exponential(1.0 / rate, size=n)
                           for rate, n in phases])
    enforce_n = sum(n for _, n in phases)
    assert enforce_n == len(prompts), (enforce_n, len(prompts))
    arrivals = np.cumsum(gaps)
    t0 = time.perf_counter()
    tickets = []
    for i, p in enumerate(prompts):
        while time.perf_counter() - t0 < arrivals[i]:
            time.sleep(0.0005)
        tickets.append(router.submit(p, max_new, session=f"s{i}"))
    router.wait(tickets, timeout=timeout_s)
    return tickets, time.perf_counter() - t0


def _gray_failure_ab(spec_kw, smoke):
    """The ``--gray-failure`` A/B: the SAME seeded open-loop trace
    against a 3-replica fleet, three arms —

    1. ``clean``: no fault, reliability plane on (the baseline the
       gate compares against);
    2. ``off``: one replica wedged ~10x slow (a seeded
       ``replica.wedge`` delay rule — the in-process SIGSTOP/GC-stall
       stand-in) with NO reliability plane: the counterfactual,
       recorded unasserted — requests keep landing on the gray
       replica and its queue melts the tail;
    3. ``on``: the same wedge with the reliability plane on —
       dispatch-latency EWMA + queue outlier trip the breaker, the
       victim leaves placement, stuck in-flight work hedges to a
       healthy replica.

    Gate (ISSUE 20 acceptance): arm 3's p99 TTFT <= 1.5x arm 1's
    (plus a small absolute slack — an 18-sample p99 is nearly a max
    across separately-timed arms), and the victim was actually
    quarantined. Arm 2 rides along as evidence, never asserted."""
    from paddle_tpu.core.enforce import enforce
    from paddle_tpu.resilience import ReliabilityConfig
    from paddle_tpu.resilience.faults import FaultInjector
    from paddle_tpu.serving_router import LocalReplica, Router

    n_rep = 3
    n_req = 18 if smoke else 36
    max_new = 6 if smoke else 8
    wedge_s = 0.12  # per-tick freeze: ~10x a warm CPU serve tick
    vocab = 1024 if smoke else 50257
    reps = [LocalReplica(_router_replica_spec(**spec_kw),
                         name=f"g{i}").start() for i in range(n_rep)]
    victim = reps[-1].name

    def mk_prompts(n, seed):
        r = np.random.default_rng(seed)
        return [r.integers(1, vocab,
                           (int(8 + (i * 5) % 16),)).astype(np.int32)
                for i in range(n)]

    def drive(rep, rids, timeout_s=600.0):
        deadline = time.time() + timeout_s
        seen = {}
        while time.time() < deadline:
            seen.update(rep.drain_results())
            if all(r in seen for r in rids):
                return seen
            time.sleep(0.01)
        raise TimeoutError(f"replica {rep.name}: warm requests "
                           f"incomplete after {timeout_s}s")

    def rel_cfg():
        # hedging arms after 6 fleet completions (the run is short);
        # the cooldown parks the victim for the whole arm — a mid-run
        # half-open probe against a still-wedged replica would only
        # churn the placement the gate is measuring
        return ReliabilityConfig(hedge_min_samples=6,
                                 quarantine_cooldown_s=600.0)

    try:
        # warm every jit path the load will hit (all prompts pad into
        # the short bucket; max_new covers the step)
        for rep in reps:
            drive(rep, [rep.submit(p, 2)
                        for p in (mk_prompts(1, 99)[0],
                                  np.ones(24, np.int32))])
        # rate calibration: one replica's closed-loop service rate;
        # 0.8x of it across a 3-replica fleet keeps the healthy
        # majority unloaded, so the tail movement IS the gray replica
        cal = mk_prompts(8, 1)
        t0 = time.perf_counter()
        drive(reps[0], [reps[0].submit(p, max_new) for p in cal])
        rate = 0.8 * len(cal) / (time.perf_counter() - t0)

        # arm 1: clean fleet, reliability on
        router = Router(reps, poll_interval_s=0.02,
                        reliability=rel_cfg())
        clean = _arm_stats(*_open_loop(
            router, mk_prompts(n_req, 7), max_new, rate,
            np.random.default_rng(300)))
        router.close()

        # arm 2: wedged victim, NO reliability (the counterfactual)
        with FaultInjector().on("replica.wedge", delay_s=wedge_s,
                                match=victim):
            router = Router(reps, poll_interval_s=0.02)
            off = _arm_stats(*_open_loop(
                router, mk_prompts(n_req, 7), max_new, rate,
                np.random.default_rng(300)))
            router.close()

        # arm 3: the same wedge, reliability on
        with FaultInjector().on("replica.wedge", delay_s=wedge_s,
                                match=victim):
            router = Router(reps, poll_interval_s=0.02,
                            reliability=rel_cfg())
            on_tickets, on_wall = _open_loop(
                router, mk_prompts(n_req, 7), max_new, rate,
                np.random.default_rng(300))
            stats = router.stats()
            router.close()
        on = _arm_stats(on_tickets, on_wall)

        # -- the gates -------------------------------------------------
        enforce(victim in (stats.get("quarantined") or []),
                "the wedged replica %s was never quarantined "
                "(quarantined=%s)", victim, stats.get("quarantined"))
        enforce(on["ttft_p99_ms"]
                <= 1.5 * clean["ttft_p99_ms"] + 250.0,
                "reliability-on p99 TTFT %.1f ms under one wedged "
                "replica blew the clean-arm bound %.1f ms (clean "
                "%.1f ms)", on["ttft_p99_ms"],
                1.5 * clean["ttft_p99_ms"] + 250.0,
                clean["ttft_p99_ms"])
    finally:
        for rep in reps:
            rep.close()

    rel = stats.get("reliability") or {}
    extras = dict(on)
    extras.update({
        "replicas": n_rep,
        "rate_rps": round(rate, 3),
        "gray_wedge_s": wedge_s,
        "gray_clean_ttft_p50_ms": clean["ttft_p50_ms"],
        "gray_clean_ttft_p99_ms": clean["ttft_p99_ms"],
        "gray_clean_itl_p99_ms": clean["itl_p99_ms"],
        "gray_clean_tokps": clean["tokps"],
        # the counterfactual, recorded but never asserted: CPU timing
        # noise must not flake the gate, the blowup speaks for itself
        "gray_off_ttft_p99_ms": off["ttft_p99_ms"],
        "gray_off_itl_p99_ms": off["itl_p99_ms"],
        "gray_off_tokps": off["tokps"],
        "gray_on_ttft_p99_ms": on["ttft_p99_ms"],
        "gray_hedges": rel.get("hedges"),
        "gray_hedge_wins": rel.get("hedge_wins"),
        "gray_quarantines": rel.get("quarantines"),
        "gray_retry_budget": (rel.get("budget") or {}).get("tokens"),
    })
    return extras.pop("tokps"), "tokens/sec", extras


def _autoscale_spike_ab(spec_kw, autoscale, smoke):
    """The ``--autoscale MIN,MAX`` A/B: the SAME seeded spiky trace
    (base rate, a 3x spike, base again) against two fleets —

    1. ``static``: MAX replicas up for the whole run (the
       over-provisioned baseline an autoscaler must justify itself
       against);
    2. ``autoscaled``: MIN replicas + a live :class:`~paddle_tpu.
       autoscale.Scaler` growing the fleet on the spike and draining
       it back on sustained headroom.

    The replicas beyond MIN are pre-built and pre-warmed before the
    timed run — the in-process stand-in for the AOT artifact shelf
    (scale-up without trace+compile; production spawns hit the same
    shape via ``spawn_replicas(..., from_artifact=...)``), so the
    measured TTFR is the artifact-boot analog, not a compile.

    Gates (ISSUE 18 acceptance):

    - strictly fewer replica-seconds than static max over the serving
      window;
    - short-prompt p99 TTFT and p99 ITL within the static arm's
      bounds (a CPU-noise slack factor — a 32-sample p99 is nearly a
      max across two separately-timed arms) and shed no worse;
    - the fleet actually grew (the spike forced at least one scale-up)
      and came back to MIN (sustained headroom drained it);
    - no flap: scale events <= the policy's cooldown-implied ceiling;
    - replaying the recorded signal trace through a fresh policy
      reproduces the live decision list bit-identically."""
    from paddle_tpu.autoscale import AutoscalePolicy, Scaler, replay
    from paddle_tpu.core.enforce import enforce
    from paddle_tpu.serving_router import LocalReplica, Router

    amin, amax = int(autoscale[0]), int(autoscale[1])
    enforce(1 <= amin < amax,
            "--autoscale needs 1 <= MIN < MAX, got %s,%s", amin, amax)
    long_len, max_new = (112, 8) if smoke else (192, 16)
    short_lt = long_len // 2
    vocab = 1024 if smoke else 50257

    def mk_prompts(n, seed):
        # the router bench's mix: every 3rd prompt LONG, so the spike
        # carries prefill weight too, not just decode ticks
        r = np.random.default_rng(seed)
        out = []
        for i in range(n):
            ln = long_len if i % 3 == 2 else int(8 + (i * 5) % 16)
            out.append(r.integers(1, vocab, (ln,)).astype(np.int32))
        return out

    def drive(rep, rids, timeout_s=600.0):
        seen = {}
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            seen.update(rep.drain_results())
            if all(r in seen for r in rids):
                return seen
            time.sleep(0.01)
        raise TimeoutError(f"replica {rep.name}: warm requests "
                           f"incomplete after {timeout_s}s")

    # the whole MAX fleet, pre-warmed (short + long jit paths) BEFORE
    # any timed arm: the static arm uses all of it; the autoscaled arm
    # starts with [:amin] and pops the rest off the "artifact shelf"
    reps = [LocalReplica(_router_replica_spec(**spec_kw),
                         name=f"as{i}").start() for i in range(amax)]
    warm = mk_prompts(2, 99)
    for rep in reps:
        drive(rep, [rep.submit(p, 2)
                    for p in (warm[0], warm[1],
                              np.ones(long_len, np.int32))])
    scaler = None
    try:
        # rate calibration on ONE replica (the autoscaled arm's floor
        # capacity): base load a single replica absorbs with headroom,
        # spike 3x that — beyond one replica, inside MAX
        cal = mk_prompts(8, 1)
        t0 = time.perf_counter()
        drive(reps[0], [reps[0].submit(p, max_new) for p in cal])
        cal_rps = len(cal) / (time.perf_counter() - t0)
        # base at 30% of one replica's closed-loop rate puts the 3x
        # spike at 0.9x aggregate capacity. That ratio is the whole
        # experiment: in-process replicas SHARE the host's compute
        # (one XLA executable already saturates it), so growing the
        # fleet buys decode SLOTS (concurrency -> queue wait), not
        # throughput — a spike above aggregate capacity builds a
        # backlog no fleet size can drain and the A/B would measure
        # queueing collapse, while at 0.9x the MIN fleet is slot-
        # starved (arrivals queue behind 2 busy slots) and the spawns
        # visibly collapse the wait. Production TPU replicas add both
        # axes; the slot axis is the one this host can exhibit.
        base = 0.30 * cal_rps
        spike = 3.0 * base
        n_base = 8 if smoke else 12
        n_spike = 16 if smoke else 24
        phases = [(base, n_base), (spike, n_spike), (base, n_base)]
        n_req = 2 * n_base + n_spike

        # arm A: static max
        router = Router(reps, poll_interval_s=0.02)
        st_tickets, st_wall = _piecewise_open_loop(
            router, mk_prompts(n_req, 11), max_new, phases,
            np.random.default_rng(200))
        router.close()
        static = _arm_stats(st_tickets, st_wall, short_lt=short_lt)
        static_rs = amax * st_wall

        # arm B: autoscaled, same arrival schedule (same seed+phases)
        shelf = list(reps[amin:])
        fresh = iter(range(amax, 1_000_000))

        def spawn():
            if shelf:
                return shelf.pop(0)
            # shelf exhausted (retire_fn repools drained replicas, so
            # only MAX-1 spawns can ever be in flight at once — this
            # is a belt-and-braces path): a real cold boot
            rep = LocalReplica(_router_replica_spec(**spec_kw),
                               name=f"as{next(fresh)}").start()
            reps.append(rep)
            rep.warmup()
            return rep

        router = Router(reps[:amin], poll_interval_s=0.02)
        # proactive up (a 100ms dispatch wait or 1.5x slots of
        # in-flight votes up — real queueing, not the momentary
        # all-slots-busy of two base arrivals overlapping), patient
        # down (Poisson base traffic has multi-second quiet gaps; the
        # headroom hold + down cooldown must outlast them or the
        # scaler drains mid-base and pays a spawn on the next burst);
        # the cooldowns (plus the measured TTFR) bound the event rate
        policy = AutoscalePolicy(
            min_replicas=amin, max_replicas=amax,
            up_queue_wait_s=0.1, up_load=1.5,
            down_queue_wait_s=0.05, down_load=0.5,
            headroom_hold_s=2.5, cooldown_up_s=0.25,
            cooldown_down_s=4.0, ttfr_hint_s=0.25)
        # retired replicas go BACK on the shelf still warm: scale-down
        # destroys the instance, not the artifact it boots from
        scaler = Scaler(router, policy, spawn, interval_s=0.05,
                        retire_fn=shelf.append)
        t_run0 = time.monotonic()
        scaler.start()
        as_tickets, as_wall = _piecewise_open_loop(
            router, mk_prompts(n_req, 11), max_new, phases,
            np.random.default_rng(200))
        serve_end = time.monotonic()
        auto = _arm_stats(as_tickets, as_wall, short_lt=short_lt)
        auto_rs = scaler.replica_seconds(until=serve_end)
        # post-trace idle tail: give sustained headroom room to drain
        # the spike's replicas back to MIN (bounded — the no-flap
        # cooldowns make each down step take hold+cooldown)
        deadline = time.monotonic() + 30.0
        while (time.monotonic() < deadline
               and scaler._live_count() > amin):
            time.sleep(0.05)
        scaler.stop()
        router.close()
        total_wall = time.monotonic() - t_run0

        ups = [e for e in scaler.scale_events()
               if e["event"] == "scale_up"]
        downs = [e for e in scaler.scale_events()
                 if e["event"] == "scale_down"]
        peak = max(n for _, n in scaler.timeline)
        final = scaler.timeline[-1][1]

        # -- the gates -------------------------------------------------
        enforce(len(ups) >= 1 and peak > amin,
                "the 3x spike never forced a scale-up (peak fleet "
                "%s from %s)", peak, amin)
        enforce(len(downs) >= 1 and final == amin,
                "sustained headroom never drained the fleet back to "
                "MIN (final %s, want %s)", final, amin)
        enforce(auto_rs < static_rs,
                "autoscaling must cost strictly fewer replica-seconds "
                "than static max (%.1f vs %.1f)", auto_rs, static_rs)
        # SLO within the static arm's bounds. Two-level, the router
        # bench gate's precedent: the MEAN short TTFT carries the
        # tight bound (a ~20-sample p99 is the max — it always
        # captures the one short that arrived in the spike's onset
        # window before the spawns landed, pure scale-up physics, not
        # a provisioning regression), while the p99 rides with a
        # collapse bound that a fleet stuck at MIN through the spike
        # blows by an order of magnitude
        enforce(auto["ttft_short_mean_ms"]
                <= 1.5 * static["ttft_short_mean_ms"] + 150.0,
                "autoscaled mean short-prompt TTFT %.1f ms blew the "
                "static-max bound %.1f ms",
                auto["ttft_short_mean_ms"],
                static["ttft_short_mean_ms"])
        enforce(auto["ttft_short_p99_ms"]
                <= 2.5 * static["ttft_short_p99_ms"] + 250.0,
                "autoscaled short-prompt p99 TTFT %.1f ms collapsed "
                "vs the static-max bound %.1f ms",
                auto["ttft_short_p99_ms"],
                static["ttft_short_p99_ms"])
        enforce(auto["itl_p99_ms"]
                <= 1.5 * static["itl_p99_ms"] + 100.0,
                "autoscaled p99 ITL %.1f ms blew the static-max "
                "bound %.1f ms", auto["itl_p99_ms"],
                static["itl_p99_ms"])
        enforce(auto["shed_rate"] <= static["shed_rate"] + 0.02,
                "autoscaled shed rate %.3f worse than static %.3f",
                auto["shed_rate"], static["shed_rate"])
        ceiling = policy.max_events(total_wall, scaler.ttfr_s)
        enforce(len(scaler.scale_events()) <= ceiling,
                "flap: %s scale events exceed the cooldown-implied "
                "ceiling %s over %.1fs",
                len(scaler.scale_events()), ceiling, total_wall)
        twin = replay(AutoscalePolicy(**policy.knobs()),
                      scaler.trace.rows)
        enforce(json.dumps(twin, sort_keys=True)
                == json.dumps(scaler.decisions, sort_keys=True),
                "replaying the recorded signal trace diverged from "
                "the live decisions")
    finally:
        if scaler is not None:
            scaler.stop()
        for rep in reps:
            rep.close()

    tl0 = scaler.timeline[0][0]
    extras = dict(auto)
    extras.update({
        "autoscale_min": amin, "autoscale_max": amax,
        "autoscale_peak": int(peak),
        "rate_rps": round(base, 3),
        "spike_rate_rps": round(spike, 3),
        "replica_seconds": round(auto_rs, 2),
        "replica_timeline": [[round(t - tl0, 2), n]
                             for t, n in scaler.timeline],
        "autoscale_scale_ups": len(ups),
        "autoscale_scale_downs": len(downs),
        "autoscale_events_ceiling": int(ceiling),
        "autoscale_ttfr_s": (round(scaler.ttfr_s, 3)
                             if scaler.ttfr_s is not None else None),
        "static_replica_seconds": round(static_rs, 2),
        "static_ttft_p50_ms": static["ttft_p50_ms"],
        "static_ttft_p99_ms": static["ttft_p99_ms"],
        "static_ttft_short_p99_ms": static.get("ttft_short_p99_ms"),
        "static_ttft_short_mean_ms": static.get("ttft_short_mean_ms"),
        "static_itl_p99_ms": static["itl_p99_ms"],
        "static_shed_rate": static["shed_rate"],
        "static_tokps": static["tokps"],
    })
    return extras.pop("tokps"), "tokens/sec", extras


def bench_gpt_router(steps: int, batch_size: int, amp=None,
                     smoke: bool = False, replicas: int = 2,
                     prefill_workers: int = 1, overload: float = 2.0,
                     kv_dtype=None, router_procs: bool = False,
                     stream: bool = False, from_artifact: bool = False,
                     autoscale=None, gray_failure: bool = False):
    """Production-serving A/B (serving_router.Router): a seeded Poisson
    OPEN-loop load with long prompts mixed in, three arms on the same
    replicas —

    1. ``mono``: single replica, monolithic whole-prompt prefill (the
       pre-router baseline: a long admission stalls every decode tick);
    2. headline: ``replicas`` decode replicas behind the router with
       ``prefill_workers`` dedicated prefill workers (long prompts
       prefill OFF the decode loop and hand off KV pages) at the SAME
       offered rate — the p99-TTFT win at equal aggregate tok/s;
    3. ``overload``: the same topology at ``overload``x the rate with
       the SLO shed policy on — p99 TTFT stays bounded (sheds absorb
       the excess) instead of queue collapse.

    The offered rate self-calibrates to 85% of the mono replica's
    closed-loop service rate (high enough that arrivals collide with
    monolithic long-prompt prefills, below mono saturation), so the
    numbers transfer across backends.
    ``--router-procs`` runs the replicas as real worker processes over
    HTTP (the deployment shape); default is in-process replica threads
    (same router code path, deterministic for the gate test)."""
    from paddle_tpu.serving_router import (LocalReplica, Router,
                                           SLOPolicy, spawn_replicas)

    if autoscale is not None:
        # the autoscaling spike A/B is its own workload (piecewise
        # rate, elastic fleet): it replaces the disagg arms entirely
        return _autoscale_spike_ab({"smoke": smoke,
                                    "kv_dtype": kv_dtype},
                                   autoscale, smoke)
    if gray_failure:
        # the gray-failure reliability A/B likewise: one wedged
        # replica, three arms, its own gate
        return _gray_failure_ab({"smoke": smoke,
                                 "kv_dtype": kv_dtype}, smoke)

    n_req = 18 if smoke else max(18, min(steps, 48))
    long_len, max_new = (112, 8) if smoke else (192, 16)
    disagg_min = long_len // 2
    rng = np.random.default_rng(0)
    vocab = 1024 if smoke else 50257
    spec_kw = {"smoke": smoke, "kv_dtype": kv_dtype}
    # the AOT TTFR A/B boots its own pair of replicas BEFORE the fleet
    # spawns (no shared page pools, so neither boot is flattered by a
    # pre-warmed process) and gates ttfr_aot < ttfr_traced
    aot_cols = _router_aot_ttfr_ab(spec_kw) if from_artifact else {}

    def mk_prompts(n, seed):
        # every 3rd prompt is LONG — the mix that makes monolithic
        # admission visibly steal decode ticks (the disagg motivation)
        r = np.random.default_rng(seed)
        out = []
        for i in range(n):
            ln = long_len if i % 3 == 2 else int(8 + (i * 5) % 16)
            out.append(r.integers(1, vocab, (ln,)).astype(np.int32))
        return out

    def drive(rep, rids, timeout_s=600.0):
        # transport-agnostic completion wait: ACCUMULATE drained
        # results locally (HttpReplica's /drain consumes server-side;
        # a keep=True peek only exists on LocalReplica)
        seen = {}
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            seen.update(rep.drain_results())
            if all(r in seen for r in rids):
                return seen
            time.sleep(0.01)
        raise TimeoutError(f"replica {rep.name}: warm/calibration "
                           f"requests incomplete after {timeout_s}s")

    if router_procs:
        spec = "bench:_router_replica_spec"
        reps = spawn_replicas(spec, replicas, spec_kw=spec_kw)
        pfs = spawn_replicas(spec, prefill_workers, role="prefill",
                             spec_kw=spec_kw) if prefill_workers else []
    else:
        reps = [LocalReplica(_router_replica_spec(**spec_kw),
                             name=f"r{i}").start()
                for i in range(replicas)]
        pfs = [LocalReplica(_router_replica_spec(**spec_kw),
                            name=f"pf{i}")
               for i in range(prefill_workers)]
        # warm every jit path the load will hit (short + long prompt
        # buckets, the serving step, the prefill worker's long bucket)
        warm = mk_prompts(2, 99)
        for rep in reps:
            drive(rep, [rep.submit(p, 2)
                        for p in (warm[0], warm[1],
                                  np.ones(long_len, np.int32))])
        for pw in pfs:
            pw.decoder.prefill_export(np.ones(long_len, np.int32))
            pw.decoder._warmed = True
        if pfs:
            # one full disagg round trip per decode replica: compiles
            # the page-import executables so the first TIMED handoff
            # isn't a cold trace
            h = pfs[0].prefill(np.ones(long_len, np.int32))
            for rep in reps:
                drive(rep, [rep.inject(h, 2)])
    try:
        # rate calibration: closed-loop service rate of ONE replica
        cal = mk_prompts(8, 1)
        t0 = time.perf_counter()
        drive(reps[0], [reps[0].submit(p, max_new) for p in cal])
        cal_rps = len(cal) / (time.perf_counter() - t0)
        # 85% of the MONO closed-loop service rate: high enough that
        # arrivals collide with monolithic long-prompt prefills (the
        # tail the router exists to fix), below mono saturation so the
        # baseline arm still drains
        rate = 0.85 * cal_rps

        # arms 1+2 (+ the streaming arm) INTERLEAVED in alternating
        # blocks over the same replicas: every arm samples the same
        # machine-load epochs, so slow background drift between
        # sequentially-timed arms can't masquerade as (or mask) the
        # disaggregation/streaming effect
        mono_router = Router(reps[:1], poll_interval_s=0.02)
        head_router = Router(reps, prefill_workers=pfs,
                             disagg_min_tokens=disagg_min,
                             poll_interval_s=0.02)
        cycle = (("mono", "head", "stream") * 2 if stream
                 else ("mono", "head", "mono", "head"))
        n_arms = len(set(cycle))
        arm_tickets = {a: [] for a in set(cycle)}
        arm_wall = {a: 0.0 for a in set(cycle)}
        half = max(6, n_req // 2)
        for b, arm in enumerate(cycle):
            router = mono_router if arm == "mono" else head_router
            # prompt seed advances per ROUND (b // n_arms), so every
            # arm samples the IDENTICAL prompt sets — a seed-dependent
            # long-prompt skew can't masquerade as an arm effect
            tickets, wall = _open_loop(
                router, mk_prompts(half, 10 + b // n_arms), max_new,
                rate, np.random.default_rng(100 + b),
                stream=(arm == "stream"))
            arm_tickets[arm].extend(tickets)
            arm_wall[arm] += wall
        mono = _arm_stats(arm_tickets["mono"], arm_wall["mono"],
                          short_lt=disagg_min)
        head = _arm_stats(arm_tickets["head"], arm_wall["head"],
                          short_lt=disagg_min)
        stream_arm = (_arm_stats(arm_tickets["stream"],
                                 arm_wall["stream"],
                                 short_lt=disagg_min)
                      if stream else None)
        mono_router.close()
        head_router.close()

        # arm 3: overload with the SLO shed policy. The overload rate
        # anchors on the CLOSED-LOOP service rate (saturation), not the
        # 70% offered rate — "2x overload" must actually exceed
        # capacity or no queue ever builds; the arm runs 2x as many
        # requests so the queue demonstrably grows without the policy
        router = Router(reps, prefill_workers=pfs,
                        disagg_min_tokens=disagg_min,
                        policy=SLOPolicy(degrade_at=1.0, shed_at=1.5),
                        poll_interval_s=0.02)
        over = _arm_stats(*_open_loop(router, mk_prompts(2 * n_req, 3),
                                      max_new, overload * cal_rps,
                                      rng))
        router.close()
    finally:
        for rep in reps + pfs:
            rep.close()
    extras = dict(head)
    extras.update({
        "replicas": replicas, "prefill_workers": prefill_workers,
        "rate_rps": round(rate, 3),
        "mono_ttft_p50_ms": mono["ttft_p50_ms"],
        "mono_ttft_p99_ms": mono["ttft_p99_ms"],
        "mono_ttft_short_p99_ms": mono.get("ttft_short_p99_ms"),
        "mono_ttft_short_mean_ms": mono.get("ttft_short_mean_ms"),
        "mono_itl_p99_ms": mono["itl_p99_ms"],
        "mono_tokps": mono["tokps"],
        "overload_ttft_p99_ms": over["ttft_p99_ms"],
        "overload_shed_rate": over["shed_rate"],
        "overload_tokps": over["tokps"],
        # provisioning-cost accounting on EVERY router row (the
        # autoscale A/B's comparison substrate): a static fleet's
        # replica-seconds are just count x wall, and its timeline one
        # flat change-point — same columns, same meaning, as the
        # elastic rows
        "replica_seconds": round(replicas * arm_wall["head"], 2),
        "replica_timeline": [[0.0, replicas]],
        "mono_replica_seconds": round(arm_wall["mono"], 2),
        "mono_replica_timeline": [[0.0, 1]],
    })
    extras.update(aot_cols)
    if stream_arm is not None:
        # the streaming arm, one column family apart: TTFT here is the
        # router-side FIRST-TOKEN stamp and ITL the client-side
        # inter-token gaps (_drain_streams) — same load, same replicas
        extras.update({
            "stream_ttft_p50_ms": stream_arm["ttft_p50_ms"],
            "stream_ttft_p99_ms": stream_arm["ttft_p99_ms"],
            "stream_ttft_short_mean_ms":
                stream_arm.get("ttft_short_mean_ms"),
            "stream_itl_p99_ms": stream_arm["itl_p99_ms"],
            "stream_tokps": stream_arm["tokps"],
        })
        # shared-system-prompt routing A/B (in-process by design: the
        # signal is the ROUTING logic's hit rate, counter-verified
        # from pool stats, not a transport latency)
        extras.update(_prefix_routing_ab())
    return extras.pop("tokps"), "tokens/sec", extras


def _prefix_routing_ab(seed: int = 0, n_req: int = 12):
    """Shared-system-prompt routing A/B: the SAME workload (two
    64-token system prompts, each carried by several requests) against
    prefix-hash routing vs session-only affinity, over 2 fresh
    prefix-cache replicas per arm. The reported hit rates are
    COUNTER-VERIFIED from the replicas' own pool stats
    (``decoder.prefix_hits`` / ``prefix_lookups``), never inferred
    from routing decisions.

    Determinism: the session arm pre-pins its sessions with a blocking
    wave of 2 x slots unique requests (slot caps force an exact split
    — the best session-only routing can do), and every session serves
    BOTH system prompts over the run, so ANY 2/2 session split makes
    both replicas prefill both prefixes: misses = 2 per prefix. The
    hash arm's fresh-session requests follow the prefix home: misses
    = 1 per prefix. Strictly higher hit rate, by construction."""
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder
    from paddle_tpu.serving_router import LocalReplica, Router

    rng = np.random.default_rng(seed)
    sys_prompts = [rng.integers(1, 500, (64,)).astype(np.int32)
                   for _ in range(2)]
    suffixes = [rng.integers(1, 500, (8,)).astype(np.int32)
                for _ in range(n_req)]
    seeds_p = [rng.integers(1, 500, (8,)).astype(np.int32)
               for _ in range(4)]
    # every session meets every prefix: (session i%4, prefix pattern
    # that rotates) — see docstring
    pattern = [(i % 4, (i + i // 4) % 2) for i in range(n_req)]

    def mk_replicas():
        reps = []
        for i in range(2):
            pt.seed(0)
            m = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
            d = BatchedDecoder(m, slots=2, capacity=192, pages=24,
                               page_size=64, prefix_cache=True)
            reps.append(LocalReplica(d, name=f"p{i}").start())
        for rep in reps:
            rep.warmup()
        return reps

    out = {}
    for arm, pht in (("hash", 64), ("session", None)):
        reps = mk_replicas()
        router = Router(reps, poll_interval_s=0.02,
                        prefix_hash_tokens=pht,
                        disagg_min_tokens=None)
        try:
            if arm == "session":
                seeds = [router.submit(seeds_p[j], 2, session=f"s{j}")
                         for j in range(4)]
                router.wait(seeds, timeout=300)
            base_h = sum(r.decoder.prefix_hits for r in reps)
            base_l = sum(r.decoder.prefix_lookups for r in reps)
            for i, (sess_i, pfx_i) in enumerate(pattern):
                p = np.concatenate([sys_prompts[pfx_i], suffixes[i]])
                sess = (f"s{sess_i}" if arm == "session"
                        else f"fresh{i}")
                # sequential on purpose: the measured quantity is the
                # hit RATE, and concurrent same-prefix admissions
                # can't hit a registry that fills at completion
                router.submit(p, 4, session=sess).wait(300)
            hits = sum(r.decoder.prefix_hits for r in reps) - base_h
            lookups = (sum(r.decoder.prefix_lookups for r in reps)
                       - base_l)
            out[f"prefix_hits_{arm}"] = int(hits)
            out[f"prefix_lookups_{arm}"] = int(lookups)
            out[f"prefix_hit_rate_{arm}"] = round(
                hits / max(1, lookups), 4)
        finally:
            router.close()
            for rep in reps:
                rep.close()
    return out


def _kv_serve_density(model, cap: int, smoke: bool):
    """The serving-density A/B behind ``--kv-dtype int8``: at ONE
    page-pool HBM budget (what ``base_pages`` fp32 pages cost), how
    many concurrent sessions does each KV storage form admit before
    the pool backpressures? Sessions are real admissions (one page
    each), counted after a single admission pass with slots sized off
    the critical path — pages are the binding resource, exactly the
    production regime (KV HBM sets the per-chip session ceiling). Both
    arms then serve the SAME prompts to completion greedily; the
    agreement of rid-matched outputs is the parity evidence (near-tie
    argmax flips compound on an untrained model, so first-half
    agreement is the gate — the same contract the spec-decode bench
    uses)."""
    from paddle_tpu.serving import BatchedDecoder, PagedKVPool

    attn0 = model.blocks[0].self_attn
    nblk = len(model.blocks)
    ps = 64

    def per_page(kvd):
        return PagedKVPool(1, ps, attn0.num_kv_heads, attn0.head_dim,
                           arrays=False, kv_dtype=kvd).pool_nbytes

    base_pages = 8 if smoke else 24
    budget = base_pages * 2 * nblk * per_page(None)
    pages = {kvd: int(budget // (2 * nblk * per_page(kvd)))
             for kvd in (None, "int8")}
    # enough submissions that BOTH arms hit pool backpressure
    n_req = pages["int8"] + 2
    rng = np.random.default_rng(7)
    vocab = model.cfg.vocab_size
    plen, mnew = 24, 8
    prompts = [rng.integers(1, vocab, (plen,)).astype(np.int32)
               for _ in range(n_req)]
    out = {"kv_page_bytes_fp32": per_page(None),
           "kv_page_bytes_int8": per_page("int8"),
           "kv_pool_budget_bytes": int(budget)}
    outs_by_arm = {}
    for kvd in (None, "int8"):
        dec = BatchedDecoder(model, slots=n_req, capacity=cap,
                             pages=pages[kvd], page_size=ps,
                             kv_dtype=kvd)
        rids = [dec.submit(p, mnew) for p in prompts]
        dec._admit()  # ONE admission wave: pages bind, slots don't
        admitted = sum(o is not None for o in dec.owner)
        out[f"max_sessions_{kvd or 'fp32'}"] = int(admitted)
        served = dec.run()
        outs_by_arm[kvd] = [served[r] for r in rids]
    if out["max_sessions_fp32"]:
        out["session_ratio"] = round(
            out["max_sessions_int8"] / out["max_sessions_fp32"], 3)
    agree = [float((a == b).mean()) for a, b in
             zip(outs_by_arm[None], outs_by_arm["int8"])]
    half = [float((a[:len(a) // 2] == b[:len(b) // 2]).mean())
            for a, b in zip(outs_by_arm[None], outs_by_arm["int8"])]
    out["kv_parity_agree"] = round(sum(agree) / len(agree), 3)
    out["kv_parity_gate"] = bool(sum(half) / len(half) >= 0.9)
    return out


def _kv_decode_step_time(model, cap: int, smoke: bool):
    """The decode-step-time A/B behind ``--kv-dtype int8`` (ISSUE 15
    column): one jitted paged-attend step at the SAME batch over
    identical live caches, fp32 storage vs int8 storage. On a real
    chip the int8 arm rides the Pallas dequant-epilogue kernel (int8
    HBM blocks, in-VMEM dequant) and the gate is parity-or-better; on
    the CPU backend both arms take the gather path, so the columns are
    recorded but the gate stays unjudged (``None`` — degraded-bench
    honesty, same contract as the rest of the r06 rows)."""
    import time as _t

    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import PagedKVPool

    attn0 = model.blocks[0].self_attn
    kvh, hd = attn0.num_kv_heads, attn0.head_dim
    nh = attn0.num_heads
    ps = 64
    bsz = 2 if smoke else 8
    nlog = cap // ps
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(bsz, 1, nh, hd)).astype(np.float32))
    t_rows = jnp.asarray([cap // 2 + (i % ps) for i in range(bsz)],
                         jnp.int32)
    out = {}
    for kvd in (None, "int8"):
        pool = PagedKVPool(pages=bsz * nlog, page_size=ps, kv_heads=kvh,
                           head_dim=hd, kv_dtype=kvd)
        table = jnp.asarray(np.stack([pool.alloc(nlog)
                                      for _ in range(bsz)]))
        kp, vp = pool.kpool, pool.vpool
        for i in range(bsz):
            n = int(t_rows[i]) + 1
            kc = jnp.asarray(rng.normal(size=(1, n, kvh, hd))
                             .astype(np.float32))
            vc = jnp.asarray(rng.normal(size=(1, n, kvh, hd))
                             .astype(np.float32))
            kp, vp = PagedKVPool.write_chunk(kp, vp, table[i], 0, kc,
                                             vc, ps)
        fn = jax.jit(lambda q, kp, vp, t: PagedKVPool.attend(
            q, kp, vp, table, t))
        jax.block_until_ready(fn(q, kp, vp, t_rows))   # compile
        iters = 3 if smoke else 10
        t0 = _t.perf_counter()
        for _ in range(iters):
            o = fn(q, kp, vp, t_rows)
        jax.block_until_ready(o)
        out[f"kv_decode_step_ms_{kvd or 'fp32'}"] = round(
            (_t.perf_counter() - t0) / iters * 1e3, 3)
    ratio = (out["kv_decode_step_ms_int8"]
             / max(out["kv_decode_step_ms_fp32"], 1e-9))
    out["kv_decode_step_ratio"] = round(ratio, 3)
    out["kv_decode_gate"] = (bool(ratio <= 1.05)
                             if jax.default_backend() == "tpu"
                             else None)
    return out


def _parse_plan_arg(plan: str) -> dict:
    """'ep=8' / 'dp=2,ep=4' -> {'dp': int, 'ep': int} (argument misuse
    raises ValueError; main() turns it into the value-0.0 error line)."""
    axes = {"dp": 1, "ep": 1}
    for part in str(plan).split(","):
        k, sep, v = part.partition("=")
        k, v = k.strip(), v.strip()
        if k not in axes or not sep or not v.isdigit() or int(v) < 1:
            raise ValueError(
                f"--plan expects 'ep=N' or 'dp=M,ep=N' with N>=1, "
                f"got {plan!r}")
        axes[k] = int(v)
    return axes


def _bench_deepfm_sparse_ep(steps, batch_size, amp, vocab, plan_arg):
    """The ep-sharded arm of deepfm_sparse: the full sharded-embedding
    vertical slice under ``Plan(dp=M, ep=N, tables=[...])`` —

    - tables row-sharded over the ``ep`` mesh axis, trained through
      ``embedding.sparse_ep_minimize_fn`` (local MergeAdd + int8
      (ids, rows) exchange; the dense (V, D) gradient never exists) and
      compiled once through ``parallel.compile_step``;
    - the byte-budget gate (the PR-6 evidence shape): the REPLICATED
      table footprint must exceed the per-device budget while the
      ep-sharded footprint fits — the table provably cannot fit one
      device, only the plan can hold it;
    - wire accounting: per-step sparse payload bytes (counter-verified
      via ``record_exchange_bytes``) next to the dense-allreduce
      counterfactual over the same device count;
    - the host-backed feeding plane: a ``HostBackedTable`` mirror of
      the big table rides ``DevicePrefetcher(prefetch_rows=...)`` so
      each batch's rows stage host->chip overlapped with compute;
      extras report its cache hit rate on the (skewed) id stream.
    """
    import contextlib

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.core.dtypes import policy_scope
    from paddle_tpu.data import DevicePrefetcher
    from paddle_tpu.embedding import (HostBackedTable, dense_grad_bytes,
                                      exchange_payload_bytes,
                                      record_exchange_bytes,
                                      should_compress,
                                      sparse_ep_minimize_fn)
    from paddle_tpu.models import deepfm as DF
    from paddle_tpu.parallel.plan import Plan, compile_step

    axes = _parse_plan_arg(plan_arg)
    dp, ep = axes["dp"], axes["ep"]
    need = dp * ep
    n_dev = len(jax.devices())
    if n_dev < need:  # main() pre-checks; defensive for direct callers
        raise RuntimeError(f"--plan {plan_arg} needs {need} devices, "
                           f"have {n_dev}")

    pt.seed(0)
    vocab = max(ep, vocab - vocab % ep)   # ep must divide the rows
    batch_size = max(dp, batch_size - batch_size % dp)
    cfg = DF.DeepFMConfig(total_vocab=vocab, num_fields=26, dense_dim=13,
                          embed_dim=16, embedding_axis=None,
                          sparse_grads=True)
    model = DF.DeepFM(cfg)
    params = model.named_parameters()
    plan = Plan(dp=dp, ep=ep,
                tables=[r"(embedding|linear_embed)\.weight$"],
                devices=jax.devices()[:need])
    table_names = sorted(n for n in params if plan.is_table(n))
    assert table_names, "no table matched the ep registration"

    # --- byte-budget gate (PR-6 evidence shape): replicated tables
    # exceed the per-device budget, the ep-sharded form fits ----------
    replicated = sum(int(np.prod(params[n].shape)) * 4
                     for n in table_names)
    planned = sum(-(-int(params[n].shape[0]) // ep)
                  * int(np.prod(params[n].shape[1:])) * 4
                  for n in table_names)
    budget = replicated // 2
    assert planned <= budget < replicated, (
        f"byte-budget gate: planned {planned} must fit budget {budget} "
        f"< replicated {replicated} (raise --vocab or ep)")

    placed = plan.place(params)

    def forward_loss(p, ids, dense):
        with (policy_scope(amp) if amp else contextlib.nullcontext()):
            logits, _ = model.functional_call(p, ids, dense)
            labels = (ids[:, 0] % 2).astype(jnp.float32)
            return DF.loss_fn(logits, labels)

    opt = optimizer.Adam(1e-3)
    init_fn, step_fn = sparse_ep_minimize_fn(model, forward_loss, opt,
                                             plan=plan)
    state = init_fn(placed)
    rep = NamedSharding(plan.mesh, P())
    s_sh = jax.tree_util.tree_map(
        lambda x: (NamedSharding(plan.mesh, P("ep", None))
                   if getattr(x, "ndim", 0) >= 1 and x.shape[0] == vocab
                   else rep), state)
    state = jax.tree_util.tree_map(jax.device_put, state, s_sh)
    p_sh = jax.tree_util.tree_map(lambda x: x.sharding, placed)
    bs = plan.batch_sharding()
    step = compile_step(plan, step_fn, in_shardings=(p_sh, s_sh, bs, bs),
                        out_shardings=(rep, p_sh, s_sh))

    # --- host-backed feeding plane: the big table's HostBackedTable
    # mirror stages each batch's rows host->chip from the prefetcher's
    # background thread (parameter_prefetch overlap, no PS fleet) ------
    cap = max(64, vocab // 16)
    host_tbl = HostBackedTable.from_array(placed[table_names[0]],
                                          capacity=cap,
                                          name="deepfm.embedding")
    rng = np.random.default_rng(0)
    total = steps + 3  # timed steps + warmup

    def batches():
        for _ in range(total):
            # power-law id skew (CTR traffic shape): the hot head makes
            # the working set meaningful — a uniform stream at V >> cap
            # would measure only cold misses
            ids = np.minimum(
                vocab * rng.random((batch_size, cfg.num_fields)) ** 8,
                vocab - 1).astype(np.int32)
            dense = rng.normal(
                size=(batch_size, cfg.dense_dim)).astype(np.float32)
            yield {"ids": ids, "dense": dense}

    pref = DevicePrefetcher(
        batches, size=2, sharding=bs,
        prefetch_rows=lambda b: host_tbl.prefetch(b["ids"]))

    # --- wire accounting (static shapes -> computed once per step) ----
    n_ids = batch_size * cfg.num_fields  # global ids per step
    payload = 0
    for n in table_names:
        dim = int(params[n].shape[1])
        comp = should_compress(n_ids, dp, dim)
        payload += exchange_payload_bytes(n_ids // dp, dim, dp,
                                          compressed=comp)
    # the counterfactual: dense (V, D) fp32 table-grad allreduce over
    # the SAME device count (what a replicated-table dp=need run moves)
    dense_cf = sum(dense_grad_bytes(vocab, int(params[n].shape[1]), need)
                   for n in table_names)

    it = iter(pref)
    for _ in range(3):
        b = next(it)
        loss, placed, state = step(placed, state, b["ids"], b["dense"])
    float(loss)
    t0 = time.perf_counter()
    done = 0
    for b in it:
        loss, placed, state = step(placed, state, b["ids"], b["dense"])
        for n in table_names:
            dim = int(params[n].shape[1])
            record_exchange_bytes(
                n_ids // dp, dim, dp,
                compressed=should_compress(n_ids, dp, dim))
        done += 1
        if done % 4 == 3:
            float(loss)
    float(loss)
    dt = time.perf_counter() - t0
    assert done == steps, f"prefetcher delivered {done}/{steps} batches"

    extras = {
        "step_time_ms": round(dt / steps * 1e3, 3),
        "emb_rows_per_sec": round(steps * n_ids / dt, 1),
        "emb_payload_bytes_per_step": int(payload),
        "emb_dense_grad_bytes_per_step": int(dense_cf),
        "emb_bytes_ratio": (round(dense_cf / payload, 1)
                            if payload else None),
        "emb_cache_hit_rate": round(host_tbl.hit_rate, 4),
        "emb_cache_capacity_rows": int(cap),
        "emb_table_rows": int(vocab),
        "peak_mem_bytes_replicated": int(replicated),
        "peak_mem_bytes_planned": int(planned),
        "byte_budget": int(budget),
        "fits_budget_only_planned": True,  # asserted above
        "shard_ratio": round(replicated / planned, 3),
        "dp": dp,
        "emb_ep": ep,
    }
    return steps * batch_size / dt, "examples/sec", extras


def bench_deepfm_sparse(steps: int, batch_size: int, amp=None,
                        vocab: int = 100_000, plan=None):
    """DeepFM with ROW-SPARSE embedding updates (the SelectedRows
    capability, reference: operators/optimizers/adam_op.h sparse branch):
    the optimizer touches O(batch x fields) table rows per step instead
    of O(vocab). Run next to --model deepfm (dense updates) — the gap IS
    the sparse-update win, and it widens with total_vocab (``--vocab``
    sweeps the crossover).

    ``--plan ep=8`` (or ``dp=2,ep=4``) switches to the ep-sharded arm:
    tables row-sharded over the plan mesh, sparse (ids, rows) gradient
    exchange, host-backed row prefetch, and the byte-budget gate — see
    :func:`_bench_deepfm_sparse_ep`."""
    if plan:
        return _bench_deepfm_sparse_ep(steps, batch_size, amp, vocab,
                                       plan)
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.models import deepfm as DF
    from paddle_tpu.optimizer.sparse import sparse_minimize_fn

    pt.seed(0)
    cfg = DF.DeepFMConfig(total_vocab=vocab, num_fields=26, dense_dim=13,
                          embed_dim=16, embedding_axis=None,
                          sparse_grads=True)
    model = DF.DeepFM(cfg)
    params = model.named_parameters()
    rng = np.random.default_rng(0)

    import contextlib

    from paddle_tpu.core.dtypes import policy_scope

    def forward_loss(p, ids, dense):
        # honor --amp exactly like _train_bench, so the dense-vs-sparse
        # comparison isolates the update path, not the dtype policy
        with (policy_scope(amp) if amp else contextlib.nullcontext()):
            logits, _ = model.functional_call(p, ids, dense)
            labels = (ids[:, 0] % 2).astype(jnp.float32)
            return DF.loss_fn(logits, labels)

    opt = optimizer.Adam(1e-3)
    init_fn, step_fn = sparse_minimize_fn(model, forward_loss, opt)
    state = init_fn(params)
    ids = jnp.asarray(rng.integers(0, cfg.total_vocab,
                                   (batch_size, cfg.num_fields)))
    dense = jnp.asarray(rng.normal(size=(batch_size, cfg.dense_dim))
                        .astype(np.float32))
    k = max(1, _STEPS_PER_CALL or 1)  # honor --steps-per-call

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, ids, dense):
        if k == 1:
            return step_fn(params, state, ids, dense)

        def body(carry, _):
            p, s = carry
            l, p, s = step_fn(p, s, ids, dense)
            return (p, s), l

        (params, state), ls = jax.lax.scan(body, (params, state), None,
                                           length=k)
        return ls[-1], params, state

    from paddle_tpu.core.profiler import RecordEvent

    dispatch_flops = _ledger_flops("bench.deepfm_sparse.step", step,
                                   params, state, ids, dense)
    for _ in range(3):
        loss, params, state = step(params, state, ids, dense)
    float(loss)
    outer = max(1, steps // k)
    t0 = time.perf_counter()
    for i in range(outer):
        with RecordEvent(f"train_step[{k}]"):
            loss, params, state = step(params, state, ids, dense)
        if i % 4 == 3:
            float(loss)
    float(loss)
    dt = time.perf_counter() - t0
    extras = {"step_time_ms": round(dt / (outer * k) * 1e3, 3)}
    if dispatch_flops:
        extras["flops_per_sec"] = dispatch_flops * outer / dt
        extras.update(ledger_program="bench.deepfm_sparse.step",
                      ledger_dispatches=outer, ledger_window_s=dt)
    return outer * k * batch_size / dt, "examples/sec", extras


def bench_deepfm(steps: int, batch_size: int, amp=None,
                 vocab: int = 100_000):
    """BASELINE config 5: DeepFM sparse CTR step (dense-gradient
    updates; ``--vocab`` scales the table for the sparse crossover)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import deepfm as DF

    pt.seed(0)
    cfg = DF.DeepFMConfig(total_vocab=vocab, num_fields=26, dense_dim=13,
                          embed_dim=16, embedding_axis=None)
    model = DF.DeepFM(cfg)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        ids = jnp.asarray(rng.integers(0, cfg.total_vocab,
                                       (bs, cfg.num_fields)))
        dense = jnp.asarray(rng.normal(size=(bs, cfg.dense_dim))
                            .astype(np.float32))
        return (ids, dense)

    def loss_fn(logits, batch):
        labels = (batch[0][:, 0] % 2).astype(jnp.float32)
        return DF.loss_fn(logits, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_stacked_lstm(steps: int, batch_size: int, amp=None,
                       scan_unroll: int = 1):
    """Bench model 6: stacked dynamic LSTM sentiment (reference:
    benchmark/fluid/models/stacked_dynamic_lstm.py), seq 100.
    ``--scan-unroll K`` unrolls the time recurrence K steps per compiled
    loop body (identical math) — the r3 3.1%-MFU diagnosis was
    batch-starved AND scan-overhead-bound; sweep with --batch-size."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import stacked_lstm as S

    pt.seed(0)
    batch_size = _cap(batch_size, 64)
    model = S.StackedLSTM(vocab_size=5149, embed_dim=512, hidden_dim=512,
                          num_layers=3, scan_unroll=scan_unroll)
    rng = np.random.default_rng(0)
    T = 100

    def make_batch(bs):
        ids = jnp.asarray(rng.integers(0, 5149, (bs, T)))
        lengths = jnp.asarray(rng.integers(T // 2, T + 1, (bs,)))
        return (ids, lengths)

    def loss_fn(logits, batch):
        labels = (batch[0][:, 0] % 2).astype(jnp.int32)
        return S.loss_fn(logits, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_vgg16(steps: int, batch_size: int, smoke: bool = False, amp=None):
    """Bench model: vgg (reference benchmark/fluid/models/vgg.py)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import vgg as V

    pt.seed(0)
    size = 224  # vgg's classifier is fixed to 7x7 feature maps
    batch_size = _cap(batch_size, 2 if smoke else 64)
    model = V.vgg16(num_classes=1000) if hasattr(V, "vgg16") else V.VGG16()
    rng = np.random.default_rng(0)

    def make_batch(bs):
        return (jnp.asarray(rng.normal(size=(bs, 3, size, size))
                            .astype(np.float32)),)

    def loss_fn(logits, batch):
        from paddle_tpu.ops import loss as L

        labels = jnp.zeros((logits.shape[0],), jnp.int32)
        return jnp.mean(L.softmax_with_cross_entropy(logits, labels))

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_se_resnext50(steps: int, batch_size: int, smoke: bool = False,
                       amp=None, layout: str = "NHWC"):
    """Bench model: se_resnext (reference benchmark list). NHWC is the
    TPU-native layout default (r3 measured 9.5% MFU in NCHW — the
    grouped-conv stack is layout-sensitive); pass --layout NCHW to
    compare."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import se_resnext as S

    pt.seed(0)
    size = 64 if smoke else 224
    batch_size = _cap(batch_size, 8 if smoke else 64)
    model = S.se_resnext50(num_classes=1000, data_format=layout)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        return (jnp.asarray(rng.normal(size=(bs, 3, size, size))
                            .astype(np.float32)),)

    def loss_fn(logits, batch):
        from paddle_tpu.ops import loss as L

        labels = jnp.zeros((logits.shape[0],), jnp.int32)
        return jnp.mean(L.softmax_with_cross_entropy(logits, labels))

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_alexnet(steps: int, batch_size: int, smoke: bool = False,
                  amp=None):
    """Legacy comparison family (reference benchmark/figs AlexNet charts)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import alexnet as A

    pt.seed(0)
    batch_size = _cap(batch_size, 8 if smoke else 256)
    model = A.alexnet(num_classes=1000)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        return (jnp.asarray(rng.normal(size=(bs, 3, 224, 224))
                            .astype(np.float32)),)

    def loss_fn(logits, batch):
        labels = jnp.zeros((logits.shape[0],), jnp.int32)
        return A.loss_fn(logits, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_googlenet(steps: int, batch_size: int, smoke: bool = False,
                    amp=None):
    """Legacy comparison family (reference benchmark/figs GoogleNet)."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import googlenet as G

    pt.seed(0)
    batch_size = _cap(batch_size, 8 if smoke else 128)
    model = G.googlenet(num_classes=1000)
    rng = np.random.default_rng(0)

    def make_batch(bs):
        return (jnp.asarray(rng.normal(size=(bs, 3, 224, 224))
                            .astype(np.float32)),)

    def loss_fn(outputs, batch):
        bs = (outputs[0] if isinstance(outputs, tuple) else outputs).shape[0]
        labels = jnp.zeros((bs,), jnp.int32)
        return G.loss_fn(outputs, labels)

    return _train_bench(model, loss_fn, make_batch, steps, batch_size,
                        amp=amp)


def bench_input_pipeline(steps: int, batch_size: int, warmup: int = 3,
                         amp=None):
    """Built-in A/B of the overlapped device input pipeline
    (data/device_loader.py): the SAME jitted train step driven from a
    host-side numpy stream (per-batch rng generation + per-row
    normalization — real input-pipeline host work), once staged
    synchronously in the consumer thread (prefetch OFF) and once through
    a depth-2 DevicePrefetcher background thread (prefetch ON). Every
    step is loss-fenced in BOTH arms, so each arm measures honest
    host+compute wall time per step and the ON/OFF delta is exactly the
    host-work overlap the prefetcher buys. Each arm runs twice and keeps
    its best time (same discipline for both, cancels machine drift).
    ``value`` is the prefetch-ON throughput; extras carry both arms and
    the speedup ratio."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.data.device_loader import DevicePrefetcher
    from paddle_tpu.models import mnist as M

    pt.seed(0)
    batch_size = _cap(batch_size, 256)
    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    trainer = parallel.Trainer.supervised(
        M.MnistMLP(hidden1=512, hidden2=256), optimizer.Adam(1e-3),
        M.loss_fn, mesh=mesh, amp=amp)

    def host_batches(n, seed=0):
        rng = np.random.default_rng(seed)
        for _ in range(n):
            x = rng.normal(size=(batch_size, 784)).astype(np.float32)
            x = (x - x.mean(axis=1, keepdims=True)) / (
                x.std(axis=1, keepdims=True) + 1e-6)
            yield {"x": x, "label": rng.integers(0, 10, batch_size)}

    # FLOPs before the first call donates the trainer state
    probe = next(host_batches(1))
    step_flops = _ledger_flops("bench.input_pipeline.step",
                               trainer._jit_step, trainer.params,
                               trainer.buffers, trainer.opt_state,
                               trainer._rng, probe)
    loss = None
    for b in DevicePrefetcher(lambda: host_batches(max(warmup, 1)),
                              size=0):
        loss, _ = trainer.train_step(b)
    float(loss)

    def run_arm(depth, seed):
        t0 = time.perf_counter()
        for b in DevicePrefetcher(lambda: host_batches(steps, seed),
                                  size=depth):
            loss, _ = trainer.train_step(b)
            float(loss)  # per-step fence — see docstring
        return time.perf_counter() - t0

    # off, on, on, off: mirrored order so slow machine drift hits both
    # arms symmetrically
    dt_off = run_arm(0, seed=1)
    dt_on = min(run_arm(2, seed=2), run_arm(2, seed=3))
    dt_off = min(dt_off, run_arm(0, seed=4))
    value = steps * batch_size / dt_on
    extras = {
        "prefetch_off": round(steps * batch_size / dt_off, 2),
        "prefetch_on": round(value, 2),
        "overlap_speedup": round(dt_off / dt_on, 4),
        "step_time_ms": round(dt_on / steps * 1e3, 3),
    }
    if step_flops:
        extras["flops_per_sec"] = step_flops * steps / dt_on
        extras.update(ledger_program="bench.input_pipeline.step",
                      ledger_dispatches=steps, ledger_window_s=dt_on)
    return value, "examples/sec", extras


def bench_checkpoint(steps: int, batch_size: int, amp=None):
    """Checkpoint save + verified-restore round trips (checkpoint.py +
    the resilience integrity plane): a ~16 MB multi-leaf state is saved
    synchronously (checksummed, COMMITTED-marked, atomic rename) and
    restored through ``CheckpointManager.restore`` — the same
    newest-committed-checksum-valid scan a crash-resumed run takes, so
    ``resume_restore_ms`` IS the recovery latency and lands in the perf
    trajectory. ``value`` is payload throughput over the full round
    trip."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from paddle_tpu.checkpoint import CheckpointManager

    del batch_size  # payload size is the workload, not the batch
    key = jax.random.key(0)
    state = {
        "params": {f"w{i}": jax.random.normal(
            jax.random.fold_in(key, i), (512, 2048), jnp.float32)
            for i in range(3)},
        "opt": {f"m{i}": jnp.zeros((512, 2048), jnp.float32)
                for i in range(1)},
        "step": jnp.asarray(0, jnp.int32),
    }
    payload_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(state))
    root = tempfile.mkdtemp(prefix="pt_bench_ckpt_")
    try:
        mgr = CheckpointManager(root, max_to_keep=2, async_save=False)
        mgr.save(0, state)  # warmup (dir creation, allocator, caches)
        mgr.restore()
        save_s, restore_s = [], []
        for i in range(1, steps + 1):
            t0 = time.perf_counter()
            mgr.save(i, state)
            t1 = time.perf_counter()
            mgr.restore()
            t2 = time.perf_counter()
            save_s.append(t1 - t0)
            restore_s.append(t2 - t1)
        dt = sum(save_s) + sum(restore_s)
        value = payload_bytes * steps * 2 / dt / 1e6  # MB through disk
        extras = {
            "payload_mb": round(payload_bytes / 1e6, 2),
            "save_ms": round(sum(save_s) / steps * 1e3, 3),
            # recovery latency: verified manager restore (checksum scan
            # + newest-committed selection + reassembly)
            "resume_restore_ms": round(sum(restore_s) / steps * 1e3, 3),
            "step_time_ms": round(dt / steps * 1e3, 3),
        }
        # step-agreed save transaction overhead: a 2-rank in-process
        # fleet (file transport) runs the two-phase global commit and
        # commit_barrier_ms is the time from this rank's last shard
        # staged to the fleet-wide COMMITTED marker landing — the
        # transaction's cost on the trend line, separate from raw IO
        import os
        import threading

        from paddle_tpu.resilience import FleetController
        from paddle_tpu.resilience.controller import FileTransport

        froot = os.path.join(root, "fleet")

        def ctl(rank):
            return FleetController(
                rank=rank, world=2, hold_poll_s=0.002,
                ckpt_timeout_s=120.0,
                transport=FileTransport(froot, "bench"))

        m0 = CheckpointManager(os.path.join(root, "ga"),
                               max_to_keep=2, async_save=False,
                               coordinator=ctl(0))
        m1 = CheckpointManager(os.path.join(root, "gb"),
                               max_to_keep=2, async_save=False,
                               coordinator=ctl(1))
        barriers = []
        for i in range(1, min(steps, 4) + 1):
            t = threading.Thread(target=lambda s=i: m1.save(s, state),
                                 name="pt-bench-ckpt-rank1")
            t.start()
            m0.save(i, state)
            t.join()
            barriers.append(m0.last_commit_barrier_s)
        extras["commit_barrier_ms"] = round(
            sum(barriers) / len(barriers) * 1e3, 3)
        return value, "MB/sec", extras
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_sharding_plan(steps: int, batch_size: int, amp=None):
    """OOM-gate bench for the sharding-plan plane (parallel/plan.py): a
    model whose REPLICATED param+opt state exceeds the per-device byte
    budget under dp=1, trained under an fsdp Plan instead. On a real
    chip the budget is HBM and the replicated form simply OOMs; on CPU
    backends (no hard HBM wall) the budget is MEASURED: replicated
    per-device bytes = the full state (every device holds every byte),
    budget = half of that, and the planned per-device footprint must
    come in under it — it lands at ~replicated/fsdp, the evidence the
    acceptance gate asks for. The timed loop is the steady-state planned
    step; one lap runs under the transfer guard (zero resharding
    copies) and the jit cache is pinned to one entry (zero retraces
    after step 1). extras carry both footprints, the budget, and the
    shard ratio."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models import mnist as M
    from paddle_tpu.parallel.plan import (Plan, guard_no_resharding,
                                          max_device_bytes)

    pt.seed(0)
    batch_size = _cap(batch_size, 256)
    n_dev = len(jax.devices())
    fsdp = next((k for k in (8, 4, 2, 1) if k <= n_dev), 1)
    plan = Plan(dp=1, fsdp=fsdp)
    model = M.MnistMLP(hidden1=2048, hidden2=2048)
    trainer = parallel.Trainer.supervised(
        model, optimizer.Adam(1e-3), M.loss_fn, plan=plan, amp=amp)
    state = {"params": trainer.params, "opt": trainer.opt_state}
    # replicated per-device footprint: every device holds every byte
    replicated = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(state))
    planned = max_device_bytes(state)
    budget = replicated // 2
    fits = planned <= budget < replicated

    rng = np.random.default_rng(0)
    assert batch_size >= fsdp > 0, \
        f"batch {batch_size} must be >= fsdp {fsdp}"
    batch_size -= batch_size % fsdp
    sh = trainer.data_sharding()
    batch = {"x": jax.device_put(jnp.asarray(
                 rng.normal(size=(batch_size, 784)).astype(np.float32)),
                 sh),
             "label": jax.device_put(
                 jnp.asarray(rng.integers(0, 10, batch_size)), sh)}
    step_flops = _ledger_flops("bench.sharding_plan.step",
                               trainer._jit_step, trainer.params,
                               trainer.buffers, trainer.opt_state,
                               trainer._rng, batch,
                               n_partitions=plan.num_devices)
    for _ in range(3):
        loss, _ = trainer.train_step(batch)
    float(loss)
    with guard_no_resharding():  # steady state pays no resharding copy
        loss, _ = trainer.train_step(batch)
    float(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss, _ = trainer.train_step(batch)
        if i % 4 == 3:
            float(loss)
    float(loss)
    dt = time.perf_counter() - t0
    assert trainer._jit_step._cache_size() == 1, \
        "planned step retraced after step 1"
    extras = {
        "step_time_ms": round(dt / steps * 1e3, 3),
        "fsdp": fsdp,
        "peak_mem_bytes_replicated": int(replicated),
        "peak_mem_bytes_planned": int(planned),
        "byte_budget": int(budget),
        "fits_budget_only_planned": bool(fits),
        "shard_ratio": round(replicated / planned, 3) if planned else None,
    }
    if step_flops:
        extras["flops_per_sec"] = step_flops * steps / dt
        extras.update(ledger_program="bench.sharding_plan.step",
                      ledger_dispatches=steps, ledger_window_s=dt)
    return steps * batch_size / dt, "examples/sec", extras


def bench_quant_comm(steps: int, batch_size: int, amp=None):
    """Compressed-gradient-allreduce A/B (quant.collectives): the SAME
    pure-DP plan trained with the fp32 ``lax.pmean`` vs the hand-written
    int8 ring psum (``Plan(grad_compression="int8")``), on however many
    devices are up (8-device sim on CPU; real chips on-TPU). Evidence
    the acceptance gate asks for: per-step collective payload bytes
    int8 vs fp32 (counter-verified against
    ``pt_collective_bytes_total{compressed=}``), step time both ways,
    and the TRAJECTORY PARITY GATE — K lockstep steps from one seed
    must keep the loss gap inside tolerance, or the extras say so
    loudly. On ICI-rich single-host sims the ring moves host-memory
    bytes, so step-time parity (not speedup) is the CPU expectation;
    the byte counters are the transferable number."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel, telemetry
    from paddle_tpu.models import mnist as M
    from paddle_tpu.parallel.plan import Plan
    from paddle_tpu.quant.collectives import _comm_metrics

    n_dev = len(jax.devices())
    dp = next((k for k in (8, 4, 2) if k <= n_dev), 0)
    if dp < 2:
        raise RuntimeError(
            f"quant_comm needs >= 2 devices for the allreduce ring, "
            f"got {n_dev} (is the 8-device sim guard stripped?)")
    batch_size = _cap(batch_size, 256)
    # round to the dp grid, never below one row per shard (an explicit
    # --batch-size 4 on the 8-device sim must not become an empty batch)
    batch_size = max(dp, batch_size - batch_size % dp)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.normal(size=(batch_size, 784))
                              .astype(np.float32)),
             "label": jnp.asarray(rng.integers(0, 10, batch_size))}

    def make(comp):
        pt.seed(0)
        model = M.MnistMLP(hidden1=1024, hidden2=1024)
        return parallel.Trainer.supervised(
            model, optimizer.Adam(1e-3), M.loss_fn, amp=amp,
            plan=Plan(dp=dp, grad_compression=comp))

    was_enabled = telemetry.enabled()
    telemetry.enable()  # the byte counters ARE the evidence
    try:
        tr_fp, tr_q = make(None), make("int8")
        # trajectory parity gate: K lockstep steps, one seed, one batch
        parity_steps = 8
        for _ in range(parity_steps):
            l_fp, _ = tr_fp.train_step(batch)
            l_q, _ = tr_q.train_step(batch)
        l_fp, l_q = float(l_fp), float(l_q)
        parity_gap = abs(l_fp - l_q)
        parity_ok = parity_gap <= max(5e-3, 5e-3 * abs(l_fp))
        # counter-verified bytes: the per-step payload each trainer
        # recorded must match what the counters actually advanced by
        m = _comm_metrics()
        c_i8, c_fp = m["bytes_int8"].value, m["bytes_fp32"].value
        warm = parity_steps
        i8_step = sum(tr_q._comm_bytes)
        fp_step = sum(tr_fp._comm_bytes)
        counters_match = (
            abs(c_i8 - tr_q._comm_bytes[0] * warm) < 1
            and abs(c_fp - (tr_fp._comm_bytes[1]
                            + tr_q._comm_bytes[1]) * warm) < 1)

        def timed(tr):
            loss, _ = tr.train_step(batch)
            float(loss)
            t0 = time.perf_counter()
            for i in range(steps):
                loss, _ = tr.train_step(batch)
                if i % 4 == 3:
                    float(loss)
            float(loss)
            return time.perf_counter() - t0

        dt_fp, dt_q = timed(tr_fp), timed(tr_q)
    finally:
        if not was_enabled:
            telemetry.disable()
    ratio = fp_step / i8_step if i8_step else None
    extras = {
        "dp": dp,
        "step_time_ms": round(dt_q / steps * 1e3, 3),
        "step_time_ms_fp32": round(dt_fp / steps * 1e3, 3),
        "comm_bytes_per_step_fp32": int(fp_step),
        "comm_bytes_per_step_int8": int(i8_step),
        "comm_byte_ratio": round(ratio, 3) if ratio else None,
        "comm_counter_verified": bool(counters_match),
        "parity_loss_fp32": round(l_fp, 6),
        "parity_loss_int8": round(l_q, 6),
        "parity_gate": bool(parity_ok),
    }
    return steps * batch_size / dt_q, "examples/sec", extras


MODELS = {
    "mnist_mlp": bench_mnist_mlp,
    "quant_comm": bench_quant_comm,
    "input_pipeline": bench_input_pipeline,
    "checkpoint": bench_checkpoint,
    "sharding_plan": bench_sharding_plan,
    "alexnet": bench_alexnet,
    "googlenet": bench_googlenet,
    "stacked_lstm": bench_stacked_lstm,
    "vgg16": bench_vgg16,
    "se_resnext50": bench_se_resnext50,
    "resnet50": bench_resnet50,
    "bert_base": bench_bert_base,
    "bert_packed": bench_bert_packed,
    "bert_moe": bench_bert_moe,
    "gpt": bench_gpt,
    "vit": bench_vit,
    "bert_long": bench_bert_long,
    "transformer_nmt": bench_transformer_nmt,
    "nmt_decode": bench_nmt_decode,
    "gpt_decode": bench_gpt_decode,
    "gpt_serve": bench_gpt_serve,
    "deepfm": bench_deepfm,
    "deepfm_sparse": bench_deepfm_sparse,
}


def hist_value(entry) -> float:
    """Numeric view of a history entry — dict form ({"value": ...} with
    metadata) or the legacy bare float."""
    return entry["value"] if isinstance(entry, dict) else entry


def run_config_fingerprint(metric: str, args, steps: int):
    """Like-for-like identity + provenance for a history entry.

    Returns ``(config_hash, config)``. The hash covers the WORKLOAD
    identity: the metric key (which already encodes model + every
    workload suffix: _vN/_wN/_nocache/_uN/_layout/_kN/_bN/_dpN/_infer)
    plus the measurement length (``steps`` — a 24-step fast-sweep number
    is noisier than a 100-step one and must never set or mask the
    headline record; it lives under its own ``metric@hash`` variant
    key). Two runs that share a metric key and steps hash identically —
    knob sweeps (remat / amp / fused-ce variants that deliberately
    compete for the headline record under one key) stay comparable. The
    ``config`` dict records the full knob set as provenance so the
    history is never silent about what produced a record (VERDICT r4
    weak #4).
    """
    import hashlib

    workload = {"metric": metric, "dp": args.dp, "steps": steps}
    config_hash = hashlib.sha1(
        json.dumps(workload, sort_keys=True).encode()).hexdigest()[:12]
    config = {
        "model": args.model, "steps": steps,
        # an explicit --batch-size is honored as given; the harness-wide
        # default is clamped per model inside the bench fn (_cap), so
        # the requested value would be provenance fiction — record the
        # truth we have
        "batch": args.batch_size if args.batch_size else "model-default",
        "amp": args.amp, "fused_ce": args.fused_ce, "remat": args.remat,
        "scan_layers": args.scan_layers, "scan_unroll": args.scan_unroll,
        "steps_per_call": args.steps_per_call, "vocab": args.vocab,
        "window": args.window, "kv_cache": args.kv_cache,
        "gamma": args.gamma, "weight_only": args.weight_only,
        "paged": args.paged,
        "router": (args.replicas if getattr(args, "router", False)
                   else None),
        "router_prefill_workers": (
            args.prefill_workers if getattr(args, "router", False)
            else None),
        "router_from_artifact": (
            True if getattr(args, "router", False)
            and getattr(args, "from_artifact", False) else None),
        "router_autoscale": (
            getattr(args, "autoscale", None)
            if getattr(args, "router", False) else None),
        "router_gray_failure": (
            True if getattr(args, "router", False)
            and getattr(args, "gray_failure", False) else None),
        "layout": args.layout, "dp": args.dp, "infer": args.infer,
    }
    # None = knob not set; False values (e.g. --no-fused-ce) are REAL
    # provenance and must stay visible
    config = {k: v for k, v in config.items() if v is not None}
    return config_hash, config


def evaluate_against_history(metric: str, value: float, history: dict, *,
                             on_accelerator: bool, record: bool,
                             device_kind=None, config_hash=None,
                             config=None, now=None):
    """Perf-regression contract: ``vs_baseline`` compares this run to the
    BEST recorded accelerator number for the SAME workload (history keeps
    the max; CPU runs never recorded). Returns (vs_baseline, regression);
    regression = accelerator run >10% below the record — the API.spec
    freeze philosophy applied to throughput. Mutates ``history`` in
    place when ``record`` and ``on_accelerator``.

    Entries are dicts ``{value, ts, device, config_hash, config}``
    (legacy bare floats still read, and are upgraded in place on the
    next record). Like-for-like gate: a run only ever compares against
    and updates an entry whose ``device`` and ``config_hash`` match its
    own. A mismatched run is NOT silently compared (vs_baseline 1.0, no
    regression flag) and records NON-destructively under the variant key
    ``metric@config_hash`` — the headline record keeps its key, so an
    alternating pair of configs can neither demote the true record nor
    mask a later real regression against it. Legacy floats carry no
    metadata; they were by construction 100-step headline chip runs
    (CPU was never recorded), so they baseline only runs whose measured
    length is the headline default."""
    def _matches(entry):
        if not isinstance(entry, dict) or entry.get("legacy"):
            # legacy bare float (or its dict upgrade) — a full-length
            # headline chip number with unknown knob provenance: it
            # baselines only headline-length runs
            return (config or {}).get("steps") in (None, HEADLINE_STEPS)
        pd, ph = entry.get("device"), entry.get("config_hash")
        if pd is not None and device_kind is not None and pd != device_kind:
            return False
        if ph is not None and config_hash is not None and ph != config_hash:
            return False
        return True

    variant_key = f"{metric}@{config_hash}" if config_hash else None
    # third tier: device-qualified variant, so runs from two chip
    # generations each keep (and regress against) their OWN record
    # instead of thrashing one key through _superseded
    device_key = (f"{variant_key}@{device_kind}"
                  if variant_key and device_kind else None)
    baseline_key, prev_entry = None, None
    for key in filter(None, (metric, variant_key, device_key)):
        entry = history.get(key)
        if entry is not None and _matches(entry):
            baseline_key, prev_entry = key, entry
            break
    prev = hist_value(prev_entry) if prev_entry is not None else None
    vs_baseline = (value / prev) if prev else 1.0
    regression = bool(on_accelerator and prev and value < 0.9 * prev)
    if record and on_accelerator:
        if prev is not None and prev >= value:
            # the record stands, keeping the metadata of the run that
            # set it; bare legacy floats get a minimal dict upgrade
            if not isinstance(prev_entry, dict):
                history[baseline_key] = {"value": prev, "legacy": True}
        else:
            entry = {"value": value}
            if now:
                entry["ts"] = now
            if device_kind:
                entry["device"] = device_kind
            if config_hash:
                entry["config_hash"] = config_hash
            if config:
                entry["config"] = config
            if baseline_key is not None:
                target = baseline_key  # beat a matching record in place
            else:
                # headline-config runs own the bare metric key when it
                # is free; anything else takes the first vacant variant
                # tier (config, then config@device). All tiers occupied
                # by mismatched entries can only mean scheme drift —
                # archive the most specific one, never drop it.
                headline = (config or {}).get("steps") in (None, HEADLINE_STEPS)
                candidates = ([metric] if headline else []) + list(
                    filter(None, (variant_key, device_key)))
                vacant = [k for k in candidates if k not in history]
                target = vacant[0] if vacant else (
                    candidates[-1] if candidates else metric)
                old = history.get(target)
                if old is not None:
                    history.setdefault("_superseded", []).append(
                        {"metric": target, "entry": old})
            history[target] = entry
    return vs_baseline, regression


def _emit_error(metric: str, msg: str) -> None:
    """One-JSON-line driver contract, argument-MISUSE form: a
    deterministic caller error keeps the value-0.0 shape (it could never
    have produced a number and never enters history)."""
    print(json.dumps({"metric": metric, "value": 0.0,
                      "unit": "examples/sec", "vs_baseline": 0.0,
                      "backend": None, "mfu": None, "step_time_ms": None,
                      "peak_mem_bytes": None, "error": msg}))


class _SkipBench(Exception):
    """Raised by a bench fn when the ENVIRONMENT (not the workload)
    makes the measurement impossible mid-run — e.g. the aot artifact
    failed to export/load. main() converts it into the ``skipped``
    JSON line via :func:`_emit_skip`; a fabricated 0.0 (or a fake TTFR)
    would read as a real measurement and poison the trend history."""

    def __init__(self, msg: str, cause: str = None):
        super().__init__(msg)
        self.cause = cause


def _emit_skip(metric: str, msg: str, cause: str = None) -> None:
    """One-JSON-line driver contract, INFRA-error form: the workload is
    fine but the environment failed (too few devices, profiler
    unsupported). Emits ``"skipped": true`` with the error and NO value
    key — a 0.0 row here would read as a real measurement and drag
    BENCH_HISTORY trend plots to zero — then EXITS NON-ZERO: a run that
    measured nothing is a failure to its caller. ``cause`` stamps a
    stable machine-readable reason (e.g. ``insufficient_devices``) so
    trend tooling can bucket degraded rounds without parsing prose."""
    line = {"metric": metric, "skipped": True,
            # infra-degraded row: trend tooling must not
            # fold it into deltas
            "backend_degraded": True,
            "peak_mem_bytes": None, "error": msg}
    if cause:
        line["cause"] = cause
    print(json.dumps(line))
    sys.stdout.flush()
    sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mnist_mlp", choices=sorted(MODELS))
    ap.add_argument("--smoke", action="store_true", help="quick run")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--layout", default=None,
                    help="conv data format for models that support it "
                    "(NHWC default on resnet)")
    ap.add_argument("--fused-ce", dest="fused_ce", default=True,
                    action="store_true",
                    help="bert/nmt: chunked linear-CE head (the default "
                    "measured configuration; pass --no-fused-ce for the "
                    "legacy full-logits path)")
    ap.add_argument("--no-fused-ce", dest="fused_ce", action="store_false")
    ap.add_argument("--remat", nargs="?", const="full", default=None,
                    choices=["full", "dots"],
                    help="bert: jax.checkpoint per transformer block; "
                    "'dots' saves matmul outputs and recomputes only the "
                    "elementwise tail (less recompute, more HBM)")
    ap.add_argument("--scan-layers", dest="scan_layers",
                    action="store_true",
                    help="bert: lax.scan over the layer stack (dropout "
                    "forced to 0)")
    ap.add_argument("--scan-unroll", dest="scan_unroll", type=int,
                    default=None,
                    help="stacked_lstm: unroll the time-recurrence scan "
                    "K steps per compiled loop body (identical math)")
    ap.add_argument("--amp", default="mixed_bf16",
                    help="dtype policy for the step (mixed_bf16 is the TPU "
                    "training default; pass float32 to disable)")
    ap.add_argument("--steps-per-call", dest="steps_per_call", type=int,
                    default=None,
                    help="fuse K update steps per dispatch (lax.scan; "
                    "identical math). Default: model-specific (mnist 8, "
                    "others 1)")
    ap.add_argument("--profile", default=None, metavar="TRACE_JSON",
                    help="wrap the timed run in the profiler and write a "
                    "chrome-trace JSON here (fluid_benchmark --profile "
                    "analog)")
    ap.add_argument("--device-trace", dest="device_trace", default=None,
                    metavar="DIR",
                    help="wrap the timed run in jax.profiler.trace(DIR): "
                    "captures DEVICE-side op timelines (xplane.pb, "
                    "TensorBoard-consumable) — the device_tracer.h half "
                    "of the profiler capability; fails loudly if the "
                    "PJRT plugin exposes no profiler")
    ap.add_argument("--vocab", type=int, default=None,
                    help="deepfm/deepfm_sparse: embedding table size "
                    "(sweeps the sparse-vs-dense update crossover)")
    ap.add_argument("--window", type=int, default=None,
                    help="bert_long: sliding-window attention width "
                    "(O(T*W) local attention vs the O(T^2) default)")
    ap.add_argument("--paged", action="store_true",
                    help="gpt_serve: paged-KV arena (page pool sized "
                    "to ~half the dense slots x capacity)")
    ap.add_argument("--kv-dtype", dest="kv_dtype", default=None,
                    choices=("int8",),
                    help="gpt_serve: quantized paged KV pool (implies "
                    "--paged; int8 values + per-vector scales — "
                    "~3.7x pages per HBM byte) plus the max-sessions "
                    "density A/B and greedy parity extras")
    ap.add_argument("--router", action="store_true",
                    help="gpt_serve: the production-serving A/B — "
                    "multi-replica router + prefill/decode "
                    "disaggregation + SLO shed under a seeded Poisson "
                    "open-loop load (p50/p99 TTFT, p99 ITL, aggregate "
                    "tok/s, shed rate; _routerN history key)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="--router: decode replica count")
    ap.add_argument("--prefill-workers", dest="prefill_workers",
                    type=int, default=1,
                    help="--router: dedicated prefill workers (0 = "
                    "no disaggregation)")
    ap.add_argument("--overload", type=float, default=2.0,
                    help="--router: overload factor for the shed arm")
    ap.add_argument("--router-procs", dest="router_procs",
                    action="store_true",
                    help="--router: replicas as real worker processes "
                    "over HTTP instead of in-process threads")
    ap.add_argument("--stream", action="store_true",
                    help="--router: add the per-token STREAMING arm "
                    "(router-side first-token TTFT + client-side "
                    "inter-token-latency columns) and the "
                    "prefix-hash vs session-only routing hit-rate "
                    "A/B to the same JSON line")
    ap.add_argument("--autoscale", default=None, metavar="MIN,MAX",
                    help="--router: replace the disagg arms with the "
                    "autoscaling spike A/B — static MAX fleet vs a "
                    "Scaler-driven fleet growing from MIN on a "
                    "seeded 3x spike and draining back on sustained "
                    "headroom, gated on SLO at strictly fewer "
                    "replica-seconds")
    ap.add_argument("--gray-failure", dest="gray_failure",
                    action="store_true",
                    help="--router: replace the disagg arms with the "
                    "gray-failure reliability A/B — one replica "
                    "wedged ~10x slow (seeded replica.wedge delay), "
                    "clean vs reliability-off vs reliability-on arms; "
                    "gates quarantine + bounded p99 TTFT with the "
                    "plane on (_gray history key)")
    ap.add_argument("--from-artifact", dest="from_artifact",
                    action="store_true",
                    help="--router: add the AOT cold-start A/B — "
                    "export the replica's compiled programs "
                    "(paddle_tpu.aot) and boot a second replica "
                    "trace-free from the artifact; reports "
                    "ttfr_traced_ms vs ttfr_aot_ms and GATES "
                    "ttfr_aot < ttfr_traced (_aot history key)")
    ap.add_argument("--prefill-chunk", dest="prefill_chunk", type=int,
                    default=None,
                    help="gpt_serve: chunked prefill — C prompt tokens "
                    "per serving tick instead of whole-prompt "
                    "admission stalls (_pcN history key)")
    ap.add_argument("--decode-steps", dest="decode_steps", type=int,
                    default=None,
                    help="gpt_serve: k tokens per serving dispatch "
                    "(in-device picks; token-identical to k=1) — "
                    "amortizes the per-dispatch round trip (_dsN key)")
    ap.add_argument("--weight-only", dest="weight_only",
                    action="store_true",
                    help="gpt_decode/gpt_serve: weight-only int8 "
                    "(W8A16) on the model's matmuls (_w8 history key)")
    ap.add_argument("--gamma", type=int, default=None,
                    help="gpt_decode: speculative-decoding draft length "
                    "(0/unset = plain greedy decode)")
    ap.add_argument("--no-kv-cache", dest="kv_cache", action="store_false",
                    help="nmt_decode: full-prefix re-run decode instead "
                    "of the K/V-cached step (same tokens; the honest "
                    "baseline for the cache win)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel device count (--gpus analog; on "
                    "--platform cpu this creates virtual host devices)")
    ap.add_argument("--plan", default=None, metavar="AXES",
                    help="deepfm_sparse: sharding plan for the embedding "
                    "tables, e.g. 'ep=8' or 'dp=2,ep=4' — tables "
                    "row-shard over the ep mesh axis with sparse "
                    "(ids, rows) gradient exchange and the byte-budget "
                    "gate (on cpu the dp*ep virtual devices are created "
                    "automatically)")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--infer", action="store_true",
                    help="inference mode: jitted forward only, reports "
                    "examples/sec + p50/p99 latency (the reference's "
                    "inference/tests/api latency-harness role)")
    args = ap.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
        if args.dp > 1 and args.platform == "cpu":
            jax.config.update("jax_num_cpu_devices", args.dp)

    steps = args.steps or (10 if args.smoke else HEADLINE_STEPS)
    batch = args.batch_size or (256 if args.smoke else 8192)
    global _EXPLICIT_BATCH
    _EXPLICIT_BATCH = bool(args.batch_size)  # assignment: a second
    # in-process main() without --batch-size gets the caps back

    # Resolve the workload-suffixed metric key ONCE, before any code
    # that can fail: error lines must carry the same key as the success
    # line for the same command, or retry/history tooling mis-files the
    # failure under a different workload. inspect on the local bench fn
    # is safe here (nothing touches the device).
    import inspect

    global _MODE
    _MODE = "infer" if args.infer else "train"
    fn = MODELS[args.model]
    if args.stream and not args.router:
        _emit_error(f"{args.model}_throughput",
                    "--stream only applies with --router "
                    "(gpt_serve streaming arm)")
        return
    if args.from_artifact and not args.router:
        _emit_error(f"{args.model}_throughput",
                    "--from-artifact only applies with --router "
                    "(the aot cold-start A/B)")
        return
    autoscale = None
    if args.autoscale:
        if not args.router:
            _emit_error(f"{args.model}_throughput",
                        "--autoscale only applies with --router "
                        "(the elastic-fleet spike A/B)")
            return
        if args.stream or args.from_artifact or args.router_procs:
            _emit_error(f"{args.model}_throughput",
                        "--autoscale is its own workload: drop "
                        "--stream/--from-artifact/--router-procs")
            return
        try:
            amin, amax = (int(x) for x in args.autoscale.split(","))
        except ValueError:
            _emit_error(f"{args.model}_throughput",
                        f"--autoscale wants MIN,MAX integers, got "
                        f"{args.autoscale!r}")
            return
        if not 1 <= amin < amax:
            _emit_error(f"{args.model}_throughput",
                        f"--autoscale needs 1 <= MIN < MAX, got "
                        f"{amin},{amax}")
            return
        autoscale = (amin, amax)
    if args.gray_failure:
        if not args.router:
            _emit_error(f"{args.model}_throughput",
                        "--gray-failure only applies with --router "
                        "(the reliability A/B)")
            return
        if (args.stream or args.from_artifact or args.router_procs
                or autoscale):
            _emit_error(f"{args.model}_throughput",
                        "--gray-failure is its own workload: drop "
                        "--stream/--from-artifact/--router-procs/"
                        "--autoscale")
            return
    if args.router:
        if args.model != "gpt_serve":
            _emit_error(f"{args.model}_throughput",
                        "--router only applies to --model gpt_serve")
            return
        fn = bench_gpt_router
    sig = inspect.signature(fn).parameters
    metric = (f"{args.model}_infer_throughput" if args.infer
              else f"{args.model}_throughput")
    if args.router:
        # the router A/B is its own WORKLOAD (open-loop Poisson load,
        # multi-replica topology): one history key per replica count
        metric += f"_router{args.replicas}"
        if args.router_procs:
            metric += "_procs"
        if args.stream:
            # the streaming arm changes the measured columns (stream
            # TTFT/ITL + the prefix-routing A/B): its own history key
            metric += "_stream"
        if args.from_artifact:
            # the AOT A/B adds the TTFR columns + its gate: own key
            metric += "_aot"
        if autoscale:
            # the elastic-fleet spike A/B is its own workload
            # (piecewise rate, fleet size varies): own key per band
            metric += f"_as{autoscale[0]}x{autoscale[1]}"
        if args.gray_failure:
            # the gray-failure A/B is its own workload (wedged
            # replica, three arms): own key
            metric += "_gray"
    if (args.vocab and "vocab" in sig
            and args.vocab != sig["vocab"].default):
        metric += f"_v{args.vocab}"
    if args.window and "window" in sig:
        # a window changes the WORKLOAD (different attention math):
        # its history key must not collide with the full-attention one
        metric += f"_w{args.window}"
    if args.gamma is not None and args.gamma < 0:
        # a negative value would fall back to greedy inside the bench fn
        # while recording under a speculative _gN key — refuse instead
        _emit_error(metric, f"--gamma must be >= 1, got {args.gamma}")
        return
    if args.gamma and "gamma" in sig:
        # speculative decode is a different WORKLOAD (draft model in the
        # loop): its own history key per gamma
        metric += f"_g{args.gamma}"
    if args.weight_only and "weight_only" in sig:
        # same workload, different weight storage — own history key so
        # the W8A16-vs-bf16 comparison stays visible
        metric += "_w8"
    if args.paged and "paged" in sig:
        # different cache layout (page pool vs dense arena): own key
        metric += "_paged"
    if args.kv_dtype and "kv_dtype" in sig:
        # different KV storage form (quantized page pool): own key so
        # the density-vs-precision trade stays visible next to fp32
        metric += f"_kv{args.kv_dtype}"
    if args.prefill_chunk and "prefill_chunk" in sig:
        # different admission schedule (prefill interleaved with
        # decode): own key per chunk size
        metric += f"_pc{args.prefill_chunk}"
    if (args.decode_steps and args.decode_steps > 1
            and "decode_steps" in sig):
        # same workload, fused dispatch — own key so the RTT
        # amortization stays visible next to the k=1 row (--decode-steps
        # 1 IS the baseline: no key fork, mirrors --gamma 0)
        metric += f"_ds{args.decode_steps}"
    if "cached" in sig and not args.kv_cache:
        # same workload, different implementation — its own history key
        # so the cache-vs-recompute comparison stays visible
        metric += "_nocache"
    if (args.scan_unroll and "scan_unroll" in sig
            and args.scan_unroll != sig["scan_unroll"].default):
        # same math, different compiled loop body — own key for the sweep
        metric += f"_u{args.scan_unroll}"
    if args.layout and "layout" in sig and args.layout != sig["layout"].default:
        metric += f"_{args.layout.lower()}"
    if args.steps_per_call:
        # a dispatch-fusion factor that DIFFERS from the model's headline
        # default is a sweep point: its own history key. Passing the
        # model's own default explicitly (e.g. mnist --steps-per-call 8)
        # must not fork the history of an identical configuration —
        # mirror the scan-unroll pattern and compare against the bench
        # signature's default (1 for models routed via _train_bench).
        _k_default = (sig["steps_per_call"].default
                      if "steps_per_call" in sig else 1)
        if not isinstance(_k_default, int):
            _k_default = 1
        if args.steps_per_call != _k_default:
            metric += f"_k{args.steps_per_call}"
    if _EXPLICIT_BATCH:
        metric += f"_b{batch}"
    if args.dp > 1:
        # data-parallel width changes the WORKLOAD (global batch shards
        # over dp devices): its own history key, never silently compared
        # against the single-device record
        metric += f"_dp{args.dp}"
    plan_axes = None
    if args.plan:
        if args.model != "deepfm_sparse" or "plan" not in sig:
            _emit_error(metric, "--plan only applies to --model "
                        "deepfm_sparse (the ep-sharded embedding arm)")
            return
        if args.infer:
            _emit_error(metric, "--infer does not support --plan "
                        "(the ep arm measures the sparse train step)")
            return
        if args.dp > 1:
            _emit_error(metric, "--plan carries its own dp axis "
                        "(use --plan dp=M,ep=N, not --dp)")
            return
        try:
            plan_axes = _parse_plan_arg(args.plan)
        except ValueError as e:
            _emit_error(metric, str(e))
            return
        # the plan shape is the WORKLOAD (mesh axes + exchange
        # topology): its own history key, e.g. _ep8 or _dp2_ep4
        metric += "_" + args.plan.replace("=", "").replace(",", "_")
    if args.infer and args.model == "deepfm_sparse":
        # sparse_grads only changes the UPDATE path; the forward is
        # identical to deepfm's — bench that instead of duplicating it
        _emit_error(metric, "--infer: use --model deepfm (the sparse "
                    "variant differs only in the optimizer update)")
        return
    if args.infer and args.model == "input_pipeline":
        # the A/B measures the TRAIN step under both staging modes; an
        # --infer run would silently measure training under an infer key
        _emit_error(metric, "--infer: input_pipeline A/Bs the train "
                    "step; run it without --infer")
        return
    if args.infer and args.model == "gpt_serve":
        _emit_error(metric, "--infer: --model gpt_serve already measures "
                    "inference serving; run it without --infer")
        return
    if args.infer and args.model == "gpt_decode":
        _emit_error(metric, "--infer: --model gpt_decode already measures "
                    "inference decode; run it without --infer")
        return
    if args.infer and args.model == "nmt_decode":
        # the decode bench IS an inference workload; an --infer run would
        # duplicate it under a second metric key and fork its history
        _emit_error(metric, "--infer: --model nmt_decode already measures "
                    "inference decode; run it without --infer")
        return
    if args.infer and args.model == "bert_packed":
        # packing is a training-batch layout; the pretraining head's
        # plain forward takes no segment_ids, so an infer run would
        # silently measure the UNPACKED attention path under a packed
        # label
        _emit_error(metric, "--infer: use --model bert_base (packing is "
                    "a training-batch layout)")
        return

    if args.model == "quant_comm" or plan_axes:
        # the allreduce ring / the plan mesh needs devices: give a
        # cpu-only run the device sim BEFORE backend init (accelerator
        # backends ignore the cpu device count — on-chip runs use the
        # real devices)
        import jax

        n_sim = (plan_axes["dp"] * plan_axes["ep"]) if plan_axes else 8
        jax.config.update("jax_num_cpu_devices", n_sim)

    # Persistent compilation cache: amortizes the slow first compile
    # across bench processes (the knob sweep re-lowers near-identical
    # modules) and lets the AOT compile inside lowered_flops' fallback be
    # reused by the timed dispatch of the same module.
    from paddle_tpu.utils.flops import enable_compile_cache

    enable_compile_cache()
    kwargs = {}
    if plan_axes:
        import jax

        need = plan_axes["dp"] * plan_axes["ep"]
        if len(jax.devices()) < need:
            # infra shape, not argument misuse: the workload is fine but
            # this host/backend cannot field the mesh (e.g. a 4-chip
            # slice asked for ep=8) — skipped row, never a 0.0 value
            _emit_skip(metric,
                       f"--plan {args.plan} needs {need} devices, "
                       f"have {len(jax.devices())}",
                       cause="insufficient_devices")
        kwargs["plan"] = args.plan
    if "smoke" in sig:
        kwargs["smoke"] = args.smoke
    if "amp" in sig and args.amp and args.amp != "float32":
        kwargs["amp"] = args.amp
    if "layout" in sig and args.layout:
        kwargs["layout"] = args.layout
    if "fused_ce" in sig:
        kwargs["fused_ce"] = args.fused_ce
    if "remat" in sig and args.remat:
        kwargs["remat"] = args.remat
    if "scan_layers" in sig and args.scan_layers:
        kwargs["scan_layers"] = True
    if "scan_unroll" in sig and args.scan_unroll:
        kwargs["scan_unroll"] = args.scan_unroll
    if "vocab" in sig and args.vocab:
        kwargs["vocab"] = args.vocab
    if "window" in sig and args.window:
        kwargs["window"] = args.window
    if "cached" in sig:
        kwargs["cached"] = args.kv_cache
    if args.gamma and "gamma" in sig:
        kwargs["gamma"] = args.gamma
    if args.weight_only and "weight_only" in sig:
        kwargs["weight_only"] = True
    if args.paged and "paged" in sig:
        kwargs["paged"] = True
    if args.kv_dtype and "kv_dtype" in sig:
        kwargs["kv_dtype"] = args.kv_dtype
    if args.router:
        kwargs["replicas"] = args.replicas
        kwargs["prefill_workers"] = args.prefill_workers
        kwargs["overload"] = args.overload
        kwargs["router_procs"] = args.router_procs
        kwargs["stream"] = args.stream
        kwargs["from_artifact"] = args.from_artifact
        kwargs["autoscale"] = autoscale
        kwargs["gray_failure"] = args.gray_failure
    if args.prefill_chunk and "prefill_chunk" in sig:
        kwargs["prefill_chunk"] = args.prefill_chunk
    if (args.decode_steps and args.decode_steps > 1
            and "decode_steps" in sig):
        kwargs["decode_steps"] = args.decode_steps
    if args.steps_per_call:
        if "steps_per_call" in sig:
            kwargs["steps_per_call"] = args.steps_per_call
        else:
            global _STEPS_PER_CALL
            _STEPS_PER_CALL = args.steps_per_call
    if args.dp > 1:
        if args.infer:
            # bench_mnist_mlp would otherwise build the dp mesh and then
            # silently measure a single-device forward under a metric
            # name that carries no dp marker
            _emit_error(metric, "--infer does not support --dp "
                        "(inference bench is single-device)")
            return
        if "dp" not in sig:
            _emit_error(metric,
                        f"--dp is not supported by model {args.model} "
                        "(single-device bench)")
            return
        kwargs["dp"] = args.dp
    import contextlib

    if args.profile:
        # fail on an unwritable path BEFORE the (possibly long) run,
        # keeping the one-JSON-line contract
        try:
            with open(args.profile, "w"):
                pass
        except OSError as e:
            _emit_error(metric,
                        f"unwritable --profile path: {e}")
            return
        from paddle_tpu.core.profiler import profiler as _prof

        ctx = _prof(timeline_path=args.profile)
    else:
        ctx = contextlib.nullcontext()
    if args.device_trace:
        import jax

        dctx = jax.profiler.trace(args.device_trace)
    else:
        dctx = contextlib.nullcontext()
    with ctx, dctx:
        try:
            value, unit, *rest = fn(steps, batch, **kwargs)
        except _SkipBench as e:
            _emit_skip(metric, str(e), cause=e.cause)
    extras = rest[0] if rest else {}
    if args.device_trace:
        # the artifact contract: at least one non-trivial xplane proto
        # must exist, or the run errors (an empty dir would let the
        # fill item mark "device trace captured" on a no-op)
        import glob as _glob

        planes = [p for p in _glob.glob(os.path.join(
            args.device_trace, "**", "*.xplane.pb"), recursive=True)
            if os.path.getsize(p) > 1024]
        if not planes:
            _emit_skip(metric, "device trace produced no xplane.pb "
                       "(PJRT profiler unsupported on this platform?)")
        extras["device_trace_planes"] = [
            {"file": os.path.relpath(p, args.device_trace),
             "bytes": os.path.getsize(p)} for p in planes]

    # `metric` was resolved up front (same suffixed key on error and
    # success lines for the same command)
    hist_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_HISTORY.json")
    config_hash, run_config = run_config_fingerprint(metric, args, steps)
    line = report_line(metric, value, unit, extras,
                       history_path=hist_path, smoke=args.smoke,
                       dp=args.dp, config_hash=config_hash,
                       run_config=run_config)
    print(json.dumps(line))


def report_line(metric, value, unit, extras, *, history_path, smoke,
                dp=1, device=None, config_hash=None, run_config=None):
    """Post-run reporting: history recording + regression contract + MFU.

    Separated from main() so the ACCELERATOR code path (history writes,
    regression warnings, MFU vs the peak table) is exercised by tests
    with a stand-in device BEFORE the first real chip session — the
    machinery must not meet hardware for the first time in production
    (VERDICT r2 'first on-chip session will shake out bugs' risk).
    ``device`` defaults to jax.devices()[0].
    """
    history = {}
    if os.path.exists(history_path):
        try:
            with open(history_path) as f:
                history = json.load(f)
        except Exception:
            history = {}
    if device is None:
        import jax

        device = jax.devices()[0]

    on_accelerator = device.platform != "cpu"
    import datetime

    vs_baseline, regression = evaluate_against_history(
        metric, value, history, on_accelerator=on_accelerator,
        record=not smoke,
        device_kind=getattr(device, "device_kind", None) or device.platform,
        config_hash=config_hash, config=run_config,
        now=datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"))
    if regression:
        # the baseline may live under a variant key; recover its value
        # from the ratio rather than assuming history[metric] holds it
        # (guarded: a 0.0 value yields vs_baseline 0.0)
        prev_str = (f"{value / vs_baseline:.2f}" if vs_baseline > 0
                    else "recorded baseline")
        print(f"WARNING: {metric} regressed >10% vs best recorded "
              f"({value:.2f} vs {prev_str} {unit})", file=sys.stderr)
    # regression-sentinel tie-in: arm from the LAST session's recorded
    # timings (the reserved "_sentinel" history section — underscore
    # keys never collide with metric names, the _superseded precedent),
    # feed this run's measured step time, and persist the updated
    # baselines back. A fresh bench session alarms on step-time drift
    # against the previous session instead of needing min_samples
    # warmup runs of its own.
    from paddle_tpu.telemetry import profiling as _profiling

    _profiling.seed_sentinel_from_history(history_path)
    perf_diag = None
    st_ms = extras.get("step_time_ms")
    if st_ms:
        perf_diag = _profiling.sentinel().observe(
            metric, device.platform, float(st_ms) / 1e3)
    if not smoke and on_accelerator:
        history[_profiling.SENTINEL_HISTORY_KEY] = (
            _profiling.sentinel_history_entry())
        # CPU debug runs never pollute the recorded trajectory
        with open(history_path, "w") as f:
            json.dump(history, f, indent=1)

    line = {"metric": metric, "value": round(value, 2), "unit": unit,
            "vs_baseline": round(vs_baseline, 4),
            # backend on EVERY line so a reader never has to infer
            # which hardware a number came from
            "backend": device.platform,
            # fenced wall time per step/dispatch — the denominator the
            # mfu field divides FLOPs by; None when a bench predates it
            "step_time_ms": extras.get("step_time_ms")}
    # device-memory high-water mark of the run (telemetry.diag monitor):
    # null where the backend has no memory_stats() (CPU) — the
    # live-array fallback is an allocation view, never a peak, and must
    # not masquerade as one in recorded numbers
    try:
        from paddle_tpu.telemetry.diag import peak_memory_bytes

        line["peak_mem_bytes"] = peak_memory_bytes()
    except Exception:
        line["peak_mem_bytes"] = None
    # MFU: model FLOP/s (XLA cost model over the lowered step) / chip peak.
    # Reported only when both sides are known (never on CPU).
    from paddle_tpu.utils.flops import mfu as _mfu

    # latency percentiles from the inference harness, the
    # speculative-decode acceptance stats, the input-pipeline A/B
    # numbers, and the sharding-plan byte-budget evidence ride along
    # verbatim
    line.update({k: v for k, v in extras.items()
                 if k.startswith(("latency_ms_", "comm_", "parity_",
                                  "kv_", "max_sessions_",
                                  # router serving A/B: TTFT/ITL
                                  # percentiles, shed rates, and the
                                  # mono/overload comparison arms (+
                                  # the streaming arm, the prefix-hash
                                  # routing A/B, and the aot TTFR
                                  # cold-start A/B columns)
                                  "ttft_", "itl_", "mono_",
                                  "stream_", "prefix_", "ttfr_",
                                  # autoscale plane: replica-seconds
                                  # accounting + fleet timelines on
                                  # every router row; the spike A/B's
                                  # static-arm comparison columns and
                                  # scale-event/TTFR evidence
                                  "replica_", "autoscale_",
                                  "static_", "spike_",
                                  # reliability plane: the
                                  # gray-failure A/B's three-arm
                                  # comparison + breaker evidence
                                  "gray_",
                                  # sharded-embedding plane: wire
                                  # payload vs dense counterfactual,
                                  # host-cache hit rate, table rows
                                  "overload_", "emb_"))
                 or k in ("aot_artifact_id",
                          "accept_per_round", "rounds", "prefetch_off",
                          "prefetch_on", "overlap_speedup", "fsdp",
                          # checkpoint bench: save/recovery latency and
                          # the step-agreed transaction's barrier cost
                          "save_ms", "resume_restore_ms",
                          "commit_barrier_ms", "payload_mb",
                          "peak_mem_bytes_replicated",
                          "peak_mem_bytes_planned", "byte_budget",
                          "fits_budget_only_planned", "shard_ratio",
                          "session_ratio", "step_time_ms_fp32", "dp",
                          "shed_rate", "replicas", "prefill_workers",
                          "rate_rps",
                          # performance-attribution plane: fraction of
                          # serving capacity that emitted tokens
                          "goodput_ratio")})
    flops_per_sec = extras.get("flops_per_sec")
    line["mfu"] = None
    if flops_per_sec:
        line["tflops_per_sec"] = round(flops_per_sec / 1e12, 3)
        m = _mfu(flops_per_sec, device, n_devices=max(1, dp))
        if m is not None:
            line["mfu"] = round(m, 4)
    # Ledger-derived columns (performance-attribution plane): the
    # roofline verdict rides straight from the cost-registry record,
    # and the mfu above is AUDITED against it — the numerator must
    # equal ledger FLOPs x scale x dispatches / window or the row
    # refuses to print an mfu at all (``mfu_audit`` says why). A bench
    # whose flops source drifts from the registry can't quietly ship a
    # hand-rolled utilization number.
    prog = extras.get("ledger_program")
    if prog:
        rec = None
        try:
            from paddle_tpu.telemetry import costs as _tcosts

            rec = _tcosts.get(prog)
        except Exception:
            pass
        rl = (rec or {}).get("roofline") or {}
        if rl.get("verdict"):
            line["roofline"] = rl["verdict"]
        n_disp = extras.get("ledger_dispatches")
        window = extras.get("ledger_window_s")
        if flops_per_sec and n_disp and window:
            rec_flops = (rec or {}).get("flops")
            if not rec_flops:
                line["mfu"] = None
                line["mfu_audit"] = "no_ledger_record"
            else:
                expected = (rec_flops
                            * float(extras.get("ledger_scale") or 1.0)
                            * n_disp / window)
                if abs(expected - flops_per_sec) <= 0.02 * expected:
                    line["mfu_audit"] = "ledger"
                else:
                    line["mfu"] = None
                    line["mfu_audit"] = "ledger_mismatch"
    if regression:
        line["regression"] = True
    if perf_diag is not None:
        # the sentinel's step-TIME alarm rides the JSON line next to
        # the throughput regression flag (different denominators — a
        # batch-size change can move one without the other)
        line["perf_regression"] = str(perf_diag)
    return line


if __name__ == "__main__":
    main()
