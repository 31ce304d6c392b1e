#!/usr/bin/env python3
"""Where a loop over stacked blocks should cast: ms a step on the chip.

    chiprun -- python3 tools/loop_cast.py

``pipeline_apply``'s one-stage fold (a scan over 8 stacked, rematted
gated-MLP blocks at ``internlm2-1.8b``'s widths, the body a pipeline's
stage or ``scan_layers`` runs) under ``mixed_bf16``, ``value_and_grad``,
three ways, two readings each, at a product-bound and a weight-bound
row count:

each_use   the program before PR 46: every Linear converts its float32
           slice at each use
in_body    what the tree runs: the block's functional_call casts the
           slice in the body (nn.Layer._cast_once)
before     the stack cast once before the scan (tried in PR 46's review
           round and dropped: PERF.md section 6)
"""
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core.dtypes import policy_scope
from paddle_tpu.nn.layer import Layer, stacked_parameters
from paddle_tpu.parallel.pipeline import pipeline_apply

H, F, L = 2048, 8192, 8


class Block(Layer):
    def __init__(self):
        super().__init__()
        self.norm = nn.RMSNorm(H)
        self.gate = nn.Linear(H, F, bias_attr=False)
        self.up = nn.Linear(H, F, bias_attr=False)
        self.down = nn.Linear(F, H, bias_attr=False)

    def forward(self, x):
        h = self.norm(x)
        return x + self.down(jax.nn.silu(self.gate(h)) * self.up(h))


def main():
    d = jax.devices()[0]
    print("device", d.platform, d.device_kind, flush=True)
    pt.seed(0)
    blocks = [Block() for _ in range(L)]
    template = blocks[0]
    stacked = stacked_parameters(blocks)
    mesh = pt.build_mesh(pp=1, devices=jax.devices()[:1])
    real = Layer._cast_once
    declared = template.compute_cast_names()

    def step_of(way):
        def block_fn(p, h):
            return template.functional_call(p, h)[0]

        def lf(p, x):
            with policy_scope("mixed_bf16"):
                if way == "before":     # the body then finds nothing wide
                    p = {k: jax.lax.optimization_barrier(
                        v.astype(jnp.bfloat16)) if k in declared else v
                        for k, v in p.items()}
                out = pipeline_apply(block_fn, p, x, num_microbatches=1,
                                     mesh=mesh, remat=True)
            return jnp.mean(jnp.square(out.astype(jnp.float32)))

        return jax.jit(jax.value_and_grad(lf))

    for rows in (16384, 1024):
        x = jax.random.normal(jax.random.key(1), (rows, H), jnp.float32)
        got = {}
        for way in ("each_use", "in_body", "before", "each_use", "in_body", "before"):
            Layer._cast_once = (lambda self, params: params) \
                if way == "each_use" else real
            step = step_of(way)
            loss, grads = step(stacked, x)      # compile + warm
            jax.block_until_ready(grads)
            n = 20
            t0 = time.perf_counter()
            for _ in range(n):
                loss, grads = step(stacked, x)
            jax.block_until_ready(grads)
            ms = (time.perf_counter() - t0) / n * 1e3
            got.setdefault(way, []).append(ms)
            print(f"rows {rows} {way}: {ms:.3f} ms a step, loss {float(loss):.6f}, "
                  f"grad dtype {grads['gate.weight'].dtype}", flush=True)
            Layer._cast_once = real
        print("rows", rows, {k: [round(v, 3) for v in vs] for k, vs in got.items()}, flush=True)


if __name__ == "__main__":
    main()
