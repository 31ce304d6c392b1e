"""Microbench: row-sparse vs dense embedding updates across vocab sizes.

The SelectedRows-capability perf claim (VERDICT r2 #4 done criterion):
the sparse train step's cost stays FLAT in V while the dense step's
optimizer update scales O(V). Prints one line per (vocab, mode) with
compiled FLOPs and measured wall-clock per step.

Usage: python tools/sparse_embedding_bench.py [--platform cpu]
               [--vocabs 10000,100000,1000000] [--steps 20]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--vocabs", default="10000,100000,1000000")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--fields", type=int, default=16)
    ap.add_argument("--dim", type=int, default=32)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer
    from paddle_tpu.optimizer.sparse import sparse_minimize_fn

    def bench(vocab: int, sparse: bool):
        pt.seed(0)

        class Model(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = nn.Embedding(vocab, args.dim, is_sparse=sparse)
                self.fc = nn.Linear(args.dim, 1)

            def forward(self, ids):
                return self.fc(jnp.mean(self.emb(ids), axis=1))

        model = Model()
        params = model.named_parameters()

        def fl(p, ids, y):
            out, _ = model.functional_call(p, ids)
            return jnp.mean((out.squeeze(-1) - y) ** 2)

        opt = optimizer.Adam(1e-3)
        if sparse:
            init_fn, step_fn = sparse_minimize_fn(model, fl, opt)
        else:
            init_fn, step_fn = opt.init, opt.minimize_fn(fl)
        state = init_fn(params)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, vocab,
                                       size=(args.batch, args.fields)))
        y = jnp.asarray(rng.normal(size=(args.batch,)).astype(np.float32))
        # donation is what makes the sparse scatter update IN PLACE —
        # without it every step copies the whole (V, D) table
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))
        compiled = jstep.lower(params, state, ids, y).compile()
        ca = compiled.cost_analysis() or {}
        loss, params_, state_ = jstep(params, state, ids, y)  # warmup
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        p, s = params_, state_
        for _ in range(args.steps):
            loss, p, s = jstep(p, s, ids, y)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / args.steps
        print(f"vocab={vocab:>9} mode={'sparse' if sparse else 'dense '} "
              f"flops={ca.get('flops', float('nan')):>14.0f} "
              f"step={dt * 1e3:8.3f} ms")
        return dt

    for v in (int(x) for x in args.vocabs.split(",")):
        ts = bench(v, True)
        td = bench(v, False)
        print(f"  -> sparse speedup at V={v}: {td / ts:.2f}x")


if __name__ == "__main__":
    main()
