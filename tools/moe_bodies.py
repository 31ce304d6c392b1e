#!/usr/bin/env python
"""Time both bodies of ``nn.moe.dropless_moe`` on the chip, at rows on
either side of every edge of ``nn.moe.streams_densely``: the numbers
that set its rule (``DENSE_MAX_ROWS``, ``DENSE_MAX_WORK``).

One program a (body, rows) pair: ``--layers`` expert layers in a row,
each the routed experts of one block at the given widths (default: the
hybrid serving cell's share, 36 held of 72 experts of 4096 x 768, 10 a
token, bfloat16), every layer's result fed to the next so that they run
in order and each streams its weights from HBM again. Prints one JSON
line a pair: ``ms_layer`` (host clock over fenced calls, a layer),
``roofline_pct`` (the larger of the held weights read once at the
chip's HBM peak and the body's own products at its bf16 peak, over it;
a tool's print, not a benchmark metric: it passes 100 where the grouped
body reads only the picked experts, at a row or two, and where the
layers' shared weights partly stay in fast memory, as 176 MB do) and
``rule``, the body ``streams_densely`` itself picks there. A body is
forced by replacing the module's rule before the trace; the library
itself has no switch.

    chiprun -- python tools/moe_bodies.py [--rows 1,16,32,128,256,512,768,1024,2048]
    chiprun -- python tools/moe_bodies.py --widths 3584,1024,64,8,4 \
        --rows 16,128,512,1024,2048,4096,8192,14336

    chiprun -- python tools/moe_bodies.py --widths 2048,768,128,16,6 \
        --rows 16384 --layers 5 --grad

(the second: the Xing4.0 cell's share, 8 held of 64 experts of 3584 x
1024, 4 a token; the third: the trained kanana-2 cell's, 16 held of 128
experts of 2048 x 768, 6 a token, a step's 16384 rows). ``--grad`` times
value and gradient (in the rows and the three expert tensors) of the
stack's sum, each layer under ``jax.checkpoint`` as a training step's
blocks are, where the default times the forward bodies alone: a trained
cell's cost is two thirds backward. ``roofline_pct`` then counts three
passes of the products. ``windows`` is what the grouped body ran a
layer at the tool's own routing (``nn.moe.windows_run``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows",
                    default="1,16,32,128,256,512,768,1024,2048")
    ap.add_argument("--widths", default="4096,768,72,36,10",
                    help="D,F,experts,held,top_k")
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--grad", action="store_true",
                    help="time value and gradient, layers checkpointed")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import moe
    from paddle_tpu.utils.flops import device_peaks

    dev = jax.devices()[0]
    peaks = device_peaks(dev) or {}
    peak, flops_peak = (peaks.get("hbm_bytes_per_s"),
                        peaks.get("bf16_flops"))
    d, f, e, held, k = (int(v) for v in args.widths.split(","))
    keys = jax.random.split(jax.random.key(0), 5)
    bf = jnp.bfloat16
    rnd = lambda key, *shape: (jax.random.normal(key, shape, jnp.float32)
                               * shape[-2] ** -0.5).astype(bf)
    router, wg, wu, wd = (rnd(keys[0], d, e), rnd(keys[1], held, d, f),
                          rnd(keys[2], held, d, f), rnd(keys[3], held, f, d))
    weight_bytes = 2 * (wg.size + wu.size + wd.size)

    def stack_fn():
        # a function of its own a pair: jit's cache goes by the function
        def layer(x, router, wg, wu, wd):
            y, tokens = moe.dropless_moe(x, router, wg, wu, wd, top_k=k,
                                         experts_held=(0, held))
            return x + y, tokens

        def stack(x, router, wg, wu, wd):
            for _ in range(args.layers):
                x, tokens = (jax.checkpoint(layer) if args.grad else layer)(
                    x, router, wg, wu, wd)
            return x, tokens

        def total(*a):
            x, tokens = stack(*a)
            return jnp.sum(x.astype(jnp.float32)), tokens

        def grads(*a):
            (_, tokens), got = jax.value_and_grad(
                total, argnums=(0, 2, 3, 4), has_aux=True)(*a)
            return got, tokens

        return jax.jit(grads if args.grad else stack)

    keep = moe.streams_densely
    try:
        for rows in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(keys[4], (rows, d), jnp.float32).astype(bf)
            rule = "dense" if keep(rows, k, e) else "grouped"
            # products of the picked pairs on held experts in the mean
            # (grouped), or of every row through every held expert
            pairs = {"grouped": rows * k * held / e, "dense": rows * held}
            for body in ("grouped", "dense"):
                moe.streams_densely = lambda *_, b=body: b == "dense"
                fn = stack_fn()
                try:
                    for _ in range(3):
                        out, tokens = jax.block_until_ready(
                            fn(x, router, wg, wu, wd))
                except Exception as err:  # noqa: BLE001 — a body that
                    # does not fit at these rows is a finding, not an end
                    print(json.dumps({"rows": rows, "body": body,
                                      "rule": rule, "error": repr(err)[:200]}),
                          flush=True)
                    continue
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = fn(x, router, wg, wu, wd)
                jax.block_until_ready(out)
                ms = ((time.perf_counter() - t0) * 1e3
                      / (args.calls * args.layers))
                passes = 3 if args.grad else 1
                least = (max(weight_bytes / peak, passes * 6 * d * f
                             * pairs[body] / flops_peak)
                         if peak and flops_peak else None)
                print(json.dumps({
                    "device": dev.device_kind, "platform": dev.platform,
                    "rows": rows, "body": body, "rule": rule,
                    "grad": args.grad, "held_pairs": int(tokens.sum()),
                    "windows": (moe.windows_run(int(tokens.sum()), rows, k, e,
                                                held)
                                if body == "grouped" else None),
                    "ms_layer": round(ms, 4),
                    "roofline_pct": (round(100 * least / (ms * 1e-3), 2)
                                     if least else None)}), flush=True)
    finally:
        moe.streams_densely = keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
