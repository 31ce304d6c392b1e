#!/usr/bin/env python
"""Time the bodies of the latent decode read on the chip: the numbers
that decide which one a decode step uses.

``ops.latent_attention.latent_read``: ``--layers`` reads in a row at
the given sizes (default: the Xing4.0 serving cell's, 16 rows, 32
heads, a record of 512 + 64 bfloat16 numbers, 16384 positions a row),
each over records of its own and fed the last one's output so that they
run in order and each streams its records from HBM again. Bodies:
``jnp`` (the whole capacity under a mask) and ``pallas``
(``ops/pallas/mla_decode.py``, live blocks only; ``pallas:512`` forces a
block of 512 records). One JSON line a body and a context (every row's
cursor): ``ms_layer`` (host clock over fenced calls, a layer),
``live_gb_s`` (the live records' bytes over that time) and
``roofline_pct`` (the least time for the live records at the HBM peak
or the read's operations at the bf16 peak, whichever is longer, over
it). A body is forced by replacing the module's rule before the trace;
the library itself has no switch.

    chiprun -- python tools/mla_bodies.py [--contexts 2048,7168,16383]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, args, calls):
    import jax

    for _ in range(2):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / calls, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=16384)
    ap.add_argument("--dims", default="32,512,64,128",
                    help="heads,kv_rank,rope,nope")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--contexts", default="2048,7168,16383")
    ap.add_argument("--bodies", default="jnp,pallas")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import latent_attention as LA
    from paddle_tpu.ops.pallas import mla_decode as K
    from paddle_tpu.utils.flops import device_peaks

    dev = jax.devices()[0]
    peaks = device_peaks(dev) or {}
    hbm, mxu = peaks.get("hbm_bytes_per_s"), peaks.get("bf16_flops")
    h, lat, rope, nope = (int(v) for v in args.dims.split(","))
    b, cap, n = args.rows, args.capacity, args.layers
    bf = jnp.dtype(args.dtype)
    scale = (nope + rope) ** -0.5
    where = {"device": dev.device_kind, "platform": dev.platform}

    def draw(i, *shape):
        return jax.random.normal(jax.random.key(i), shape,
                                 jnp.float32).astype(bf)

    qa, qr = draw(0, b, h, lat), draw(1, b, h, rope)
    records = [(draw(10 + i, b, cap, lat), draw(50 + i, b, cap, rope))
               for i in range(n)]

    def stack_fn():
        # a function of its own a body: jit's cache goes by the function
        def stack(qa, qr, records, t):
            out = 0.0
            for c, r in records:
                got = LA.latent_read(qa + jnp.asarray(out, qa.dtype), qr,
                                     c, r, t, scale)
                out = jnp.mean(got) * 1e-3
            return out

        return jax.jit(stack)

    keep = LA.read_kernel_ok, K.BLOCKS
    try:
        for body in args.bodies.split(","):
            name, _, bk = body.partition(":")
            LA.read_kernel_ok = lambda *_, p=(name == "pallas"): p
            K.BLOCKS = (int(bk),) if bk else keep[1]
            fn = stack_fn()
            for ctx in (int(v) for v in args.contexts.split(",")):
                t = jnp.full((b,), ctx - 1, jnp.int32)
                ms, out = timed(fn, (qa, qr, records, t), args.calls)
                ms /= n
                live = b * ctx * (lat + rope) * bf.itemsize
                flops = b * ctx * 2 * h * (2 * lat + rope)
                least = max(live / hbm, flops / mxu) if hbm else 0.0
                print(json.dumps({
                    **where, "what": "decode_read", "rows": b,
                    "context": ctx, "body": body,
                    "ms_layer": round(ms, 4),
                    "live_gb_s": round(live / ms / 1e6, 1),
                    "roofline_pct": hbm and round(
                        100 * least * 1e3 / ms, 2),
                    "finite": bool(jnp.isfinite(out))}), flush=True)
    finally:
        LA.read_kernel_ok, K.BLOCKS = keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
