#!/usr/bin/env python
"""Time the bodies of ``ops.retention.retention_step_parts`` on the
chip: the numbers that decide which one a decode step uses.

One program a body: ``--layers`` retention steps in a row at the given
sizes (default: the Brumby serving cell's, 16 slots, 40 q / 8 kv heads
of 128, float32 state), each on a state of its own and fed the last
one's output so that they run in order and each streams its state from
HBM again; the states are donated, as the arena is. Prints one JSON
line a body: ``ms_layer`` (host clock over fenced calls, a layer),
``passes`` (the time as passes over one layer's state at the chip's HBM
peak: the least is 2, one read and one write) and ``roofline_pct`` (2 /
passes). ``copy`` is the attainable floor: ``S = g S`` alone. A body is
forced by replacing the module's rule before the trace; the library
itself has no switch.

    chiprun -- python tools/retention_bodies.py [--slots 16]
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
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--heads", default="40,8,128", help="H,KV,d")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--bodies", default="copy,jnp,pallas")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import retention as R
    from paddle_tpu.utils.flops import device_peaks

    dev = jax.devices()[0]
    peak = (device_peaks(dev) or {}).get("hbm_bytes_per_s")
    h, kv, d = (int(v) for v in args.heads.split(","))
    b, n = args.slots, args.layers
    keys = jax.random.split(jax.random.key(0), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(keys[0], (b, h, d), jnp.float32).astype(bf)
    k = jax.random.normal(keys[1], (b, kv, d), jnp.float32).astype(bf)
    v = jax.random.normal(keys[2], (b, kv, d), jnp.float32).astype(bf)
    log_g = jnp.log(jax.random.uniform(keys[3], (b, kv), jnp.float32,
                                       0.3, 0.9))
    state_bytes = b * kv * R.phi_dim(d) * d * 4

    def stack_fn(body):
        # a function of its own a body: jit's cache goes by the function
        def stack(q, k, v, log_g, states):
            out, new = 0.0, []
            for S, z in states:
                if body == "copy":
                    new.append((jnp.exp(log_g)[..., None, None] * S, z))
                    continue
                # feed the last layer's output on, so the layers run in
                # order
                num, den, st = R.retention_step_parts(
                    q + jnp.asarray(out, q.dtype), k, v, log_g, (S, z))
                out = jnp.mean(num / (den[..., None] + 1e-6)) * 1e-3
                new.append(st)
            return out, new

        return jax.jit(stack, donate_argnums=(4,))

    keep = R.step_kernel_ok
    try:
        for body in args.bodies.split(","):
            R.step_kernel_ok = lambda *_, p=(body == "pallas"): p
            fn = stack_fn(body)
            states = [R.zero_state(b, kv, d) for _ in range(n)]
            for _ in range(2):
                out, states = fn(q, k, v, log_g, states)
            jax.block_until_ready(states)
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out, states = fn(q, k, v, log_g, states)
            jax.block_until_ready((out, states))
            ms = (time.perf_counter() - t0) * 1e3 / (args.calls * n)
            passes = ms * 1e-3 * peak / state_bytes if peak else None
            print(json.dumps({
                "device": dev.device_kind, "platform": dev.platform,
                "slots": b, "body": body, "ms_layer": round(ms, 4),
                "passes": passes and round(passes, 3),
                "roofline_pct": passes and round(200 / passes, 2),
                "finite": bool(jnp.isfinite(jnp.asarray(out)))}),
                flush=True)
            del states
    finally:
        R.step_kernel_ok = keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
