#!/usr/bin/env python
"""Time the bodies of learned sparse attention over a latent arena on
the chip: the numbers that decide which ones a decode step and a
prefill use (``ops/latent_attention.py``; PERF.md section 3).

A DECODE STEP of one layer, ``--rows`` rows at one context each
(default: the GLM-5 serving cell's 16 rows x 32768 positions, 64 heads,
a record of 512 + 64 numbers, an indexer of 32 heads of 128 that picks
2048), one JSON line a body and a context:

- ``scores``: the index scores alone (``step_index_scores``);
- ``pick:search`` / ``pick:top_k``: the exact pick alone, as the
  library's threshold search (``pick_mask``) or as ``lax.top_k``;
- ``step:mask``: scores + pick + every live record read under the pick
  as a mask (body (a), ``mla_decode`` with ``keep``);
- ``step:gather``: scores + pick + the picked records gathered (a
  prefix sum, a search, ``take_along_axis``) and read by ``mla_decode``
  (body (b), which the library does not ship: this file's own);
- ``step:top_k``: the same with ``lax.top_k``'s indices;
- ``step:dense``: no selection, ``latent_read`` over every live record
  (another model: the yardstick).

A PREFILL of one layer and one row of ``context`` positions, heads of
256 / 256 made beforehand:

- ``prefill:index``: the spans' index scores and picks alone
  (``span_pick``);
- ``prefill:masked``: the masked flash attention of every span under
  those picks (``masked_attention``), all heads;
- ``prefill:picked``: the picked records gathered a query and read
  absorbed, 256 queries at a time in ``jax.numpy``;
- ``prefill:dense``: the causal flash kernel with no selection.

    chiprun -- python tools/dsa_bodies.py [--contexts 4096,12288,28672]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mask_positions(keep, k: int):
    """The first ``k`` true positions of ``keep`` (B, T), ascending:
    (B, k) int32; where a row holds fewer, the tail repeats ``T - 1``."""
    import jax
    import jax.numpy as jnp

    total = jnp.cumsum(keep, axis=-1, dtype=jnp.int32)
    nth = jnp.arange(1, k + 1, dtype=jnp.int32)
    at = jax.vmap(lambda row: jnp.searchsorted(row, nth, side="left"))(total)
    return jnp.minimum(at, keep.shape[-1] - 1).astype(jnp.int32)


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
    ap.add_argument("--capacity", type=int, default=32768)
    ap.add_argument("--dims", default="64,512,64,192,256",
                    help="heads,kv_rank,rope,nope,v")
    ap.add_argument("--index", default="32,128,2048", help="heads,dim,topk")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--contexts", default="4096,12288,28672")
    ap.add_argument("--parts", default="step,prefill")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.ops import latent_attention as LA

    dev = jax.devices()[0]
    where = {"device": dev.device_kind, "platform": dev.platform}
    h, lat, rope, nope, vd = (int(v) for v in args.dims.split(","))
    ih, idim, topk = (int(v) for v in args.index.split(","))
    b, cap = args.rows, args.capacity
    bf = jnp.dtype(args.dtype)
    scale = (nope + rope) ** -0.5
    contexts = [int(v) for v in args.contexts.split(",")]

    def draw(i, *shape, dtype=bf):
        return jax.random.normal(jax.random.key(i), shape,
                                 jnp.float32).astype(dtype)

    def say(what, body, ctx, ms, out):
        print(json.dumps({**where, "what": what, "body": body,
                          "context": ctx, "ms_layer": round(ms, 4),
                          "finite": bool(jnp.all(jnp.isfinite(
                              jnp.asarray(out, jnp.float32))))}), flush=True)

    if "step" in args.parts:
        qa, qr = draw(0, b, h, lat), draw(1, b, h, rope)
        c, r = draw(2, b, cap, lat), draw(3, b, cap, rope)
        qi, ki = draw(4, b, ih, idim), draw(5, b, cap, idim)
        wi = draw(6, b, ih, dtype=jnp.float32)
        live = lambda t: jnp.arange(cap)[None, :] <= t[:, None]

        def gathered(qa, qr, c, r, t, at):
            n = jnp.minimum(t + 1, topk)
            return LA.latent_read(
                qa, qr, jnp.take_along_axis(c, at[..., None], axis=1),
                jnp.take_along_axis(r, at[..., None], axis=1), n - 1, scale)

        bodies = {
            "scores": lambda t: LA.step_index_scores(qi, wi, ki),
            "pick:search": lambda t, sc: LA.pick_mask(sc, live(t), topk),
            "pick:top_k": lambda t, sc: lax.top_k(
                jnp.where(live(t), sc, -jnp.inf), topk)[1],
            "step:mask": lambda t: LA.latent_read(
                qa, qr, c, r, t, scale, LA.pick_mask(
                    LA.step_index_scores(qi, wi, ki), live(t), topk)),
            "step:gather": lambda t: gathered(
                qa, qr, c, r, t, mask_positions(LA.pick_mask(
                    LA.step_index_scores(qi, wi, ki), live(t), topk), topk)),
            "step:top_k": lambda t: gathered(
                qa, qr, c, r, t, lax.top_k(jnp.where(
                    live(t), LA.step_index_scores(qi, wi, ki), -jnp.inf),
                    topk)[1]),
            "step:dense": lambda t: LA.latent_read(qa, qr, c, r, t, scale),
        }
        sc = jax.jit(bodies["scores"])(jnp.zeros((b,), jnp.int32))
        for name, body in bodies.items():
            fn = jax.jit(body)
            for ctx in contexts:
                t = jnp.full((b,), ctx - 1, jnp.int32)
                extra = (sc,) if name.startswith("pick") else ()
                ms, out = timed(fn, (t, *extra), args.calls)
                say("decode_step", name, ctx, ms, out)

    if "prefill" in args.parts:
        for ctx in contexts:
            s = ctx
            q, k = draw(10, 1, h, s, nope + rope), draw(11, 1, h, s,
                                                        nope + rope)
            v = draw(12, 1, h, s, vd)
            qi, ki = draw(13, 1, s, ih, idim), draw(14, 1, s, idim)
            wi = draw(15, 1, s, ih, dtype=jnp.float32)
            spans = LA.sparse_spans(s) or [(0, s)]

            @jax.jit
            def index(qi, wi, ki):
                return [LA.span_pick(qi, wi, ki, a, e, topk).astype(jnp.int8)
                        for a, e in spans]

            @jax.jit
            def masked(q, k, v, keeps):
                return jnp.concatenate(
                    [LA.masked_attention(q, k, v, keep, scale, a)
                     for (a, _), keep in zip(spans, keeps)], axis=2)

            ms, keeps = timed(index, (qi, wi, ki), max(2, args.calls // 3))
            say("prefill", "prefill:index", ctx, ms, keeps[-1])
            ms, out = timed(masked, (q, k, v, keeps),
                            max(2, args.calls // 3))
            say("prefill", "prefill:masked", ctx, ms, out[0, 0])
            del q, k, v, out

            # the picked records gathered a query, read absorbed
            qa, qr = draw(20, s, h, lat), draw(21, s, h, rope)
            c, r = draw(22, s, lat), draw(23, s, rope)

            @jax.jit
            def picked(qa, qr, c, r, keeps):
                outs = []
                for (a, e), keep in zip(spans, keeps):
                    at = mask_positions(keep[0] != 0, topk)   # (Sq, k)
                    n = jnp.minimum(jnp.arange(a, e) + 1, topk)

                    def chunk(inp):
                        qa_, qr_, at_, n_ = inp             # 256 queries
                        return LA._read_jnp(
                            qa_, qr_, c[at_], r[at_], n_ - 1, scale)

                    cut = lambda x: x.reshape(-1, min(256, e - a),
                                              *x.shape[1:])
                    outs.append(lax.map(chunk, (
                        cut(qa[a:e]), cut(qr[a:e]), cut(at), cut(n))))
                return jnp.concatenate(outs).reshape(s, h, lat)

            ms, out = timed(picked, (qa, qr, c, r, keeps), 2)
            say("prefill", "prefill:picked", ctx, ms, out[0])
            del qa, qr, c, r, out, keeps

            q = draw(30, 1, s, h, nope + rope)
            k, v = draw(31, 1, s, h, nope + rope), draw(32, 1, s, h, vd)
            dense = jax.jit(lambda q, k, v: LA.causal_attention(
                q, k, v, scale))
            ms, out = timed(dense, (q, k, v), max(2, args.calls // 3))
            say("prefill", "prefill:dense", ctx, ms, out[0, 0])
            del q, k, v, out
    return 0


if __name__ == "__main__":
    sys.exit(main())
