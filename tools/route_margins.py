#!/usr/bin/env python
"""On the chip, at a latent_moe cell's own sizes: where the served
tokens of the bfloat16 program leave the float32 reference's best, and
how near a held expert then lay to the edge of the reference's picks.
The readings the reference's ``PICK_MARGIN`` / ``UNDECIDED_DEPTH`` and
the cell's limit are set from (PERF.md section 6, PR 41).

**Served tokens** (``--seeds``, ``--control-seeds``): one
``BatchedDecoder`` (the cell's sizes, its own ``pt_prefill_<bucket>``
and ``pt_decode_step``) serves, seed after seed with that seed's
weights, the first ``--requests`` requests of the seed's deck;
``check.pick_sample``'s requests then go through the reference as
``check.serve_reference`` walks it, with the logits as they stand and
each position's margin (``reference.layerwise``). A seed's line has
the widest gap as it stands, and for each ``--margins`` value the
widest gap ``check.serve_gap`` reads on the held logits, the share of
positions left undecided and, for a control seed, the float8
control's reading on the same logits; ``<out>/<seed>.npz`` keeps every
position's gap, margin and control gap.

**Picks** (``--picks N``, one seed): the program's forward over one
prompt of ``N`` tokens with every expert layer's picks read out,
against the reference's picks and margins at the same tokens: how many
(token, layer) picks differ, how many of those move a HELD expert, and
the reference's margin where they do.

    chiprun -- python tools/route_margins.py --seeds 1,2,3 \\
        --control-seeds 1 --picks 4096 --out chiprun_out/margins

``--tiny`` shrinks the cell as ``benchmark/tests/test_latent_moe.py``
does (a CPU rehearsal of the tool, not a reading).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "Xing4.0-29B-A4B.longctx_closed16"


def say(**row):
    print(json.dumps(row), flush=True)


def gaps_of(lg, tokens, mask):
    """Every position's gap, as ``check._gaps`` counts one."""
    import jax.numpy as jnp

    took = jnp.take_along_axis(lg, tokens[..., None], -1)[..., 0]
    gap = (jnp.max(lg, axis=-1) - took) / jnp.std(lg, axis=-1)
    return jnp.where(mask, gap, 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests served a seed (default: the slots)")
    ap.add_argument("--margins", default="0,0.005,0.01,0.02,0.03,0.05")
    ap.add_argument("--depth", type=float, default=None)
    ap.add_argument("--picks", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/margins")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--unselect", default="",
                    choices=("", "program", "both"),
                    help="a family with a learned selection "
                    "(ops.latent_attention.pick_mask): attend every live "
                    "position in the program, switched off from outside "
                    "the library (what the comparison must catch), or in "
                    "the program and the reference both (what the noise "
                    "is without the selection)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import (check, loadgen, manifest, program,
                                   runtime, weights as W)
    from paddle_tpu.serving import BatchedDecoder

    if args.tiny:
        from benchmark.tests.test_latent_moe import tiny_cell

        cell = tiny_cell()
    else:
        cell = manifest.Cell(manifest.load_manifest(), args.workload)
        runtime.require_chips(cell.chips)
    runtime.place_compile_cache()
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    dims, serve, R = fam.Dims.from_config(cfg), cfg["serve"], fam.reference
    depth = R.UNDECIDED_DEPTH if args.depth is None else args.depth
    margins = [float(v) for v in args.margins.split(",")]
    seeds = [int(v) for v in args.seeds.split(",") if v]
    control = {int(v) for v in args.control_seeds.split(",") if v}
    os.makedirs(args.out, exist_ok=True)
    hold = jax.jit(R.hold_undecided, static_argnums=(2, 3))
    gaps = jax.jit(gaps_of)
    # the reference as check.serve_reference walks it, but with the
    # logits as they stand; the margins are kept on the side
    raw = types.SimpleNamespace(margin=None)

    if args.unselect:
        from paddle_tpu.ops import latent_attention as LA

        LA.pick_mask = lambda scores, live, k: live & (scores == scores)
    both = {"select": False} if args.unselect == "both" else {}

    def layerwise_raw(*a, **k):
        # a family with a learned selection gives a second margin, the
        # queries' position margins (reference.pick)
        lg, raw.margin, *rest = R.layerwise(*a, **k, **both)
        raw.position = rest[0] if rest else None
        return lg

    shim = types.SimpleNamespace(
        reference=types.SimpleNamespace(layerwise_logits=layerwise_raw),
        leaf_rule=fam.leaf_rule, layer_shapes=fam.layer_shapes,
        top_shapes=fam.top_shapes)

    model = program.build_model(fam, cfg, dims, (seeds or [0])[0],
                                cfg["dtype"], serve["capacity"], False)
    names = list(W.leaf_shapes(fam, dims))

    def let_go(dec=None):
        """Free the weights (and the arena): the reference needs the
        chip to itself, as in ``serve_job.run``."""
        if dec is not None:
            dec.caches = dec._mstate = None
        model.set_parameters({k: jnp.zeros((), cfg["dtype"])
                              for k in names})
        gc.collect()

    n_check, max_out = int(mix["check_requests"]), int(
        mix["output_tokens"]["max"])
    dec = None
    for seed in seeds:
        t0 = time.perf_counter()
        model.set_parameters(W.make_all(seed, fam, dims, cfg["dtype"]))
        if dec is None:
            dec = BatchedDecoder(model.eval(), slots=serve["slots"],
                                 capacity=serve["capacity"],
                                 prompt_bucket=serve["prompt_bucket"],
                                 decode_steps=serve["decode_steps"])
        else:
            dec.caches = model.init_cache(serve["slots"], serve["capacity"])
        plan = loadgen.ClosedLoopPlan(mix, dims.vocab, seed)
        reqs = [plan.next_request()
                for _ in range(args.requests or serve["slots"])]
        rids = [dec.submit(p, n) for p, n in reqs]
        out = dec.run()
        finished = [(p, np.asarray(out[rid], np.int32))
                    for (p, _), rid in zip(reqs, rids)]
        t_served = time.perf_counter() - t0
        let_go(dec)
        sample = [finished[i]
                  for i in check.pick_sample(finished, n_check, seed)]
        lg, served, mask = check.serve_reference(
            seed, shim, dims, cfg["dtype"], sample, n_check,
            serve["capacity"], max_out, "f32")
        margin, served, mask = raw.margin, jnp.asarray(served), jnp.asarray(
            mask)
        keep = {"gap": gaps(lg, served, mask), "margin": margin,
                "mask": mask}
        ctl = None
        if seed in control:
            lc, _, _ = check.serve_reference(
                seed, shim, dims, cfg["dtype"], sample, n_check,
                serve["capacity"], max_out, "fp8")
            ctl = jnp.argmax(lc, axis=-1).astype(jnp.int32)
            del lc
            keep["control_gap"] = gaps(lg, ctl, mask)
        by = {}
        for m in margins:
            held = hold(lg, margin, m, depth)
            row = {"sound": check.serve_gap(held, served, mask)[0],
                   "undecided_share": float(
                       jnp.sum(mask & (margin < m)) / jnp.sum(mask))}
            if ctl is not None:
                row["control"] = check.serve_gap(held, ctl, mask)[0]
            by[str(m)] = row
            del held
        by_position = None
        if raw.position is not None:
            # the expert hold as shipped, then the position hold at each
            # (margin, depth)
            at = raw.position
            keep["position_margin"] = at
            level = jax.jit(R.hold)
            by_expert = jnp.where(margin < R.PICK_MARGIN, depth, 0.0)
            seen = np.asarray(at)[np.asarray(mask)]
            by_position = {"quantiles": {
                str(q): float(np.quantile(seen[np.isfinite(seen)], q))
                for q in (0.5, 0.9, 0.99, 1.0)} if np.isfinite(
                    seen).any() else None}
            for m in (0.0, 0.001, 0.003, 0.01, 0.03):
                for d in (0.5, 1.0, 1.5):
                    both_held = level(lg, jnp.maximum(
                        by_expert, jnp.where(at < m, d, 0.0)))
                    row = {"sound": check.serve_gap(both_held, served,
                                                    mask)[0],
                           "undecided_share": float(
                               jnp.sum(mask & (at < m)) / jnp.sum(mask))}
                    if ctl is not None:
                        row["control"] = check.serve_gap(both_held, ctl,
                                                         mask)[0]
                    by_position[f"{m}/{d}"] = row
            del both_held
        g, same, n = check.serve_gap(lg, served, mask)
        np.savez(os.path.join(args.out, f"{seed}.npz"),
                 **{k: np.asarray(v) for k, v in keep.items()})
        say(what="served", seed=seed, unselect=args.unselect,
            requests=len(finished),
            compared=len(sample), tokens=n, same=same, raw_gap_max=g,
            depth=depth, by_margin=by, by_position_margin=by_position,
            served_s=round(t_served, 1),
            seed_s=round(time.perf_counter() - t0, 1))
        del lg, keep, margin, served, mask, ctl
        gc.collect()

    if args.picks:
        picks(args.picks, model, fam, dims, cfg, (seeds or [0])[0])
    return 0


def picks(n, model, fam, dims, cfg, seed):
    """The program's picks over one prompt of ``n`` tokens against the
    reference's, an expert layer a line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import weights as W
    from paddle_tpu.nn import moe
    from paddle_tpu.nn.layer import inject_state

    R = fam.reference
    tokens = jnp.asarray(np.random.default_rng([seed, 9]).integers(
        0, dims.vocab, n).astype(np.int32))
    seen, route = [], moe.route

    def spy(logits, *a, **k):
        gates, top_i = route(logits, *a, **k)
        seen.append(top_i)
        return gates, top_i

    @jax.jit
    def program_picks(params, ids):
        del seen[:]
        with inject_state((model, params, {})):
            model.forward(ids[None])
        return list(seen)

    params = W.make_all(seed, fam, dims, cfg["dtype"])
    moe.route = spy
    try:
        got = [np.asarray(p) for p in program_picks(params, tokens)]
    finally:
        moe.route = route
    del params
    gc.collect()

    get = lambda shapes: W.make_leaves(seed, shapes, cfg["dtype"],
                                       fam.leaf_rule)
    top = fam.top_shapes(dims)
    X = R._embed(tokens, get({"embed.weight": top["embed.weight"]})[
        "embed.weight"], dims)
    first, count = dims.held
    held_of = lambda p: np.where((p >= first) & (p < first + count), p, -1)
    total = dict(pairs=0, differ=0, differ_held=0)
    where = []
    for i in range(dims.layers):
        j = 0 if dims.is_dense(i) else dims.dense_layers
        w = {k.replace(f"blocks.{i}.", f"blocks.{j}."): a
             for k, a in get(fam.layer_shapes(dims, i)).items()}
        X, routed = R._layer_row(X, w, j, dims, "f32")
        del w
        if routed is None:
            continue
        ref, margin = np.sort(np.asarray(routed[0]), -1), np.asarray(
            routed[1])
        prog = np.sort(got[len(where)], -1)
        differ = np.any(prog != ref, axis=-1)
        moved = np.any(np.sort(held_of(prog), -1)
                       != np.sort(held_of(ref), -1), axis=-1)
        row = dict(layer=i, tokens=int(n), differ=int(differ.sum()),
                   differ_held=int(moved.sum()),
                   margin_where_held_differs=[
                       float(v) for v in np.quantile(
                           margin[moved], [0.5, 0.9, 0.99, 1.0])]
                   if moved.any() else None,
                   margin_under={str(m): int((margin < m).sum())
                                 for m in (0.005, 0.01, 0.02, 0.03, 0.05)})
        where.append(row)
        say(what="picks", seed=seed, **row)
        total["pairs"] += int(n)
        total["differ"] += int(differ.sum())
        total["differ_held"] += int(moved.sum())
    say(what="picks_total", seed=seed, **total)


if __name__ == "__main__":
    sys.exit(main())
