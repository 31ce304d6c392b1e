#!/usr/bin/env python
"""The control of the mechanism of a ``window_moe`` cell, made from
outside the library: the cell's own program with every sliding layer's
window taken away, so that its cache is full-length and its queries read
every live position. What it serves is another model's tokens, and the
comparison that decides ``correct`` has to say so (PERF.md section 6,
PR 52).

    chiprun --timeout 1800 -- python tools/window_control.py \\
        --seeds 1,2 --every-position 1

One ``serve_job.run`` a seed, at the cell's sizes but ``--slots`` slots
and as many callers (twenty full-length caches of 16 slots x 16384 are
21.5 GB: the cell cannot run without its rings, which is the point of
it; at 4 slots they are 5.4 GB). ``--every-position 0`` runs the sound
program at the same slots, for the reading beside it. One JSON line a
seed. With ``--as-they-stand 1`` the reference levels nothing, every
compared position's gap and expert margin (and with ``--control 1`` the
float8 control's gap) are kept in ``<out>/<seed>.npz``, and the line has
the widest gap as it stands, the share of positions under the
reference's ``PICK_MARGIN`` and the widest gap a few depths would leave:
what this family's ``UNDECIDED_DEPTH`` is set from.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "Laguna-XS.2.longctx_closed16"


@contextlib.contextmanager
def every_position():
    """Inside, every ``nn.GatedAttention`` is built without its window:
    a full-length cache, causal attention over the whole chunk, every
    live position read by a step. Its heads, its rotary embedding and
    its gate stay. The library has no such switch; this replaces the
    constructor for the time being."""
    from paddle_tpu.nn import gated_attention as G

    init = G.GatedAttention.__init__

    def without_window(self, *args, **kw):
        init(self, *args, **kw)
        self.window = None

    G.GatedAttention.__init__ = without_window
    try:
        yield
    finally:
        G.GatedAttention.__init__ = init


@contextlib.contextmanager
def gaps_as_they_stand(fam, seen: dict):
    """Inside, the float32 reference levels nothing (its
    ``UNDECIDED_DEPTH`` is 0: ``hold_undecided`` then returns the logits
    as they stand), and ``check.serve_reference`` leaves in ``seen``,
    position by position over the compared ones: ``gap``, how far the
    served token lies under the reference's best in deviations of that
    position's logits; ``margin``, the position's expert margin; and,
    where the job also asks for the float8 control, ``control_gap``, the
    same gap for the control's best token. ``correct`` is then judged on
    the logits as they stand; what holds for any margin and depth is
    reckoned from the arrays afterwards (:func:`held_max`)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import check

    R, inner = fam.reference, check.serve_reference
    hold, depth, box = R._hold, R.UNDECIDED_DEPTH, {}

    def gaps(lg, tokens, mask):
        took = jnp.take_along_axis(lg, jnp.asarray(tokens)[..., None],
                                   -1)[..., 0]
        gap = (jnp.max(lg, axis=-1) - took) / jnp.std(lg, axis=-1)
        return np.asarray(gap)[np.asarray(mask)]

    def keep_margin(lg, margin, pick_margin, depth):
        box["margin"] = np.asarray(margin)
        return hold(lg, margin, pick_margin, depth)

    def serve_reference(*args, **kw):
        lg, served, mask = inner(*args, **kw)
        if "margin" in box and "gap" not in seen:      # the float32 call
            box["lg"], box["mask"] = lg, mask
            seen["gap"] = gaps(lg, served, mask)
            seen["margin"] = box["margin"][np.asarray(mask)]
        elif "lg" in box:                              # the control's
            seen["control_gap"] = gaps(
                box["lg"], np.asarray(lg.argmax(-1), np.int32), box["mask"])
        return lg, served, mask

    check.serve_reference, R._hold, R.UNDECIDED_DEPTH = (
        serve_reference, keep_margin, 0.0)
    try:
        yield
    finally:
        check.serve_reference, R._hold, R.UNDECIDED_DEPTH = (
            inner, hold, depth)
        box.clear()


def held_max(gap, margin, pick_margin: float, depth: float) -> float:
    """The widest gap once the positions whose margin is under
    ``pick_margin`` are levelled ``depth`` deviations under their best:
    such a position's gap is what it exceeds ``depth`` by (to the small
    change of the levelled logits' deviation)."""
    import numpy as np

    under = margin < pick_margin
    return float(np.max(np.where(under, np.maximum(gap - depth, 0.0), gap),
                        initial=0.0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--slots", type=int, default=4,
                    help="0: the cell's own")
    ap.add_argument("--every-position", type=int, choices=(0, 1),
                    default=1)
    ap.add_argument("--as-they-stand", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--depths", default="0.75,1,1.25,1.5,1.75,2,2.5")
    ap.add_argument("--out", default="chiprun_out/gaps")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark.harness import manifest, runtime, serve_job

    device = runtime.require_chips(1)
    runtime.place_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = manifest.Cell(manifest.load_manifest(), args.workload)
        if args.slots:
            cell.config["serve"]["slots"] = args.slots
            cell.traffic["clients"] = args.slots
        broken = (every_position() if args.every_position
                  else contextlib.nullcontext())
        seen, R = {}, cell.family.reference
        raw = (gaps_as_they_stand(cell.family, seen) if args.as_they_stand
               else contextlib.nullcontext())
        with broken, raw:
            job = serve_job.run(cell, seed, args.seconds, False, device,
                                time.perf_counter(),
                                control=bool(args.control))
        row = {"workload": cell.name, "seed": seed,
               "slots": cell.config["serve"]["slots"],
               "every_position": bool(args.every_position),
               "correct": job["correct"], "numbers": job["numbers"],
               "control": job.get("control_numbers"),
               "attempted": job["attempted"], "failed": job["failed"],
               "end_to_end": job["end_to_end"],
               "memory_peak_bytes": job["memory_peak_bytes"]}
        if seen:
            os.makedirs(args.out, exist_ok=True)
            np.savez(os.path.join(args.out, f"{seed}.npz"), **seen)
            depths = [float(d) for d in args.depths.split(",")]
            row.update(
                positions=int(seen["gap"].size),
                as_it_stands=float(seen["gap"].max(initial=0.0)),
                undecided_share=float(
                    (seen["margin"] < R.PICK_MARGIN).mean()),
                held={d: held_max(seen["gap"], seen["margin"],
                                  R.PICK_MARGIN, d) for d in depths})
            if "control_gap" in seen:
                row["control_held"] = {
                    d: held_max(seen["control_gap"], seen["margin"],
                                R.PICK_MARGIN, d) for d in depths}
        print(json.dumps(row), flush=True)
        del job
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
