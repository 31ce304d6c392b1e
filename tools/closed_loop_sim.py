#!/usr/bin/env python
"""How far a closed-loop serving cell's numbers swing with the seed's
order of the deck, without the chip: the deck exactly as
``benchmark/harness/loadgen.py`` deals it, the arena as a model (a tick
is one decode step over the live rows plus at most one prefill, a freed
slot's caller submits at once), the costs from chip readings.

Written for ``GLM-5.longctx32k_closed16`` (PR 45), whose defaults these
are: a step of ``--step-ms`` + ``--position-ns`` a live position (the
masked read streams every live record), a prefill of bucket ``b``
``--prefill a,c``: ``a b + c b^2`` seconds (0.66 s at 12288 and 1.0 s at
16384), + ``--cliff`` s at the largest bucket. Against 13 runs of that
cell on the chip it read the window's tokens/s within 2% on 12 (PERF.md
section 6, PR 45). A line a (sigma, ramp): the median and the spread
(quartile distance over median) of ``serve_tokens_per_s`` and
``itl_p95_ms`` over ``--seeds`` decks, the spread of sets of six, and
the largest share of a window's gaps that held a prefill.

    python tools/closed_loop_sim.py [--workload CELL] [--sigmas 0.4,0.25]
        [--ramps 20,60] [--seeds 240] [--one SEED]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def simulate(mix, seed, *, slots, bucket, window, step_s, position_s,
             prefill):
    """One run: (tokens/s in the window, p95 of the gaps that closed in
    it in ms, the share of them that held a prefill, requests submitted
    in it)."""
    from benchmark.harness import loadgen

    plan = loadgen.ClosedLoopPlan(mix, 2, seed)
    dealt = 0

    def deal():
        nonlocal dealt
        dealt += 1
        return plan.lengths(dealt - 1)

    queue = [deal() for _ in range(plan.clients)]
    rows = []                     # [context, tokens left, last stamp]
    t0 = float(mix["ramp_s"])
    t1, t = t0 + window, 0.0
    tokens, submitted, gaps = 0, 0, []
    while t <= t1:
        if rows:
            t += step_s + position_s * sum(r[0] for r in rows)
            for r in rows:
                if t0 <= t <= t1:
                    tokens += 1
                    gaps.append(t - r[2])
                r[0], r[1], r[2] = r[0] + 1, r[1] - 1, t
            for r in [r for r in rows if r[1] <= 0]:
                rows.remove(r)
                queue.append(deal())
                submitted += t0 <= t < t1
        if queue and len(rows) < slots:
            plen, out = queue.pop(0)
            t += prefill(-(-plen // bucket) * bucket)
            tokens += t0 <= t <= t1               # the first token
            rows.append([plen + 1, out - 1, t])
        elif not rows:
            t += 1e-3
    gaps.sort()
    p95 = gaps[int(0.95 * (len(gaps) - 1))] if gaps else float("nan")
    held = sum(g > 3 * step_s for g in gaps) / max(1, len(gaps))
    return tokens / window, p95 * 1e3, held, submitted


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="GLM-5.longctx32k_closed16")
    ap.add_argument("--sigmas", default="",
                    help="prompt_tokens.sigma to try (default: the mix's)")
    ap.add_argument("--ramps", default="", help="ramp_s to try")
    ap.add_argument("--seeds", type=int, default=240)
    ap.add_argument("--one", type=int, default=None,
                    help="one seed's numbers, to hold against a chip run")
    ap.add_argument("--window", type=float, default=30.0)
    ap.add_argument("--step-ms", type=float, default=11.4)
    ap.add_argument("--position-ns", type=float, default=7.9)
    ap.add_argument("--prefill", default="3.17e-5,1.79e-9")
    ap.add_argument("--cliff", type=float, default=0.35)
    args = ap.parse_args(argv)

    from benchmark.harness import loadgen, manifest

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    serve, mix = cell.config["serve"], json.loads(json.dumps(cell.traffic))
    a, c = (float(v) for v in args.prefill.split(","))
    top = max(loadgen.prompt_buckets(mix, serve["prompt_bucket"],
                                     serve["capacity"]))
    prefill = lambda b: a * b + c * b * b + (args.cliff if b >= top else 0)
    costs = dict(slots=serve["slots"], bucket=serve["prompt_bucket"],
                 window=args.window, step_s=args.step_ms * 1e-3,
                 position_s=args.position_ns * 1e-9, prefill=prefill)
    if args.one is not None:
        print(json.dumps(dict(zip(
            ("serve_tokens_per_s", "itl_p95_ms", "prefill_gap_share",
             "submitted"), simulate(mix, args.one, **costs)))))
        return 0
    seeds = [2147480000 + 7 * i for i in range(args.seeds)]
    for sigma in [float(v) for v in args.sigmas.split(",") if v] or [
            mix["prompt_tokens"]["sigma"]]:
        mix["prompt_tokens"]["sigma"] = sigma
        for ramp in [float(v) for v in args.ramps.split(",") if v] or [
                mix["ramp_s"]]:
            mix["ramp_s"] = ramp
            runs = [simulate(mix, s, **costs) for s in seeds]
            line = {"sigma": sigma, "ramp_s": ramp, "decks": len(runs),
                    "prefill_gap_share_max": round(max(r[2] for r in runs), 4)}
            for i, name in enumerate(("serve_tokens_per_s", "itl_p95_ms")):
                v = [r[i] for r in runs]
                six = sorted(spread(v[j:j + 6])
                             for j in range(0, len(v) - 5, 6))
                line[name] = {
                    "median": round(statistics.median(v), 2),
                    "spread_pct": round(100 * spread(v), 2),
                    "sets_of_six_median_pct": round(
                        100 * statistics.median(six), 2),
                    "sets_of_six_under_5_pct": round(
                        sum(x < 0.05 for x in six) / len(six), 3)}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
