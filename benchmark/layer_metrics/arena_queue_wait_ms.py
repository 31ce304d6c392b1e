"""Serving arena (`serving.py` `BatchedDecoder`): median of the
`queued_us` argument of the traced `serve.prefill` program spans: from
`Request.t_submit` (the arena has the request) to the start of its
prefill, the wait for a free slot and for the tick to come round."""

import statistics
import sys

from benchmark.harness import program_spans as P


def read(run):
    t = P.load(run)
    waits = [s["stats"]["queued_us"] / 1e3
             for s in (P.named(t["host"], "serve.prefill") if t else [])
             if "queued_us" in s["stats"]]
    if not waits:
        return None
    print(f"[arena_queue_wait_ms] n={len(waits)}, max {max(waits):.2f} ms",
          file=sys.stderr)
    return statistics.median(waits)
