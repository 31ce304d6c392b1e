"""Serving arena (`serving.py` `BatchedDecoder` under
`LocalReplica._tick_locked`): the host's part of a tick. Median over the
traced `serve.tick` program spans of the span's duration minus the time
its `serve.step.fetch` (the host blocked on the decode step) and
`serve.prefill` (a prefill that fell into the tick) spans cover. Prints
how the ticks' time divides among their phases, and on how many ticks
the host's spans and the device's `XLA Modules` line agree."""

import sys

from benchmark.harness import program_spans as P


def read(run):
    t = P.load(run)
    ticks = P.named(t["host"], "serve.tick") if t else []
    if not ticks:
        return None
    host = [tick["dur"] - P.covered_ns(P.inside(
        t["host"], tick, ("serve.step.fetch", "serve.prefill")))
        for tick in ticks]
    total, split = P.children_split(t["host"], "serve.tick")
    fetch = split.get("serve.step.fetch", 0)
    rest = max(total - fetch, 1)
    parts = ", ".join(f"{k} {v / len(ticks) / 1e6:.3f}"
                      for k, v in sorted(split.items(), key=lambda kv: -kv[1]))
    n, hits = P.clocks_agree(t["host"], t["modules"])
    print(f"[tick_host_ms] {len(ticks)} ticks, median "
          f"{P.median_ms([x['dur'] for x in ticks]):.3f} ms whole; mean ms "
          f"a tick by phase: {parts}; phases other than the fetch cover "
          f"{100 * (sum(split.values()) - fetch) / rest:.1f}% of the time "
          f"outside it; a pt_decode_step run starts between dispatch and "
          f"fetch end in {hits} of {n} stepping ticks", file=sys.stderr)
    return P.median_ms(host)
