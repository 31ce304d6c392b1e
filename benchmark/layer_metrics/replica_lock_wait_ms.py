"""Router (`serving_router.py` `LocalReplica`): mean duration of the
traced `replica.lock_wait.submit` program spans: how long a caller's
`submit` waits for the replica's lock `_mu`, which the serve loop holds
for the whole of every tick. A few seconds of trace hold about ten
submits, so the mean is a thin and skewed sample; beside it this prints
the drain's and the other callers' waits, and the steadier figure: the
waits of all callers summed, a second of trace (from the first
`serve.tick` to the last one's end), which counts every waiter."""

import sys

from benchmark.harness import program_spans as P

WAITERS = ("submit", "drain", "other")


def read(run):
    t = P.load(run)
    if not t:
        return None
    waits = {who: [s["dur"] for s in
                   P.named(t["host"], "replica.lock_wait." + who)]
             for who in WAITERS}
    if not waits["submit"]:
        return None
    ticks = P.named(t["host"], "serve.tick")
    traced = (max(s["start"] + s["dur"] for s in ticks)
              - min(s["start"] for s in ticks)) if ticks else 0
    total = sum(map(sum, waits.values()))
    print("[replica_lock_wait_ms] mean / max ms (n): " + "; ".join(
        f"{who} {sum(d) / len(d) / 1e6:.3f} / {max(d) / 1e6:.3f} ({len(d)})"
        for who, d in waits.items() if d)
        + (f"; all callers waited {total / traced:.3f} s a second of "
           f"{traced / 1e9:.2f} s traced" if traced else ""),
        file=sys.stderr)
    return sum(waits["submit"]) / len(waits["submit"]) / 1e6
