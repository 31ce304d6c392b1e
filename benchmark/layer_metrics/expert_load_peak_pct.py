"""Model (`nn/moe.py::DroplessMoE`, the router): the busiest held
expert's tokens over the mean held expert's, in percent (100 = an even
load; the busiest expert's group is what a grouped product waits for).
From the program's counter `serving.ArenaCounters.expert_tokens`: the
(token, pick) pairs each held expert got, summed over layers and over
every decode step the process's arena ran (warm-up, ramp, window, traced
seconds and drain: the harness frees the decoder before a reader runs
and hands over no window edges, so the counter is the arena's whole
life; idle slots' junk rows count too). None where the program keeps no
such counter."""

import sys


def read(run):
    if run.get("kind") != "serve":
        return None
    try:
        from paddle_tpu import serving
        tokens = serving.last_counters.expert_tokens
        steps = serving.last_counters.steps
    except (ImportError, AttributeError):
        return None
    if tokens is None or not tokens.sum():
        return None
    print(f"[expert_load_peak_pct] {int(tokens.sum())} pairs on "
          f"{len(tokens)} held experts over {steps} steps: busiest "
          f"{int(tokens.max())}, mean {tokens.mean():.1f}, idlest "
          f"{int(tokens.min())}", file=sys.stderr)
    return 100.0 * float(tokens.max()) / float(tokens.mean())
