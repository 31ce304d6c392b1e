"""Kernels (`nn/gated_attention.py`): how much of what a decode step's
attention reads lies in rings: the program's counter
`kv_positions_window` over its sum with `kv_positions_full`, in percent.
Both are returned by the decode step with its tokens
(`HybridForCausalLM.step_counters`) and summed by
`serving.ArenaCounters` over blocks, rows (an idle slot's row too) and
every decode step the process's arena ran (warm-up, ramp, window,
traced seconds and drain: the harness frees the decoder before a reader
runs and hands over no window edges). At a mean context of 7k positions
over 5 full layers and 15 rings of 512 it reads about 18; were every
layer's cache full-length the same positions would be 75% of the read.
None where the program keeps no such counters."""

import sys


def read(run):
    if run.get("kind") != "serve":
        return None
    try:
        from paddle_tpu import serving
        sums, steps = serving.last_counters.sums, serving.last_counters.steps
        ring, full = sums["kv_positions_window"], sums["kv_positions_full"]
    except (ImportError, AttributeError, KeyError, TypeError):
        return None
    if not (ring + full):
        return None
    print(f"[window_kv_share_pct] {int(ring)} positions read in rings, "
          f"{int(full)} in full caches over {steps} steps", file=sys.stderr)
    return 100.0 * float(ring) / float(ring + full)
