"""Kernels (`ops/latent_attention.py::latent_read` under a pick): the records
a decode step's main attention was given over the records its rows
held, in percent (100: no selection; `index_topk` over the context
where every context is past it). From the program's counters
`dsa_positions_read` / `dsa_positions_live`, which the decode step
returns with its tokens (`HybridForCausalLM.step_counters`), summed by
`serving.ArenaCounters` over latent blocks, rows (an idle slot's junk
row too) and every decode step the process's arena ran (warm-up, ramp,
window, traced seconds and drain: the harness frees the decoder before
a reader runs and hands over no window edges). None where the program
keeps no such counters."""

import sys


def read(run):
    if run.get("kind") != "serve":
        return None
    try:
        from paddle_tpu import serving
        sums, steps = serving.last_counters.sums, serving.last_counters.steps
        live, got = sums["dsa_positions_live"], sums["dsa_positions_read"]
    except (ImportError, AttributeError, KeyError, TypeError):
        return None
    if not live:
        return None
    print(f"[dsa_read_share_pct] {int(got)} records read of {int(live)} "
          f"held over {steps} steps", file=sys.stderr)
    return 100.0 * float(got) / float(live)
