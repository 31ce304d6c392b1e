"""Kernels (`nn/gated_attention.py`, full layers): the least time the
chip could take for what a decode step's full-attention mixers NEED,
over the time `gqa_full_step_ms` reads. The need is the family's, the
same work whatever implements it: the live keys and values once (the
program's counter `kv_positions_full`, the positions a step's rows read
summed over the full layers, a mean over every decode step the
process's arena ran, times `kv_bytes`: 4096 bytes a position a layer)
plus the mixers' weights once (`gqa_step_bytes`) at the HBM peak, or the
projections' and the read's operations (`gqa_step_flops`) at the bf16
peak, whichever is longer. Every slot's row is projected and read, live
or idle, and the counter counts every row. Prints which bound binds.
None where the scope or the counter is missing."""

import sys

from benchmark.harness import manifest

KIND, COUNTER, STEP_MS = "full_attention", "kv_positions_full", \
    "gqa_full_step_ms"


def positions_a_step(counter):
    """The counter's mean over the steps the arena ran, or None."""
    try:
        from paddle_tpu import serving
        c = serving.last_counters
        total, steps = c.sums[counter], c.steps
    except (ImportError, AttributeError, KeyError, TypeError):
        return None
    return float(total) / steps if steps else None


def read(run, kind=KIND, counter=COUNTER, step_ms=STEP_MS):
    ms = manifest.load_reader(step_ms)(run)
    positions = positions_a_step(counter)
    if ms is None or positions is None:
        return None
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    blocks = fam.kinds(dims, kind)
    slots = run["config"]["serve"]["slots"]
    a_layer = positions / blocks
    by_bytes = blocks * fam.gqa_step_bytes(dims, kind, a_layer) / peaks[
        "hbm_bytes_per_s"] * 1e3
    by_flops = blocks * fam.gqa_step_flops(dims, kind, slots, a_layer
                                           ) / peaks["bf16_flops_per_s"] * 1e3
    least_ms = max(by_bytes, by_flops)
    name = step_ms[:-len("_step_ms")] + "_roofline_pct"
    print(f"[{name}] {a_layer:.0f} positions a step a layer over {blocks} "
          f"{kind} blocks: {by_bytes:.3f} ms at the HBM peak, "
          f"{by_flops:.3f} ms at the bf16 peak "
          f"({'memory' if by_bytes >= by_flops else 'compute'}-bound) "
          f"against {ms:.3f} ms spent", file=sys.stderr)
    return 100.0 * least_ms / ms
