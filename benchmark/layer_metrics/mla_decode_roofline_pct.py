"""Kernels (`ops/latent_attention.py::latent_read`): the least time the
chip could take for what a decode step's latent mixers need, over the
time `mla_decode_ms` reads. The need is the family's, the same work
whatever implements it: a block's live records read once (all heads
read the same 1152 bytes a position, for the score and for the value)
and the mixer's weights once (`mla_decode_bytes`) at the HBM peak, or
the absorbed step's operations (`mla_decode_flops`) at the bf16 peak,
whichever is longer. The live positions of a step are the window's mean
live rows a tick (`tick_tokens` / `ticks`) times the mean context of
the window's requests (`mean_context_tokens`: prompt plus half the
answer); every slot's row is projected, live or idle. Prints which
bound binds and the arena's bytes from the program's counters."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("mla_decode_ms")(run)
    if ms is None or not run.get("ticks") or not run.get(
            "mean_context_tokens"):
        return None
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    blocks = fam.kinds(dims, "latent")
    live = run["tick_tokens"] / run["ticks"]
    context = live * run["mean_context_tokens"]
    slots = run["config"]["serve"]["slots"]
    by_bytes = blocks * fam.mla_decode_bytes(dims, context) / peaks[
        "hbm_bytes_per_s"] * 1e3
    by_flops = blocks * fam.mla_decode_flops(dims, slots, context) / peaks[
        "bf16_flops_per_s"] * 1e3
    least_ms = max(by_bytes, by_flops)
    print(f"[mla_decode_roofline_pct] {live:.2f} live rows x "
          f"{run['mean_context_tokens']:.0f} positions over {blocks} "
          f"blocks: {by_bytes:.3f} ms at the HBM peak, {by_flops:.3f} ms "
          f"at the bf16 peak ({'memory' if by_bytes >= by_flops else 'compute'}"
          f"-bound) against {ms:.3f} ms spent{_arena()}", file=sys.stderr)
    return 100.0 * least_ms / ms


def _arena() -> str:
    try:
        from paddle_tpu import serving
        counters = serving.last_counters
        kv = counters.state_bytes["kv"]
    except (ImportError, AttributeError, KeyError, TypeError):
        return ""
    return (f"; arena {kv / 1e9:.3f} GB of latent records; mhc_unbalanced "
            f"{counters.sums.get('mhc_unbalanced')} over {counters.steps} "
            f"steps")
