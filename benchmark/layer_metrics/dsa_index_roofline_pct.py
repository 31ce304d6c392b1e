"""Kernels (the indexer of `nn/latent.py::LatentAttention`): the least
time the chip could take for what a decode step's indexers need, over
the time `dsa_index_ms` reads. The need is the family's, the same work
whatever implements it: a block's live index keys read once (256 bytes
a position) and the indexer's weights once (`dsa_index_bytes`) at the
HBM peak, or its operations (`dsa_index_flops`: the projections of every
slot's row and a dot product a head a live position) at the bf16 peak,
whichever is longer. The live positions of a step are the window's mean
live rows a tick (`tick_tokens` / `ticks`) times the mean context of
the window's requests (`mean_context_tokens`: prompt plus half the
answer), as `mla_decode_roofline_pct` reckons them."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("dsa_index_ms")(run)
    if ms is None or not run.get("ticks") or not run.get(
            "mean_context_tokens"):
        return None
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    blocks = fam.kinds(dims, "latent")
    live = run["tick_tokens"] / run["ticks"]
    context = live * run["mean_context_tokens"]
    slots = run["config"]["serve"]["slots"]
    by_bytes = blocks * fam.dsa_index_bytes(dims, context) / peaks[
        "hbm_bytes_per_s"] * 1e3
    by_flops = blocks * fam.dsa_index_flops(dims, slots, context) / peaks[
        "bf16_flops_per_s"] * 1e3
    least_ms = max(by_bytes, by_flops)
    print(f"[dsa_index_roofline_pct] {live:.2f} live rows x "
          f"{run['mean_context_tokens']:.0f} positions over {blocks} "
          f"blocks: {by_bytes:.3f} ms at the HBM peak, {by_flops:.3f} ms "
          f"at the bf16 peak ({'memory' if by_bytes >= by_flops else 'compute'}"
          f"-bound) against {ms:.3f} ms spent", file=sys.stderr)
    return 100.0 * least_ms / ms
