"""Kernels (`ops/latent_attention.py::latent_read` under a pick): the least
time the chip could take for what a decode step's latent mixers need
UNDER THE SELECTION, over the time `mla_decode_ms` reads (the scope
keeps the read under the pick; the indexer is `dsa_index_ms`'s). The
need is the family's: a live row reads the records its pick names,
`min(context, index_topk)` of 1152 bytes, and the mixer's weights once
(`mla_decode_bytes` of `read_tokens`) at the HBM peak, or the absorbed
operations over those records (`mla_decode_flops`) at the bf16 peak,
whichever is longer. `mla_decode_roofline_pct` states the need of every
live record, which a mixer with an indexer does not have; this cell is
on this share and not on that one. Rows and contexts as there: the
window's mean live rows a tick, each at the window's mean context."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("mla_decode_ms")(run)
    if ms is None or not run.get("ticks") or not run.get(
            "mean_context_tokens"):
        return None
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    if not hasattr(fam, "read_tokens"):
        return None
    blocks = fam.kinds(dims, "latent")
    live = run["tick_tokens"] / run["ticks"]
    read_tokens = live * fam.read_tokens(dims, [run["mean_context_tokens"]])
    slots = run["config"]["serve"]["slots"]
    by_bytes = blocks * fam.mla_decode_bytes(dims, read_tokens) / peaks[
        "hbm_bytes_per_s"] * 1e3
    by_flops = blocks * fam.mla_decode_flops(
        dims, slots, read_tokens) / peaks["bf16_flops_per_s"] * 1e3
    least_ms = max(by_bytes, by_flops)
    print(f"[dsa_read_roofline_pct] {live:.2f} live rows x "
          f"{read_tokens / live:.0f} picked records over {blocks} blocks: "
          f"{by_bytes:.3f} ms at the HBM peak, {by_flops:.3f} ms at the "
          f"bf16 peak ({'memory' if by_bytes >= by_flops else 'compute'}"
          f"-bound) against {ms:.3f} ms spent", file=sys.stderr)
    return 100.0 * least_ms / ms
