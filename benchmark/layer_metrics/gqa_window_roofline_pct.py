"""Kernels (`nn/gated_attention.py`, sliding layers): the least time
the chip could take for what a decode step's sliding-window mixers
NEED, over the time `gqa_window_step_ms` reads. The need is the
family's: the rings' live keys and values once (the program's counter
`kv_positions_window`, each row's `min(t + 1, window)` summed over the
sliding layers, a mean over every decode step the process's arena ran,
times `kv_bytes`) plus the mixers' weights once (`gqa_step_bytes`) at
the HBM peak, or the projections' and the read's operations
(`gqa_step_flops`) at the bf16 peak, whichever is longer. None where
the scope or the counter is missing."""

from benchmark.harness import manifest


def read(run):
    return manifest.load_reader("gqa_full_roofline_pct")(
        run, kind="sliding_attention", counter="kv_positions_window",
        step_ms="gqa_window_step_ms")
