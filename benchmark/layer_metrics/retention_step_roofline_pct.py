"""Kernels (`ops/retention.py::retention_step_parts`): the least time
the chip could take to move what a decode step's retention mixers need
(the family's `retention_step_bytes` a block: the float32 state of
every slot read and written at its least size, `d (d + 1) / 2` products
a head whatever the program's tiling, and the mixer's weights once;
memory-bound) over the time `retention_step_ms` reads. Every slot
counts, live or idle: the step computes all rows. Prints the arena's
bytes by kind and the step's count of tiny denominators from the
program's counters."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("retention_step_ms")(run)
    if ms is None:
        return None
    fam, dims = run["family"], run["dims"]
    slots = run["config"]["serve"]["slots"]
    blocks = fam.kinds(dims, "retention")
    need = blocks * fam.retention_step_bytes(dims, slots)
    least_ms = need / run["device"]["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"[retention_step_roofline_pct] {need / 1e9:.3f} GB a step over "
          f"{blocks} blocks and {slots} slots: {least_ms:.3f} ms at the "
          f"HBM peak against {ms:.3f} ms spent{_state_bytes()}",
          file=sys.stderr)
    return 100.0 * least_ms / ms


def _state_bytes() -> str:
    try:
        from paddle_tpu import serving
        counters = serving.last_counters
        sb = counters.state_bytes
    except (ImportError, AttributeError):
        return ""
    small = counters.sums.get("retention_small_norm")
    return (f"; arena {sb['kv'] / 1e9:.3f} GB of keys and values, "
            f"{sb['recurrent'] / 1e9:.3f} GB of recurrent state; "
            f"retention_small_norm {small} over {counters.steps} steps")
