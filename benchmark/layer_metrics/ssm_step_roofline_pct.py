"""Kernels (`ops/ssm.py::ssd_step`): the least time the chip could take
to move what a decode step's state-space blocks need (the family's
`ssm_step_bytes` a block: the float32 state of every slot read and
written, the convolution tails, the block's weights once; memory-bound)
over the time `ssm_step_ms` reads. Every slot counts, live or idle: the
step computes all rows. Prints the arena's bytes by kind from the
program's counter."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("ssm_step_ms")(run)
    if ms is None:
        return None
    fam, dims = run["family"], run["dims"]
    slots = run["config"]["serve"]["slots"]
    need = fam.kinds(dims, "mamba") * fam.ssm_step_bytes(dims, slots)
    least_ms = need / run["device"]["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"[ssm_step_roofline_pct] {need / 1e9:.3f} GB a step over "
          f"{fam.kinds(dims, 'mamba')} blocks and {slots} slots: "
          f"{least_ms:.3f} ms at the HBM peak against {ms:.3f} ms spent"
          f"{_state_bytes()}", file=sys.stderr)
    return 100.0 * least_ms / ms


def _state_bytes() -> str:
    try:
        from paddle_tpu import serving
        sb = serving.last_counters.state_bytes
    except (ImportError, AttributeError):
        return ""
    return (f"; arena {sb['kv'] / 1e9:.3f} GB of keys and values, "
            f"{sb['recurrent'] / 1e9:.3f} GB of recurrent state")
