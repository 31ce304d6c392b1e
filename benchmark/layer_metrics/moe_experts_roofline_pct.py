"""Kernels (`nn/moe.py::dropless_moe`): the least time the chip could
take to read the held experts' weights once a layer (the family's
`expert_step_bytes`; memory-bound at a decode step's few rows an expert)
over the time `moe_experts_ms` reads. All held experts count as touched:
at 32 rows of 10 picks of 72 one is idle in a step with probability
(62/72)^32 = 0.8%, and the grouped product reads a weight block whether
or not its group has rows only if XLA's kernel does so (a share over
100% would mean it skips them: the count would then be too high)."""

import sys

from benchmark.harness import manifest


def read(run):
    ms = manifest.load_reader("moe_experts_ms")(run)
    if ms is None:
        return None
    fam, dims = run["family"], run["dims"]
    need = dims.layers * fam.expert_step_bytes(dims)
    least_ms = need / run["device"]["peaks"]["hbm_bytes_per_s"] * 1e3
    print(f"[moe_experts_roofline_pct] {need / 1e9:.3f} GB of held "
          f"experts over {dims.layers} layers: {least_ms:.3f} ms at the "
          f"HBM peak against {ms:.3f} ms spent", file=sys.stderr)
    return 100.0 * least_ms / ms
