"""Kernels (`nn/moe.py::dropless_moe` in a TRAINING step): the least
time the chip could take for the routed experts' products of a step over
the time `moe_train_experts_ms` reads. The need is the family's
(`expert_train_flops`, `expert_train_bytes`): three products in three
passes (forward, and two each backward) over the EXPECTED held pairs,
`rows x seq x top_k x held / experts` a layer, and the held weights read
once a pass with their float32 gradients written once; whichever of the
bf16 peak and the HBM peak takes longer. **The pair count is the
expectation under uniform picks, not the step's own**: a reader reaches
the trace and `run` only, and the step's counts stay on the device.
Remat's second forward is in the time and not in the need, as are the
sort, the gathers and every pair that lands on an absent expert."""

import sys

from benchmark.harness import flops, manifest


def read(run):
    fam = run.get("family")
    if not hasattr(fam, "expert_train_flops"):
        return None
    ms = manifest.load_reader("moe_train_experts_ms")(run)
    if ms is None:
        return None
    dims, mix = run["dims"], run["traffic"]
    layers = fam.kinds(dims, "experts")
    need, bound = flops.roofline_seconds(
        layers * fam.expert_train_flops(dims, mix["rows"] * mix["seq"]),
        layers * fam.expert_train_bytes(dims), run["device"]["peaks"])
    print(f"[moe_train_experts_roofline_pct] {bound}-bound; {layers} expert "
          f"layers need {need * 1e3:.3f} ms a step against {ms:.3f} ms "
          f"spent", file=sys.stderr)
    return 100.0 * need * 1e3 / ms
