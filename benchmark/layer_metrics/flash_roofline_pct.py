"""Kernels (`ops/pallas/flash_attention.py`, forward + backward): the
least time the chip could take for the causal attention a training step
needs (operations and bytes from the run's family, `benchmark/families/`:
lower triangle, forward once, backward's four matmuls, q/k/v/o moved
once, at the bf16 the configuration states) over the time of the step's
Pallas kernels in the device trace. On a v5e trace those are the `XLA Ops`
events whose instruction is a `custom-call` to `tpu_custom_call`
(`%jvp__.N` forward, `%rematted_computation.N` remat's forward again,
`%checkpoint.N` dq and dk/dv); the train step has no other. Remat's
second forward is in the time and not in the need."""

import sys

from benchmark.harness import flops, trace_reduce


def read(run):
    t = run.get("trace")
    if not t or run.get("kind") != "train":
        return None
    kernels = trace_reduce.kernel_events(t["ops"], "tpu_custom_call")
    steps = trace_reduce.whole_runs(t["modules"])
    if not kernels or not steps:
        return None
    fam, dims, seq = run["family"], run["dims"], run["traffic"]["seq"]
    need, bound = 0.0, None
    for backward in (False, True):
        s, bound = flops.roofline_seconds(
            fam.attention_flops(dims, seq, backward),
            fam.attention_bytes(dims, seq, 2, backward),
            run["device"]["peaks"])
        need += s
    need *= dims.layers * run["traffic"]["rows"] * steps
    spent = sum(e[2] for e in kernels) / 1e9
    print(f"[flash_roofline_pct] {bound}-bound; {len(kernels)} kernel "
          f"events over {steps} steps: {spent / steps * 1e3:.2f} ms a "
          f"step against {need / steps * 1e3:.2f} ms needed",
          file=sys.stderr)
    return 100.0 * need / spent
