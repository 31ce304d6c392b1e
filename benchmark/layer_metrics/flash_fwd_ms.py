"""Kernels (`ops/pallas/flash_attention.py`, forward): device time a
train step spends in the `pt_flash_fwd` kernel: the `XLA Ops` events
named `%pt_flash_fwd.N` over the `pt_train_step` runs of the trace.
Remat runs the forward a second time in the backward pass; both runs
count. Prints the events a step."""

import sys

from benchmark.harness import program_spans as P


def read(run):
    if run.get("kind") != "train":
        return None
    got = P.kernel_ms_a_step(P.load(run), ("pt_flash_fwd",))
    if got is None:
        return None
    ms, events, steps = got
    print(f"[flash_fwd_ms] {events} pt_flash_fwd events over {steps} steps "
          f"({events / steps:.1f} a step): {ms:.2f} ms a step",
          file=sys.stderr)
    return ms
