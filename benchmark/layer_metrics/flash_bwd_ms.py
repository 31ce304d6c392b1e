"""Kernels (`ops/pallas/flash_attention.py`, backward): device time a
train step spends in the `pt_flash_dq` and `pt_flash_dkdv` kernels: the
`XLA Ops` events named `%pt_flash_dq.N` and `%pt_flash_dkdv.N` over the
`pt_train_step` runs of the trace. Prints each kernel's share."""

import sys

from benchmark.harness import program_spans as P


def read(run):
    if run.get("kind") != "train":
        return None
    t = P.load(run)
    dq = P.kernel_ms_a_step(t, ("pt_flash_dq",))
    dkdv = P.kernel_ms_a_step(t, ("pt_flash_dkdv",))
    if dq is None or dkdv is None:
        return None
    print(f"[flash_bwd_ms] over {dq[2]} steps: pt_flash_dq {dq[0]:.2f} ms "
          f"a step ({dq[1] / dq[2]:.1f} events), pt_flash_dkdv "
          f"{dkdv[0]:.2f} ms ({dkdv[1] / dkdv[2]:.1f} events)",
          file=sys.stderr)
    return dq[0] + dkdv[0]
