"""Kernels (`ops/latent_attention.py::causal_attention` through
`ops/pallas/flash_attention.py` in a TRAINING step): the least time the
chip could take for the causal latent attention a step needs over the
time of the flash kernels alone. The need is the family's
(`attention_flops`, `attention_bytes`): the USEFUL operations and bytes
at the published head widths (scores over `nope + rope`, values over
`v`), the lower triangle, forward once and the backward's four
products, q / k / v / o moved once at the bf16 the configuration
states. The time is the `XLA Ops` events named `%pt_flash_fwd.N`,
`%pt_flash_dq.N` and `%pt_flash_dkdv.N` over the `pt_train_step` runs
(`flash_roofline_pct` counts every `tpu_custom_call`, and the grouped
expert products are such calls too). The kernel pads the heads to 256
and remat runs the forward twice: both are in the time and not in the
need, so padding and recomputation read as a LOW share."""

import sys

from benchmark.harness import flops, program_spans as P

KERNELS = ("pt_flash_fwd", "pt_flash_dq", "pt_flash_dkdv")


def read(run):
    fam = run.get("family")
    if run.get("kind") != "train" or not hasattr(fam, "kinds"):
        return None
    got = P.kernel_ms_a_step(P.load(run), KERNELS)
    if got is None:
        return None
    ms, events, steps = got
    dims, mix = run["dims"], run["traffic"]
    need, bound = 0.0, None
    for backward in (False, True):
        s, bound = flops.roofline_seconds(
            fam.attention_flops(dims, mix["seq"], backward),
            fam.attention_bytes(dims, mix["seq"], 2, backward),
            run["device"]["peaks"])
        need += s
    need *= fam.kinds(dims, "latent") * mix["rows"]
    print(f"[mla_flash_roofline_pct] {bound}-bound; {events} kernel events "
          f"over {steps} steps: {ms:.2f} ms a step against "
          f"{need * 1e3:.2f} ms needed", file=sys.stderr)
    return 100.0 * need * 1e3 / ms
