"""Kernels (`ops/ssm.py::ssd_step` under `models/hybrid.py::SSDMixer`):
device self time a decode step spends in the state-space blocks: the
`XLA Ops` events traced under `jax.named_scope("ssm_step")` (projections,
convolution step, state update, gated norm) that start inside a
`pt_decode_step` run, over those runs. The prefill programs step the last
prompt token under the same scope; their operations are left out."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("ssm_step",),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[ssm_step_ms] {events} operations under ssm_step over "
          f"{len(runs)} decode steps: {ms:.3f} ms of self time a step",
          file=sys.stderr)
    return ms
