"""Kernels (`ops/retention.py::retention_step_parts` under
`models/hybrid.py::RetentionMixer`): device self time a decode step
spends in the retention mixers: the `XLA Ops` events traced under
`jax.named_scope("retention_step")` (projections, head norms, rotary
embedding, the state's update and read, output projection) that start
inside a `pt_decode_step` run, over those runs. The prefill programs
step the last prompt token under the same scope; their operations are
left out."""

import sys

from benchmark.harness import program_scopes, program_spans as P


def read(run):
    if run.get("kind") != "serve":
        return None
    got = program_scopes.scope_ms_a_run(P.load(run), ("retention_step",),
                                        "pt_decode_step")
    if got is None:
        return None
    ms, events, runs = got
    print(f"[retention_step_ms] {events} operations under retention_step "
          f"over {len(runs)} decode steps: {ms:.3f} ms of self time a "
          f"step", file=sys.stderr)
    return ms
