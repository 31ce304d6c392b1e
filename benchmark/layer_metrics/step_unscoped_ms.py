"""Model: device self time a decode step spends under NO listed scope,
over the `pt_decode_step` runs of the trace: the residual norms and adds
beside a recurrent or latent mixer, and operations the compiler made
without a scope (a weight's `%copy`, an asynchronous slice);
`harness/scope_table.py` prints it by operation family. 0.0 where every
operation has an owner; None for a program without the list of
scopes."""

from benchmark.harness import program_spans as P, scope_table


def read(run):
    if run.get("kind") != "serve":
        return None
    return scope_table.unscoped_ms(P.load(run), "pt_decode_step")
