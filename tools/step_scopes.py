#!/usr/bin/env python3
"""Where a trace's programs spent their device time, by the program's
own scopes.

    python3 tools/step_scopes.py <checkout that holds .bench_trace> [program prefix ...]

Reads the newest ``.xplane.pb`` a ``benchmark/run.py --trace 1`` run
left under ``<checkout>/.bench_trace`` with that checkout's own
``benchmark/harness/scope_table.py`` (the one reader of the scopes
``paddle_tpu/telemetry/scopes.py`` lists) and prints, for the runs of
each program prefix (default ``pt_train_step``, ``pt_decode_step`` and
``pt_prefill_``), the table the benchmark's readers print (scope x
pass, ms a run) with twenty unscoped operation families in place of
five. A fusion carries its root's scope, so work the compiler fused
across a scope's edge shows under the neighbour. Nothing here is a
benchmark metric."""

import os
import sys


def main(argv):
    root = os.path.abspath(argv[0])
    sys.path.insert(0, root)
    from benchmark.harness import program_spans as P, scope_table

    trace = P.read_xplane(P.newest_xplane(root))
    for prefix in argv[1:] or ("pt_train_step", "pt_decode_step",
                               "pt_prefill_"):
        tab = scope_table.table(trace, prefix)
        if tab is None:
            print(f"{prefix}: no run in the trace, or no list of scopes "
                  "in this checkout")
            continue
        scope_table.show(prefix, tab, families=20, file=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
