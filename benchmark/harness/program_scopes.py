"""Device time of a named scope inside the runs of one program.

``program_spans.scope_ms_a_step`` sums a scope over the whole trace. A
serving trace holds several programs (the decode step, a prefill a
bucket), and a layer traced under one ``jax.named_scope`` runs in more
than one of them, so the readers of such a layer ask here: only the
``XLA Ops`` events that start inside an ``XLA Modules`` event of the
program count. Self time, as there: a loop and its body are all events
of the one line."""

from __future__ import annotations

import bisect
from typing import Optional, Sequence, Tuple

from . import program_spans as P


def runs_of(trace: dict, program_prefix: str) -> list:
    """The ``XLA Modules`` events of the programs whose name starts
    with ``program_prefix``: ``pt_decode_step`` is that program alone
    (``jit_pt_decode_step(``), ``pt_prefill_`` every prefill bucket."""
    head = "jit_" + program_prefix
    if not program_prefix.endswith("_"):
        head += "("
    return [m for m in trace["modules"] if m["name"].startswith(head)]


def scope_ms_a_run(trace: Optional[dict], scopes: Sequence[str],
                   program_prefix: str, op_heads: Sequence[str] = ()
                   ) -> Optional[Tuple[float, int, list]]:
    """(milliseconds of self time a run, events, the runs) of the
    operations inside the runs of the program that were traced under one
    of ``scopes`` (the scope is part of the operation's ``op_name``, the
    ``tf_op`` stat) or whose own name starts with one of ``op_heads``
    (an operation the compiler made, which carries no scope). None where
    the trace has no such run or no such operation: a program without
    the scope, as the parent of the PR that added it."""
    if not trace or not trace.get("ops"):
        return None
    if trace.get("path") and "scoped" not in trace:
        names = P.op_names(trace["path"])
        for e in trace["ops"]:
            if e["name"] in names:
                e["stats"]["tf_op"] = names[e["name"]]
        trace["scoped"] = True
    runs = runs_of(trace, program_prefix)
    if not runs:
        return None
    starts = [m["start"] for m in runs]
    heads = tuple(op_heads)
    total = events = 0
    for e, ns in zip(trace["ops"], P.self_ns(trace["ops"])):
        i = bisect.bisect_right(starts, e["start"]) - 1
        if i < 0 or e["start"] >= runs[i]["start"] + runs[i]["dur"]:
            continue
        op = e["stats"].get("tf_op", "")
        if any(s in op for s in scopes) or (heads and
                                            e["name"].startswith(heads)):
            total += ns
            events += 1
    if not events:
        return None
    return total / len(runs) / 1e6, events, runs
