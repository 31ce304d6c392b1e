"""The device time of one program's runs, split by the program's own
block scopes and by pass.

``paddle_tpu.telemetry.scopes`` lists the block-level scopes a step is
divided into (``embed``, ``attn``, ``mlp``, ``head``, ``optimizer`` and
the mixers' and the experts' own), and every operation traced under one
carries it in its ``op_name`` (the ``tf_op`` stat of an ``XLA Ops``
event, ``program_spans.op_names``). :func:`table` partitions the self
time of the operations that start inside the runs of ONE program
(``program_scopes.runs_of``: a serving trace holds several programs
that share scopes) by those names, so the scopes' times and the
remainder add up to the runs' self time exactly.

How an ``op_name`` is read (jax 0.9.0; the same on the CPU and the
chip)::

    jit(pt_train_step)/jvp(mlp)/dot_general                      forward
    jit(pt_train_step)/transpose(jvp(jvp()))/checkpoint/mlp/mul  backward
    .../transpose(jvp(jvp()))/checkpoint/rematted_computation/mlp/...
                                                                 recompute
    jit(pt_decode_step)/while/body/attn/pt_flash_decode          forward

A transform (``jvp(``, ``transpose(``, ``vmap(``) wraps the scopes that
were open when it was applied: it is unwrapped, and what it held are
path components like any other. A component with an argument of its
own (``jit(pt_train_step)``, ``jit(mlp)``) is a FUNCTION's name and
never a scope. A scope is matched as a whole component, never as a
substring (``attn`` is not in ``latent_attention``, ``head`` not in
``heads``), and an operation belongs to the OUTERMOST listed scope of
its path. The pass is ``recompute`` under ``rematted_computation``
(remat's second forward, which runs inside the backward pass), else
``backward`` under ``transpose(``, else ``forward``.

A fusion carries its root's ``op_name``, so work the compiler fused
across a scope's edge is counted with the root; an operation the
compiler made (a ``%copy`` of a weight, an asynchronous slice) may
carry none and lands in the remainder, listed by operation family.
Where the list cannot be imported (the parent of the PR that brought
it) there is no table and every reader gives None.
"""

from __future__ import annotations

import bisect
import re
import sys
from typing import Dict, Optional, Sequence, Tuple

from . import program_scopes, program_spans as P, trace_reduce as T

PASSES = ("forward", "recompute", "backward")
_TRANSFORM = re.compile(r"\b(?:jvp|transpose|vmap)\(")


def listed_scopes() -> Optional[Tuple[str, ...]]:
    try:
        from paddle_tpu.telemetry import scopes
    except ImportError:
        return None
    return tuple(scopes.SCOPES)


def components(op_name: str) -> list:
    """The path components of an ``op_name`` with the transforms
    unwrapped and the function names (``jit(f)``) left out."""
    out = []
    for part in _TRANSFORM.sub("", op_name).split("/"):
        if "(" not in part:
            out.append(part.rstrip(")"))
    return out


def place(op_name: str, scopes: Sequence[str]) -> Tuple[Optional[str], str]:
    """(the outermost of ``scopes`` in the path or None, the pass)."""
    parts = components(op_name)
    scope = next((p for p in parts if p in scopes), None)
    if "rematted_computation" in parts:
        return scope, "recompute"
    return scope, "backward" if "transpose(" in op_name else "forward"


def fill_op_names(trace: dict) -> None:
    """Give the trace's operations their ``tf_op`` from the file, once
    (as ``program_scopes.scope_ms_a_run`` does, under the same mark)."""
    if trace.get("path") and "scoped" not in trace:
        names = P.op_names(trace["path"])
        for e in trace["ops"]:
            if e["name"] in names:
                e["stats"]["tf_op"] = names[e["name"]]
        trace["scoped"] = True


def table(trace: Optional[dict], program_prefix: str) -> Optional[dict]:
    """The partition of the runs of ``program_prefix``::

        {"scopes":   {scope: {pass: ns}},       self time, listed scopes
         "unscoped": {family: [ns, op_name or the largest event's
                               instruction]},   the remainder
         "runs":     the ``XLA Modules`` events,
         "self_ns":  summed self time of the operations inside them,
         "module_ns": summed durations of the runs}

    None without a trace, a run of the program or the list of scopes.
    Computed once a trace and program."""
    scopes = listed_scopes()
    if scopes is None or not trace or not trace.get("ops"):
        return None
    done = trace.setdefault("scope_tables", {})
    if program_prefix in done:
        return done[program_prefix]
    runs = program_scopes.runs_of(trace, program_prefix)
    if not runs:
        return None
    fill_op_names(trace)
    starts = [m["start"] for m in runs]
    placed: Dict[str, tuple] = {}
    by_scope: Dict[str, Dict[str, int]] = {}
    unscoped: Dict[str, list] = {}
    total = 0
    for e, ns in zip(trace["ops"], P.self_ns(trace["ops"])):
        i = bisect.bisect_right(starts, e["start"]) - 1
        if i < 0 or e["start"] >= runs[i]["start"] + runs[i]["dur"]:
            continue
        total += ns
        op = e["stats"].get("tf_op", "")
        if op not in placed:
            placed[op] = place(op, scopes)
        scope, which = placed[op]
        if scope is None:
            row = unscoped.setdefault(T.family(e["name"]), [0, op, 0])
            row[0] += ns
            if not op and ns > row[2]:      # the compiler's own: say which
                row[1:] = e["name"][:160], ns
        else:
            passes = by_scope.setdefault(scope, {})
            passes[which] = passes.get(which, 0) + ns
    done[program_prefix] = {
        "scopes": by_scope, "runs": runs,
        "unscoped": {fam: row[:2] for fam, row in unscoped.items()},
        "self_ns": total, "module_ns": sum(m["dur"] for m in runs)}
    return done[program_prefix]


def show(program_prefix: str, tab: dict, families: int = 5,
         file=None) -> None:
    """The whole table: scope x pass in ms a run, then the largest
    unscoped families with the tail of their ``op_name`` or, for an
    operation the compiler made and gave none, the head of the
    instruction of the family's largest event."""
    file = file or sys.stderr
    n = len(tab["runs"])
    ms = lambda ns: ns / n / 1e6
    rest = sum(row[0] for row in tab["unscoped"].values())
    print(f"[scope_table] {program_prefix}: {n} runs, "
          f"{P.median_ms([m['dur'] for m in tab['runs']]):.3f} ms a run in "
          f"the median; self time {ms(tab['self_ns']):.3f} ms a run "
          f"({100 * tab['self_ns'] / max(tab['module_ns'], 1):.2f}% of the "
          f"runs' durations), of it unscoped {ms(rest):.3f}", file=file)
    for scope, passes in sorted(tab["scopes"].items(),
                                key=lambda kv: -sum(kv[1].values())):
        print(f"[scope_table]   {scope:15s} {ms(sum(passes.values())):9.3f}"
              + "".join(f"  {p} {ms(passes[p]):.3f}" for p in PASSES
                        if p in passes), file=file)
    for fam, (ns, op) in sorted(tab["unscoped"].items(),
                                key=lambda kv: -kv[1][0])[:families]:
        print(f"[scope_table]   unscoped {fam}: {ms(ns):.3f}  "
              + (f"...{op[-90:]}" if op.startswith("jit(") else op),
              file=file)


def shown_table(trace: Optional[dict], program_prefix: str
                ) -> Optional[dict]:
    """:func:`table` for a metric's reader: the first reader of a
    program prints the whole table on stderr, so a traced run shows the
    partition and not only the numbers registered."""
    tab = table(trace, program_prefix)
    if tab is not None and not tab.get("shown"):
        tab["shown"] = True
        show(program_prefix, tab)
    return tab


def scope_ms(trace: Optional[dict], program_prefix: str,
             scope: Optional[str] = None,
             passes: Sequence[str] = PASSES) -> Optional[float]:
    """Milliseconds of self time a run under ``scope`` (every listed
    scope where None) in ``passes``. None where there is no table or
    nothing of the program ran there."""
    tab = shown_table(trace, program_prefix)
    if tab is None:
        return None
    rows = (tab["scopes"].values() if scope is None
            else [tab["scopes"].get(scope, {})])
    hit = [row[p] for row in rows for p in passes if p in row]
    if not hit:
        return None
    return sum(hit) / len(tab["runs"]) / 1e6


def unscoped_ms(trace: Optional[dict], program_prefix: str
                ) -> Optional[float]:
    """Milliseconds of self time a run under no listed scope: 0.0, not
    None, where every operation has an owner."""
    tab = shown_table(trace, program_prefix)
    if tab is None:
        return None
    return (sum(row[0] for row in tab["unscoped"].values())
            / len(tab["runs"]) / 1e6)
