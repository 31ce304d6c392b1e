"""From a profiler trace to numbers: device busy and idle, a named
kernel's time, the operations that took most time and the longest idle
gaps by what the host was doing.

``read_xplane`` turns the ``.xplane.pb`` JAX's profiler writes into a
plain dict (what the tests keep a recording of); everything else works
on that dict. On a TPU v5e trace (read by hand, PR 24): each chip is a
plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO operation (a Pallas kernel appears under the name of its
``tpu_custom_call`` instruction), ``XLA Modules`` one per program run;
host threads are lines of the plane ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    return sorted((p for p in trace["planes"]
                   if re.match(r"^/device:TPU:\d+$", p["name"])),
                  key=lambda p: p["name"])


def line_events(plane: dict, line_name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def merged(events: Sequence[list]) -> List[Tuple[int, int]]:
    """Union of the events' intervals as sorted disjoint (start, end)."""
    out: List[Tuple[int, int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def busy_seconds(events: Sequence[list]) -> float:
    return sum(b - a for a, b in merged(events)) / 1e9


def kernel_events(events: Sequence[list], pattern: str) -> List[list]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def whole_runs(modules: Sequence[list]) -> int:
    """How many runs of the trace's main program (the module with most
    time) the ``XLA Modules`` line holds."""
    total: Dict[str, int] = {}
    for name, _, dur in modules:
        total[name] = total.get(name, 0) + dur
    if not total:
        return 0
    main = max(total, key=total.get)
    return sum(1 for e in modules if e[0] == main)


def short_name(name: str) -> str:
    """``%instr opcode`` of an ``XLA Ops`` event, whose name is the whole
    HLO instruction (operands and layouts included)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    m = re.search(r"(?<![\w\]])([a-z][a-z\-]*)\(", rest)
    tgt = re.search(r'custom_call_target="([^"]+)"', rest)
    op = m.group(1) if m else "?"
    return f"{head} {op}" + (f":{tgt.group(1)}" if tgt else "")


def family(name: str) -> str:
    """:func:`short_name` without the instruction's number: the sixteen
    layers' ``%copy.560`` ... ``%copy.582`` are one row of a table."""
    head, _, op = short_name(name).partition(" ")
    return re.sub(r"\.\d+$", "", head) + (" " + op if op else "")


def self_times(events: Sequence[list]) -> List[Tuple[str, int]]:
    """(name, nanoseconds not covered by a nested event) per event: a
    ``while`` and the operations of its body are all events of the one
    line, so plain durations count loops twice."""
    out, stack = [], []          # stack of [end, index]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            i = stack[-1][1]
            out[i] = (out[i][0], out[i][1] - dur)
        out.append((name, dur))
        stack.append([start + dur, len(out) - 1])
    return out


def top_ops(events: Sequence[list], k: int = 10) -> List[list]:
    """[[operation family, seconds of self time]] of the ``k`` families
    with most."""
    total: Dict[str, int] = {}
    for name, ns in self_times(events):
        fam = family(name)
        total[fam] = total.get(fam, 0) + ns
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in rows]


def all_host_spans(trace: dict, min_ns: int = 20_000) -> List[list]:
    """Every host-side span of at least ``min_ns``: the harness's own
    annotations and the runtime's (``PjitFunction(step)``,
    ``np.asarray(jax.Array)``, transfers)."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                out += [e for e in line["events"] if e[2] >= min_ns]
    return sorted(out, key=lambda e: e[1])


def idle_gaps(events: Sequence[list], spans: Sequence[list],
              k: int = 10, min_ns: int = 20_000) -> List[list]:
    """[[label, seconds]] of the ``k`` labels with most idle time. A gap
    between two busy intervals is labelled with the shortest host span
    that covers at least half of it (what the host was doing), else
    with the operation that ran before it; gaps under ``min_ns`` are
    one label."""
    busy = merged(events)
    ordered = sorted(events, key=lambda e: e[1] + e[2])
    ends = [e[1] + e[2] for e in ordered]
    spans = sorted(spans, key=lambda e: e[1])
    starts = [e[1] for e in spans]
    long_spans = [e for e in spans if e[2] > 50_000_000]
    total: Dict[str, int] = {}
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap = start - end
        if gap < min_ns:
            label = f"gaps under {min_ns // 1000} us"
        else:
            hi = bisect.bisect_left(starts, start)
            near = spans[max(0, hi - 64):hi] + long_spans
            cover = [e for e in near
                     if min(e[1] + e[2], start) - max(e[1], end)
                     >= gap / 2]
            if cover:
                label = min(cover, key=lambda e: e[2])[0][:120]
            else:
                i = bisect.bisect_right(ends, end) - 1
                label = "after " + family(ordered[max(i, 0)][0])
        total[label] = total.get(label, 0) + gap
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in rows]


def reduce(trace: dict, chips: int = 1) -> Optional[dict]:
    """What the result line and the readers need, averaged over the
    first ``chips`` device planes; None when no operation ran."""
    planes = device_planes(trace)[:chips]
    if not planes:
        return None
    spans = all_host_spans(trace)
    per = []
    for p in planes:
        ops = line_events(p, OPS_LINE)
        if not ops:
            continue
        per.append({
            "busy_s": busy_seconds(ops),
            "span_s": (max(e[1] + e[2] for e in ops) - ops[0][1]) / 1e9,
            "ops": ops, "modules": line_events(p, MODULES_LINE)})
    if not per:
        return None
    return {
        "busy_s": sum(x["busy_s"] for x in per) / len(per),
        "span_s": sum(x["span_s"] for x in per) / len(per),
        "ops": per[0]["ops"], "modules": per[0]["modules"],
        "breakdown": {"device_ops": top_ops(per[0]["ops"]),
                      "idle_gaps": idle_gaps(per[0]["ops"], spans)},
    }
