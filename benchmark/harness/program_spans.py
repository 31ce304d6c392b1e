"""The program's own spans and names, read back from a profiler trace.

``paddle_tpu`` enters a ``jax.profiler.TraceAnnotation`` at every phase
boundary of the serving tick, around every wait for the replica's lock
and around each train step (``telemetry/trace.py::Span``), and gives its
jitted programs and Pallas kernels names of its own (``pt_*``). They land
in whatever profiler session is running, so the harness's ``Tracer``
records them on the clock of the ``XLA Ops`` line.

The harness hands readers the reduced trace (``run["trace"]``), not the
file, so :func:`load` takes the newest ``.xplane.pb`` under
``.bench_trace/`` and parses it once per process into a plain dict:

    {"host":    [{"name", "start", "dur", "line", "stats"}, ...],
     "ops":     [{"name", "start", "dur", "stats"}, ...],
     "modules": [{"name", "start", "dur"}, ...],
     "path":    the file}

``host`` keeps the events whose name starts with one of
:data:`PROGRAM_PREFIXES`, from every host line (a host line is named
after the process, not the Python thread: select by span name, never by
line name); ``line`` numbers the line an event came from, so nesting is
containment on one line. A span's keyword arguments come back as the
event's ``stats``. ``ops`` and ``modules`` are the first chip's
``XLA Ops`` and ``XLA Modules`` lines; an operation's ``stats`` hold its
``op_name`` as ``tf_op`` once :func:`scope_ms_a_step` has asked for it
(see :func:`op_names`).
Times are nanoseconds. A program that has none of this (the parent of
the PR that added it) gives empty lists, and every reader returns None.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace_reduce
from .runtime import ROOT
from .trace_reduce import MODULES_LINE, OPS_LINE

PROGRAM_PREFIXES = ("serve.", "replica.", "train_step")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_cache: Dict[Tuple[str, float], dict] = {}


def newest_xplane(root: str = ROOT) -> Optional[str]:
    found = glob.glob(os.path.join(root, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _planes(path: str) -> Dict[str, memoryview]:
    """{plane name: its ``XPlane`` message} of a trace file
    (``xplane.proto``: XSpace.planes=1, XPlane.name=2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no == 1:
            name = next((bytes(v).decode() for n, v in _fields(plane)
                         if n == 2), "")
            out[name] = plane
    return out


def _metadata(plane) -> Tuple[Dict[int, str], List[Tuple[str, List[dict]]]]:
    """({stat id: stat name}, [(event name, its METADATA's stats, each a
    {field number: value})]) of one ``XPlane`` message (XPlane
    .event_metadata=4, .stat_metadata=5, both maps whose value is field
    2; XStatMetadata.id=1, .name=2; XEventMetadata.name=2, .stats=5).
    The plane's lines are skipped unread, so this costs time by the
    instruction, not by the event."""
    stat_names, events = {}, []
    for no, entry in _fields(plane):
        if no not in (4, 5):
            continue
        message = next((v for n, v in _fields(entry) if n == 2), b"")
        if no == 5:
            row = dict(_fields(message))
            stat_names[row.get(1, 0)] = bytes(row.get(2, b"")).decode()
        else:
            name, stats = "", []
            for n, v in _fields(message):
                if n == 2:
                    name = bytes(v).decode()
                elif n == 5:
                    stats.append(dict(_fields(v)))
            events.append((name, stats))
    return stat_names, events


def op_names(path: str, stat: str = "tf_op") -> Dict[str, str]:
    """{``XLA Ops`` event name: its ``op_name``} of the first chip's
    plane. A v5e trace keeps an instruction's ``op_name`` (the scopes it
    was traced under: ``jit(pt_train_step)/jvp(linear_ce)/while/...``)
    as the stat ``tf_op`` of the event's METADATA, which
    ``jax.profiler.ProfileData`` does not hand out (its ``stats`` are the
    event's own). So the file's ``XPlane`` messages are read here, field
    by field (XStat.metadata_id=1, .str_value=5, .ref_value=7). Only
    :func:`scope_ms_a_step` asks for this, once a trace."""
    planes = {name: plane for name, plane in _planes(path).items()
              if DEVICE_PLANE.match(name)}
    if not planes:
        return {}
    stat_names, events = _metadata(planes[min(planes)])
    out = {}
    for name, stats in events:
        for row in stats:
            if stat_names.get(row.get(1)) == stat:
                value = (bytes(row[5]).decode() if 5 in row
                         else stat_names.get(row.get(7), ""))
                if value:
                    out[name] = value
    return out


def _stats(event) -> dict:
    out = {}
    for key, value in event.stats:
        if key is not None:
            out[str(key)] = value if isinstance(value, (int, float)) \
                else str(value)
    return out


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, ops, modules, n_line = [], [], [], 0
    device = sorted((p for p in data.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)[:1]
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n_line += 1
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    host.append({"name": e.name, "start": int(e.start_ns),
                                 "dur": int(e.duration_ns), "line": n_line,
                                 "stats": _stats(e)})
    for plane in device:
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = [{"name": e.name, "start": int(e.start_ns),
                            "dur": int(e.duration_ns)} for e in line.events]
            elif line.name == OPS_LINE:
                ops = [{"name": e.name, "start": int(e.start_ns),
                        "dur": int(e.duration_ns), "stats": {}}
                       for e in line.events]
    for rows in (host, ops, modules):
        rows.sort(key=lambda e: (e["start"], -e["dur"]))
    return {"host": host, "ops": ops, "modules": modules, "path": path}


def load(run: dict, root: str = ROOT) -> Optional[dict]:
    """The parsed trace of this run, or None where the run was not
    traced (``run["trace"]`` empty) or no trace file is there."""
    if not run.get("trace"):
        return None
    path = newest_xplane(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = read_xplane(path)
    return _cache[key]


def named(events: Sequence[dict], name: str) -> List[dict]:
    return [e for e in events if e["name"] == name]


def inside(events: Sequence[dict], outer: dict,
           names: Optional[Iterable[str]] = None) -> List[dict]:
    """The events of ``outer``'s line that lie within it (``outer``
    itself left out), optionally only those called one of ``names``.
    ``events`` is sorted by start, as :func:`read_xplane` leaves it."""
    names = None if names is None else set(names)
    lo = bisect.bisect_left([e["start"] for e in events], outer["start"])
    end = outer["start"] + outer["dur"]
    out = []
    for e in events[lo:]:
        if e["start"] >= end:
            break
        if (e is not outer and e.get("line") == outer.get("line")
                and e["start"] + e["dur"] <= end
                and (names is None or e["name"] in names)):
            out.append(e)
    return out


def _rows(events: Sequence[dict]) -> List[list]:
    """The events as ``trace_reduce`` takes them: [name, start, dur]."""
    return [[e["name"], e["start"], e["dur"]] for e in events]


def covered_ns(events: Sequence[dict]) -> int:
    """Length of the union of the events' intervals."""
    return sum(b - a for a, b in trace_reduce.merged(_rows(events)))


def self_ns(events: Sequence[dict]) -> List[int]:
    """Per event, its nanoseconds not covered by an event nested in it
    (events of one line, sorted by start then longest first, as
    :func:`read_xplane` leaves them and ``trace_reduce.self_times``
    orders them): a ``while`` and the operations of its body are all
    events of the ``XLA Ops`` line, so plain durations would count a
    loop twice."""
    return [ns for _, ns in trace_reduce.self_times(_rows(events))]


def children_split(host: Sequence[dict], outer_name: str
                   ) -> Tuple[int, Dict[str, int]]:
    """(summed nanoseconds of the ``outer_name`` spans, {child name:
    summed nanoseconds of the spans directly inside them}): how a tick's
    time divides among its phases. A span inside a child is the child's,
    not counted again."""
    total, split = 0, {}
    for outer in named(host, outer_name):
        total += outer["dur"]
        edge = outer["start"]
        for e in inside(host, outer):
            if e["start"] >= edge:       # direct child, not a grandchild
                split[e["name"]] = split.get(e["name"], 0) + e["dur"]
                edge = e["start"] + e["dur"]
    return total, split


def median_ms(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) / 1e6 if values else None


def step_runs(modules: Sequence[dict], program: str) -> List[dict]:
    """The ``XLA Modules`` events of the program called ``program``:
    ``jit_<program>(<fingerprint>)`` in the trace, so ``pt_train_step``
    does not count ``pt_train_steps_4``, which holds four steps a run."""
    head = "jit_" + program + "("
    return [m for m in modules if m["name"].startswith(head)]


def kernel_ms_a_step(trace: Optional[dict], kernels: Sequence[str],
                     program: str = "pt_train_step"
                     ) -> Optional[Tuple[float, int, int]]:
    """(milliseconds a step, events, steps) of the ``XLA Ops`` events
    named ``%<kernel>.N`` for a kernel of ``kernels``, over the runs of
    ``program`` in the trace; None where either is missing."""
    if not trace:
        return None
    steps = len(step_runs(trace["modules"], program))
    heads = tuple("%" + k + "." for k in kernels)
    events = [e for e in trace["ops"] if e["name"].startswith(heads)]
    if not steps or not events:
        return None
    return sum(e["dur"] for e in events) / steps / 1e6, len(events), steps


def scope_ms_a_step(trace: Optional[dict], scope: str,
                    program: str = "pt_train_step"
                    ) -> Optional[Tuple[float, int, int]]:
    """(milliseconds of self time a step, events, steps) of the
    ``XLA Ops`` events one of whose stats names ``scope``. The first
    call on a trace read from a file fills the operations' ``tf_op``
    from :func:`op_names`; the serving cells never ask, and never pay
    for that pass over the file."""
    if not trace:
        return None
    if trace.get("path") and "scoped" not in trace:
        names = op_names(trace["path"])
        for e in trace["ops"]:
            if e["name"] in names:
                e["stats"]["tf_op"] = names[e["name"]]
        trace["scoped"] = True
    steps = len(step_runs(trace["modules"], program))
    own = self_ns(trace["ops"])
    hit = [ns for e, ns in zip(trace["ops"], own)
           if any(scope in v for v in e["stats"].values())]
    if not steps or not hit:
        return None
    return sum(hit) / steps / 1e6, len(hit), steps


def clocks_agree(host: Sequence[dict], modules: Sequence[dict],
                 program: str = "pt_decode_step") -> Tuple[int, int]:
    """(ticks with a dispatch and a fetch, those of them in which a run
    of ``program`` on the device starts between the tick's
    ``serve.step.dispatch`` start and its ``serve.step.fetch`` end): the
    host's spans and the device's lines are on one clock when nearly all
    do."""
    starts = sorted(m["start"] for m in step_runs(modules, program))
    ticks = hits = 0
    for tick in named(host, "serve.tick"):
        step = inside(host, tick, ("serve.step.dispatch", "serve.step.fetch"))
        d = named(step, "serve.step.dispatch")
        f = named(step, "serve.step.fetch")
        if not d or not f:
            continue
        ticks += 1
        lo, hi = d[0]["start"], f[-1]["start"] + f[-1]["dur"]
        i = bisect.bisect_left(starts, lo)
        hits += i < len(starts) and starts[i] <= hi
    return ticks, hits
