"""Program spans — supersedes ``core/profiler.py``'s RecordEvent.

One span machinery for the whole framework: RAII/context-manager spans
(reference: paddle/fluid/platform/profiler.h:81 RecordEvent). A span is
first of all a ``jax.profiler.TraceAnnotation``, entered ALWAYS, so it
lands in every profiler session on the clock of the device's lines (the
rule, the cost and the sites are on :class:`Span`). While
``start_profiler()`` collects, spans are also kept host-side with
monotonic timestamps and a thread-local nesting stack, exported as

- chrome-trace JSON (``export_chrome_trace`` — the historical
  tools/timeline.py contract, preserved verbatim), and
- a structured JSONL event log (``export_jsonl`` — one JSON object per
  line with monotonic ns timestamps, name, duration, pid/tid, nesting
  depth and parent span; greppable/streamable where chrome-trace is
  load-the-whole-file).

Device-side tracing delegates to ``jax.profiler`` (XPlane /
TensorBoard — the TPU analog of CUPTI); jax is imported lazily (at the
first span) so the telemetry package stays import-light.

``core/profiler.py`` and ``fluid/profiler.py`` are thin shims over this
module. Compat invariant: ``_events`` is only ever mutated IN PLACE
(never rebound) — the shims import the list object itself.

Span durations optionally feed a metrics histogram: pass
``histogram=`` (a ``metrics.Histogram``) and the span observes its own
duration when telemetry is enabled — one timer, both sinks.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from . import metrics as _metrics

_lock = threading.Lock()
_events: List[Dict[str, Any]] = []   # in-place mutation only (shim compat)
_enabled = False
_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _tid() -> int:
    try:
        return threading.get_native_id()
    except AttributeError:  # pragma: no cover (py<3.8)
        return threading.get_ident() % 100000


class Span:
    """Context-manager program span.

    Always enters a ``jax.profiler.TraceAnnotation`` of its name, so the
    span shows in EVERY profiler session on the profiler's own clock —
    ``start_profiler`` here, ``POST /profilez``, a benchmark's tracer —
    whoever started it. With no session running the annotation is inert
    (one atomic load in the runtime). Keyword arguments ride the
    annotation and come back as the event's stats
    (``Span("serve.prefill", rid=7, plen=300)``); keep them to counts
    and ids, and keep spans at phase boundaries — per tick, per prefill,
    per train step, never per token or per layer.

    The host-side event list (nesting depth, parent, chrome-trace
    export) is kept only while ``start_profiler()`` collects."""

    __slots__ = ("name", "cat", "histogram", "_t0", "_ann", "_depth",
                 "_parent", "_pushed")

    def __init__(self, name: str, cat: str = "host", histogram=None,
                 **args):
        import jax  # lazy: keeps `import paddle_tpu.telemetry` light

        self.name = name
        self.cat = cat
        self.histogram = histogram
        self._t0 = 0.0
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._depth = 0
        self._parent = None
        self._pushed = False

    def __enter__(self):
        if _enabled:
            stack = _stack()
            self._depth = len(stack)
            self._parent = stack[-1].name if stack else None
            stack.append(self)
            self._pushed = True
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._pushed:
            # pop by identity, and even when collection was stopped
            # mid-span — an `if _enabled` guard here would leak the
            # stack entry and corrupt depth/parent for this thread in
            # every later profiler window
            self._pushed = False
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:
                stack.remove(self)
        if _enabled:
            with _lock:
                _events.append({
                    "name": self.name,
                    "cat": self.cat,
                    "ph": "X",
                    "ts": self._t0 / 1e3,  # chrome trace wants µs
                    "dur": (t1 - self._t0) / 1e3,
                    "pid": os.getpid(),
                    # REAL OS thread id: spans from named worker
                    # threads (pt-reader-*, pt-ckpt-async-writer,
                    # pt-fleet-watcher) must land in their own chrome
                    # lanes — the old get_ident()%100000 hash collided
                    # and carried no name
                    "tid": _tid(),
                    "args": {"depth": self._depth,
                             "parent": self._parent,
                             "thread": threading.current_thread().name},
                })
        if self.histogram is not None and _metrics.enabled():
            self.histogram.observe((t1 - self._t0) / 1e9)
        return False


def named(fn, name: str):
    """``fn`` under the ``__name__`` ``name``: ``jax.jit`` calls the
    program ``jit_<name>``, which is what a profile's ``XLA Modules``
    line and the HLO module carry. A stable name chosen by the program
    survives a rename of the Python function."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        return fn(*args, **kwargs)

    run.__name__ = run.__qualname__ = name
    return run


# historical names, kept as the same objects (API.spec / shim compat)
RecordEvent = Span


def record_event(name: str) -> Span:
    return Span(name)


def span(name: str, cat: str = "host", histogram=None) -> Span:
    return Span(name, cat, histogram)


def tracing() -> bool:
    return _enabled


def start_profiler(device_trace_dir: Optional[str] = None) -> None:
    """Begin collecting host spans; optionally also start a jax device
    trace."""
    global _enabled
    with _lock:
        _events.clear()
    _enabled = True
    if device_trace_dir:
        import jax

        jax.profiler.start_trace(device_trace_dir)


def stop_profiler(timeline_path: Optional[str] = None,
                  device_trace: bool = False) -> List[Dict[str, Any]]:
    """Stop collection; optionally write chrome-trace JSON
    (tools/timeline.py analog)."""
    global _enabled
    _enabled = False
    if device_trace:
        import jax

        jax.profiler.stop_trace()
    with _lock:
        events = list(_events)
    if timeline_path:
        export_chrome_trace(events, timeline_path)
    return events


def get_events() -> List[Dict[str, Any]]:
    """Copy of the collected span list (running or stopped)."""
    with _lock:
        return list(_events)


def reset() -> None:
    """Drop collected spans without toggling collection."""
    with _lock:
        _events.clear()


def export_chrome_trace(events: List[Dict[str, Any]], path: str) -> None:
    """Chrome-trace JSON with proper lanes: thread_name/process_name
    METADATA events are emitted for every (pid, tid) seen, so spans
    from named worker threads (pt-reader-*, pt-ckpt-async-writer,
    pt-fleet-watcher, ...) render in their own labeled lane instead of
    interleaving anonymously."""
    from ..utils.atomic import atomic_write_text

    meta: List[Dict[str, Any]] = []
    seen_pids: set = set()
    seen_tids: set = set()
    for e in events:
        pid, tid = e.get("pid"), e.get("tid")
        if pid is not None and pid not in seen_pids:
            seen_pids.add(pid)
            meta.append({"ph": "M", "name": "process_name",
                         "pid": pid, "tid": 0,
                         "args": {"name": f"pid {pid}"}})
        tname = (e.get("args") or {}).get("thread")
        if tname and (pid, tid) not in seen_tids:
            seen_tids.add((pid, tid))
            meta.append({"ph": "M", "name": "thread_name",
                         "pid": pid, "tid": tid,
                         "args": {"name": tname}})
    atomic_write_text(path, json.dumps(
        {"traceEvents": meta + list(events), "displayTimeUnit": "ms"}))


def export_jsonl(events: List[Dict[str, Any]], path: str) -> None:
    """Structured event log: one JSON object per line, monotonic ns
    timestamps (``ts_ns``/``dur_ns``), nesting depth + parent."""
    with open(path, "w") as f:
        for e in events:
            args = e.get("args", {})
            f.write(json.dumps({
                "name": e["name"],
                "cat": e.get("cat", "host"),
                "ts_ns": int(e["ts"] * 1e3),
                "dur_ns": int(e["dur"] * 1e3),
                "pid": e["pid"],
                "tid": e["tid"],
                "depth": args.get("depth", 0),
                "parent": args.get("parent"),
            }) + "\n")


@contextlib.contextmanager
def profiler(timeline_path: Optional[str] = None,
             device_trace_dir: Optional[str] = None):
    """``with profiler("/tmp/timeline.json"):`` — fluid.profiler.profiler
    analog."""
    start_profiler(device_trace_dir)
    try:
        yield
    finally:
        stop_profiler(timeline_path,
                      device_trace=device_trace_dir is not None)
