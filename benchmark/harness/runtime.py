"""What every job needs from the machine: the chip guard, the compile
cache, a count of compilations, peak memory and the profiler."""

from __future__ import annotations

import glob
import os
import sys
import threading
from typing import Optional

from . import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised (exit code 3) when the cell's chips are not there."""

    def __init__(self, why: str):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


def require_chips(n: int) -> dict:
    """The ``device`` object of the result line, or exit 3 with no
    result: the first device is a TPU whose kind is in the benchmark's
    peak table, and at least ``n`` of them are attached. There is no
    switch that makes this pass elsewhere; tests hand the jobs a device
    description of their own instead of calling it."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"jax.devices()[0].platform is {d.platform!r}, not "
                     "'tpu'; the benchmark runs on nothing else")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    try:
        row = peaks.peaks_for(d.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return {"platform": d.platform, "kind": d.device_kind, "count": n,
            "peaks": row}


def place_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed place: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
    (the path is part of the cache key). Every program is cached,
    however small or quick, so a warm set-up compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations (cache loads included: either means
    a program the warm-up missed) while ``active``. JAX offers no way
    to remove a listener, so one is registered per process."""

    _lock = threading.Lock()
    _registered = False
    _live = []

    def __init__(self):
        self.count = 0
        self.names = []
        self.active = False
        with CompileCounter._lock:
            if not CompileCounter._registered:
                import jax.monitoring

                jax.monitoring.register_event_duration_secs_listener(
                    CompileCounter._on_event)
                CompileCounter._registered = True
            CompileCounter._live.append(self)

    @staticmethod
    def _on_event(event, duration, **kw):
        if event != COMPILE_EVENT:
            return
        for c in CompileCounter._live:
            if c.active:
                c.count += 1
                c.names.append(str(kw.get("fun_name", "?")))

    def close(self):
        self.active = False
        with CompileCounter._lock:
            if self in CompileCounter._live:
                CompileCounter._live.remove(self)


def memory_peak_bytes(n: int = 1) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest of the first ``n`` devices;
    None where the backend reports none (the CPU)."""
    import jax

    best = None
    for d in jax.devices()[:n]:
        v = (d.memory_stats() or {}).get("peak_bytes_in_use")
        if v is not None:
            best = max(best or 0, int(v))
    return best


class Tracer:
    """Profiles ``seconds`` of the window into ``<checkout>/.bench_trace``
    and returns the path of the ``.xplane.pb`` it wrote. The Python
    tracer is off: it slows the host loop it is there to observe."""

    def __init__(self, tag: str):
        self.dir = os.path.join(ROOT, ".bench_trace", tag)
        self.started = False

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True

    def stop(self) -> Optional[str]:
        import jax

        if not self.started:
            return None
        jax.profiler.stop_trace()
        self.started = False
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return max(found, key=os.path.getmtime) if found else None
