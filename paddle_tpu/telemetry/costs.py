"""Program cost ledger — what every compiled executable costs, derived
once and attributed forever.

The repo caches compiled programs in three places (Trainer step variants
via ``parallel.plan.compile_step``, ``serving.BatchedDecoder``'s
``_step_fns``/prefill buckets, and AOT-rehydrated artifacts); until now
none of them could say what a dispatch *costs*. This module is the one
registry they all report into: per program it records XLA's own numbers
— ``cost_analysis()`` FLOPs + bytes accessed (the HBM traffic estimate)
and ``memory_analysis()`` peak temp bytes.

From a record plus a measured wall time the ledger derives the three
attribution currencies:

- **MFU** — program FLOPs / (wall x chip peak), the Gemma-study
  comparison number, now computed from the registry instead of
  hand-estimated per bench.
- **arithmetic intensity** — FLOPs / HBM bytes (FLOP per byte moved).
- **roofline verdict** — ``compute_bound`` when the program's intensity
  clears the backend's ridge point (peak FLOP/s / peak HBM byte/s),
  ``hbm_bound`` below it. Peaks come from the ONE table
  ``utils.flops.DEVICE_PEAKS`` (exact ``device_kind``); the CPU has no
  peak, so there the verdict is ``unknown``.

Instrumented call-sites go through :func:`ensure_program`, which is
zero-cost when telemetry is off (one ``enabled()`` check) and amortized
to a set lookup when on — the one extra ``lower().compile()`` per
program fingerprint rides the persistent compile cache. Benches that
want the numbers without enabling the whole telemetry plane call
:func:`analyze_callable` directly (an explicit opt-in).

Served on ``/statusz`` as the ``costs`` section; gauges:
``pt_program_flops`` / ``pt_program_hbm_bytes`` (per program) and
``pt_step_mfu`` (set by :func:`observe_step`).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from . import metrics as _metrics


def backend_peaks(device: Optional[Any] = None
                  ) -> Optional[Dict[str, Any]]:
    """Peak FLOP/s + HBM byte/s for ``device`` (default: first jax
    device) from ``utils.flops.DEVICE_PEAKS``. None on the CPU; an
    accelerator the table does not list raises (``NotFoundError``)."""
    from ..utils import flops as _flops

    if device is None:
        import jax

        device = jax.devices()[0]
    row = _flops.device_peaks(device)
    if row is None:
        return None
    return {"backend": device.platform,
            "device_kind": device.device_kind,
            "peak_flops": row["bf16_flops"],
            "peak_hbm_bytes_per_s": row["hbm_bytes_per_s"],
            "ridge_flops_per_byte": (row["bf16_flops"]
                                     / row["hbm_bytes_per_s"])}


def roofline(flops: Optional[float], hbm_bytes: Optional[float],
             device: Optional[Any] = None) -> Dict[str, Any]:
    """Roofline placement of one program: arithmetic intensity vs the
    backend's ridge point. ``verdict`` is ``"compute_bound"`` /
    ``"hbm_bound"`` / ``"unknown"`` (either side missing, or a device
    with no peak — the CPU)."""
    peaks = backend_peaks(device)
    ridge = peaks["ridge_flops_per_byte"] if peaks else None
    out = {"intensity_flops_per_byte": None,
           "ridge_flops_per_byte": round(ridge, 2) if ridge else None,
           "verdict": "unknown"}
    if flops and hbm_bytes:
        intensity = flops / hbm_bytes
        out["intensity_flops_per_byte"] = round(intensity, 3)
        if ridge:
            out["verdict"] = ("compute_bound" if intensity >= ridge
                              else "hbm_bound")
    return out


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_LEDGER: Dict[str, Dict[str, Any]] = {}  # program name -> record


@_metrics.cached_instruments
def _cost_metrics(reg):
    return {
        "mfu": reg.gauge(
            "pt_step_mfu",
            "model-FLOPs utilization of the last observed step "
            "(ledger FLOPs / wall / chip peak)"),
    }


def _analyze(fn, args: tuple, kwargs: Optional[dict],
             n_partitions: int = 1) -> Dict[str, Any]:
    """One ``lower().compile()`` pass over ``fn(*args)`` -> cost fields.

    Never raises: backends without an analysis yield None fields (the
    record still registers — provenance is worth keeping even when XLA
    won't cost the program). FLOPs prefer the LOWERED module (global,
    pre-partitioning — the MFU numerator); bytes/temp only exist on the
    compiled executable, so those are per-partition scaled by
    ``n_partitions`` like utils.flops.lowered_flops' fallback."""
    from ..utils import compat as _compat

    out = {"flops": None, "hbm_bytes": None, "peak_temp_bytes": None,
           "argument_bytes": None, "output_bytes": None}
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
    except Exception:
        return out
    scale = float(max(1, n_partitions))
    try:
        cost = lowered.cost_analysis() or {}
        flops = cost.get("flops")
        if flops and flops > 0:
            out["flops"] = float(flops)
    except Exception:
        pass
    try:
        compiled = lowered.compile()
    except Exception:
        return out
    try:
        cost = compiled.cost_analysis() or {}
        if out["flops"] is None:
            flops = cost.get("flops")
            if flops and flops > 0:
                out["flops"] = float(flops) * scale
        ba = cost.get("bytes accessed")
        if ba and ba > 0:
            out["hbm_bytes"] = float(ba) * scale
    except Exception:
        pass
    mem = _compat.memory_analysis(compiled)
    if mem.get("temp_size_in_bytes") is not None:
        out["peak_temp_bytes"] = int(mem["temp_size_in_bytes"])
    if mem.get("argument_size_in_bytes") is not None:
        out["argument_bytes"] = int(mem["argument_size_in_bytes"])
    if mem.get("output_size_in_bytes") is not None:
        out["output_bytes"] = int(mem["output_size_in_bytes"])
    return out


def _register(name: str, analysis: Dict[str, Any], *, origin: str,
              n_partitions: int, fingerprint: Optional[str],
              device=None) -> Dict[str, Any]:
    import jax

    if device is None:
        device = jax.devices()[0]
    rec = dict(analysis)
    rec["analyzed"] = True
    rec["program"] = name
    rec["origin"] = origin
    rec["backend"] = getattr(device, "platform", "unknown")
    rec["n_partitions"] = int(max(1, n_partitions))
    rec["fingerprint"] = fingerprint
    rec["roofline"] = roofline(rec.get("flops"), rec.get("hbm_bytes"),
                               device)
    with _lock:
        _LEDGER[name] = rec
    if _metrics.enabled():
        reg = _metrics.registry()
        if rec.get("flops"):
            reg.gauge("pt_program_flops",
                      "XLA cost-model FLOPs per dispatch",
                      labels={"program": name}).set(rec["flops"])
        if rec.get("hbm_bytes"):
            reg.gauge("pt_program_hbm_bytes",
                      "XLA cost-model bytes accessed per dispatch",
                      labels={"program": name}).set(rec["hbm_bytes"])
    return rec


def ensure_program(name: str, fn, args: tuple = (),
                   kwargs: Optional[dict] = None, *,
                   n_partitions: int = 1, origin: str = "jit",
                   fingerprint: Optional[str] = None) -> None:
    """Instrumented-call-site entry: register ``name`` in the ledger if
    telemetry is on and the program is not yet known. Zero-cost when
    telemetry is disabled; a set-membership check when already
    registered. Analysis failures register a provenance-only record, so
    a backend without cost_analysis never re-pays the probe."""
    if not _metrics.enabled():
        return
    with _lock:
        rec = _LEDGER.get(name)
        if rec is not None and rec.get("analyzed"):
            return
        # a provenance-only stub (note_aot_program) still needs its
        # numbers — keep its origin/artifact fields through the merge
        stub = dict(rec) if rec is not None else None
    analyzed = _analyze(fn, args, kwargs, n_partitions)
    if stub is not None:
        origin = stub.get("origin", origin)
    _register(name, analyzed, origin=origin,
              n_partitions=n_partitions, fingerprint=fingerprint)
    if stub is not None and stub.get("artifact_id") is not None:
        with _lock:
            _LEDGER[name]["artifact_id"] = stub["artifact_id"]
    return


def analyze_callable(name: str, fn, *args, n_partitions: int = 1,
                     origin: str = "bench",
                     **kwargs) -> Dict[str, Any]:
    """Explicit (non-gated) analysis + registration — the bench path.

    Unlike :func:`ensure_program` this runs regardless of the telemetry
    flag (calling it IS the opt-in) and returns the record, so a bench
    derives ``flops_per_sec``/MFU/roofline from the registry instead of
    a local estimate."""
    with _lock:
        if name in _LEDGER:
            return _LEDGER[name]
    return _register(name, _analyze(fn, args, kwargs, n_partitions),
                     origin=origin, n_partitions=n_partitions,
                     fingerprint=None)


def note_aot_program(name: str, *, artifact_id=None) -> None:
    """Mark an AOT-rehydrated program's provenance. The executable's
    cost fields land later at the first dispatch (ensure_program from
    the serving step path) — this pins *where it came from* even if the
    rehydrated module never yields an analysis. Zero-cost when
    telemetry is off."""
    if not _metrics.enabled():
        return
    with _lock:
        rec = _LEDGER.setdefault(
            name, {"program": name, "flops": None, "hbm_bytes": None,
                   "peak_temp_bytes": None, "roofline": None})
        rec["origin"] = "aot"
        rec["artifact_id"] = artifact_id


def get(name: str) -> Optional[Dict[str, Any]]:
    """The registered record for ``name`` (None when unknown)."""
    with _lock:
        rec = _LEDGER.get(name)
        return dict(rec) if rec else None


def derive_mfu(name: str, seconds: float, *,
               n_devices: int = 1) -> Optional[float]:
    """MFU of one dispatch of ``name`` taking ``seconds``, from the
    LEDGER's FLOPs and the backend peak table — the auditable path
    (registry in the numerator, never a caller-supplied estimate).
    None when the program is unknown, uncosted, or the device has no
    peak (the CPU)."""
    from ..utils import flops as _flops

    rec = get(name)
    if not rec or not rec.get("flops") or seconds <= 0:
        return None
    return _flops.mfu(rec["flops"] / seconds,
                      n_devices=max(n_devices, rec.get(
                          "n_partitions", 1)))


def observe_step(name: str, seconds: float, *,
                 n_devices: int = 1) -> Optional[float]:
    """Record a measured step time against program ``name``: sets the
    ``pt_step_mfu`` gauge from the ledger-derived MFU and returns it.
    Zero-cost when telemetry is off."""
    if not _metrics.enabled():
        return None
    m = derive_mfu(name, seconds, n_devices=n_devices)
    if m is not None:
        _cost_metrics()["mfu"].set(m)
    return m


def ledger() -> Dict[str, Dict[str, Any]]:
    """Snapshot of every registered record (copies — mutation-safe)."""
    with _lock:
        return {k: dict(v) for k, v in _LEDGER.items()}


def statusz_section() -> Dict[str, Any]:
    """The /statusz ``costs`` section: the full ledger plus the backend
    peak row the verdicts were judged against."""
    return {"programs": ledger(), "peaks": backend_peaks()}


def reset() -> None:
    """Drop every record (tests / between bench phases)."""
    with _lock:
        _LEDGER.clear()


__all__ = ["analyze_callable", "backend_peaks", "derive_mfu",
           "ensure_program", "get", "ledger", "note_aot_program",
           "observe_step", "reset", "roofline", "statusz_section"]
