"""FLOPs accounting + MFU (model-FLOPs utilization) reporting.

The reference's benchmark harness reports only examples/sec
(reference: benchmark/fluid/fluid_benchmark.py:296-300); a TPU-native
framework must also say how much of the chip those examples used. MFU =
(model FLOPs executed per second) / (peak chip FLOP/s). Model FLOPs come
from XLA's own cost model over the *lowered* (pre-backend-optimization)
module — this counts the math the program asks for (fwd+bwd+optimizer),
not remat duplicates, so it is the MFU numerator rather than an HFU one.

Peak numbers are the published per-chip peaks, in ONE table keyed by
the exact ``device_kind`` the device reports. A TPU that is not in the
table is an error, never a guess; the CPU has no peak.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from ..core.enforce import NotFoundError

# Published per-chip peaks by exact ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s). Add a row (with its source) to run on
# another chip.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def device_peaks(device: Optional[Any] = None
                 ) -> Optional[Dict[str, float]]:
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: first jax
    device). None on the CPU (no peak — callers omit MFU and rooflines
    rather than report against a made-up one); raises
    :class:`NotFoundError` for an accelerator the table does not list."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    row = DEVICE_PEAKS.get(device.device_kind)
    if row is None:
        raise NotFoundError(
            f"no published peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); known: "
            f"{sorted(DEVICE_PEAKS)} — add a sourced row to "
            "utils.flops.DEVICE_PEAKS")
    return row


def device_peak_flops(device: Optional[Any] = None,
                      dtype: str = "bf16") -> Optional[float]:
    """Peak FLOP/s for ``device``; None on the CPU. The bf16 peak is the
    denominator for float runs too: JAX's default matmul precision on
    TPU feeds the MXU bf16 inputs even for fp32 arrays, so the bf16
    peak IS the hardware ceiling of the emitted program."""
    row = device_peaks(device)
    if row is None:
        return None
    return row["int8_ops"] if dtype == "int8" else row["bf16_flops"]


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """The ONE place the persistent compilation cache is placed. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax already uses it: return it
    and set nothing in code. Otherwise point jax at the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    never moves). Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_REPO_ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def lowered_flops(jitted_fn, *args, n_partitions: int = 1,
                  **kwargs) -> Optional[float]:
    """GLOBAL FLOPs of one dispatch of ``jitted_fn(*args)`` per XLA's
    cost model.

    Prefers the *lowered* (pre-backend-optimization, pre-partitioning)
    module — the true MFU numerator, already global. Where a backend
    returns no analysis there, fall back
    to the *compiled* executable's analysis, which counts
    post-optimization, post-SPMD-partitioning FLOPs — a PER-DEVICE,
    HFU-flavoured number (remat duplicates included, eliminated math
    excluded) — scaled back to global by ``n_partitions`` (the mesh size
    the program spans; collective overhead makes this a mild
    overestimate of model FLOPs). The fallback costs an AOT compile;
    enable_compile_cache() makes the jit dispatch right after reuse it.
    Returns None when neither side is available — never raises."""
    try:
        lowered = jitted_fn.lower(*args, **kwargs)
    except Exception:
        return None
    for analyzed, scale in ((lambda: lowered, 1.0),
                            (lowered.compile,
                             float(max(1, n_partitions)))):
        try:
            analysis = analyzed().cost_analysis()
            if not analysis:
                continue
            flops = analysis.get("flops")
            if flops and flops > 0:
                return float(flops) * scale
        except Exception:
            continue
    return None


def mfu(flops_per_sec: Optional[float], device: Optional[Any] = None,
        dtype: str = "bf16", n_devices: int = 1) -> Optional[float]:
    """Model-FLOPs utilization in [0, 1], or None when either side is
    unknown. ``flops_per_sec`` is the GLOBAL program rate (XLA lowers the
    pre-partitioning module), so the peak scales by ``n_devices`` when
    the program spans a mesh."""
    if not flops_per_sec:
        return None
    peak = device_peak_flops(device, dtype=dtype)
    if not peak:
        return None
    return flops_per_sec / (peak * max(1, n_devices))
