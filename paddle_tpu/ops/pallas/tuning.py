"""Pallas kernel tuning table — the runtime-benchmark-picked kernel
capability (reference: paddle/fluid/operators/jit/README.md:1 — the jit
KernelPool benchmarks candidate implementations per shape and caches the
winner; cuDNN autotuning plays the same role for convs, reference:
operators/conv_cudnn_op.cu.cc workspace search).

Here the tunables are Pallas grid/block sizes (and the flash-vs-XLA
dispatch choice). ``tools/pallas_tune.py`` sweeps candidates ON THE REAL
CHIP and persists winners to ``tuned_blocks.json`` next to this file,
keyed by (kernel, device_kind, shape bucket, operand type); kernels
consult the table at call time and fall back to the static defaults
when no entry exists.
Entries tuned on one chip generation never apply to another (device_kind
is in the key).
"""

from __future__ import annotations

import functools
import json
import os
import threading
from typing import Dict, Optional

from ... import telemetry

_TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tuned_blocks.json")
_lock = threading.Lock()
_cache: Optional[Dict[str, dict]] = None
# keys set with persist=False — session-only overrides that must never
# reach the shared on-disk table
_session_only: set = set()


@functools.lru_cache(maxsize=1)
def _device_kind() -> str:
    # cached for the process: this sits on the eager dispatch path
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return "cpu"
    # the kind the device itself reports ("TPU v5 lite" -> "tpu_v5_lite")
    return d.device_kind.lower().replace(" ", "_")


def _load() -> Dict[str, dict]:
    global _cache
    with _lock:
        if _cache is None:
            try:
                with open(_TABLE_PATH) as f:
                    _cache = json.load(f)
            except (OSError, ValueError):
                _cache = {}
        return _cache


def _pow2_bucket(n: int) -> int:
    """Round up to the next power of two — one table entry serves the
    whole bucket."""
    b = 1
    while b < n:
        b *= 2
    return b


_DTYPE_TAGS = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def dtype_tag(dtype) -> str:
    """Short name of an operand type in a table key (``f32``, ``bf16``)."""
    import numpy as np

    name = np.dtype(dtype).name
    return _DTYPE_TAGS.get(name, name)


def attention_key(tq: int, tk: int, d: int, causal: bool,
                  kind: Optional[str] = None, dtype="float32",
                  e: Optional[int] = None) -> str:
    """Flash-attention bucket: pow2 sequence lengths x head_dim x mask
    x OPERAND TYPE (as :func:`decode_key` keys by ``pool_dtype``). The
    type of q/k/v decides the MXU rate and how much VMEM a score block
    takes, so blocks measured at bf16 never size an f32 call: a
    1024 x 1024 f32 score block is 4 MB a buffer. ``e`` is the value
    width where it is not the score width ``d`` (``d256e128``): the
    value block, the output block and the accumulator are that wide, so
    such a call has room, and winners, of its own; equal widths keep
    the key they had."""
    width = f"d{d}" if e in (None, d) else f"d{d}e{e}"
    return (f"flash_attention|{kind or _device_kind()}|"
            f"tq{_pow2_bucket(tq)}|tk{_pow2_bucket(tk)}|{width}|"
            f"{'causal' if causal else 'full'}|{dtype_tag(dtype)}")


def decode_key(capacity: int, d: int, kind: Optional[str] = None,
               pool_dtype: str = "f32") -> str:
    """Flash-decode bucket: capacity x head_dim x POOL DTYPE (t varies
    at runtime inside one compiled loop, heads only change the tiny row
    count). ``pool_dtype`` names the KV storage form — the int8 paged
    variant dequantizes in-kernel (different arithmetic intensity, its
    own winner), so entries are keyed per form. Float keys carry an
    explicit ``|pf32`` suffix; pre-dtype tables (no suffix) are honored
    for f32 lookups through :func:`get_tuned_decode`'s legacy fallback."""
    return (f"flash_decode|{kind or _device_kind()}|"
            f"cap{_pow2_bucket(capacity)}|d{d}|p{pool_dtype}")


def _legacy_decode_key(capacity: int, d: int,
                       kind: Optional[str] = None) -> str:
    """The pre-dtype (PR <15) decode key form — read-only back-compat."""
    return (f"flash_decode|{kind or _device_kind()}|"
            f"cap{_pow2_bucket(capacity)}|d{d}")


# keys already diagnosed as stale (warn ONCE per key per process) and
# the typed findings themselves (tests / CI assert on them)
_stale_dtype_seen: set = set()
_stale_dtype_findings: list = []


def stale_dtype_findings() -> list:
    """Typed PT-TUNE-501 findings emitted so far (cleared by
    :func:`reset_cache`)."""
    with _lock:
        return list(_stale_dtype_findings)


def _note_stale_dtype(key: str, legacy_key: str) -> None:
    """A device-matched decode entry exists under the LEGACY (pre-int8)
    key but the dtype-keyed entry is missing: the table predates the
    dtype-keyed schema for this shape. Silent fallback would quietly run
    static default blocks forever — emit a typed diagnostic instead so
    stale tables are visible (re-running tools/pallas_tune.py --decode
    on the chip clears it)."""
    import warnings

    from ...analysis.diagnostics import Diagnostic

    # check-and-record under _lock: concurrent decode traces (router
    # claim lanes) must not double-emit the warn-ONCE-per-key finding
    with _lock:
        if key in _stale_dtype_seen:
            return
        _stale_dtype_seen.add(key)
        diag = Diagnostic(
            code="PT-TUNE-501", severity="warning",
            message=(f"tuned_blocks.json has a device-matched decode entry "
                     f"at {legacy_key!r} but no dtype-keyed entry {key!r} "
                     f"— stale pre-int8 tuning table for this shape"),
            hint=("re-run tools/pallas_tune.py --decode on this chip to "
                  "record the dtype-keyed entries"),
            path=_TABLE_PATH)
        _stale_dtype_findings.append(diag)
    warnings.warn(str(diag), stacklevel=3)
    if telemetry.enabled():
        telemetry.registry().counter(
            "pt_tuning_stale_dtype_total",
            "decode tuning-table lookups that found only a pre-int8 "
            "legacy entry for a dtype-keyed shape").inc()


def get_tuned_decode(capacity: int, d: int, pool_dtype: str = "f32",
                     kind: Optional[str] = None) -> Optional[dict]:
    """Decode-table lookup under the dtype-keyed schema. f32 lookups
    fall back to the legacy (pre-dtype) key silently — same semantics,
    the on-disk chips' entries stay live AND a served legacy entry
    counts as a cache HIT (the kernel really launches with
    chip-measured blocks — the coverage signal must say so); other
    dtypes finding ONLY a legacy entry emit the typed PT-TUNE-501
    diagnostic and return None (static defaults run, but the staleness
    is visible)."""
    table = _load()
    key = decode_key(capacity, d, kind, pool_dtype)
    legacy_key = _legacy_decode_key(capacity, d, kind)
    entry = table.get(key)
    if entry is None and pool_dtype == "f32":
        entry = table.get(legacy_key)
    _count_lookup(entry is not None)   # ONE lookup, one hit-or-miss
    if entry is not None:
        return entry
    if pool_dtype != "f32" and table.get(legacy_key) is not None:
        _note_stale_dtype(key, legacy_key)
    return None


def matmul_key(m: int, n: int, k: int, kind: Optional[str] = None) -> str:
    return (f"quant_matmul|{kind or _device_kind()}|"
            f"m{_pow2_bucket(m)}|n{_pow2_bucket(n)}|k{_pow2_bucket(k)}")


def _count_lookup(hit: bool) -> None:
    """hit = a kernel launches with chip-measured blocks; miss = it
    runs on static defaults (the tuning-coverage signal)."""
    if telemetry.enabled():
        telemetry.registry().counter(
            "pt_tuning_cache_hits_total" if hit
            else "pt_tuning_cache_misses_total",
            "pallas tuning-table lookups "
            + ("served by" if hit else "absent from")
            + " tuned_blocks.json").inc()


def get_tuned(key: str) -> Optional[dict]:
    entry = _load().get(key)
    _count_lookup(entry is not None)
    return entry


def set_tuned(key: str, entry: dict, persist: bool = True) -> None:
    table = _load()
    with _lock:
        table[key] = entry
        if not persist:
            _session_only.add(key)
        else:
            _session_only.discard(key)
        if persist:
            # On DISK: union of disk and memory; disk wins on conflict
            # (a concurrent tuner's winners survive) except the key just
            # tuned, and memory keys absent from disk are re-persisted so
            # a corrupt/deleted file cannot shrink the write.
            # In MEMORY: our own entries win (persist=False overrides
            # stay deliberate); keys we lack adopt the disk value.
            disk = {}
            try:
                with open(_TABLE_PATH) as f:
                    disk = json.load(f)
            except (OSError, ValueError):
                pass
            merged = {k: v for k, v in table.items()
                      if k not in _session_only}
            merged.update(disk)
            merged[key] = entry
            for k, v in merged.items():
                table.setdefault(k, v)
            tmp = _TABLE_PATH + ".tmp"
            with open(tmp, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, _TABLE_PATH)


def reset_cache() -> None:
    """Drop the in-process cache (tests / after external table edits)."""
    global _cache
    with _lock:
        _cache = None
        _session_only.clear()
        _stale_dtype_seen.clear()
        del _stale_dtype_findings[:]
