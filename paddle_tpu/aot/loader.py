"""Trace-free serving bring-up from an AOT artifact.

:func:`load_decoder` rebuilds a ready-to-serve
:class:`serving.BatchedDecoder` from an artifact directory WITHOUT
constructing the Python model object: the "model" handed to the
decoder is a :class:`ModelStub` that only answers the host-side
questions the arena asks (cache geometry, weight/buffer snapshots) and
raises a typed :class:`AotTraceError` from every forward/trace entry
point — so if any code path would re-trace (an unseen prompt bucket,
a feature the artifact doesn't cover), it fails loudly instead of
silently recompiling, and the trace-free claim is pinned by tests that
boot from an artifact whose stub (and whose spec factory) booby-trap
tracing.

The decoder's compiled-fn caches (``_step_fns`` keyed by
tokens-per-dispatch, ``_prefill_cache`` keyed by prompt bucket) are
pre-seeded with the artifact's deserialized executables, each wrapped
``jax.jit(exported.call)`` ONCE so per-tick dispatch is a cache hit.
``warm_step()`` then dispatches the rehydrated step program — which is
what flips ``ready``/``/readyz`` — without ever touching the stub's
booby-trapped trace methods.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .artifact import (AotError, check_fingerprint, load_programs,
                       load_state, read_manifest, resolve_artifact)


class AotTraceError(AotError):
    """A trace-free (AOT-booted) replica hit a trace entry point: an
    unseen prompt bucket, an uncovered decode mode, or a code path the
    artifact does not serialize. The request should be re-routed (or
    the artifact re-exported with the missing bucket), never silently
    recompiled — the stub has no real model to trace."""


class _StubAttn:
    """Attention-shaped metadata the arena constructor reads: cache
    geometry for contiguous arenas, (num_kv_heads, head_dim) for the
    paged allocator."""

    def __init__(self, num_kv_heads: Optional[int],
                 head_dim: Optional[int], leaf_specs):
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self._leaf_specs = leaf_specs  # [{shape, dtype}, ...] or None

    def init_cache(self, batch: int, capacity: int, dtype=None):
        if self._leaf_specs is None:
            raise AotTraceError(
                "aot stub: init_cache called on a paged artifact — the "
                "paged arena mints pools from the allocator, never from "
                "the model")
        return tuple(jnp.zeros(tuple(s["shape"]), s["dtype"])
                     for s in self._leaf_specs)


class _StubBlock:
    def __init__(self, attn):
        self.self_attn = attn


def _trace_trap(name: str):
    def trap(self, *a, **k):
        raise AotTraceError(
            f"aot stub: {name} reached — this replica was booted "
            "trace-free from a serialized artifact and has no Python "
            "model to trace. An unseen prompt bucket or uncovered "
            "decode mode needs a re-export (aot.export_decoder with "
            "buckets=...) or the ordinary trace path")
    trap.__name__ = name
    return trap


class ModelStub:
    """Stands in for the model object inside an AOT-booted
    BatchedDecoder. Serves the host-side surface (``blocks`` metadata,
    ``named_parameters``/``named_buffers`` snapshots from the
    artifact); every traced-forward entry point is a booby trap."""

    def __init__(self, cfg: Dict[str, Any], params: Dict[str, Any],
                 buffers: Dict[str, Any]):
        self._params = params
        self._buffers = buffers
        n = int(cfg["n_blocks"])
        if cfg["paged"]:
            attns = [_StubAttn(int(cfg["num_kv_heads"]),
                               int(cfg["head_dim"]), None)
                     for _ in range(n)]
        else:
            spec = cfg["cache_spec"]
            attns = [_StubAttn(None, None, spec[i]) for i in range(n)]
        self.blocks = [_StubBlock(a) for a in attns]

    def init_cache(self, batch: int, capacity: int, dtype=None):
        return [blk.self_attn.init_cache(batch, capacity, dtype)
                for blk in self.blocks]

    def named_parameters(self) -> Dict[str, Any]:
        return dict(self._params)

    def named_buffers(self) -> Dict[str, Any]:
        return dict(self._buffers)

    # every trace entry point the serving fn builders reach for —
    # set_parameters/set_buffers first (inject_state enters through
    # them before any logits method runs):
    set_parameters = _trace_trap("set_parameters")
    set_buffers = _trace_trap("set_buffers")
    _step_logits = _trace_trap("_step_logits")
    _chunk_logits = _trace_trap("_chunk_logits")
    _step_logits_paged = _trace_trap("_step_logits_paged")
    _chunk_logits_paged = _trace_trap("_chunk_logits_paged")
    _chunk_logits_rows = _trace_trap("_chunk_logits_rows")
    _chunk_logits_paged_rows = _trace_trap("_chunk_logits_paged_rows")
    forward = _trace_trap("forward")
    __call__ = _trace_trap("__call__")
    functional_call = _trace_trap("functional_call")


def load_decoder(path: str, *, check: bool = True):
    """Artifact directory (or checkpoint root) -> warmed-cache
    :class:`serving.BatchedDecoder` over a :class:`ModelStub` — the
    ``restore_and_run`` loader. No model construction, no tracing:
    the returned decoder's step/prefill caches hold the artifact's
    rehydrated executables; call ``warm_step()`` to dispatch once and
    flip ``ready``.

    ``check=False`` skips the fingerprint gate (tests only — a
    mismatched rehydrate can miscompile silently; serving always
    checks and falls back to the trace path instead)."""
    directory = resolve_artifact(path)
    man = read_manifest(directory)
    if check:
        check_fingerprint(man, directory)
    t0 = time.perf_counter()
    params, buffers = load_state(directory, man)
    cfg = man["decoder"]
    stub = ModelStub(cfg, params, buffers)

    key = None
    if cfg.get("sampled_key") is not None:
        try:
            key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(cfg["sampled_key"],
                                       np.uint32)))
        except Exception:
            key = jax.random.key(0)  # best-effort: stream differs,
            # distribution doesn't (greedy artifacts never get here)

    from .. import serving as _serving

    dec = _serving.BatchedDecoder(
        stub, int(cfg["slots"]), int(cfg["capacity"]),
        eos_id=cfg.get("eos_id"), key=key,
        temperature=float(cfg.get("temperature", 0.0)),
        top_k=int(cfg.get("top_k", 0)),
        top_p=float(cfg.get("top_p", 1.0)),
        prompt_bucket=int(cfg["prompt_bucket"]),
        pages=cfg.get("pages"),
        page_size=int(cfg.get("page_size") or 128),
        kv_dtype=cfg.get("kv_dtype"),
        decode_steps=int(cfg.get("decode_steps", 1)))

    step_fns, prefill_fns = load_programs(directory, man)
    dec._step_fns.update(step_fns)
    for lb, fn in prefill_fns.items():
        dec._prefill_cache[("paged", lb) if dec.paged else lb] = fn
    # cost-ledger provenance: the rehydrated programs register under
    # the SAME names the serving dispatch sites use, so when a tick
    # fills in their cost_analysis numbers the record still says
    # "aot" + which artifact. Zero-cost when telemetry is off.
    from ..telemetry import costs as _costs

    for kd in step_fns:
        _costs.note_aot_program(f"serving.step[k={kd}]",
                                artifact_id=man.get("artifact_id"))
    for lb in prefill_fns:
        name = (f"serving.prefill[paged,{lb}]" if dec.paged
                else f"serving.prefill[{lb}]")
        _costs.note_aot_program(name,
                                artifact_id=man.get("artifact_id"))
    # /statusz "aot" section source + bench TTFR provenance
    dec.aot_info = {
        "artifact": directory,
        "artifact_id": man.get("artifact_id"),
        "step": man.get("step"),
        "model_tag": man.get("model_tag"),
        "fingerprint": man.get("fingerprint"),
        "programs": {"steps": sorted(step_fns),
                     "prefill_buckets": sorted(prefill_fns)},
        "load_ms": (time.perf_counter() - t0) * 1e3,
    }
    return dec
