"""Seams around the installed jax (0.9) that more than one module needs.

- :func:`memory_analysis` — ``compiled.memory_analysis()`` flattened to
  the plain dict of byte counts the cost ledger records.
- :func:`runtime_fingerprint` — (jax, jaxlib, platform) identity that
  gates serialized-executable artifacts.
- :func:`native_int8_allreduce` — feature probe for a runtime-native
  int8 AllReduce (EQuARX); every quantized-psum spelling in
  ``quant.collectives`` funnels its dispatch through it, so the
  hand-written ring retires the day the toolchain ships one.
"""

from __future__ import annotations

import jax


def memory_analysis(compiled):
    """``compiled.memory_analysis()`` as ONE plain dict of the fields the
    cost ledger records (peak temp/argument/output/generated bytes);
    ``{}`` where the backend reports no analysis."""
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    out = {}
    for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes",
                  "host_temp_size_in_bytes"):
        val = getattr(mem, field, None)
        if val is not None:
            out[field] = int(val)
    return out


def runtime_fingerprint():
    """(jax, jaxlib, platform) identity of THIS process — the compat
    gate for serialized-executable artifacts (``paddle_tpu.aot``,
    ``jit.save``). A serialized StableHLO program is only trusted to
    rehydrate under the toolchain that produced it; anything that
    compares these dicts funnels through here so the fields evolve in
    ONE place (a new field tightens every artifact at once)."""
    try:
        import jaxlib

        jaxlib_ver = getattr(jaxlib, "__version__", "unknown")
    except ImportError:  # jaxlib always ships with jax, but stay typed
        jaxlib_ver = "unknown"
    return {"jax": jax.__version__, "jaxlib": jaxlib_ver,
            "platform": jax.default_backend()}


def native_int8_allreduce():
    """Feature probe for a RUNTIME-NATIVE int8 AllReduce (the EQuARX
    in-XLA collective, PAPERS.md). No released jax/XLA exposes one
    today, so this returns None and the hand-written int8 ring in
    ``quant.collectives`` runs; the moment the toolchain grows one it
    is adopted here WITHOUT an API change anywhere else — every
    quantized collective funnels its dispatch through this probe.

    Resolution order (first hit wins):

    1. ``PT_NATIVE_INT8_ALLREDUCE=module:fn`` — an out-of-tree impl
       with signature ``f(x, *, axis_name, axis_size, group, key)``
       returning the summed array in ``x``'s dtype (the
       quantized_psum contract, nan-poison semantics included; the
       FULL contract, stochastic ``key`` included, is on the impl).
    2. a ``jax.lax.psum_quantized`` attribute (the anticipated
       upstream spelling), adapted to the same signature. The adapter
       is marked ``partial_contract = True`` — it cannot forward the
       per-group granularity or the stochastic-rounding key, so
       quantized_psum REFUSES it for ``key=`` (int8_sr) calls and
       keeps the ring: silently dropping the key would let rounding
       bias accumulate, the exact failure mode SR exists to prevent.
    3. None — callers run the hand-written ring.

    Read per call (cheap: one env lookup + one getattr) so tests can
    monkeypatch the env or this function without cache games."""
    import importlib
    import os

    spec = os.environ.get("PT_NATIVE_INT8_ALLREDUCE")
    if spec:
        from ..core.enforce import enforce

        mod, sep, fn = spec.partition(":")
        enforce(mod and sep and fn,
                "PT_NATIVE_INT8_ALLREDUCE must name 'module:fn', got %r",
                spec)
        return getattr(importlib.import_module(mod), fn)
    native = getattr(jax.lax, "psum_quantized", None)
    if native is not None:
        def adapted(x, *, axis_name, axis_size, group, key):
            return native(x, axis_name)

        adapted.partial_contract = True   # no group=/key= support
        return adapted
    return None
