"""Dygraph model export — ``paddle.jit.save``-style (the reference's fluid
line only exports static Programs, io.py save_inference_model:898; its
successor API traces dygraph Layers. Here any ``nn.Layer`` exports to the
same StableHLO artifact (manifest v2) that ``static.load_inference_model``
and the C++ PJRT predictor (native/src/predictor.cc, ptserve) consume —
one serving format for both authoring modes, quantized models included
(buffers, e.g. frozen activation scales, are baked as constants)."""

from __future__ import annotations

import json
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .core.enforce import enforce
from .nn.layer import Layer
from .utils import compat as _compat



def save(layer: Layer, dirname: str, example_args: Sequence,
         input_names: Optional[Sequence[str]] = None,
         batch_polymorphic: bool = True, method: str = "forward",
         method_kwargs: Optional[dict] = None) -> None:
    """Export ``layer.<method>(*example_args)`` (eval mode) as an
    inference artifact. ``example_args``: arrays or ShapeDtypeStructs;
    leading dims export symbolically when ``batch_polymorphic``.
    ``method`` lets a model export an alternative jittable entry point —
    e.g. TransformerNMT.greedy_decode_cached, so the SERVING artifact
    carries the K/V-cached decode loop, not just the teacher-forced
    forward; ``method_kwargs`` bakes static non-array options (e.g.
    ``{"max_len": 128}``) into the traced artifact."""
    layer.eval()
    params = {k: jnp.asarray(v) for k, v in layer.named_parameters().items()}
    buffers = {k: jnp.asarray(v) for k, v in layer.named_buffers().items()}
    names = list(input_names or [f"x{i}" for i in range(len(example_args))])
    enforce(len(names) == len(example_args),
            "input_names length %s != example args %s", len(names),
            len(example_args))

    mkw = dict(method_kwargs or {})

    def infer_fn(params, feeds):
        out, _ = layer.functional_call(
            params, *[feeds[n] for n in names], buffers=buffers,
            training=False, method=method, **mkw)
        return list(out) if isinstance(out, (tuple, list)) else [out]

    feed_specs, polymorphic = {}, False
    for name, a in zip(names, example_args):
        shape = tuple(np.shape(a)) if not hasattr(a, "shape") else tuple(
            a.shape)
        dtype = getattr(a, "dtype", np.asarray(a).dtype)
        if batch_polymorphic and len(shape) >= 1:
            polymorphic = True
            sym = jax.export.symbolic_shape(
                ",".join(["b"] + [str(d) for d in shape[1:]]))
            feed_specs[name] = jax.ShapeDtypeStruct(sym, dtype)
        else:
            feed_specs[name] = jax.ShapeDtypeStruct(shape, dtype)
    param_specs = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for n, v in params.items()}
    try:
        exported = jax.export.export(jax.jit(infer_fn))(param_specs,
                                                        feed_specs)
    except Exception:
        if not polymorphic:
            raise
        polymorphic = False  # fall back to the example's concrete shapes
        for name, a in zip(names, example_args):
            shape = tuple(a.shape) if hasattr(a, "shape") else np.shape(a)
            dtype = getattr(a, "dtype", np.asarray(a).dtype)
            feed_specs[name] = jax.ShapeDtypeStruct(shape, dtype)
        exported = jax.export.export(jax.jit(infer_fn))(param_specs,
                                                        feed_specs)

    n_out = len(exported.out_avals)
    fetch_names = [f"out{i}" for i in range(n_out)]
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "program.stablehlo"), "wb") as f:
        f.write(exported.serialize())
    with open(os.path.join(dirname, "program.mlir.bc"), "wb") as f:
        f.write(exported.mlir_module_serialized)
    np.savez(os.path.join(dirname, "params.npz"),
             **{n: np.asarray(v) for n, v in params.items()})
    from .utils.atomic import atomic_write_text

    atomic_write_text(
        os.path.join(dirname, "manifest.json"),
        json.dumps({
            "feed_target_names": names,
            "fetch_target_names": fetch_names,
            "feed_shapes": {
                n: [-1 if polymorphic and i == 0 else int(d)
                    for i, d in enumerate(
                        a.shape if hasattr(a, "shape") else np.shape(a))]
                for n, a in zip(names, example_args)},
            "feed_dtypes": {n: np.dtype(feed_specs[n].dtype).name
                            for n in feed_specs},
            "arg_order": ([f"param:{n}" for n in sorted(params)] +
                          [f"feed:{n}" for n in sorted(feed_specs)]),
            "batch_polymorphic": polymorphic,
            # the producing toolchain identity (the aot-plane compat
            # gate) — consumers that rehydrate the serialized program
            # (rather than re-lowering the StableHLO) compare it
            "fingerprint": _compat.runtime_fingerprint(),
            "format": "stablehlo+npz/v2",
        }, indent=1))


def load(dirname: str):
    """Load a saved artifact as a predictor (shared loader with static)."""
    from .static.io import load_inference_model

    return load_inference_model(dirname)
