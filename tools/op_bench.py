#!/usr/bin/env python
"""Config-driven single-op microbenchmark (reference:
paddle/fluid/operators/benchmark/op_tester.cc + op_tester_config.* — time
one op from a small spec, report latency).

Spec (JSON file or inline --op): a list of cases
  {"op": "ops.nn.conv2d", "args": {"x": [8, 64, 56, 56], "weight":
   [64, 64, 3, 3]}, "kwargs": {"stride": 1, "padding": 1},
   "dtype": "float32", "grad": true}
Array-valued entries in "args" are materialized with normal noise of that
shape. Prints one JSON line per case: {"op", "forward_ms", "grad_ms",
"repeat"}.

Timing uses the host-fetch fence: a scalar device-to-host read of the
output, since ``block_until_ready`` alone may return early.

Usage:
  python tools/op_bench.py --config cases.json
  python tools/op_bench.py --config tools/op_bench_cases.json   # hot-op set
  python tools/op_bench.py --op ops.math.matmul --shapes 1024x1024,1024x1024
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def resolve(path: str):
    import importlib

    mod_path, fn = path.rsplit(".", 1)
    mod = importlib.import_module(f"paddle_tpu.{mod_path}")
    return getattr(mod, fn)


def materialize(args_spec, dtype, rng):
    import jax.numpy as jnp

    out = {}
    for name, spec in args_spec.items():
        if isinstance(spec, list):
            out[name] = jnp.asarray(
                rng.normal(size=tuple(spec)).astype(dtype))
        elif isinstance(spec, dict) and "shape" in spec:
            # typed spec: {"shape": [...], "dtype": "int32",
            #              "low": 0, "high": 100} — integer operands
            # (labels, int8 tensors) for ops the float default can't feed
            sdt = spec.get("dtype", dtype)
            shape = tuple(spec["shape"])
            if "int" in sdt:
                lo = spec.get("low", 0)
                hi = spec.get("high", 100)
                out[name] = jnp.asarray(
                    rng.integers(lo, hi, shape).astype(sdt))
            else:
                out[name] = jnp.asarray(
                    rng.normal(size=shape).astype(sdt))
        else:
            out[name] = spec
    return out


def fence(x):
    """Host-fetch fence: forces the dependency chain."""
    leaf = x
    while isinstance(leaf, (tuple, list)):
        leaf = leaf[0]
    float(np.asarray(leaf).ravel()[0])


def time_fn(fn, args, repeat, warmup=3):
    import jax

    jfn = jax.jit(fn)
    for _ in range(warmup):
        out = jfn(**args)
    fence(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = jfn(**args)
    fence(out)
    return (time.perf_counter() - t0) / repeat * 1e3


def run_case(case, repeat):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    fn = resolve(case["op"])
    dtype = case.get("dtype", "float32")
    args = materialize(case.get("args", {}), dtype, rng)
    kwargs = case.get("kwargs", {})
    result = {"op": case["op"], "repeat": repeat}
    result["forward_ms"] = round(
        time_fn(lambda **a: fn(**a, **kwargs), args, repeat), 4)
    if case.get("grad"):
        float_args = {k: v for k, v in args.items()
                      if hasattr(v, "dtype") and
                      jnp.issubdtype(v.dtype, jnp.floating)}
        names = list(float_args)

        def loss(**a):
            out = fn(**a, **kwargs)
            leaf = out
            while isinstance(leaf, (tuple, list)):
                leaf = leaf[0]
            return jnp.sum(leaf ** 2)

        grad_fn = jax.grad(lambda vals: loss(**dict(args, **dict(
            zip(names, vals)))))
        vals = tuple(float_args[n] for n in names)
        result["grad_ms"] = round(
            time_fn(lambda vals: grad_fn(vals), {"vals": vals}, repeat), 4)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="JSON file with a list of cases")
    ap.add_argument("--op", help="single op path, e.g. ops.math.matmul")
    ap.add_argument("--shapes", help="comma-sep AxBxC shapes for --op "
                                     "positional args")
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--grad", action="store_true")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from paddle_tpu.utils.flops import enable_compile_cache

    enable_compile_cache()
    cases = []
    if args.config:
        with open(args.config) as f:
            cases = json.load(f)
    elif args.op:
        import inspect

        fn = resolve(args.op)
        pnames = list(inspect.signature(fn).parameters)
        shapes = [[int(d) for d in s.split("x")]
                  for s in (args.shapes or "").split(",") if s]
        cases = [{"op": args.op, "grad": args.grad,
                  "args": {pnames[i]: shp for i, shp in enumerate(shapes)}}]
    else:
        ap.error("need --config or --op")
    for case in cases:
        print(json.dumps(run_case(case, args.repeat)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
