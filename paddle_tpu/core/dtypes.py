"""Dtype registry and mixed-precision policy.

Parity targets: the reference's dtype enum (framework.proto VarType.Type),
``platform::float16`` (reference: paddle/fluid/platform/float16.h) and the
mixed-precision decorator (reference:
python/paddle/fluid/contrib/mixed_precision/decorator.py:26,190).

TPU-first stance: bfloat16 is the native half type (no loss scaling needed);
a Policy captures (param_dtype, compute_dtype, output_dtype). An fp16-compat
mode with dynamic loss scaling exists for capability parity in
``paddle_tpu.optimizer.loss_scaler``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Union

import jax.numpy as jnp
import numpy as np

from .enforce import enforce

# Canonical name -> jnp dtype. Mirrors VarType.Type coverage.
_DTYPES = {
    "bool": jnp.bool_,
    "int8": jnp.int8,
    "uint8": jnp.uint8,
    "int16": jnp.int16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float64": jnp.float64,
    "complex64": jnp.complex64,
}

DTypeLike = Union[str, np.dtype, type]


def to_dtype(d: DTypeLike):
    if isinstance(d, str):
        enforce(d in _DTYPES, "unknown dtype name %s", d)
        return jnp.dtype(_DTYPES[d])
    return jnp.dtype(d)


def is_floating(d: DTypeLike) -> bool:
    return jnp.issubdtype(to_dtype(d), jnp.floating)


def is_integer(d: DTypeLike) -> bool:
    return jnp.issubdtype(to_dtype(d), jnp.integer)


@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision policy: where each dtype applies."""

    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    output_dtype: str = "float32"

    def cast_to_compute(self, x):
        return _cast_floating(x, to_dtype(self.compute_dtype))

    def cast_to_output(self, x):
        return _cast_floating(x, to_dtype(self.output_dtype))


# Named policies. "mixed_bf16" is the TPU default for training at scale:
# fp32 master params, bf16 compute (MXU-native), fp32 outputs/loss.
POLICIES = {
    "float32": Policy(),
    "bfloat16": Policy("bfloat16", "bfloat16", "bfloat16"),
    "mixed_bf16": Policy("float32", "bfloat16", "float32"),
    "mixed_fp16": Policy("float32", "float16", "float32"),
}

_current_policy = POLICIES["float32"]


def get_policy() -> Policy:
    return _current_policy


def set_policy(p: Union[str, Policy]) -> Policy:
    global _current_policy
    if isinstance(p, str):
        enforce(p in POLICIES, "unknown policy %s", p)
        p = POLICIES[p]
    _current_policy = p
    return p


@contextlib.contextmanager
def policy_scope(p: Union[str, Policy]):
    prev = get_policy()
    set_policy(p)
    try:
        yield get_policy()
    finally:
        set_policy(prev)


def compute_dtype_of(stored):
    """The type a layer computes in over a leaf of type ``stored``: the
    current policy's compute type where that is narrower (a mixed
    policy over float32 master weights), else ``stored`` itself (bf16
    weights served under the float32 policy stay bf16: no wide copy)."""
    stored = jnp.dtype(stored)
    compute = to_dtype(get_policy().compute_dtype)
    return compute if compute.itemsize < stored.itemsize else stored


def _cast_floating(x, dtype):
    import jax

    def cast_leaf(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map(cast_leaf, x)


def default_dtype():
    from .config import FLAGS

    return to_dtype(FLAGS.get("default_dtype"))
