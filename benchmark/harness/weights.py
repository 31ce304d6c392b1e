"""Seeded weights, made by the benchmark for both sides.

A leaf is what its family's ``leaf_rule`` says: ones, zeros, or seeded
uniform. A seeded leaf is a pure function of (seed, leaf name, element
index): a 32-bit integer hash of the element's row-major index
(murmur3's finaliser) mapped to a uniform value in [-a, a] with standard
deviation ``INIT_STD``. Integer arithmetic only, so the CPU and the
chip, one big jitted call and a leaf-by-leaf one, all give the same
bits. The program gets its weights from :func:`make_all` (one jitted
call, on the device, in the type it runs in); the reference regenerates
its own, layer by layer, with :func:`make_leaves`. The seed is a traced
argument, so one compiled generator serves every seed.
"""

from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INIT_STD = 0.02
_A = INIT_STD * 3.0 ** 0.5


def leaf_shapes(fam, dims) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every weight of the model ``dims`` describes."""
    out = dict(fam.top_shapes(dims))
    for i in range(dims.layers):
        out.update(fam.layer_shapes(dims, i))
    return out


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def leaf(seed_u32, name: str, shape, dtype, rule: str):
    """One leaf by its family's ``rule``. The element index is the
    row-major flat one in 32 wrapping bits, so a leaf of any rank holds
    the values of its rank-2 reshape."""
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    if rule != "uniform":
        raise ValueError(f"unknown leaf rule {rule!r} for {name}")
    salt = _fmix32(seed_u32 ^ jnp.uint32(zlib.crc32(name.encode())))
    idx = lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    for axis in range(len(shape) - 1):
        stride = math.prod(shape[axis + 1:]) % (1 << 32)
        idx = idx + (lax.broadcasted_iota(jnp.uint32, shape, axis)
                     * jnp.uint32(stride))
    bits = _fmix32(idx * jnp.uint32(0x9E3779B1) + salt)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return ((2.0 * u - 1.0) * jnp.float32(_A)).astype(dtype)


def seed_arg(seed: int):
    """``--seed`` (any whole number) as the generator's traced scalar."""
    return np.uint32(int(seed) % (1 << 32))


def rules_of(rule: Callable[[str, tuple], str],
             shapes: Dict[str, Tuple[int, ...]]) -> Tuple[str, ...]:
    """The rule of each leaf, in the sorted order of the names."""
    return tuple(rule(n, tuple(shapes[n])) for n in sorted(shapes))


def make_leaves(seed: int, shapes: Dict[str, Tuple[int, ...]], dtype,
                rule: Callable[[str, tuple], str]):
    """The named leaves in ``dtype``, one jitted call."""
    names = tuple(sorted(shapes))
    return _make(seed_arg(seed), names,
                 tuple(tuple(shapes[n]) for n in names),
                 rules_of(rule, shapes), jnp.dtype(dtype).name)


def make_all(seed: int, fam, dims, dtype):
    return make_leaves(seed, leaf_shapes(fam, dims), dtype, fam.leaf_rule)


def _make_impl(seed_u32, names, shapes, rules, dtype):
    return {n: leaf(seed_u32, n, s, jnp.dtype(dtype), r)
            for n, s, r in zip(names, shapes, rules)}


_make = jax.jit(_make_impl, static_argnums=(1, 2, 3, 4))


def check_names(expected: Dict[str, Tuple[int, ...]],
                got: Iterable[Tuple[str, Tuple[int, ...]]]) -> None:
    """The program's parameter tree must be exactly the leaves the
    benchmark generates; anything else is a model its family does not
    describe."""
    got = {k: tuple(v) for k, v in got}
    if got != {k: tuple(v) for k, v in expected.items()}:
        odd = sorted(set(got.items()) ^ set(expected.items()))[:6]
        raise ValueError(f"program parameters differ from the "
                         f"benchmark's description, e.g. {odd}")
