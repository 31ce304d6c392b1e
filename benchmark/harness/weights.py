"""Seeded weights, made by the benchmark for both sides.

Every leaf is a pure function of (seed, leaf name, element index): a
32-bit integer hash of the index (murmur3's finaliser) mapped to a
uniform value in [-a, a] with standard deviation ``INIT_STD``; norm
weights are 1. Integer arithmetic only, so the CPU and the chip, one
big jitted call and a leaf-by-leaf one, all give the same bits. The
program gets its weights from :func:`make_all` (one jitted call, on the
device, in the type it runs in); the reference regenerates its own,
layer by layer, with :func:`make_leaves`. The seed is a traced argument,
so one compiled generator serves every seed.
"""

from __future__ import annotations

import zlib
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

INIT_STD = 0.02
_A = INIT_STD * 3.0 ** 0.5


def leaf_shapes(dims) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every weight of the decoder ``dims`` describes.
    Linear weights are (in, out); the head is (hidden, vocab)."""
    h = dims.hidden
    out = {"embed.weight": (dims.vocab, h), "norm_f.weight": (h,)}
    if not dims.tied:
        out["lm_head"] = (h, dims.vocab)
    for i in range(dims.layers):
        out.update(layer_shapes(dims, i))
    return out


def layer_shapes(dims, i: int) -> Dict[str, Tuple[int, ...]]:
    h, f = dims.hidden, dims.ffn
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    p = f"blocks.{i}."
    return {p + "norm1.weight": (h,), p + "norm2.weight": (h,),
            p + "self_attn.q_proj.weight": (h, q),
            p + "self_attn.k_proj.weight": (h, kv),
            p + "self_attn.v_proj.weight": (h, kv),
            p + "self_attn.out_proj.weight": (q, h),
            p + "ffn.gate.weight": (h, f), p + "ffn.up.weight": (h, f),
            p + "ffn.down.weight": (f, h)}


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def leaf(seed_u32, name: str, shape, dtype):
    if len(shape) == 1:                      # norm scale
        return jnp.ones(shape, dtype)
    salt = _fmix32(seed_u32 ^ jnp.uint32(zlib.crc32(name.encode())))
    idx = (lax.broadcasted_iota(jnp.uint32, shape, 0)
           * jnp.uint32(shape[1])
           + lax.broadcasted_iota(jnp.uint32, shape, 1))
    bits = _fmix32(idx * jnp.uint32(0x9E3779B1) + salt)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return ((2.0 * u - 1.0) * jnp.float32(_A)).astype(dtype)


def seed_arg(seed: int):
    """``--seed`` (any whole number) as the generator's traced scalar."""
    return np.uint32(int(seed) % (1 << 32))


def make_leaves(seed: int, shapes: Dict[str, Tuple[int, ...]], dtype):
    """The named leaves in ``dtype``, one jitted call."""
    names = tuple(sorted(shapes))
    return _make(seed_arg(seed), names,
                 tuple(tuple(shapes[n]) for n in names),
                 jnp.dtype(dtype).name)


def make_all(seed: int, dims, dtype):
    return make_leaves(seed, leaf_shapes(dims), dtype)


def _make_impl(seed_u32, names, shapes, dtype):
    return {n: leaf(seed_u32, n, s, jnp.dtype(dtype))
            for n, s in zip(names, shapes)}


_make = jax.jit(_make_impl, static_argnums=(1, 2, 3))


def check_names(expected: Dict[str, Tuple[int, ...]],
                got: Iterable[Tuple[str, Tuple[int, ...]]]) -> None:
    """The program's parameter tree must be exactly the leaves the
    benchmark generates; anything else is a model this harness does not
    describe."""
    got = {k: tuple(v) for k, v in got}
    if got != {k: tuple(v) for k, v in expected.items()}:
        odd = sorted(set(got.items()) ^ set(expected.items()))[:6]
        raise ValueError(f"program parameters differ from the "
                         f"benchmark's description, e.g. {odd}")
