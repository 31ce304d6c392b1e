"""The flash route's operand type follows the active policy.

Under ``mixed_bf16`` the activations are f32 and every ``Linear``
multiplies in bf16; ``flash_attention()`` narrows q/k/v the same way,
once, before its custom VJP, and hands the result back in the caller's
type. Under the ``float32`` and ``bfloat16`` policies nothing is cast.
CPU, interpret mode: what the kernels are handed and what comes back,
never a time.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.dtypes import policy_scope
from paddle_tpu.ops.attention import (flash_operand_dtype, force_flash,
                                      scaled_dot_product_attention,
                                      xla_attention)

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# d128 as in the benchmark's train cell; GQA 16 q / 8 kv heads is its
# head layout, MHA the plain one
HEADS = {"mha": (2, 2), "gqa16_8": (16, 8)}


def _qkv(h, kv, t=256, d=128, b=1, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda heads: jnp.asarray(
        rng.normal(size=(b, t, heads, d)).astype(np.float32))
    return mk(h), mk(kv), mk(kv), mk(h)


@pytest.fixture
def kernel_dtypes(monkeypatch):
    """Types of (q, k, v) as the custom VJP receives them, per call;
    ``.real`` is the custom VJP itself."""
    class Seen(list):
        real = staticmethod(FA._flash)

    seen = Seen()

    def spy(q, k, v, *rest):
        seen.append((q.dtype, k.dtype, v.dtype))
        return seen.real(q, k, v, *rest)

    monkeypatch.setattr(FA, "_flash", spy)
    return seen


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_mixed_bf16_kernel_gets_bf16_and_matches_xla(heads, kernel_dtypes):
    """f32 in, bf16 at the kernel, f32 out; output and the three
    gradients agree with exact attention on the SAME bf16-rounded
    inputs. Tolerance 5e-2 absolute; the largest reading is 3.4e-2, in
    dv under GQA where values reach 6.5 and one bf16 rounding of the
    output alone is 2.5e-2 (the kernel also rounds p and ds to bf16
    before the MXU; the reference rounds nothing)."""
    h, kv = HEADS[heads]
    q, k, v, ct = _qkv(h, kv)

    def flash_loss(q, k, v):
        return (scaled_dot_product_attention(
            q, k, v, causal=True, use_flash=True) * ct).sum()

    with force_flash(), policy_scope("mixed_bf16"):
        out = scaled_dot_product_attention(q, k, v, causal=True,
                                           use_flash=True)
        grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    assert kernel_dtypes and all(
        dts == (jnp.bfloat16,) * 3 for dts in kernel_dtypes)
    assert out.dtype == jnp.float32
    assert all(g.dtype == jnp.float32 for g in grads)

    rounded = [x.astype(jnp.bfloat16).astype(jnp.float32)
               for x in (q, k, v)]
    ref = xla_attention(*rounded, causal=True)
    ref_grads = jax.grad(
        lambda q, k, v: (xla_attention(q, k, v, causal=True) * ct).sum(),
        argnums=(0, 1, 2))(*rounded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=5e-2)
    for g, r, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=0,
                                   atol=5e-2, err_msg=f"d{name}")


@pytest.mark.parametrize("policy,dtype", [("float32", jnp.float32),
                                          ("float32", jnp.bfloat16),
                                          ("bfloat16", jnp.bfloat16),
                                          ("mixed_bf16", jnp.bfloat16),
                                          ("mixed_fp16", jnp.float32)])
def test_no_cast_where_nothing_narrows(policy, dtype, kernel_dtypes):
    """The kernel sees the caller's own type, and output and gradients
    are bit-equal to the custom VJP called directly at the resolved
    blocks: nothing was put around it. mixed_fp16 stays f32 (Mosaic
    refuses float16 operands)."""
    q, k, v, ct = (x.astype(dtype) for x in _qkv(2, 2, d=64))
    blocks = FA.resolve_block_sizes(256, 256, 64, True, dtype=dtype)
    assert blocks == (128,) * 4

    def via_route(q, k, v):
        return FA.flash_attention(q, k, v, causal=True)

    def direct(q, k, v):
        return kernel_dtypes.real(q, k, v, None, None, None, True, None,
                                  64 ** -0.5, 0.0, *blocks, True)

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) * ct).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with policy_scope(policy):
        assert flash_operand_dtype(dtype) == dtype
        out, got = via_route(q, k, v), grads(via_route)
    assert kernel_dtypes and all(
        dts == (dtype,) * 3 for dts in kernel_dtypes)
    assert out.dtype == dtype
    for a, b in zip((out, *got), (direct(q, k, v), *grads(direct))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_operand_dtype_narrows_only_to_bf16():
    with policy_scope("mixed_bf16"):
        assert flash_operand_dtype(jnp.float32) == jnp.bfloat16
        assert flash_operand_dtype(jnp.int32) == jnp.int32
    with policy_scope("bfloat16"):
        assert flash_operand_dtype(jnp.float32) == jnp.bfloat16
    assert flash_operand_dtype(jnp.float32) == jnp.float32
