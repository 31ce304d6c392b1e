"""``MultiHeadAttention(scale=)``: the factor the scores are multiplied
by, where a model does not want 1/sqrt(head_dim). Every entry that
computes scores has to use the same one: the full causal forward, the
cached chunk, the per-row cached step on the masked XLA path, and the
per-row step on the flash-decode kernel (forced onto the CPU in
interpret mode). Head dimension 64 with scale 1/64, where the default
would be 1/8: an entry that dropped the argument would be off by far
more than the 2e-5 that float32 sums in another order allow (the kernel
keeps a running maximum, the plain path a whole softmax)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.ops import attention as A

B, T, CAP, D, H, KV = 2, 24, 64, 128, 2, 1
SCALE = 1.0 / 64
TOL = dict(rtol=2e-5, atol=2e-5)


def layer(scale):
    pt.seed(3)
    return nn.MultiHeadAttention(D, H, bias=False, num_kv_heads=KV,
                                 rotary=False, scale=scale).eval()


def plain(mha, x, scale):
    """Causal softmax attention written out."""
    hd = D // H
    q = (x @ mha.q_proj.weight).reshape(B, T, H, hd)
    k = (x @ mha.k_proj.weight).reshape(B, T, KV, hd).repeat(H // KV, 2)
    v = (x @ mha.v_proj.weight).reshape(B, T, KV, hd).repeat(H // KV, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(
        s - s.max(-1, keepdims=True)) / jnp.exp(
            s - s.max(-1, keepdims=True)).sum(-1, keepdims=True), v)
    return a.reshape(B, T, D) @ mha.out_proj.weight


@pytest.fixture(scope="module")
def x():
    return jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, T, D)), jnp.float32)


def test_every_entry_uses_the_scale(x):
    mha = layer(SCALE)
    want = plain(mha, x, SCALE)
    np.testing.assert_allclose(mha(x, causal=True), want, **TOL)
    # cached: a chunk of 16, then the rest a position at a time, the
    # two rows at cursors of their own
    ck, cv = mha.init_cache(B, CAP)
    out, ck, cv = mha.forward_chunk(x[:, :16], ck, cv, 0)
    np.testing.assert_allclose(out, want[:, :16], **TOL)
    with A.force_flash():
        assert A.decode_flash_ok(CAP, D // H)      # the kernel is taken
    for kernel in (False, True):
        k2, v2 = ck, cv
        for t in range(16, T):
            rows = jnp.full((B,), t, jnp.int32)
            with A.force_flash(kernel):
                out, k2, v2 = mha.forward_step_rows(
                    x[:, t:t + 1], k2, v2, rows, decode_kernel=kernel)
            np.testing.assert_allclose(out[:, 0], want[:, t], **TOL)


def test_the_default_is_unchanged(x):
    np.testing.assert_allclose(layer(None)(x, causal=True),
                               plain(layer(None), x, (D // H) ** -0.5),
                               **TOL)
    assert abs(float(jnp.max(jnp.abs(
        layer(None)(x, causal=True) - layer(SCALE)(x, causal=True)))
    )) > 1e-3


def test_paths_that_do_not_carry_it_refuse():
    with pytest.raises(EnforceError, match="seq_parallel"):
        nn.MultiHeadAttention(D, H, seq_parallel="ring", scale=SCALE)
    mha = layer(SCALE)
    with pytest.raises(EnforceError, match="paged"):
        mha.forward_step_paged(jnp.zeros((1, 1, D)), None, None, None,
                               jnp.zeros((1,), jnp.int32))
