"""Flash-attention kernel vs XLA reference — fwd + grads, causal + full.

Runs the Pallas kernel in interpret mode on CPU (same code path that Mosaic
compiles on TPU), mirroring the reference OpTest check_output/check_grad
strategy (reference: tests/unittests/op_test.py:134) with the XLA composite
as the numpy-oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import xla_attention
from paddle_tpu.ops.pallas import flash_attention


def _rand_qkv(b=2, t=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32),
                             dtype=dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla_forward(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla_grads(causal):
    q, k, v = _rand_qkv(b=1, t=256, h=1, d=64)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(o * jnp.cos(o))  # nontrivial cotangent

    def loss_ref(q, k, v):
        o = xla_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_cross_attention_lengths(causal):
    # decoder-style tq != tk; causal must honour the tk-tq diagonal offset
    # (xla_attention's tril(..., tk - tq) semantics)
    q, _, _ = _rand_qkv(t=128)
    _, k, v = _rand_qkv(t=256, seed=1)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_short_seq_shrinks_blocks():
    q, k, v = _rand_qkv(t=64)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_grads(causal):
    """bf16 inputs through the backward kernels: exercises the
    quantize-to-input-dtype casts on p/ds (the bf16-native MXU precision
    contract) that float32 tests cannot reach — a wrong cast target
    breaks numerics here, not just on-chip speed."""
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    rng = np.random.default_rng(7)
    ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=128,
                                block_k=128, block_q_bwd=64,
                                block_k_bwd=128,
                                interpret=True).astype(jnp.float32)
                * ct).sum()

    def g(q, k, v):
        return (xla_attention(q, k, v, causal=causal).astype(jnp.float32)
                * ct).sum()

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gg):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-1, atol=1e-1)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kv_mask_matches_xla(causal):
    """Key-padding mask through the kernel (the ragged-batch/LoD serving
    form): masked keys contribute nothing; a fully-masked row outputs
    zeros — both matching the xla_attention oracle."""
    b, t = 2, 256
    q, k, v = _rand_qkv(b=b, t=t)
    rng = np.random.default_rng(3)
    lengths = np.array([200, 128])
    keep = jnp.asarray(np.arange(t)[None, :] < lengths[:, None])

    out = flash_attention(q, k, v, causal=causal, kv_mask=keep,
                          interpret=True)
    ref = xla_attention(q, k, v, mask=keep[:, None, None, :],
                        causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # fully-masked batch row -> zeros (flash-kernel convention both paths)
    none_keep = jnp.asarray(np.zeros((b, t), bool))
    out0 = flash_attention(q, k, v, causal=causal, kv_mask=none_keep,
                           interpret=True)
    assert float(jnp.max(jnp.abs(out0))) == 0.0


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kv_mask_grads_match_xla(causal):
    b, t = 2, 256
    q, k, v = _rand_qkv(b=b, t=t)
    rng = np.random.default_rng(5)
    keep = jnp.asarray(np.arange(t)[None, :] < np.array([224, 96])[:, None])
    ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal, kv_mask=keep,
                                interpret=True) * ct).sum()

    def g(q, k, v):
        return (xla_attention(q, k, v, mask=keep[:, None, None, :],
                              causal=causal) * ct).sum()

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gf, gg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)


def test_dispatch_routes_key_padding_mask_to_flash(monkeypatch):
    """scaled_dot_product_attention sends (B,1,1,Tk) keep-masks to the
    flash kernel and arbitrary per-query masks to XLA."""
    from paddle_tpu.ops import attention as A

    called = {}

    def fake_flash(q, k, v, **kw):
        called["kv_mask"] = kw.get("kv_mask")
        return q

    monkeypatch.setattr(A, "_get_flash", lambda: fake_flash)
    monkeypatch.setattr(A, "_flash_ok", lambda *a, **k: True)
    q = jnp.zeros((2, 128, 2, 64), jnp.float32)

    keep4 = jnp.ones((2, 1, 1, 128), bool)
    A.scaled_dot_product_attention(q, q, q, mask=keep4)
    assert called["kv_mask"].shape == (2, 128)

    called.clear()
    per_query = jnp.ones((2, 1, 128, 128), bool)
    out = A.scaled_dot_product_attention(q, q, q, mask=per_query)
    assert "kv_mask" not in called  # arbitrary mask stays on XLA


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_matches_xla(causal):
    """Packed-batch attention (segment ids): positions attend only
    within their own segment — the padding-free pretraining layout."""
    b, t = 2, 256
    q, k, v = _rand_qkv(b=b, t=t, seed=11)
    # rows packed as [seg0 x 96 | seg1 x 100 | seg2 x 60] and
    # [seg0 x 256] respectively
    ids = np.zeros((b, t), np.int32)
    ids[0, 96:196] = 1
    ids[0, 196:] = 2
    ids_j = jnp.asarray(ids)

    out = flash_attention(q, k, v, causal=causal, segment_ids=ids_j,
                          interpret=True)
    ref = xla_attention(q, k, v, causal=causal, segment_ids=ids_j)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_grads_match_xla(causal):
    b, t = 2, 256
    q, k, v = _rand_qkv(b=b, t=t, seed=13)
    rng = np.random.default_rng(13)
    ids = np.zeros((b, t), np.int32)
    ids[0, 128:] = 1
    ids[1, 64:160] = 1
    ids[1, 160:] = 2
    ids_j = jnp.asarray(ids)
    ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def f(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                segment_ids=ids_j, block_q=128,
                                block_k=128, block_q_bwd=64,
                                block_k_bwd=128, interpret=True) * ct).sum()

    def g(q, k, v):
        return (xla_attention(q, k, v, causal=causal,
                              segment_ids=ids_j) * ct).sum()

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gf, gg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)


def test_flash_segment_ids_compose_with_kv_mask():
    """Packing + padding together: the tail of each row is padding
    (kv_mask False) AND its own segment."""
    b, t = 2, 256
    q, k, v = _rand_qkv(b=b, t=t, seed=17)
    ids = np.zeros((b, t), np.int32)
    ids[:, 128:] = 1
    keep = jnp.asarray(np.arange(t)[None, :] < np.array([224, 192])[:, None])
    ids_j = jnp.asarray(ids)
    out = flash_attention(q, k, v, segment_ids=ids_j, kv_mask=keep,
                          interpret=True)
    ref = xla_attention(q, k, v, mask=keep[:, None, None, :],
                        segment_ids=ids_j)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


class TestFlashDropout:
    """In-kernel attention-probability dropout: the counter-based mask is
    coordinate-addressed, so fwd and bwd (even with DIFFERENT block
    sizes) rebuild it bit-identically, and a pure-jnp reference sharing
    the same mask must match exactly."""

    @staticmethod
    def _ref_keep(key, b, h, t, p):
        """The mask flash builds, reconstructed outside the kernel: hash
        of (per-(b,h) seed, global row, global col) — block-size AND
        sharding invariant by construction."""
        from paddle_tpu.ops.pallas.flash_attention import _dropout_keep

        seed = jax.random.randint(key, (b, h), -2 ** 31, 2 ** 31 - 1,
                                  dtype=jnp.int32)
        rows = []
        for bh in range(b * h):
            rows.append(_dropout_keep(seed[bh // h, bh % h], 0, 0, t, t, p))
        return jnp.stack(rows).reshape(b, h, t, t)

    @staticmethod
    def _ref_attn(q, k, v, keep, p, causal=False):
        scale = q.shape[-1] ** -0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            t = logits.shape[-1]
            logits = jnp.where(jnp.tril(jnp.ones((t, t), bool)), logits,
                               jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(keep, probs / (1.0 - p), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_matches_shared_mask_reference(self, causal):
        b, t, h, p = 2, 256, 2, 0.2
        q, k, v = _rand_qkv(b=b, t=t, h=h)
        key = jax.random.PRNGKey(42)
        out = flash_attention(q, k, v, causal=causal, dropout_p=p,
                              dropout_key=key, interpret=True)
        keep = self._ref_keep(key, b, h, t, p)
        ref = self._ref_attn(q, k, v, keep, p, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_shared_mask_reference(self):
        b, t, h, p = 2, 256, 2, 0.15
        q, k, v = _rand_qkv(b=b, t=t, h=h, seed=23)
        key = jax.random.PRNGKey(7)
        rng = np.random.default_rng(23)
        ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def f(q, k, v):
            # distinct bwd blocks: the coordinate-addressed mask must
            # survive a different bwd decomposition
            return (flash_attention(q, k, v, dropout_p=p, dropout_key=key,
                                    block_q=128, block_k=128,
                                    block_q_bwd=64, block_k_bwd=128,
                                    interpret=True) * ct).sum()

        keep = self._ref_keep(key, b, h, t, p)

        def g(q, k, v):
            return (self._ref_attn(q, k, v, keep, p) * ct).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=2e-4, atol=2e-4)

    def test_determinism_and_key_sensitivity(self):
        q, k, v = _rand_qkv()
        k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
        o1 = flash_attention(q, k, v, dropout_p=0.3, dropout_key=k1,
                             interpret=True)
        o1b = flash_attention(q, k, v, dropout_p=0.3, dropout_key=k1,
                              interpret=True)
        o2 = flash_attention(q, k, v, dropout_p=0.3, dropout_key=k2,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
        assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-3

    def test_drop_rate_and_scaling(self):
        """Empirical drop rate ~ p, and the 1/(1-p) rescale keeps the
        output mean in range."""
        from paddle_tpu.ops.pallas.flash_attention import _dropout_keep

        keep = _dropout_keep(jnp.int32(123), 0, 0, 512, 512, 0.25)
        rate = 1.0 - float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(rate - 0.25) < 0.01

    def test_requires_key(self):
        q, k, v = _rand_qkv()
        with pytest.raises(ValueError, match="dropout_key"):
            flash_attention(q, k, v, dropout_p=0.1, interpret=True)


def test_flash_all_features_compose():
    """kv_mask + segment_ids + causal + dropout in ONE call: the mask
    logic layers must not interfere (dropout checked via determinism +
    the other constraints via a same-mask reference)."""
    from paddle_tpu.ops.pallas.flash_attention import _dropout_keep

    b, t, h, p = 2, 256, 2, 0.1
    q, k, v = _rand_qkv(b=b, t=t, h=h, seed=31)
    ids = np.zeros((b, t), np.int32)
    ids[:, 128:] = 1
    keep_pad = jnp.asarray(np.arange(t)[None, :]
                           < np.array([224, 192])[:, None])
    key = jax.random.PRNGKey(3)
    ids_j = jnp.asarray(ids)

    out = flash_attention(q, k, v, causal=True, kv_mask=keep_pad,
                          segment_ids=ids_j, dropout_p=p, dropout_key=key,
                          interpret=True)
    # reference: same dropout mask, explicit everything else
    seed = jax.random.randint(key, (b, h), -2 ** 31, 2 ** 31 - 1,
                              dtype=jnp.int32)
    dkeep = jnp.stack([_dropout_keep(seed[bh // h, bh % h], 0, 0, t, t, p)
                       for bh in range(b * h)]).reshape(b, h, t, t)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    m = jnp.tril(jnp.ones((t, t), bool))[None, None]
    m = m & keep_pad[:, None, None, :]
    m = m & (ids_j[:, None, :, None] == ids_j[:, None, None, :])
    logits = jnp.where(m, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.any(m, -1, keepdims=True), probs, 0.0)
    probs = jnp.where(dkeep, probs / (1 - p), 0.0)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


class TestFlashWindow:
    """Sliding-window/local attention: banded masking with block-level
    compute skipping (O(T*window) — the long-context local pattern)."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("window", [64, 100, 256])
    def test_matches_oracle(self, causal, window):
        q, k, v = _rand_qkv(t=512, seed=41)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
        ref = xla_attention(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, causal):
        q, k, v = _rand_qkv(t=256, seed=43)
        rng = np.random.default_rng(43)
        ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=causal, window=96,
                                    block_q=128, block_k=128,
                                    block_q_bwd=64, block_k_bwd=128,
                                    interpret=True) * ct).sum()

        def g(q, k, v):
            return (xla_attention(q, k, v, causal=causal,
                                  window=96) * ct).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(gf, gg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=2e-4, atol=2e-4)

    def test_window_composes_with_mask_and_segments(self):
        q, k, v = _rand_qkv(t=256, seed=47)
        keep = jnp.asarray(np.arange(256)[None, :]
                           < np.array([224, 192])[:, None])
        ids = np.zeros((2, 256), np.int32)
        ids[:, 128:] = 1
        ids_j = jnp.asarray(ids)
        out = flash_attention(q, k, v, causal=True, window=80,
                              kv_mask=keep, segment_ids=ids_j,
                              interpret=True)
        ref = xla_attention(q, k, v, causal=True, window=80,
                            mask=keep[:, None, None, :],
                            segment_ids=ids_j)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_window_validation(self):
        q, k, v = _rand_qkv(t=128)
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, k, v, window=0, interpret=True)


class TestFlashGQA:
    """Grouped-query attention: K/V carry fewer heads; the kernel reads
    the shared block via its index map (no HBM head-repeat) and dK/dV
    group-sum onto the shared heads."""

    @pytest.mark.parametrize("h_kv", [1, 2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_repeated_kv_oracle(self, causal, h_kv):
        b, t, h, d = 2, 256, 8, 64
        rng = np.random.default_rng(51)
        q = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, t, h_kv, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, t, h_kv, d)).astype(np.float32))
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = xla_attention(q, k, v, causal=causal)  # oracle repeats kv
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_oracle(self):
        b, t, h, h_kv, d = 2, 256, 8, 2, 64
        rng = np.random.default_rng(53)
        q = jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, t, h_kv, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, t, h_kv, d)).astype(np.float32))
        ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True,
                                    interpret=True) * ct).sum()

        def g(q, k, v):
            return (xla_attention(q, k, v, causal=True) * ct).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, bb, name in zip(gf, gg, "qkv"):
            assert a.shape == bb.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=f"d{name}")

    def test_gqa_composes_with_window_and_mask(self):
        b, t, h, h_kv = 2, 256, 4, 2
        rng = np.random.default_rng(55)
        q = jnp.asarray(rng.normal(size=(b, t, h, 64)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, t, h_kv, 64)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, t, h_kv, 64)).astype(np.float32))
        keep = jnp.asarray(np.arange(t)[None, :]
                           < np.array([224, 160])[:, None])
        out = flash_attention(q, k, v, causal=True, window=96,
                              kv_mask=keep, interpret=True)
        ref = xla_attention(q, k, v, causal=True, window=96,
                            mask=keep[:, None, None, :])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_heads(self):
        q = jnp.zeros((1, 128, 6, 64), jnp.float32)
        k = jnp.zeros((1, 128, 4, 64), jnp.float32)
        with pytest.raises(ValueError, match="kv heads"):
            flash_attention(q, k, k, interpret=True)


class TestFlashWindowBandedGrid:
    """Window shapes where the BANDED grid engages (band < n_j): the
    reduced grid + clamped index maps must agree with the oracle — edge
    blocks, in-kernel index recovery, and the transposed dkv band."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_banded(self, causal):
        q, k, v = _rand_qkv(b=1, t=1024, h=1, seed=61)
        out = flash_attention(q, k, v, causal=causal, window=64,
                              block_q=128, block_k=128, interpret=True)
        ref = xla_attention(q, k, v, causal=causal, window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_banded(self, causal):
        q, k, v = _rand_qkv(b=1, t=1024, h=1, seed=63)
        rng = np.random.default_rng(63)
        ct = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=causal, window=64,
                                    block_q=128, block_k=128,
                                    interpret=True) * ct).sum()

        def g(q, k, v):
            return (xla_attention(q, k, v, causal=causal,
                                  window=64) * ct).sum()

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gg = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, bb, name in zip(gf, gg, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name}")

    def test_banded_composes_with_mask_and_dropout(self):
        from paddle_tpu.ops.pallas.flash_attention import _dropout_keep

        b, t, h, p, W = 1, 1024, 2, 0.1, 96
        q, k, v = _rand_qkv(b=b, t=t, h=h, seed=65)
        keep = jnp.asarray(np.arange(t)[None, :] < np.array([960])[:, None])
        key = jax.random.PRNGKey(17)
        out = flash_attention(q, k, v, causal=True, window=W,
                              kv_mask=keep, dropout_p=p, dropout_key=key,
                              block_q=128, block_k=128, interpret=True)
        seed = jax.random.randint(key, (b, h), -2 ** 31, 2 ** 31 - 1,
                                  dtype=jnp.int32)
        dk = jnp.stack([_dropout_keep(seed[bh // h, bh % h], 0, 0, t, t, p)
                        for bh in range(b * h)]).reshape(b, h, t, t)
        scale = q.shape[-1] ** -0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        rows = np.arange(t)[:, None]
        cols = np.arange(t)[None, :]
        m = (rows >= cols) & (rows - cols < W)
        m = jnp.asarray(m)[None, None] & keep[:, None, None, :]
        logits = jnp.where(m, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.any(m, -1, keepdims=True), probs, 0.0)
        probs = jnp.where(dk, probs / (1 - p), 0.0)
        ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_banded_grid_actually_engages(self):
        """Meta-check: these shapes DO take the banded path (band < n_j),
        so the tests above exercise it rather than the dense fallback."""
        import importlib

        FA = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        for causal in (False, True):
            band = FA._band_width_j(block_q=128, block_k=128, window=64,
                                    causal=causal, n_j=8)
            assert band < 8, (causal, band)


class TestFlashTwoWidths:
    """q and k share a score width, v has a value width of its own
    (latent attention: 192 / 128 runs as 256 / 128). The oracle is the
    XLA composite on operands zero-padded to ONE width, cut back: zeros
    add nothing to a score and the padded value columns are exact
    zeros, so forward and all three gradients must agree."""

    # feature -> flash_attention's keywords; "gqa" halves the K/V heads
    FEATURES = {
        "plain": {},
        "gqa": {},
        "kv_mask": {"kv_mask": jnp.asarray(
            np.arange(256)[None, :] < np.array([200, 131])[:, None])},
        "segment_ids": {"segment_ids": jnp.asarray(
            (np.arange(256)[None, :] >= np.array([96, 160])[:, None])
            .astype(np.int32))},
        "window": {"window": 96},
    }
    CASES = ([("plain", c) for c in (False, True)]
             + [("gqa", True), ("kv_mask", False), ("segment_ids", True),
                ("window", False)])

    @pytest.mark.parametrize("feature,causal", CASES)
    @pytest.mark.parametrize("widths", [(256, 128), (128, 256)])
    def test_forward_and_grads_match_padded_oracle(self, widths, feature,
                                                   causal):
        d, e = widths
        b, t, h = 2, 256, 2
        h_kv = 1 if feature == "gqa" else h
        rng = np.random.default_rng(61)
        draw = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
        q, k, v = draw(b, t, h, d), draw(b, t, h_kv, d), draw(b, t, h_kv, e)
        ct = draw(b, t, h, e)
        kw = dict(self.FEATURES[feature])
        ref_kw = dict(kw)
        if "kv_mask" in ref_kw:
            ref_kw["mask"] = ref_kw.pop("kv_mask")[:, None, None, :]

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=128,
                                   block_k=128, block_q_bwd=64,
                                   block_k_bwd=128, interpret=True, **kw)

        def oracle(q, k, v):
            pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + (
                (0, max(d, e) - a.shape[-1]),))
            return xla_attention(pad(q), pad(k), pad(v), causal=causal,
                                 scale=d ** -0.5, **ref_kw)[..., :e]

        out, pull = jax.vjp(flash, q, k, v)
        ref, ref_pull = jax.vjp(oracle, q, k, v)
        assert out.shape == (b, t, h, e)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        for got, want, name in zip(pull(ct), ref_pull(ct), "qkv"):
            assert got.shape == want.shape, name
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name}")

    def test_q_and_k_must_share_the_score_width(self):
        q, k, v = _rand_qkv(t=128, d=64)
        with pytest.raises(ValueError, match="score width"):
            flash_attention(q, k[..., :32], v, interpret=True)
