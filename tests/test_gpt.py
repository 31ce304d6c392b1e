"""Decoder-only causal LM family (models/gpt.py): RoPE + GQA + SwiGLU +
KV-cached decode + fused-CE training, composing with flash, ring SP,
the pipeline, and MoE. Green-field vs the reference (its transformer is
the encoder-decoder NMT benchmark,
benchmark/fluid/models/machine_translation.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import gpt as G


def _ids(cfg, b=2, t=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)))


def test_forward_shape_and_causality():
    pt.seed(0)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    ids = _ids(cfg)
    logits = m(ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # causality: changing token 10 must not move logits at positions < 10
    ids2 = ids.at[:, 10].set((ids[:, 10] + 1) % cfg.vocab_size)
    logits2 = m(ids2)
    np.testing.assert_allclose(np.asarray(logits[:, :10]),
                               np.asarray(logits2[:, :10]),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(logits[:, 10:] - logits2[:, 10:]).max()) > 1e-4


def test_forward_loss_matches_unfused_oracle():
    pt.seed(1)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    ids = _ids(cfg, seed=1)
    fused = m.forward_loss(ids)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full((2, 1), -100, ids.dtype)], axis=1)
    oracle = G.loss_fn(m(ids), labels)
    assert abs(float(fused) - float(oracle)) < 1e-4


def test_train_step_loss_decreases():
    from paddle_tpu import optimizer

    pt.seed(2)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg)
    params = m.named_parameters()
    opt = optimizer.Adam(1e-3)
    state = opt.init(params)
    ids = _ids(cfg, b=4, t=32, seed=2)

    @jax.jit
    def step(params, state):
        def loss(p):
            out, _ = m.functional_call(p, ids, training=True,
                                       method="forward_loss")
            return out

        l, g = jax.value_and_grad(loss)(params)
        params, state = opt.apply(params, g, state)
        return l, params, state

    losses = []
    for _ in range(8):
        l, params, state = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses
    # the tied embedding is the LM head: it must be receiving gradient
    assert cfg.tie_embeddings


def test_greedy_decode_matches_full_recompute():
    """KV-cached decode is token-identical to argmax over the full
    forward at every generated position (RoPE cache convention: K
    rotated at write, q at its own position)."""
    pt.seed(3)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    prompt = _ids(cfg, b=2, t=4, seed=3)
    out = m.greedy_decode(prompt, 12)
    assert out.shape == (2, 12)
    np.testing.assert_array_equal(np.asarray(out[:, :4]),
                                  np.asarray(prompt))
    full_next = jnp.argmax(m(out[:, :-1]), axis=-1)
    np.testing.assert_array_equal(np.asarray(full_next[:, 3:]),
                                  np.asarray(out[:, 4:]))


def test_rotary_relative_position_property():
    """<rot(q, m), rot(k, n)> depends only on m - n (the property RoPE
    exists for)."""
    from paddle_tpu.ops.attention import rotary_embedding

    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, 2, 64)).astype(np.float32))

    def score(mpos, npos):
        qm = rotary_embedding(q, jnp.array([mpos]))
        kn = rotary_embedding(k, jnp.array([npos]))
        return float(jnp.sum(qm * kn))

    assert abs(score(7, 3) - score(104, 100)) < 1e-4
    assert abs(score(0, 0) - float(jnp.sum(q * k))) < 1e-4
    # norms preserved (it's a rotation)
    r = rotary_embedding(q, jnp.array([13]))
    np.testing.assert_allclose(
        np.asarray(jnp.sum(r * r)), np.asarray(jnp.sum(q * q)),
        rtol=1e-5)


def test_gqa_flash_path_engages(monkeypatch):
    """Kernel-eligible geometry (T % 64 == 0, head_dim 64) under
    force_flash: the GQA causal attention rides the Pallas kernel."""
    from paddle_tpu.ops import attention as A

    pt.seed(5)
    cfg = G.GPTConfig(vocab_size=256, hidden_size=256, num_layers=1,
                      num_heads=4, num_kv_heads=2,
                      intermediate_size=512, max_position=64)
    m = G.GPTForCausalLM(cfg).eval()
    ids = _ids(cfg, b=2, t=64, seed=5)
    ref = m(ids)

    calls = {"n": 0}
    real = A._get_flash()

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(A, "_get_flash", lambda: counting)
    with A.force_flash():
        got = m(ids)
    assert calls["n"] > 0, "GPT attention did not ride the kernel"
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ring_sp_matches_plain():
    """seq_parallel='ring' on the sp mesh reproduces the plain stack
    (GQA blocks rotate with their fewer heads)."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = pt.build_mesh(dp=2, sp=4, devices=devs[:8])
    with pt.core.mesh.mesh_scope(mesh):
        pt.seed(6)
        cfg = G.GPTConfig.tiny()
        cfg.seq_parallel = "ring"
        m = G.GPTForCausalLM(cfg).eval()
        ids = _ids(cfg, b=2, t=64, seed=6)
        got = m(ids)
        for blk in m.blocks:
            blk.self_attn.seq_parallel = None
        want = m(ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_blocks_compose_with_pipeline():
    """GPT blocks are uniform h -> h: the stacked-params pipeline over
    'pp' matches the sequential fold (same contract as BERT's hybrid)."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    from paddle_tpu.nn.layer import stacked_parameters
    from paddle_tpu.parallel import pipeline_apply

    mesh = pt.build_mesh(dp=2, pp=2, tp=2, devices=devs[:8])
    pt.seed(7)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    template = m.blocks[0]
    stacked = stacked_parameters(list(m.blocks))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.hidden_size))
                    .astype(np.float32))

    def block_fn(p_l, h):
        out, _ = template.functional_call(p_l, h, training=False)
        return out

    got = pipeline_apply(block_fn, stacked, x, num_microbatches=2,
                         mesh=mesh)
    want = x
    for blk in m.blocks:
        want = blk(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_moe_variant_trains_with_aux():
    pt.seed(8)
    cfg = G.GPTConfig.tiny()
    cfg.moe_experts = 4
    cfg.moe_capacity_factor = 2.0
    m = G.GPTForCausalLM(cfg)
    ids = _ids(cfg, b=2, t=16, seed=8)

    def loss(p):
        out, nb = m.functional_call(p, ids, training=True,
                                    method="forward_loss")
        aux = sum(v for k, v in nb.items() if k.endswith("ffn.aux_loss"))
        return out + 0.01 * aux

    l, g = jax.value_and_grad(loss)(m.named_parameters())
    assert np.isfinite(float(l))
    router = [k for k in g if k.endswith("router_w")]
    assert router and all(np.abs(np.asarray(g[k])).max() > 0
                          for k in router)


def test_padded_batch_kv_mask():
    """Right-padding via kv_mask: logits at valid positions match the
    unpadded run of the same prefix."""
    pt.seed(9)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    ids_full = _ids(cfg, b=1, t=12, seed=9)
    ids_short = ids_full[:, :8]
    padded = jnp.concatenate(
        [ids_short, jnp.zeros((1, 4), ids_full.dtype)], axis=1)
    keep = jnp.asarray(np.arange(12)[None, :] < 8)
    got = m(padded, kv_mask=keep)[:, :8]
    want = m(ids_short)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_generate_top_k1_matches_greedy():
    """top_k=1 sampling collapses to the greedy path token-for-token at
    any temperature."""
    pt.seed(10)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    prompt = _ids(cfg, b=2, t=4, seed=10)
    greedy = m.greedy_decode(prompt, 12)
    sampled = m.generate(prompt, 12, key=jax.random.key(0),
                         temperature=1.7, top_k=1)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_generate_reproducible_and_key_sensitive():
    pt.seed(11)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    prompt = _ids(cfg, b=2, t=4, seed=11)
    a = m.generate(prompt, 24, key=jax.random.key(7), temperature=1.0)
    b = m.generate(prompt, 24, key=jax.random.key(7), temperature=1.0)
    c = m.generate(prompt, 24, key=jax.random.key(8), temperature=1.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()
    assert np.asarray(a).max() < cfg.vocab_size and np.asarray(a).min() >= 0


def test_generate_eos_freezes_finished_rows():
    """Once a row emits eos outside the prompt, every later token in
    that row is eos."""
    pt.seed(12)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    prompt = _ids(cfg, b=4, t=4, seed=12)
    # derive eos from an eos-free run with the SAME key: the draw
    # stream is identical until the first hit, so that row must freeze
    free = np.asarray(m.generate(prompt, 48, key=jax.random.key(1),
                                 temperature=3.0))
    eos = int(free[0, 10])
    out = np.asarray(m.generate(prompt, 48, key=jax.random.key(1),
                                temperature=3.0, eos_id=eos))
    hit = (out[:, 4:] == eos).any(axis=1)
    assert hit.any(), "no row emitted eos; raise temperature or length"
    for row in out[hit]:
        first = 4 + int(np.argmax(row[4:] == eos))
        assert (row[first:] == eos).all()


def test_generate_requires_key_when_sampling():
    pt.seed(13)
    cfg = G.GPTConfig.tiny()
    m = G.GPTForCausalLM(cfg).eval()
    prompt = _ids(cfg, b=1, t=4, seed=13)
    with pytest.raises(Exception, match="PRNG key"):
        m.generate(prompt, 8, temperature=1.0)
