"""GPT composed-3D step (parallel/hybrid.py build_gpt_hybrid_step): the
decoder-LM flagship under dp x tp x pp, loss matching the sequential
fold and the public model API."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt


def _mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return pt.build_mesh(dp=2, tp=2, pp=2, devices=devs[:8])


def test_gpt_hybrid_matches_sequential_and_trains():
    from paddle_tpu.parallel.hybrid import build_gpt_hybrid_step

    mesh = _mesh()
    step, ref_step, params, feed = build_gpt_hybrid_step(mesh)
    jh, jr = jax.jit(step), jax.jit(ref_step)
    lh, ph = jh(params, *feed)
    lr_, pr = jr(params, *feed)
    np.testing.assert_allclose(float(lh), float(lr_), rtol=2e-4)
    lh2, _ = jh(ph, *feed)
    lr2, _ = jr(pr, *feed)
    np.testing.assert_allclose(float(lh2), float(lr2), rtol=5e-4)
    assert float(lh2) < float(lh), "SGD step must reduce the loss"


def test_gpt_hybrid_matches_model_api_loss():
    """The split-param loss IS the public model's forward_loss on an
    identically-seeded GPTForCausalLM."""
    from paddle_tpu.core.random import seed as set_seed
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.parallel.hybrid import build_gpt_hybrid_step

    mesh = _mesh()
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_position=64)
    step, _ref, params, feed = build_gpt_hybrid_step(mesh, cfg=cfg,
                                                     seed=3)
    loss, _ = jax.jit(step)(params, *feed)
    set_seed(3)
    model = GPTForCausalLM(cfg).eval()
    want = model.forward_loss(jax.device_get(feed[0]), vocab_chunk=256)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-4)


def test_gpt_hybrid_interleaved_schedule():
    from paddle_tpu.parallel.hybrid import build_gpt_hybrid_step

    mesh = _mesh()
    step, ref_step, params, feed = build_gpt_hybrid_step(
        mesh, pipeline_schedule="interleaved", virtual_stages=2)
    lh, _ = jax.jit(step)(params, *feed)
    lr_, _ = jax.jit(ref_step)(params, *feed)
    np.testing.assert_allclose(float(lh), float(lr_), rtol=2e-4)


def test_gpt_hybrid_moe_composes():
    """dp x tp x pp x ep: Switch-MoE FFN blocks, aux riding the
    pipeline carry (same contract as bert_moe)."""
    from paddle_tpu.parallel.hybrid import build_gpt_hybrid_step

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    from paddle_tpu.models.gpt import GPTConfig

    mesh = pt.build_mesh(dp=1, tp=2, pp=2, ep=2, devices=devs[:8])
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                    num_heads=4, num_kv_heads=2, intermediate_size=128,
                    max_position=64, moe_experts=2,
                    moe_capacity_factor=2.0)
    step, ref_step, params, feed = build_gpt_hybrid_step(mesh, cfg=cfg)
    lh, _ = jax.jit(step)(params, *feed)
    lr_, _ = jax.jit(ref_step)(params, *feed)
    np.testing.assert_allclose(float(lh), float(lr_), rtol=5e-4)
