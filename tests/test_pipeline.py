"""Pipeline parallelism: GPipe schedule vs sequential layer fold.

Multi-device semantics validated on the virtual 8-CPU-device mesh
(conftest.py) — the test_dist_base-style strategy (reference:
python/paddle/fluid/tests/unittests/test_dist_base.py:305).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.pipeline import GPipe, pipeline_apply


L, D, B = 8, 16, 12


@pytest.fixture(scope="module")
def pp_mesh():
    mesh = pt.build_mesh(pp=4, dp=2, devices=jax.devices()[:8])
    with pt.core.mesh.mesh_scope(mesh):
        yield mesh


def _block_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(scale=0.5, size=(L, D, D)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(scale=0.1, size=(L, D)).astype(np.float32)),
    }


def _sequential(params, x):
    h = x
    for l in range(L):
        h = _block_fn({"w": params["w"][l], "b": params["b"][l]}, h)
    return h


def test_pipeline_forward_matches_sequential(pp_mesh):
    params = _params()
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(B, D)).astype(np.float32))
    got = pipeline_apply(_block_fn, params, x, num_microbatches=4,
                         mesh=pp_mesh)
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_grads_match_sequential(pp_mesh):
    params = _params(2)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(B, D)).astype(np.float32))

    def loss_pipe(params):
        return jnp.mean(pipeline_apply(_block_fn, params, x,
                                       num_microbatches=4, mesh=pp_mesh) ** 2)

    def loss_seq(params):
        return jnp.mean(_sequential(params, x) ** 2)

    gp = jax.grad(loss_pipe)(params)
    gs = jax.grad(loss_seq)(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                   atol=5e-5, rtol=5e-5)


def test_pipeline_jit_with_stage_placed_params(pp_mesh):
    """jit + params physically placed per stage (the production memory
    layout: each chip holds L/n layers)."""
    from paddle_tpu.parallel.pipeline import (_stack_to_stages,
                                              stage_param_sharding)

    params = _params(4)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(B, D)).astype(np.float32))
    f = jax.jit(lambda p, x: pipeline_apply(
        _block_fn, p, x, num_microbatches=3, mesh=pp_mesh))
    got = f(params, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_sequential(params, x)),
                               atol=1e-5, rtol=1e-5)
    shardings = stage_param_sharding(params, 4, mesh=pp_mesh)
    placed = jax.tree_util.tree_map(jax.device_put,
                                    _stack_to_stages(params, 4), shardings)
    # each leaf is sharded over pp: stage s holds layers [2s, 2s+2)
    assert not placed["w"].sharding.is_fully_replicated


def test_gpipe_layer_wrapper(pp_mesh):
    import paddle_tpu.nn as nn

    pt.seed(11)
    blocks = [nn.Linear(D, D, act="tanh") for _ in range(L)]
    gp = GPipe(blocks, num_microbatches=4, mesh=pp_mesh)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(B, D)).astype(np.float32))
    got = gp(x)
    h = x
    for blk in blocks:
        h = blk(h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_rejects_bad_layer_count(pp_mesh):
    params = {"w": jnp.zeros((6, D, D)), "b": jnp.zeros((6, D))}
    with pytest.raises(Exception, match="divide pp"):
        pipeline_apply(_block_fn, params, jnp.zeros((B, D)),
                       num_microbatches=4, mesh=pp_mesh)
