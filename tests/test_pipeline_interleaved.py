"""Interleaved (virtual-stage) pipeline schedule — VERDICT r3 #5.

The Megatron-style interleaved schedule next to GPipe: each device holds
``v`` round-robin layer chunks, microbatches circulate the ring ``v``
times, and the pipe fills/drains in chunk ticks (1/v of a GPipe tick) —
bubble (n-1)/(m*v + n-1) vs GPipe's (n-1)/(m+n-1). Green-field design
(the reference has no pipeline parallelism; SURVEY §2.5/§7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.pipeline import (bubble_fraction, gpipe_ticks,
                                          interleaved_ticks,
                                          pipeline_apply)

L, D, B = 8, 16, 16


@pytest.fixture(scope="module")
def pp_mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = pt.build_mesh(pp=4, dp=2, devices=devs[:8])
    with pt.core.mesh.mesh_scope(mesh):
        yield mesh


def _block_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(scale=0.5, size=(L, D, D))
                         .astype(np.float32)),
        "b": jnp.asarray(rng.normal(scale=0.1, size=(L, D))
                         .astype(np.float32)),
    }


def _sequential(params, x):
    h = x
    for l in range(L):
        h = _block_fn({"w": params["w"][l], "b": params["b"][l]}, h)
    return h


def test_bubble_strictly_lower_than_gpipe():
    """The schedule's reason to exist, in tick arithmetic: at pp=4, m=8,
    v=2 the interleaved pipe idles 16% of device time vs GPipe's 27%
    (ticks counted in stage-units: 19/2 = 9.5 vs 11)."""
    n, m, v = 4, 8, 2
    t_gpipe = gpipe_ticks(n, m)                       # 11 stage ticks
    t_inter = interleaved_ticks(n, m, v)              # 19 chunk ticks
    assert t_gpipe == 11 and t_inter == 19
    assert t_inter / v < t_gpipe                      # 9.5 < 11
    bg = bubble_fraction(n, m)
    bi = bubble_fraction(n, m, "interleaved", v)
    assert bi < bg, (bi, bg)
    assert abs(bg - 3 / 11) < 1e-9 and abs(bi - 3 / 19) < 1e-9
    # more virtual stages -> smaller bubble, monotonically
    assert bubble_fraction(n, m, "interleaved", 4) < bi


@pytest.mark.parametrize("v,m", [(2, 4), (2, 8), (2, 6), (1, 4)])
def test_interleaved_forward_matches_sequential(pp_mesh, v, m):
    """Every (virtual_stages, microbatch) combination — including m not
    divisible by n (ragged last burst) and the v=1 degenerate form —
    reproduces the sequential layer fold exactly."""
    params = _params()
    rng = np.random.default_rng(1)
    b = m * 2
    x = jnp.asarray(rng.normal(size=(b, D)).astype(np.float32))
    got = pipeline_apply(_block_fn, params, x, num_microbatches=m,
                         mesh=pp_mesh, schedule="interleaved",
                         virtual_stages=v)
    want = _sequential(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_interleaved_grads_match_sequential(pp_mesh):
    """Autodiff through the interleaved ring (the backward pipeline is
    the transposed schedule) gives the sequential gradients."""
    params = _params(2)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(B, D)).astype(np.float32))

    def loss_inter(params):
        return jnp.mean(pipeline_apply(
            _block_fn, params, x, num_microbatches=4, mesh=pp_mesh,
            schedule="interleaved", virtual_stages=2) ** 2)

    def loss_seq(params):
        return jnp.mean(_sequential(params, x) ** 2)

    gi = jax.grad(loss_inter)(params)
    gs = jax.grad(loss_seq)(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(gi[k]), np.asarray(gs[k]),
                                   atol=5e-5, rtol=5e-5, err_msg=k)


def test_interleaved_matches_gpipe_loss(pp_mesh):
    params = _params(4)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(B, D)).astype(np.float32))
    out_g = pipeline_apply(_block_fn, params, x, num_microbatches=4,
                           mesh=pp_mesh)
    out_i = pipeline_apply(_block_fn, params, x, num_microbatches=4,
                           mesh=pp_mesh, schedule="interleaved",
                           virtual_stages=2)
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_g),
                               atol=1e-5, rtol=1e-5)


def test_interleaved_still_single_hop_ring(pp_mesh):
    """Golden HLO: the interleaved schedule's collective stays a
    neighbour collective-permute (plus the wrap link) — no all-to-all,
    no all-gather of activations."""
    params = _params(6)
    x = jnp.asarray(np.random.default_rng(7).normal(
        size=(B, D)).astype(np.float32))

    def f(params, x):
        return pipeline_apply(_block_fn, params, x, num_microbatches=4,
                              mesh=pp_mesh, schedule="interleaved",
                              virtual_stages=2)

    txt = jax.jit(f).lower(params, x).compile().as_text()
    assert "collective-permute" in txt
    assert "all-to-all" not in txt


def test_hybrid_bert_selects_interleaved(pp_mesh):
    """Selectable from the flagship hybrid builder: BERT dp x tp x pp
    with the interleaved schedule loss-matches its sequential form."""
    devs = jax.devices()
    mesh = pt.build_mesh(dp=2, tp=2, pp=2, devices=devs[:8])
    from paddle_tpu.parallel.hybrid import build_bert_hybrid_step

    step, ref_step, params, feed = build_bert_hybrid_step(
        mesh, batch=8, num_microbatches=2, pipeline_schedule="interleaved",
        virtual_stages=2)
    loss, _ = jax.jit(step)(params, *feed)
    ref_loss, _ = jax.jit(ref_step)(params, *feed)
    assert np.isfinite(float(loss))
    assert abs(float(loss) - float(ref_loss)) < 1e-4, \
        (float(loss), float(ref_loss))


def test_bad_virtual_stage_configs(pp_mesh):
    params = _params()
    x = jnp.zeros((8, D), jnp.float32)
    with pytest.raises(Exception, match="virtual stages"):
        pipeline_apply(_block_fn, params, x, num_microbatches=4,
                       mesh=pp_mesh, schedule="interleaved",
                       virtual_stages=3)  # 8 layers % (4*3) != 0
    with pytest.raises(Exception, match="gpipe schedule"):
        pipeline_apply(_block_fn, params, x, num_microbatches=4,
                       mesh=pp_mesh, virtual_stages=2)


def test_hybrid_interleaved_weights_never_all_to_all(pp_mesh):
    """Ring-order parameter storage: the interleaved hybrid step's
    compiled module must contain NO all-to-all — a logical-order
    'pp'-sharded stack would reshard every layer weight every step
    (caught by tools/comm_report.py; the fix is ring_order_layers at
    placement + a local reshape per step)."""
    devs = jax.devices()
    mesh = pt.build_mesh(dp=2, tp=2, pp=2, devices=devs[:8])
    from paddle_tpu.parallel.hybrid import build_bert_hybrid_step

    step, ref_step, params, feed = build_bert_hybrid_step(
        mesh, batch=8, num_microbatches=2,
        pipeline_schedule="interleaved", virtual_stages=2)
    compiled = jax.jit(step).lower(params, *feed).compile()
    txt = compiled.as_text()
    assert "all-to-all" not in txt, \
        "interleaved layer stack is resharding weights every step"
    loss, _ = compiled(params, *feed)
    ref_loss, _ = jax.jit(ref_step)(params, *feed)
    assert abs(float(loss) - float(ref_loss)) < 1e-4


def test_ring_order_roundtrip():
    from paddle_tpu.parallel import ring_order_layers

    n, v, k = 4, 2, 3
    L = n * v * k
    x = {"w": jnp.arange(L * 2).reshape(L, 2)}
    r = ring_order_layers(x, n, v)
    # device d's contiguous rows are chunks d, n+d (each k layers)
    got = np.asarray(r["w"][:, 0]).reshape(n, v, k) // 2
    for d in range(n):
        for j in range(v):
            want = (j * n + d) * k
            assert got[d, j, 0] == want, (d, j, got[d, j], want)
    back = ring_order_layers(r, n, v, inverse=True)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x["w"]))


def test_interleaved_deep_wrap_v4(pp_mesh):
    """v=4 on 4 stages (16 layer-chunks, 4 ring wraps per microbatch):
    the deepest interleaving still reproduces the sequential fold, with
    a ragged burst (m=6 over n=4)."""
    L16 = 16
    rng = np.random.default_rng(21)
    params = {"w": jnp.asarray(rng.normal(scale=0.35, size=(L16, D, D))
                               .astype(np.float32))}
    x = jnp.asarray(rng.normal(size=(12, D)).astype(np.float32))
    got = pipeline_apply(_block_fn_w, params, x, num_microbatches=6,
                         mesh=pp_mesh, schedule="interleaved",
                         virtual_stages=4)
    h = x
    for l in range(L16):
        h = _block_fn_w({"w": params["w"][l]}, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                               atol=1e-5, rtol=1e-5)


def _block_fn_w(p, h):
    return jnp.tanh(h @ p["w"])
