"""DGC gradient compression tests: top-k sparsify semantics, momentum
correction + error feedback, dense warmup, convergence under heavy
compression, quantized allreduce accuracy on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from jax import shard_map
from paddle_tpu.parallel import (DGCMomentum, dgc_allreduce,
                                 quantized_allreduce, top_k_sparsify)

RNG = np.random.default_rng(41)


class TestTopKSparsify:
    def test_keeps_exactly_topk_and_residual_sums(self):
        g = jnp.asarray(RNG.normal(size=(100,)).astype(np.float32))
        kept, residual = top_k_sparsify(g, sparsity=0.9)
        nz = int(jnp.sum(kept != 0))
        assert 10 <= nz <= 12  # ties can add a couple
        np.testing.assert_allclose(kept + residual, g, rtol=1e-6)
        # kept entries are the largest by magnitude
        assert float(jnp.min(jnp.abs(kept[kept != 0]))) >= float(
            jnp.max(jnp.abs(residual)))

    def test_always_keeps_at_least_one(self):
        g = jnp.asarray(RNG.normal(size=(5,)).astype(np.float32))
        kept, _ = top_k_sparsify(g, sparsity=0.9999)
        assert int(jnp.sum(kept != 0)) >= 1


class TestDGCMomentum:
    def test_error_feedback_accumulates(self):
        """A small gradient entry must eventually be applied once its
        accumulated magnitude crosses the top-k threshold."""
        opt = DGCMomentum(0.1, momentum=0.0, sparsity=0.5)
        params = {"w": jnp.zeros(4)}
        state = opt.init(params)
        g = {"w": jnp.asarray(np.array([1.0, 0.3, 0.2, 0.15], np.float32))}
        p = params
        for _ in range(8):
            p, state = opt.apply(p, g, state)
        # all entries moved (small ones via accumulated residual)
        assert np.all(np.asarray(p["w"]) < 0)

    def test_dense_warmup(self):
        opt = DGCMomentum(0.1, momentum=0.0, sparsity=0.75,
                          rampup_begin_step=5)
        params = {"w": jnp.zeros(4)}
        state = opt.init(params)
        g = {"w": jnp.asarray(np.array([1.0, 0.5, 0.1, 0.05], np.float32))}
        p, state = opt.apply(params, g, state)
        # warmup: every entry applied immediately, no residual
        np.testing.assert_allclose(p["w"], -0.1 * np.asarray(g["w"]),
                                   rtol=1e-6)
        np.testing.assert_allclose(state["leaf"][0]["v"], 0.0, atol=1e-7)

    def test_converges_on_quadratic(self):
        """Heavily compressed DGC still minimizes a quadratic."""
        target = jnp.asarray(RNG.normal(size=(64,)).astype(np.float32))
        opt = DGCMomentum(0.02, momentum=0.9, sparsity=0.9)
        params = {"w": jnp.zeros(64)}
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            loss, g = jax.value_and_grad(
                lambda p: jnp.sum((p["w"] - target) ** 2))(params)
            params, state = opt.apply(params, g, state)
            return params, state, loss

        losses = []
        for _ in range(150):
            params, state, l = step(params, state)
            losses.append(float(l))
        assert losses[-1] < 0.05 * losses[0]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
class TestQuantizedAllreduce:
    def test_matches_exact_psum_within_tolerance(self):
        mesh = pt.build_mesh(dp=8)
        x = RNG.normal(size=(8, 128)).astype(np.float32)

        def f(xs):
            return quantized_allreduce(xs[0], "dp")[None]

        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"),
                                out_specs=P("dp")))(jnp.asarray(x))
        exact = x.sum(axis=0)
        got = np.asarray(out)[0]
        # two int8 quantization phases: tolerance ~ 2 * max|x| * n / 127
        tol = 2.5 * np.abs(x).max() * 8 / 127
        np.testing.assert_allclose(got, exact, atol=tol)
        # and it must be meaningfully correct, not garbage
        corr = np.corrcoef(got, exact)[0, 1]
        assert corr > 0.999

    def test_dgc_allreduce_tree(self):
        mesh = pt.build_mesh(dp=8)
        g1 = RNG.normal(size=(8, 64)).astype(np.float32)
        g2 = RNG.normal(size=(8, 16)).astype(np.float32)

        def f(tree):
            return jax.tree_util.tree_map(
                lambda v: v[None],
                dgc_allreduce({"a": tree["a"][0], "b": tree["b"][0]},
                              "dp", sparsity=0.5, quantize=False))

        out = jax.jit(shard_map(
            f, mesh=mesh, in_specs=({"a": P("dp"), "b": P("dp")},),
            out_specs={"a": P("dp"), "b": P("dp")}))(
            {"a": jnp.asarray(g1), "b": jnp.asarray(g2)})
        # each shard's top-50% summed: result correlates with exact sum
        exact = g1.sum(axis=0)
        got = np.asarray(out["a"])[0]
        assert np.corrcoef(got, exact)[0, 1] > 0.7
