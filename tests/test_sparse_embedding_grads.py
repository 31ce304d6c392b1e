"""Row-sparse embedding-gradient updates — the SelectedRows capability
(VERDICT r2 #4; reference: framework/selected_rows.h:32, sparse branches
in operators/optimizers/adam_op.h + operators/math/selected_rows_functor.cc,
lookup_table_op.cc is_sparse).

Contract under test: a train step built by optimizer.sparse_minimize_fn
1. numerically matches the dense step on every touched row (first steps),
2. leaves untouched rows (params AND accumulators) bitwise unchanged
   (lazy_mode semantics),
3. compiles to a step whose FLOPs are FLAT in vocab size,
4. composes with ShardedEmbedding on an ep mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.optimizer.sparse import (apply_rows, merge_rows,
                                         sparse_minimize_fn)

V, D = 500, 8


class Toy(nn.Layer):
    def __init__(self, vocab=V, sparse=True, padding_idx=None):
        super().__init__()
        self.emb = nn.Embedding(vocab, D, is_sparse=sparse,
                                padding_idx=padding_idx)
        self.fc = nn.Linear(D, 1)

    def forward(self, ids):
        return self.fc(jnp.mean(self.emb(ids), axis=1))


def _forward_loss(model):
    def f(p, ids, y):
        out, _ = model.functional_call(p, ids)
        return jnp.mean((out.squeeze(-1) - y) ** 2)

    return f


def _batch(seed=0, high=50):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, high, size=(4, 6)))  # dup-heavy
    y = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
    return ids, y


@pytest.mark.parametrize("make_opt", [
    lambda: optimizer.SGD(0.1),
    lambda: optimizer.Adam(0.01),
    lambda: optimizer.Adagrad(0.1),
    lambda: optimizer.Momentum(0.1, momentum=0.9),
], ids=["sgd", "adam", "adagrad", "momentum"])
def test_sparse_step_matches_dense(make_opt):
    pt.seed(0)
    model = Toy()
    params = model.named_parameters()
    fl = _forward_loss(model)
    opt = make_opt()
    init_fn, step_fn = sparse_minimize_fn(model, fl, opt)
    jstep = jax.jit(step_fn)
    dstep = jax.jit(make_opt().minimize_fn(fl))

    ids, y = _batch()
    state, dstate = init_fn(params), make_opt().init(params)
    p, dp = params, params
    for i in range(2):  # same ids twice: every touched row stays in sync
        loss, p, state = jstep(p, state, ids, y)
        dloss, dp, dstate = dstep(dp, dstate, ids, y)
        np.testing.assert_allclose(float(loss), float(dloss), rtol=1e-5)
        for k in params:
            np.testing.assert_allclose(np.asarray(p[k]), np.asarray(dp[k]),
                                       atol=1e-5, err_msg=f"{k} step{i}")


def test_untouched_rows_bitwise_frozen():
    """Lazy semantics: rows outside the batch keep params AND state."""
    pt.seed(0)
    model = Toy()
    params = model.named_parameters()
    init_fn, step_fn = sparse_minimize_fn(
        model, _forward_loss(model), optimizer.Adam(0.05))
    state = init_fn(params)
    ids, y = _batch(high=50)  # rows 50.. untouched
    loss, p1, s1 = jax.jit(step_fn)(params, state, ids, y)
    w0 = np.asarray(params["emb.weight"])
    w1 = np.asarray(p1["emb.weight"])
    touched = np.unique(np.asarray(ids))
    mask = np.ones(V, bool)
    mask[touched] = False
    assert np.array_equal(w0[mask], w1[mask]), "untouched rows moved"
    assert not np.allclose(w0[touched], w1[touched]), "touched rows frozen"
    for k, v in s1["sparse"]["emb.weight"].items():
        v = np.asarray(v)
        if v.ndim and v.shape[0] == V:
            assert np.all(v[mask] == 0), f"untouched {k} state written"


def test_flops_flat_in_vocab():
    """The whole point: step cost O(B*T*D), not O(V*D)."""

    def flops(vocab):
        pt.seed(0)
        model = Toy(vocab=vocab)
        params = model.named_parameters()
        init_fn, step_fn = sparse_minimize_fn(
            model, _forward_loss(model), optimizer.Adam(0.01))
        state = init_fn(params)
        ids = jnp.zeros((8, 16), jnp.int32)
        y = jnp.zeros((8,), jnp.float32)
        c = jax.jit(step_fn).lower(params, state, ids, y).compile()
        ca = c.cost_analysis()
        if not ca or "flops" not in ca:
            pytest.skip("backend reports no cost analysis")
        return ca["flops"]

    f_small, f_big = flops(10_000), flops(200_000)
    assert f_big <= f_small * 1.05, (f_small, f_big)


def test_padding_idx_row_never_updates():
    pt.seed(0)
    model = Toy(padding_idx=0)
    params = model.named_parameters()
    init_fn, step_fn = sparse_minimize_fn(
        model, _forward_loss(model), optimizer.SGD(0.5))
    state = init_fn(params)
    ids = jnp.asarray([[0, 1, 2, 0], [3, 0, 4, 0]])
    y = jnp.asarray([1.0, -1.0], jnp.float32)
    _, p1, _ = jax.jit(step_fn)(params, state, ids, y)
    np.testing.assert_array_equal(np.asarray(p1["emb.weight"])[0],
                                  np.asarray(params["emb.weight"])[0])
    assert not np.allclose(np.asarray(p1["emb.weight"])[1],
                           np.asarray(params["emb.weight"])[1])


def test_merge_rows_merges_duplicates():
    ids = jnp.asarray([3, 1, 3, 3])
    g = jnp.asarray([[1.0], [2.0], [10.0], [100.0]])
    uids, merged = merge_rows(ids, g, vocab_size=8)
    got = {int(u): float(m[0]) for u, m in zip(uids, merged) if int(u) < 8}
    assert got == {1: 2.0, 3: 111.0}


def test_apply_rows_multi_hot_matches_manual_sgd():
    table = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    sgd = optimizer.SGD(1.0)
    ids = jnp.asarray([[1, 2], [2, 2]])
    g = jnp.ones((2, 2, 3), jnp.float32)
    new_table, _ = apply_rows(sgd, table, ids, g, {},
                              jnp.asarray(1.0), jnp.asarray(0))
    want = np.asarray(table).copy()
    want[1] -= 1.0
    want[2] -= 3.0
    np.testing.assert_allclose(np.asarray(new_table), want)


def test_sharded_embedding_sparse_on_ep_mesh():
    """ShardedEmbedding(is_sparse=True) trains under dp x ep; the sparse
    step loss-matches the dense ShardedEmbedding step."""
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = pt.build_mesh(dp=2, ep=2, devices=devs[:4])
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel import ShardedEmbedding

    with pt.core.mesh.mesh_scope(mesh):
        pt.seed(0)

        class ShardedToy(nn.Layer):
            def __init__(self, sparse):
                super().__init__()
                self.emb = ShardedEmbedding(64, D, mesh=mesh,
                                            is_sparse=sparse)
                self.fc = nn.Linear(D, 1)

            def forward(self, ids):
                return self.fc(jnp.mean(self.emb(ids), axis=1))

        model = ShardedToy(sparse=True)
        params = dict(model.named_parameters())
        params["emb.weight"] = jax.device_put(
            params["emb.weight"], NamedSharding(mesh, P("ep", None)))
        fl = _forward_loss(model)
        init_fn, step_fn = sparse_minimize_fn(model, fl,
                                              optimizer.Adagrad(0.1))
        state = init_fn(params)
        ids, y = _batch(high=64)
        loss, p1, s1 = jax.jit(step_fn)(params, state, ids, y)
        dstep = jax.jit(optimizer.Adagrad(0.1).minimize_fn(fl))
        dloss, dp1, _ = dstep(params, optimizer.Adagrad(0.1).init(params),
                              ids, y)
        np.testing.assert_allclose(float(loss), float(dloss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(p1["emb.weight"]),
                                   np.asarray(dp1["emb.weight"]), atol=1e-5)
        # placement survives the update
        assert not p1["emb.weight"].sharding.is_fully_replicated


def test_multiple_calls_same_layer_accumulate():
    """A sparse embedding called twice in one forward (two fields sharing
    one table) must accumulate both call-sites' grads."""
    pt.seed(0)

    class TwoCall(nn.Layer):
        def __init__(self, sparse):
            super().__init__()
            self.emb = nn.Embedding(V, D, is_sparse=sparse)
            self.fc = nn.Linear(2 * D, 1)

        def forward(self, a, b):
            ha = jnp.mean(self.emb(a), axis=1)
            hb = jnp.mean(self.emb(b), axis=1)
            return self.fc(jnp.concatenate([ha, hb], -1))

    model = TwoCall(sparse=True)
    params = model.named_parameters()

    def fl(p, a, b, y):
        out, _ = model.functional_call(p, a, b)
        return jnp.mean((out.squeeze(-1) - y) ** 2)

    opt = optimizer.SGD(0.1)
    init_fn, step_fn = sparse_minimize_fn(model, fl, opt)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 30, size=(4, 3)))
    b = jnp.asarray(rng.integers(0, 30, size=(4, 5)))
    y = jnp.asarray(rng.normal(size=(4,)).astype(np.float32))
    loss, p1, _ = jax.jit(step_fn)(params, init_fn(params), a, b, y)
    dloss, dp1, _ = jax.jit(optimizer.SGD(0.1).minimize_fn(fl))(
        params, optimizer.SGD(0.1).init(params), a, b, y)
    np.testing.assert_allclose(float(loss), float(dloss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p1["emb.weight"]),
                               np.asarray(dp1["emb.weight"]), atol=1e-6)
