"""Tests for tensor-manipulation, reduction, control-flow and sequence ops,
plus regressions from review findings (FLAGS.set parsing, key_for stability,
sequence_pool 'last' 2-D, position_encoding odd dims, lazy subpackage access)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops import control_flow as CF
from paddle_tpu.ops import reduction as R
from paddle_tpu.ops import sequence as S
from paddle_tpu.ops import tensor as T
from op_test import check_output

RNG = np.random.default_rng(2)


def u(shape, lo=-1.0, hi=1.0):
    return RNG.uniform(lo, hi, shape).astype(np.float32)


# --- tensor ops ------------------------------------------------------------

def test_reshape_zero_and_minus_one():
    x = u((2, 3, 4))
    assert T.reshape(x, [0, -1]).shape == (2, 12)
    assert T.reshape(x, [6, 4]).shape == (6, 4)


def test_concat_split_roundtrip():
    x = u((6, 4))
    parts = T.split(x, 3, axis=0)
    back = T.concat(parts, axis=0)
    np.testing.assert_allclose(np.asarray(back), x)


def test_gather_scatter():
    x = u((5, 3))
    idx = np.array([0, 2, 4])
    g = T.gather(x, jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(g), x[idx])
    s = T.scatter(jnp.asarray(x), jnp.asarray([1]), jnp.zeros((1, 3)))
    assert np.all(np.asarray(s)[1] == 0)
    s2 = T.scatter(jnp.asarray(x), jnp.asarray([1]), jnp.ones((1, 3)), overwrite=False)
    np.testing.assert_allclose(np.asarray(s2)[1], x[1] + 1, rtol=1e-5)


def test_top_k_argsort():
    x = u((3, 10))
    vals, idx = T.top_k(jnp.asarray(x), 3)
    expected = np.sort(x, axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.asarray(vals), expected, rtol=1e-5)
    sv, si = T.argsort(jnp.asarray(x), descending=True)
    np.testing.assert_allclose(np.asarray(sv)[:, :3], expected, rtol=1e-5)


def test_pad_and_pad_constant_like():
    x = u((2, 3))
    out = T.pad(x, [1, 0, 0, 2], 9.0)
    assert out.shape == (3, 5)
    assert np.asarray(out)[0, 0] == 9.0
    big, small = u((4, 5)), u((2, 3))
    out = T.pad_constant_like(big, small)
    assert out.shape == (4, 5)


def test_multiplex():
    a, b = u((4, 3)), u((4, 3))
    idx = np.array([0, 1, 1, 0])
    out = T.multiplex(jnp.asarray(idx), [jnp.asarray(a), jnp.asarray(b)])
    expected = np.where(idx[:, None] == 0, a, b)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_expand_and_tile():
    x = u((2, 3))
    assert T.expand(x, [2, 1]).shape == (4, 3)
    assert T.unsqueeze(x, [0, 3]).shape == (1, 2, 3, 1)
    assert T.squeeze(T.unsqueeze(x, [0]), [0]).shape == (2, 3)


def test_creation_ops():
    assert T.fill_constant([2, 2], 3.0).sum() == 12
    ref = u((5, 2))
    out = T.fill_constant_batch_size_like(ref, [1, 7], 1.0)
    assert out.shape == (5, 7)
    assert T.linspace(0, 1, 5).shape == (5,)
    assert np.asarray(T.eye(3)).trace() == 3


def test_random_ops_deterministic():
    k = jax.random.key(7)
    a = T.uniform_random((3, 3), k, -1, 1)
    b = T.uniform_random((3, 3), k, -1, 1)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(T.truncated_gaussian_random((1000,), k))).max() <= 2.0 * 1.0 + 1e-3


# --- reductions ------------------------------------------------------------

@pytest.mark.parametrize("op,npop", [
    (R.reduce_sum, np.sum), (R.reduce_mean, np.mean),
    (R.reduce_max, np.max), (R.reduce_min, np.min), (R.reduce_prod, np.prod),
])
def test_reductions(op, npop):
    x = u((3, 4, 5), 0.5, 1.5)
    check_output(lambda a: op(a, dim=[1]), [x], npop(x, axis=1), rtol=1e-4)
    check_output(lambda a: op(a, dim=1, keep_dim=True), [x],
                 npop(x, axis=1, keepdims=True), rtol=1e-4)
    check_output(op, [x], npop(x), rtol=1e-4)


def test_reduce_bool():
    x = np.array([[True, False], [True, True]])
    assert not bool(R.reduce_all(x))
    assert bool(R.reduce_any(x))
    np.testing.assert_array_equal(np.asarray(R.reduce_all(x, dim=[1])), [False, True])


def test_sum_list():
    xs = [u((2, 2)) for _ in range(3)]
    np.testing.assert_allclose(np.asarray(R.sum(xs)), xs[0] + xs[1] + xs[2], rtol=1e-5)


# --- control flow ----------------------------------------------------------

def test_compare_logical():
    a, b = np.array([1, 2, 3]), np.array([2, 2, 2])
    np.testing.assert_array_equal(np.asarray(CF.less_than(a, b)), [True, False, False])
    np.testing.assert_array_equal(np.asarray(CF.equal(a, b)), [False, True, False])
    t = np.array([True, False])
    np.testing.assert_array_equal(np.asarray(CF.logical_not(t)), [False, True])


def test_while_loop_and_cond():
    out = CF.while_loop(lambda c: c[0] < 10, lambda c: (c[0] + 1, c[1] * 1.1),
                        (0, 1.0))
    assert out[0] == 10
    r = CF.cond(jnp.array(True), lambda: 1.0, lambda: 2.0)
    assert float(r) == 1.0


def test_switch_case_and_case():
    f = lambda i: CF.switch_case(i, [lambda: jnp.array(10.),
                                     lambda: jnp.array(20.),
                                     lambda: jnp.array(30.)])
    assert float(jax.jit(f)(jnp.array(1))) == 20.0
    r = CF.case([(jnp.array(False), lambda: jnp.array(1.0)),
                 (jnp.array(True), lambda: jnp.array(2.0))],
                default=lambda: jnp.array(3.0))
    assert float(r) == 2.0


def test_static_rnn_cumsum():
    # running-sum RNN: state' = state + x_t
    x = u((2, 5, 3))

    def step(x_t, state):
        new = state + x_t
        return new, new

    outs, final = CF.static_rnn(step, jnp.asarray(x), jnp.zeros((2, 3)))
    np.testing.assert_allclose(np.asarray(outs), np.cumsum(x, axis=1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(final), x.sum(axis=1), rtol=1e-5)


def test_tensor_array_in_scan():
    ta = CF.TensorArray(4, (2,))

    def body(i, ta):
        return ta.write(i, jnp.full((2,), i, jnp.float32))

    ta = CF.fori_loop(0, 4, body, ta)
    np.testing.assert_allclose(np.asarray(ta.stack())[:, 0], [0, 1, 2, 3])
    np.testing.assert_allclose(np.asarray(ta.read(2)), [2, 2])


# --- sequence (ragged) ops -------------------------------------------------

def test_sequence_mask():
    m = S.sequence_mask(jnp.array([1, 3]), 4)
    np.testing.assert_allclose(np.asarray(m), [[1, 0, 0, 0], [1, 1, 1, 0]])


def test_sequence_pad_unpad_roundtrip():
    flat = u((5, 2))
    lengths = jnp.array([2, 3])
    padded = S.sequence_pad(jnp.asarray(flat), lengths, 4)
    assert padded.shape == (2, 4, 2)
    np.testing.assert_allclose(np.asarray(padded)[0, :2], flat[:2], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(padded)[1, :3], flat[2:], rtol=1e-6)
    assert np.all(np.asarray(padded)[0, 2:] == 0)
    back = S.sequence_unpad(padded, [2, 3])
    np.testing.assert_allclose(np.asarray(back), flat, rtol=1e-6)


@pytest.mark.parametrize("pool,ref", [
    ("sum", lambda x, l: np.array([x[0, :2].sum(0), x[1, :3].sum(0)])),
    ("average", lambda x, l: np.array([x[0, :2].mean(0), x[1, :3].mean(0)])),
    ("max", lambda x, l: np.array([x[0, :2].max(0), x[1, :3].max(0)])),
    ("last", lambda x, l: np.array([x[0, 1], x[1, 2]])),
    ("first", lambda x, l: x[:, 0]),
])
def test_sequence_pool(pool, ref):
    x = u((2, 4, 3))
    lengths = jnp.array([2, 3])
    out = S.sequence_pool(jnp.asarray(x), lengths, pool)
    np.testing.assert_allclose(np.asarray(out), ref(x, lengths), rtol=1e-5)


def test_sequence_pool_last_2d():
    # regression: 'last' must work on (B, T) input
    x = u((2, 4))
    out = S.sequence_pool(jnp.asarray(x), jnp.array([2, 4]), "last")
    np.testing.assert_allclose(np.asarray(out), [x[0, 1], x[1, 3]], rtol=1e-6)


def test_sequence_softmax():
    x = u((2, 4))
    out = S.sequence_softmax(jnp.asarray(x), jnp.array([2, 4]))
    row0 = np.asarray(out)[0]
    assert abs(row0[:2].sum() - 1.0) < 1e-5 and np.all(row0[2:] == 0)


def test_sequence_reverse():
    x = np.arange(8, dtype=np.float32).reshape(2, 4)
    out = S.sequence_reverse(jnp.asarray(x), jnp.array([3, 4]))
    np.testing.assert_allclose(np.asarray(out)[0], [2, 1, 0, 3])
    np.testing.assert_allclose(np.asarray(out)[1], [7, 6, 5, 4])


def test_sequence_expand():
    x = u((2, 3))
    out = S.sequence_expand(jnp.asarray(x), jnp.array([2, 1]))
    assert out.shape == (2, 2, 3)
    np.testing.assert_allclose(np.asarray(out)[0, 1], x[0], rtol=1e-6)
    assert np.all(np.asarray(out)[1, 1] == 0)


def test_sequence_concat():
    a = np.arange(6, dtype=np.float32).reshape(2, 3, 1)
    b = np.arange(10, 18, dtype=np.float32).reshape(2, 4, 1)
    out, lens = S.sequence_concat([jnp.asarray(a), jnp.asarray(b)],
                                  [jnp.array([2, 3]), jnp.array([1, 4])])
    np.testing.assert_array_equal(np.asarray(lens), [3, 7])
    np.testing.assert_allclose(np.asarray(out)[0, :3, 0], [0, 1, 10])
    np.testing.assert_allclose(np.asarray(out)[1, :7, 0], [3, 4, 5, 14, 15, 16, 17])


def test_sequence_enumerate():
    x = np.array([[1, 2, 3, 0]], dtype=np.int32)
    out = S.sequence_enumerate(jnp.asarray(x), jnp.array([3]), 2, pad_value=0)
    np.testing.assert_array_equal(np.asarray(out)[0, 0], [1, 2])
    np.testing.assert_array_equal(np.asarray(out)[0, 2], [3, 0])


def test_position_encoding_even_and_odd():
    for d in (6, 5):
        x = np.zeros((1, 3, d), np.float32)
        out = S.position_encoding(jnp.asarray(x))
        assert out.shape == (1, 3, d)
        # position 0: sin part 0, cos part 1
        np.testing.assert_allclose(np.asarray(out)[0, 0, :(d + 1) // 2], 0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out)[0, 0, (d + 1) // 2:], 1, atol=1e-6)


def test_hash_embedding_ids():
    ids = np.array([5, 5, 7])
    out = S.hash_embedding_ids(jnp.asarray(ids), 100, num_hash=2)
    assert out.shape == (3, 2)
    assert np.all(np.asarray(out) >= 0) and np.all(np.asarray(out) < 100)
    np.testing.assert_array_equal(np.asarray(out)[0], np.asarray(out)[1])


# --- review regressions ----------------------------------------------------

def test_flags_set_string_bool():
    from paddle_tpu.core import FLAGS

    FLAGS.set("benchmark", "false")
    assert FLAGS.get("benchmark") is False
    FLAGS.set("benchmark", "on")
    assert FLAGS.get("benchmark") is True
    FLAGS.reset("benchmark")


def test_key_for_stable_across_processes():
    # force the CPU backend in the children: a bare import would try to
    # take the TPU where there is one
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import paddle_tpu as pt, numpy as np; pt.seed(3); "
            "print(np.asarray(jax.random.key_data(pt.core.random.key_for('dropout'))).tolist())")
    outs = {subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, cwd="/root/repo", env=env,
                           timeout=120).stdout.strip()
            for _ in range(2)}
    assert len(outs) == 1 and next(iter(outs)), outs


def test_lazy_subpackage_attribute_error():
    with pytest.raises(AttributeError):
        pt.nonexistent_thing
    assert not hasattr(pt, "definitely_not_real")


def test_sequence_pool_2d_all_types():
    # regression: (B, T) input for average/sqrt/max must give (B,), not (B, B)
    x = np.array([[1., 2., 3., 4.], [4., 6., 0., 0.]], np.float32)
    lengths = jnp.array([2, 2])
    for pool, expected in [("average", [1.5, 5.0]), ("max", [2.0, 6.0]),
                           ("sqrt", [3 / np.sqrt(2), 10 / np.sqrt(2)])]:
        out = S.sequence_pool(jnp.asarray(x), lengths, pool)
        assert out.shape == (2,), (pool, out.shape)
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_sequence_expand_under_jit():
    # regression: static rmax makes sequence_expand jit-safe
    x = u((2, 3))
    f = jax.jit(lambda a, r: S.sequence_expand(a, r, rmax=4))
    out = f(jnp.asarray(x), jnp.array([2, 4]))
    assert out.shape == (2, 4, 3)
    assert np.all(np.asarray(out)[0, 2:] == 0)


def test_sequence_pad_preserves_int_dtype():
    flat = np.array([[1], [2], [3], [4], [5]], np.int32)
    out = S.sequence_pad(jnp.asarray(flat), jnp.array([2, 3]), 4, pad_value=0)
    assert out.dtype == jnp.int32


def test_sequence_pool_max_int():
    x = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    out = S.sequence_pool(jnp.asarray(x), jnp.array([2, 3]), "max")
    np.testing.assert_array_equal(np.asarray(out), [2, 6])


def test_argsort_descending_uint8():
    x = np.array([3, 0, 7, 1], np.uint8)
    vals, idx = T.argsort(jnp.asarray(x), descending=True)
    np.testing.assert_array_equal(np.asarray(vals), [7, 3, 1, 0])
