"""Power retention (``ops/retention.py``) against its attention form
written as plain float64 numpy loops, in float32 on the CPU.

The sizes make the test hard where the chunked form can go wrong: five
query heads share a key-value head, the sequence is and is not a
multiple of the chunk, the state it starts from is not zero,
``valid_len`` falls inside a chunk, and in the long-memory case ``log g
= -1e-3`` keeps a token's weight at 36% after 1024 positions over 16
chunks, so that a state dropped, decayed twice or passed on wrongly at
a chunk's edge moves every later output.

Tolerance: the oracle is float64; the program is float32. The
attention form sums non-negative weights; the state form sums the
``D`` products of ``phi(q)`` and the state, which are of either sign
and cancel down to the same weight, so float32 rounding of the larger terms
is absolute in numerator and denominator: 1e-4 absolute and relative
on outputs of order 1, and where a denominator is under 1 (weights are
of order 1 a position) on the output times its denominator
(``close``). A state kept in bfloat16 is off by 1e-3 and more
(``test_a_bfloat16_state_would_fail``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import retention as R

TOL = dict(rtol=1e-4, atol=1e-4)
B, H, KV, D, CHUNK = 2, 10, 2, 8, 8
EPS = 1e-6


def draw(t, seed=0, log_g=None):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    g = (np.log(rng.uniform(0.3, 0.99, (B, t, KV))) if log_g is None
         else np.full((B, t, KV), log_g)).astype(np.float32)
    return f(B, t, H, D), f(B, t, KV, D), f(B, t, KV, D), g


def attention_form(q, k, v, log_g, eps=EPS):
    """y[t, j] = sum_i w v_i / (sum_i w + eps), w = (q_t . k_i)^2 / d *
    exp(sum_{s=i+1..t} log g_s): float64, one (row, head) at a time."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, t, h, d))
    den = np.zeros((b, t, h))
    keep = np.tril(np.ones((t, t), bool))
    for r in range(b):
        for j in range(h):
            c = j // rep
            cum = np.cumsum(log_g[r, :, c])
            s = q[r, :, j] @ k[r, :, c].T / np.sqrt(d)
            w = np.where(keep, s ** 2 * np.exp(np.where(
                keep, cum[:, None] - cum[None, :], 0.0)), 0.0)
            den[r, :, j] = w.sum(-1)
            out[r, :, j] = (w @ v[r, :, c]) / (den[r, :, j, None] + eps)
    return out, den


def close(y, want, den):
    w = np.minimum(1.0, den)[..., None]
    np.testing.assert_allclose(np.asarray(y) * w, want * w, **TOL)


def test_phi_is_the_symmetric_square():
    rng = np.random.default_rng(3)
    for d in (2, 8, 128):
        a, b = (rng.standard_normal((5, d)).astype(np.float32)
                for _ in range(2))
        pa, pb = R.phi(jnp.asarray(a)), R.phi(jnp.asarray(b))
        assert pa.shape == (5, R.phi_dim(d)) and pa.dtype == jnp.float32
        assert R.phi_dim(d) == (d // 2 + 1) * d
        np.testing.assert_allclose(
            np.sum(np.asarray(pa, np.float64) * np.asarray(pb), -1),
            np.sum(a.astype(np.float64) * b, -1) ** 2, rtol=1e-5,
            atol=1e-5)
    with pytest.raises(ValueError):
        R.phi(jnp.ones((3,)))


@pytest.mark.parametrize("t", [32, 37, 5])
def test_chunked_is_the_attention_form(t):
    q, k, v, g = draw(t)
    y, (S, z) = R.retention_chunked(q, k, v, g, CHUNK)
    assert y.dtype == S.dtype == z.dtype == jnp.float32
    assert S.shape == (B, KV, R.phi_dim(D), D) and z.shape == S.shape[:3]
    close(y, *attention_form(q, k, v, g))


def test_steps_from_zeros_are_the_chunked_form():
    t = 21
    q, k, v, g = draw(t, seed=1)
    want, (S_want, z_want) = R.retention_chunked(q, k, v, g, CHUNK)
    den = attention_form(q, k, v, g)[1]
    state = R.zero_state(B, KV, D)
    for i in range(t):
        y, state = R.retention_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                    state)
        close(y, np.asarray(want[:, i]), den[:, i])
    np.testing.assert_allclose(state[0], S_want, **TOL)
    np.testing.assert_allclose(state[1], z_want, **TOL)


def test_a_carried_state_continues_the_sequence():
    t, cut = 29, 13
    q, k, v, g = draw(t, seed=2)
    want, final = R.retention_chunked(q, k, v, g, CHUNK)
    cutat = lambda a, lo, hi: tuple(x[:, lo:hi] for x in a)
    y0, mid = R.retention_chunked(*cutat((q, k, v, g), 0, cut), CHUNK)
    y1, end = R.retention_chunked(*cutat((q, k, v, g), cut, t), CHUNK,
                                  state0=mid)
    close(jnp.concatenate([y0, y1], 1), np.asarray(want),
          attention_form(q, k, v, g)[1])
    for got, ref in zip(end, final):
        np.testing.assert_allclose(got, ref, **TOL)


def test_valid_len_leaves_the_state_untouched_beyond_it():
    t, valid = 24, 11
    q, k, v, g = draw(t, seed=4)
    y, state = R.retention_chunked(q, k, v, g, CHUNK,
                                   valid_len=jnp.int32(valid))
    ys, short = R.retention_chunked(q[:, :valid], k[:, :valid],
                                    v[:, :valid], g[:, :valid], CHUNK)
    close(y[:, :valid], np.asarray(ys),
          attention_form(q, k, v, g)[1][:, :valid])
    for got, ref in zip(state, short):
        np.testing.assert_allclose(got, ref, **TOL)
    assert np.isfinite(np.asarray(y)).all()


def test_long_memory_over_sixteen_chunks():
    """log g = -1e-3 over 1024 positions: the first token still weighs
    exp(-1.023) = 36% at the last, through 15 passes of the state."""
    t, chunk = 1024, 64
    q, k, v, g = draw(t, seed=5, log_g=-1e-3)
    q, k, v, g = q[:1], k[:1], v[:1], g[:1]
    y, _ = R.retention_chunked(q, k, v, g, chunk)
    want, den = attention_form(q, k, v, g)
    close(y, want, den)
    # and the memory is really long: dropping the first half of the
    # sequence moves the last outputs by far more than the tolerance
    half, _ = attention_form(q[:, t // 2:], k[:, t // 2:], v[:, t // 2:],
                             g[:, t // 2:])
    assert np.abs(half[:, -8:] - want[:, -8:]).max() > 1e-2
    assert den.min() > 0


def test_a_zero_state_gives_finite_output():
    z = lambda *s: jnp.zeros(s, jnp.float32)
    y, (S, zz) = R.retention_step(z(B, H, D), z(B, KV, D), z(B, KV, D),
                                  z(B, KV), R.zero_state(B, KV, D))
    assert np.array_equal(np.asarray(y), np.zeros((B, H, D)))
    assert not np.asarray(S).any() and not np.asarray(zz).any()
    # a query that sees nothing (zero keys so far) reads 0, not 0 / 0
    q, _, v, g = draw(1, seed=6)
    y, _ = R.retention_step(q[:, 0], z(B, KV, D), v[:, 0], g[:, 0],
                            R.zero_state(B, KV, D))
    assert np.array_equal(np.asarray(y), np.zeros((B, H, D)))
    y, _ = R.retention_chunked(q, z(B, 1, KV, D), v, g, CHUNK)
    assert np.isfinite(np.asarray(y)).all()


def test_step_parts_give_the_denominator():
    q, k, v, g = draw(6, seed=7)
    _, den = attention_form(q, k, v, g)
    state = R.zero_state(B, KV, D)
    for i in range(6):
        num, got, state = R.retention_step_parts(
            q[:, i], k[:, i], v[:, i], g[:, i], state)
        np.testing.assert_allclose(got, den[:, i], **TOL)


def test_a_bfloat16_state_would_fail():
    t = 64
    q, k, v, g = draw(t, seed=8, log_g=-1e-2)
    want, _ = attention_form(q, k, v, g)
    state = R.zero_state(B, KV, D)
    worst = 0.0
    for i in range(t):
        y, state = R.retention_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                    state)
        state = tuple(a.astype(jnp.bfloat16).astype(jnp.float32)
                      for a in state)
        worst = max(worst, float(np.abs(np.asarray(y) - want[:, i]).max()))
    assert worst > 1e-3


def test_the_kernel_body_is_the_jnp_body():
    """``pallas/retention_step.py`` (interpreted here) against the
    ``jax.numpy`` body through the one entry that chooses between them,
    at the head dimension the kernel is for, five query heads a
    key-value head, from a state that is not zero: the update is exact,
    the read sums bfloat16 halves on both sides (2^-17 a term)."""
    from paddle_tpu.ops import attention

    b, h, kv, d = 2, 10, 2, 128
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(b, h, d), f(b, kv, d), f(b, kv, d)
    g = jnp.log(jnp.asarray(rng.uniform(0.3, 0.9, (b, kv)), jnp.float32))
    state = (f(b, kv, R.phi_dim(d), d), jnp.abs(f(b, kv, R.phi_dim(d))))
    assert not R.step_kernel_ok(d, h // kv)         # the CPU: plain jnp
    want = R.retention_step_parts(q, k, v, g, state)
    with attention.force_flash():
        assert R.step_kernel_ok(d, h // kv)
        assert not R.step_kernel_ok(64, 5) and not R.step_kernel_ok(d, 9)
        got = R.retention_step_parts(q, k, v, g, state)
    num, den, (S, z) = got
    scale = float(np.abs(want[0]).std())
    np.testing.assert_allclose(num, want[0], rtol=0, atol=1e-4 * scale)
    np.testing.assert_allclose(den, want[1], rtol=1e-6)
    np.testing.assert_allclose(S, want[2][0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(z, want[2][1], rtol=1e-6, atol=1e-6)
