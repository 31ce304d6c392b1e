"""State-space operations (``ops/ssm.py``) against the recurrence
written as a plain ``lax.scan`` over positions, in float32 on the CPU.

The sizes make the test hard where the chunked form can go wrong: ``dt``
drawn in 0.001 .. 0.1 with ``A = -(1 .. H)`` keeps a state alive far
longer than a chunk (a head with A = -1 and dt = 0.01 forgets with a
time constant of 100 positions against chunks of 8), the sequence is
not a multiple of the chunk, the state it starts from is not zero and
``valid_len`` falls inside a chunk.

Tolerance: both sides are float32 and differ only in the order of sums
(a chunk's quadratic form against a running state) and in ``exp`` of a
difference against a product of ``exp``: a few float32 roundings of
values of order 1, so 2e-5 absolute and relative. A state kept in
bfloat16 (eight bits of mantissa) is off by 1e-3 and more, and
``test_bfloat16_state_would_fail`` holds the tolerance to that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import ssm

TOL = dict(rtol=2e-5, atol=2e-5)
B, T, H, P, N, CHUNK = 2, 37, 4, 8, 16, 8


def draw(seed=0, t=T):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(
        x=f(B, t, H, P), B=f(B, t, N), C=f(B, t, N),
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (B, t, H)), jnp.float32),
        A=-jnp.arange(1, H + 1, dtype=jnp.float32),
        D=f(H), state0=f(B, H, P, N))


def scan_reference(x, dt, A, B, C, D, state0, valid_len=None,
                   state_dtype=jnp.float32):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D
    x_t, one position at a time; past ``valid_len`` the state stands."""
    def step(S, inp):
        i, x_t, dt_t, b_t, c_t = inp
        new = (jnp.exp(dt_t * A)[..., None, None] * S.astype(jnp.float32)
               + (dt_t[..., None] * x_t)[..., None]
               * b_t[:, None, None, :])
        if valid_len is not None:
            new = jnp.where(i < valid_len, new, S)
        new = new.astype(state_dtype)
        y = jnp.einsum("bhpn,bn->bhp", new.astype(jnp.float32), c_t)
        return new, y + D[:, None] * x_t

    t = x.shape[1]
    S, y = lax.scan(step, state0.astype(state_dtype),
                    (jnp.arange(t), *(jnp.moveaxis(a, 1, 0)
                                      for a in (x, dt, B, C))))
    return jnp.moveaxis(y, 0, 1), S.astype(jnp.float32)


@pytest.mark.parametrize("valid_len", [None, 21, 3, 0])
def test_chunked_is_the_recurrence(valid_len):
    d = draw()
    y, S = ssm.ssd_chunked(d["x"], d["dt"], d["A"], d["B"], d["C"], d["D"],
                           CHUNK, d["state0"], valid_len)
    y_ref, S_ref = scan_reference(valid_len=valid_len, **d)
    n = T if valid_len is None else valid_len
    np.testing.assert_allclose(y[:, :n], y_ref[:, :n], **TOL)
    np.testing.assert_allclose(S, S_ref, **TOL)
    assert np.isfinite(np.asarray(y)).all()
    if valid_len == 0:
        np.testing.assert_array_equal(S, d["state0"])


def test_chunked_from_no_state_and_a_whole_number_of_chunks():
    d = draw(1, t=4 * CHUNK)
    y, S = ssm.ssd_chunked(d["x"], d["dt"], d["A"], d["B"], d["C"], d["D"],
                           CHUNK)
    d["state0"] = jnp.zeros_like(d["state0"])
    y_ref, S_ref = scan_reference(**d)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_allclose(S, S_ref, **TOL)


def test_step_continues_the_chunked_state():
    """Prefill 21 positions of a padded 37 chunk-wise, then step the
    next 16 one at a time: the decode path's two halves against one
    scan over all 37."""
    d = draw(2)
    cut = 21
    _, S = ssm.ssd_chunked(d["x"], d["dt"], d["A"], d["B"], d["C"], d["D"],
                           CHUNK, d["state0"], cut)
    ys = []
    for t in range(cut, T):
        y, S = ssm.ssd_step(d["x"][:, t], d["dt"][:, t], d["A"],
                            d["B"][:, t], d["C"][:, t], d["D"], S)
        ys.append(y)
    y_ref, S_ref = scan_reference(**d)
    np.testing.assert_allclose(jnp.stack(ys, 1), y_ref[:, cut:], **TOL)
    np.testing.assert_allclose(S, S_ref, **TOL)


def test_bfloat16_state_would_fail():
    d = draw()
    y_ref, S_ref = scan_reference(**d)
    y_lo, S_lo = scan_reference(state_dtype=jnp.bfloat16, **d)
    worst = float(jnp.max(jnp.abs(S_lo - S_ref) / (jnp.abs(S_ref) + 1)))
    assert worst > 50 * TOL["rtol"]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(y_lo, y_ref, **TOL)


def conv_reference(x, w, b, tail):
    k = w.shape[0]
    xp = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    y = np.zeros_like(np.asarray(x))
    for t in range(x.shape[1]):
        for i in range(k):
            y[:, t] += xp[:, t + i] * np.asarray(w[i])
    return y + np.asarray(b), xp


@pytest.mark.parametrize("valid_len", [None, 5, 2, 0])
def test_convolution_and_its_tail(valid_len):
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, w, b, tail = f(B, 9, 6), f(4, 6), f(6), f(B, 3, 6)
    y, new = ssm.causal_conv1d(x, w, b, tail, valid_len)
    y_ref, xp = conv_reference(x, w, b, tail)
    # four products a value: float32 rounding only
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=1e-6)
    at = 9 if valid_len is None else valid_len
    np.testing.assert_array_equal(new, xp[:, at:at + 3])


def test_convolution_step_continues_the_tail():
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, w, b = f(B, 9, 6), f(4, 6), f(6)
    zeros = jnp.zeros((B, 3, 6), jnp.float32)
    y_all, _ = ssm.causal_conv1d(x, w, b, zeros)
    _, tail = ssm.causal_conv1d(x, w, b, zeros, 4)
    for t in range(4, 9):
        y, tail = ssm.causal_conv1d_step(x[:, t], w, b, tail)
        np.testing.assert_allclose(y, y_all[:, t], rtol=1e-6, atol=1e-6)


def test_inputs_in_bfloat16_keep_a_float32_state():
    d = draw(5)
    lo = {k: (v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v)
          for k, v in d.items()}
    y, S = ssm.ssd_chunked(lo["x"], lo["dt"], lo["A"], lo["B"], lo["C"],
                           lo["D"], CHUNK, lo["state0"])
    assert y.dtype == jnp.float32 and S.dtype == jnp.float32
    y1, S1 = ssm.ssd_step(lo["x"][:, 0], lo["dt"][:, 0], lo["A"],
                          lo["B"][:, 0], lo["C"][:, 0], lo["D"], S)
    assert y1.dtype == jnp.float32 and S1.dtype == jnp.float32
