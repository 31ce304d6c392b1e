"""``nn.DroplessMoE`` / ``nn.moe.dropless_moe``: top-k routing with no
capacity, the grouped product over the experts held here.

The oracle is a loop over tokens and picks in numpy, at the published
router shape (the 10 largest of 72 logits, softmax over those 10). Ties
are made on purpose (router columns repeated, so several experts get
the same logit to the last bit) and must break as ``lax.top_k`` breaks
them, lowest index first, on both sides. Tolerance: float32 both sides,
sums over a width of 16 to 24 in another order: 1e-5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.nn.moe import dropless_moe

S, D, F, E, K = 24, 16, 24, 72, 10
TOL = dict(rtol=1e-5, atol=1e-5)


def weights(seed=0, ties=True):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    router = f(D, E)
    if ties:
        # experts 3, 40 and 41 tie with expert 2; 70 ties with 36
        router[:, [3, 40, 41]] = router[:, [2]]
        router[:, 70] = router[:, 36]
    return dict(x=f(S, D), router=router, wg=0.3 * f(E, D, F),
                wu=0.3 * f(E, D, F), wd=0.3 * f(E, F, D))


def silu(a):
    return a / (1.0 + np.exp(-a))


def loop_oracle(w, first, count):
    """Token by token, pick by pick."""
    logits = w["x"] @ w["router"]
    y = np.zeros((S, D), np.float32)
    tokens = np.zeros(count, np.int64)
    for s in range(S):
        # the k largest, the lowest index first among equals
        picks = sorted(range(E), key=lambda e: (-logits[s, e], e))[:K]
        top = logits[s, picks]
        gates = np.exp(top - top.max())
        gates /= gates.sum()
        for g, e in zip(gates, picks):
            if first <= e < first + count:
                tokens[e - first] += 1
                x = w["x"][s]
                y[s] += g * ((silu(x @ w["wg"][e]) * (x @ w["wu"][e]))
                             @ w["wd"][e])
    return y, tokens


@pytest.mark.parametrize("held", [(0, 72), (0, 36), (36, 36), (30, 12)])
def test_against_the_token_loop_with_ties(held):
    w = weights()
    first, count = held
    sl = slice(first, first + count)
    y, tokens = dropless_moe(
        jnp.asarray(w["x"]), jnp.asarray(w["router"]),
        jnp.asarray(w["wg"][sl]), jnp.asarray(w["wu"][sl]),
        jnp.asarray(w["wd"][sl]), top_k=K, experts_held=held)
    y_ref, tokens_ref = loop_oracle(w, first, count)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_array_equal(tokens, tokens_ref)
    if held == (0, 72):
        assert int(tokens.sum()) == S * K      # nothing dropped


def test_ties_go_to_the_lowest_index():
    w = weights()
    logits = jnp.asarray(w["x"] @ w["router"])
    _, idx = jax.lax.top_k(logits, K)
    idx = np.asarray(idx)
    # wherever the tied experts 2, 3, 40, 41 are not all picked, the
    # ones picked are the lowest of them
    for row in idx:
        got = [e for e in (2, 3, 40, 41) if e in row]
        assert got == [2, 3, 40, 41][:len(got)]


def test_the_shares_add_up_to_the_whole_layer():
    w = weights(1, ties=False)
    args = lambda sl: (jnp.asarray(w["x"]), jnp.asarray(w["router"]),
                       jnp.asarray(w["wg"][sl]), jnp.asarray(w["wu"][sl]),
                       jnp.asarray(w["wd"][sl]))
    whole, n = dropless_moe(*args(slice(None)), top_k=K)
    a, na = dropless_moe(*args(slice(0, 36)), top_k=K,
                         experts_held=(0, 36))
    b, nb = dropless_moe(*args(slice(36, 72)), top_k=K,
                         experts_held=(36, 36))
    np.testing.assert_allclose(a + b, whole, **TOL)
    np.testing.assert_array_equal(np.concatenate([na, nb]), n)


def test_bfloat16_weights_are_not_copied_up():
    """The grouped product runs in the weights' type: tokens are cast
    down to it and the sums are float32. Against float32 weights the
    result moves by bfloat16 rounding (1e-2), no more."""
    w = weights(2, ties=False)
    lo = lambda a: jnp.asarray(a).astype(jnp.bfloat16)
    y_lo, _ = dropless_moe(jnp.asarray(w["x"]), lo(w["router"]),
                           lo(w["wg"]), lo(w["wu"]), lo(w["wd"]), top_k=K)
    assert y_lo.dtype == jnp.float32
    jaxpr = str(jax.make_jaxpr(lambda *a: dropless_moe(*a, top_k=K))(
        jnp.asarray(w["x"]), lo(w["router"]), lo(w["wg"]), lo(w["wu"]),
        lo(w["wd"])))
    assert "f32[72,16,24]" not in jaxpr       # no float32 copy of experts


def test_layer_checks_its_arguments():
    pt.seed(0)
    layer = nn.DroplessMoE(16, 24, 12, 4, experts_held=(3, 6))
    assert layer.w_gate.shape == (6, 16, 24)
    assert layer.router.weight.shape == (16, 12)
    y, tokens = layer.forward_counted(jnp.ones((2, 5, 16)))
    assert y.shape == (2, 5, 16) and tokens.shape == (6,)
    for kw in (dict(top_k=13), dict(top_k=0),
               dict(top_k=4, experts_held=(8, 6)),
               dict(top_k=4, experts_held=(0, 0)),
               dict(top_k=4, routing="sigmoid")):
        with pytest.raises(EnforceError):
            nn.DroplessMoE(16, 24, 12, **kw)
