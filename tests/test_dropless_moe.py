"""``nn.DroplessMoE`` / ``nn.moe.dropless_moe``: top-k routing with no
capacity, over the experts held here, by both of its bodies: the dense
products over every held expert, and the sort and grouped product. The
static counts of the call alone choose (``moe.streams_densely``: rows,
picks a token, router outputs); a test that wants a given body for its
rows replaces that rule for its own duration, as nothing else can.

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
from paddle_tpu.nn import moe
from paddle_tpu.nn.moe import dropless_moe

S, D, F, E, K = 24, 16, 24, 72, 10
TOL = dict(rtol=1e-5, atol=1e-5)
BODIES = ["dense", "grouped"]


def force(monkeypatch, name):
    """Send every call through the named body."""
    monkeypatch.setattr(moe, "streams_densely",
                        lambda *counts: name == "dense")


@pytest.fixture
def body(request, monkeypatch):
    force(monkeypatch, request.param)
    return request.param


def weights(seed=0, ties=True, rows=S):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    router = f(D, E)
    if ties:
        # experts 3, 40 and 41 tie with expert 2; 70 ties with 36
        router[:, [3, 40, 41]] = router[:, [2]]
        router[:, 70] = router[:, 36]
    return dict(x=f(rows, D), router=router, wg=0.3 * f(E, D, F),
                wu=0.3 * f(E, D, F), wd=0.3 * f(E, F, D))


def silu(a):
    return a / (1.0 + np.exp(-a))


def equations(jaxpr):
    """Every equation of a jaxpr, nested ones too."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def primitives(jaxpr):
    return [eqn.primitive.name for eqn in equations(jaxpr)]


def loop_oracle(w, first, count):
    """Token by token, pick by pick."""
    logits = w["x"] @ w["router"]
    y = np.zeros(w["x"].shape, np.float32)
    tokens = np.zeros(count, np.int64)
    for s in range(len(y)):
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


@pytest.mark.parametrize("body", BODIES, indirect=True)
@pytest.mark.parametrize("held", [(0, 72), (0, 36), (36, 36), (30, 12)])
def test_against_the_token_loop_with_ties(held, body):
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


@pytest.mark.parametrize("body", BODIES, indirect=True)
def test_a_tied_pick_counts_for_the_lowest_index_alone(body):
    """Experts 2, 3, 40 and 41 have one logit a token. Held alone, 2
    gets a pair from every token that picks any of them, and 41 from
    those only that pick all four: in the result and in the count."""
    w = weights()
    logits = w["x"] @ w["router"]
    picked = [sorted(range(E), key=lambda e: (-logits[s, e], e))[:K]
              for s in range(S)]
    for e in (2, 41):
        y, tokens = dropless_moe(
            jnp.asarray(w["x"]), jnp.asarray(w["router"]),
            jnp.asarray(w["wg"][e:e + 1]), jnp.asarray(w["wu"][e:e + 1]),
            jnp.asarray(w["wd"][e:e + 1]), top_k=K, experts_held=(e, 1))
        rows = np.array([e in p for p in picked])
        assert int(tokens[0]) == rows.sum()
        np.testing.assert_array_equal(np.abs(np.asarray(y)).sum(-1) > 0,
                                      rows)
    assert all(2 in p for p in picked if 41 in p)


@pytest.mark.parametrize("body", BODIES, indirect=True)
def test_the_shares_add_up_to_the_whole_layer(body):
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


@pytest.mark.parametrize("body", BODIES, indirect=True)
def test_bfloat16_weights_are_not_copied_up(body):
    """Either body's products run in the weights' type: tokens are cast
    down to it and the sums are float32. Against float32 weights the
    result moves by bfloat16 rounding (some 1e-2 of its size), no
    more."""
    w = weights(2, ties=False)
    lo = lambda a: jnp.asarray(a).astype(jnp.bfloat16)
    y_lo, _ = dropless_moe(jnp.asarray(w["x"]), lo(w["router"]),
                           lo(w["wg"]), lo(w["wu"]), lo(w["wd"]), top_k=K)
    assert y_lo.dtype == jnp.float32
    y_hi, _ = dropless_moe(*(jnp.asarray(w[k]) for k in
                             ("x", "router", "wg", "wu", "wd")), top_k=K)
    assert np.abs(y_lo - y_hi).max() < 0.05 * np.abs(y_hi).max()
    jaxpr = jax.make_jaxpr(lambda *a: dropless_moe(*a, top_k=K))(
        jnp.asarray(w["x"]), lo(w["router"]), lo(w["wg"]), lo(w["wu"]),
        lo(w["wd"]))
    # no expert weight is cast up: nothing of their shapes turns float32
    assert not [eqn for eqn in equations(jaxpr)
                if eqn.primitive.name == "convert_element_type"
                and eqn.outvars[0].aval.dtype == jnp.float32
                and eqn.outvars[0].aval.shape in ((E, D, F), (E, F, D))]
    assert ("ragged_dot_general" in primitives(jaxpr)) == (
        body == "grouped")


@pytest.mark.parametrize("held", [(0, 72), (36, 36), (30, 12)])
def test_both_bodies_count_the_same_tokens(held, monkeypatch):
    w = weights(3)
    first, count = held
    sl = slice(first, first + count)
    got = {}
    for body in BODIES:
        force(monkeypatch, body)
        got[body] = dropless_moe(
            jnp.asarray(w["x"]), jnp.asarray(w["router"]),
            jnp.asarray(w["wg"][sl]), jnp.asarray(w["wu"][sl]),
            jnp.asarray(w["wd"][sl]), top_k=K, experts_held=held)
    np.testing.assert_array_equal(got["dense"][1], got["grouped"][1])
    np.testing.assert_allclose(got["dense"][0], got["grouped"][0], **TOL)


@pytest.mark.parametrize("body", BODIES, indirect=True)
@pytest.mark.parametrize("rows", [256, 1024])
def test_a_prefills_rows_against_the_token_loop(rows, body):
    """A prefill's row counts, which the rule sends through either body
    by the router's shape, with a share of the experts held (30 to 41
    of 72: most picks fall on absent experts) and a held expert, 33,
    that no row picks (every token's first feature is 1 and that
    router weight -100: its logit lies 100 under the others')."""
    w = weights(4, ties=False, rows=rows)
    w["x"][:, 0] = 1.0
    w["router"][0, 33] = -100.0
    first, count = 30, 12
    sl = slice(first, first + count)
    y, tokens = dropless_moe(
        jnp.asarray(w["x"]), jnp.asarray(w["router"]),
        jnp.asarray(w["wg"][sl]), jnp.asarray(w["wu"][sl]),
        jnp.asarray(w["wd"][sl]), top_k=K, experts_held=(first, count))
    y_ref, tokens_ref = loop_oracle(w, first, count)
    np.testing.assert_allclose(y, y_ref, **TOL)
    np.testing.assert_array_equal(tokens, tokens_ref)
    assert tokens[33 - first] == 0 < np.delete(tokens, 33 - first).min()
    assert int(tokens.sum()) < rows * K     # picks on absent experts


def experts_dense_by_expert(x, w_gate, w_up, w_down, local, gates):
    """The dense body as it stood until PR 44: a result a held expert,
    (held, S, D) float32, weighted by the gate after the down product."""
    held = w_gate.shape[0]
    hit = local[:, :, None] == jnp.arange(held, dtype=local.dtype)
    gate = jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)
    h = (jax.nn.silu(jnp.einsum("sd,edf->esf", x, w_gate))
         * jnp.einsum("sd,edf->esf", x, w_up))
    return jnp.einsum("esd,se->sd", jnp.einsum("esf,efd->esd", h, w_down),
                      gate)


@pytest.mark.parametrize("rows", [16, 32, 512])
def test_the_dense_body_weighs_before_the_down_product_to_no_effect(
        rows, monkeypatch):
    """Gate times hidden activations, summed over (expert, width) at
    once, is the former gate times a result an expert, term for term:
    float32 roundoff apart at a decode step's rows and at a prefill's,
    and exactly 0.0 where no held expert was picked."""
    w = weights(5, ties=False, rows=rows)
    args = (jnp.asarray(w["x"]), jnp.asarray(w["router"]),
            jnp.asarray(w["wg"][:36]), jnp.asarray(w["wu"][:36]),
            jnp.asarray(w["wd"][:36]))
    force(monkeypatch, "dense")
    new, n_new = dropless_moe(*args, top_k=K, experts_held=(0, 36))
    monkeypatch.setattr(moe, "_experts_dense", experts_dense_by_expert)
    old, n_old = dropless_moe(*args, top_k=K, experts_held=(0, 36))
    np.testing.assert_allclose(new, old, **TOL)
    np.testing.assert_array_equal(n_new, n_old)
    # a lone held expert: rows that do not pick it get exact zeros
    y, tokens = dropless_moe(*args[:2], *(a[:1] for a in args[2:]),
                             top_k=K, experts_held=(0, 1))
    monkeypatch.undo()
    picked = np.asarray(jax.lax.top_k(args[0] @ args[1], K)[1] == 0).any(-1)
    assert 0 < picked.sum() == int(tokens[0]) < rows
    np.testing.assert_array_equal(np.asarray(y)[~picked], 0.0)


# (rows, dense) on both sides of every edge of the rule, for the two
# router shapes the benchmark holds
HYBRID = dict(top_k=10, experts=72, held=36)    # work ratio 7.2
XING = dict(top_k=4, experts=64, held=8)        # work ratio 16
RULE = [(HYBRID, 1, False), (HYBRID, 7, False), (HYBRID, 8, True),
        (HYBRID, 32, True), (HYBRID, 256, True), (HYBRID, 512, True),
        (HYBRID, 1024, True), (HYBRID, 4096, True),
        (XING, 1, False), (XING, 15, False), (XING, 16, True),
        (XING, moe.DENSE_MAX_ROWS, True),
        (XING, moe.DENSE_MAX_ROWS + 1, False), (XING, 2048, False),
        (XING, 14336, False)]


@pytest.mark.parametrize("shape,rows,dense", RULE, ids=[
    f"{'hybrid' if shape is HYBRID else 'xing'}-{rows}"
    for shape, rows, _ in RULE])
def test_the_static_counts_alone_choose_the_body(shape, rows, dense):
    """A decode step's rows (32 slots and 16 in the serving cells)
    stream every held expert densely: no grouped product and no sort of
    the pairs. So does a prefill whose router picks 10 of 72 (the dense
    body does 7.2 times the grouped body's products, under
    ``DENSE_MAX_WORK``), at any row count. A prefill whose router picks
    4 of 64 (16 times the products) is sorted into three grouped
    products once it has more than ``DENSE_MAX_ROWS`` rows, and so is
    any call whose picks are fewer than the experts, a prefill's lone
    last token for one: most held experts then get no row, and only the
    grouped body skips them. Nothing but the static counts is asked:
    not how many experts are held, nor how wide they are."""
    k, e, held = shape["top_k"], shape["experts"], shape["held"]
    assert moe.streams_densely(rows, k, e) == dense
    assert nn.DroplessMoE(D, F, e, k, experts_held=(0, held)
                          ).streams_densely(rows) == dense
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda *a: dropless_moe(*a, top_k=k, experts_held=(0, held)))(
            sds(rows, D), sds(D, e), sds(held, D, F), sds(held, D, F),
            sds(held, F, D))
    used = primitives(jaxpr)
    assert used.count("ragged_dot_general") == (0 if dense else 3)
    # ``lax.top_k`` is a primitive of its own: a sort is the pairs' only
    assert ("sort" in used) == (not dense)
    assert ("gather" in used) == (not dense)
    # the dense body sums over the experts inside its down product: no
    # result a held expert, (held, rows, D), is ever made
    assert (held, rows, D) not in [
        getattr(v.aval, "shape", None) for eqn in equations(jaxpr)
        for v in eqn.outvars]


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


# --------------------------------------------------------------------------
# the grouped body's windows (PR 49): the pairs on held experts alone,
# ``window_rows`` of them a pass of the three grouped products
# --------------------------------------------------------------------------

WE, WK, WHELD = 16, 2, (0, 4)       # 16 router outputs, 2 a token, 4 held
WINDOW = 32                         # of 96 or 100 pairs, at a tile of 8


def classed(rows, all_held=0, none_held=0, one_held=0, seed=6):
    """Tokens and a router in which the first ``all_held`` tokens send
    both picks to held experts, the next ``none_held`` both to absent
    ones and the next ``one_held`` one each way (three indicator
    features and router rows of 50 on them); the rest route by their
    other, random features."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    x, router = f(rows, D), f(D, WE)
    x[:, :3], router[:3] = 0.0, 0.0
    marks = np.repeat([0, 1, 2], [all_held, none_held, one_held])
    x[np.arange(len(marks)), marks] = 1.0
    router[0, :4], router[1, 4:], router[2, [0, 5]] = 50.0, 50.0, 50.0
    return tuple(jnp.asarray(a) for a in (
        x, router, 0.3 * f(4, D, F), 0.3 * f(4, D, F), 0.3 * f(4, F, D)))


def expert_loop(x, router, wg, wu, wd):
    """Held expert by held expert over every token, the gate 0.0 where
    the token did not pick it: the terms the grouped body owes."""
    gates, top_i = moe.route(x @ router, WK)
    y = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        gate = jnp.sum(jnp.where(top_i == WHELD[0] + e, gates, 0.0), -1)
        y += gate[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                              @ wd[e])
    return y


def windowed(x, router, wg, wu, wd):
    return dropless_moe(x, router, wg, wu, wd, top_k=WK,
                        experts_held=WHELD)


def value_and_grads(fn, args, seed=7):
    """(y, the gradients of ``sum(y * c)`` in the tokens and the three
    expert tensors) for a fixed random ``c``."""
    c = jnp.asarray(np.random.default_rng(seed).standard_normal(
        args[0].shape).astype(np.float32))
    take = lambda out: out[0] if isinstance(out, tuple) else out
    y = take(fn(*args))
    grads = jax.grad(lambda *a: jnp.sum(take(fn(*a)) * c),
                     argnums=(0, 2, 3, 4))(*args)
    return y, grads


@pytest.fixture
def small_windows(monkeypatch):
    force(monkeypatch, "grouped")
    monkeypatch.setattr(moe, "WINDOW_TILE", 8)


def unwritten_past_the_groups(real):
    """``lax.ragged_dot`` as the TPU runs it, made worse: the rows of
    the left operand that belong to no group are NaN before the product
    reads them, and the rows of the result that belong to no group are
    NaN, going forward and in the transpose with respect to the left
    operand (the chip leaves them unwritten; the CPU writes zeros, which
    hides a missing mask)."""
    def past(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None],
                         a, jnp.nan)

    @jax.custom_vjp
    def product(lhs, rhs, sizes):
        return past(real(past(lhs, sizes), rhs, sizes,
                         preferred_element_type=jnp.float32), sizes)

    def fwd(lhs, rhs, sizes):
        return product(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(kept, g):
        lhs, rhs, sizes = kept
        _, pull = jax.vjp(lambda a, b: real(
            a, b, sizes, preferred_element_type=jnp.float32),
            past(lhs, sizes), rhs)
        d_lhs, d_rhs = pull(g)
        return past(d_lhs, sizes), d_rhs, None

    product.defvjp(fwd, bwd)
    return lambda lhs, rhs, sizes, preferred_element_type=None: product(
        lhs, rhs, sizes)


# (rows, all_held, none_held, one_held) -> the held pairs they make
WINDOWED = {
    "even-one-window": ((50, 0, 0, 0), None, 1),
    "every-pair-held-all-windows": ((50, 50, 0, 0), 100, 4),
    "every-pair-held-whole-windows": ((48, 48, 0, 0), 96, 3),
    "no-pair-held-no-window": ((50, 0, 50, 0), 0, 0),
    "held-pairs-end-on-a-windows-edge": ((50, 16, 34, 0), 32, 1),
    "one-row-past-the-edge": ((50, 16, 33, 1), 33, 2),
}


@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["plain", "rows-no-group-owns-are-nan"])
@pytest.mark.parametrize("case", list(WINDOWED))
def test_the_windows_give_the_held_pairs_terms_and_gradients(
        case, poisoned, small_windows, monkeypatch):
    """The windowed body against the loop over held experts, value and
    gradients in the tokens and all three expert tensors, whatever share
    of the pairs is held: one window under even routing; every pair held
    (all ``W`` windows run, the last part full where ``S k`` is no
    multiple of the window); none held (no window: result and gradients
    exactly 0); the held pairs ending on a window's edge and one row
    past it. ``poisoned``: the same with every row that no group owns
    NaN wherever a grouped product reads or writes one, as the chip
    leaves them unwritten: none may reach the result or a gradient."""
    (rows, *classes), pairs, windows = WINDOWED[case]
    args = classed(rows, *classes)
    assert moe.window_rows(rows, WK, WE, 4) == WINDOW
    want_y, want_g = value_and_grads(expert_loop, args)
    if poisoned:
        monkeypatch.setattr(jax.lax, "ragged_dot",
                            unwritten_past_the_groups(jax.lax.ragged_dot))
    (got_y, tokens), got_g = windowed(*args), value_and_grads(
        windowed, args)[1]
    held_pairs = int(tokens.sum())
    assert pairs in (None, held_pairs) and 0 < (held_pairs or 1) <= rows * WK
    assert moe.windows_run(held_pairs, rows, WK, WE, 4) == windows
    np.testing.assert_allclose(got_y, want_y, **TOL)
    for got, want in zip(got_g, want_g):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if not held_pairs:
        assert not np.asarray(got_y).any()
        assert not any(np.asarray(g).any() for g in got_g)


def test_the_windows_are_one_loop_body_whatever_their_count(monkeypatch):
    """The form PR 48 was refused for: the expert layer's program must
    not grow with the windows. Differentiated, the layer holds the same
    grouped products at 2 windows as at 8 (three forward; going
    backward the three again, over the rows gathered anew, the third
    of them traced and never read, and two a product for the
    transposes), and its lowered text is the same size to 5%."""
    force(monkeypatch, "grouped")
    args = classed(64)
    fn = jax.grad(lambda *a: jnp.sum(windowed(*a)[0]), argnums=(0, 2, 3, 4))
    dots, text = {}, {}
    for room, tile in ((1.25, 64), (0.5, 16)):
        monkeypatch.setattr(moe, "WINDOW_ROOM", room)
        monkeypatch.setattr(moe, "WINDOW_TILE", tile)
        w = -(-64 * WK // moe.window_rows(64, WK, WE, 4))
        dots[w] = primitives(jax.make_jaxpr(fn)(*args)).count(
            "ragged_dot_general")
        text[w] = len(jax.jit(fn).lower(*args).as_text())
    assert sorted(dots) == [2, 8]
    assert dots[2] == dots[8] == 3 + 3 + 6
    assert abs(text[2] - text[8]) < 0.05 * text[2]


def test_one_window_is_straight_line():
    """Where all the pairs fit one window (a decode step's rows, a
    prefill's lone last row, every expert held) there is no loop."""
    args = classed(6)
    assert moe.window_rows(6, WK, WE, 4) == 6 * WK
    used = primitives(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(windowed(*a)[0]), argnums=(0, 2)))(*args))
    assert "while" not in used and "cond" not in used
    assert used.count("ragged_dot_general") == 12


@pytest.mark.parametrize("counts,rows", [
    ((16384, 6, 128, 16), 15360),       # the trained cell: 12288 x 1.25
    ((14336, 4, 64, 8), 9216),          # Xing4.0's longest prefill
    ((4096, 8, 256, 16), 2560),         # a part of a GLM-5 prefill
    ((16, 8, 256, 16), 128),            # its decode step: every pair
    ((1, 10, 72, 36), 10),              # a prefill's lone last row
    ((24, 10, 72, 72), 240)])           # every expert held
def test_a_window_is_a_quarter_over_the_even_share(counts, rows):
    assert moe.window_rows(*counts) == rows
    assert rows == counts[0] * counts[1] or rows % moe.WINDOW_TILE == 0
