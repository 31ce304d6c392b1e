"""A declared parameter is cast to the compute type once, where the
parameters enter the model (``nn.Layer.functional_call``).

Under a policy whose compute type is narrower than a parameter's, a
parameter that its layer reads ONLY through ``Policy.cast_to_compute``
(``create_parameter(..., compute_cast=True)``: ``Linear`` and the
``Conv*`` layers) is converted once inside the differentiated function;
every product of the call reads that leaf and the gradient comes back
through the one convert in the parameter's own type. Nothing reorders:
a convert's transpose is a convert, and each ``Linear`` of these models
is used once a forward, so its cotangent is one bf16 array either way.
**Loss and every gradient leaf equal the formulation that casts at each
use (the parent's) to the last bit**, in ``_step``, in ``_accum_step``
and on a two-device dp mesh. The one place where the formulations part
is a layer applied SEVERAL times in one call: its uses' cotangents then
meet in the compute type before the convert where they met in float32
after it, one rounding of bf16; the last test holds that to 1 ulp.

**The narrow copy has one reader: the declaring layer's own code**
(``Layer.__call__``). A parent that takes ``child.weight``, a wrapper
that goes through ``child._params`` or calls ``child.forward`` itself
(``quant.QuantedLayer``), reads the parameter as it is stored, so what
it computes is what it computed: QAT, the latent attention's by-head
reads and the dropless router are held to the cast-at-each-use form
here, and a source walk refuses the one reader the mechanism cannot
see, a subclass of a declaring layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer, parallel
from paddle_tpu.core.dtypes import policy_scope
from paddle_tpu.models import gpt as G
from paddle_tpu.nn.layer import Layer

ROWS, SEQ = 4, 16


def tiny(dtype=None, **kw):
    pt.seed(0)
    model = G.GPTForCausalLM(dataclasses.replace(
        G.GPTConfig.tiny(), remat=True, tie_embeddings=False, **kw))
    if dtype is not None:
        model.set_parameters({k: v.astype(dtype) for k, v
                              in model.named_parameters().items()})
    return model


def loss_builder_of(model):
    def loss_builder(params, buffers, rng, ids):
        loss, new_buffers = model.functional_call(
            params, ids, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    return loss_builder


class GradsOut:
    """An optimizer whose update IS the gradient, so a step's new
    parameters are its gradient leaves, bit for bit."""

    def init(self, params):
        return {}

    def apply(self, params, grads, state):
        return grads, state


@pytest.fixture
def each_use(monkeypatch):
    """The parent's formulation: no cast on entry, every ``Linear``
    converts its float32 weight at each use."""
    def switch():
        monkeypatch.setattr(Layer, "_cast_once", lambda self, params: params)

    return switch


def batch(seed=1):
    return jax.random.randint(jax.random.key(seed), (ROWS, SEQ), 0, 512)


def trainer(model, opt=None, **kw):
    kw.setdefault("mesh", pt.build_mesh(dp=1, devices=jax.devices()[:1]))
    return parallel.Trainer(model, opt or GradsOut(), loss_builder_of(model),
                            **kw)


def linear_leaves(model):
    return {f"{path}.{leaf}" for path, sub in model.named_sublayers()
            if isinstance(sub, nn.Linear) for leaf in sub._params}


def assert_same_bits(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def test_the_declared_set_is_the_linear_leaves():
    model = tiny()
    names = model.compute_cast_names()
    assert names == linear_leaves(model)
    assert len(names) == 2 * 7      # q, k, v, out, gate, up, down a block
    assert not {n for n in names if "norm" in n or "embed" in n
                or n == "lm_head"}


def test_convs_declare_and_no_other_layer_does():
    conv = nn.Conv2D(3, 4, 3)
    assert conv.compute_cast_names() == {"weight", "bias"}
    assert nn.Conv2DTranspose(3, 4, 3).compute_cast_names() \
        == {"weight", "bias"}
    assert nn.RMSNorm(8).compute_cast_names() == frozenset()
    assert nn.Embedding(8, 4).compute_cast_names() == frozenset()
    # PR 47: the three expert stacks are declared too (the layer's own
    # code reads them through the policy's type alone); the selection
    # bias is not
    moe = nn.DroplessMoE(8, 16, 4, top_k=2, routing="sigmoid_noaux_tc")
    assert moe.compute_cast_names() == {"router.weight", "w_gate", "w_up",
                                        "w_down"}


class Spy:
    """Records the type of ``weight`` as each ``Linear``'s own forward
    reads it."""

    def __init__(self, monkeypatch):
        self.own = {}
        forward = nn.Linear.forward

        def spying(layer, x):
            self.own[id(layer)] = layer.weight.dtype
            return forward(layer, x)

        monkeypatch.setattr(nn.Linear, "forward", spying)


def test_what_reaches_the_model(monkeypatch):
    """Under ``mixed_bf16`` a ``Linear``'s own forward reads its leaves in
    bf16; every other reader, and every other leaf (norm scales, the
    embedding, ``lm_head``), gets float32 as stored. Under ``float32``
    every leaf arrives as it is, bf16 leaves too: no float32 copy."""
    model = tiny()
    spy = Spy(monkeypatch)
    params = dict(model.named_parameters())
    ids = batch()
    linears = [sub for _, sub in model.named_sublayers()
               if isinstance(sub, nn.Linear)]

    def from_outside(_ids):
        return (dict(model.named_parameters()),
                [lin.weight for lin in linears],
                [lin._params["weight"] for lin in linears])

    with policy_scope("mixed_bf16"):
        model.functional_call(params, ids, method="forward_loss")
        assert len(spy.own) == len(linears) == 2 * 7
        assert set(spy.own.values()) == {jnp.dtype(jnp.bfloat16)}
        model.from_outside = from_outside
        (bound, attrs, stored), _ = model.functional_call(
            params, ids, method="from_outside")
    assert all(bound[k] is params[k] for k in params)
    assert all(w.dtype == jnp.float32 for w in attrs + stored)
    narrow = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    for policy in ("float32", "mixed_bf16"):
        with policy_scope(policy):
            (bound, _, _), _ = model.functional_call(narrow, ids,
                                                     method="from_outside")
        assert all(bound[k] is narrow[k] for k in narrow), policy
    assert all(v.dtype == jnp.float32            # and the model keeps its own
               for v in model.named_parameters().values())
    assert not any(lin._narrow or lin._own_depth for lin in linears)
    root = nn.Linear(4, 4)      # the method a functional_call names is own code
    with policy_scope("mixed_bf16"):
        root.functional_call(dict(root.named_parameters()), jnp.ones((2, 4)))
    assert spy.own[id(root)] == jnp.bfloat16


def test_only_the_declaring_layers_own_call_reads_the_narrow_copy():
    """A parent's attribute read, ``_params``, a direct ``forward`` and a
    call after a wrapper swapped the stored leaf all see what is stored."""
    seen = {}

    class Parent(Layer):
        def __init__(self):
            super().__init__()
            self.f = nn.Linear(4, 4)

        def forward(self, x):
            seen["attribute"] = self.f.weight.dtype
            seen["_params"] = self.f._params["weight"].dtype
            seen["own"] = self.f(x).dtype, self.f._own_depth
            swapped, kept = self.f.weight * 2.0, self.f._params["weight"]
            self.f._params["weight"] = swapped
            try:
                seen["swapped"] = self.f(x)
                seen["direct"] = self.f.forward(x)
            finally:
                self.f._params["weight"] = kept
            return self.f(x)

    pt.seed(0)
    model = Parent()
    x = jax.random.normal(jax.random.key(0), (2, 4))
    with policy_scope("mixed_bf16"):
        out, _ = model.functional_call(dict(model.named_parameters()), x)
        twice = nn.Linear.forward(model.f, x) * 2.0 - model.f.bias
    assert seen["attribute"] == seen["_params"] == jnp.float32
    np.testing.assert_allclose(seen["swapped"], twice, rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(seen["swapped"], seen["direct"])
    assert not model.f._narrow and model.f._own_depth == 0


def test_no_layer_subclasses_a_declaring_one():
    """The one reader the mechanism cannot tell from the declaring
    layer is a subclass: its ``forward`` runs as the layer's own code.
    ``compute_cast=True`` stands in ``nn/layers.py`` and, for the routed
    experts' three stacks, in ``nn/moe.py::DroplessMoE`` (PR 47), and no
    class of the package derives from a layer that declares. Whoever adds one
    keeps its reads behind ``cast_to_compute`` and lists it here."""
    import ast
    import pathlib

    root = pathlib.Path(pt.__file__).parent
    declaring, derived = {}, []
    for path in sorted(root.rglob("*.py")):
        text = path.read_text()
        if "compute_cast=True" not in text and "class " not in text:
            continue
        for node in ast.walk(ast.parse(text)):
            if not isinstance(node, ast.ClassDef):
                continue
            bases = {getattr(b, "attr", getattr(b, "id", None))
                     for b in node.bases}
            if "compute_cast=True" in ast.get_source_segment(text, node):
                declaring[node.name] = str(path.relative_to(root))
            derived.append((node.name, bases))
    assert declaring == {"Linear": "nn/layers.py", "Conv2D": "nn/layers.py",
                         "Conv2DTranspose": "nn/layers.py",
                         "DroplessMoE": "nn/moe.py"}
    assert [name for name, bases in derived if bases & set(declaring)] == []


def _loss_and_grads(model, *args, method="forward"):
    params = dict(model.named_parameters())

    def lf(p):
        with policy_scope("mixed_bf16"):
            out, _ = model.functional_call(p, *args, training=True,
                                           method=method)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                   for leaf in leaves)

    return jax.jit(jax.value_and_grad(lf))(params)


def _outside_reader(name):
    pt.seed(0)
    key = jax.random.key(3)
    if name == "qat":
        from paddle_tpu import quant

        model = quant.quantize_model(nn.Sequential(
            nn.Linear(16, 32, act="relu"), nn.Linear(32, 8)))
        assert model.compute_cast_names() == {
            "0.inner.weight", "0.inner.bias", "1.inner.weight",
            "1.inner.bias"}
        return model, (jax.random.normal(key, (8, 16)),)
    if name == "latent":
        from paddle_tpu.nn.latent import LatentAttention

        model = LatentAttention(32, 2, q_rank=16, kv_rank=8, nope_dim=8,
                                rope_dim=4, v_dim=8)
        assert {"q_b_proj.weight", "kv_b_proj.weight", "out_proj.weight"} \
            <= model.compute_cast_names()
        return model, (jax.random.normal(key, (2, 8, 32)),)
    model = nn.DroplessMoE(16, 32, 4, top_k=2)
    return model, (jax.random.normal(key, (2, 8, 16)),)


@pytest.mark.parametrize("name", ["qat", "latent", "dropless_moe"])
def test_a_layer_with_an_outside_reader_equals_the_cast_at_each_use(
        each_use, name):
    """``QuantedLayer`` fake-quantizes ``inner._params["weight"]`` and
    calls ``inner.forward`` itself; ``LatentAttention`` takes three of
    its projections' weights by head; ``DroplessMoE`` hands its router's
    to the routing function. Each reads the float32 master as before:
    the abs-max, the scale and the rounding of QAT run in float32."""
    model, args = _outside_reader(name)
    once = _loss_and_grads(model, *args)
    each_use()
    each = _loss_and_grads(model, *args)
    assert float(once[0]) == float(each[0])
    assert_same_bits(once[1], each[1])
    assert all(g.dtype == jnp.float32 for g in once[1].values())
    assert any(float(jnp.abs(g).max()) > 0 for g in once[1].values())


@pytest.mark.parametrize("accum", [1, 2], ids=["step", "accum_step"])
def test_loss_and_gradients_equal_the_cast_at_each_use(each_use, accum):
    """``GradsOut`` hands the (mean accumulated) gradient out as the new
    parameters: every leaf float32 and equal to the last bit."""
    got = {}
    for side in ("once", "each_use"):
        if side == "each_use":
            each_use()
        model = tiny()
        tr = trainer(model, amp="mixed_bf16", grad_accum_steps=accum)
        losses = [tr.train_step(batch(seed))[0] for seed in range(accum)]
        got[side] = ([float(x) for x in losses], dict(tr.params))
    assert got["once"][0] == got["each_use"][0]
    assert_same_bits(got["once"][1], got["each_use"][1])
    grads = got["once"][1]
    assert all(g.dtype == jnp.float32 for g in grads.values())
    assert all(float(jnp.abs(g).max()) > 0 for g in grads.values())


def test_updated_parameters_are_float32_and_equal_under_adam(each_use):
    got = {}
    for side in ("once", "each_use"):
        if side == "each_use":
            each_use()
        tr = trainer(tiny(), optimizer.Adam(1e-3), amp="mixed_bf16")
        losses = [float(tr.train_step(batch(s))[0]) for s in range(3)]
        got[side] = (losses, dict(tr.params))
    assert got["once"][0] == got["each_use"][0]
    assert_same_bits(got["once"][1], got["each_use"][1])
    assert all(v.dtype == jnp.float32 for v in got["once"][1].values())


def test_two_device_dp_mesh_equals_the_cast_at_each_use(each_use):
    devices = jax.devices()[:2]
    if len(devices) < 2:
        pytest.skip("needs two devices")
    got = {}
    for side in ("once", "each_use"):
        if side == "each_use":
            each_use()
        tr = trainer(tiny(), amp="mixed_bf16",
                     mesh=pt.build_mesh(dp=2, devices=devices))
        got[side] = (float(tr.train_step(batch())[0]), dict(tr.params))
    assert got["once"][0] == got["each_use"][0]
    assert_same_bits(got["once"][1], got["each_use"][1])


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def casts(closed_jaxpr):
    """The forward converts to bf16 under scope ``weight_cast``."""
    return [e for e in _equations(closed_jaxpr.jaxpr)
            if e.primitive.name == "convert_element_type"
            and "weight_cast" in str(e.source_info.name_stack)
            and e.params["new_dtype"] == jnp.bfloat16]


def _step_jaxpr(tr, ids):
    return jax.make_jaxpr(tr._step)(
        tr.params, tr.buffers, tr.opt_state, jax.random.key(0), ids)


@pytest.mark.parametrize("case", ["amp_none", "float32_policy",
                                  "bf16_leaves_float32_policy"])
def test_where_nothing_narrows_the_jaxpr_is_the_parents(each_use, case):
    dtype = jnp.bfloat16 if case.startswith("bf16") else None
    amp = None if case == "amp_none" else "float32"
    texts = []
    for side in ("once", "each_use"):
        if side == "each_use":
            each_use()
        tr = trainer(tiny(dtype), optimizer.Adam(1e-3), amp=amp)
        texts.append(_step_jaxpr(tr, batch()))
    assert not casts(texts[0])
    assert str(texts[0]) == str(texts[1])


def test_the_mixed_step_holds_one_convert_a_declared_leaf():
    model = tiny()
    tr = trainer(model, optimizer.Adam(1e-3), amp="mixed_bf16")
    assert len(casts(_step_jaxpr(tr, batch()))) \
        == len(model.compute_cast_names())


def test_a_bare_trainer_as_the_rehearsal_makes_it_steps_and_casts():
    """``benchmark/rehearse_compile.py`` builds a ``Trainer`` with
    ``object.__new__`` and six attributes: nothing the cast needs may
    live on the trainer."""
    model = tiny()
    tr = object.__new__(parallel.Trainer)
    tr.amp_policy = "mixed_bf16"
    tr.optimizer = optimizer.Adam(1e-3)
    tr._pmean_axes, tr.grad_compression, tr.plan = (), None, None
    tr.loss_builder = loss_builder_of(model)
    params = dict(model.named_parameters())
    args = (params, {}, tr.optimizer.init(params), jax.random.key(0),
            batch())
    assert len(casts(jax.make_jaxpr(tr._step)(*args))) \
        == len(model.compute_cast_names())
    loss, _, new_params, _, _ = jax.jit(tr._step)(*args)
    whole = trainer(tiny(), optimizer.Adam(1e-3), amp="mixed_bf16")
    assert float(loss) == float(whole.train_step(batch())[0])
    assert all(v.dtype == jnp.float32 for v in new_params.values())


def casts_in_loops(closed_jaxpr):
    """The ``weight_cast`` converts inside a scan, a ``shard_map`` or a
    rematted body."""
    found = []
    for e in _equations(closed_jaxpr.jaxpr):
        if e.primitive.name in ("scan", "shard_map", "checkpoint",
                                "remat"):
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    found += [c for c in _equations(inner)
                              if c.primitive.name == "convert_element_type"
                              and "weight_cast" in str(
                                  c.source_info.name_stack)]
    return found


def _stack(kind):
    """(model, call, input) of a loop over stacked blocks."""
    pt.seed(0)
    if kind == "scan_layers":
        model = nn.TransformerEncoder(3, 32, 2, 64, dropout=0.0,
                                      use_flash=False, remat=True,
                                      scan_layers=True)
        return model, model.functional_call, jax.random.normal(
            jax.random.key(1), (2, 8, 32))
    from paddle_tpu.parallel.pipeline import GPipe

    mesh = pt.build_mesh(pp=2, devices=jax.devices()[:2])
    blocks = [nn.Linear(16, 16, act="tanh") for _ in range(4)]
    gp = GPipe(blocks, num_microbatches=2, mesh=mesh)
    model = nn.LayerList(blocks)

    def call(params, x):
        stacked = {k: jnp.stack([params[f"{i}.{k}"] for i in range(4)])
                   for k in ("weight", "bias")}
        return gp(x, stacked), {}

    return model, call, jax.random.normal(jax.random.key(1), (4, 16))


@pytest.mark.parametrize("kind", ["scan_layers", "gpipe"])
def test_a_loop_over_stacked_blocks_equals_the_cast_at_each_use(each_use,
                                                                kind):
    """Both hand each block's ``functional_call`` a slice of the stacked
    leaves, and the call casts the slice in the loop's body (rematted:
    in the second forward again). Values as cast at each use, to the
    last bit: ``GPipe`` runs a block once a MICROBATCH, and a weight's
    cotangents still meet in float32 (a stack cast before the loop
    summed them in bf16: one ulp of bf16 off on a quarter of a bias;
    on a v5e it was also the slower of the two, PERF.md section 6)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    got = {}
    for side in ("once", "each_use"):
        if side == "each_use":
            each_use()
        model, call, x = _stack(kind)   # anew: a trace is cached by function
        params = dict(model.named_parameters())

        def lf(p):
            with policy_scope("mixed_bf16"):
                return jnp.sum(jnp.square(
                    call(p, x)[0].astype(jnp.float32)))

        grad = jax.value_and_grad(lf)
        jaxpr = jax.make_jaxpr(grad)(params)
        got[side] = jax.jit(grad)(params)
        if side == "each_use":
            assert not casts(jaxpr)
        else:
            assert casts_in_loops(jaxpr)
    assert float(got["once"][0]) == float(got["each_use"][0])
    assert_same_bits(got["once"][1], got["each_use"][1])
    assert all(g.dtype == jnp.float32 for g in got["once"][1].values())


def test_a_layer_used_twice_sums_its_cotangents_in_the_compute_type(
        each_use):
    """Where the formulations part: ``y = f(x1) + f(x2)`` through ONE
    ``Linear``. Cast at each use, the two bf16 cotangents of the weight
    are converted and added in float32; cast once, they are added in
    bf16 and converted. One rounding of bf16: 2^-8 of the sum."""
    class Twice(Layer):
        def __init__(self):
            super().__init__()
            self.f = nn.Linear(16, 16, bias_attr=False)

        def forward(self, a, b):
            return jnp.sum(self.f(a) * 0.5 + self.f(b) * 0.25)

    pt.seed(0)
    model = Twice()
    a, b = (jax.random.normal(jax.random.key(i), (8, 16)) for i in (1, 2))
    params = dict(model.named_parameters())

    def grad():
        def lf(p):
            with policy_scope("mixed_bf16"):
                return model.functional_call(p, a, b)[0]

        return jax.grad(lf)(params)["f.weight"]

    once = grad()
    each_use()
    each = grad()
    assert once.dtype == each.dtype == jnp.float32
    ulp = 2.0 ** -8 * jnp.maximum(jnp.abs(each), 2.0 ** -126)
    assert bool(jnp.all(jnp.abs(once - each) <= ulp))
