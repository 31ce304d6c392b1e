"""The mixers' convention (``models/hybrid.py``'s module docstring), held
as a seam: (a) every kind ``MIXERS`` registers, built from a tiny
configuration and driven through the convention alone: the cache comes
back from a chunk, a step and a per-row step as ``init_cache`` gave it,
a chunk then a step is the same positions from empty state, ``counted``
holds int32 scalars, the declarations are on the class; (b) a kind the
shell has never heard of, DEFINED HERE and registered under a new name,
is served by ``BatchedDecoder`` and trained through ``forward_loss``
with no edit to ``paddle_tpu/``.

Tolerance, ``close``: float32 on both sides, differing in the order of
sums only (a chunked form against a step, decompressed heads against
the absorbed read): 1e-4 of the wanted values' standard deviation,
absolute, as ``tests/test_hybrid.py`` and ``tests/test_latent.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.models import hybrid
from paddle_tpu.models.gpt import loss_fn
from paddle_tpu.models.hybrid import MIXERS, HybridConfig, HybridForCausalLM
from paddle_tpu.nn.layer import Layer, inject_state
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.telemetry.scopes import SCOPES

CAPACITY = 16


class RunningMean(Layer):
    """A toy mixer: a projection of the causal running mean of the
    positions so far. Its state is ONE array a sequence, (B, hidden + 1)
    float32: the running sum and, last, the count."""

    state_kind, cache_record = "recurrent", None
    cached_scope = empty_scope = None
    counted = {}

    def __init__(self, cfg: HybridConfig):
        super().__init__()
        self.proj = nn.Linear(cfg.hidden_size, cfg.hidden_size,
                              bias_attr=False)

    def init_cache(self, batch, capacity, dtype=None):
        return jnp.zeros((batch, self.proj.weight.shape[0] + 1), jnp.float32)

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel=False):
        ones = jnp.ones((*x.shape[:2], 1), jnp.float32)
        run = cache[:, None] + jnp.cumsum(
            jnp.concatenate([x.astype(jnp.float32), ones], -1), axis=1)
        last = x.shape[1] if valid_len is None else valid_len
        cache = jax.lax.dynamic_index_in_dim(run, last - 1, 1, False)
        return self.proj((run[..., :-1] / run[..., -1:]).astype(x.dtype)), cache

    def forward_step(self, x, cache, t=None, decode_kernel=False):
        return self.forward_chunk(x, cache)

    forward_step_rows = forward_step

    def forward(self, x):
        return self.forward_chunk(x, self.init_cache(x.shape[0], 0))[0]


CASES = {      # a kind -> the tiny configuration it is built from
    "mamba": (HybridConfig.tiny, "mamba"),
    "attention": (HybridConfig.tiny, "attention"),
    "retention": (HybridConfig.tiny_retention, "retention"),
    "latent": (HybridConfig.tiny_latent, "latent"),
    "latent+indexer": (HybridConfig.tiny_sparse_latent, "latent"),
    "full_attention": (HybridConfig.tiny_window, "full_attention"),
    "sliding_attention": (HybridConfig.tiny_window, "sliding_attention"),
    "toy": (HybridConfig.tiny, "running_mean"),
}


@pytest.fixture
def toy_kind(monkeypatch):
    monkeypatch.setitem(MIXERS, "running_mean", RunningMean)


def close(got, want, tol=1e-4):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=tol * want.std())


def test_every_registered_kind_is_a_case():
    assert set(MIXERS) == {kind for _, kind in CASES.values()} - {
        "running_mean"}


def same_cache(got, want):
    """``got`` is ``want``'s pytree: structure, shapes and types."""
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype


def int32_scalars(counted):
    assert isinstance(counted, dict)
    for name, n in counted.items():
        assert isinstance(name, str) and n.shape == () and (
            n.dtype == jnp.int32), (name, n)


@pytest.mark.parametrize("case", list(CASES))
def test_a_kind_answers_the_convention(case, toy_kind):
    make, kind = CASES[case]
    cfg = make()
    pt.seed(5)
    mixer = MIXERS[kind](cfg).eval()
    # what it declares is on a class, and is one of the things it may be
    for name in ("state_kind", "cache_record", "cached_scope",
                 "empty_scope", "counted"):
        assert any(name in vars(c) for c in type(mixer).__mro__), name
    assert mixer.state_kind in ("kv", "recurrent")
    assert mixer.cache_record in (
        ("heads", "ring", "latent") if mixer.state_kind == "kv"
        else (None,))
    assert {mixer.cached_scope, mixer.empty_scope} <= {None, *SCOPES}

    s = 11                       # 12 positions: past the indexer's 8
    #                              and the sliding kind's window of 8
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, s + 1, cfg.hidden_size)), jnp.float32)
    want = mixer(x)              # from empty state, causal
    empty = mixer.init_cache(2, CAPACITY, jnp.float32)
    for leaf in jax.tree_util.tree_leaves(empty):
        assert leaf.shape[0] == 2

    # a chunk of s positions, then a step: s + 1 positions from empty
    a, cache = mixer.forward_chunk(x[:, :s], empty, 0, None, False)
    same_cache(cache, empty)
    int32_scalars(mixer.counted)
    close(a, want[:, :s])
    one, stepped = mixer.forward_step(x[:, s:], cache, jnp.int32(s), False)
    same_cache(stepped, empty)
    int32_scalars(mixer.counted)
    close(one[:, 0], want[:, s])
    rows, stepped_rows = mixer.forward_step_rows(
        x[:, s:], cache, jnp.full((2,), s, jnp.int32), False)
    same_cache(stepped_rows, empty)
    int32_scalars(mixer.counted)
    close(rows[:, 0], want[:, s])

    # a prompt of 5 in a bucket of 8: the padding is written above the
    # cursor or not at all, and the step at 5 continues the prompt
    _, cache = mixer.forward_chunk(x[:, :8], empty, 0, 5, False)
    same_cache(cache, empty)
    one, _ = mixer.forward_step_rows(
        x[:, 5:6], cache, jnp.full((2,), 5, jnp.int32), False)
    close(one[:, 0], want[:, 5])


# --------------------------------------------------------------------------
# (b) a kind of the test's own, through the shell
# --------------------------------------------------------------------------

def toy_model(seed=0):
    pt.seed(seed)
    cfg = HybridConfig(
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        layer_types=("running_mean", "attention", "running_mean"),
        channel_mix="mlp", mlp_width=48)
    return cfg, HybridForCausalLM(cfg).eval()


def test_a_new_kind_is_served_without_an_edit_to_the_shell(toy_kind):
    """Greedy tokens through ``BatchedDecoder`` (bucketed one-pass
    prefill, per-row decode steps, a slot used twice) equal the same
    model stepped by hand, a token at a time at one cursor."""
    cfg, model = toy_model()
    assert model.cache_kinds == ["recurrent", "kv", "recurrent"]
    assert model.cache_records == [None, "heads", None]
    dec = BatchedDecoder(model, slots=2, capacity=CAPACITY, prompt_bucket=8)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 8, 3)]
    rids = [dec.submit(p, 4) for p in prompts]
    out = dec.run()
    assert model.step_counters() == {}

    @jax.jit
    def step(mstate, caches, tok, t):
        with inject_state((model, *mstate)):
            return model._step_logits(tok, caches, t)

    for prompt, rid in zip(prompts, rids):
        caches, toks, got = model.init_cache(1, CAPACITY), list(prompt), []
        for t in range(len(prompt) + 3):
            logits, caches = step(dec._mstate, caches,
                                  jnp.asarray(toks[t:t + 1]), t)
            if t >= len(prompt) - 1:
                got.append(int(np.argmax(logits[0])))
                toks.append(got[-1])
        assert list(out[rid]) == got


def test_a_new_kind_trains_through_forward_loss(toy_kind):
    cfg, model = toy_model()
    ids = jnp.asarray(np.random.default_rng(10).integers(
        0, cfg.vocab_size, (2, 9)))
    labels = jnp.concatenate([ids[:, 1:], jnp.full((2, 1), -100)], axis=1)
    params = dict(model.named_parameters())

    def loss(p):
        return model.functional_call(p, ids, training=True,
                                     method="forward_loss")[0]

    value, grads = jax.value_and_grad(loss)(params)
    close(value, loss_fn(model(ids), labels), 1e-5)
    for name in ("blocks.0.mixer.proj.weight", "blocks.2.mixer.proj.weight"):
        assert float(jnp.abs(grads[name]).max()) > 0


def test_an_unknown_kind_is_refused_by_name():
    with pytest.raises(pt.core.EnforceError, match="running_mean"):
        hybrid.HybridBlock(HybridConfig.tiny(), "running_mean")
