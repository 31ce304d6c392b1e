"""A prefill on the contiguous arena reads the weights once: the first
token's logits are the head applied to the chunk's own row ``plen - 1``
(``_chunk_logits(..., valid_len=plen, head_at=plen - 1)``), and no step
of the last prompt token through the whole model follows the chunk.

The program it replaced is written out here (``old_prefill``: the chunk
cache-only at ``valid_len=plen - 1``, then ``_step_logits`` at
``plen - 1``) and both run from the same dirty arena, for the three
families the arena serves (dense attention; state-space + attention +
routed experts; power retention), at prompt lengths 1, 2, inside the
bucket, on and just past a chunk boundary of the recurrences (chunk 8),
and the whole bucket. Both sides are float32 and differ in the order of
sums only (the chunk's masked attention and chunked scan against the
step's), so logits and state agree to 1e-4 of their standard
deviation (tests/test_hybrid.py's ``close``), and the argmax is the same.

Then the program's shape, so that the second pass cannot come back
unseen: its jaxpr holds the products of one cache-only chunk and one
head, and for the dense model as many as the decode step; and the
counter ``ArenaCounters.prefill_resteps``, which stays 0 on the
contiguous arena and counts on the paths that keep the re-step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.serving import BatchedDecoder
from test_hybrid import build, build_retention, close

SLOTS, CAPACITY, LB, SLOT = 3, 64, 16, 1
PLENS = [1, 2, 5, 8, 9, LB]
FAMILIES = ["dense", "hybrid", "retention"]


@functools.lru_cache(maxsize=None)
def family(name):
    """(model, decoder at bucket ``LB``, the old program jitted)."""
    if name == "dense":
        pt.seed(0)
        model = GPTForCausalLM(GPTConfig.tiny()).eval()
    else:
        model = (build() if name == "hybrid" else build_retention())[1]
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=LB)
    return model, dec, jax.jit(old_prefill(model, dec))


def old_prefill(model, dec):
    """What ``_prefill_fn`` was before it took the chunk's own row."""
    def prefill(mstate, caches, padded, plen, s):
        def body(row):
            _, row = model._chunk_logits(
                padded[None], dec._fresh_row(row), 0, head=False,
                valid_len=plen - 1)
            last = lax.dynamic_index_in_dim(padded, plen - 1,
                                            keepdims=False)
            return model._step_logits(last[None], row, plen - 1)

        with inject_state((model, *mstate)):
            logits, new = serving._row_apply(caches, s, body)
        return new, logits[0]

    return prefill


def dirty(caches, seed):
    """An arena that a slot's last request and idle steps have left
    junk in: every leaf drawn, so a state not started from zeros or a
    key read above the cursor shows."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda c: jnp.asarray(rng.standard_normal(c.shape), c.dtype),
        caches)


@pytest.mark.parametrize("plen", PLENS)
@pytest.mark.parametrize("name", FAMILIES)
def test_one_pass_prefill_is_the_chunk_and_step_it_replaced(name, plen):
    model, dec, old = family(name)
    vocab = model.cfg.vocab_size
    padded = np.zeros((LB,), np.int32)
    padded[:plen] = np.random.default_rng(100 + plen).integers(
        0, vocab, plen)
    arena = dirty(dec.caches, plen)
    want_arena, want = old(dec._mstate, arena, jnp.asarray(padded), plen,
                           SLOT)
    # the program consumes the arena it is given: hand it a copy
    got_arena, got = dec._prefill_fn(LB)(
        dec._mstate, jax.tree_util.tree_map(jnp.copy, arena),
        jnp.asarray(padded), plen, SLOT)
    assert got.shape == (vocab,)
    close(got, want)
    assert int(np.argmax(got)) == int(np.argmax(want))
    for kind, g, w, before in zip(dec._kinds, got_arena, want_arena, arena):
        for gl, wl, bl in zip(*map(jax.tree_util.tree_leaves,
                                   (g, w, before))):
            others = [s for s in range(SLOTS) if s != SLOT]
            np.testing.assert_array_equal(np.asarray(gl)[others],
                                          np.asarray(bl)[others])
            if kind == "kv":
                # keys and values of the prompt; what lies above the
                # cursor is masked and differs where a recurrence below
                # it stopped one position earlier
                close(gl[SLOT, :plen], wl[SLOT, :plen])
                np.testing.assert_array_equal(np.asarray(gl[SLOT, LB:]),
                                              np.asarray(bl[SLOT, LB:]))
            else:
                # the convolution's tail, the recurrent state and its
                # denominators after exactly plen tokens
                close(gl[SLOT], wl[SLOT])


# --------------------------------------------------------------------------
# the program's shape
# --------------------------------------------------------------------------

def products(jaxpr) -> int:
    """Matrix products of a jaxpr, those of every nested one too (the
    routed experts' are three ``dot_general``s in the dense body and
    three grouped ``ragged_dot``s in the other:
    ``nn.moe.streams_densely``)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in ("dot_general", "ragged_dot",
                                    "ragged_dot_general")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += products(sub)
    return n


def products_of(fn, *args) -> int:
    return products(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("name", FAMILIES)
def test_the_prefill_program_holds_one_pass_of_products(name):
    model, dec, old = family(name)
    args = (dec._mstate, dec.caches, jnp.zeros((LB,), jnp.int32), 5, SLOT)

    def chunk_only(mstate, caches, padded, plen, s):
        with inject_state((model, *mstate)):
            _, new = serving._row_apply(
                caches, s, lambda row: model._chunk_logits(
                    padded[None], row, 0, head=False, valid_len=plen))
        return new

    got = products_of(dec._prefill_fn(LB), *args)
    # every product of the chunk and the head's one: nothing else. The
    # hybrid's last block routes the one row the head reads, not the
    # chunk's ``LB``: its experts take the grouped body there, whose
    # three grouped products stand where the dense body's three stood
    # (it adds a token's picks up by a scatter-add, no product of its
    # own since PR 49)
    if name == "hybrid":
        assert model.expert_layers(LB, 1) == (6, 5)
    assert got == products_of(chunk_only, *args) + 1
    # the counter sees a second pass: the old program held the step's
    # products too (a step has the head and one product a Linear)
    linears = sum(type(m).__name__ == "Linear"
                  for _, m in model.named_sublayers())
    assert products_of(old, *args) >= got + linears
    if name == "dense":
        # attention's two products and the Linears' are the same count
        # at one position a row as over a chunk: the decode step's
        step_fn, step_args = dec._step_call()
        assert got == products_of(step_fn, *step_args)


def serve(**kw):
    pt.seed(0)
    dec = BatchedDecoder(GPTForCausalLM(GPTConfig.tiny()).eval(),
                         slots=SLOTS, capacity=128, prompt_bucket=8, **kw)
    rng = np.random.default_rng(3)
    for plen in (1, 5, 8, 13, 3):
        dec.submit(rng.integers(0, 512, plen).astype(np.int32), 4)
    dec.run()
    return dec.counters


def test_prefill_resteps_stays_zero_on_the_contiguous_arena():
    counters = serve()
    assert serving.last_counters is counters
    assert (counters.prefills, counters.prefill_resteps) == (5, 0)


@pytest.mark.parametrize("kw", [dict(prefill_chunk=8),
                                dict(pages=8, page_size=64)],
                         ids=["chunked", "paged"])
def test_prefill_resteps_counts_the_paths_that_keep_the_step(kw):
    counters = serve(**kw)
    assert (counters.prefills, counters.prefill_resteps) == (5, 5)
