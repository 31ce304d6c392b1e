"""The serving arena is donated: every jitted program of
``BatchedDecoder`` that takes the arena (contiguous caches, page pools,
the draft's caches, a hybrid model's recurrent state) and returns it
marks every leaf of it as donated in its lowered text, and the compiled
program aliases every such leaf to an output, so the cursor writes
happen in place and no program copies the arena. Beside that: greedy
tokens are the parent commit's (donation changes where a result is
written, never the result), the arena passes the donation-safety check
at construction and a handoff's host arrays are laundered before they
reach a donated program, and a program that fails after it consumed
the arena marks the decoder lost instead of leaving it to raise
"Array has been deleted" for ever.

jax implements donation on the CPU backend, so the runs below execute
the donated programs for real: a reader of an old arena fails here."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis import donation
from paddle_tpu.core import EnforceError
from paddle_tpu.core.config import FLAGS
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import ArenaLostError, BatchedDecoder
from paddle_tpu.serving_router import LocalReplica

SLOTS, CAPACITY, BUCKET = 3, 128, 8


def dense(seed=0):
    pt.seed(seed)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def hybrid():
    """tests/test_hybrid.py's model: two periods of (mamba, mamba,
    attention), the leaves that start at 0 or 1 drawn."""
    from test_hybrid import build

    return build()[1]


def decoder(model=None, **kw):
    return BatchedDecoder(model or dense(), slots=SLOTS, capacity=CAPACITY,
                          prompt_bucket=BUCKET, **kw)


PAGED = dict(pages=6, page_size=64)
i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
padded = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731


# --------------------------------------------------------------------------
# every arena-taking program: (decoder, program, its arguments, which of
# them are the arena)
# --------------------------------------------------------------------------

def _step(kd, **kw):
    dec = decoder(decode_steps=kd, **kw)
    fn, args = dec._step_call()
    return fn, args, (1,)


def _prefill():
    dec = decoder()
    return (dec._prefill_fn(16),
            (dec._mstate, dec.caches, padded(16), 5, 0), (1,))


def _prefill_paged():
    dec = decoder(**PAGED)
    return (dec._prefill_fn_paged(16),
            (dec._mstate, dec.pools, padded(dec.n_log), padded(16), 5),
            (1,))


def _chunk():
    dec = decoder(prefill_chunk=8)
    return (dec._chunk_fn_contig(8),
            (dec._mstate, dec.caches, padded(8), i32(0), i32(0)), (1,))


def _restep():
    dec = decoder(prefill_chunk=8)
    return (dec._restep_contig(),
            (dec._mstate, dec.caches, i32(1), i32(4), i32(0)), (1,))


def _suffix(which):
    dec = decoder(prefix_cache=True, **PAGED)
    chunk_fn, restep_fn = dec._suffix_fns(16)
    row = padded(dec.n_log)
    if which == "chunk":
        return chunk_fn, (dec._mstate, dec.pools, row, padded(16), 64), (1,)
    return restep_fn, (dec._mstate, dec.pools, row, i32(1), 4), (1,)


def _draft_prefill():
    dec = decoder(draft=dense(1), gamma=3)
    return (dec._draft_prefill_fn(16),
            (dec._dstate, dec.caches_d, padded(16), i32(0)), (1,))


def _spec(**kw):
    dec = decoder(draft=dense(1), gamma=3, **kw)
    gens = jnp.zeros((SLOTS,), jnp.uint32)
    arena = dec.pools if dec.paged else dec.caches
    table = jnp.asarray(dec.table) if dec.paged else None
    return (dec._build_spec_step(),
            (dec._mstate, dec._dstate, arena, table, dec.caches_d,
             dec.tok, dec.t, gens), (2, 4))


def _hybrid(which):
    dec = decoder(hybrid())
    if which == "step":
        fn, args = dec._step_call()
        return fn, args, (1,)
    return (dec._prefill_fn(16),
            (dec._mstate, dec.caches, padded(16), 5, 0), (1,))


def _handoff_import():
    dec = decoder(**PAGED)
    page = np.zeros((1, 64) + tuple(dec._allocator.shape[2:]), np.float32)
    blocks = [(page, page)] * len(dec.pools)
    return dec._import_fn(), (dec.pools, i32([2]), blocks), (0,)


PROGRAMS = {
    "decode_step": lambda: _step(1),
    "decode_step_k4": lambda: _step(4),
    "decode_step_paged": lambda: _step(1, **PAGED),
    "decode_step_paged_k4": lambda: _step(4, **PAGED),
    "decode_step_paged_int8": lambda: _step(1, kv_dtype="int8", **PAGED),
    "prefill": _prefill,
    "prefill_paged": _prefill_paged,
    "prefill_chunk": _chunk,
    "prefill_restep": _restep,
    "prefill_suffix": lambda: _suffix("chunk"),
    "prefill_restep_paged": lambda: _suffix("restep"),
    "draft_prefill": _draft_prefill,
    "spec_round": _spec,
    "spec_round_paged": lambda: _spec(**PAGED),
    "hybrid_prefill": lambda: _hybrid("prefill"),
    "hybrid_decode_step": lambda: _hybrid("step"),
    "handoff_import": _handoff_import,
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_arena_leaf_is_donated_and_aliased(program):
    fn, args, arena_argnums = PROGRAMS[program]()
    leaves = [leaf for i in arena_argnums
              for leaf in jax.tree_util.tree_leaves(args[i])]
    assert leaves
    lowered = fn.lower(*args)
    # the lowered text: a donated argument carries the output it may
    # alias, or the bare donor mark where jax found no output of its
    # shape and type
    marks = len(re.findall(r"tf\.aliasing_output|jax\.buffer_donor",
                           lowered.as_text()))
    assert marks == len(leaves), (marks, len(leaves))
    # the compiled program: one alias entry a leaf, and together they
    # cover the arena's bytes
    compiled = lowered.compile()
    header = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                       compiled.as_text(), re.S)
    assert header, "the compiled program aliases nothing"
    entries = re.findall(r"\(\d+, \{\}, (?:may|must)-alias\)",
                         header.group(1))
    assert len(entries) == len(leaves), (len(entries), len(leaves))
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased == sum(int(leaf.nbytes) for leaf in leaves)


def test_no_serving_program_is_jitted_without_the_helper():
    """ISSUE 31's grep: ``jax.jit(`` occurs once in serving.py, inside
    ``_arena_jit``."""
    import paddle_tpu.serving as S

    with open(S.__file__) as f:
        sites = [line for line in f if "jax.jit(" in line
                 and not line.lstrip().startswith(("#", '"', "`"))]
    assert len(sites) == 1 and "donate_argnums=arena_argnums" in sites[0]


# --------------------------------------------------------------------------
# the tokens are the parent's
# --------------------------------------------------------------------------

# greedy tokens of commit e498dda (the parent of the PR that donated the
# arena), 6 a prompt, prompts of 5, 11, 3, 8 and 17 tokens drawn from
# default_rng(31): every dense form emitted the same there
DENSE = [[3, 3, 93, 93, 93, 93], [489, 482, 482, 482, 506, 45],
         [293, 67, 45, 136, 106, 121], [32, 341, 341, 207, 88, 93],
         [479, 315, 315, 315, 315, 315]]
HYBRID = [[134, 159, 71, 131, 102, 139], [30, 79, 72, 175, 139, 236],
          [108, 29, 151, 151, 63, 250], [182, 36, 137, 51, 31, 22],
          [108, 155, 240, 104, 154, 238]]
FORMS = {
    "dense": (dense, lambda: {}, DENSE),
    "dense_k4": (dense, lambda: dict(decode_steps=4), DENSE),
    "dense_paged": (dense, lambda: PAGED, DENSE),
    "dense_paged_prefix_chunked": (
        dense, lambda: dict(prefix_cache=True, prefill_chunk=8, **PAGED),
        DENSE),
    "dense_chunked": (dense, lambda: dict(prefill_chunk=8), DENSE),
    "dense_speculative": (dense, lambda: dict(draft=dense(1), gamma=3),
                          DENSE),
    "hybrid": (hybrid, lambda: {}, HYBRID),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_greedy_tokens_are_the_parents(form):
    build, options, want = FORMS[form]
    model = build()
    dec = decoder(model, **options())
    rng = np.random.default_rng(31)
    rids = [dec.submit(rng.integers(1, model.cfg.vocab_size,
                                    (n,)).astype(np.int32), 6)
            for n in (5, 11, 3, 8, 17)]
    out = dec.run()
    assert [out[r].tolist() for r in rids] == want


def test_warm_step_warms_the_speculative_round_too():
    prompt = np.arange(1, 6, dtype=np.int32)
    plain = decoder()
    rid = plain.submit(prompt, 6)
    want = plain.run()[rid].tolist()
    dec = decoder(draft=dense(1), gamma=3)
    dec.warm_step()
    assert dec.ready and dec._spec_fn is not None
    rid = dec.submit(prompt, 6)
    assert dec.run()[rid].tolist() == want


# --------------------------------------------------------------------------
# the donation-safety check, and the handoff's host arrays
# --------------------------------------------------------------------------

def _host_backed(model):
    """``model.init_cache`` as a restore from host arrays would leave
    it: every leaf put on the device from numpy, which the CPU client
    may alias and not copy."""
    init = model.init_cache

    def init_cache(slots, capacity, *a, **k):
        with donation.track_host_transfers():
            return jax.tree_util.tree_map(
                lambda leaf: jax.device_put(np.asarray(leaf)),
                init(slots, capacity, *a, **k))

    model.init_cache = init_cache
    return model


def _one_buffer_twice(model):
    init = model.init_cache

    def init_cache(slots, capacity, *a, **k):
        return [(k_, k_) for k_, _ in init(slots, capacity, *a, **k)]

    model.init_cache = init_cache
    return model


@pytest.mark.parametrize("fault, code", [(_host_backed, "PT-DON-101"),
                                         (_one_buffer_twice, "PT-DON-104")])
def test_an_arena_that_cannot_be_donated_is_refused(fault, code):
    with pytest.raises(EnforceError, match=code):
        decoder(fault(dense()))


def test_the_check_is_static_verifys_to_skip():
    was = FLAGS.get("static_verify")
    FLAGS.set("static_verify", False)
    try:
        decoder(_host_backed(dense()))
    finally:
        FLAGS.set("static_verify", was)


def test_an_injected_handoff_is_laundered(monkeypatch):
    import paddle_tpu.serving as S

    prompt = np.arange(1, 70, dtype=np.int32)  # two pages of 64
    plain = decoder(**PAGED)
    rid = plain.submit(prompt, 6)
    want = plain.run()[rid].tolist()

    handoff = decoder(**PAGED).prefill_export(prompt)
    laundered = []
    owned = S.owned_on_device
    monkeypatch.setattr(S, "owned_on_device",
                        lambda a: laundered.append(owned(a)) or laundered[-1])
    dec = decoder(**PAGED)
    with donation.track_host_transfers():
        rid = dec.inject_prefilled(handoff, 6)
        got = dec.run()[rid].tolist()
    assert got == want
    # K and V of every block went through owned_on_device, none is left
    # host-backed, and the pools they were written into still pass
    assert len(laundered) == 2 * len(dec.pools)
    assert {donation.classify_provenance(a) for a in laundered} == {"owned"}
    assert not donation.check_donation((dec.pools,), (0,))


# --------------------------------------------------------------------------
# a program that fails after it consumed the arena
# --------------------------------------------------------------------------

def _fail_after_consuming(dec):
    def step(mstate, arena, *rest):
        for leaf in jax.tree_util.tree_leaves(arena):
            leaf.delete()
        raise RuntimeError("device fault")

    dec._step_fns[1] = step


def test_a_consumed_arena_marks_the_decoder_lost():
    dec = decoder()
    dec.submit(np.arange(1, 6, dtype=np.int32), 6)
    _fail_after_consuming(dec)
    with pytest.raises(RuntimeError, match="device fault"):
        dec.run()
    assert dec.arena_lost and not dec.ready
    with pytest.raises(ArenaLostError):
        dec._tick()
    with pytest.raises(ArenaLostError):
        dec.warm_step()
    replica = LocalReplica(dec)
    for probe in (replica.healthz, replica.load):
        with pytest.raises(ArenaLostError):
            probe()


def test_a_failure_that_left_the_arena_whole_loses_nothing():
    dec = decoder()
    rid = dec.submit(np.arange(1, 6, dtype=np.int32), 6)

    def refuse(*args):
        raise RuntimeError("refused before it ran")

    real = dec._build_multi_step(1)
    dec._step_fns[1] = refuse
    with pytest.raises(RuntimeError, match="refused"):
        dec.run()
    assert not dec.arena_lost
    dec._step_fns[1] = real
    assert len(dec.run()[rid]) == 6
    assert LocalReplica(dec).healthz()["status"] == "ok"
