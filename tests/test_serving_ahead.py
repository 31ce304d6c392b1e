"""The contiguous arena's decode step runs one ahead of the host
(``BatchedDecoder._step_multi``): step N+1 is dispatched on the cursor
step N left on the device before N's tokens are fetched. What that
order must not change, and what it does to the edges: every request's
tokens are the synchronous body's, a budget's end costs no surplus
step, an ``eos`` costs exactly one dropped row, a freed slot starts its
next request clean, a request torn down with tokens in flight gets
none of them, nothing stays in flight when the arena runs empty, and a
paged or speculative arena keeps the synchronous body.

The synchronous body is in the tree (``_step_sync``: the paged and the
speculative arena run it) and serves a contiguous arena as well, so
the comparisons below run both bodies on the same model."""

import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving as S
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.resilience import reliability
from paddle_tpu.serving import ArenaLostError, BatchedDecoder, TokenStream
from paddle_tpu.serving_router import LocalReplica

CAPACITY, BUCKET = 64, 8


def dense(seed=0):
    pt.seed(seed)
    return GPTForCausalLM(GPTConfig.tiny()).eval()


def hybrid():
    from test_hybrid import build

    return build()[1]


MODELS = {"gpt": dense, "hybrid": hybrid}


def decoder(model=None, sync=False, slots=2, **kw):
    dec = BatchedDecoder(model or dense(), slots=slots, capacity=CAPACITY,
                         prompt_bucket=BUCKET, **kw)
    if sync:
        dec._step_multi = dec._step_sync
    return dec


def prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 250, (n,)).astype(np.int32)


MIX = [(4, 8), (7, 14), (3, 5), (9, 10), (5, 12), (11, 2), (6, 1)]


def serve(dec, mix=MIX):
    rids = [dec.submit(prompt(n, 10 + i), new)
            for i, (n, new) in enumerate(mix)]
    out = dec.run()
    return [out[r].tolist() for r in rids]


def count_steps(dec):
    """The decode-step programs of ``dec`` counted as they are called."""
    calls = []

    def counting(kd):
        fn = dec._build_multi_step(kd)

        def step(*args):
            calls.append(kd)
            return fn(*args)
        return step

    for kd in {1, dec.decode_steps}:
        dec._step_fns[kd] = counting(kd)
    return calls


# --------------------------------------------------------------------------
# tokens
# --------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_tokens_are_the_synchronous_bodys(sampled, k):
    model = dense()
    kw = dict(decode_steps=k)
    if sampled:
        kw.update(temperature=0.8, top_k=20, key=jax.random.key(3))
    ahead = decoder(model, **kw)
    got = serve(ahead)
    assert got == serve(decoder(model, sync=True, **kw))
    assert [len(g) for g in got] == [new for _, new in MIX]
    c = ahead.counters
    assert c.rows_dropped == 0 and ahead._ahead is None
    # all but the steps that follow an empty arena or a tick with no
    # row live ran ahead of the one before
    assert 0 < c.steps_ahead < c.steps == ahead.tick_count


@pytest.mark.parametrize("k, new, steps", [(1, 6, 5), (1, 2, 1), (3, 6, 2),
                                           (3, 8, 3), (3, 1, 0)])
def test_a_budgets_end_dispatches_no_surplus_step(k, new, steps):
    dec = decoder(decode_steps=k)
    calls = count_steps(dec)
    rid = dec.submit(prompt(5, 1), new)
    assert len(dec.run()[rid]) == new
    assert len(calls) == steps == dec.counters.steps
    assert dec.counters.rows_dropped == 0
    assert dec.counters.steps_ahead == max(steps - 1, 0)


@pytest.mark.parametrize("k", [1, 3])
def test_an_eos_drops_one_row_and_streams_nothing_after_it(k):
    model = dense(1)
    free = serve(decoder(model, decode_steps=k), [(6, 20)])[0]
    # a token whose first occurrence lies mid-stream, past the first step
    at = next(i for i in range(k + 1, len(free) - k - 1)
              if free[i] not in free[:i])
    dec = decoder(model, decode_steps=k, eos_id=free[at])
    calls = count_steps(dec)
    stream = TokenStream()
    rid = dec.submit(prompt(6, 10), 20, stream=stream)
    got = dec.run()[rid].tolist()
    assert got == free[:at + 1]
    streamed = [rec["tok"] for rec in stream if "tok" in rec]
    assert streamed == got
    # the step dispatched before the eos was read: one row, dropped
    assert dec.counters.rows_dropped == 1 and dec._ahead is None
    assert len(calls) == dec.counters.steps == -(-at // k) + 1


# --------------------------------------------------------------------------
# slots
# --------------------------------------------------------------------------

TURNOVER = [(5, 30), (9, 6), (4, 7), (12, 5), (6, 4)]
# sampled: the tiny hybrid's greedy tokens are all one token, whatever
# its state; a draw follows the logits
DRAWN = dict(temperature=1.0, key=jax.random.key(7))


@pytest.mark.parametrize("kind", ["gpt", "hybrid"])
def test_a_freed_slot_starts_its_next_request_clean(kind):
    """Two slots, one long request and a queue of short ones: each
    short request's slot is stepped once more after its last token, as
    a row that is not live (the step in flight when it ended), before
    the next prefill takes it. Keys, values and recurrent state of the
    new request are its prefill's alone: the tokens are those of the
    synchronous body, where no step falls between end and prefill."""
    model = MODELS[kind]()
    dec = decoder(model, **DRAWN)
    got = serve(dec, TURNOVER)
    assert got == serve(decoder(model, sync=True, **DRAWN), TURNOVER)
    assert len({t for g in got for t in g}) > 8    # the draws do differ
    assert dec.counters.rows_dropped == 0


@pytest.mark.parametrize("kind", ["gpt", "hybrid"])
def test_a_slot_freed_by_eos_is_stepped_once_more_and_reused(kind):
    """With an ``eos`` the surplus step runs the dead row as a LIVE one
    (cursor advanced, state advanced) before the next prefill."""
    model = MODELS[kind]()
    free = serve(decoder(model, sync=True, **DRAWN), TURNOVER)
    eos = free[1][2]
    dec = decoder(model, eos_id=eos, **DRAWN)
    got = serve(dec, TURNOVER)
    assert got == serve(decoder(model, sync=True, eos_id=eos, **DRAWN),
                        TURNOVER)
    assert got[1] == free[1][:3] and dec.counters.rows_dropped >= 1
    assert all(eos not in g[:-1] for g in got)


def test_a_request_torn_down_with_tokens_in_flight_gets_none_of_them():
    model = dense(2)
    want = serve(decoder(model, sync=True), [(5, 10)])[0]
    dec = decoder(model)
    with reliability.bind(reliability.Deadline.after(3600)):
        doomed = dec.submit(prompt(7, 3), 30)
    kept = dec.submit(prompt(5, 10), 10)
    for _ in range(3):
        dec._tick()
    assert dec._ahead is not None and dec._ahead.live.sum() == 2
    victim = next(r for r in dec.owner if r is not None and r.rid == doomed)
    held = len(victim.t_tokens)
    victim.deadline.t_end = time.time() - 1.0
    out = dec.run()
    assert out[doomed] is None and victim.deadline_exceeded
    assert len(victim.t_tokens) == held       # nothing emitted after
    assert out[kept].tolist() == want
    assert dec.counters.rows_dropped == 1 and dec._dl_active == 0


# --------------------------------------------------------------------------
# the arena runs empty, the lever moves, the device fails
# --------------------------------------------------------------------------

def test_run_ends_with_nothing_in_flight():
    dec = decoder()
    serve(dec)
    assert dec._ahead is None and not dec.active.any()
    before = dec.counters.steps
    dec._tick()                               # an idle tick dispatches none
    assert dec._ahead is None and dec.counters.steps == before


def test_a_local_replica_ends_with_nothing_in_flight():
    dec = decoder()
    rep = LocalReplica(dec, name="ahead").start()
    try:
        rids = {rep.submit(prompt(n, i), new)
                for i, (n, new) in enumerate(MIX)}
        got, deadline = {}, time.monotonic() + 120
        while not rids <= set(got) and time.monotonic() < deadline:
            got.update(rep.drain_results())
            time.sleep(0.002)
        assert rids <= set(got)
        with rep._locked("other"):
            assert dec._ahead is None and not dec.active.any()
    finally:
        rep.stop()
    assert [got[r]["n_tokens"] for r in sorted(rids)] == [
        new for _, new in MIX]


def test_warm_step_leaves_nothing_in_flight_and_the_cursor_as_it_was():
    dec = decoder()
    tok, t = np.asarray(dec.tok), np.asarray(dec.t)
    dec.warm_step()
    assert dec.ready and dec._ahead is None
    np.testing.assert_array_equal(np.asarray(dec.tok), tok)
    np.testing.assert_array_equal(np.asarray(dec.t), t)


def test_the_degrade_lever_moved_between_dispatch_and_settle():
    """``kd`` is read at dispatch and kept with the step in flight: the
    step that was dispatched at k=3 is emitted as 3 tokens a row after
    the lever went to k=1, and back."""
    model = dense(3)
    want = serve(decoder(model, sync=True), [(5, 17), (8, 11)])
    dec = decoder(model, decode_steps=3)
    calls = count_steps(dec)
    rids = [dec.submit(prompt(5, 10), 17), dec.submit(prompt(8, 11), 11)]
    dec._tick()
    assert dec._ahead.kd == 3
    dec.set_degraded(True)
    dec._tick()                               # dispatches k=1, settles k=3
    assert dec._ahead.kd == 1
    assert [len(e) for e in dec.emitted] == [4, 4]
    dec._tick()
    dec.set_degraded(False)
    out = dec.run()
    assert [out[r].tolist() for r in rids] == want
    assert calls[:3] == [3, 1, 1] and 3 in calls[3:]
    assert dec.counters.rows_dropped == 0


def test_a_step_that_fails_on_the_device_loses_the_arena_at_its_settle():
    """The dispatch returns; the fault shows when the host reads the
    step, one tick later, inside that tick's guard."""
    dec = decoder()
    real = dec._build_multi_step(1)

    def faulty(*args):
        caches, toks = real(*args)
        for leaf in jax.tree_util.tree_leaves((caches, toks)):
            leaf.delete()
        return caches, toks

    dec._step_fns[1] = faulty
    # the cursor's program would refuse the deleted tokens at dispatch
    real_cursor, S._advance_cursor = (
        S._advance_cursor, lambda tok, t, toks, live: (tok, t))
    try:
        dec.submit(prompt(5, 1), 2)
        dec._tick()                           # dispatched, nothing read
        assert dec._ahead is not None and not dec.arena_lost
        with pytest.raises(RuntimeError, match="deleted"):
            dec._tick()
    finally:
        S._advance_cursor = real_cursor
    assert dec.arena_lost and not dec.ready and dec._ahead is None
    with pytest.raises(ArenaLostError):
        dec._tick()


# --------------------------------------------------------------------------
# the order itself, and who keeps the old one
# --------------------------------------------------------------------------

class _Spans:
    """Stands in for ``serving.Span``: the names, in order of entry."""

    def __init__(self):
        self.names = []
        log = self.names

        class Span:
            def __init__(self, name, *a, **kw):
                self.name = name

            def __enter__(self):
                log.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

        self.Span = Span


@pytest.fixture
def spans(monkeypatch):
    rec = _Spans()
    monkeypatch.setattr(S, "Span", rec.Span)
    return rec.names


def test_step_n_plus_1_is_dispatched_before_step_n_is_fetched(spans):
    dec = decoder()
    serve(dec, [(5, 6)])
    steps = [n.rsplit(".", 1)[1] for n in spans
             if n.startswith("serve.step.")]
    # one request's six tokens: five steps, each fetched after the next
    # one's dispatch, but for the last; the cursor's program follows
    # its step, and nothing of the cursor follows a fetch
    ahead = ["dispatch", "cursor"]
    assert steps == ahead + (ahead + ["fetch", "emit"]) * 4 + [
        "fetch", "emit"]


@pytest.mark.parametrize("kw", [dict(pages=4, page_size=64),
                                dict(draft=True)], ids=["paged", "draft"])
def test_a_paged_or_speculative_arena_keeps_the_synchronous_tick(spans, kw):
    """Their safety rests on the host setting the cursor before the
    next dispatch (a retired row's pages are freed; a round's accepted
    count moves the cursor), so they run ``_step_sync``: dispatch,
    fetch, emit, cursor, and nothing in flight between ticks."""
    if kw.get("draft"):
        kw = dict(draft=dense(5), gamma=2)
    dec = decoder(dense(4), **kw)
    if dec.draft is not None:
        dec.set_degraded(True)                # the plain step, not a round
    serve(dec, [(5, 6)])
    steps = [n for n in spans if n.startswith("serve.step.")]
    assert steps == ["serve.step.dispatch", "serve.step.fetch",
                     "serve.step.emit", "serve.step.cursor"] * 5
    assert dec._ahead is None and dec.counters.steps_ahead == 0
    assert dec.counters.steps == 5


@pytest.mark.parametrize("kw, n_in", [(dict(), 5),
                                      (dict(pages=4, page_size=64), 6)],
                         ids=["contiguous", "paged"])
def test_the_step_programs_arguments_and_outputs_are_the_parents(kw, n_in):
    """``pt_decode_step`` takes (weights, arena, [table,] tok, t, gens)
    and returns (arena, tokens): the convention the AOT artifacts, the
    benchmark's compile rehearsal and its tests call it with. The
    cursor is ``pt_step_cursor``'s, not a further output."""
    dec = decoder(dense(4), **kw)
    fn, args = dec._step_call()
    assert len(args) == n_in
    out = jax.eval_shape(fn, *args)
    assert len(out) == 2 and out[1].shape == (dec.slots, 1)
    assert "@jit_pt_decode_step" in dec.lower_step().as_text()
    cursor = S._advance_cursor.lower(dec.tok, dec.t, out[1], dec.active)
    assert "@jit_pt_step_cursor" in cursor.as_text()
