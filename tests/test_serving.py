"""Continuous-batching LM serving (serving.py): slot arena, per-slot
cursors, host-side admission/refill, request-level generate semantics.
Green-field vs the reference's one-request predictor
(paddle/fluid/inference/api/api_impl.cc role)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import gpt as G
from paddle_tpu.serving import BatchedDecoder


def _model(seed=0):
    pt.seed(seed)
    return G.GPTForCausalLM(G.GPTConfig.tiny()).eval()


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


def test_single_request_matches_generate():
    """One request through the slot machinery == model.generate greedy
    (prefill is chunked here vs stepped there; tiny fp divergence can
    flip a near-tie on an untrained model, so require near-total
    agreement rather than byte equality)."""
    m = _model()
    prompt = _prompt(6, 1)
    dec = BatchedDecoder(m, slots=2, capacity=64)
    rid = dec.submit(prompt, max_new=20)
    out = dec.run()[rid]
    assert out.shape == (20,)
    want = np.asarray(m.generate(jnp.asarray(prompt)[None], 26,
                                 temperature=0.0))[0, 6:]
    agree = (out == want).mean()
    assert agree >= 0.9, (agree, out, want)


def test_more_requests_than_slots_all_complete():
    """5 requests of different lengths over 2 slots: every request
    completes with its own max_new, and each result matches a solo run
    of the same request."""
    m = _model(1)
    dec = BatchedDecoder(m, slots=2, capacity=64)
    reqs = {}
    for i, (plen, mnew) in enumerate([(4, 8), (7, 14), (3, 5),
                                      (9, 10), (5, 12)]):
        reqs[dec.submit(_prompt(plen, 10 + i), mnew)] = (plen, mnew,
                                                         10 + i)
    outs = dec.run()
    assert sorted(outs) == sorted(reqs)
    for rid, (plen, mnew, seed) in reqs.items():
        assert outs[rid].shape == (mnew,)
        solo = BatchedDecoder(m, slots=1, capacity=64)
        srid = solo.submit(_prompt(plen, seed), mnew)
        np.testing.assert_array_equal(solo.run()[srid], outs[rid])


@pytest.mark.parametrize("plens, want_active", [
    # two buckets of 16 fit the tick's budget of 32; the third waits
    ((5, 9, 12), (2, 3, 3)),
    # a prompt longer than the budget is prefilled alone, never starved
    ((40, 5, 5), (1, 3, 3)),
    # what does not fit keeps its place in the queue: the short prompt
    # behind the long one waits with it
    ((5, 40, 5), (1, 2, 3)),
])
def test_a_tick_prefills_two_buckets_or_one_prompt(plens, want_active):
    """Every decoding row waits out every prefill of its tick, so
    ``_admit`` prefills at most ``prefill_budget`` padded prompt tokens
    a tick (two buckets), or one prompt however long; the rest stay
    queued in order for the next tick. Tokens are what a solo run
    gives: the budget moves when a request starts, nothing else."""
    m = _model(2)
    dec = BatchedDecoder(m, slots=4, capacity=64)
    assert dec.prefill_budget == 2 * dec.bucket == 32
    rids = [dec.submit(_prompt(n, 30 + i), 12)
            for i, n in enumerate(plens)]
    seen = []
    for _ in want_active:
        dec._tick()
        seen.append(int(dec.active.sum()))
    assert tuple(seen) == want_active
    outs = dec.run()
    for i, (rid, n) in enumerate(zip(rids, plens)):
        solo = BatchedDecoder(m, slots=1, capacity=64)
        srid = solo.submit(_prompt(n, 30 + i), 12)
        np.testing.assert_array_equal(solo.run()[srid], outs[rid])


def test_eos_ends_request_early():
    m = _model(2)
    prompt = _prompt(5, 20)
    free = BatchedDecoder(m, slots=1, capacity=64)
    rid = free.submit(prompt, max_new=30)
    tokens = free.run()[rid]
    eos = int(tokens[7])
    dec = BatchedDecoder(m, slots=1, capacity=64, eos_id=eos)
    rid = dec.submit(prompt, max_new=30)
    out = dec.run()[rid]
    assert len(out) <= 30
    assert out[-1] == eos or len(out) == 30
    first = int(np.argmax(out == eos)) if (out == eos).any() else None
    if first is not None:
        assert first == len(out) - 1  # nothing emitted past eos


def test_sampling_mode_runs_and_is_deterministic():
    m = _model(3)
    a = BatchedDecoder(m, slots=2, capacity=64, key=jax.random.key(5),
                       temperature=1.0, top_k=40)
    b = BatchedDecoder(m, slots=2, capacity=64, key=jax.random.key(5),
                       temperature=1.0, top_k=40)
    for dec in (a, b):
        dec.submit(_prompt(4, 30), 10)
        dec.submit(_prompt(6, 31), 10)
    oa, ob = a.run(), b.run()
    for rid in oa:
        np.testing.assert_array_equal(oa[rid], ob[rid])


def test_weight_only_composes():
    from paddle_tpu import quant

    m = _model(4)
    quant.apply_weight_only_int8(m)
    dec = BatchedDecoder(m, slots=2, capacity=64)
    rid = dec.submit(_prompt(4, 40), 8)
    out = dec.run()[rid]
    assert out.shape == (8,)


def test_typed_errors():
    m = _model(5)
    dec = BatchedDecoder(m, slots=1, capacity=32)
    with pytest.raises(Exception, match="capacity"):
        dec.submit(_prompt(20, 50), 20)
    with pytest.raises(Exception, match="max_new"):
        dec.submit(_prompt(4, 51), 0)
    with pytest.raises(Exception, match="PRNG key"):
        BatchedDecoder(m, slots=1, capacity=32, temperature=1.0)


class TestPagedMode:
    """BatchedDecoder(pages=N): paged-KV serving — outputs identical to
    contiguous mode, memory bounded by allocated pages, admission
    backpressure on pool exhaustion."""

    def test_outputs_match_contiguous_mode(self):
        m = _model(20)
        prompts = [_prompt(n, 60 + i)
                   for i, n in enumerate((4, 9, 5, 7, 3))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=128, **kw)
            rids = [dec.submit(p, 12) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(pages=12, page_size=64)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_quantized_kv_serves_and_logit_parity(self):
        """kv_dtype="int8" end to end, with the PARITY GATE in logit
        form: teacher-forced decode (identical token stream into the
        fp32 and int8 page pools) keeps every step's logits within a
        few % of the logit spread. Token-level agreement is NOT the
        gate — on an untrained model near-tie argmax flips compound
        into full divergence from one flip (seed-dependent), while the
        logit bound is the deterministic consequence of the int8
        round-trip; the gpt_serve bench still reports the token
        agreement alongside."""
        from paddle_tpu.ops.paged_kv import QuantizedPool
        from paddle_tpu.serving import PagedKVPool

        m = _model(24)
        # e2e: the quantized arena completes real requests
        prompts = [_prompt(n, 80 + i)
                   for i, n in enumerate((5, 23, 40))]
        dec = BatchedDecoder(m, slots=2, capacity=128, pages=8,
                             page_size=64, kv_dtype="int8")
        rids = [dec.submit(p, 12) for p in prompts]
        outs = dec.run()
        assert isinstance(dec.pools[0][0], QuantizedPool)
        assert sorted(outs) == sorted(rids)
        assert all(outs[r].shape == (12,) for r in rids)

        # logit parity: same prompt prefilled, then 8 teacher-forced
        # steps; compare per-step logits fp32 vs int8 pools
        attn0 = m.blocks[0].self_attn

        def mint(kvd):
            al = PagedKVPool(2, 64, attn0.num_kv_heads, attn0.head_dim,
                             arrays=False, kv_dtype=kvd)
            table = jnp.asarray(al.alloc(2))[None]     # (1, 2)
            return [(al.empty_pool(), al.empty_pool())
                    for _ in m.blocks], table

        chunk_f = jax.jit(m._chunk_logits_paged)
        step_f = jax.jit(m._step_logits_paged)
        pf, tf = mint(None)
        pq, tq = mint("int8")
        prompt = jnp.asarray(_prompt(37, 83))[None]
        lf, pf = chunk_f(prompt, pf, tf[0], 0)
        lq, pq = chunk_f(prompt, pq, tq[0], 0)
        spread = float(np.ptp(np.asarray(lf)))
        tok = jnp.argmax(lf[:, -1], -1).astype(jnp.int32)
        assert np.abs(np.asarray(lq - lf)).max() < 0.05 * spread
        for i in range(6):
            t = jnp.asarray([37 + i], jnp.int32)
            lf, pf = step_f(tok, pf, tf, t)
            lq, pq = step_f(tok, pq, tq, t)
            assert np.abs(np.asarray(lq - lf)).max() < 0.05 * spread, i
            tok = jnp.argmax(lf, -1).astype(jnp.int32)  # teacher-forced

        # density arithmetic: the int8 pool holds >= 3.5x less HBM at
        # the same page count (what buys the extra sessions)
        fp = BatchedDecoder(m, slots=2, capacity=128, pages=8,
                            page_size=64)
        ratio = (fp._allocator.pool_nbytes
                 / dec._allocator.pool_nbytes)
        assert ratio >= 3.5, ratio
        st = dec._statusz()
        assert st["kv_dtype"] == "int8" and st["kv_pool_bytes"] > 0

    def test_quantized_kv_requires_paged_mode(self):
        with pytest.raises(Exception, match="paged mode"):
            BatchedDecoder(_model(25), slots=2, capacity=64,
                           kv_dtype="int8")

    def test_backpressure_on_page_exhaustion(self):
        """A pool too small for two concurrent requests serializes
        them (queued until completions free pages) — all complete."""
        m = _model(21)
        # each request needs ceil((6+20)/64) = 1 page; a 1-page pool
        # forces strict serialization across the 3 requests
        dec = BatchedDecoder(m, slots=3, capacity=128, pages=1,
                             page_size=64)
        rids = [dec.submit(_prompt(6, 70 + i), 20) for i in range(3)]
        outs = dec.run()
        assert sorted(outs) == sorted(rids)
        # CONTENT must match solo runs — idle slots sharing the step
        # with the active one must not corrupt its pages (the page-0
        # scatter hazard: idle cursors park past capacity)
        for i, r in enumerate(rids):
            solo = BatchedDecoder(m, slots=1, capacity=128, pages=1,
                                  page_size=64)
            srid = solo.submit(_prompt(6, 70 + i), 20)
            np.testing.assert_array_equal(solo.run()[srid], outs[r])
        assert dec._allocator.free_pages == 1  # everything returned
        # a request larger than the WHOLE pool is a typed error, not a
        # silent run() hang
        with pytest.raises(Exception, match="pool only has"):
            dec.submit(_prompt(6, 99), 120)

    def test_freed_pages_are_reused_without_corruption(self):
        """Requests streaming through a small pool reuse pages; each
        result still matches a solo run of the same request."""
        m = _model(22)
        dec = BatchedDecoder(m, slots=2, capacity=64, pages=3,
                             page_size=64)
        reqs = {dec.submit(_prompt(5, 80 + i), 10): 80 + i
                for i in range(5)}
        outs = dec.run()
        for rid, seed in reqs.items():
            solo = BatchedDecoder(m, slots=1, capacity=64, pages=1,
                                  page_size=64)
            srid = solo.submit(_prompt(5, seed), 10)
            np.testing.assert_array_equal(solo.run()[srid], outs[rid])


class TestPrefixCache:
    """Prefix caching (paged mode, opt-in): shared system prompts
    reuse their page-aligned KV pages; only suffixes prefill."""

    def test_shared_prefix_reuses_pages_and_matches_cold(self):
        m = _model(30)
        sys_prompt = _prompt(64, 90)            # exactly one page
        mk = lambda tail_seed, n: np.concatenate(
            [sys_prompt, _prompt(n, tail_seed)])

        def run(prefix_cache):
            dec = BatchedDecoder(m, slots=1, capacity=128, pages=6,
                                 page_size=64,
                                 prefix_cache=prefix_cache)
            rids = [dec.submit(mk(91 + i, 4 + i), 8) for i in range(3)]
            outs = dec.run()
            return dec, [outs[r] for r in rids]

        cold_dec, cold = run(prefix_cache=False)
        hot_dec, hot = run(prefix_cache=True)
        assert hot_dec.prefix_hits == 2         # requests 2 and 3 hit
        for h, c in zip(hot, cold):
            agree = (h == c).mean()
            assert agree >= 0.9, (agree, h, c)  # fp near-ties only
        # the registry retains the prefix page (refcounted), live
        # requests released theirs
        assert hot_dec._allocator.free_pages == 6 - 1

    def test_fully_cached_prompt_and_eviction(self):
        m = _model(31)
        p64 = _prompt(64, 95)                   # page-aligned prompt
        dec = BatchedDecoder(m, slots=1, capacity=128, pages=3,
                             page_size=64, prefix_cache=True)
        a = dec.submit(p64, 8)
        outs = dec.run()
        assert outs[a].shape == (8,)
        # identical prompt again: fully-cached prefix (suffix empty)
        b = dec.submit(p64, 8)
        outs2 = dec.run()
        assert dec.prefix_hits == 1
        agree = (outs2[b] == outs[a]).mean()
        assert agree >= 0.9, (outs2[b], outs[a])
        # fill the pool with fresh prompts: the registry entry is
        # EVICTED to satisfy admission instead of deadlocking
        c = dec.submit(_prompt(80, 96), 40)     # needs 2 pages
        d = dec.submit(_prompt(80, 97), 40)
        outs3 = dec.run()
        assert outs3[c].shape == (40,) and outs3[d].shape == (40,)

    def test_refcount_share_and_double_free_guards(self):
        from paddle_tpu.serving import PagedKVPool

        pool = PagedKVPool(pages=2, page_size=64, kv_heads=2,
                           head_dim=64)
        a = pool.alloc(1)
        pool.share(a)
        pool.free(a)                            # ref 2 -> 1: still live
        assert pool.free_pages == 1
        pool.free(a)                            # ref 1 -> 0: returns
        assert pool.free_pages == 2
        with pytest.raises(Exception, match="double free"):
            pool.free(a)
        with pytest.raises(Exception, match="unallocated"):
            pool.share(a)

    def test_evicting_the_hit_does_not_corrupt(self):
        """The reviewer repro: the hit's registry entry is evicted to
        satisfy the same admission — the pinned shared pages must NOT
        be handed back as 'new' pages (duplicate physical page in one
        table). Output must match a cold run."""
        m = _model(32)
        P = _prompt(64, 98)
        tail = _prompt(4, 99)
        full = np.concatenate([P, tail])

        cold = BatchedDecoder(m, slots=2, capacity=128, pages=3,
                              page_size=64)
        crid = cold.submit(full, 8)
        cold_out = cold.run()[crid]

        dec = BatchedDecoder(m, slots=2, capacity=128, pages=3,
                             page_size=64, prefix_cache=True)
        r0 = dec.submit(P, 8)                   # registers page for P
        dec.run()
        a = dec.submit(_prompt(70, 100), 40)    # needs 2 pages
        b = dec.submit(full, 8)                 # hits P while the pool
        outs = dec.run()                        # is dry
        # the PIN makes the dangerous path impossible: eviction cannot
        # free the hit's pages (our reference holds them), so b
        # backpressures instead of receiving its own prefix page back
        # as a "new" page; it admits cold after `a` completes (the
        # registry entry was evicted meanwhile — hits may be 0)
        assert dec.prefix_hits <= 1
        assert outs[a].shape == (40,)
        agree = (outs[b] == cold_out).mean()
        assert agree >= 0.9, (agree, outs[b], cold_out)
        assert dec._allocator.free_pages + len(
            dec._prefix_registry) >= 3 - 1      # nothing leaked


class TestChunkedPrefill:
    """BatchedDecoder(prefill_chunk=C): admission only allocates; the
    prompt prefills C tokens per serving-loop tick so active slots keep
    their decode cadence (Sarathi-style throughput smoothing).
    Token-identical to monolithic prefill in both cache modes."""

    def test_matches_monolithic_contiguous(self):
        m = _model(40)
        prompts = [_prompt(n, 110 + i)
                   for i, n in enumerate((30, 5, 21, 9))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=64, **kw)
            rids = [dec.submit(p, 10) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(prefill_chunk=16)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_matches_monolithic_paged(self):
        m = _model(41)
        prompts = [_prompt(n, 120 + i)
                   for i, n in enumerate((40, 6, 17))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=128, pages=8,
                                 page_size=64, **kw)
            rids = [dec.submit(p, 12) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(prefill_chunk=32)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_decode_keeps_moving_while_long_prompt_prefills(self):
        """Admit a short request, then a LONG one: the short slot must
        emit tokens BETWEEN the long prompt's chunk ticks (the feature
        this mode exists for), and both results must match solo runs."""
        m = _model(42)
        short, long_p = _prompt(4, 130), _prompt(48, 131)
        dec = BatchedDecoder(m, slots=2, capacity=64, prefill_chunk=16)
        r_short = dec.submit(short, 12)
        dec._admit()                       # short slot admits + chunks
        while dec._pf_order:               # drain short's own chunks
            dec._prefill_tick()
        r_long = dec.submit(long_p, 6)
        dec._admit()                       # long slot allocates only
        assert dec._pf_order               # still prefilling...
        s_short = next(s for s in range(2)
                       if dec.owner[s] is not None and dec.active[s])
        before = len(dec.emitted[s_short])
        dec._step()                        # short slot's step dispatched
        dec._prefill_tick()                # one chunk of the long prompt
        dec._step()                        # short slot decodes meanwhile:
        # the step that ran beside the chunk is read, the next is off
        assert dec._pf_order               # long STILL prefilling
        assert len(dec.emitted[s_short]) == before + 1  # ...but short
        # emitted a token between the long prompt's chunk ticks
        outs = dec.run()
        for rid, (p, mn) in ((r_short, (short, 12)),
                             (r_long, (long_p, 6))):
            solo = BatchedDecoder(m, slots=1, capacity=64)
            srid = solo.submit(p, mn)
            np.testing.assert_array_equal(solo.run()[srid], outs[rid])

    def test_composes_with_prefix_cache(self):
        """Chunked suffix prefill from a page-aligned cached frontier
        matches the cold result."""
        m = _model(43)
        sys_p = _prompt(64, 140)
        full = np.concatenate([sys_p, _prompt(9, 141)])
        cold = BatchedDecoder(m, slots=1, capacity=128, pages=6,
                              page_size=64)
        cout = cold.submit(full, 8)
        cold_out = cold.run()[cout]
        dec = BatchedDecoder(m, slots=1, capacity=128, pages=6,
                             page_size=64, prefix_cache=True,
                             prefill_chunk=32)
        dec.submit(sys_p, 4)
        dec.run()                          # registers the prefix page
        rid = dec.submit(full, 8)
        out = dec.run()[rid]
        assert dec.prefix_hits == 1
        agree = (out == cold_out).mean()
        assert agree >= 0.9, (agree, out, cold_out)

    def test_final_chunk_slide_at_capacity(self):
        """capacity NOT a multiple of the chunk: the final chunk must
        slide back (t0 = capacity - C) instead of clamp-corrupting K/V
        below the frontier — the overlap re-writes the same real
        tokens idempotently, so the result matches monolithic
        prefill. (Contiguous-only: paged capacities are page-multiples
        and the page demand bounds the grid, so the slide can't
        trigger there.)"""
        m = _model(45)
        prompt = _prompt(50, 145)      # grid pads to 64 > capacity 56

        def run(**kw):
            dec = BatchedDecoder(m, slots=1, capacity=56, **kw)
            rid = dec.submit(prompt, 4)
            return dec.run()[rid]

        np.testing.assert_array_equal(run(prefill_chunk=16), run())

    def test_typed_errors(self):
        m = _model(44)
        with pytest.raises(Exception, match="divide page_size"):
            BatchedDecoder(m, slots=1, capacity=128, pages=4,
                           page_size=64, prefill_chunk=48)
        with pytest.raises(Exception, match="capacity"):
            BatchedDecoder(m, slots=1, capacity=32, prefill_chunk=64)


class TestSpeculativeArena:
    """BatchedDecoder(draft=..., gamma=g): speculative decoding over
    the continuous-batching arena — per-row draft steps + ONE per-row
    verify chunk per round. Greedy output matches the plain arena
    (token-identical up to near-tie argmax flips between differently
    fused programs — the documented speculative soft spot)."""

    def _pair(self, seed=50):
        m = _model(seed)
        pt.seed(seed + 1)
        dcfg = G.GPTConfig(vocab_size=512, hidden_size=64,
                           num_layers=1, num_heads=2, num_kv_heads=2,
                           intermediate_size=128, max_position=128)
        d = G.GPTForCausalLM(dcfg).eval()
        return m, d

    def _agree(self, got, want, thresh=0.9):
        n = min(len(got), len(want))
        agree = (got[:n] == want[:n]).mean()
        assert agree >= thresh, (agree, got, want)

    def test_greedy_matches_plain_arena_contiguous(self):
        m, d = self._pair(50)
        prompts = [_prompt(n, 150 + i)
                   for i, n in enumerate((6, 11, 4))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=64, **kw)
            rids = [dec.submit(p, 12) for p in prompts]
            outs = dec.run()
            return dec, [outs[r] for r in rids]

        _, want = run()
        dec, got = run(draft=d, gamma=3)
        assert dec.spec_rounds > 0
        for g, w in zip(got, want):
            assert g.shape == w.shape
            self._agree(g, w)

    def test_greedy_paged_matches_contiguous_spec(self):
        m, d = self._pair(51)
        prompts = [_prompt(n, 160 + i) for i, n in enumerate((5, 9))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=128,
                                 draft=d, gamma=4, **kw)
            rids = [dec.submit(p, 10) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(pages=8, page_size=64)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            self._agree(g, w)

    def test_self_draft_accepts_nearly_everything(self):
        """Draft == target: greedy drafts should nearly always match
        the target's argmax (flips only at fused-vs-chunked near-ties),
        so accepted/round approaches gamma."""
        m, _ = self._pair(52)
        dec = BatchedDecoder(m, slots=2, capacity=64, draft=m, gamma=3)
        for i in range(3):
            dec.submit(_prompt(5 + i, 170 + i), 15)
        dec.run()
        rate = dec.spec_accepted / max(1, dec.spec_row_rounds * 3)
        assert rate > 0.7, (dec.spec_accepted, dec.spec_row_rounds)

    def test_eos_and_budget_respected(self):
        m, d = self._pair(53)
        prompt = _prompt(5, 180)
        free = BatchedDecoder(m, slots=1, capacity=64)
        rid = free.submit(prompt, 24)
        tokens = free.run()[rid]
        eos = int(tokens[9])
        dec = BatchedDecoder(m, slots=1, capacity=64, draft=d,
                             gamma=4, eos_id=eos)
        rid = dec.submit(prompt, 24)
        out = dec.run()[rid]
        assert len(out) <= 24
        hits = np.flatnonzero(out == eos)
        if len(hits):
            assert hits[0] == len(out) - 1  # nothing emitted past eos

    def test_sampled_runs_and_is_deterministic(self):
        m, d = self._pair(54)
        prompts = [_prompt(4, 190), _prompt(7, 191)]

        def run():
            dec = BatchedDecoder(m, slots=2, capacity=64, draft=d,
                                 gamma=3, temperature=0.8, top_k=40,
                                 key=jax.random.key(9))
            rids = [dec.submit(p, 10) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        a, b = run(), run()
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
            assert ((0 <= x) & (x < 512)).all()

    def test_composes_with_chunked_prefill(self):
        m, d = self._pair(55)
        prompts = [_prompt(34, 195), _prompt(6, 196)]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=128, **kw)
            rids = [dec.submit(p, 8) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(draft=d, gamma=3, prefill_chunk=16)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            self._agree(g, w)

    def test_typed_errors(self):
        m, d = self._pair(56)
        pt.seed(99)
        bad = G.GPTForCausalLM(
            G.GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                        num_heads=2, intermediate_size=128)).eval()
        with pytest.raises(Exception, match="vocab"):
            BatchedDecoder(m, slots=1, capacity=64, draft=bad)
        dec = BatchedDecoder(m, slots=1, capacity=32, draft=d, gamma=4)
        with pytest.raises(Exception, match="margin"):
            dec.submit(_prompt(8, 197), 21)    # 8 + 21 + 4 > 32


class TestMultiStepDecode:
    """BatchedDecoder(decode_steps=k): one dispatch advances every slot
    k tokens with IN-DEVICE picks — token-identical to k=1 (the same
    fold_in key chain), with per-token budget/eos finishing host-side.
    The steps-per-call lever applied to serving (RTT-bound links)."""

    def test_greedy_matches_k1_both_cache_modes(self):
        m = _model(60)
        prompts = [_prompt(n, 200 + i) for i, n in enumerate((5, 9, 4))]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=64, **kw)
            rids = [dec.submit(p, 12) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        for base in ({}, {"pages": 8, "page_size": 64}):
            want = run(**base)
            got = run(decode_steps=4, **base)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)

    def test_sampled_matches_k1(self):
        m = _model(61)

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=64,
                                 temperature=0.8, top_k=40,
                                 key=jax.random.key(7), **kw)
            rids = [dec.submit(_prompt(5, 210), 10),
                    dec.submit(_prompt(8, 211), 10)]
            outs = dec.run()
            return [outs[r] for r in rids]

        for x, y in zip(run(), run(decode_steps=5)):
            np.testing.assert_array_equal(x, y)

    def test_eos_and_budget_respected_mid_window(self):
        """Budgets NOT divisible by k and an eos landing mid-window:
        nothing emits past either; results match k=1 exactly."""
        m = _model(62)
        prompt = _prompt(5, 220)
        free = BatchedDecoder(m, slots=1, capacity=64)
        rid = free.submit(prompt, 20)
        eos = int(free.run()[rid][6])       # fires mid-window for k=4

        def run(**kw):
            dec = BatchedDecoder(m, slots=1, capacity=64, eos_id=eos,
                                 **kw)
            r1 = dec.submit(prompt, 21)     # 21 % 4 != 0
            r2 = dec.submit(_prompt(4, 221), 3)  # budget < k
            outs = dec.run()
            return [outs[r1], outs[r2]]

        want, got = run(), run(decode_steps=4)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        hits = np.flatnonzero(got[0] == eos)
        if len(hits):
            assert hits[0] == len(got[0]) - 1

    def test_composes_with_chunked_prefill(self):
        m = _model(63)
        prompts = [_prompt(34, 230), _prompt(6, 231)]

        def run(**kw):
            dec = BatchedDecoder(m, slots=2, capacity=128, pages=8,
                                 page_size=64, **kw)
            rids = [dec.submit(p, 9) for p in prompts]
            outs = dec.run()
            return [outs[r] for r in rids]

        want = run()
        got = run(decode_steps=3, prefill_chunk=32)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_typed_errors(self):
        m = _model(64)
        d = _model(65)
        with pytest.raises(Exception, match="decode_steps"):
            BatchedDecoder(m, slots=1, capacity=64, draft=d,
                           decode_steps=4)
        with pytest.raises(Exception, match="decode_steps"):
            BatchedDecoder(m, slots=1, capacity=64, decode_steps=0)
        # the k-1 overrun margin is budgeted at admission
        dec = BatchedDecoder(m, slots=1, capacity=32, decode_steps=8)
        with pytest.raises(Exception, match="margin"):
            dec.submit(_prompt(8, 240), 18)   # 8 + 18 + 7 > 32
