"""Latent attention under a learned selection (``nn/latent.py`` with an
indexer, ``ops/latent_attention.py``, ``ops/pallas/dsa.py``,
``models/hybrid.py``) against the plain reference
``benchmark/reference/sparse_latent_moe_f32.py``, which imports nothing
of the program and picks by a SORT where the program searches for the
threshold bit by bit: the pick itself; the full forward pass at
contexts under, at and over ``index_topk``; prefill then decode through
``BatchedDecoder``'s own programs over a cache of three arrays; each
Pallas body (interpreted) against its ``jax.numpy`` body; the step's
counters; the scope; the expert shares; the grouped body in parts.

Tolerance of every logits comparison, ``close``: both sides are float32
and differ in the order of sums only: 1e-4 of the logits' standard
deviation, absolute. A wrong pick moves logits by tenths: at these
sizes a query drops 11 of 19 positions."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.reference import sparse_latent_moe_f32 as R
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.nn import moe
from paddle_tpu.nn.latent import LatentAttention
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import latent_attention as LA
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.telemetry import scopes

SLOTS, CAPACITY, BUCKET, PAD, TOPK = 3, 64, 8, 32, 8


def dims_of(cfg: HybridConfig, held=None) -> R.Dims:
    return R.Dims(
        hidden=cfg.hidden_size, layers=len(cfg.layer_types),
        dense_layers=cfg.channel_mixes().count("mlp"),
        heads=cfg.num_heads, q_rank=cfg.q_lora_rank,
        kv_rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
        index_heads=cfg.index_n_heads, index_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk, index_eps=1e-6, ffn=cfg.mlp_width,
        expert_width=cfg.expert_width, shared_width=cfg.shared_width,
        experts=cfg.num_experts, top_k=cfg.experts_per_token,
        held=held or cfg.experts_held or (0, cfg.num_experts),
        scaling=cfg.routed_scaling_factor, vocab=cfg.vocab_size,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps)


def build(held=None, seed=0, topk=TOPK):
    """One dense and two expert blocks whose mixers pick ``topk``
    positions. Norm scales and biases (the index key's LayerNorm among
    them) and the selection bias are drawn, so that no leaf is at a
    value (0 or 1) that would hide its use; the indexer's projections
    are scaled up so that index scores spread."""
    pt.seed(seed)
    cfg = HybridConfig.tiny_sparse_latent(3, topk=topk)
    cfg.experts_held = held
    model = HybridForCausalLM(cfg).eval()
    rng = np.random.default_rng(seed + 1)
    params = dict(model.named_parameters())
    for k, v in params.items():
        if k.endswith(("norm.weight", "norm1.weight", "norm2.weight",
                       "norm_f.weight")):
            params[k] = jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(v.shape), v.dtype)
        elif k.endswith((".bias", "score_bias")):
            params[k] = jnp.asarray(
                0.3 * rng.standard_normal(v.shape), v.dtype)
        elif "index_" in k and v.ndim == 2:
            params[k] = v * 3.0
    model.set_parameters(params)
    return cfg, model, params


def close(got, want, tol=1e-4):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=tol * want.std())


@functools.lru_cache(maxsize=None)
def _reference(dims, select):
    return jax.jit(lambda tokens, params: R.logits(tokens, params, dims,
                                                   select=select))


def reference_logits(params, dims, tokens, select=True):
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(dims, select)(
            jnp.asarray(padded), params))[:len(tokens)]


# --------------------------------------------------------------------------
# (a) the pick
# --------------------------------------------------------------------------

def by_sort(scores, live, k):
    out = np.zeros(scores.shape, bool)
    for b in range(len(scores)):
        order = sorted(np.flatnonzero(live[b]),
                       key=lambda i: (-scores[b, i], i))
        out[b, order[:k]] = True
    return out


PICK_CASES = {
    "spread": lambda rng: rng.standard_normal((6, 40)),
    "ties across the edge": lambda rng: np.round(
        rng.standard_normal((6, 40)), 0),
    "all equal": lambda rng: np.zeros((6, 40)),
    "negative zero is zero": lambda rng: np.where(
        rng.random((6, 40)) < 0.5, -0.0, 0.0),
    "negatives and huge": lambda rng: rng.standard_normal((6, 40)) * 1e30,
    "small": lambda rng: rng.standard_normal((6, 40)) * 1e-30,
}


@pytest.mark.parametrize("case", PICK_CASES)
@pytest.mark.parametrize("k", [1, 8, 39])
def test_the_pick_is_a_stable_sorts_first_k(case, k):
    """The ``k`` live positions of largest score, ties to the lower
    position, every live one where there are no more than ``k``: rows
    with 40, 40, 6, 21, 1 and 9 live positions."""
    rng = np.random.default_rng(len(case) + k)
    scores = PICK_CASES[case](rng).astype(np.float32)
    live = np.arange(40)[None, :] <= np.array([39, 39, 5, 20, 0, 8])[:, None]
    got = np.asarray(LA.pick_mask(jnp.asarray(scores), jnp.asarray(live), k))
    np.testing.assert_array_equal(got, by_sort(scores, live, k))
    assert np.all(got.sum(-1) == np.minimum(live.sum(-1), k))
    # the reference's pick (a sort) says the same
    np.testing.assert_array_equal(
        np.asarray(R.pick(jnp.asarray(scores), jnp.asarray(live), k)[0]),
        got)


# --------------------------------------------------------------------------
# (b) the full forward pass, around index_topk
# --------------------------------------------------------------------------

@pytest.mark.parametrize("length", [19, 8, 5],
                         ids=["over topk", "at topk", "under topk"])
def test_forward_is_the_reference(length):
    cfg, model, params = build()
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size,
                                               (2, length))
    got = model(jnp.asarray(tokens))
    for row in range(2):
        close(got[row], reference_logits(params, dims_of(cfg), tokens[row]))


def test_up_to_topk_positions_the_layer_is_dense_latent_attention():
    """A mixer with an indexer and the same mixer without one agree to
    the bit on the first ``index_topk`` positions and differ after
    them; the model without its selection is another model by tenths of
    a deviation."""
    pt.seed(3)
    sizes = dict(hidden=64, num_heads=4, q_rank=24, kv_rank=32, nope_dim=16,
                 rope_dim=8, v_dim=24)
    sparse = LatentAttention(**sizes, index_heads=4, index_dim=16,
                             index_topk=TOPK)
    dense = LatentAttention(**sizes)
    params = dict(sparse.named_parameters())
    dense.set_parameters({k: v for k, v in params.items()
                          if "index_" not in k})
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 20, 64)),
                    jnp.float32)
    a, b = np.asarray(sparse(x)), np.asarray(dense(x))
    np.testing.assert_allclose(a[:, :TOPK], b[:, :TOPK], rtol=0, atol=1e-6)
    assert np.abs(a[:, TOPK:] - b[:, TOPK:]).max() > 0.1 * b.std()
    cfg, model, params = build()
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 19)
    on = reference_logits(params, dims_of(cfg), tokens)
    off = reference_logits(params, dims_of(cfg), tokens, select=False)
    close(on[:TOPK], off[:TOPK])
    assert np.abs(on[TOPK:] - off[TOPK:]).max() > 0.1 * on.std()


def test_an_indexer_is_three_sizes_and_a_cache_of_three_arrays():
    cfg, model, _ = build()
    caches = model.init_cache(2, 16)
    assert [tuple(a.shape for a in c) for c in caches] == [
        ((2, 16, 32), (2, 16, 8), (2, 16, 16))] * 3
    assert model.cache_kinds == ["kv"] * 3
    assert model.cache_records == ["latent"] * 3
    assert {k.rsplit(".", 2)[-2] for k in model.named_parameters()
            if "index_" in k} == {"index_q_proj", "index_k_proj",
                                  "index_k_norm", "index_w_proj"}
    with pytest.raises(EnforceError, match="three sizes together"):
        LatentAttention(64, 4, 24, 32, 16, 8, 16, index_heads=4)
    mixer = model.blocks[0].mixer
    x = jnp.zeros((2, 4, 64))
    with pytest.raises(EnforceError, match="takes 3 cache arrays, got 2"):
        mixer.forward_chunk(x, caches[0][:2], 0)
    with pytest.raises(EnforceError, match="static offset 0"):
        mixer.forward_chunk(x, caches[0], 4)


# --------------------------------------------------------------------------
# (c) prefill, then decode, through the arena's own programs
# --------------------------------------------------------------------------

def arena_logits(dec, model, wave, steps):
    """``tests/test_latent.py::arena_logits``: prefill each (slot,
    prompt) with the decoder's own program, then step every slot
    through the model entry its decode step calls (teacher forcing)."""
    out = {s: [] for s, _, _ in wave}
    for s, prompt, _ in wave:
        plen = len(prompt)
        lb = dec._bucket_len(plen)
        padded = np.zeros((lb,), np.int32)
        padded[:plen] = prompt
        dec.caches, logits = dec._prefill_fn(lb)(
            dec._mstate, dec.caches, jnp.asarray(padded), plen, s)
        out[s].append(np.asarray(logits))

    @jax.jit
    def step(mstate, caches, tok, t):
        with inject_state((model, *mstate)):
            return model._step_logits_rows(tok, caches, t)

    tok = np.zeros((dec.slots,), np.int32)
    t = np.zeros((dec.slots,), np.int32)
    for j in range(steps):
        for s, prompt, cont in wave:
            tok[s], t[s] = cont[j], len(prompt) + j
        logits, dec.caches = step(dec._mstate, dec.caches,
                                  jnp.asarray(tok), jnp.asarray(t))
        for s, _, _ in wave:
            out[s].append(np.asarray(logits[s]))
    return out


def test_arena_prefill_and_decode_are_the_reference_and_slots_reuse():
    """Bucket 8 = ``index_topk``: prompts of 5 and 3 stay under the
    pick, 8 fills it, 11, 9 and 17 pass it; the second wave writes over
    the first wave's records, index keys among them."""
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    assert dec.counters.state_bytes == {
        "kv": 3 * SLOTS * CAPACITY * (32 + 8 + 16) * 4, "recurrent": 0}
    rng = np.random.default_rng(11)
    draw = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    first = [(0, draw(5), draw(9)), (1, draw(11), draw(9)),
             (2, draw(8), draw(9))]
    second = [(0, draw(17), draw(6)), (1, draw(3), draw(6)),
              (2, draw(9), draw(6))]
    for wave, steps in ((first, 9), (second, 6)):
        got = arena_logits(dec, model, wave, steps)
        for s, prompt, cont in wave:
            full = np.concatenate([prompt, cont[:steps]])
            want = reference_logits(params, dims_of(cfg),
                                    full)[len(prompt) - 1:]
            close(np.stack(got[s]), want)


def test_served_tokens_are_the_references_best_and_the_step_counts():
    """Seven requests over three slots through ``run()``: each served
    token is the reference's best at its position. The step returns the
    records its rows held and those their attention was given beside the
    tokens."""
    cfg, model, params = build(held=(4, 8))
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 8, 9, 16, 17, 23, 3)]
    rids = [dec.submit(p, 6) for p in prompts]
    out = dec.run()
    for p, rid in zip(prompts, rids):
        full = np.concatenate([p, out[rid]])
        want = reference_logits(params, dims_of(cfg), full)[len(p) - 1:-1]
        took = want[np.arange(len(out[rid])), out[rid]]
        assert np.all(want.max(-1) - took <= 1e-4 * want.std())
    sums, steps = dec.counters.sums, dec.counters.steps
    live, read = sums["dsa_positions_live"], sums["dsa_positions_read"]
    # 3 rows x 3 blocks a step, at most TOPK records a row a block
    assert 0 < read <= steps * 9 * TOPK and read < live
    assert sums["expert_tokens"].shape == (8,)
    assert dec.counters.prefills == 7 and dec.counters.prefill_resteps == 0


def test_a_step_counts_what_its_rows_hold_and_what_they_read():
    cfg, model, _ = build()
    caches = model.init_cache(3, 32)
    t = jnp.asarray([2, 7, 20], jnp.int32)
    model._step_logits_rows(jnp.zeros((3,), jnp.int32), caches, t)
    counted = model.step_counters()
    assert int(counted["dsa_positions_live"]) == 3 * (3 + 8 + 21)
    assert int(counted["dsa_positions_read"]) == 3 * (3 + 8 + 8)
    model._chunk_logits(jnp.zeros((1, 8), jnp.int32),
                        model.init_cache(1, 32), 0)
    assert int(model.step_counters()["dsa_positions_read"]) == 0


# --------------------------------------------------------------------------
# (d) each Pallas body against its jax.numpy body
# --------------------------------------------------------------------------

def draws(seed, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]


def test_the_scores_kernel_gives_the_jnp_scores_span_by_span():
    qi, wi, ki = draws(0, (1, 256, 4, 16), (1, 256, 4), (1, 256, 16))
    want = np.asarray(LA.index_scores(qi, wi, ki))
    assert not LA.scores_kernel_ok(256, 256)
    with A.force_flash():
        assert LA.scores_kernel_ok(256, 256)
        whole = np.asarray(LA.index_scores(qi, wi, ki))
        span = np.asarray(LA.index_scores(qi, wi, ki, 128, 128))
    causal = np.asarray(LA.causal_keep(0, 256))[0]
    np.testing.assert_allclose(np.where(causal, whole, 0),
                               np.where(causal, want, 0), atol=2e-5)
    np.testing.assert_allclose(np.where(causal[128:], span, 0),
                               np.where(causal[128:], want[0, 128:], 0)[None],
                               atol=2e-5)
    # one query a row: the step's scores are the chunk's last row
    step = LA.step_index_scores(qi[:, -1], wi[:, -1], ki)
    np.testing.assert_allclose(np.asarray(step), want[:, -1], atol=2e-5)


def test_the_masked_prefill_kernel_gives_the_jnp_attention_span_by_span():
    q, k, v = draws(1, (1, 4, 256, 24), (1, 4, 256, 24), (1, 4, 256, 16))
    (scores,) = draws(2, (1, 256, 256))
    keep = LA.pick_mask(scores, LA.causal_keep(0, 256), 32)
    want = np.asarray(LA.masked_attention(q, k, v, keep, 0.2))
    with A.force_flash():
        whole = LA.masked_attention(q, k, v, keep, 0.2)
        span = LA.masked_attention(q, k, v, keep[:, 128:], 0.2, q0=128)
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(span), want[:, :, 128:], atol=2e-6)


@pytest.mark.parametrize("cursors", [(255, 100, 3), (0, 128, 127)])
def test_the_masked_read_kernel_gives_the_jnp_read(cursors):
    qa, qr, c, r, scores = draws(3, (3, 4, 128), (3, 4, 8), (3, 256, 128),
                                 (3, 256, 8), (3, 256))
    t = jnp.asarray(cursors, jnp.int32)
    keep = LA.pick_mask(scores, jnp.arange(256)[None] <= t[:, None], 16)
    want = np.asarray(LA.latent_read(qa, qr, c, r, t, 0.1, keep))
    # the same read over the picked records alone, gathered to the front
    at = np.stack([np.resize(np.flatnonzero(row), 16)
                   for row in np.asarray(keep)])[..., None]
    np.testing.assert_allclose(np.asarray(LA.latent_read(
        qa, qr, jnp.take_along_axis(c, at, 1), jnp.take_along_axis(r, at, 1),
        jnp.minimum(t + 1, 16) - 1, 0.1)), want, atol=2e-6)
    with A.force_flash():
        assert LA.read_kernel_ok(256, 128, 8, 4)
        masked = LA.latent_read(qa, qr, c, r, t, 0.1, keep)
    np.testing.assert_allclose(np.asarray(masked), want, atol=2e-6)
    picked, n = LA.step_pick(scores, t, 16)
    np.testing.assert_array_equal(np.asarray(picked), np.asarray(keep))
    assert LA.step_pick(scores[:, :16], jnp.minimum(t, 15), 16)[0] is None
    np.testing.assert_array_equal(np.asarray(n),
                                  np.minimum(np.asarray(cursors) + 1, 16))


def test_a_chunk_by_spans_through_the_kernels_is_the_whole_chunk(
        monkeypatch):
    """The mixer over 256 positions in spans of 128 queries, index
    scores and masked attention through the interpreted kernels, heads
    in groups of two, against the one-span ``jax.numpy`` chunk."""
    pt.seed(5)
    mixer = LatentAttention(64, 4, 24, 32, 16, 8, 24, index_heads=4,
                            index_dim=16, index_topk=32)
    (x,) = draws(6, (1, 256, 64))
    want = np.asarray(mixer(x))
    monkeypatch.setattr(LA, "QUERY_SPAN", 128)
    monkeypatch.setattr(LA, "head_group", lambda heads: 2)
    assert LA.sparse_spans(256) is None
    with A.force_flash():
        assert LA.sparse_spans(256) == [(0, 128), (128, 256)]
        got = np.asarray(mixer(x))
    np.testing.assert_allclose(got, want, atol=1e-5 * want.std())


# --------------------------------------------------------------------------
# (e) the scope, the shares, the parts
# --------------------------------------------------------------------------

def test_the_indexer_is_traced_beside_the_mixers_scopes_not_inside():
    assert "dsa_index" in scopes.SCOPES
    cfg, model, _ = build()
    caches = model.init_cache(2, 32)
    state = (dict(model.named_parameters()), dict(model.named_buffers()))

    def step(mstate, caches, tok, t):
        with inject_state((model, *mstate)):
            return model._step_logits_rows(tok, caches, t)

    def chunk(mstate, caches, toks):
        with inject_state((model, *mstate)):
            return model._chunk_logits(toks, caches, 0)

    for fn, args, own in (
            (step, (jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32)),
             "mla_decode"),
            (chunk, (jnp.zeros((2, 16), jnp.int32),), "mla_prefill")):
        text = jax.jit(fn).lower(state, caches, *args).as_text(
            debug_info=True)
        assert "/dsa_index/" in text and f"/{own}/" in text
        assert f"{own}/dsa_index" not in text
        assert f"dsa_index/{own}" not in text


def test_the_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Four chips hold four of the sixteen experts each; every chip
    routes over all sixteen and computes its own experts' part. The
    parts, with the shared expert counted once, are the uncut
    reference's expert layer."""
    cfg, model, params = build()
    blk, p = model.blocks[1], "blocks.1."
    (u,) = draws(10, (37, 64))
    dims = dims_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = R.experts(u, params, p + "moe.", dims, "f32")[0] + R.gated(
            u, params[p + "shared.gate.weight"],
            params[p + "shared.up.weight"],
            params[p + "shared.down.weight"], "f32")
    total, pairs = blk.shared(u), 0
    for first in range(0, 16, 4):
        part, tokens = moe.dropless_moe(
            u, params[p + "moe.router.weight"],
            params[p + "moe.w_gate"][first:first + 4],
            params[p + "moe.w_up"][first:first + 4],
            params[p + "moe.w_down"][first:first + 4], top_k=4,
            experts_held=(first, 4), routing="sigmoid_noaux_tc",
            score_bias=params[p + "moe.score_bias"], scaling=2.0)
        total, pairs = total + part, pairs + int(tokens.sum())
    assert pairs == 37 * 4
    close(total, want, 1e-5)


def test_a_model_built_with_a_share_is_the_reference_with_that_share():
    cfg, model, params = build(held=(4, 8))
    tokens = np.random.default_rng(13).integers(0, cfg.vocab_size, 15)
    close(model(jnp.asarray(tokens[None]))[0],
          reference_logits(params, dims_of(cfg), tokens))
    assert params["blocks.1.moe.w_gate"].shape[0] == 8


def test_the_grouped_body_in_parts_is_the_grouped_body_whole(monkeypatch):
    """Past ``GROUPED_MAX_BYTES`` of float32 pairs the rows go through
    the grouped body in equal parts: the same terms, the same counts.
    The accepted cells' largest prefills stay whole."""
    assert moe.grouped_parts(14336, 4, 3584) == 1      # Xing4.0
    assert moe.grouped_parts(1024, 10, 4096) == 1      # the hybrid
    assert moe.grouped_parts(28672, 8, 6144) == 7
    assert moe.grouped_parts(8192, 8, 6144) == 2
    assert moe.grouped_parts(1, 8, 6144) == 1
    u, router, wg, wu, wd = draws(14, (48, 16), (16, 32), (4, 16, 8),
                                  (4, 16, 8), (4, 8, 16))
    monkeypatch.setattr(moe, "streams_densely", lambda *counts: False)
    args = dict(top_k=2, experts_held=(3, 4))
    want, pairs = moe.dropless_moe(u, router, wg, wu, wd, **args)
    monkeypatch.setattr(moe, "GROUPED_MAX_BYTES", 12 * 2 * 16 * 4)
    assert moe.grouped_parts(48, 2, 16) == 4
    got, counted = moe.dropless_moe(u, router, wg, wu, wd, **args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(pairs))


def test_a_settled_residual_is_the_plain_sum():
    from paddle_tpu.nn.latent import PlainResidual

    x, y = draws(15, (2, 3, 8), (2, 3, 8))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(PlainResidual(0.5, settle=True).write,
                           static_argnums=2)(x, y, None)),
        np.asarray(PlainResidual(0.5).write(x, y, None)))
    cfg = dataclasses.replace(HybridConfig.tiny_sparse_latent(),
                              settle_residual=True)
    assert HybridForCausalLM(cfg).blocks[0].res1.settle
