"""Window and full attention layers in one model (``nn/gated_attention.py``
through ``models/hybrid.py`` and ``serving.BatchedDecoder``) against the
plain reference ``benchmark/reference/window_moe_f32.py``, which imports
nothing of the program and knows no cache and no ring: (a) the full
forward pass; (b) a prefill of a padded bucket with ``valid_len`` and
then steps at per-row cursors well past the window, through the
decoder's own prefill program and the model entry its step calls; (c)
the same through ``run()``, a slot used again after a LONGER request
and a prompt shorter than the window; (d) the ring against a
full-length cache under a mask; (e) the expert shares; (f) what the
arena refuses for a ring; (g) the gradient of ``forward``; the rotary
embedding over a leading part of a head; the counters.

Tolerance of every logits comparison, ``close``: both sides are float32
and differ in the order of sums only (a ring read in the ring's order
against a band in the sequence's, blockwise against whole, grouped
products against a masked loop), over eight blocks whose softmaxes
the scaled-up query and key projections sharpen: 3e-4 of the logits'
standard deviation, absolute (float32's own rounding reads up to 1.4e-4
of it here). A key at the wrong place of a ring, a stale entry read, a
padded position written, a rotary part turned at the wrong width or a
gate left out moves logits by hundredths of a deviation and more; the
model without its windows and bfloat16 in float32's place both read
ten times the tolerance and more
(``test_the_windows_are_seen_by_the_comparison``,
``test_bfloat16_in_float32s_place_fails``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.reference import window_moe_f32 as R
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.nn.gated_attention import GatedAttention
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.ops import attention as A
from paddle_tpu.serving import BatchedDecoder, KVHandoff

SLOTS, CAPACITY, BUCKET, PAD, WINDOW = 3, 64, 8, 56, 8


def dims_of(cfg: HybridConfig, held=None) -> R.Dims:
    full, slide = cfg.attn_rope[R.FULL], cfg.attn_rope[R.SLIDING]
    y = full["yarn"]
    return R.Dims(
        hidden=cfg.hidden_size, layers=len(cfg.layer_types),
        kinds=tuple(cfg.layer_types),
        heads=tuple(cfg.attn_heads[k] for k in cfg.layer_types),
        kv_heads=cfg.num_kv_heads, head_dim=cfg.attn_head_dim,
        window=cfg.sliding_window,
        mixes=tuple("dense" if m == "mlp" else "sparse"
                    for m in cfg.channel_mixes()),
        ffn=cfg.mlp_width, expert_width=cfg.expert_width,
        shared_width=cfg.shared_width, experts=cfg.num_experts,
        top_k=cfg.experts_per_token,
        held=held or cfg.experts_held or (0, cfg.num_experts),
        scaling=cfg.routed_scaling_factor, vocab=cfg.vocab_size,
        eps=cfg.rms_norm_eps, full_theta=full["rope_theta"],
        full_rotary=full["rotary_dim"], yarn_factor=y["factor"],
        yarn_original=y["original_max_position"],
        beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
        attention_factor=full["attention_factor"],
        sliding_theta=slide["rope_theta"],
        sliding_rotary=slide.get("rotary_dim") or cfg.attn_head_dim)


def build(held=(0, 4), seed=0, periods=2):
    """Two periods of (full, sliding, sliding, sliding), the first block
    dense and seven with 16 experts of which ``held`` are here. Norm
    scales and the selection bias are drawn, so that no leaf is at a
    value (0 or 1) that would hide its use; the matrices are scaled up
    so that attention is far from a plain mean and the gates from 1/2."""
    pt.seed(seed)
    cfg = HybridConfig.tiny_window(periods, held)
    model = HybridForCausalLM(cfg).eval()
    rng = np.random.default_rng(seed + 1)
    params = dict(model.named_parameters())
    for k, v in params.items():
        if k.endswith(("norm1.weight", "norm2.weight", "norm_f.weight")):
            params[k] = jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(v.shape), v.dtype)
        elif k.endswith("score_bias"):
            params[k] = jnp.asarray(
                0.3 * rng.standard_normal(v.shape), v.dtype)
        elif k.endswith(("q_proj.weight", "k_proj.weight",
                         "gate_proj.weight")):
            params[k] = v * 2.0
    model.set_parameters(params)
    return cfg, model, params


TOL = 3e-4


def close(got, want, tol=TOL):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=tol * want.std())


@functools.lru_cache(maxsize=None)
def _reference(dims, window_off=False):
    return jax.jit(lambda tokens, params: R.logits(
        tokens, params, dims, window_off=window_off))


def reference_logits(params, dims, tokens, window_off=False):
    """The reference's logits for one sequence. It is causal, so the
    sequence is padded to one length and one program serves them all."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(dims, window_off)(
            jnp.asarray(padded), params))[:len(tokens)]


# --------------------------------------------------------------------------
# (a) the forward pass
# --------------------------------------------------------------------------

@pytest.mark.parametrize("length", [41, 8, 5])
def test_forward_is_the_reference(length):
    cfg, model, params = build()
    tokens = np.random.default_rng(length).integers(0, cfg.vocab_size,
                                                    length)
    close(model(jnp.asarray(tokens[None]))[0],
          reference_logits(params, dims_of(cfg), tokens))


def test_the_windows_are_seen_by_the_comparison():
    """Past the window the reference without its windows is another
    model; inside it, the same one."""
    cfg, model, params = build()
    tokens = np.random.default_rng(2).integers(0, cfg.vocab_size, 41)
    want = reference_logits(params, dims_of(cfg), tokens)
    off = reference_logits(params, dims_of(cfg), tokens, window_off=True)
    close(off[:WINDOW], want[:WINDOW])
    far = np.abs(off[WINDOW:] - want[WINDOW:]).max(-1)
    assert np.all(far > 10 * TOL * want.std())


def test_bfloat16_in_float32s_place_fails():
    cfg, model, params = build()
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, 41)
    want = reference_logits(params, dims_of(cfg), tokens)
    model.set_parameters({k: v.astype(jnp.bfloat16)
                          for k, v in params.items()})
    got = np.asarray(model(jnp.asarray(tokens[None]))[0], np.float32)
    assert np.abs(got - want).max() > 10 * TOL * want.std()


def test_the_model_names_its_own_leaves_kinds_and_cache_sizes():
    cfg, model, params = build()
    assert model.cache_kinds == ["kv"] * 8
    assert model.cache_records == ["heads", "ring", "ring", "ring"] * 2
    assert [type(b.mixer) for b in model.blocks] == [GatedAttention] * 8
    assert [b.mixer.heads for b in model.blocks] == [6, 8, 8, 8] * 2
    assert params["blocks.0.mixer.q_proj.weight"].shape == (64, 6 * 16)
    assert params["blocks.1.mixer.q_proj.weight"].shape == (64, 8 * 16)
    assert params["blocks.1.mixer.gate_proj.weight"].shape == (64, 8)
    assert params["blocks.1.mixer.k_proj.weight"].shape == (64, 2 * 16)
    assert [b.moe is None for b in model.blocks] == [True] + [False] * 7
    # a ring is the window's length whatever the capacity
    for capacity in (64, 1024):
        caches = model.init_cache(3, capacity)
        assert [c[0].shape[1] for c in caches] == [capacity, 8, 8, 8] * 2
        assert all(c[0].shape == c[1].shape == (3, c[0].shape[1], 2, 16)
                   for c in caches)
    # ... and the capacity's where that is shorter
    assert [c[0].shape[1] for c in model.init_cache(1, 4)] == [4] * 8
    with pytest.raises(EnforceError, match="sliding_window"):
        HybridForCausalLM(HybridConfig(
            layer_types=("sliding_attention",), hidden_size=64))


# --------------------------------------------------------------------------
# the rotary embedding over a leading part of a head
# --------------------------------------------------------------------------

def test_a_leading_part_turns_as_a_head_that_wide_and_the_rest_stays():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 7, 3, 16)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 500, (2, 7)))
    yarn = dict(factor=4.0, original_max_position=16, beta_fast=8.0,
                beta_slow=1.0)
    for kw in (dict(theta=10000.0), dict(theta=500000.0, yarn=yarn)):
        part = A.rotary_embedding(x, pos, rotary_dim=8, **kw)
        np.testing.assert_array_equal(
            part[..., :8], A.rotary_embedding(x[..., :8], pos, **kw))
        np.testing.assert_array_equal(part[..., 8:], x[..., 8:])
        # the whole width, named or not, is what every caller got
        np.testing.assert_array_equal(
            A.rotary_embedding(x, pos, rotary_dim=16, **kw),
            A.rotary_embedding(x, pos, **kw))
    scaled = A.rotary_embedding(x, pos, rotary_dim=8, attention_factor=1.5)
    plain = A.rotary_embedding(x, pos, rotary_dim=8)
    np.testing.assert_allclose(scaled[..., :8], 1.5 * plain[..., :8],
                               rtol=2e-6, atol=3e-7)
    np.testing.assert_array_equal(scaled[..., 8:], x[..., 8:])
    for bad in (0, 7, 18):
        with pytest.raises(EnforceError, match="even"):
            A.rotary_embedding(x, pos, rotary_dim=bad)


# --------------------------------------------------------------------------
# (b) prefill, then steps past the window, through the arena's programs
# --------------------------------------------------------------------------

def arena_logits(dec, model, wave, steps):
    """Prefill each (slot, prompt) of ``wave`` with the decoder's own
    prefill program (a padded bucket, ``valid_len`` the prompt's
    length), then step every slot ``steps`` times through the model
    entry its decode step calls, at per-row cursors, feeding the
    continuation's tokens (teacher forcing). Returns per slot the
    logits at positions plen - 1 .. plen - 1 + steps."""
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


def waves(cfg, seed=11):
    """Window 8, bucket 8. First wave: prompts of 21 (padded to 24: the
    padding would land on three of the ring's live keys), 11 and 8, then
    18 steps, two windows and more past each. Second wave, on the same
    slots: 3 and 5 (shorter than the window, after LONGER requests whose
    keys still fill the ring above them), and 17; 14 steps, so that the
    short ones grow through the window's edge."""
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    return (([(0, draw(21), draw(18)), (1, draw(11), draw(18)),
              (2, draw(8), draw(18))], 18),
            ([(0, draw(3), draw(14)), (1, draw(17), draw(14)),
              (2, draw(5), draw(14))], 14))


def check_waves(dec, model, cfg, params):
    for wave, steps in waves(cfg):
        got = arena_logits(dec, model, wave, steps)
        for s, prompt, cont in wave:
            full = np.concatenate([prompt, cont[:steps]])
            want = reference_logits(params, dims_of(cfg),
                                    full)[len(prompt) - 1:]
            close(np.stack(got[s]), want)


def test_arena_prefill_and_decode_are_the_reference_and_slots_reuse():
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    one = SLOTS * 2 * 16 * 4 * 2        # keys and values, float32
    assert dec.counters.state_bytes == {
        "kv": 2 * CAPACITY * one, "recurrent": 0, "ring": 6 * WINDOW * one}
    check_waves(dec, model, cfg, params)


def test_a_padded_bucket_written_whole_would_fail(monkeypatch):
    """The prefill every other cache takes, the whole padded bucket
    written at its positions, turns a ring's live keys into padding's:
    the comparison sees it."""
    cfg, model, params = build()
    chunk = GatedAttention.forward_chunk
    monkeypatch.setattr(
        GatedAttention, "forward_chunk",
        lambda self, x, cache, t0=0, valid_len=None, decode_kernel=False:
        chunk(self, x, cache, t0, None, decode_kernel))
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    with pytest.raises(AssertionError):
        check_waves(dec, model, cfg, params)


# --------------------------------------------------------------------------
# (c) through run(): served tokens, counters
# --------------------------------------------------------------------------

def test_served_tokens_are_the_references_best_and_the_step_counts():
    """Eight requests over three slots through ``run()``, long ones
    first so that the short ones land on their leftovers: each served
    token is the reference's best at its position (or within the
    tolerance of it)."""
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (23, 17, 21, 3, 5, 9, 8, 2)]
    new = (20, 12, 16, 18, 14, 12, 12, 11)
    rids = [dec.submit(p, n) for p, n in zip(prompts, new)]
    out = dec.run()
    for p, rid in zip(prompts, rids):
        full = np.concatenate([p, out[rid]])
        want = reference_logits(params, dims_of(cfg), full)[len(p) - 1:-1]
        took = want[np.arange(len(out[rid])), out[rid]]
        assert np.all(want.max(-1) - took <= TOL * want.std())
    sums, steps = dec.counters.sums, dec.counters.steps
    # a step's rows read t + 1 positions in each of 2 full layers and
    # min(t + 1, 8) in each of 6 rings
    assert sums["kv_positions_window"] <= steps * SLOTS * 6 * WINDOW
    assert sums["kv_positions_window"] > steps * 6 * WINDOW // 2
    assert sums["kv_positions_full"] > sums["kv_positions_window"] / 3
    assert sums["expert_tokens"].shape == (4,)
    assert dec.counters.prefills == 8 and dec.counters.prefill_resteps == 0


def test_a_step_counts_the_positions_its_rows_read():
    cfg, model, params = build(periods=1)
    caches = model.init_cache(3, CAPACITY)
    t = jnp.asarray([0, 5, 40], jnp.int32)
    model._step_logits_rows(jnp.zeros((3,), jnp.int32), caches, t)
    got = model.step_counters()
    # one full layer reads t + 1 a row, three rings min(t + 1, 8)
    assert int(got["kv_positions_full"]) == 1 + 6 + 41
    assert int(got["kv_positions_window"]) == 3 * (1 + 6 + 8)
    model._chunk_logits(jnp.zeros((1, 16), jnp.int32),
                        model.init_cache(1, CAPACITY), 0, valid_len=11,
                        head_at=10)
    got = model.step_counters()
    assert int(got["kv_positions_full"]) == 11
    assert int(got["kv_positions_window"]) == 3 * 8


# --------------------------------------------------------------------------
# (d) the ring against a full-length cache under a mask
# --------------------------------------------------------------------------

class MaskedFull(GatedAttention):
    """The sliding layer as a cache of the capacity's length read under
    a mask of the band: what a ring replaces."""

    def init_cache(self, batch, capacity, dtype=None):
        shape = (batch, capacity, self.kv_heads, self.head_dim)
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    def forward_chunk(self, x, cache, t0=0, valid_len=None,
                      decode_kernel=False):
        ck, cv = cache
        q, k, v = self._project(x, jnp.arange(x.shape[1]))
        put = lambda c, new: jax.lax.dynamic_update_slice_in_dim(
            c, new, 0, axis=1)
        return (self._finish(x, self._attend_chunk(q, k, v)),
                (put(ck, k), put(cv, v)))

    def forward_step_rows(self, x, cache, t_rows, decode_kernel=False):
        ck, cv = cache
        q, k, v = self._project(x, t_rows[:, None])
        write = jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(
            c, u, s, axis=0))
        ck, cv = write(ck, k, t_rows), write(cv, v, t_rows)
        pos = jnp.arange(ck.shape[1])[None, :]
        keep = (pos <= t_rows[:, None]) & (pos > t_rows[:, None]
                                           - self.window)
        a = A.xla_attention(q, ck, cv, mask=keep[:, None, None, :],
                            scale=self.scale)
        return self._finish(x, a), (ck, cv)


def test_the_ring_is_a_full_length_cache_under_the_bands_mask():
    cfg, model, params = build()
    _, masked, _ = build()
    for blk in masked.blocks:
        if blk.mixer.window is not None:
            blk.mixer.__class__ = MaskedFull
    decs = [BatchedDecoder(m, slots=SLOTS, capacity=CAPACITY,
                           prompt_bucket=BUCKET) for m in (model, masked)]
    assert [c[0].shape[1] for c in decs[1].caches] == [CAPACITY] * 8
    for wave, steps in waves(cfg, seed=21):
        ring, full = (arena_logits(d, m, wave, steps)
                      for d, m in zip(decs, (model, masked)))
        for s, _, _ in wave:
            close(np.stack(ring[s]), np.stack(full[s]), 3e-5)


def test_the_decode_kernel_reads_a_ring_as_the_plain_body_does():
    """Heads of 64 and a ring of 128, which the Pallas decode kernel
    takes (interpreted here): cursors inside the ring, at its edge and
    far past it, 3 query heads a key-value head."""
    pt.seed(5)
    mixer = GatedAttention(64, 6, 2, 64, window=128, gate=True)
    rng = np.random.default_rng(5)
    cache = tuple(jnp.asarray(rng.standard_normal((3, 128, 2, 64)),
                              jnp.float32) for _ in range(2))
    x = jnp.asarray(rng.standard_normal((3, 1, 64)), jnp.float32)
    t = jnp.asarray([5, 127, 1000], jnp.int32)
    want, kept = mixer.forward_step_rows(x, cache, t)
    with A.force_flash():
        got, kept_k = jax.jit(lambda x, c, t: mixer.forward_step_rows(
            x, c, t, decode_kernel=True))(x, cache, t)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    for a, b in zip(kept, kept_k):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    # the key of position 1000 lives at 1000 mod 128
    assert not np.array_equal(kept[0][2, 1000 % 128], cache[0][2, 1000 % 128])
    np.testing.assert_array_equal(kept[0][2, :1000 % 128],
                                  cache[0][2, :1000 % 128])


def test_a_prefill_through_the_banded_flash_kernel_is_the_plain_body():
    """What the chip runs, interpreted here: a padded bucket of 128 with
    100 valid positions through the banded flash forward kernel (window
    64, 3 query heads a key-value head), the last 64 valid positions
    gathered into the ring, then two steps through the decode kernel."""
    pt.seed(6)
    mixer = GatedAttention(64, 6, 2, 64, window=64, gate=True)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 130, 64)), jnp.float32)
    empty = mixer.init_cache(2, 256, jnp.float32)

    def run(x, kernel):
        out, cache = mixer.forward_chunk(x[:, :128], empty, 0, 100)
        steps = []
        for t in (100, 101):
            a, cache = mixer.forward_step_rows(
                x[:, t:t + 1], cache, jnp.full((2,), t, jnp.int32), kernel)
            steps.append(a)
        return out[:, :100], jnp.concatenate(steps, 1), cache

    want = run(x, False)
    with A.force_flash():
        got = jax.jit(lambda x: run(x, True))(x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-6)
    # the steps read what the whole sequence's band gives them
    whole = mixer(x[:, :102])
    np.testing.assert_allclose(got[1], whole[:, 100:], rtol=0, atol=5e-6)


def test_a_chunk_at_an_offset_is_refused():
    cfg, model, _ = build()
    mixer = model.blocks[1].mixer
    x = jnp.zeros((1, 4, 64), jnp.float32)
    with pytest.raises(EnforceError, match="static offset 0"):
        mixer.forward_chunk(x, mixer.init_cache(1, 32), 4)


# --------------------------------------------------------------------------
# (e) the shares of the experts
# --------------------------------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Four chips hold four of the sixteen experts each; every chip
    routes over all sixteen and computes its own experts' part. The
    parts, with the shared expert counted once, are the uncut
    reference's expert layer."""
    cfg, model, params = build(held=(0, 16))
    blk, p = model.blocks[1], "blocks.1."
    u = jnp.asarray(np.random.default_rng(10).standard_normal((37, 64)),
                    jnp.float32)
    dims = dims_of(cfg)
    assert dims.held == (0, 16)
    with jax.default_matmul_precision("highest"):
        want = R.experts(u, params, p + "moe.", dims, "f32")[0] + R.gated(
            u, params[p + "shared.gate.weight"],
            params[p + "shared.up.weight"],
            params[p + "shared.down.weight"], "f32")
    total, pairs = blk.shared(u), 0
    for first in range(0, 16, 4):
        part, tokens = nn.moe.dropless_moe(
            u, params[p + "moe.router.weight"],
            params[p + "moe.w_gate"][first:first + 4],
            params[p + "moe.w_up"][first:first + 4],
            params[p + "moe.w_down"][first:first + 4], top_k=4,
            experts_held=(first, 4), routing="sigmoid_noaux_tc",
            score_bias=params[p + "moe.score_bias"], scaling=2.5)
        total, pairs = total + part, pairs + int(tokens.sum())
    assert pairs == 37 * 4
    close(total, want, 1e-5)


def test_a_model_built_with_a_share_is_the_reference_with_that_share():
    cfg, model, params = build(held=(8, 4))
    tokens = np.random.default_rng(13).integers(0, cfg.vocab_size, 25)
    close(model(jnp.asarray(tokens[None]))[0],
          reference_logits(params, dims_of(cfg), tokens))
    assert params["blocks.1.moe.w_gate"].shape[0] == 4
    assert params["blocks.1.moe.router.weight"].shape[1] == 16


# --------------------------------------------------------------------------
# (f) what the arena refuses for a ring
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(pages=8, page_size=64), dict(prefix_cache=True),
    dict(kv_dtype="int8"), dict(prefill_chunk=8), dict(draft="gpt")])
def test_modes_that_assume_the_capacitys_positions_are_refused(mode):
    cfg, model, _ = build()
    if mode.get("draft") == "gpt":
        pt.seed(1)
        tiny = GPTConfig.tiny()
        tiny.vocab_size = cfg.vocab_size
        mode = dict(draft=GPTForCausalLM(tiny).eval())
    with pytest.raises(EnforceError, match="is refused for a model with a "
                       "ring: a window layer's cache holds its last window"):
        BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8,
                       **mode)


def test_handoff_is_refused_for_a_ring():
    cfg, model, _ = build()
    dec = BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8)
    with pytest.raises(EnforceError, match="prefill_export is refused for "
                       "a model with a ring"):
        dec.prefill_export(np.arange(5))
    handoff = KVHandoff(np.arange(5), 5, np.zeros(4), [], 64)
    with pytest.raises(EnforceError, match="inject_prefilled is refused "
                       "for a model with a ring"):
        dec.inject_prefilled(handoff, 4)


# --------------------------------------------------------------------------
# (g) the gradient
# --------------------------------------------------------------------------

def test_the_gradient_of_forward_is_the_references():
    """The mixer is no serving-only class: ``forward`` differentiates,
    and every leaf's gradient of a random functional of the logits is
    the reference's, to 1e-4 of the leaf's own gradient's largest entry
    (both float32; sums in another order)."""
    cfg, model, params = build(periods=1)
    dims = dims_of(cfg)
    rng = np.random.default_rng(14)
    tokens = rng.integers(0, cfg.vocab_size, 19).astype(np.int32)
    cot = jnp.asarray(rng.standard_normal((19, cfg.vocab_size)),
                      jnp.float32)
    got = jax.jit(jax.grad(lambda p: jnp.sum(model.functional_call(
        p, jnp.asarray(tokens[None]))[0][0] * cot)))(params)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: jnp.sum(R.logits(
            jnp.asarray(tokens), p, dims) * cot)))(params)
    assert set(got) == set(want)
    for k in sorted(want):
        w = np.asarray(want[k])
        if k.endswith("score_bias"):    # a pick is a step function
            assert not np.any(np.asarray(got[k])) and not np.any(w)
            continue
        assert np.abs(w).max() > 0, k
        np.testing.assert_allclose(np.asarray(got[k]), w, rtol=0,
                                   atol=1e-4 * np.abs(w).max(), err_msg=k)
