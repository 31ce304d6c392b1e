"""Latent attention, hyper-connections and sigmoid routing in the hybrid
shell (``nn/latent.py``, ``ops/latent_attention.py``, ``nn/moe.py``,
``models/hybrid.py``) against the plain reference
``benchmark/reference/latent_moe_f32.py``, which imports nothing of the
program: the full forward pass; the absorbed read against the heads'
attention over the same records, in ``jax.numpy`` and in the Pallas
kernel (interpreted); prefill then decode through ``BatchedDecoder``'s
own programs with prompts that straddle a bucket and slots used a
second time; Sinkhorn and the maps; the routing rule; the expert
shares; the modes the arena refuses for a latent record.

Tolerance of every logits comparison, ``close``: both sides are float32
and differ in the order of sums only (absorbed against decompressed,
blockwise against whole, dense or grouped products against a masked
loop), over three blocks: 1e-4 of the logits' standard deviation,
absolute. A record at the wrong cursor, a rotary key at the wrong
position or a map applied in the wrong order moves logits by tenths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.reference import latent_moe_f32 as R
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.hybrid import (HybridBlock, HybridConfig,
                                      HybridForCausalLM)
from paddle_tpu.nn.latent import (HyperConnection, LatentAttention,
                                  PlainResidual, sinkhorn)
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.nn.moe import route
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import latent_attention as LA
from paddle_tpu.serving import BatchedDecoder, KVHandoff

SLOTS, CAPACITY, BUCKET, PAD = 3, 64, 8, 32


def dims_of(cfg: HybridConfig, held=None) -> R.Dims:
    y = cfg.rope_yarn
    return R.Dims(
        hidden=cfg.hidden_size, layers=len(cfg.layer_types),
        dense_layers=cfg.channel_mixes().count("mlp"),
        heads=cfg.num_heads, q_rank=cfg.q_lora_rank,
        kv_rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
        ffn=cfg.mlp_width, expert_width=cfg.expert_width,
        shared_width=cfg.shared_width, experts=cfg.num_experts,
        top_k=cfg.experts_per_token,
        held=held or cfg.experts_held or (0, cfg.num_experts),
        scaling=cfg.routed_scaling_factor, streams=cfg.hc_mult,
        sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
        clamp=tuple(cfg.hc_clamp), vocab=cfg.vocab_size,
        theta=cfg.rope_theta, yarn_factor=y["factor"],
        yarn_original=y["original_max_position"],
        beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
        mscale_all_dim=cfg.rope_mscale_all_dim, eps=cfg.rms_norm_eps)


def build(held=None, seed=0):
    """One dense and two expert blocks over four streams. Norm scales,
    the maps' gains and biases and the selection bias are drawn, so that
    no leaf is at a value (0 or 1) that would hide its use; ``phi`` is
    scaled up so that the maps move with the state."""
    pt.seed(seed)
    cfg = HybridConfig.tiny_latent(3)
    cfg.experts_held = held
    model = HybridForCausalLM(cfg).eval()
    rng = np.random.default_rng(seed + 1)
    params = dict(model.named_parameters())
    for k, v in params.items():
        if k.endswith(("norm.weight", "norm1.weight", "norm2.weight",
                       "norm_f.weight", ".gain")):
            params[k] = jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(v.shape), v.dtype)
        elif k.endswith((".bias", "score_bias")):
            params[k] = jnp.asarray(
                0.3 * rng.standard_normal(v.shape), v.dtype)
        elif k.endswith(".phi"):
            params[k] = v * 4.0
    model.set_parameters(params)
    return cfg, model, params


def close(got, want, tol=1e-4):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=tol * want.std())


@functools.lru_cache(maxsize=None)
def _reference(dims):
    return jax.jit(lambda tokens, params: R.logits(tokens, params, dims))


def reference_logits(params, dims, tokens):
    """The reference's logits for one sequence. It is causal, so the
    sequence is padded to one length and one program serves them all."""
    padded = np.zeros((PAD,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference(dims)(jnp.asarray(padded),
                                           params))[:len(tokens)]


# --------------------------------------------------------------------------
# (a) the full forward pass
# --------------------------------------------------------------------------

@pytest.mark.parametrize("length", [19, 8])
def test_forward_is_the_reference(length):
    cfg, model, params = build()
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size,
                                               (2, length))
    got = model(jnp.asarray(tokens))
    for row in range(2):
        close(got[row], reference_logits(params, dims_of(cfg), tokens[row]))


def test_the_model_names_its_own_leaves_and_kinds():
    cfg, model, params = build()
    assert model.cache_kinds == ["kv"] * 3
    assert model.cache_records == ["latent"] * 3
    assert [b.moe is None for b in model.blocks] == [True, False, False]
    assert params["blocks.0.res1.phi"].shape == (4 * 64, 24)
    assert params["blocks.1.moe.score_bias"].shape == (16,)
    assert "blocks.0.moe.router.weight" not in params
    assert "blocks.1.mlp.gate.weight" not in params
    c, r = model.blocks[0].mixer.init_cache(2, 16)
    assert c.shape == (2, 16, 32) and r.shape == (2, 16, 8)
    with pytest.raises(EnforceError, match="channel_mix names"):
        HybridConfig(layer_types=("attention",) * 2,
                     channel_mix=("mlp",)).channel_mixes()


# --------------------------------------------------------------------------
# (b) the absorbed read against the heads' attention, same records
# --------------------------------------------------------------------------

def mixer_and_input(seed=3, length=21):
    pt.seed(seed)
    cfg = HybridConfig.tiny_latent(1)
    mixer = LatentAttention(
        cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        cfg.rope_theta, cfg.rope_yarn)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, length, cfg.hidden_size)), jnp.float32)
    return mixer, x


def test_an_absorbed_step_is_the_heads_attention_over_the_same_records():
    """Decompressed over the whole sequence (a prefill's form) against
    the cache a prefill of all but the last position leaves, read by an
    absorbed step at per-row cursors."""
    mixer, x = mixer_and_input()
    want = mixer(x)
    cache = mixer.init_cache(2, 32)
    out, (c, r) = mixer.forward_chunk(x[:, :-1], cache, 0)
    close(out, want[:, :-1], 1e-5)
    step, (c2, r2) = mixer.forward_step_rows(
        x[:, -1:], (c, r), jnp.full((2,), x.shape[1] - 1))
    close(step[:, 0], want[:, -1], 1e-5)
    # the record is 32 + 8 numbers a position and only that row moved
    assert c2.shape == (2, 32, 32) and r2.shape == (2, 32, 8)
    at = x.shape[1] - 1
    np.testing.assert_array_equal(np.asarray(c2[:, :at]),
                                  np.asarray(c[:, :at]))
    assert np.abs(np.asarray(c2[:, at])).min() > 0
    np.testing.assert_array_equal(np.asarray(c2[:, at + 1:]), 0)


def test_a_step_continues_a_cache_and_a_chunk_at_an_offset_is_refused():
    mixer, x = mixer_and_input()
    want = mixer(x)
    cache = mixer.init_cache(2, 32)
    _, cache = mixer.forward_chunk(x[:, :9], cache, 0)
    for t in range(9, x.shape[1]):
        one, cache = mixer.forward_step(x[:, t:t + 1], cache, jnp.int32(t))
        close(one[:, 0], want[:, t], 1e-5)
    for t0 in (9, jnp.int32(0)):
        with pytest.raises(Exception, match="static offset 0"):
            mixer.forward_chunk(x[:, 9:], cache, t0)


@pytest.mark.parametrize("cursors", [(0, 5), (127, 128), (255, 131)])
def test_the_kernel_reads_what_the_jnp_body_reads(cursors):
    """The Pallas body (interpreted) against ``jax.numpy`` at per-row
    cursors on either side of a block's edge; records past a cursor are
    junk that neither may read."""
    rng = np.random.default_rng(5)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    qa, qr = draw(2, 4, 128), draw(2, 4, 8)
    c, r = draw(2, 256, 128), draw(2, 256, 8)
    t = jnp.asarray(cursors, jnp.int32)
    want = LA.latent_read(qa, qr, c, r, t, 0.17)
    assert not LA.read_kernel_ok(256, 128, 8, 4)        # the CPU's rule
    with A.force_flash():
        assert LA.read_kernel_ok(256, 128, 8, 4)
        assert not LA.read_kernel_ok(256, 96, 8, 4)
        assert not LA.read_kernel_ok(200, 128, 8, 4)
        got = jax.jit(lambda *a: LA.latent_read(*a, 0.17))(qa, qr, c, r, t)
    assert got.shape == (2, 4, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("length", [64, 24, 16, 1])
def test_the_plain_body_is_whole_attention(length):
    rng = np.random.default_rng(6)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = draw(2, length, 3, 24), draw(2, length, 3, 24), draw(
        2, length, 3, 16)
    got = LA.causal_attention(q, k, v, 0.2)
    want = A.xla_attention(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 8),)),
                           causal=True, scale=0.2)[..., :16]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


def test_the_padded_flash_body_is_the_jnp_body():
    """Heads of 24 / 16, each width padded to whole lanes, through the
    Pallas flash kernel (interpreted) against the plain ``jax.numpy``
    body; the rule takes the kernel only where its query block divides
    the length."""
    rng = np.random.default_rng(8)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = draw(1, 1024, 2, 24), draw(1, 1024, 2, 24), draw(
        1, 1024, 2, 16)
    want = LA.causal_attention(q, k, v, 0.2)
    assert not LA.prefill_kernel_ok(1024, 24, 16)       # the CPU's rule
    with A.force_flash():
        assert LA.prefill_kernel_ok(1024, 24, 16)
        assert not LA.prefill_kernel_ok(512, 24, 16)
        assert not LA.prefill_kernel_ok(1024, 320, 16)
        got = jax.jit(lambda *a: LA.causal_attention(*a, 0.2))(q, k, v)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("widths", [(192, 128), (24, 16)])
def test_the_flash_body_at_two_widths_is_the_jnp_body(widths):
    """Scores of dq and values of dv through the Pallas flash kernel
    (interpreted), each padded to its own whole lanes (192 / 128 as
    256 / 128, 24 / 16 as 128 / 128), against the plain ``jax.numpy``
    body: forward and the three gradients."""
    dq, dv = widths
    rng = np.random.default_rng(9)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = draw(1, 1024, 2, dq), draw(1, 1024, 2, dq), draw(
        1, 1024, 2, dv)
    ct = draw(1, 1024, 2, dv)

    def body(q, k, v):
        o = LA.causal_attention(q, k, v, dq ** -0.5)
        return jnp.sum(o * ct), o

    def run():  # a new function a call: jit's cache goes by the function
        return jax.jit(jax.value_and_grad(
            lambda *a: body(*a), argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (_, want), want_grads = run()
    with A.force_flash():
        assert LA.prefill_kernel_ok(1024, dq, dv)
        (_, got), got_grads = run()
    assert got.shape == want.shape == (1, 1024, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)
    for g, w, name in zip(got_grads, want_grads, "qkv"):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=0, atol=2e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("dq,dv,dtype,tuned", [
    (192, 128, jnp.bfloat16, True),     # kanana-2, Xing4.0: 256 / 128
    (24, 16, jnp.bfloat16, False),      # 128 / 128: another key
    (128, 256, jnp.bfloat16, False),    # 128 / 256: another key
    (192, 256, jnp.bfloat16, False),    # 256 / 256: the equal-width key
    (192, 128, jnp.float32, False),     # four-byte operands: another key
])
def test_the_prefills_blocks_are_the_tuned_tables(monkeypatch, dq, dv,
                                                  dtype, tuned):
    """One owner picks the flash kernels' blocks: the table measured on
    the chip, keyed by the length's bucket, the two widths as padded and
    the type the operands reach the kernels in; where it has no entry
    for the call, the prefill's static 1024 x 512."""
    import importlib

    from paddle_tpu.ops.pallas import tuning

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    entry = {"block_q": 512, "block_k": 1024, "block_q_bwd": 256,
             "block_k_bwd": 512}
    seen = {}

    def fake_flash(q, k, v, **kw):
        seen.update(kw, widths=(q.shape[-1], k.shape[-1], v.shape[-1]))
        return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

    monkeypatch.setattr(FA, "flash_attention", fake_flash)
    tuning.reset_cache()
    try:
        tuning.set_tuned(tuning.attention_key(
            2048, 2048, 256, True, dtype=jnp.bfloat16, e=128), entry,
            persist=False)
        q, k, v = (jnp.zeros((1, 2048, 2, w), dtype) for w in (dq, dq, dv))
        out = LA._flash_padded(q, k, v, 0.1)
    finally:
        tuning.reset_cache()
    lanes = lambda w: w + -w % 128
    assert out.shape == (1, 2048, 2, dv)
    assert seen["widths"] == (lanes(dq), lanes(dq), lanes(dv))
    blocks = {name: seen[name] for name in entry}
    assert blocks == (entry if tuned else {
        "block_q": LA.FLASH_BLOCK_Q, "block_k": LA.FLASH_BLOCK_K,
        "block_q_bwd": LA.FLASH_BLOCK_Q, "block_k_bwd": LA.FLASH_BLOCK_K})


def test_yarn_frequencies_blend_between_the_two_turn_counts():
    f = np.asarray(A.yarn_frequencies(32, 1e4, 64.0, 4096, 32.0, 1.0))
    plain = 1e4 ** (-np.arange(32) / 32)
    # pairs that turn often over 4096 positions keep their frequency,
    # those that turn less than once are interpolated by the factor
    turns = 4096 * plain / (2 * np.pi)
    np.testing.assert_allclose(f[turns > 40], plain[turns > 40], rtol=1e-6)
    np.testing.assert_allclose(f[turns < 0.8], plain[turns < 0.8] / 64,
                               rtol=1e-6)
    assert np.all(np.diff(f) < 0) and np.all(f <= plain * (1 + 1e-6))
    cfg = HybridConfig.tiny_latent(1)
    np.testing.assert_allclose(
        np.asarray(A.yarn_frequencies(4, 1e4, **cfg.rope_yarn)),
        np.asarray(R.yarn_frequencies(dims_of(cfg))), rtol=1e-6)
    x = jnp.ones((1, 3, 1, 8))
    assert not np.allclose(
        np.asarray(A.rotary_embedding(x, jnp.arange(3), 1e4)),
        np.asarray(A.rotary_embedding(x, jnp.arange(3), 1e4,
                                      cfg.rope_yarn)))


# --------------------------------------------------------------------------
# (c) prefill, then decode, through the arena's own programs
# --------------------------------------------------------------------------

def arena_logits(dec, model, wave, steps):
    """Prefill each (slot, prompt) of ``wave`` with the decoder's own
    prefill program, then step every slot ``steps`` times through the
    model entry its decode step calls, feeding the continuation's tokens
    (teacher forcing). Returns per slot the logits at positions
    plen - 1 .. plen - 1 + steps of prompt + continuation."""
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
    """Bucket 8: prompts of 5, 11 and 3 leave padding in their bucket, 8
    fills it, 9 and 17 straddle one; the second wave writes over the
    first wave's records, which lie above its cursors."""
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    assert dec.counters.state_bytes == {
        "kv": 3 * SLOTS * CAPACITY * (32 + 8) * 4, "recurrent": 0}
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
    token is the reference's best at its position (or within the
    tolerance of it). The step returns the experts' pairs and the
    unbalanced maps beside the tokens."""
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
    sums = dec.counters.sums
    # 3 rows x 6 maps a step; phi is scaled up here, so that 20 rounds
    # leave some maps off balance by more than 1e-3 and the count shows
    assert 0 < sums["mhc_unbalanced"] < dec.counters.steps * 18
    assert sums["expert_tokens"].shape == (8,)
    # 3 rows x 4 picks x 2 expert layers a step, half the experts held
    assert 0 < sums["expert_tokens"].sum() < dec.counters.steps * 24
    # 16 experts > 3 rows x 4 picks: a step's rows take the grouped body
    assert sums["expert_dense_layers"] == 0
    assert dec.counters.prefills == 7 and dec.counters.prefill_resteps == 0


# --------------------------------------------------------------------------
# (d) hyper-connections
# --------------------------------------------------------------------------

def test_sinkhorn_balances_and_the_clamp_holds():
    rng = np.random.default_rng(2)
    a = jnp.asarray(0.5 * rng.standard_normal((50, 4, 4)), jnp.float32)
    m = np.asarray(sinkhorn(a, 20, 1e-6))
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    assert np.all(m >= 0)
    # logits that spread over 4: the last division leaves the columns
    # exact, and 20 rounds leave the rows off by up to some hundredths
    # (what ``unbalanced`` counts)
    wide = np.asarray(sinkhorn(8.0 * a, 20, 1e-6))
    np.testing.assert_allclose(wide.sum(-2), 1.0, atol=1e-5)
    assert 1e-3 < np.abs(wide.sum(-1) - 1.0).max() < 0.2
    # logits of +-1000: without the clamp exp overflows; with it the
    # maps are those of logits cut to +-30, finite, and counted as
    # unbalanced where 20 rounds cannot balance e^60
    pt.seed(0)
    hc = HyperConnection(8, 4)
    params = dict(hc.named_parameters())
    params["gain"] = jnp.asarray([1.0, 1.0, 1000.0])
    params["bias"] = jnp.zeros((24,))
    hc.set_parameters(params)
    x = jnp.asarray(rng.standard_normal((6, 4, 8)), jnp.float32)
    pre, post, res = hc.maps(x)
    assert np.all(np.isfinite(np.asarray(res)))
    flat = x.reshape(6, -1)
    flat = flat / jnp.sqrt(jnp.mean(flat ** 2, -1, keepdims=True) + 1e-6)
    logits = 1000.0 * (flat @ params["phi"])[:, 8:].reshape(6, 4, 4)
    assert float(jnp.abs(logits).max()) > 30
    np.testing.assert_allclose(
        np.asarray(res),
        np.asarray(sinkhorn(jnp.clip(logits, -30, 30), 20, 1e-6)),
        rtol=1e-5, atol=1e-9)
    off = np.maximum(np.abs(np.asarray(res).sum(-1) - 1).max(-1),
                     np.abs(np.asarray(res).sum(-2) - 1).max(-1))
    assert int(hc.counted["mhc_unbalanced"]) == int((off > 1e-3).sum())
    assert np.all((np.asarray(pre) > 0) & (np.asarray(pre) < 1))
    assert np.all((np.asarray(post) > 0) & (np.asarray(post) < 2))


def test_one_stream_with_unit_maps_is_the_plain_path_bit_for_bit():
    """``n`` = 1, ``phi`` = 0 and settings at which the float32 maps are
    exactly 1: ``H_pre`` = sigmoid(20), ``H_post`` = 2 sigmoid(0), and
    the one-by-one Sinkhorn ``M / M`` with ``hc_eps`` = 0 (with 1e-6
    each round divides 1 by 1 + 1e-6)."""
    pt.seed(4)
    cfg = HybridConfig.tiny_latent(1)
    cfg.hc_mult = 1
    blk = HybridBlock(cfg, "latent", "mlp").eval()
    assert isinstance(blk.res1, PlainResidual) and blk.res1 is blk.res2
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 11, 64)),
                    jnp.float32)
    want = blk(x)
    unit = HyperConnection(64, 1, eps=0.0)
    unit.set_parameters({"phi": jnp.zeros((64, 3)),
                         "bias": jnp.asarray([20.0, 0.0, 30.0]),
                         "gain": jnp.ones((3,))})
    pre, post, res = unit.maps(x[:, :, None, :])
    assert float(pre.min()) == float(post.min()) == float(res.min()) == 1.0
    object.__setattr__(blk, "res1", unit)
    object.__setattr__(blk, "res2", unit)
    got = blk(x[:, :, None, :])
    assert got.shape == (2, 11, 1, 64)
    np.testing.assert_array_equal(np.asarray(got[:, :, 0]),
                                  np.asarray(want))


def test_the_maps_are_float32_whatever_the_weights():
    pt.seed(5)
    hc = HyperConnection(16, 4)
    hc.set_parameters({k: v.astype(jnp.bfloat16)
                       for k, v in hc.named_parameters().items()})
    x = jnp.asarray(np.random.default_rng(5).standard_normal((3, 4, 16)),
                    jnp.bfloat16)
    u, (post, res) = hc.read(x)
    assert u.dtype == post.dtype == res.dtype == jnp.float32
    # and the state stays float32 between sublayers
    new = hc.write(x, u, (post, res))
    assert new.dtype == jnp.float32 and new.shape == x.shape
    pt.seed(5)
    model = HybridForCausalLM(HybridConfig.tiny_latent(1)).eval()
    assert model._embed(jnp.zeros((1, 3), jnp.int32)).shape == (1, 3, 4, 64)
    assert model._embed(jnp.zeros((1, 3), jnp.int32)).dtype == jnp.float32


# --------------------------------------------------------------------------
# (e) the routing rule and the expert shares
# --------------------------------------------------------------------------

def test_the_bias_selects_and_does_not_weigh():
    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    zero = jnp.zeros((16,))
    bias = jnp.asarray(np.where(np.arange(16) < 4, 5.0, 0.0), jnp.float32)
    g0, p0 = route(logits, 4, "sigmoid_noaux_tc", zero, 2.0)
    g1, p1 = route(logits, 4, "sigmoid_noaux_tc", bias, 2.0)
    # a bias of 5 on experts 0 to 3 makes them every token's picks
    assert np.all(np.sort(np.asarray(p1), -1) == np.arange(4))
    assert np.any(np.sort(np.asarray(p0), -1) != np.arange(4))
    # and the gates are the unbiased scores of those picks, normalised
    sc = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(sc, np.asarray(p1), -1)
    np.testing.assert_allclose(
        np.asarray(g1), 2.0 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-6)
    for g in (g0, g1):
        np.testing.assert_allclose(np.asarray(g).sum(-1), 2.0, rtol=1e-6)
    gs, ps = route(logits, 4)
    np.testing.assert_allclose(np.asarray(gs).sum(-1), 1.0, rtol=1e-6)
    assert nn.DroplessMoE.ROUTING == ("topk_softmax", "sigmoid_noaux_tc")
    with pytest.raises(EnforceError, match="routing rule"):
        nn.DroplessMoE(8, 8, 4, 2, routing="sigmoid")
    with pytest.raises(EnforceError, match="lacks its bias"):
        route(logits, 4, "sigmoid_noaux_tc")


def test_the_eight_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Eight chips hold two of the sixteen experts each; every chip
    routes over all sixteen and computes its own experts' part. The
    parts, with the shared expert counted once, are the uncut
    reference's expert layer."""
    cfg, model, params = build()
    blk, p = model.blocks[1], "blocks.1."
    u = jnp.asarray(np.random.default_rng(10).standard_normal((37, 64)),
                    jnp.float32)
    dims = dims_of(cfg)
    with jax.default_matmul_precision("highest"):
        want = R.experts(u, params, p + "moe.", dims, "f32") + R.gated(
            u, params[p + "shared.gate.weight"],
            params[p + "shared.up.weight"],
            params[p + "shared.down.weight"], "f32")
    total, pairs = blk.shared(u), 0
    for first in range(0, 16, 2):
        part, tokens = nn.moe.dropless_moe(
            u, params[p + "moe.router.weight"],
            params[p + "moe.w_gate"][first:first + 2],
            params[p + "moe.w_up"][first:first + 2],
            params[p + "moe.w_down"][first:first + 2], top_k=4,
            experts_held=(first, 2), routing="sigmoid_noaux_tc",
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
    assert params["blocks.1.moe.router.weight"].shape[1] == 16


# --------------------------------------------------------------------------
# (f) what the arena refuses for a latent record
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(pages=8, page_size=64), dict(prefix_cache=True),
    dict(kv_dtype="int8"), dict(prefill_chunk=8), dict(draft="gpt")])
def test_modes_that_assume_keys_and_values_by_head_are_refused(mode):
    cfg, model, _ = build()
    if mode.get("draft") == "gpt":
        pt.seed(1)
        tiny = GPTConfig.tiny()
        tiny.vocab_size = cfg.vocab_size
        mode = dict(draft=GPTForCausalLM(tiny).eval())
    with pytest.raises(EnforceError, match="latent record"):
        BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8,
                       **mode)


def test_handoff_is_refused_for_a_latent_record():
    cfg, model, _ = build()
    dec = BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8)
    with pytest.raises(EnforceError, match="latent record"):
        dec.prefill_export(np.arange(5))
    handoff = KVHandoff(np.arange(5), 5, np.zeros(4), [], 64)
    with pytest.raises(EnforceError, match="latent record"):
        dec.inject_prefilled(handoff, 4)
