"""The hybrid state-space / attention / routed-experts LM
(``models/hybrid.py``) against its plain reference
(``benchmark/reference/hybrid_moe_f32.py``, which imports nothing of the
program), at a tiny size in float32 on the CPU with seeded weights:
the full forward pass; prefill then decode through ``BatchedDecoder``'s
own programs with prompts that do not fill their bucket and slots used
a second time; the expert shares; the modes the arena refuses. Section
(g) holds the shell's other kinds (retention mixer, gated MLP, untied
head) to ``benchmark/reference/retention_f32.py`` the same way.

Tolerance of every logits comparison, ``close``: both sides are float32
and differ in the order of sums only (chunked scan against a position
scan, dense or grouped products against a masked loop, cached attention
against a full one), over six blocks: some tens of float32 roundings, so 1e-4 of
the logits' standard deviation, absolute. What the arena could get
wrong reads far above that: a state advanced over the bucket's padding,
a last token applied twice or a state left from the slot's last request
each move logits by tenths of a standard deviation, and a state kept in
bfloat16 by 1e-2 (``test_a_bfloat16_state_would_fail``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.reference import hybrid_moe_f32 as R
from benchmark.reference import retention_f32 as RR
from paddle_tpu import nn
from paddle_tpu.core import EnforceError
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.serving import BatchedDecoder, KVHandoff

SLOTS, CAPACITY, BUCKET, PAD = 3, 64, 8, 32


def dims_of(cfg: HybridConfig, held=None) -> R.Dims:
    return R.Dims(
        hidden=cfg.hidden_size, layer_types=tuple(cfg.layer_types),
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.hidden_size // cfg.num_heads,
        expert_width=cfg.expert_width, shared_width=cfg.shared_width,
        experts=cfg.num_experts, top_k=cfg.experts_per_token,
        held=held or cfg.experts_held or (0, cfg.num_experts),
        ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
        ssm_state=cfg.ssm_state, ssm_conv=cfg.ssm_conv,
        ssm_chunk=cfg.ssm_chunk, vocab=cfg.vocab_size,
        eps=cfg.rms_norm_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling)


def build(held=None, seed=0):
    """Two periods of (mamba, mamba, attention). The embedding's and
    the residual's multipliers are 1 here so that the blocks, not the
    token's own embedding, decide the logits; the convolution's bias and
    the norm scales are drawn, so that no leaf is at a value (0 or 1)
    that would hide its use."""
    pt.seed(seed)
    cfg = HybridConfig.tiny(2)
    cfg.embedding_multiplier, cfg.residual_multiplier = 2.0, 0.7
    cfg.experts_held = held
    model = HybridForCausalLM(cfg).eval()
    rng = np.random.default_rng(seed + 1)
    params = dict(model.named_parameters())
    for k, v in params.items():
        if k.endswith(("conv_bias", "norm.weight", "norm1.weight",
                       "norm2.weight", "norm_f.weight", ".D")):
            params[k] = jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(v.shape), v.dtype)
    model.set_parameters(params)
    return cfg, model, params


def close(got, want, tol=1e-4):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=tol * want.std())


@functools.lru_cache(maxsize=None)
def _reference(dims):
    ref = RR if isinstance(dims, RR.Dims) else R
    return jax.jit(lambda tokens, params: ref.logits(tokens, params, dims))


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


def test_forward_loss_is_the_references():
    cfg, model, params = build()
    tokens = jnp.asarray(np.random.default_rng(8).integers(
        0, cfg.vocab_size, (2, 13)))
    with jax.default_matmul_precision("highest"):
        want = float(R.loss(params, tokens, dims_of(cfg)))
    # a mean of log-probabilities of order 5: float32 rounding
    assert abs(float(model.forward_loss(tokens)) - want) < 1e-4 * want


# --------------------------------------------------------------------------
# (b) prefill, then decode, through the arena's own programs
# --------------------------------------------------------------------------

def arena_logits(dec, model, wave, steps, round_state=None):
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
        if round_state is not None:
            dec.caches = round_state(dec.caches)
        for s, _, _ in wave:
            out[s].append(np.asarray(logits[s]))
    return out


def waves(vocab):
    """Two waves over the same three slots. Bucket 8: prompts of 5, 11
    and 3 leave padding in their bucket, 8 fills it, 17 takes three."""
    rng = np.random.default_rng(11)
    draw = lambda n: rng.integers(0, vocab, n).astype(np.int32)
    first = [(0, draw(5), draw(9)), (1, draw(11), draw(9)),
             (2, draw(8), draw(9))]
    second = [(0, draw(17), draw(6)), (1, draw(3), draw(6)),
              (2, draw(1), draw(6))]
    return first, second


def check_wave(got, wave, steps, params, dims, tol=1e-4):
    for s, prompt, cont in wave:
        full = np.concatenate([prompt, cont[:steps]])
        want = reference_logits(params, dims, full)[len(prompt) - 1:]
        close(np.stack(got[s]), want, tol)


def test_arena_prefill_and_decode_are_the_reference_and_slots_reuse():
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    assert dec.counters.state_bytes["recurrent"] > 0
    assert dec.counters.state_bytes["kv"] > 0
    first, second = waves(cfg.vocab_size)
    check_wave(arena_logits(dec, model, first, 9), first, 9, params,
               dims_of(cfg))
    # the slots now hold the first wave's states, nine steps on
    check_wave(arena_logits(dec, model, second, 6), second, 6, params,
               dims_of(cfg))


def test_a_bfloat16_state_would_fail():
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    first, _ = waves(cfg.vocab_size)

    def rounded(caches):
        return [tuple(a.astype(jnp.bfloat16).astype(a.dtype) for a in c)
                if kind == "recurrent" else c
                for kind, c in zip(model.cache_kinds, caches)]

    got = arena_logits(dec, model, first, 9, round_state=rounded)
    with pytest.raises(AssertionError):
        check_wave(got, first, 9, params, dims_of(cfg))


def serve_seven_over_three_slots():
    """Seven requests over three slots, so every slot serves a second
    and a third request; each served token is the reference's best at
    its position (or within the tolerance of it, where two logits all
    but tie). Returns (cfg, the drained decoder)."""
    cfg, model, params = build()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8, 3, 17, 9, 1)]
    rids = [dec.submit(p, 7) for p in prompts]
    out = dec.run()
    for p, rid in zip(prompts, rids):
        full = np.concatenate([p, out[rid]])
        want = reference_logits(params, dims_of(cfg), full)[len(p) - 1:-1]
        took = want[np.arange(len(out[rid])), out[rid]]
        assert (want.max(-1) - took <= 1e-4 * want.std()).all()
    return cfg, dec


def test_served_tokens_are_the_references_best_over_reused_slots():
    """The whole arena, end to end."""
    cfg, dec = serve_seven_over_three_slots()
    # the step counted the pairs each held expert got, every row of it
    assert dec.counters.steps == dec.tick_count
    assert int(dec.counters.expert_tokens.sum()) == (
        dec.tick_count * SLOTS * cfg.experts_per_token
        * len(cfg.layer_types))


@pytest.mark.parametrize("dense", [True, False])
def test_the_step_counts_the_expert_layers_that_streamed_densely(
        dense, monkeypatch):
    """A decode step whose rows ``nn.moe.streams_densely`` sends
    through the dense body says so beside the tokens, every expert
    layer of it; under a rule that sorts and groups those rows the sum
    stays 0. The arena counts the same for its prefills on the host,
    from the bucket alone: every expert layer at the bucket's rows but
    the last block's, which runs at the one row the head reads and is
    grouped under the library's rule (4 picks for 12 experts). The
    served tokens are the reference's either way."""
    if not dense:
        monkeypatch.setattr(nn.moe, "streams_densely", lambda *_: False)
    cfg, dec = serve_seven_over_three_slots()
    layers = len(cfg.layer_types)
    assert dec.counters.steps == dec.tick_count > 0
    assert int(dec.counters.sums["expert_dense_layers"]) == (
        layers * dec.counters.steps if dense else 0)
    assert dec.counters.prefills == 7
    assert dec.counters.prefill_expert_layers == 7 * layers
    assert dec.counters.prefill_dense_layers == (
        7 * (layers - 1) if dense else 0)
    assert dec.model.expert_layers(BUCKET, 1) == (
        layers, layers - 1 if dense else 0)
    assert dec.model.expert_layers(SLOTS) == (layers,
                                              layers if dense else 0)


def test_a_prefill_at_bucket_256_is_the_stepped_prompt():
    """A prompt of 200 tokens in a bucket of 256: the expert layers
    take the dense body at 256 rows (all but the last block's one row)
    and the one-pass prefill leaves the first token's logits and the
    slot's state that stepping the prompt token by token through the
    decode entry leaves (the experts at one row a step: grouped)."""
    cfg, model, params = build()
    layers = len(cfg.layer_types)
    assert model.expert_layers(256, 1) == (layers, layers - 1)
    assert model.expert_layers(1) == (layers, 0)
    plen, s = 200, 1
    prompt = np.random.default_rng(14).integers(
        0, cfg.vocab_size, plen).astype(np.int32)
    dec = BatchedDecoder(model, slots=SLOTS, capacity=256,
                         prompt_bucket=256)
    padded = np.zeros((256,), np.int32)
    padded[:plen] = prompt
    pf = dec._prefill_fn(256)
    args = (dec._mstate, dec.caches, jnp.asarray(padded), plen, s)
    # the last block's one row is the program's only grouped layer
    assert str(jax.make_jaxpr(pf)(*args)).count("ragged_dot_general[") == 3
    dec.caches, logits = pf(*args)

    @jax.jit
    def step(mstate, caches, tok, t):
        with inject_state((model, *mstate)):
            return model._step_logits(tok, caches, t)

    row = model.init_cache(1, 256)
    for t, tok in enumerate(prompt):
        want, row = step(dec._mstate, row, jnp.asarray([tok]), t)
    close(np.asarray(logits), np.asarray(want[0]))
    assert int(np.argmax(logits)) == int(np.argmax(want[0]))
    for kind, got, ref in zip(model.cache_kinds, dec.caches, row):
        # keys and values of the prompt's positions; a state whole
        upto = slice(plen) if kind == "kv" else slice(None)
        for g, r in zip(got, ref):
            g, r = np.asarray(g[s])[upto], np.asarray(r[0])[upto]
            np.testing.assert_allclose(g, r, rtol=1e-3,
                                       atol=1e-4 * np.abs(r).max())


# --------------------------------------------------------------------------
# (d) the share, tied to the model
# --------------------------------------------------------------------------

def test_the_two_shares_and_the_shared_mlp_once_are_the_whole_block():
    """Chip A holds experts 0..5, chip B 6..11; both compute the shared
    MLP. A's routed part + B's routed part + the shared MLP once is the
    uncut reference's whole expert block."""
    cfg, model, params = build()
    blk = model.blocks[0]
    u = jnp.asarray(np.random.default_rng(13).standard_normal(
        (9, cfg.hidden_size)), jnp.float32)
    p = "blocks.0."
    w = {k: v for k, v in params.items() if k.startswith(p)}
    with jax.default_matmul_precision("highest"):
        whole = (R.experts(u, w, p + "moe.", dims_of(cfg, (0, 12)), "f32")
                 + R.gated(u, w[p + "shared.gate.weight"],
                           w[p + "shared.up.weight"],
                           w[p + "shared.down.weight"], "f32"))
    parts = 0
    for first in (0, 6):
        pt.seed(0)
        share = nn.DroplessMoE(cfg.hidden_size, cfg.expert_width, 12,
                               cfg.experts_per_token,
                               experts_held=(first, 6))
        share.set_parameters({
            "router.weight": w[p + "moe.router.weight"],
            **{k: w[p + "moe." + k][first:first + 6]
               for k in ("w_gate", "w_up", "w_down")}})
        parts = parts + share(u)
    close(parts + blk.shared(u), whole)


def test_a_model_built_with_a_share_is_the_reference_with_that_share():
    cfg, model, params = build(held=(3, 6))
    assert params["blocks.0.moe.w_gate"].shape[0] == 6
    tokens = np.random.default_rng(14).integers(0, cfg.vocab_size, 15)
    close(model(jnp.asarray(tokens[None]))[0],
          reference_logits(params, dims_of(cfg), tokens))


# --------------------------------------------------------------------------
# (f) what the arena refuses for a recurrent state
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(pages=8, page_size=64), dict(prefix_cache=True),
    dict(kv_dtype="int8"), dict(prefill_chunk=8), dict(draft="gpt")])
def test_position_addressed_modes_are_refused(mode):
    cfg, model, _ = build()
    if mode.get("draft") == "gpt":
        pt.seed(1)
        tiny = GPTConfig.tiny()
        tiny.vocab_size = cfg.vocab_size
        mode = dict(draft=GPTForCausalLM(tiny).eval())
    with pytest.raises(EnforceError, match="recurrent state"):
        BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8,
                       **mode)


def test_handoff_is_refused():
    cfg, model, _ = build()
    dec = BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8)
    with pytest.raises(EnforceError, match="recurrent state"):
        dec.prefill_export(np.arange(5))
    handoff = KVHandoff(np.arange(5), 5, np.zeros(4), [], 64)
    with pytest.raises(EnforceError, match="recurrent state"):
        dec.inject_prefilled(handoff, 4)


def test_an_attention_only_model_is_refused_nothing():
    pt.seed(2)
    gpt = GPTForCausalLM(GPTConfig.tiny()).eval()
    dec = BatchedDecoder(gpt, slots=2, capacity=128, prompt_bucket=16,
                         prefill_chunk=16)
    assert dec.counters.state_bytes["recurrent"] == 0
    assert dec.counters.expert_tokens is None
    rid = dec.submit(np.arange(1, 20), 5)
    assert len(dec.run()[rid]) == 5


# --------------------------------------------------------------------------
# (g) the shell's other kinds: retention mixer, gated MLP, untied head
# --------------------------------------------------------------------------

# The tolerance is ``close``'s: the decode step reads the state through
# ``phi``, whose D products are of either sign and cancel down to the
# weight the attention form sums directly, but it computes the token's
# own term directly too, so a denominator never rests on the
# cancellation alone (largest difference seen here 5e-5 of the logits'
# deviation; a state kept in bfloat16 reads 1e-2 to 4e-1).


def build_retention(seed=0):
    """Three retention blocks, five query heads a key-value head. The
    norm scales (the per-head ones of queries and keys too) are drawn,
    and the gate's weights are scaled up so that its decays spread over
    0.05 .. 0.95 instead of sitting at 0.5."""
    pt.seed(seed)
    cfg = HybridConfig.tiny_retention(3)
    model = HybridForCausalLM(cfg).eval()
    rng = np.random.default_rng(seed + 1)
    params = dict(model.named_parameters())
    for k, v in params.items():
        if k.endswith("norm.weight") or k.endswith(
                ("norm1.weight", "norm2.weight", "norm_f.weight")):
            params[k] = jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(v.shape), v.dtype)
        if k.endswith("gate_proj.weight"):
            params[k] = v * 8.0
    model.set_parameters(params)
    dims = RR.Dims(hidden=cfg.hidden_size, layers=len(cfg.layer_types),
                   heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.hidden_size // cfg.num_heads,
                   ffn=cfg.mlp_width, vocab=cfg.vocab_size,
                   theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
                   retention_eps=cfg.retention_eps)
    return cfg, model, params, dims


def test_the_retention_model_names_its_own_leaves():
    cfg, model, params, _ = build_retention()
    assert model.cache_kinds == ["recurrent"] * 3
    assert "lm_head" in params and params["lm_head"].shape == (80, 256)
    assert {k.split(".", 2)[2] for k in params if k.startswith(
        "blocks.0.")} == {
        "norm1.weight", "norm2.weight", "mixer.q_proj.weight",
        "mixer.k_proj.weight", "mixer.v_proj.weight",
        "mixer.gate_proj.weight", "mixer.q_norm.weight",
        "mixer.k_norm.weight", "mixer.out_proj.weight",
        "mlp.gate.weight", "mlp.up.weight", "mlp.down.weight"}
    S, z = model.init_cache(2, 64)[0]
    assert S.shape == (2, 2, 40, 8) and z.shape == (2, 2, 40)
    assert S.dtype == z.dtype == jnp.float32
    with pytest.raises(EnforceError, match="degree"):
        bad = HybridConfig.tiny_retention(1)
        bad.retention_degree = 3
        HybridForCausalLM(bad)
    with pytest.raises(EnforceError, match="layer type"):
        bad = HybridConfig.tiny_retention(1)
        bad.layer_types = ("softmax",)
        HybridForCausalLM(bad)


@pytest.mark.parametrize("length", [19, 8])
def test_retention_forward_is_the_reference(length):
    cfg, model, params, dims = build_retention()
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size,
                                               (2, length))
    got = model(jnp.asarray(tokens))
    for row in range(2):
        close(got[row], reference_logits(params, dims, tokens[row]))


def test_retention_arena_prefill_and_decode_are_the_reference():
    """Rows at different cursors (the rotary embedding reads each
    row's own), prompts that do not fill their bucket, slots used a
    second time on top of the first wave's states."""
    cfg, model, params, dims = build_retention()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    # no keys and values at all: the state is the whole arena
    assert dec.counters.state_bytes == {
        "kv": 0, "recurrent": 3 * SLOTS * 2 * 40 * (8 + 1) * 4}
    first, second = waves(cfg.vocab_size)
    check_wave(arena_logits(dec, model, first, 9), first, 9, params, dims)
    check_wave(arena_logits(dec, model, second, 6), second, 6, params,
               dims)


def test_a_bfloat16_retention_state_would_fail():
    cfg, model, params, dims = build_retention()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    first, _ = waves(cfg.vocab_size)
    rounded = lambda caches: [
        tuple(a.astype(jnp.bfloat16).astype(a.dtype) for a in c)
        for c in caches]
    got = arena_logits(dec, model, first, 9, round_state=rounded)
    with pytest.raises(AssertionError):
        check_wave(got, first, 9, params, dims)


def test_retention_served_tokens_and_the_small_norm_counter():
    """Seven requests over three slots through ``submit`` / ``run``;
    every served token is the reference's best. The step counts the
    denominators under ``10 eps``: a slot that has served nothing yet
    steps on a zero state with junk, whose own term keeps its
    denominator up, so the count is small against rows x heads x
    blocks x steps; and it rides the tokens' fetch."""
    cfg, model, params, dims = build_retention()
    dec = BatchedDecoder(model, slots=SLOTS, capacity=CAPACITY,
                         prompt_bucket=BUCKET)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 11, 8, 3, 17, 9, 1)]
    rids = [dec.submit(p, 7) for p in prompts]
    out = dec.run()
    for p, rid in zip(prompts, rids):
        full = np.concatenate([p, out[rid]])
        want = reference_logits(params, dims, full)[len(p) - 1:-1]
        took = want[np.arange(len(out[rid])), out[rid]]
        assert (want.max(-1) - took <= 1e-4 * want.std()).all()
    assert dec.counters.steps == dec.tick_count > 0
    assert dec.counters.expert_tokens is None
    small = int(dec.counters.sums["retention_small_norm"])
    assert 0 <= small < 0.05 * dec.counters.steps * SLOTS * 10 * 3
    assert all(np.isfinite(np.asarray(leaf)).all()
               for c in dec.caches for leaf in c)


def test_the_small_norm_counter_counts_a_zero_denominator():
    """A step whose keys are zero on a zero state: every (row, head,
    block) denominator is 0, the outputs are 0 and finite."""
    cfg, model, params, _ = build_retention()
    params = {k: (jnp.zeros_like(v) if k.endswith("k_proj.weight") else v)
              for k, v in params.items()}
    model.set_parameters(params)
    caches = model.init_cache(SLOTS, CAPACITY)
    logits, caches = model._step_logits_rows(
        jnp.arange(SLOTS), caches, jnp.arange(SLOTS))
    assert int(model.step_counters()["retention_small_norm"]) == (
        SLOTS * cfg.num_heads * 3)
    assert np.isfinite(np.asarray(logits)).all()
    assert not any(np.asarray(leaf).any() for c in caches for leaf in c)


@pytest.mark.parametrize("mode", [
    dict(pages=8, page_size=64), dict(prefix_cache=True),
    dict(kv_dtype="int8"), dict(prefill_chunk=8)])
def test_position_addressed_modes_are_refused_for_retention(mode):
    _, model, _, _ = build_retention()
    with pytest.raises(EnforceError, match="recurrent state"):
        BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8,
                       **mode)


def test_handoff_is_refused_for_retention():
    _, model, _, _ = build_retention()
    dec = BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8)
    with pytest.raises(EnforceError, match="recurrent state"):
        dec.prefill_export(np.arange(5))
