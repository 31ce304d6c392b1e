"""Training through the hybrid shell (``models/hybrid.py``): latent
attention and sigmoid-routed experts go backward under the one
``parallel.Trainer``, held to the plain reference
``benchmark/reference/latent_moe_train_f32.py`` (which imports nothing
of the program) at a tiny size in float32 on the CPU with seeded
weights:

(a) ``forward_loss`` and every leaf's gradient, for the direct and the
    low-rank query, both expert bodies, a share of the experts and all
    of them, remat on and off (equal bit for bit), the fused linear
    cross-entropy against ``models.gpt.loss_fn``;
(b) the shares add up going backward too;
(c) the bias rule against the reference's ``bias_update`` over whole
    batches for three steps of the ``Trainer``, Adam leaving
    ``score_bias`` where it is, a served model reading the moved bias;
(d) prefill then decode through the arena with the direct query against
    the reference's full forward, logits compared.

Tolerances: both sides are float32 and differ in the order of sums only
(grouped or dense products against a masked loop, a chunked head
against whole logits, jax.numpy attention against blocks of queries):
some tens of roundings, so 2e-5 of a leaf's gradient norm and 1e-5 of
the loss. A pick decided the other way would move an expert leaf's
gradient by several percent; the tiny sizes' margins are far above
float32's noise."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.reference import latent_moe_train_f32 as R
from paddle_tpu import nn, optimizer, parallel
from paddle_tpu.models.gpt import loss_fn
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.nn import moe
from paddle_tpu.nn.layer import inject_state
from paddle_tpu.serving import BatchedDecoder

GAMMA = 0.01


def config(q_rank=0, held=(0, 4), remat=False, gamma=GAMMA, layers=3):
    """1 dense + 2 expert latent blocks over the plain residual path:
    hidden 64, 4 heads of 16 + 8 (scores) / 16 (values), 16 experts of
    width 24, 4 a token, two shared experts as one MLP of 48."""
    return dataclasses.replace(
        HybridConfig.tiny_latent(layers), hc_mult=1, rope_yarn=None,
        q_lora_rank=q_rank, shared_width=48, experts_held=held,
        routed_scaling_factor=2.448, router_bias_update_rate=gamma,
        remat=remat)


def dims_of(cfg: HybridConfig, held=None) -> R.Dims:
    return R.Dims(
        hidden=cfg.hidden_size, layers=len(cfg.layer_types),
        dense_layers=cfg.channel_mixes().count("mlp"), heads=cfg.num_heads,
        q_rank=cfg.q_lora_rank or 0, kv_rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, ffn=cfg.mlp_width,
        expert_width=cfg.expert_width, shared_width=cfg.shared_width,
        experts=cfg.num_experts, top_k=cfg.experts_per_token,
        held=held or cfg.experts_held or (0, cfg.num_experts),
        scaling=cfg.routed_scaling_factor, vocab=cfg.vocab_size,
        theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
        gamma=cfg.router_bias_update_rate)


def build(cfg, seed=0):
    """The model with every matrix and the selection bias seeded; the
    norms' scales off 1 so that a dropped scale would show."""
    pt.seed(seed)
    model = HybridForCausalLM(cfg)
    key = jax.random.key(seed + 100)
    leaves = {}
    for i, (name, v) in enumerate(sorted(model.named_parameters().items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("score_bias"):
            leaves[name] = 0.05 * jax.random.normal(k, v.shape, v.dtype)
        elif v.ndim == 1:
            leaves[name] = 1.0 + 0.1 * jax.random.normal(k, v.shape, v.dtype)
        else:
            leaves[name] = v.shape[-2] ** -0.5 * jax.random.normal(
                k, v.shape, v.dtype)
    model.set_parameters(leaves)
    return model


def batch(rows=2, seq=16, seed=3, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (rows, seq)), jnp.int32)


def program_loss_and_grads(model, ids):
    params, buffers = model.named_parameters(), model.named_buffers()

    def lf(p):
        return model.functional_call(p, ids, buffers=buffers, training=True,
                                     method="forward_loss")

    (loss, new_buffers), grads = jax.jit(
        jax.value_and_grad(lf, has_aux=True))(params)
    return loss, grads, new_buffers


def reference_loss_and_grads(model, ids, dims):
    w = dict(model.named_parameters())
    return jax.jit(jax.value_and_grad(
        lambda p: R.loss(p, ids, dims, "f32", remat=False)))(w)


def force(monkeypatch, dense: bool):
    """One expert body, whatever the rule says (``tests/
    test_dropless_moe.py::force``)."""
    monkeypatch.setattr(moe, "streams_densely", lambda *a: dense)


# --------------------------------------------------------------------------
# (a) the loss and every leaf's gradient
# --------------------------------------------------------------------------

@pytest.mark.parametrize("q_rank", [0, 24], ids=["direct", "low_rank"])
@pytest.mark.parametrize("dense", [False, True], ids=["grouped", "dense"])
@pytest.mark.parametrize("held", [(4, 4), (0, 16)], ids=["share", "whole"])
def test_loss_and_every_gradient_are_the_references(monkeypatch, q_rank,
                                                    dense, held):
    force(monkeypatch, dense)
    cfg = config(q_rank=q_rank, held=held)
    model, ids = build(cfg), batch()
    loss, grads, _ = program_loss_and_grads(model, ids)
    want, want_g = reference_loss_and_grads(model, ids, dims_of(cfg))
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert set(grads) == set(want_g)
    for name in sorted(grads):
        g, r = np.asarray(grads[name]), np.asarray(want_g[name])
        scale = max(float(np.linalg.norm(r)), 1e-6)
        assert float(np.linalg.norm(g - r)) < 2e-5 * scale + 1e-9, name
        if name.endswith("score_bias"):     # it selects, it does not weigh
            assert not g.any() and not r.any()


def test_the_grouped_body_in_parts_goes_backward(monkeypatch):
    """``grouped_parts``' ``lax.map`` under ``jax.checkpoint``: the
    parts' gradients are the whole call's."""
    force(monkeypatch, False)
    cfg = config(remat=True)
    model, ids = build(cfg), batch()
    whole = program_loss_and_grads(model, ids)
    monkeypatch.setattr(moe, "GROUPED_MAX_BYTES", 8 * 4 * 64 * 4)
    assert moe.grouped_parts(32, 4, 64) == 4
    parts = program_loss_and_grads(model, ids)
    assert abs(float(whole[0]) - float(parts[0])) < 1e-6
    for name in whole[1]:
        np.testing.assert_allclose(parts[1][name], whole[1][name],
                                   rtol=1e-4, atol=1e-7, err_msg=name)
    for name in whole[2]:       # the load is counted part by part
        np.testing.assert_array_equal(parts[2][name], whole[2][name])


def test_remat_changes_no_bit():
    ids = batch()
    plain = program_loss_and_grads(build(config(remat=False)), ids)
    remat = program_loss_and_grads(build(config(remat=True)), ids)
    assert float(plain[0]) == float(remat[0])
    for name in plain[1]:
        np.testing.assert_array_equal(plain[1][name], remat[1][name],
                                      err_msg=name)
    for name in plain[2]:
        np.testing.assert_array_equal(plain[2][name], remat[2][name])


@pytest.mark.parametrize("tied", [False, True])
def test_the_fused_head_is_the_plain_loss(tied):
    """``forward_loss`` never makes the logits; it equals ``loss_fn``
    over ``forward``'s, with labels and holes handed in too, under a
    logits divisor and a tied head as well."""
    cfg = dataclasses.replace(config(), tie_embeddings=tied,
                              logits_scaling=4.0)
    model, ids = build(cfg).eval(), batch()
    shifted = jnp.concatenate(
        [ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)], axis=1)
    want = loss_fn(model(ids), shifted)
    assert abs(float(model.forward_loss(ids)) - float(want)) < 1e-5
    labels = shifted.at[0, 3].set(-100)
    want = loss_fn(model(ids), labels)
    got = model.forward_loss(ids, labels, vocab_chunk=96)
    assert abs(float(got) - float(want)) < 1e-5


def test_a_training_call_fills_the_counters():
    cfg = config()
    model, ids = build(cfg), batch()
    model.forward_loss(ids)
    got = model.step_counters()
    assert set(got) == {"expert_tokens", "expert_dense_layers",
                        "expert_load"}
    assert got["expert_load"].shape == (2, 16)
    assert int(got["expert_load"].sum()) == 2 * ids.size * 4
    first, held = cfg.experts_held
    np.testing.assert_array_equal(
        got["expert_tokens"],
        got["expert_load"][:, first:first + held].sum(axis=0))
    assert model.expert_layers(ids.size) == (
        2, int(got["expert_dense_layers"]))


# --------------------------------------------------------------------------
# (b) the shares add up going backward
# --------------------------------------------------------------------------

def test_the_shares_gradients_add_up_to_the_uncut_layers():
    """Over the 8 shares of one expert layer (16 experts, 2 a share):
    the gradient of sum(cotangent . routed part) with respect to the
    layer's input, summed over the shares, with the shared expert's
    counted once, is the uncut reference's."""
    cfg = config(held=(0, 16))
    whole = build(cfg)
    blk = whole.blocks[1]
    dims = dims_of(cfg)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    w = {k: v for k, v in whole.named_parameters().items()
         if k.startswith("blocks.1.")}

    def uncut(x):
        y = (R.routed_experts(x, w, "blocks.1.moe.", dims, "f32")
             + R.swiglu(x, w["blocks.1.shared.gate.weight"],
                        w["blocks.1.shared.up.weight"],
                        w["blocks.1.shared.down.weight"], "f32"))
        return jnp.sum(y * ct)

    want = jax.grad(uncut)(u)
    total = jax.grad(lambda x: jnp.sum(blk.shared(x) * ct))(u)
    for first in range(0, 16, 2):
        share = nn.DroplessMoE(64, 24, 16, 4, experts_held=(first, 2),
                               routing="sigmoid_noaux_tc", scaling=2.448)
        share.set_parameters({
            "router.weight": blk.moe.router.weight,
            "score_bias": blk.moe.score_bias,
            **{n: getattr(blk.moe, n)[first:first + 2]
               for n in ("w_gate", "w_up", "w_down")}})
        total = total + jax.grad(lambda x: jnp.sum(share(x) * ct))(u)
    assert float(jnp.linalg.norm(total - want)) < 2e-5 * float(
        jnp.linalg.norm(want))


# --------------------------------------------------------------------------
# (c) the bias rule
# --------------------------------------------------------------------------

def trainer_of(model, amp=None, lr=1e-2):
    def loss_builder(params, buffers, rng, ids):
        loss, new_buffers = model.functional_call(
            params, ids, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    return parallel.Trainer(model, optimizer.Adam(lr), loss_builder,
                            mesh=mesh, amp=amp)


def test_the_bias_rule_follows_the_reference_for_three_steps():
    """Each step routes by score_bias + what the rule has moved it by so
    far, counts the whole batch's picks over all 16 outputs and moves
    each by gamma towards the mean load: the reference's ``picks_of`` +
    ``bias_update`` on the trainer's own parameters of that step. Adam
    never moves ``score_bias``."""
    cfg = config(remat=True)
    model = build(cfg)
    dims = dims_of(cfg)
    start = {k: np.asarray(v) for k, v in model.named_parameters().items()
             if k.endswith("score_bias")}
    tr = trainer_of(model)
    want = {i: jnp.asarray(start[f"blocks.{i}.moe.score_bias"])
            for i in (1, 2)}
    for step in range(3):
        ids = batch(rows=4, seed=20 + step)
        w = {k: jnp.asarray(v) for k, v in tr.params.items()}
        for i in (1, 2):        # the reference routes by the moved bias
            w[f"blocks.{i}.moe.score_bias"] = want[i]
        picks = {i: R.picks_of(w, ids, i, dims) for i in (1, 2)}
        tr.train_step(ids)
        for i in (1, 2):
            want[i] = R.bias_update(want[i], picks[i], GAMMA)
            p = f"blocks.{i}.moe."
            np.testing.assert_array_equal(
                tr.buffers[p + "expert_load"],
                np.bincount(np.asarray(picks[i]).reshape(-1), minlength=16))
            np.testing.assert_allclose(
                start[p + "score_bias"] + np.asarray(
                    tr.buffers[p + "bias_shift"]), want[i], atol=1e-7)
            np.testing.assert_array_equal(tr.params[p + "score_bias"],
                                          start[p + "score_bias"])
    # some output moved all three steps one way, and the moves differ
    shift = np.asarray(tr.buffers["blocks.1.moe.bias_shift"])
    assert np.isclose(np.abs(shift).max(), 3 * GAMMA)
    assert len(np.unique(np.round(shift / GAMMA))) > 1


@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_the_second_step_is_not_traced_again(opt):
    """A trainer's first step and every later one are ONE program: the
    leaves the optimizer makes itself (its step counter) are placed on
    the mesh like the moments, so the
    state the first step is given has the type of the state it hands
    back. Left on the default device the second step was traced,
    lowered and compiled (or loaded) again: half a training cell's
    set-up."""
    traced = []
    model = build(config(layers=2))

    def loss_builder(params, buffers, rng, ids):
        traced.append(1)
        loss, new_buffers = model.functional_call(
            params, ids, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    made = {"adam": optimizer.Adam, "sgd": optimizer.SGD}
    tr = parallel.Trainer(
        model, made[opt](1e-2), loss_builder,
        mesh=pt.build_mesh(dp=1, devices=jax.devices()[:1]))
    for step in range(3):
        tr.train_step(batch(seed=step))
    assert len(traced) == 1


def test_the_trainers_telemetry_reads_the_routers_counters():
    """``Trainer.router_telemetry``: the held experts' pairs, the peak
    load over the mean over all outputs and the bias's largest
    magnitude, a layer, from the buffers the step left (no fetch inside
    the step); with telemetry on, in the trainer's gauges too."""
    from paddle_tpu import telemetry

    cfg = config(held=(4, 4))
    tr = trainer_of(build(cfg))
    assert tr.router_telemetry()["blocks.1.moe"]["load_peak"] == 0.0
    tr.train_step(batch(rows=4))
    telemetry.enable()
    try:
        got = tr.router_telemetry()
        reg = telemetry.registry()
        for name in ("blocks.1.moe", "blocks.2.moe"):
            load = np.asarray(tr.buffers[name + ".expert_load"])
            np.testing.assert_array_equal(got[name]["pairs"], load[4:8])
            assert got[name]["load_peak"] == pytest.approx(
                load.max() / load.mean())
            bias = np.asarray(tr.params[name + ".score_bias"]) + np.asarray(
                tr.buffers[name + ".bias_shift"])
            assert got[name]["bias_max"] == pytest.approx(
                np.abs(bias).max())
            assert reg.get("pt_trainer_expert_pairs", {
                "layer": name, "expert": "5"}).value == load[5]
            assert reg.get("pt_trainer_expert_load_peak_ratio", {
                "layer": name}).value == pytest.approx(
                    got[name]["load_peak"])
    finally:
        telemetry.disable()
    assert trainer_of(build(config(gamma=0.0))).router_telemetry() == {}


@pytest.mark.parametrize("favoured,windows", [
    (None, 1), (range(4, 8), 4), (range(8, 12), 0)],
    ids=["even-one-window", "every-pair-held-all-windows",
         "no-pair-held-no-window"])
def test_the_trainers_telemetry_counts_the_windows(monkeypatch, favoured,
                                                   windows):
    """``router_telemetry()["windows"]``: the windows of held pairs the
    grouped body ran a layer in the last step, host arithmetic on the
    pairs the step left in its buffers (``nn.DroplessMoE.windows_run``).
    64 tokens x 4 picks over 16 outputs of which 4 are held, windows of
    80 pairs: 1 under the seeded routing; all 4 where a selection bias
    of 10 sends every pick to the held experts; 0 where it sends every
    pick elsewhere. In the trainer's gauge too."""
    from paddle_tpu import telemetry

    force(monkeypatch, False)
    monkeypatch.setattr(moe, "WINDOW_TILE", 8)
    assert moe.window_rows(64, 4, 16, 4) == 80
    model = build(config(held=(4, 4)))
    if favoured is not None:
        model.set_parameters({
            f"blocks.{i}.moe.score_bias": jnp.zeros((16,)).at[
                jnp.asarray(favoured)].set(10.0) for i in (1, 2)})
    tr = trainer_of(model)
    tr.train_step(batch(rows=4))
    telemetry.enable()
    try:
        got = tr.router_telemetry()
        for name in ("blocks.1.moe", "blocks.2.moe"):
            held_pairs = int(got[name]["pairs"].sum())
            assert held_pairs == {None: held_pairs, 4: 256, 0: 0}[
                None if favoured is None else windows]
            assert got[name]["windows"] == windows == -(-held_pairs // 80)
            assert telemetry.registry().get("pt_trainer_expert_windows", {
                "layer": name}).value == windows
    finally:
        telemetry.disable()


def test_gamma_zero_is_todays_layer():
    cfg = config(gamma=0.0)
    model = build(cfg)
    assert not model.named_buffers()
    tr = trainer_of(model)
    before = np.asarray(tr.params["blocks.1.moe.score_bias"])
    tr.train_step(batch())
    assert not tr.buffers
    np.testing.assert_array_equal(tr.params["blocks.1.moe.score_bias"],
                                  before)


def arena_logits(dec, model, prompt, cont):
    """Prefill ``prompt`` into slot 0 with the decoder's own prefill
    program, then step it through the model entry the decode step calls,
    feeding ``cont`` (teacher forcing): the logits at positions
    len(prompt) - 1 .. len(prompt) - 1 + len(cont)."""
    plen = len(prompt)
    lb = dec._bucket_len(plen)
    padded = np.zeros((lb,), np.int32)
    padded[:plen] = prompt
    dec.caches, logits = dec._prefill_fn(lb)(
        dec._mstate, dec.caches, jnp.asarray(padded), plen, 0)
    out = [np.asarray(logits)]

    @jax.jit
    def step(mstate, caches, tok, t):
        with inject_state((model, *mstate)):
            return model._step_logits_rows(tok, caches, t)

    tok = np.zeros((dec.slots,), np.int32)
    t = np.zeros((dec.slots,), np.int32)
    for j, c in enumerate(cont):
        tok[0], t[0] = c, plen + j
        logits, dec.caches = step(dec._mstate, dec.caches,
                                  jnp.asarray(tok), jnp.asarray(t))
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def close(got, want, tol=1e-4):
    want = np.asarray(want)
    assert np.abs(np.asarray(got) - want).max() < tol * want.std()


def test_a_served_model_reads_the_moved_bias():
    """A bias the rule has moved changes what the arena serves, as the
    reference with ``score_bias + bias_shift`` for its bias says: the
    buffers are arguments of the serving programs."""
    cfg = config()
    model = build(cfg).eval()
    dims = dims_of(cfg)
    rng = np.random.default_rng(9)
    prompt, cont = (rng.integers(0, 256, n).astype(np.int32)
                    for n in (11, 5))
    shift = jnp.where(jnp.arange(16) % 2 == 0, -0.3, 0.3)
    model.set_buffers({f"blocks.{i}.moe.bias_shift": shift for i in (1, 2)})
    dec = BatchedDecoder(model, slots=2, capacity=32, prompt_bucket=8)
    got = arena_logits(dec, model, prompt, cont)
    w = dict(model.named_parameters())
    full = jnp.asarray(np.concatenate([prompt, cont]))
    still = R.logits(full, w, dims)[len(prompt) - 1:]
    for i in (1, 2):
        w[f"blocks.{i}.moe.score_bias"] = (
            w[f"blocks.{i}.moe.score_bias"] + shift)
    moved = R.logits(full, w, dims)[len(prompt) - 1:]
    close(got, moved)
    assert np.abs(got - np.asarray(still)).max() > 1e-2 * float(
        jnp.std(still))


def test_mixed_bf16_casts_the_experts_once():
    """Under ``amp="mixed_bf16"`` over float32 master weights the three
    expert tensors are on the cast-once list and the step runs; the
    router's weight, the bias and the norms stay as stored."""
    cfg = config(remat=True)
    model = build(cfg)
    names = model.compute_cast_names()
    for leaf in ("w_gate", "w_up", "w_down"):
        assert f"blocks.1.moe.{leaf}" in names
    assert "blocks.1.moe.score_bias" not in names
    assert "blocks.1.mixer.q_proj.weight" in names
    tr = trainer_of(model, amp="mixed_bf16", lr=1e-3)
    ids = batch()
    text = tr.lower_step(ids).as_text()
    assert "bf16" in text
    first = float(tr.train_step(ids)[0])
    again = float(tr.train_step(ids)[0])
    assert np.isfinite(first) and again < first
    assert tr.params["blocks.1.moe.w_gate"].dtype == jnp.float32


# --------------------------------------------------------------------------
# (d) the same model object still serves
# --------------------------------------------------------------------------

def test_prefill_then_decode_with_the_direct_query_matches_the_reference():
    """Through ``BatchedDecoder``'s own programs, a prompt that does not
    fill its bucket and one that straddles two: every position's logits
    against the reference's full forward over prompt + continuation."""
    cfg = config(gamma=0.0)
    model = build(cfg).eval()
    dims = dims_of(cfg)
    w = dict(model.named_parameters())
    dec = BatchedDecoder(model, slots=2, capacity=64, prompt_bucket=8)
    rng = np.random.default_rng(11)
    for plen in (5, 13):
        prompt, cont = (rng.integers(0, 256, n).astype(np.int32)
                        for n in (plen, 7))
        got = arena_logits(dec, model, prompt, cont)
        full = jnp.asarray(np.concatenate([prompt, cont]))
        close(got, R.logits(full, w, dims)[plen - 1:])
