"""``remat=True`` keeps a block's flash kernel: the forward rule of
``ops/pallas/flash_attention.py`` names the two residuals its backward
kernels take (``o`` and ``lse``, ``REMAT_NAMES``) and the model shells
pass ``nn.remat_policy()`` to their ``jax.checkpoint``, so the backward
pass of a block recomputes everything but the kernel.

On the CPU, the repo's own kernels interpreted under
``ops.attention.force_flash`` and every function traced fresh:

(a) ``jax.grad`` of ``forward_loss``, lowered for the TPU, holds each of
    the three kernels once a layer, remat on or off (twice the forward
    where the policy is taken away), for the dense shell (GQA, heads of
    64) and the hybrid shell's latent mixer (through
    ``ops.latent_attention._flash_padded``: scores of 24 as 128, values
    of 16 as 128), and loss and every gradient are equal bit for bit;
(b) what a rematted block keeps is its input, ``o`` and ``lse`` and
    nothing else of their size, so a policy that keeps more fails here
    and not at a memory limit;
(c) outside any ``jax.checkpoint`` the names are no operation: the call
    lowers to the text it lowers to without them;
(d) ``nn.TransformerEncoder`` follows the same rule under both of its
    ``remat_policy`` values.
"""

import dataclasses
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.hybrid import HybridConfig, HybridForCausalLM
from paddle_tpu.ops import attention as A
from paddle_tpu.ops import latent_attention as LA

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
# the forward and the ONE backward kernel (dq beside dk and dv since PR 54;
# ``pt_flash_dq`` runs only past the rule ``bwd_is_fused``: never here)
KERNELS = ("pt_flash_fwd", "pt_flash_dkdv")
ROWS, SEQ, LAYERS = 2, 128, 2


def gpt(remat):
    """The dense shell: hidden 256, 4 query / 2 key-value heads of 64."""
    return GPTForCausalLM(dataclasses.replace(
        GPTConfig.tiny(), hidden_size=256, num_layers=LAYERS, remat=remat))


def latent(remat):
    """The hybrid shell over latent blocks (1 dense, 1 with experts):
    4 heads of 16 + 8 (scores) / 16 (values), the plain residual path."""
    return HybridForCausalLM(dataclasses.replace(
        HybridConfig.tiny_latent(LAYERS), hc_mult=1, rope_yarn=None,
        remat=remat))


MODELS = {"gpt_gqa": gpt, "latent": latent}


@pytest.fixture(autouse=True)
def flash_on_the_cpu(monkeypatch):
    """The kernels interpreted, and the latent prefill's static blocks
    cut to the test's length (a call takes ``_flash_padded`` where the
    query block divides the sequence)."""
    monkeypatch.setattr(LA, "FLASH_BLOCK_Q", SEQ)
    monkeypatch.setattr(LA, "FLASH_BLOCK_K", SEQ)
    with A.force_flash():
        yield


def build(make, remat, seed=0):
    pt.seed(seed)
    return make(remat)


def batch(vocab=256):
    return jnp.asarray(np.random.default_rng(3).integers(
        0, vocab, (ROWS, SEQ)), jnp.int32)


def loss_of(model, ids):
    buffers = model.named_buffers()

    def lf(p):
        return model.functional_call(p, ids, buffers=buffers, training=True,
                                     method="forward_loss")[0]

    return lf


def kernel_counts(fn, *args):
    """How often each kernel is called in ``fn`` lowered for the TPU
    (``jax.export``: the Mosaic calls the chip would get, no chip
    needed; interpreted, a kernel is inlined and leaves no name)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FA, "_use_interpret", lambda: False)
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *args).mlir_module()
    assert "tpu_custom_call" in text
    assert 'kernel_name = "pt_flash_dq"' not in text
    return {k: text.count(f'kernel_name = "{k}"') for k in KERNELS}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_each_kernel_runs_once_a_layer_and_no_bit_moves(name, monkeypatch):
    ids, got = batch(), {}
    for remat in (False, True):
        model = build(MODELS[name], remat)
        params = model.named_parameters()
        # a function traced once keeps the body it was traced with
        step = lambda: jax.value_and_grad(loss_of(model, ids))
        counts = kernel_counts(step(), params)
        assert counts == dict.fromkeys(KERNELS, LAYERS), (remat, counts)
        got[remat] = jax.jit(step())(params)
    # what the policy is for: without it the forward kernel runs twice
    monkeypatch.setattr(nn, "remat_policy", lambda: None)
    assert kernel_counts(step(), params) == dict(
        dict.fromkeys(KERNELS, LAYERS), pt_flash_fwd=2 * LAYERS)
    (loss, grads), (loss_r, grads_r) = got[False], got[True]
    assert float(loss) == float(loss_r)
    assert set(grads) == set(grads_r) and grads
    for leaf in grads:
        np.testing.assert_array_equal(grads[leaf], grads_r[leaf],
                                      err_msg=leaf)


@pytest.mark.parametrize("policy", [None, "dots"])
def test_the_shared_encoder_follows_the_same_rule(policy):
    """``nn.TransformerEncoder`` (BERT's and ViT's trunk) passes the same
    policy: under either of its ``remat_policy`` values a block's
    backward pass holds no second forward kernel."""
    pt.seed(0)
    enc = nn.TransformerEncoder(LAYERS, 256, 4, 512, dropout=0.0,
                                remat=True, remat_policy=policy)
    x = jnp.ones((ROWS, SEQ, 256), jnp.float32)

    def loss(params):
        return jnp.sum(enc.functional_call(params, x, training=True)[0])

    assert kernel_counts(jax.grad(loss), enc.named_parameters()) \
        == dict.fromkeys(KERNELS, LAYERS)


def residuals(model, run, x, policy):
    """What ``jax.checkpoint(run, policy=policy)`` keeps for the backward
    pass, one line a residual, the arguments (weights and ``x``) left
    out."""
    import contextlib
    import io

    from jax.ad_checkpoint import print_saved_residuals

    def block(params, x):
        with nn.layer.inject_state((model, params)):
            out = jax.checkpoint(run, policy=policy)(x)
        return jnp.sum(out[0] if isinstance(out, tuple) else out)

    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        print_saved_residuals(block, model.named_parameters(), x)
    lines = said.getvalue().strip().splitlines()
    assert any("from the argument x" in ln for ln in lines), lines
    return [ln for ln in lines if "from the argument" not in ln]


# the kernel's output as the block's call hands it over: (rows, seq,
# heads, value width as the kernel sees it)
O_SHAPE = {"gpt_gqa": f"f32[{ROWS},{SEQ},4,64]",
           "latent": f"f32[{ROWS},{SEQ},4,128]"}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_rematted_block_keeps_its_input_o_and_lse(name):
    """Besides its arguments a block keeps two arrays, both made by
    ``flash_attention``: ``lse`` under its name and ``o`` (which jax
    hands on through a ``reduce_precision``, its guard around a residual
    that is also a result). With no policy it keeps neither: the names
    alone save nothing."""
    model = build(MODELS[name], True)
    blk = model.blocks[LAYERS - 1]
    run = blk.forward_counted if name == "latent" else blk
    x = jnp.ones((ROWS, SEQ, model.cfg.hidden_size), jnp.float32)
    kept = residuals(model, run, x, nn.remat_policy())
    assert len(kept) == 2 and all("flash_attention" in ln for ln in kept), \
        kept
    lse, = [ln for ln in kept if f"named '{FA.REMAT_LSE}'" in ln]
    o, = [ln for ln in kept if ln is not lse]
    assert o.startswith(O_SHAPE[name] + " "), o
    assert lse.startswith("f32[") and lse.split("]")[0].endswith(
        f",{SEQ}"), lse
    assert not [ln for ln in residuals(model, run, x, None)
                if "flash_attention" in ln or "named" in ln]


@pytest.mark.parametrize("gqa", [False, True])
def test_outside_a_checkpoint_the_names_are_no_operation(gqa, monkeypatch):
    q = jnp.ones((ROWS, SEQ, 4, 64), jnp.float32)
    k = v = jnp.ones((ROWS, SEQ, 2 if gqa else 4, 64), jnp.float32)

    # fresh functions at every use: a traced one keeps its body
    f = lambda: lambda q, k, v: FA.flash_attention(q, k, v, causal=True)
    g = lambda: jax.grad(lambda q, k, v: jnp.sum(f()(q, k, v)), (0, 1, 2))

    def lowered():
        # a private function's name ends in a counter of the lowering's
        # (``@floor_divide_63``): the text is compared without them
        return [re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1",
                       jax.jit(fn).lower(q, k, v).as_text())
                for fn in (f(), g())]

    named = lowered()
    assert kernel_counts(f(), q, k, v) == dict(
        dict.fromkeys(KERNELS, 0), pt_flash_fwd=1)
    assert kernel_counts(g(), q, k, v) == dict.fromkeys(KERNELS, 1)
    monkeypatch.setattr(FA, "_checkpoint_name", lambda x, name: x)
    assert lowered() == named
