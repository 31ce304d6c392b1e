"""The block-level scopes of ``paddle_tpu.telemetry.scopes`` in the
programs that run on the chip.

A scope is metadata of the lowered program: a component of every
operation's ``op_name``, which a profiler trace keeps and
``benchmark/harness/scope_table.py`` splits a step's device time by.
Pinned here, on tiny programs compiled for the CPU: each program holds
the scopes it should and no other of the list, **every ``dot_general``
lies under a listed scope** (a product nobody names is a matmul's worth
of a step that the table can only call "unscoped"), a train step with
remat shows its blocks in all three passes, and a name off the list is
refused where it is entered.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from benchmark.harness import scope_table
from paddle_tpu.core import EnforceError
from paddle_tpu.models import gpt as G
from paddle_tpu.models import hybrid as H
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.telemetry import scopes

LISTED = tuple(scopes.SCOPES)
TRUNK = {"embed", "head"}
EXPERTS = {"moe_route", "moe_experts", "moe_shared"}
# program -> the scopes of the list it holds
HOLDS = {
    "gpt_train": TRUNK | {"attn", "mlp", "linear_ce", "optimizer"},
    # under mixed_bf16 the declared leaves are cast once on entry
    "gpt_train_mixed": TRUNK | {"attn", "mlp", "linear_ce", "optimizer",
                                "weight_cast"},
    "gpt_decode": TRUNK | {"attn", "mlp"},
    "hybrid_decode": TRUNK | EXPERTS | {"attn", "ssm_step"},
    "retention_decode": TRUNK | {"retention_step", "mlp"},
    "latent_decode": TRUNK | EXPERTS | {"mla_decode", "mhc_mix", "mlp"},
    # a latent block trained through the hybrid shell: its mixer runs
    # under ``attn`` (``mla_prefill`` inside it, so never the outermost),
    # the routers' bias rule after the blocks
    "latent_train_mixed": TRUNK | EXPERTS | {
        "attn", "mlp", "linear_ce", "optimizer", "weight_cast",
        "moe_bias_update"},
}


def _latent_model():
    return H.HybridForCausalLM(dataclasses.replace(
        H.HybridConfig.tiny_latent(3), hc_mult=1, rope_yarn=None,
        q_lora_rank=None, experts_held=(0, 4), remat=True,
        router_bias_update_rate=0.01))


def _gpt_train(amp=None, build=None):
    pt.seed(0)
    model = build() if build else G.GPTForCausalLM(
        dataclasses.replace(G.GPTConfig.tiny(), remat=True))

    def loss_builder(params, buffers, rng, batch):
        loss, new_buffers = model.functional_call(
            params, batch, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    trainer = pt.parallel.Trainer(
        model, pt.optimizer.Adam(learning_rate=1e-3), loss_builder, amp=amp)
    return trainer.lower_step(jnp.zeros((2, 16), jnp.int32))


def _decode(model, **kw):
    pt.seed(0)
    return BatchedDecoder(model.eval(), **kw).lower_step()


def _hybrid_decode(cfg):
    return _decode(H.HybridForCausalLM(cfg), slots=3, capacity=64,
                   prompt_bucket=8)


LOWER = {
    "gpt_train": _gpt_train,
    "gpt_train_mixed": lambda: _gpt_train("mixed_bf16"),
    "gpt_decode": lambda: _decode(G.GPTForCausalLM(G.GPTConfig.tiny()),
                                  slots=2, capacity=128),
    "hybrid_decode": lambda: _hybrid_decode(H.HybridConfig.tiny(1)),
    "retention_decode": lambda: _hybrid_decode(
        H.HybridConfig.tiny_retention(3)),
    "latent_decode": lambda: _hybrid_decode(H.HybridConfig.tiny_latent(3)),
    "latent_train_mixed": lambda: _gpt_train("mixed_bf16", _latent_model),
}


METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


@functools.lru_cache(maxsize=None)
def op_names(program):
    """The distinct ``op_name``s of the program's compiled text. The
    persistent compile cache keys a program WITHOUT its metadata, so an
    executable found there carries the names of whichever tree compiled
    it first: this compile asks for a key that holds them."""
    lowered = LOWER[program]()
    keep = getattr(jax.config, METADATA_IN_KEY)
    jax.config.update(METADATA_IN_KEY, True)
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update(METADATA_IN_KEY, keep)
    return frozenset(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("program", sorted(HOLDS))
def test_the_program_holds_its_scopes_and_no_other(program):
    seen = {scope_table.place(op, LISTED)[0] for op in op_names(program)}
    assert seen - {None} == HOLDS[program]


@pytest.mark.parametrize("program", sorted(HOLDS))
def test_every_dot_general_lies_under_a_listed_scope(program):
    dots = [op for op in op_names(program)
            if scope_table.components(op)[-1:] == ["dot_general"]]
    assert dots, "the compiled text names no dot_general"
    assert [op for op in dots
            if scope_table.place(op, LISTED)[0] is None] == []


@pytest.mark.parametrize("program", ["gpt_train", "gpt_train_mixed",
                                     "latent_train_mixed"])
def test_a_train_step_with_remat_shows_its_blocks_in_three_passes(program):
    passes = {}
    for op in op_names(program):
        scope, which = scope_table.place(op, LISTED)
        passes.setdefault(scope, set()).add(which)
    blocks = ("attn", "mlp")
    if program == "latent_train_mixed":
        blocks += ("moe_experts", "moe_shared")
        # the router's top-k has no transpose worth an operation
        assert passes["moe_route"] >= {"forward", "recompute"}
        # the rule runs once, after the blocks, outside the checkpoint
        assert passes["moe_bias_update"] == {"forward"}
    for block in blocks:
        assert passes[block] == {"forward", "recompute", "backward"}
    assert passes["linear_ce"] == {"forward", "backward"}
    assert passes["optimizer"] == {"forward"}
    if program != "gpt_train":
        # the gradient's convert back fuses into whatever reads it
        assert "forward" in passes["weight_cast"]


def test_weight_cast_is_on_the_list_with_its_line():
    assert LISTED[-1] == "weight_cast" and len(LISTED) == 23
    assert "functional_call" in scopes.SCOPES["weight_cast"]


def test_a_name_off_the_list_is_refused():
    with pytest.raises(EnforceError, match="nonesuch"):
        scopes.scope("nonesuch")
    with scopes.scope("mlp"):      # a listed one is a context manager
        pass


def test_no_scope_is_entered_by_a_literal_outside_the_list():
    """``jax.named_scope`` is called in ``telemetry/scopes.py`` and in
    the Pallas kernels' ``_named_call`` (a kernel's name, not a block
    scope) and nowhere else under ``paddle_tpu/``."""
    root = os.path.dirname(os.path.abspath(pt.__file__))
    found = []
    for folder, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                for no, line in enumerate(f, 1):
                    if re.search(r"\bnamed_scope\(", line) \
                            and not line.lstrip().startswith(("#", '"')):
                        found.append(f"{os.path.relpath(path, root)}:{no}")
    assert [hit for hit in found if not hit.startswith((
        "telemetry/scopes.py:", "ops/pallas/flash_attention.py:"))] == []
