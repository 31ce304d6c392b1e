"""The plain reference against ``GPTForCausalLM`` at tiny sizes on the
CPU, float32 both sides: forward logits, the loss, one gradient.

Tolerance 2e-4 relative to the largest magnitude compared: both sides
are float32 with different operation orders (fused QKV layouts, the
program's chunked linear-CE against a plain log-softmax); observed gaps
are about 1e-6, and computing either side in bfloat16 gives about 1e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import decoder as F
from benchmark.harness import program, weights as W
from benchmark.reference import decoder_f32 as R
from benchmark.tests import tiny

TOL = 2e-4
flops = F       # the shape formulas live with the family


@pytest.fixture(scope="module")
def setup():
    cell = tiny.cell(tiny.TRAIN)
    dims = R.Dims.from_config(cell.config)
    model = program.build_model(F, cell.config, dims, 11, "float32", 64,
                                remat=False)
    w = W.make_all(11, F, dims, jnp.float32)
    ids = np.random.default_rng(0).integers(0, dims.vocab, (2, 48))
    return model, w, dims, jnp.asarray(ids, jnp.int32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


def test_weights_are_a_function_of_seed_name_and_index(setup):
    _, w, dims, _ = setup
    again = W.make_leaves(11, F.layer_shapes(dims, 1), jnp.float32,
                          F.leaf_rule)
    for k, v in again.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(w[k]))
    other = W.make_leaves(12, F.layer_shapes(dims, 1), jnp.float32,
                          F.leaf_rule)
    k = "blocks.1.ffn.up.weight"
    assert not np.array_equal(np.asarray(other[k]), np.asarray(w[k]))
    assert abs(float(jnp.std(w[k])) - W.INIT_STD) < 0.002
    big = W.make_leaves(2**31 + 12345, {k: (8, 8)}, jnp.bfloat16,
                        F.leaf_rule)
    assert big[k].dtype == jnp.bfloat16


def test_forward_logits(setup):
    model, w, dims, ids = setup
    got = model.eval()(ids)
    ref = jnp.stack([R.logits(r, w, dims) for r in ids])
    assert rel(got, ref) < TOL


def test_layerwise_logits_match_whole_forward(setup):
    _, w, dims, ids = setup
    pos = jnp.asarray([[5, 9, 47], [0, 1, 2]], jnp.int32)
    lw = R.layerwise_logits(
        ids, pos, dims, "f32",
        get=lambda shapes: W.make_leaves(11, shapes, jnp.float32,
                                         F.leaf_rule),
        shapes_of_layer=lambda i: F.layer_shapes(dims, i),
        top_shapes=F.top_shapes(dims))
    ref = jnp.stack([R.logits(r, w, dims)[p] for r, p in zip(ids, pos)])
    assert rel(lw, ref) < TOL


def test_loss_and_one_gradient(setup):
    model, w, dims, ids = setup

    def prog_loss(p):
        out, _ = model.train().functional_call(
            p, ids, buffers={}, rng=jax.random.key(0), training=True,
            method="forward_loss")
        return out

    lp, gp = jax.value_and_grad(prog_loss)(w)
    lr, gr = jax.value_and_grad(lambda p: R.loss(p, ids, dims))(w)
    assert abs(float(lp) - float(lr)) / float(lr) < TOL
    for k in ("lm_head", "blocks.0.self_attn.k_proj.weight",
              "embed.weight"):
        assert rel(gp[k], gr[k]) < 5 * TOL, k


def test_lower_precision_moves_the_logits(setup):
    _, w, dims, ids = setup
    ref = R.logits(ids[0], w, dims)
    assert rel(R.logits(ids[0], w, dims, "bf16"), ref) > 10 * TOL
    assert rel(R.logits(ids[0], w, dims, "fp8"), ref) > \
        rel(R.logits(ids[0], w, dims, "bf16"), ref)


def test_flops_against_a_hand_count():
    import json
    import os

    from benchmark.harness import runtime

    def dims_of(name):
        with open(os.path.join(runtime.ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            return R.Dims.from_config(json.load(f))

    d = dims_of("internlm2-1.8b")
    # wqkv 2048 x (2048 + 2 x 1024), wo 2048 x 2048, SwiGLU 3 x 2048 x 8192
    assert flops.layer_matmul_params(d) == (
        2048 * 4096 + 2048 * 2048 + 3 * 2048 * 8192) == 62_914_560
    assert flops.matmul_params(d) == 4 * 62_914_560 + 2048 * 92544
    # causal attention, one layer, 2048 tokens: 2 matmuls of
    # 16 heads x 128 over the 2048 x 2049 / 2 lower triangle, forward
    tri = 2048 * 2049 // 2
    assert flops.attention_flops(d, 2048, False) == 2 * 2 * 2048 * tri
    assert flops.attention_flops(d, 2048, True) == 4 * 2 * 2048 * tri
    per_token = flops.train_flops_per_token(d, 2048)
    assert abs(per_token - 2.747e9) / 2.747e9 < 2e-3
    # q, o: 2048 x 2048 each; k, v: 2048 x 1024 each; bf16
    assert flops.attention_bytes(d, 2048, 2, False) == 2 * (
        2 * 2048 * 2048 + 2 * 2048 * 1024)
    m = dims_of("mistral-7b-v0.1")
    assert flops.layer_matmul_params(m) == (
        4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336) == 218_103_808
    # one tick, one layer, 16 slots x 500 live positions, bf16 KV
    assert flops.decode_attention_bytes(m, 8000, 2) == 2 * 1024 * 8000 * 2
    assert flops.decode_attention_flops(m, 8000) == 4 * 4096 * 8000
    assert flops._attended(4096, 1024) == 1024 * 1025 // 2 + 3072 * 1024
