"""The flash backward as ONE kernel against the pair it replaces.

``_bwd_call`` runs ``pt_flash_dkdv`` grown by ``ds k`` (dq accumulated in
VMEM over the whole query length) where the rule ``bwd_is_fused`` says the
accumulator fits, and the pair ``pt_flash_dq`` + ``pt_flash_dkdv``
otherwise. Both are the same sums: for a fixed query block the key blocks
arrive in ascending order either way and add in float32, so at equal
blocks the three gradients are BIT-equal, on every option that acts on the
score block. The rule is forced each way by the one thing it reads besides
the shapes, the ceiling (no argument selects a path). CPU, interpret mode.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

# name: (b, heads, kv_heads, tq, tk, d, e, block_q, block_k, dtype, options)
CASES = {
    "causal": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16, {}),
    "full": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16,
             {"causal": False}),
    "causal_window": (1, 2, 2, 512, 512, 32, 32, 64, 64, jnp.bfloat16,
                      {"window": 100}),
    "full_window": (1, 2, 2, 512, 512, 32, 32, 64, 64, jnp.bfloat16,
                    {"causal": False, "window": 70}),
    "key_padding": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16,
                    {"causal": False, "mask": True}),
    "segments": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16,
                 {"segs": True}),
    "dropout": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16,
                {"dropout_p": 0.2}),
    "gqa_4_2": (2, 4, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16, {}),
    "widths_24_16": (2, 2, 2, 256, 256, 24, 16, 64, 64, jnp.bfloat16, {}),
    "widths_192_128": (1, 2, 2, 384, 256, 192, 128, 128, 64, jnp.bfloat16,
                       {}),
    "offset": (2, 2, 2, 192, 384, 32, 32, 64, 64, jnp.bfloat16, {}),
    "passed_delta": (2, 2, 2, 256, 256, 32, 32, 64, 64, jnp.bfloat16,
                     {"causal": False, "delta": True}),
    "unequal_blocks": (1, 2, 2, 512, 512, 32, 32, 128, 64, jnp.bfloat16,
                       {}),
    "float32": (1, 2, 2, 256, 256, 32, 32, 64, 64, jnp.float32,
                {"mask": True, "dropout_p": 0.1}),
    "everything": (2, 4, 2, 256, 256, 24, 16, 64, 64, jnp.bfloat16,
                   {"mask": True, "segs": True, "dropout_p": 0.1,
                    "window": 90}),
}


def _operands(b, h, hkv, tq, tk, d, e, bq, bk, dtype, causal=True,
              window=None, mask=False, segs=False, dropout_p=0.0,
              delta=False):
    ks = jax.random.split(jax.random.PRNGKey(54), 7)
    rows = lambda key, heads, t, w: jax.random.normal(
        key, (b * heads, t, w), jnp.float32).astype(dtype)
    q, k, v = rows(ks[0], h, tq, d), rows(ks[1], hkv, tk, d), rows(
        ks[2], hkv, tk, e)
    do = rows(ks[3], h, tq, e)
    kvm = ((jax.random.uniform(ks[4], (b, 1, tk)) > 0.25).astype(jnp.float32)
           if mask else None)
    seg = jnp.sort(jax.random.randint(ks[5], (b, tq), 0, 3), axis=1).astype(
        jnp.int32) if segs else None
    qseg = None if seg is None else seg.reshape(b, tq, 1)
    kseg = None if seg is None else seg.reshape(b, 1, tk)
    seed = (jax.random.randint(ks[6], (1, b * h), -2 ** 31, 2 ** 31 - 1,
                               jnp.int32) if dropout_p else None)
    scale = d ** -0.5
    o, lse = FA._fwd_call(q, k, v, kvm, qseg, kseg, seed, h, hkv, causal,
                          window, scale, dropout_p, bq, bk, True)
    args = (q, k, v, kvm, qseg, kseg, seed, h, hkv, o, lse, do, causal,
            window, scale, dropout_p, bq, bk, True)
    kw = {}
    if delta:  # a ring hop's: rowsum(do * o) of the FINAL output, passed in
        kw["delta"] = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                              axis=-1, keepdims=True) * 1.25
    return args, kw


def _kernels(args, kw):
    text = str(jax.make_jaxpr(lambda: FA._bwd_call(*args, **kw))())
    return {n for n in ("pt_flash_dq", "pt_flash_dkdv") if n in text}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_backward_is_the_pair_bit_for_bit(case, monkeypatch):
    *shape, options = CASES[case]
    tq, bq, bk = shape[3], shape[7], shape[8]
    assert tq // bq > 2 and shape[4] // bk > 2  # more than two blocks a side
    args, kw = _operands(*shape, **options)
    assert _kernels(args, kw) == {"pt_flash_dkdv"}
    fused = FA._bwd_call(*args, **kw)
    monkeypatch.setattr(FA, "FUSED_BWD_VMEM_LIMIT", 0)
    assert _kernels(args, kw) == {"pt_flash_dq", "pt_flash_dkdv"}
    pair = FA._bwd_call(*args, **kw)
    for name, got, want in zip(("dq", "dk", "dv"), fused, pair):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
        assert np.abs(want).max() > 0, name
        # same terms in the same order, float32 sums: not one ulp apart
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_the_rule_by_shape_alone():
    """Fused at every call the cells and the block table make; the pair
    where dq's accumulator cannot live in VMEM. The table's blocks are
    read as committed, for the chip they were measured on."""
    from paddle_tpu.ops.pallas import tuning

    bf16 = jnp.bfloat16
    assert FA.bwd_is_fused(8192, 256, 128, 1024, 1024, bf16)    # kanana
    assert FA.bwd_is_fused(2048, 128, 128, 1024, 1024, bf16)    # dense
    assert FA.bwd_is_fused(2048, 128, 128, 512, 1024, jnp.float32)
    table = tuning._load()
    seen = 0
    for key, entry in table.items():
        if not key.startswith("flash_attention|") or "block_q_bwd" not in entry:
            continue
        t, d = entry["shape"][1], entry["shape"][4]
        e = entry["shape"][5] if len(entry["shape"]) > 5 else d
        dtype = jnp.float32 if key.endswith("|f32") else bf16
        assert FA.bwd_is_fused(t, d, e, entry["block_q_bwd"],
                               entry["block_k_bwd"], dtype), key
        seen += 1
    assert seen >= 5
    # what the ceiling is for: an accumulator of tq x d float32 beside
    # its output block; far past the table the pair takes over
    assert FA.fused_bwd_vmem_bytes(8192, 256, 128, 1024, 1024, bf16) \
        > 8192 * 256 * 4
    assert not FA.bwd_is_fused(65536, 256, 128, 1024, 1024, bf16)
    assert FA.bwd_is_fused(36864, 256, 128, 1024, 1024, bf16)  # the last
    assert not FA.bwd_is_fused(131072, 128, 128, 1024, 1024, bf16)
    # monotone in the length: one threshold, not islands
    fits = [FA.bwd_is_fused(t, 256, 128, 1024, 1024, bf16)
            for t in range(1024, 70000, 1024)]
    assert fits == sorted(fits, reverse=True)


def test_flash_attention_gradients_run_the_one_kernel():
    """Through the public entry, under ``jax.grad``: the forward kernel
    and the one backward kernel, no ``pt_flash_dq``."""
    q = jnp.ones((1, 256, 2, 32), jnp.bfloat16)

    def loss(q, k, v):
        return FA.flash_attention(q, k, v, causal=True, block_q=64,
                                  block_k=64).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert "pt_flash_fwd" in text and "pt_flash_dkdv" in text
    assert "pt_flash_dq" not in text
