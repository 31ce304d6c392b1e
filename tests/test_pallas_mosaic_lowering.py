"""Mosaic lowering gate for the Pallas kernels, runnable WITHOUT a TPU.

``jax.export`` with ``platforms=['tpu']`` runs the full Pallas->Mosaic
MLIR lowering on a CPU host — the stage where block-spec/tiling bugs
surface (VERDICT r2: "Mosaic compilation is exactly where
block-spec/tiling bugs surface"). Interpret-mode correctness tests never
exercise it; this file does, for the shapes AND block/tile grids the
tuner sweeps (reference niche: paddle/fluid/operators/jit/ — kernels
must *compile* per shape before the KernelPool can time them). Each
export is asserted to actually contain a Mosaic payload
(``tpu_custom_call``) so the gate cannot pass vacuously if dispatch
silently reroutes to the XLA fallback.

Only the Mosaic->machine-code stage and runtime performance still need
the chip (tools/pallas_tune.py).
"""

import itertools

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.quant_matmul import quant_matmul

# (b, t, h, d): BERT-base pretrain block and the 2k long-context shape
ATTN_SHAPES = [(8, 512, 12, 64), (2, 2048, 16, 128)]
# the tuner's full block grid (tools/pallas_tune.py ATTN_BLOCKS product),
# incl. the untuned 128x128 default every production call starts from
BLOCK_PAIRS = list(itertools.product([128, 256, 512], repeat=2))


def _export_tpu(jitted, *args):
    exported = jax.export.export(jitted, platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module(), (
        "export contains no Mosaic payload — the Pallas kernel path "
        "was not taken")
    return exported


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fwd_bwd_lowers_to_mosaic(shape, causal):
    b, t, h, d = shape
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    for bq, bk in BLOCK_PAIRS:
        if bq > t or bk > t:
            continue
        fwd = jax.jit(lambda q, k, v, _b=(bq, bk): flash_attention(
            q, k, v, causal=causal, block_q=_b[0], block_k=_b[1],
            interpret=False))
        _export_tpu(fwd, q, q, q)

        bwd = jax.jit(jax.grad(
            lambda q, k, v, _b=(bq, bk): flash_attention(
                q, k, v, causal=causal, block_q=_b[0], block_k=_b[1],
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        _export_tpu(bwd, q, q, q)


@pytest.mark.parametrize("mnk", [(512, 768, 768), (256, 30528, 768)])
def test_quant_matmul_lowers_to_mosaic(mnk):
    m, n, k = mnk
    a = jnp.zeros((m, k), jnp.int8)
    b = jnp.zeros((k, n), jnp.int8)
    sa = jnp.float32(0.01)
    sb = jnp.ones((n,), jnp.float32)
    for tm, tn, tk in itertools.product([128, 256, 512], repeat=3):
        if tm > m or tn > n or tk > k:
            continue
        f = jax.jit(lambda a, b, _t=(tm, tn, tk): quant_matmul(
            a, b, sa, sb, tile_m=_t[0], tile_n=_t[1], tile_k=_t[2],
            use_pallas=True))
        _export_tpu(f, a, b)


@pytest.mark.parametrize("blocks", [(128, 128), (64, 64), (256, 128)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_kv_mask_lowers_to_mosaic(causal, blocks):
    """The key-padding-mask kernel variant (extra (B,1,Tk) full-lane-row
    input with a b//h folding index map) must Mosaic-lower too — for
    EVERY block size the %64 dispatch gate can produce, incl. block 64
    (a (1,1,64) lane block would violate Mosaic tiling; the full-row
    spec + in-kernel pl.ds slice is what makes this legal)."""
    bq, bk = blocks
    b, t, h, d = 8, 512, 12, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    keep = jnp.ones((b, t), jnp.bool_)
    fwd = jax.jit(lambda q, k, v, m: flash_attention(
        q, k, v, causal=causal, kv_mask=m, block_q=bq, block_k=bk,
        interpret=False))
    _export_tpu(fwd, q, q, q, keep)

    bwd = jax.jit(jax.grad(
        lambda q, k, v, m: flash_attention(
            q, k, v, causal=causal, kv_mask=m, block_q=bq, block_k=bk,
            interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    _export_tpu(bwd, q, q, q, keep)


def test_flash_t192_masked_lowers_to_mosaic():
    """tq=192 (64-mod-128, admitted by the relaxed gate) resolves to
    block 64 via the divisor fallback chain and must lower masked."""
    b, t, h, d = 2, 192, 4, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    keep = jnp.ones((b, t), jnp.bool_)
    fwd = jax.jit(lambda q, k, v, m: flash_attention(
        q, k, v, kv_mask=m, interpret=False))
    _export_tpu(fwd, q, q, q, keep)


def test_flash_t64_lowers_to_mosaic():
    """The t=64 short-sequence path (block=t fallback) the dispatch gate
    now admits — NMT's seq-64 shape."""
    b, t, h, d = 64, 64, 8, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=64, block_k=64, interpret=False))
    _export_tpu(fwd, q, q, q)


@pytest.mark.parametrize("blocks", [(128, 128), (64, 64)])
def test_flash_segment_ids_lower_to_mosaic(blocks):
    """Packed-batch segment ids add a (B,T,1) lse-layout q-side input and
    a (B,1,T) full-row kv-side input — both must Mosaic-lower at every
    gate-admissible block size."""
    bq, bk = blocks
    b, t, h, d = 4, 512, 8, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    ids = jnp.zeros((b, t), jnp.int32)
    fwd = jax.jit(lambda q, k, v, s: flash_attention(
        q, k, v, segment_ids=s, block_q=bq, block_k=bk, interpret=False))
    _export_tpu(fwd, q, q, q, ids)

    bwd = jax.jit(jax.grad(
        lambda q, k, v, s: flash_attention(
            q, k, v, segment_ids=s, block_q=bq, block_k=bk,
            interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    _export_tpu(bwd, q, q, q, ids)


@pytest.mark.parametrize("blocks", [(128, 128), (64, 64)])
def test_flash_dropout_lowers_to_mosaic(blocks):
    """In-kernel attention dropout adds an SMEM (1,1) seed input and
    int32 hash/iota arithmetic — both must Mosaic-lower, fwd and bwd
    (bwd rebuilds the mask, possibly at different block sizes)."""
    bq, bk = blocks
    b, t, h, d = 4, 512, 8, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    prng = jax.random.PRNGKey(0)
    fwd = jax.jit(lambda q, k, v, pk: flash_attention(
        q, k, v, dropout_p=0.1, dropout_key=pk, block_q=bq, block_k=bk,
        interpret=False))
    _export_tpu(fwd, q, q, q, prng)

    bwd = jax.jit(jax.grad(
        lambda q, k, v, pk: flash_attention(
            q, k, v, dropout_p=0.1, dropout_key=pk, block_q=bq,
            block_k=bk, block_q_bwd=128, block_k_bwd=128,
            interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    _export_tpu(bwd, q, q, q, prng)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_window_lowers_to_mosaic(causal):
    """Banded (sliding-window) attention — the block-skip predicate and
    in-kernel band mask must Mosaic-lower."""
    b, t, h, d = 2, 2048, 8, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=256, block_q=128, block_k=128,
        interpret=False))
    _export_tpu(fwd, q, q, q)

    bwd = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=256, block_q=128, block_k=128,
            interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    _export_tpu(bwd, q, q, q)


def test_flash_gqa_lowers_to_mosaic():
    """GQA: the kv index-map folding (q-head grid row -> shared kv row)
    must Mosaic-lower, fwd and bwd."""
    b, t, h, h_kv, d = 2, 512, 8, 2, 64
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    k = jnp.zeros((b, t, h_kv, d), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=False))
    _export_tpu(fwd, q, k, k)

    bwd = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            interpret=False).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    _export_tpu(bwd, q, k, k)


# (b, t, h, h_kv, score width, value width): the trained latent cell's
# real call (kanana-2, 2 x 8192, 32 heads of 192 / 128 with the scores
# padded to whole lanes), the same with the scores as published (a block
# whose last dim is the array's own), and unequal widths under GQA
TWO_WIDTH_CASES = {
    "latent_train_cell": (2, 8192, 32, 32, 256, 128),
    "latent_unpadded_scores": (2, 8192, 32, 32, 192, 128),
    "gqa": (2, 2048, 8, 2, 256, 128),
}


@pytest.mark.parametrize("blocks", ["static", "v5e_table"])
@pytest.mark.parametrize("case", sorted(TWO_WIDTH_CASES))
def test_flash_two_widths_lower_to_mosaic(monkeypatch, case, blocks):
    """A score width and a value width of its own, at the latent
    prefill's blocks (its static 1024 x 512, and what the committed
    table gives the call on the v5e): forward and ``jax.grad`` (v, o,
    do and dv blocks at the value width, q, k, dq and dk at the score
    width)."""
    from paddle_tpu.ops.latent_attention import (FLASH_BLOCK_K,
                                                 FLASH_BLOCK_Q)
    from paddle_tpu.ops.pallas import tuning
    from paddle_tpu.ops.pallas.flash_attention import resolve_block_sizes

    b, t, h, h_kv, d, e = TWO_WIDTH_CASES[case]
    bq, bk, bq_bwd, bk_bwd = (FLASH_BLOCK_Q, FLASH_BLOCK_K) * 2
    if blocks == "v5e_table":
        monkeypatch.setattr(tuning, "_device_kind", lambda: "tpu_v5_lite")
        bq, bk, bq_bwd, bk_bwd = resolve_block_sizes(
            t, t, d, True, dtype=jnp.bfloat16, e=e,
            default_q=FLASH_BLOCK_Q, default_k=FLASH_BLOCK_K)
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    k = jnp.zeros((b, t, h_kv, d), jnp.bfloat16)
    v = jnp.zeros((b, t, h_kv, e), jnp.bfloat16)
    attend = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, block_q_bwd=bq_bwd,
        block_k_bwd=bk_bwd, interpret=False)
    fwd = _export_tpu(jax.jit(attend), q, k, v)
    assert fwd.out_avals[0].shape == (b, t, h, e)
    bwd = _export_tpu(jax.jit(jax.grad(
        lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))), q, k, v)
    assert [a.shape for a in bwd.out_avals] == [q.shape, k.shape, v.shape]


# --- flash-DECODE kernels (serving hot loop) --------------------------------
# The NMT lesson applied forward: interpret-mode correctness never
# exercises Mosaic tiling/scalar-prefetch legality, so the decode
# kernels get the same export gate — contiguous + paged, every block
# size decode_block_k can produce, per-row cursors, and INSIDE a
# lax.scan body (the BatchedDecoder decode_steps program shape).

from paddle_tpu.ops.pallas.flash_decode import (  # noqa: E402
    flash_decode, flash_decode_paged)

# (cap, d, h, kv): GQA serving shape + the small NMT decode cache
# + the window cell's two kinds of layer (Laguna-XS.2): a full cache
# read by 48 query heads and a ring of 512 read by 64, 8 key-value heads
DECODE_SHAPES = [(2048, 64, 12, 4), (256, 64, 8, 8), (512, 128, 16, 8),
                 (16384, 128, 48, 8), (512, 128, 64, 8)]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_flash_decode_lowers_to_mosaic(shape):
    cap, d, h, kv = shape
    b = 4
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    k = jnp.zeros((b, cap, kv, d), jnp.bfloat16)
    t = jnp.full((b,), cap // 2, jnp.int32)      # per-row cursors
    for bk in (64, 128, 256):
        if cap % bk:
            continue
        fn = jax.jit(lambda q, k, v, t, _bk=bk: flash_decode(
            q, k, v, t, block_k=_bk, interpret=False))
        _export_tpu(fn, q, k, k, t)
    # windowed variant at the default block
    fnw = jax.jit(lambda q, k, v, t: flash_decode(
        q, k, v, t, window=128, interpret=False))
    _export_tpu(fnw, q, k, k, t)


@pytest.mark.parametrize("page_size", [64, 128, 256])
def test_flash_decode_paged_lowers_to_mosaic(page_size):
    b, h, kv, d, n_log = 4, 8, 4, 64, 4
    pages = b * n_log
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    pool = jnp.zeros((pages, page_size, kv, d), jnp.bfloat16)
    table = jnp.arange(b * n_log, dtype=jnp.int32).reshape(b, n_log)
    t = jnp.full((b,), page_size + 3, jnp.int32)
    fn = jax.jit(lambda q, kp, vp, tb, t: flash_decode_paged(
        q, kp, vp, tb, t, interpret=False))
    _export_tpu(fn, q, pool, pool, table, t)


@pytest.mark.parametrize("page_size", [64, 128, 256])
def test_flash_decode_paged_int8_lowers_to_mosaic(page_size):
    """The int8 dequant-epilogue variant (ISSUE 15): int8 value blocks
    + rank-3 f32 scale blocks ride the same clamped page walk — the
    tiling/layout legality of BOTH block shapes must clear Mosaic, not
    just interpret mode."""
    b, h, kv, d, n_log = 4, 8, 4, 64, 4
    pages = b * n_log
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    pool = jnp.zeros((pages, page_size, kv, d), jnp.int8)
    sc = jnp.zeros((pages, page_size, kv), jnp.float32)
    table = jnp.arange(b * n_log, dtype=jnp.int32).reshape(b, n_log)
    t = jnp.full((b,), page_size + 3, jnp.int32)
    fn = jax.jit(lambda q, kp, ks, vp, vs, tb, t: flash_decode_paged(
        q, kp, vp, tb, t, k_scale=ks, v_scale=vs, interpret=False))
    _export_tpu(fn, q, pool, sc, pool, sc, table, t)
    # windowed variant (the sliding-window serving config)
    fnw = jax.jit(lambda q, kp, ks, vp, vs, tb, t: flash_decode_paged(
        q, kp, vp, tb, t, k_scale=ks, v_scale=vs, window=page_size,
        interpret=False))
    _export_tpu(fnw, q, pool, sc, pool, sc, table, t)


def test_flash_decode_inside_scan_lowers_to_mosaic():
    """The decode_steps serving program: the scalar-prefetch
    pallas_call sits INSIDE a lax.scan body whose cursor is a loop
    carry — the exact program BatchedDecoder(decode_steps=k)
    compiles."""
    b, cap, h, kv, d = 4, 256, 8, 4, 64
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    k = jnp.zeros((b, cap, kv, d), jnp.bfloat16)
    t0 = jnp.full((b,), 7, jnp.int32)

    def multi(q, k, v, t0):
        def body(c, _):
            t, o = c
            o = flash_decode(q, k, v, t, interpret=False)
            return (t + 1, o), None

        (_, o), _ = jax.lax.scan(body, (t0, q), None, length=4)
        return o

    _export_tpu(jax.jit(multi), q, k, k, t0)
