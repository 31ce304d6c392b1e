"""The main path's Pallas kernels compile for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached: what Mosaic refuses on the chip it
refuses here, at no chip time (interpret mode and the Mosaic-MLIR
lowering of tests/test_pallas_mosaic_lowering.py both stop short of the
layout/VMEM checks that refused quant_matmul's 1-D scale operand).
Shapes are GPT-small's real ones (12 q / 4 kv heads, head dim 64,
seq 1024, decode capacity 2048, page 64), and for the flash forward and
backward also the benchmark's train cell (8 x 2048, 16 q / 8 kv heads of
128) at the blocks the committed table holds for the v5e, with bf16 and
with f32 operands.

Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture — never at
import, in a skipif, or in parametrize: only ONE process at a time may
load libtpu, so under pytest-xdist only the worker that is handed this
file may load it. For the same reason every compile runs in the test's
own process, and all of them live in this one file.
"""

import importlib
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import paddle_tpu as pt
from paddle_tpu.core.mesh import mesh_scope
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.flash_decode import (flash_decode,
                                                flash_decode_paged)
from paddle_tpu.ops.pallas.quant_matmul import quant_matmul

# GPT-small training shape (GPTConfig.small, batch 8 x seq 1024)
B, T, H, H_KV, D = 8, 1024, 12, 4, 64
SLOTS, CAPACITY, PAGE = 8, 2048, 64
# (b, t, h, h_kv, d, operand type): GPT-small as above, and
# internlm2-1.8b.pretrain_2k's attention as mixed_bf16 / float32 hand it
# to the kernels
ATTN_CASES = {
    "gpt_small": (B, T, H, H_KV, D, jnp.bfloat16),
    "train_cell_bf16": (8, 2048, 16, 8, 128, jnp.bfloat16),
    "train_cell_f32": (8, 2048, 16, 8, 128, jnp.float32),
}


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # another process may hold libtpu for a few seconds (the ptserve
    # child of tests/test_native_predictor.py, on another xdist worker):
    # its lock frees when it exits, so wait a bounded time before
    # giving up
    deadline = time.monotonic() + 60.0
    while True:
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means skip
            if "lockfile" in str(e) and time.monotonic() < deadline:
                time.sleep(2.0)
                continue
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chips(topo):
    """The four described chips, with the persistent compile cache off:
    a compile for a described chip is written to the cache but cannot
    be read back without a chip (the next run would warn and compile
    again)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(chips):
    return SingleDeviceSharding(chips[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _attn_shapes(one_chip, case="gpt_small"):
    b, t, h, h_kv, d, dtype = ATTN_CASES[case]
    q = jax.ShapeDtypeStruct((b, t, h, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, h_kv, d), dtype, sharding=one_chip)
    return q, kv


@pytest.fixture
def table_of_described_chip(topo, monkeypatch):
    """Block sizes are looked up under the kind of the device the process
    runs on, which is the CPU here: steer that one seam to the described
    chip, so that the compile runs at the blocks the committed table
    (ops/pallas/tuned_blocks.json) holds for it."""
    from paddle_tpu.ops.pallas import tuning

    kind = topo.devices[0].device_kind.lower().replace(" ", "_")
    monkeypatch.setattr(tuning, "_device_kind", lambda: kind)
    tuning.reset_cache()
    yield
    tuning.reset_cache()


def _assert_committed_blocks(case):
    from paddle_tpu.ops.pallas.flash_attention import resolve_block_sizes

    _, t, _, _, d, dtype = ATTN_CASES[case]
    blocks = resolve_block_sizes(t, t, d, True, dtype=dtype)
    if case == "gpt_small":
        assert blocks == (128,) * 4  # no entry at d64: the defaults
    else:
        assert min(blocks) > 128, blocks


def test_described_chip_is_a_v5e(topo):
    assert topo.devices[0].platform == "tpu"
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_flash_forward_compiles(one_chip, table_of_described_chip, case):
    _assert_committed_blocks(case)
    q, kv = _attn_shapes(one_chip, case)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text
    assert "%pt_flash_fwd" in text


@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_flash_backward_compiles(one_chip, table_of_described_chip, case):
    _assert_committed_blocks(case)
    q, kv = _attn_shapes(one_chip, case)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # the forward + the ONE backward kernel (dq beside dk and dv, under
    # the limit of its own that ``_bwd_call`` asks Mosaic for)
    assert text.count("tpu_custom_call") >= 2
    # each custom-call instruction carries its kernel's own name: what a
    # profile's XLA Ops line calls the event
    for name in ("%pt_flash_fwd", "%pt_flash_dkdv"):
        assert name in text, name
    assert "%pt_flash_dq" not in text


@pytest.mark.parametrize("rows, seq", [(2, 8192), (1, 14336)],
                         ids=["kanana_2x8192", "longest_1x14336"])
def test_flash_two_widths_compile_at_the_trained_latent_cells_call(
        one_chip, table_of_described_chip, rows, seq):
    """kanana-2's attention as ``causal_attention`` hands it to the
    kernels: 2 x 8192 positions, 32 heads, scores padded 192 -> 256,
    values at their own 128, bf16, at the blocks the committed table
    gives the call on the v5e; forward and backward through the TPU
    compiler, which is where a block too large for VMEM is refused.
    This is the one-kernel backward's VMEM gate: dq's float32
    accumulator spans the whole query length (8 MiB here beside its
    4 MiB output block, twice), so the call compiles only under the
    limit ``_bwd_call`` asks for; the table's longest entry at these
    widths (1 x 14336) is where the accumulator is largest."""
    from paddle_tpu.ops.latent_attention import (FLASH_BLOCK_K,
                                                 FLASH_BLOCK_Q)
    from paddle_tpu.ops.pallas import tuning
    from paddle_tpu.ops.pallas.flash_attention import (bwd_is_fused,
                                                       resolve_block_sizes)

    key = tuning.attention_key(seq, seq, 256, True, dtype=jnp.bfloat16,
                               e=128)
    assert tuning.get_tuned(key), key
    bq, bk, bq_bwd, bk_bwd = resolve_block_sizes(
        seq, seq, 256, True, dtype=jnp.bfloat16, e=128,
        default_q=FLASH_BLOCK_Q, default_k=FLASH_BLOCK_K)
    assert bwd_is_fused(seq, 256, 128, bq_bwd, bk_bwd, jnp.bfloat16)
    qk = jax.ShapeDtypeStruct((rows, seq, 32, 256), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((rows, seq, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5, block_q=bq,
            block_k=bk, block_q_bwd=bq_bwd, block_k_bwd=bk_bwd,
            interpret=False).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    # what each kernel writes: o and dv as wide as the values, dq and dk
    # as wide as the scores; the one backward kernel writes all three
    assert "%pt_flash_dq" not in text
    wrote = {name: re.search(rf"%{name}[.\d]* = (.*?) custom-call\(",
                             text).group(1)
             for name in ("pt_flash_fwd", "pt_flash_dkdv")}
    scores, values = (f"bf16[{rows * 32},{seq},{w}]" for w in (256, 128))
    assert wrote["pt_flash_fwd"].count(values) == 1
    assert wrote["pt_flash_dkdv"].count(scores) == 2       # dq, dk
    assert wrote["pt_flash_dkdv"].count(values) == 1       # dv
    assert (wrote["pt_flash_dkdv"].rindex(scores)
            < wrote["pt_flash_dkdv"].index(values))


def test_flash_key_padding_mask_compiles(one_chip):
    q, kv = _attn_shapes(one_chip)
    mask = jax.ShapeDtypeStruct((B, T), jnp.bool_, sharding=one_chip)

    def loss(q, k, v, m):
        return flash_attention(q, k, v, kv_mask=m,
                               interpret=False).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                          mask)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_decode_compiles(one_chip, dtype):
    q = jax.ShapeDtypeStruct((SLOTS, 1, H, D), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((SLOTS, CAPACITY, H_KV, D), dtype,
                              sharding=one_chip)
    t = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, t: flash_decode(q, k, v, t, interpret=False),
        q, kv, kv, t)
    assert "tpu_custom_call" in text
    assert "%pt_flash_decode" in text


@pytest.mark.parametrize("capacity,heads", [(16384, 48), (512, 64)],
                         ids=["full_6_a_kv_head", "ring_8_a_kv_head"])
def test_flash_decode_compiles_at_the_window_cells_two_head_counts(
        one_chip, capacity, heads):
    """Laguna-XS.2's two kinds of layer: a full cache of 16384 read by
    48 query heads and a ring of 512 read by 64, both over 8 key-value
    heads of 128: 6 and 8 query heads a key-value head, where the served
    dense cell has 4 (a group of 6 rows is no multiple of a sublane
    tile)."""
    q = jax.ShapeDtypeStruct((16, 1, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((16, capacity, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    t = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, t: flash_decode(q, k, v, t, interpret=False),
        q, kv, kv, t)
    assert "%pt_flash_decode" in text


def test_retention_step_compiles_and_writes_the_state_in_place(one_chip):
    """The power-retention step kernel at the Brumby cell's widths (40 q
    / 8 kv heads of 128, a state of 8320 x 128 a head; 4 slots here):
    Mosaic takes its rotations, its 4.3 MB blocks and its VMEM limit,
    and the compiled step holds the state once (the kernel's output is
    its input: no temporary of the state's size)."""
    from paddle_tpu.ops import retention

    b, h, kv, d = 4, 40, 8, 128
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    big = retention.phi_dim(d)

    def step(q, k, v, log_g, S, z):
        from paddle_tpu.ops.pallas.retention_step import (
            retention_state_step)

        scale = d ** -0.25
        return retention_state_step(
            S, k.astype(jnp.float32) * scale, v,
            q.astype(jnp.float32).reshape(b, kv, h // kv, d) * scale,
            jnp.exp(log_g), interpret=False)

    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        sd((b, h, d), jnp.bfloat16), sd((b, kv, d), jnp.bfloat16),
        sd((b, kv, d), jnp.bfloat16), sd((b, kv)), sd((b, kv, big, d)),
        sd((b, kv, big))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%pt_retention_step" in text
    mem = compiled.memory_analysis()
    state = b * kv * big * d * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < state // 8


def test_mla_decode_compiles_at_the_cells_widths(one_chip):
    """The latent decode read at the Xing4.0 cell's widths (16 rows, 32
    heads over one record of 512 + 64 bfloat16 numbers, 16384 positions,
    blocks of 1024 records): Mosaic takes the 64-wide rotary block, the
    two products that make one score and the clamped block walk, and the
    read holds no copy of the records."""
    from paddle_tpu.ops.pallas.mla_decode import block_k, mla_decode

    b, h, lat, rope, cap = 16, 32, 512, 64, 16384
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    assert block_k(cap) == 1024
    compiled = jax.jit(lambda qa, qr, c, r, t: mla_decode(
        qa, qr, c, r, t, scale=0.1, interpret=False)).lower(
        sd((b, h, lat)), sd((b, h, rope)), sd((b, cap, lat)),
        sd((b, cap, rope)), sd((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%pt_mla_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (
        b * cap * lat * 2) // 8


def test_the_sparse_latent_kernels_compile_at_the_cells_widths(one_chip):
    """Learned sparse attention at the GLM-5 cell's widths: the masked
    read of a decode step (16 rows, 64 heads, records of 512 + 64, 32768
    positions, a keep-mask a row), a span's index scores (2048 queries
    of 32 heads of 128 against 12288 keys of a 28672-token chunk) and
    the masked flash attention of that span (16 heads of 256 / 256, an
    int8 mask shared by the heads): Mosaic takes each, named, and none
    copies the chunk's arrays to cut its span out of them."""
    from paddle_tpu.ops.pallas import dsa, mla_decode

    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    b, h, lat, rope, cap = 16, 64, 512, 64, 32768
    read = jax.jit(lambda qa, qr, c, r, keep, t: mla_decode.mla_decode(
        qa, qr, c, r, t, scale=0.1, keep=keep, interpret=False)).lower(
        sd((b, h, lat)), sd((b, h, rope)), sd((b, cap, lat)),
        sd((b, cap, rope)), sd((b, cap), jnp.int32),
        sd((b,), jnp.int32)).compile()
    assert "%pt_dsa_read" in read.as_text()
    # no copy of the latents (the 64-wide rotary keys are laid out anew)
    assert read.memory_analysis().temp_size_in_bytes < (
        b * cap * lat * 2) // 3
    s, q0, span = 28672, 10240, 2048
    scores = jax.jit(lambda q, w, k: dsa.dsa_scores(
        q, w, k, q0=q0, span=span, interpret=False)).lower(
        sd((1, s, 32, 128)), sd((1, s, 32), jnp.float32),
        sd((1, s, 128))).compile()
    assert "%pt_dsa_scores" in scores.as_text()
    out = span * (q0 + span) * 4
    assert scores.memory_analysis().output_size_in_bytes == out
    # the queries laid out anew with their heads side by side, once; no
    # (queries, heads, keys) product and no slice of the keys
    assert scores.memory_analysis().temp_size_in_bytes < (
        1.1 * s * 32 * 128 * 2)
    attend = jax.jit(lambda q, k, v, keep: dsa.dsa_prefill(
        q, k, v, keep, scale=1 / 16, q0=q0, interpret=False)).lower(
        sd((1, 16, s, 256)), sd((1, 16, s, 256)), sd((1, 16, s, 256)),
        sd((1, span, q0 + span), jnp.int8)).compile()
    assert "%pt_dsa_prefill" in attend.as_text()
    assert attend.memory_analysis().temp_size_in_bytes < (
        16 * s * 256 * 2) // 8


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_flash_decode_paged_compiles(one_chip, quantized):
    n_log = CAPACITY // PAGE
    pages = SLOTS * n_log
    q = jax.ShapeDtypeStruct((SLOTS, 1, H, D), jnp.float32,
                             sharding=one_chip)
    pool = jax.ShapeDtypeStruct(
        (pages, PAGE, H_KV, D), jnp.int8 if quantized else jnp.float32,
        sharding=one_chip)
    table = jax.ShapeDtypeStruct((SLOTS, n_log), jnp.int32,
                                 sharding=one_chip)
    t = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    if quantized:
        scale = jax.ShapeDtypeStruct((pages, PAGE, H_KV), jnp.float32,
                                     sharding=one_chip)
        text = _compiled_text(
            lambda q, kp, vp, ks, vs, tb, t: flash_decode_paged(
                q, kp, vp, tb, t, k_scale=ks, v_scale=vs,
                interpret=False),
            q, pool, pool, scale, scale, table, t)
    else:
        text = _compiled_text(
            lambda q, kp, vp, tb, t: flash_decode_paged(
                q, kp, vp, tb, t, interpret=False),
            q, pool, pool, table, t)
    assert "tpu_custom_call" in text
    assert "%pt_flash_decode_paged" in text


@pytest.mark.parametrize("n", [2048, 768], ids=["ffn_up", "ffn_down"])
def test_quant_matmul_compiles(one_chip, n):
    """int8 GEMM at GPT-small's FFN widths with per-channel weight
    scales — the operand whose 1-D layout Mosaic used to refuse."""
    k = 768 if n == 2048 else 2048
    a = jax.ShapeDtypeStruct((512, k), jnp.int8, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip)
    sa = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    sb = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda a, b, sa, sb: quant_matmul(a, b, sa, sb, use_pallas=True),
        a, b, sa, sb)
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# four chips: the data-parallel mesh of chip_smoke.py --chips 4
# ---------------------------------------------------------------------------

def test_tpu_compiler_refuses_custom_partitioning(chips):
    """libtpu does not implement custom_partitioning: on more than one
    chip the call survives to the compiler, which has no emitter for it.
    This is WHY the flash kernel rides jax.shard_map on a multi-chip TPU
    mesh; the day this test fails, that second route can retire."""
    from jax.experimental.custom_partitioning import custom_partitioning

    @custom_partitioning
    def double(x):
        return x * 2

    double.def_partition(
        partition=lambda mesh, args, res: (
            mesh, lambda x: x * 2, args[0].sharding, (args[0].sharding,)),
        infer_sharding_from_operands=lambda mesh, args, res:
            args[0].sharding,
        sharding_rule="i j -> i j")
    mesh = pt.build_mesh(dp=4, devices=chips)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp")))
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="CustomSPMDPartitioning"):
        jax.jit(double).lower(x).compile()


def test_flash_dp4_compiles_via_shard_map(chips, monkeypatch):
    """Flash forward + backward at the GPT-small training shape, batch
    sharded over a dp=4 mesh of the described chips: compiles, keeps the
    kernel, gathers nothing. The route is chosen from the backend, which
    is the CPU here, so the test steers that one seam."""
    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(FA, "_use_interpret", lambda: False)
    mesh = pt.build_mesh(dp=4, devices=chips)
    sh = NamedSharding(mesh, P("dp"))
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((B, T, H_KV, D), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        return flash_attention(q, k, v,
                               causal=True).astype(jnp.float32).sum()

    with mesh_scope(mesh):
        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # the forward and the one backward kernel, each on its quarter
    assert text.count("tpu_custom_call") >= 2
    assert "%pt_flash_fwd" in text and "%pt_flash_dkdv" in text
    assert "all-gather" not in text
    # each chip works on its quarter of the batch
    assert f"bf16[{B // 4},{T},{H_KV}," in text


# ---------------------------------------------------------------------------
# whole serving programs: the donated arena is written in place
# ---------------------------------------------------------------------------

# mistral-7b-v0.1.chat_closed16 as benchmark/configs/mistral-7b-v0.1.json
# runs it, depth 16 -> 2: an arena leaf is (16, 2048, 8, 128) bf16
SERVE_SLOTS, SERVE_CAPACITY, SERVE_BUCKET = 16, 2048, 256
ARENA_LEAF = f"bf16[{SERVE_SLOTS},{SERVE_CAPACITY},8,128]"


@pytest.fixture(scope="module")
def mistral_decoder(one_chip):
    """A ``BatchedDecoder`` over the Mistral cell's model at depth 2
    with no weights behind it (the constructor runs under
    ``jax.eval_shape``, as ``benchmark/harness/program.build_model``
    does), the shapes of its parameters and of its arena placed on the
    described chip."""
    from paddle_tpu.core.config import FLAGS
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import BatchedDecoder

    was = FLAGS.get("default_dtype")
    FLAGS.set("default_dtype", "bfloat16")
    box = {}

    def construct():
        box["model"] = GPTForCausalLM(GPTConfig(
            vocab_size=32000, hidden_size=4096, num_layers=2,
            num_heads=32, num_kv_heads=8, intermediate_size=14336,
            max_position=SERVE_CAPACITY, rope_theta=10000.0,
            tie_embeddings=False)).eval()
        return dict(box["model"].named_parameters())

    try:
        pt.seed(0)
        shapes = jax.eval_shape(construct)
        pt.seed(0)  # the global key held a tracer: make it concrete again
        dec = BatchedDecoder(box["model"], slots=SERVE_SLOTS,
                             capacity=SERVE_CAPACITY,
                             prompt_bucket=SERVE_BUCKET)
    finally:
        FLAGS.set("default_dtype", was)
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    return dec, on_chip((shapes, {})), on_chip(dec.caches)


def _arena_copies(text):
    """The optimised HLO's ``copy`` / ``copy-start`` instructions whose
    result is (or, for the asynchronous form, starts with) an arena
    leaf."""
    head = re.compile(r"= \(?" + re.escape(ARENA_LEAF)
                      + r"\S* (?:\S+ )*?copy(-start)?\(")
    return [line.strip()[:160] for line in text.splitlines()
            if head.search(line)]


@pytest.mark.parametrize("program", ["decode_step", "prefill_256"])
def test_serving_programs_write_the_arena_in_place(mistral_decoder,
                                                   one_chip, monkeypatch,
                                                   program):
    """The check a session without a chip can make before its first
    chip call: compiled for the v5e, the decode step and a prefill hold
    no copy of an arena leaf, and every leaf is aliased to an output
    (undonated, each leaf is copied whole once a program: 4.3 GB moved a
    decode step at depth 16). The decode step keeps its kernel."""
    import paddle_tpu.ops.attention as attn

    dec, mstate, caches = mistral_decoder
    for mod in ("flash_attention", "flash_decode"):
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas." + mod), "_use_interpret", lambda: False)
    i32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.int32, sharding=one_chip)
    with attn.force_flash():
        if program == "decode_step":
            lowered = dec._build_multi_step(1).lower(
                mstate, caches, i32(SERVE_SLOTS), i32(SERVE_SLOTS),
                jax.ShapeDtypeStruct((SERVE_SLOTS,), jnp.uint32,
                                     sharding=one_chip))
        else:
            lowered = dec._prefill_fn(SERVE_BUCKET).lower(
                mstate, caches, i32(SERVE_BUCKET), 7, 0)
        compiled = lowered.compile()
    text = compiled.as_text()
    assert ARENA_LEAF in text
    assert not _arena_copies(text), _arena_copies(text)
    leaves = jax.tree_util.tree_leaves(caches)
    assert text.split("entry_computation_layout")[0].count(
        "-alias)") == len(leaves)
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        leaf.size * leaf.dtype.itemsize for leaf in leaves)
    if program == "decode_step":
        assert text.count("tpu_custom_call") >= 2


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%(\S+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%(\S+)\s+=\s+(.*?)\s([a-z][a-z-]*)\((.*)$")


def _wide_weights_of_products(text, names):
    """The float32 entry parameters among ``names`` that a fusion
    holding a ``convolution`` (what a ``dot_general`` compiles to) takes
    as an operand, straight or through a copy, a prefetch into fast
    memory or a bitcast."""
    comps, current = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(2), [])
        elif current is not None and _INSTRUCTION.match(line):
            current.append(_INSTRUCTION.match(line).groups())

    def holds_product(comp):
        return any(op == "convolution" or (
            op == "fusion" and holds_product(
                re.search(r"calls=%([^\s,]+)", rest).group(1)))
            for _, _, op, rest in comps.get(comp, ()))

    found = set()
    for rows in comps.values():
        origin = {}
        for name, dtype, op, rest in rows:
            operands = re.findall(r"%([^\s,()]+)", rest.split("), ")[0])
            leaf = re.search(r"op_name=\"params\[\\'([^\\]+)\\'\]\"", rest)
            if op == "parameter" and leaf and dtype.startswith("f32") \
                    and leaf.group(1) in names:
                origin[name] = leaf.group(1)
            elif op in ("copy", "copy-start", "copy-done", "bitcast") \
                    and operands and operands[0] in origin:
                origin[name] = origin[operands[0]]
            elif op == "fusion" and holds_product(
                    re.search(r"calls=%([^\s,]+)", rest).group(1)):
                found |= {origin[o] for o in operands if o in origin}
    return found


def test_no_product_of_the_train_step_reads_a_float32_linear_weight(
        one_chip, chips, monkeypatch):
    """A two-layer train step at ``internlm2-1.8b.pretrain_2k``'s widths
    under ``mixed_bf16`` with remat, compiled for the v5e as
    ``benchmark/rehearse_compile.py`` compiles the cell's: **no product
    is fed by a float32 entry parameter of a ``Linear``** (cast at each
    use, 54 of the 112 product fusions of the cell's four-layer step
    were: the compiler fuses the convert into the product, which then
    streams the float32 master weight and rounds it tile by tile, for
    every block of rows), and the step holds one ``weight_cast`` convert
    a declared leaf."""
    import importlib

    import paddle_tpu.ops.attention as attn
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    monkeypatch.setattr(importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention"), "_use_interpret",
        lambda: False)
    rows, seq = 8, 2048
    box = {}

    def construct():
        box["model"] = GPTForCausalLM(GPTConfig(
            vocab_size=92544, hidden_size=2048, num_layers=2, num_heads=16,
            num_kv_heads=8, intermediate_size=8192, max_position=seq,
            rope_theta=1e6, remat=True, tie_embeddings=False))
        return dict(box["model"].named_parameters())

    pt.seed(0)
    params = jax.eval_shape(construct)
    pt.seed(0)  # the global key held a tracer: make it concrete again
    model = box["model"]
    names = model.compute_cast_names()
    assert len(names) == 2 * 7

    tr = object.__new__(parallel.Trainer)
    tr.amp_policy, tr.optimizer = "mixed_bf16", optimizer.Adam(1e-4)
    tr._pmean_axes, tr.grad_compression, tr.plan = (), None, None

    def loss_builder(p, buffers, rng, ids):
        loss, nb = model.functional_call(p, ids, buffers=buffers, rng=rng,
                                         training=True,
                                         method="forward_loss")
        return loss, ({}, nb)

    tr.loss_builder = loss_builder
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    args = on_chip((params, {}, jax.eval_shape(tr.optimizer.init, params),
                    jax.eval_shape(lambda: jax.random.key(0)),
                    jax.ShapeDtypeStruct((rows, seq), jnp.int32)))
    with attn.force_flash(), mesh_scope(jax.sharding.Mesh(chips[:1],
                                                          ("dp",))):
        text = jax.jit(tr._step, donate_argnums=(0, 1, 2)).lower(
            *args).compile().as_text()
    # remat keeps a block's flash o and lse (``nn.remat_policy``), and the
    # compiler leaves it so: each kernel once a layer in the COMPILED step
    # (two layers: the forward kernel and the one backward kernel twice)
    for kernel in ("pt_flash_fwd", "pt_flash_dkdv"):
        assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 2, kernel
    assert "%pt_flash_dq" not in text
    assert text.count(" convolution(") >= 2 * 7 * 4    # forward, second
    assert _wide_weights_of_products(text, names) == set()  # forward, two back
    control = """
%fused.1 (p0: f32[4,8], p1: bf16[2,4]) -> bf16[2,8] {
  %p1 = bf16[2,4]{1,0} parameter(1)
  %p0 = f32[4,8]{1,0} parameter(0)
  %convert.1 = bf16[4,8]{1,0} convert(%p0)
  ROOT %convolution.1 = bf16[2,8]{1,0} convolution(%p1, %convert.1), dim_labels=bf_io->bf
}

ENTRY %main (w: f32[4,8], x: bf16[2,4]) -> bf16[2,8] {
  %w = f32[4,8]{1,0} parameter(0), metadata={op_name="params[\\'up.weight\\']"}
  %x = bf16[2,4]{1,0} parameter(1), metadata={op_name="ids"}
  %copy-start.1 = (f32[4,8]{1,0:S(1)}, f32[4,8]{1,0}, u32[]) copy-start(%w)
  %copy-done.1 = f32[4,8]{1,0:S(1)} copy-done(%copy-start.1)
  ROOT %fusion.1 = bf16[2,8]{1,0} fusion(%copy-done.1, %x), kind=kOutput, calls=%fused.1
}
"""
    assert _wide_weights_of_products(control, {"up.weight"}) == {"up.weight"}
    converts = [line for line in text.splitlines() if re.search(
        r"= bf16\[\S+ convert\(.*op_name=\"[^\"]*weight_cast", line)
        and "transpose(" not in line]
    assert len(converts) == len(names)
