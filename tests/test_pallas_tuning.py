"""Pallas tuning-table tests (ops/pallas/tuning.py + the
tools/pallas_tune.py contract) — table lookup/persist, kernel
consultation, and the measured use_flash dispatch override.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import tuning


@pytest.fixture
def table(tmp_path, monkeypatch):
    path = tmp_path / "tuned_blocks.json"
    monkeypatch.setattr(tuning, "_TABLE_PATH", str(path))
    tuning.reset_cache()
    yield path
    tuning.reset_cache()


def test_keys_bucket_by_pow2_and_device(table):
    k1 = tuning.attention_key(128, 128, 64, True, kind="v5e")
    k2 = tuning.attention_key(100, 120, 64, True, kind="v5e")
    assert k1 == k2  # same pow2 bucket
    assert tuning.attention_key(256, 256, 64, True, kind="v5e") != k1
    assert tuning.attention_key(128, 128, 64, True, kind="v4") != k1
    assert "causal" in k1
    assert tuning.attention_key(128, 128, 64, False, kind="v5e") != k1


def test_set_get_persist_roundtrip(table):
    key = tuning.matmul_key(1024, 1024, 768, kind="v5e")
    entry = {"tile_m": 256, "tile_n": 128, "tile_k": 512}
    tuning.set_tuned(key, entry)
    assert tuning.get_tuned(key) == entry
    # persisted to disk and reloadable after a cache reset
    tuning.reset_cache()
    assert tuning.get_tuned(key) == entry
    assert json.loads(table.read_text())[key] == entry


def test_flash_attention_consults_table(table, monkeypatch):
    """Tuned block sizes flow into the kernel call; an entry whose block
    doesn't divide the actual seq len falls back to the 128 defaults
    instead of raising (pow2 buckets hold non-divisible shapes)."""
    import importlib

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    calls = []
    real = FA._flash

    def spy(q, k, v, kvm, seg, seed, causal, window, scale, dropout_p,
            bq, bk, bq_bwd, bk_bwd, interpret):
        calls.append((bq, bk, bq_bwd, bk_bwd))
        return real(q, k, v, kvm, seg, seed, causal, window, scale,
                    dropout_p, bq, bk, bq_bwd, bk_bwd, interpret)

    monkeypatch.setattr(FA, "_flash", spy)
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)

    key = tuning.attention_key(128, 128, 64, False)
    tuning.set_tuned(key, {"block_q": 64, "block_k": 64}, persist=False)
    FA.flash_attention(q, q, q)
    # tuned fwd blocks used; bwd defaults to the fwd blocks
    assert calls[-1] == (64, 64, 64, 64)

    tuning.set_tuned(key, {"block_q": 64, "block_k": 64,
                           "block_q_bwd": 32, "block_k_bwd": 128},
                     persist=False)
    FA.flash_attention(q, q, q)
    assert calls[-1] == (64, 64, 32, 128)  # independent tuned bwd blocks

    tuning.set_tuned(key, {"block_q": 96, "block_k": 96}, persist=False)
    FA.flash_attention(q, q, q)
    assert calls[-1] == (128, 128, 128, 128)  # 128 % 96 != 0 -> defaults

    FA.flash_attention(q, q, q, block_q=32, block_k=32)
    assert calls[-1] == (32, 32, 32, 32)  # explicit args override the table


def test_use_flash_false_routes_to_xla(table, monkeypatch):
    """A measured use_flash=False verdict forces the XLA fallback even on
    a TPU backend (the autotuner's dispatch contract)."""
    from paddle_tpu.ops import attention as A

    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    key = tuning.attention_key(128, 128, 64, False)
    tuning.set_tuned(key, {"use_flash": False}, persist=False)
    assert not A._flash_ok(q, q, False)
    tuning.set_tuned(key, {"use_flash": True, "block_q": 128,
                           "block_k": 128}, persist=False)
    assert A._flash_ok(q, q, False)


def test_tune_tool_refuses_cpu(table):
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "pallas_tune.py"),
         "--dry-run", "--platform", "cpu"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert "refusing to tune" in r.stderr


def test_set_tuned_preserves_concurrent_writer(table):
    """ADVICE r2: disk wins over our stale in-memory copy for every key
    except the one just tuned."""
    k_ours = tuning.matmul_key(512, 512, 512, kind="v5e")
    k_shared = tuning.matmul_key(1024, 1024, 1024, kind="v5e")
    tuning.set_tuned(k_shared, {"tile_m": 64})   # our stale view
    # a concurrent tuner process overwrites k_shared on disk
    disk = json.loads(table.read_text())
    disk[k_shared] = {"tile_m": 999}
    table.write_text(json.dumps(disk))
    # our next set_tuned for a DIFFERENT key must not clobber it
    tuning.set_tuned(k_ours, {"tile_m": 128})
    on_disk = json.loads(table.read_text())
    assert on_disk[k_shared] == {"tile_m": 999}
    assert on_disk[k_ours] == {"tile_m": 128}
    # in-memory keeps OUR entry (deliberate overrides stay); a cache
    # reset picks up the disk winner
    assert tuning.get_tuned(k_shared) == {"tile_m": 64}
    tuning.reset_cache()
    assert tuning.get_tuned(k_shared) == {"tile_m": 999}


def test_set_tuned_persist_false_override_survives(table):
    """Review r3: a persist=False in-memory override must not be
    reverted to the disk value by a later persist=True write."""
    k1 = tuning.matmul_key(512, 512, 512, kind="v5e")
    k2 = tuning.matmul_key(4096, 4096, 4096, kind="v5e")
    table.write_text(json.dumps({k1: {"tile_m": 1}}))
    tuning.reset_cache()
    tuning.set_tuned(k1, {"tile_m": 64}, persist=False)
    tuning.set_tuned(k2, {"tile_m": 256})
    assert tuning.get_tuned(k1) == {"tile_m": 64}   # override kept
    # disk still has the persisted k1 (persist=False never touches disk)
    assert json.loads(table.read_text())[k1] == {"tile_m": 1}


def test_set_tuned_repersists_memory_when_disk_lost(table):
    """Review r3: a deleted/corrupt table file must not shrink the
    persisted table to one entry — in-memory winners are re-written."""
    k1 = tuning.matmul_key(256, 256, 256, kind="v5e")
    k2 = tuning.matmul_key(2048, 2048, 2048, kind="v5e")
    tuning.set_tuned(k1, {"tile_m": 64})
    table.unlink()  # operator deletes the file mid-sweep
    tuning.set_tuned(k2, {"tile_m": 256})
    on_disk = json.loads(table.read_text())
    assert on_disk[k1] == {"tile_m": 64}
    assert on_disk[k2] == {"tile_m": 256}


def test_persist_false_key_never_reaches_disk(table):
    """Review r3: a session-only override for a key ABSENT from disk must
    not be leaked to disk by a later persist=True write."""
    k_sess = tuning.matmul_key(128, 128, 128, kind="v5e")
    k_other = tuning.matmul_key(8192, 8192, 8192, kind="v5e")
    tuning.set_tuned(k_sess, {"tile_m": 8}, persist=False)
    tuning.set_tuned(k_other, {"tile_m": 512})
    on_disk = json.loads(table.read_text())
    assert k_sess not in on_disk
    assert on_disk[k_other] == {"tile_m": 512}
    # the override is still live in-process
    assert tuning.get_tuned(k_sess) == {"tile_m": 8}
    # re-tuning the same key WITH persist does write it
    tuning.set_tuned(k_sess, {"tile_m": 16})
    assert json.loads(table.read_text())[k_sess] == {"tile_m": 16}


def _load_pallas_tune():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "pallas_tune_under_test", os.path.join(repo, "tools",
                                               "pallas_tune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tune_attention_sweeps_fwd_and_bwd_independently(monkeypatch):
    """The tuner's split sweep: fwd blocks picked first, bwd blocks swept
    with fwd fixed at its winner, both pairs recorded in the entry
    (tools/pallas_tune.py; the kernel consumes block_q_bwd/block_k_bwd
    via flash_attention's custom VJP)."""
    import importlib

    pt_mod = _load_pallas_tune()
    from paddle_tpu.ops import attention as A

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    fwd_cost = {(128, 128): 5.0, (128, 256): 3.0,
                (256, 128): 6.0, (256, 256): 7.0}
    bwd_cost = {(128, 128): 9.0, (128, 256): 8.0,
                (256, 128): 4.0, (256, 256): 6.0}
    seen = []

    def fake_flash(q, k, v, causal=False, scale=None, block_q=None,
                   block_k=None, block_q_bwd=None, block_k_bwd=None,
                   interpret=None):
        seen.append({"block_q": block_q, "block_k": block_k,
                     "block_q_bwd": block_q_bwd,
                     "block_k_bwd": block_k_bwd})
        return q * 1.0

    def fake_xla(q, k, v, causal=False, scale=None, **kw):
        seen.append({"xla": True})
        return q * 1.0

    def fake_time(fn, *args, **kw):
        out = fn(*args)  # trace -> the stub records its block config
        del out
        rec = seen[-1]
        if rec.get("xla"):
            return 5.0  # same for fwd and grad: x_total = 10
        if rec["block_q_bwd"] is not None:
            # bwd sweep must hold fwd at its measured winner
            assert (rec["block_q"], rec["block_k"]) == (128, 256)
            return bwd_cost[(rec["block_q_bwd"], rec["block_k_bwd"])]
        return fwd_cost[(rec["block_q"], rec["block_k"])]

    monkeypatch.setattr(FA, "flash_attention", fake_flash)
    monkeypatch.setattr(A, "xla_attention", fake_xla)
    monkeypatch.setattr(pt_mod, "_time", fake_time)

    entry = pt_mod.tune_attention(1, 256, 2, 64, causal=False,
                                  dry_run=True)
    assert entry["block_q"] == 128 and entry["block_k"] == 256
    assert entry["block_q_bwd"] == 256 and entry["block_k_bwd"] == 128
    # flash_total = best fwd (3) + best bwd (4) = 7 < xla 10
    assert entry["use_flash"] is True
    assert entry["flash_ms"] == pytest.approx(7000.0)
    assert entry["xla_ms"] == pytest.approx(10000.0)


def test_tune_attention_at_a_value_width_of_its_own(monkeypatch):
    """``--value-width`` / ``--blocks``: v, the result and the cotangent
    are ``e`` wide, the sweep is over the given candidates alone, the
    entry says at what widths it was measured, and an XLA fallback that
    cannot run (a long shape's whole score) loses to the kernel instead
    of stopping the tuner."""
    import importlib

    pt_mod = _load_pallas_tune()
    from paddle_tpu.ops import attention as A

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    pairs = []

    def fake_flash(q, k, v, causal=False, block_q=None, block_k=None,
                   block_q_bwd=None, block_k_bwd=None, interpret=None):
        assert (q.shape[-1], k.shape[-1], v.shape[-1]) == (256, 256, 128)
        pairs.append((block_q, block_k, block_q_bwd, block_k_bwd))
        return q[..., :128] * 1.0

    def no_xla(*a, **kw):
        raise MemoryError("a (B, H, T, T) score")

    monkeypatch.setattr(FA, "flash_attention", fake_flash)
    monkeypatch.setattr(A, "xla_attention", no_xla)
    monkeypatch.setattr(pt_mod, "_time", lambda fn, *a, **kw: (
        fn(*a), 1.0 / sum(x or 1 for x in pairs[-1]))[1])

    entry = pt_mod.tune_attention(1, 1024, 2, 256, causal=True,
                                  dry_run=True, e=128, blocks=[512, 1024])
    assert entry["shape"] == [1, 1024, 2, 2, 256, 128]
    assert set(entry["sweep_fwd_ms"]) == set(entry["sweep_grad_ms"]) == {
        "512x512", "512x1024", "1024x512", "1024x1024"}
    # which backward each gradient timing ran, by ``_bwd_call``'s own rule
    assert entry["sweep_grad_backward"] == {
        pair: "fused" if FA.bwd_is_fused(
            1024, 256, 128, *map(int, pair.split("x")), jnp.bfloat16)
        else "pair" for pair in entry["sweep_grad_ms"]}
    assert set(entry["sweep_grad_backward"].values()) == {"fused"}
    monkeypatch.setattr(FA, "FUSED_BWD_VMEM_LIMIT", 0)
    assert set(pt_mod.tune_attention(
        1, 1024, 2, 256, causal=True, dry_run=True, e=128,
        blocks=[512])["sweep_grad_backward"].values()) == {"pair"}
    assert [entry[n] for n in ("block_q", "block_k", "block_q_bwd",
                               "block_k_bwd")] == [1024] * 4
    assert entry["use_flash"] is True and "xla_ms" not in entry


# ---------------------------------------------------------------------------
# the committed table (paddle_tpu/ops/pallas/tuned_blocks.json) and the
# operand type in its keys
# ---------------------------------------------------------------------------

V5E = "tpu_v5_lite"  # what _device_kind() makes of the chip's "TPU v5 lite"


@pytest.fixture
def committed(monkeypatch):
    """The table as committed, looked up as the v5e would."""
    tuning.reset_cache()
    monkeypatch.setattr(tuning, "_device_kind", lambda: V5E)
    with open(tuning._TABLE_PATH) as f:
        yield json.load(f)
    tuning.reset_cache()


def test_attention_key_carries_operand_type():
    k32 = tuning.attention_key(2048, 2048, 128, True, kind=V5E)
    k16 = tuning.attention_key(2048, 2048, 128, True, kind=V5E,
                               dtype=jnp.bfloat16)
    assert k32 == "flash_attention|tpu_v5_lite|tq2048|tk2048|d128|causal|f32"
    assert k16 == "flash_attention|tpu_v5_lite|tq2048|tk2048|d128|causal|bf16"
    assert tuning.attention_key(2048, 2048, 128, True, kind=V5E,
                                dtype="bfloat16") == k16


def test_attention_key_carries_the_value_width_where_it_differs():
    key = tuning.attention_key(8192, 8192, 256, True, kind=V5E,
                               dtype=jnp.bfloat16, e=128)
    assert key == ("flash_attention|tpu_v5_lite|tq8192|tk8192|d256e128|"
                   "causal|bf16")
    # equal widths keep the key they had: the committed entries stand
    assert tuning.attention_key(
        2048, 2048, 128, True, kind=V5E, e=128) == tuning.attention_key(
        2048, 2048, 128, True, kind=V5E)


@pytest.mark.parametrize("length", [2048, 4096, 6144, 8192, 14336])
def test_committed_entries_size_the_latent_prefills(committed, monkeypatch,
                                                    length):
    """A latent prefill's call on a v5e (heads of 192 / 128 as 256 / 128,
    bf16: the trained kanana-2 cell at 8192, the served Xing4.0 cell's
    prompt buckets of 2048 to 14336) reaches the flash kernels with the
    blocks of the committed entry of its length's bucket, which records
    the widths it was measured at and the sweep it won."""
    import importlib

    from paddle_tpu.ops import latent_attention as LA

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    key = tuning.attention_key(length, length, 256, True,
                               dtype=jnp.bfloat16, e=128)
    entry = committed[key]
    assert "|d256e128|causal|bf16" in key and key.split("|")[1] == V5E
    assert entry["shape"][2:] == [32, 32, 256, 128]
    seen = {}

    def fake_flash(q, k, v, **kw):
        seen.update(kw)
        return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

    monkeypatch.setattr(FA, "flash_attention", fake_flash)
    q, k, v = (jnp.zeros((1, length, 1, w), jnp.bfloat16)
               for w in (192, 192, 128))
    LA._flash_padded(q, k, v, 192 ** -0.5)
    got = tuple(seen[n] for n in ("block_q", "block_k", "block_q_bwd",
                                  "block_k_bwd"))
    assert got == (entry["block_q"], entry["block_k"],
                   entry["block_q_bwd"], entry["block_k_bwd"])
    fwd, grad = entry["sweep_fwd_ms"], entry["sweep_grad_ms"]
    assert min(fwd, key=fwd.get) == f"{got[0]}x{got[1]}"
    assert min(grad, key=grad.get) == f"{got[2]}x{got[3]}"
    # whole blocks at every length ``prefill_kernel_ok`` admits
    assert all(LA.FLASH_BLOCK_Q % blk == 0 for blk in got)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_committed_entry_sizes_the_train_cell(committed, dtype):
    """(2048, 2048, 128, causal) on a v5e resolves to the committed
    entry of ITS operand type, which carries the chip's kind in its key
    and the timings it was chosen from."""
    from paddle_tpu.ops.pallas.flash_attention import resolve_block_sizes

    key = tuning.attention_key(2048, 2048, 128, True, dtype=dtype)
    entry = committed[key]
    assert key.split("|")[1] == V5E
    got = resolve_block_sizes(2048, 2048, 128, True, dtype=dtype)
    assert got == (entry["block_q"], entry["block_k"],
                   entry["block_q_bwd"], entry["block_k_bwd"])
    assert got != (128,) * 4
    # the winners are the fastest pairs of the sweep the entry records
    fwd, grad = entry["sweep_fwd_ms"], entry["sweep_grad_ms"]
    assert min(fwd, key=fwd.get) == f"{got[0]}x{got[1]}"
    assert min(grad, key=grad.get) == f"{got[2]}x{got[3]}"
    assert entry["shape"] == [8, 2048, 16, 8, 128]
    assert entry["fwd_ms"] == fwd[f"{got[0]}x{got[1]}"]
    assert entry["xla_ms"] > 0 and entry["fwd_spread_pct"] >= 0


def test_committed_table_leaves_other_shapes_at_128(committed):
    from paddle_tpu.ops.pallas.flash_attention import resolve_block_sizes

    for shape in ((512, 512, 64, True), (2048, 2048, 64, True),
                  (2048, 2048, 128, False), (1024, 1024, 128, True)):
        for dtype in (jnp.bfloat16, jnp.float32):
            assert resolve_block_sizes(*shape, dtype=dtype) == (128,) * 4
    # another chip generation never reads the v5e's entry
    assert tuning.attention_key(2048, 2048, 128, True, kind="tpu_v4",
                                dtype=jnp.bfloat16) not in committed


def test_f32_call_never_gets_blocks_measured_at_bf16(table, monkeypatch):
    """Only a bf16 entry in the table: the bf16 call takes it, the f32
    call at the same shape keeps 128 x 128, through flash_attention()
    too — under mixed_bf16 the f32 caller IS a bf16 call."""
    import importlib

    from paddle_tpu.core.dtypes import policy_scope

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(tuning, "_device_kind", lambda: V5E)
    tuning.set_tuned(
        tuning.attention_key(256, 256, 64, True, dtype=jnp.bfloat16),
        {"block_q": 256, "block_k": 64, "block_q_bwd": 64,
         "block_k_bwd": 256}, persist=False)
    assert FA.resolve_block_sizes(256, 256, 64, True,
                                  dtype=jnp.bfloat16) == (256, 64, 64, 256)
    assert FA.resolve_block_sizes(256, 256, 64, True,
                                  dtype=jnp.float32) == (128,) * 4
    assert FA.resolve_block_sizes(256, 256, 64, True) == (128,) * 4

    calls = []
    real = FA._flash
    monkeypatch.setattr(FA, "_flash", lambda q, k, v, *rest: (
        calls.append((q.dtype, rest[7:11])), real(q, k, v, *rest))[1])
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    FA.flash_attention(q, q, q, causal=True)
    assert calls[-1] == (jnp.float32, (128,) * 4)
    with policy_scope("mixed_bf16"):
        FA.flash_attention(q, q, q, causal=True)
    assert calls[-1] == (jnp.bfloat16, (256, 64, 64, 256))


def test_table_lookups_are_counted(table, monkeypatch):
    """Engagement is read from the hit counter: a lookup the table
    serves counts as a hit, one it lacks as a miss."""
    from paddle_tpu import telemetry

    monkeypatch.setattr(telemetry, "enabled", lambda: True)
    counts = lambda: {
        n: telemetry.registry().counter(f"pt_tuning_cache_{n}_total",
                                        "").value
        for n in ("hits", "misses")}
    key = tuning.attention_key(2048, 2048, 128, True, kind=V5E,
                               dtype=jnp.bfloat16)
    tuning.set_tuned(key, {"block_q": 512, "block_k": 512}, persist=False)
    before = counts()
    assert tuning.get_tuned(key)["block_q"] == 512
    assert tuning.get_tuned(key.replace("bf16", "f32")) is None
    after = counts()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"] + 1
