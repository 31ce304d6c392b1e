"""AOT compiled-program plane (paddle_tpu.aot): serialized serving
executables next to the checkpoint, trace-free cold start, fingerprint
compat gate with the PT-AOT-601 traced fallback, GC staleness, and the
multi-model router seam.

Tiers: fast committed-write/GC/fingerprint units, an in-process
bit-identical round trip over a real tiny-GPT decoder (the ci.sh "aot
smoke" body), and a slow-marked subprocess e2e that boots a worker
``--from-artifact`` with NO ``--spec`` — the trace-free cold-start
acceptance path."""

import json
import os
import shutil
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import aot, telemetry
from paddle_tpu.aot import (AotCompatError, AotError, AotTraceError,
                            ModelStub)
from paddle_tpu.checkpoint import CheckpointManager
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.models import gpt as G
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.serving_router import LocalReplica, Router

from replica_spec import router_replica_spec, worker_env


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _decoder(seed=0, paged=False, **kw):
    pt.seed(seed)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    if paged:
        kw.setdefault("pages", 16)
        kw.setdefault("page_size", 64)
    return BatchedDecoder(model, slots=2, capacity=128, **kw)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


def _decode(dec, prompt, max_new=8):
    rid = dec.submit(prompt, max_new)
    return np.asarray(dec.run()[rid])


# ---------------------------------------------------------------------------
# round trip: traced decode == artifact-booted decode, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.smoke
@pytest.mark.mid
def test_round_trip_bit_identical(tmp_path):
    """Export a warmed contiguous decoder, boot a second decoder from
    the artifact alone, decode the same prompt: the token streams pin
    bit-identical (the compiled program IS the deployment unit — the
    rehydrated executable must be the executable, not a re-trace)."""
    dec = _decoder()
    p = _prompt(6, 1)
    want = _decode(dec, p)
    art = aot.export_decoder(dec, str(tmp_path / "art"))

    dec2 = aot.restore_and_run(art)
    assert isinstance(dec2.model, ModelStub)
    got = _decode(dec2, p)
    np.testing.assert_array_equal(want, got)
    # provenance rides the loaded decoder for /statusz
    assert dec2.aot_info["artifact_id"]
    assert dec2.aot_info["programs"]["steps"] == [1]


@pytest.mark.mid
def test_round_trip_paged_multi_step(tmp_path):
    """Same pin over the paged arena with k=2 fused dispatch: both the
    k and the k=1 degrade program serialize, and the paged pools/page
    table rehydrate into identical tokens."""
    dec = _decoder(paged=True, decode_steps=2)
    p = _prompt(6, 2)
    want = _decode(dec, p)
    art = aot.export_decoder(dec, str(tmp_path / "art"), buckets=[40])

    dec2 = aot.load_decoder(art)
    assert dec2.aot_info["programs"]["steps"] == [1, 2]
    got = _decode(dec2, p)
    np.testing.assert_array_equal(want, got)
    # the explicitly requested bucket serves too (len-40 prompt)
    long = _decode(dec2, _prompt(40, 3), 4)
    assert long.shape == (4,)


# ---------------------------------------------------------------------------
# trace-free boot: ready flips off the rehydrated program; any path
# that would re-trace hits the stub's typed tripwire
# ---------------------------------------------------------------------------

@pytest.mark.smoke
@pytest.mark.mid
def test_trace_free_boot_flips_ready_and_tripwires(tmp_path):
    dec = _decoder()
    _decode(dec, _prompt(6, 1))  # warm one real bucket pre-export
    art = aot.export_decoder(dec, str(tmp_path / "art"))

    dec2 = aot.load_decoder(art)
    assert not dec2.ready
    dec2.warm_step()  # dispatches the REHYDRATED step program
    assert dec2.ready
    # the tripwire: an unseen prompt bucket would re-trace through the
    # model — the stub raises the typed error instead of a silent
    # recompile (there is no model to trace)
    big = _prompt(100, 4)
    rid = dec2.submit(big, 2)
    with pytest.raises(AotTraceError):
        dec2.run()
    # every trace entry point is booby-trapped, not just prefill
    with pytest.raises(AotTraceError):
        dec2.model.forward(None)
    with pytest.raises(AotTraceError):
        dec2.model.set_parameters({})


# ---------------------------------------------------------------------------
# compat gate + PT-AOT-601 traced fallback
# ---------------------------------------------------------------------------

@pytest.mark.smoke
@pytest.mark.mid
def test_fingerprint_mismatch_typed_fallback(tmp_path, monkeypatch,
                                             capsys):
    """A doctored toolchain fingerprint (simulated jax upgrade) makes
    the loader raise the typed AotCompatError; the worker bring-up
    catches it, warns ONCE with the PT-AOT-601 diagnostic, and boots
    the trace path instead — never a crash, never a silent rehydrate."""
    from paddle_tpu import serving_router as SR

    dec = _decoder()
    art = aot.export_decoder(dec, str(tmp_path / "art"))

    real = dict(aot.fingerprint())
    doctored = dict(real, jax="0.0.1-doctored")
    monkeypatch.setattr("paddle_tpu.aot.artifact.fingerprint",
                        lambda: doctored)
    with pytest.raises(AotCompatError) as ei:
        aot.load_decoder(art)
    assert "jax" in str(ei.value) and "0.0.1-doctored" in str(ei.value)

    # worker fallback: spec traces, diagnostic is typed and warn-once
    sentinel = object()
    monkeypatch.setattr(SR, "_resolve_spec", lambda spec, kw: sentinel)
    monkeypatch.setattr(SR, "_aot_fallback_warned", False)
    got, mode, diag = SR._boot_decoder("x:y", None, art)
    assert got is sentinel and mode == "traced_fallback"
    assert diag.startswith("[PT-AOT-601]")
    assert "[PT-AOT-601]" in capsys.readouterr().err
    got2, mode2, _ = SR._boot_decoder("x:y", None, art)
    assert got2 is sentinel and mode2 == "traced_fallback"
    assert "[PT-AOT-601]" not in capsys.readouterr().err  # warn-once
    # artifact-only boot (no spec to fall back to): typed re-raise
    with pytest.raises(AotCompatError):
        SR._boot_decoder(None, None, art)


def test_torn_artifact_rejected(tmp_path):
    """COMMITTED is the read gate: an artifact missing its marker (a
    kill mid-export) raises the typed AotError, and a hand-edited
    manifest fails the COMMITTED checksum."""
    dec = _decoder()
    art = aot.export_decoder(dec, str(tmp_path / "art"))
    man = aot.read_manifest(art)  # intact reads fine
    assert man["format"] == aot.ARTIFACT_FORMAT

    os.remove(os.path.join(art, "COMMITTED"))
    with pytest.raises(AotError, match="torn"):
        aot.read_manifest(art)

    art2 = aot.export_decoder(dec, str(tmp_path / "art2"))
    mpath = os.path.join(art2, "manifest.json")
    with open(mpath) as f:
        doctored = json.load(f)
    doctored["decoder"]["slots"] = 999
    with open(mpath, "w") as f:
        json.dump(doctored, f)
    with pytest.raises(AotError, match="checksum"):
        aot.read_manifest(art2)


# ---------------------------------------------------------------------------
# GC: artifacts ride checkpoint retention; stale ones never selected
# ---------------------------------------------------------------------------

def _fake_artifact(root, step):
    d = os.path.join(root, f"aot_step_{step}")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "COMMITTED"), "w") as f:
        f.write("{}")
    return d


def test_gc_prunes_artifact_with_its_step(tmp_path):
    """ISSUE 17 regression pin: checkpoint GC prunes ``aot_step_N``
    together with ``step_N``, and ``latest_artifact`` NEVER selects an
    artifact whose checkpoint step is gone or torn."""
    root = str(tmp_path / "ckpt")
    mgr = CheckpointManager(root, max_to_keep=2, async_save=False)
    for s in (1, 2, 3):
        mgr.save(s, {"x": jnp.full((4,), s, jnp.float32)})
        _fake_artifact(root, s)
    mgr.wait_until_finished()
    assert mgr.committed_steps() == [2, 3]
    # step_1 fell out of retention -> its artifact went with it
    assert not os.path.exists(os.path.join(root, "aot_step_1"))
    assert aot.latest_artifact(root) == os.path.join(root, "aot_step_3")

    # stale-artifact selection guard: step_3's checkpoint turns torn
    # (marker gone) — the selector must fall back to aot_step_2, and a
    # fully deleted step_2 leaves nothing selectable
    os.remove(os.path.join(root, "step_3", "COMMITTED"))
    assert aot.latest_artifact(root) == os.path.join(root, "aot_step_2")
    shutil.rmtree(os.path.join(root, "step_2"))
    _ = _fake_artifact(root, 9)  # artifact with NO step at all
    assert aot.latest_artifact(root) is None
    with pytest.raises(AotError, match="no committed aot artifact"):
        aot.resolve_artifact(root)

    # a later GC pass sweeps the now-stale artifacts too
    mgr2 = CheckpointManager(root, max_to_keep=2, async_save=False)
    mgr2.save(10, {"x": jnp.zeros(4)})
    mgr2.save(11, {"x": jnp.zeros(4)})
    mgr2.wait_until_finished()
    assert not os.path.exists(os.path.join(root, "aot_step_2"))
    assert not os.path.exists(os.path.join(root, "aot_step_9"))


def test_resolve_artifact_direct_dir(tmp_path):
    dec = _decoder()
    art = aot.export_decoder(dec, str(tmp_path / "standalone"))
    assert aot.resolve_artifact(art) == art
    # and via the checkpoint-root selector when placed canonically
    root = str(tmp_path / "ckpt")
    mgr = CheckpointManager(root, max_to_keep=2, async_save=False)
    mgr.save(7, {"x": jnp.zeros(2)})
    mgr.wait_until_finished()
    art7 = aot.export_decoder(dec, aot.artifact_dir_for_step(root, 7),
                              step=7)
    assert aot.resolve_artifact(root) == art7


# ---------------------------------------------------------------------------
# multi-model router: one Router, per-model replicas + page pools
# ---------------------------------------------------------------------------

@pytest.mark.smoke
@pytest.mark.mid
def test_two_model_router_e2e():
    """Two models behind ONE router: model-tagged tickets land only on
    their model's replicas (different seeds -> provably different
    weights: the tokens pin the placement), page pools stay per-model,
    and an unknown model id is a typed submit-time error."""
    reps = [LocalReplica(_decoder(seed=0, paged=True), name="a0",
                         model="a").start(),
            LocalReplica(_decoder(seed=7, paged=True), name="b0",
                         model="b").start()]
    for rep in reps:
        rep.warmup()
    # per-model page pools: each replica's arena owns its own pools
    assert reps[0].decoder.pools is not reps[1].decoder.pools
    router = Router(reps, poll_interval_s=0.02, disagg_min_tokens=None)
    try:
        assert router.stats()["models"] == ["a", "b"]
        p = _prompt(6, 5)
        ta = router.submit(p, 6, model="a")
        tb = router.submit(p, 6, model="b")
        router.wait([ta, tb], timeout=300)
        assert ta.ok and tb.ok
        assert ta.replica == "a0" and tb.replica == "b0"
        np.testing.assert_array_equal(
            ta.tokens, _decode(_decoder(seed=0, paged=True), p, 6))
        np.testing.assert_array_equal(
            tb.tokens, _decode(_decoder(seed=7, paged=True), p, 6))
        # same prompt, different weights: routing is visible in tokens
        assert not np.array_equal(ta.tokens, tb.tokens)
        with pytest.raises(EnforceError, match="unknown model"):
            router.submit(p, 4, model="nope")
        # untagged tickets still serve (any replica may take them)
        t = router.submit(p, 4)
        t.wait(timeout=300)
        assert t.ok
    finally:
        router.close()
        for rep in reps:
            rep.close()


def test_parse_specs_grammar():
    from paddle_tpu.serving_router import _parse_specs

    assert _parse_specs(None) == [(None, None)]
    assert _parse_specs("m:f") == [(None, "m:f")]
    assert _parse_specs("a=m:f,b=m2:g") == [("a", "m:f"), ("b", "m2:g")]
    with pytest.raises(EnforceError):
        _parse_specs("a=m:f,a=m2:g")  # duplicate name
    with pytest.raises(EnforceError):
        _parse_specs("a=,b=m:f")


def test_slo_policy_per_model_classes():
    from paddle_tpu.serving_router import SLOPolicy

    base = SLOPolicy(degrade_at=2.0, shed_at=4.0,
                     classes={"a": SLOPolicy(degrade_at=0.5,
                                             shed_at=1.0)})
    assert base.resolve("a").shed_at == 1.0
    assert base.resolve("b") is base  # unclassed models get the base
    assert base.resolve(None) is base
    with pytest.raises(EnforceError):
        SLOPolicy(classes={"a": object()})


# ---------------------------------------------------------------------------
# subprocess e2e: the acceptance path — a worker boots --from-artifact
# with NO --spec, flips /readyz off the rehydrated program, serves
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.mid
def test_worker_boots_from_artifact_trace_free(tmp_path):
    """Trace-free cold start, end to end through the deployment seam:
    export the test replica's programs, then spawn a worker process
    with ``--from-artifact`` and NO ``--spec`` — the worker has nothing
    to trace from, so readiness + served tokens PROVE the serialized
    programs booted it. /statusz reports the aot section."""
    from paddle_tpu.serving_router import spawn_replicas

    dec = router_replica_spec(smoke=True)
    art = aot.export_decoder(dec, str(tmp_path / "art"))
    del dec

    reps = spawn_replicas(None, 1, log_dir=str(tmp_path),
                          env=worker_env(), from_artifact=art)
    router = Router(reps, poll_interval_s=0.05,
                    disagg_min_tokens=None)
    try:
        assert reps[0].healthz()["ready"] is True
        t = router.submit(_prompt(6, 11), 4)
        t.wait(timeout=300)
        assert t.ok and len(t.tokens) == 4
        with urllib.request.urlopen(reps[0].url + "/statusz") as r:
            st = json.loads(r.read())
        aotz = st["status"]["aot"]
        assert aotz["mode"] == "aot"
        assert aotz["artifact_id"]
        assert aotz["ttfr_ms"] and aotz["ttfr_ms"] > 0
        assert st["run_config"]["boot"] == "aot"
    finally:
        router.close(replicas=True)
