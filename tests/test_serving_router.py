"""Production serving plane (serving_router.py): multi-replica router,
prefill/decode disaggregation with KV-page handoff, SLO-aware load
shedding, liveness/readiness split, and replica-death failover.

Three tiers: deterministic unit tests over stub replicas (no jax work),
an in-process e2e over real tiny-GPT replicas, and slow-marked
subprocess chaos e2e (SIGKILL mid-stream). Green-field vs the reference
(one-request-at-a-time predictor, no cross-replica routing)."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import telemetry
from paddle_tpu.models import gpt as G
from paddle_tpu.resilience import FaultInjector
from paddle_tpu.serving import BatchedDecoder, KVHandoff, reject_cause
from paddle_tpu.serving_router import (HttpReplica, LocalReplica,
                                       NoReplicasError, RequestShedError,
                                       Router, SLOPolicy, spawn_replicas)

from replica_spec import SPEC, worker_env


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _decoder(slots=2, capacity=128, pages=16, seed=0, **kw):
    """Fresh tiny-GPT paged decoder. Each decoder gets its OWN model
    instance (same seed = identical weights): in-process replicas must
    not share a model (inject_state rebinds parameters during trace)."""
    pt.seed(seed)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    return BatchedDecoder(model, slots=slots, capacity=capacity,
                          pages=pages, page_size=64, **kw)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# SLO policy (pure function — fully deterministic)
# ---------------------------------------------------------------------------

class TestSLOPolicy:
    def test_queue_depth_ladder(self):
        p = SLOPolicy(degrade_at=1.5, shed_at=3.0)
        assert p.admit(0, 4) == "admit"
        assert p.admit(5, 4) == "admit"        # lf 1.25
        assert p.admit(6, 4) == "degrade"      # lf 1.5
        assert p.admit(11, 4) == "degrade"     # lf 2.75
        assert p.admit(12, 4) == "shed"        # lf 3.0
        assert p.admit(1, 0) == "shed"         # no capacity at all

    def test_deadline_ladder(self):
        p = SLOPolicy(target_ttft_s=1.0, degrade_at=10, shed_at=20)
        # est wait = lf * ewma: 2 in flight over 2 slots at 0.6s TTFT
        assert p.admit(2, 2, ewma_ttft_s=0.3) == "admit"
        assert p.admit(2, 2, ewma_ttft_s=0.6) == "degrade"
        assert p.admit(2, 2, ewma_ttft_s=1.2) == "shed"
        # no EWMA yet: queue ladder only
        assert p.admit(2, 2) == "admit"

    def test_shed_below_degrade_is_typed_error(self):
        with pytest.raises(Exception, match="shed_at"):
            SLOPolicy(degrade_at=2.0, shed_at=1.0)


# ---------------------------------------------------------------------------
# KV handoff (prefill/decode disaggregation wire unit)
# ---------------------------------------------------------------------------

class TestKVHandoff:
    def test_export_import_matches_local_decode(self):
        """A prompt prefilled on worker A and injected into replica B
        decodes EXACTLY like a solo run on C: the pages and logits
        cross the handoff bit-identical (same weights, same prefill
        executable), so not even a near-tie can flip."""
        prompt = _prompt(40, 1)
        worker = _decoder()
        h = worker.prefill_export(prompt)
        assert h.plen == 40
        assert h.pages == 1  # ceil(40/64)
        dec = _decoder()
        rid = dec.inject_prefilled(h, 12)
        out = dec.run()[rid]
        solo = _decoder()
        srid = solo.submit(prompt, 12)
        np.testing.assert_array_equal(solo.run()[srid], out)

    def test_wire_roundtrip_and_worker_pool_reclaimed(self):
        worker = _decoder()
        free0 = worker._allocator.free_pages
        h = worker.prefill_export(_prompt(70, 2))  # 2 pages
        # export frees its pages: a prefill worker's pool holds only
        # in-flight prompts
        assert worker._allocator.free_pages == free0
        h2 = KVHandoff.from_bytes(h.to_bytes())
        assert h2.plen == h.plen and h2.kv_dtype is None
        np.testing.assert_array_equal(h2.prompt, h.prompt)
        np.testing.assert_array_equal(h2.logits, h.logits)
        for (k1, v1), (k2, v2) in zip(h.blocks, h2.blocks):
            np.testing.assert_array_equal(k1, k2)
            np.testing.assert_array_equal(v1, v2)

    def test_quantized_handoff_roundtrip(self):
        """int8 pools hand off (q, scale) pairs intact — no silent
        dequant/requant — and the injected decode matches a solo
        int8 run exactly."""
        prompt = _prompt(30, 3)
        worker = _decoder(kv_dtype="int8")
        h = KVHandoff.from_bytes(
            worker.prefill_export(prompt).to_bytes())
        assert h.kv_dtype == "int8"
        assert h.blocks[0][0][0].dtype == np.int8
        dec = _decoder(kv_dtype="int8")
        rid = dec.inject_prefilled(h, 8)
        out = dec.run()[rid]
        solo = _decoder(kv_dtype="int8")
        srid = solo.submit(prompt, 8)
        np.testing.assert_array_equal(solo.run()[srid], out)

    def test_typed_errors(self):
        worker = _decoder()
        h = worker.prefill_export(_prompt(8, 4))
        pt.seed(0)
        contiguous = BatchedDecoder(
            G.GPTForCausalLM(G.GPTConfig.tiny()).eval(),
            slots=1, capacity=64)
        with pytest.raises(Exception, match="paged"):
            contiguous.inject_prefilled(h, 4)
        with pytest.raises(Exception, match="paged"):
            contiguous.prefill_export(_prompt(8, 4))
        q = _decoder(kv_dtype="int8")
        with pytest.raises(Exception, match="kv_dtype"):
            q.inject_prefilled(h, 4)
        with pytest.raises(Exception, match="page_size"):
            _decoder(page_size=128, capacity=256).inject_prefilled(h, 4)
        with pytest.raises(Exception, match="capacity"):
            _decoder().inject_prefilled(h, 1000)

    def test_handoff_skips_prefix_sharing_no_corruption(self):
        """Injected pages are always FRESH allocations: a handoff for a
        prompt whose prefix is registered must not import over shared
        pages. The cold-prefix request decoded after the handoff still
        matches its solo run."""
        prompt = _prompt(70, 5)
        dec = _decoder(pages=24, prefix_cache=True)
        # serve once normally: registers the 64-token prefix
        rid0 = dec.submit(prompt, 6)
        out0 = dec.run()[rid0]
        worker = _decoder()
        h = worker.prefill_export(prompt)
        rid1 = dec.inject_prefilled(h, 6)
        out1 = dec.run()[rid1]
        np.testing.assert_array_equal(out0, out1)
        # prefix registry survives and still serves a normal submit
        rid2 = dec.submit(prompt, 6)
        np.testing.assert_array_equal(dec.run()[rid2], out0)


# ---------------------------------------------------------------------------
# Readiness split + degrade lever + labeled rejections
# ---------------------------------------------------------------------------

class TestReadinessAndDegrade:
    def test_ready_tracks_warm_and_drain(self):
        dec = _decoder()
        assert not dec.ready  # cold jit cache: not placeable
        rep = LocalReplica(dec, name="w").start()
        try:
            rep.warmup()
            assert dec.ready
            dec.preempted = True  # draining
            assert not dec.ready
        finally:
            rep.close()

    def test_readyz_endpoint_and_healthz_field(self):
        from paddle_tpu.telemetry import server as dbg

        flag = [False]
        srv = dbg.DebugServer(port=0)
        srv.set_ready(lambda: flag[0])
        srv.start()
        try:
            def get(path):
                try:
                    with urllib.request.urlopen(srv.url(path)) as r:
                        return r.status, json.loads(r.read())
                except urllib.error.HTTPError as e:
                    return e.code, json.loads(e.read())

            code, body = get("/readyz")
            assert code == 503 and body["ready"] is False
            assert get("/healthz")[1]["ready"] is False
            flag[0] = True
            code, body = get("/readyz")
            assert code == 200 and body["ready"] is True
            # provider failure fails CLOSED (not ready), never a 500
            srv.set_ready(lambda: 1 / 0)
            assert get("/readyz")[0] == 503
        finally:
            srv.stop()
            telemetry.disable()

    def test_readyz_404_without_provider(self):
        from paddle_tpu.telemetry import server as dbg

        srv = dbg.DebugServer(port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url("/readyz"))
            assert e.value.code == 404
            with urllib.request.urlopen(srv.url("/healthz")) as r:
                assert "ready" not in json.loads(r.read())
        finally:
            srv.stop()
            telemetry.disable()

    def test_degraded_forces_k1_and_bypasses_spec(self):
        """set_degraded(True) mid-run drops to one token per dispatch
        and skips speculative rounds; outputs stay correct (the plain
        step emits the target's own picks)."""
        dec = _decoder(decode_steps=4, capacity=128)
        rid = dec.submit(_prompt(6, 7), 8)
        out_plain = _decoder(decode_steps=4, capacity=128)
        srid = out_plain.submit(_prompt(6, 7), 8)
        want = out_plain.run()[srid]
        dec.set_degraded(True)
        assert dec.degraded and dec._statusz()["degraded"]
        out = dec.run()[rid]
        np.testing.assert_array_equal(out, want)
        assert 1 in dec._step_fns and 4 not in dec._step_fns

    def test_labeled_rejection_causes(self):
        telemetry.enable()
        telemetry.registry().reset()
        # pool too small for both requests at once -> pool_exhausted
        dec = _decoder(slots=2, pages=3, capacity=128)
        dec.submit(_prompt(8, 8), 100)   # needs 2 pages (+margin)
        dec.submit(_prompt(8, 9), 100)
        dec._admit()
        reject_cause("shed")  # the router's contribution
        reg = telemetry.registry()
        total = reg.get("pt_serving_admission_rejections_total")
        pool = reg.get("pt_serving_admission_rejections_total",
                       {"cause": "pool_exhausted"})
        shed = reg.get("pt_serving_admission_rejections_total",
                       {"cause": "shed"})
        assert total.value == 2  # unlabeled total keeps BOTH causes
        assert pool.value == 1 and shed.value == 1


# ---------------------------------------------------------------------------
# Router logic over stub replicas (no jax — deterministic)
# ---------------------------------------------------------------------------

class _FakeReplica:
    """Replica-interface stub: completes instantly on drain, dies on
    demand — the router's placement/failover logic is tested without
    any model in the loop."""

    def __init__(self, name, slots=2):
        self.name = name
        self.slots = slots
        self.dead = False
        self.hold = False   # park completions (streams "in flight")
        self.degraded = None
        self.submits = []
        self.injects = 0
        self._rid = 0
        self._pending = {}
        self._mu = threading.Lock()

    def _check(self):
        if self.dead:
            raise OSError(f"{self.name} down")

    def submit(self, prompt, max_new, session=None):
        self._check()
        with self._mu:
            rid = self._rid
            self._rid += 1
            self.submits.append((rid, len(prompt), session))
            self._pending[rid] = {
                "tokens": np.arange(max_new, dtype=np.int32),
                "ttft_s": 0.001, "itl_p99_s": 0.0005,
                "n_tokens": max_new}
        return rid

    def inject(self, handoff, max_new, session=None):
        self.injects += 1
        return self.submit(handoff.prompt, max_new, session)

    def prefill(self, prompt):
        self._check()
        return KVHandoff(prompt, len(prompt),
                         np.zeros(4, np.float32), [], 64)

    def drain_results(self):
        self._check()
        if self.hold:
            return {}
        with self._mu:
            out = dict(self._pending)
            self._pending.clear()
            return out

    def set_degraded(self, on):
        self._check()
        self.degraded = bool(on)

    def healthz(self):
        self._check()
        return {"status": "ok", "ready": True}

    def load(self):
        self._check()
        return {"queue_depth": len(self._pending), "active_slots": 0,
                "prefilling": 0, "slots": self.slots}

    def close(self):
        pass


def _router(replicas, **kw):
    kw.setdefault("poll_interval_s", 0.01)
    kw.setdefault("dispatchers", 1)
    return Router(replicas, **kw)


class TestRouterLogic:
    def test_least_loaded_placement(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], poll_interval_s=30)  # no draining: load grows
        try:
            ts = [r.submit(_prompt(4), 2) for _ in range(4)]
            deadline = time.time() + 10
            while any(t.replica is None for t in ts) \
                    and time.time() < deadline:
                time.sleep(0.01)
            # drained manually AFTER placement settled
            assert len(a.submits) == 2 and len(b.submits) == 2
            r._poll_once()
            r.wait(ts, timeout=5)
        finally:
            r.close()

    def test_a_closed_router_lets_go_of_its_replicas(self):
        """Whoever still holds a closed router (a caller's ticket, a
        client thread's frame) must not keep an in-process replica's
        weights and arena alive with it: after ``close`` the replica is
        reachable from its owner alone."""
        import gc
        import weakref

        a = _FakeReplica("a")
        r = _router([a])
        t = r.submit(_prompt(4), 2)
        r._poll_once()
        r.wait([t], timeout=5)
        ref = weakref.ref(a)
        r.close()
        del a
        gc.collect()
        assert ref() is None
        assert r.stats()["replicas"] == 0

    def test_session_affinity_beats_load(self):
        a, b = _FakeReplica("a", slots=4), _FakeReplica("b", slots=4)
        r = _router([a, b], poll_interval_s=30)
        try:
            t0 = r.submit(_prompt(4), 2, session="conv")
            deadline = time.time() + 5
            while t0.replica is None and time.time() < deadline:
                time.sleep(0.01)
            home = t0.replica
            # home replica now carries load (nothing drains at a 30s
            # poll); the session's STRONG hint sticks anyway — only
            # the home claims it from the pull queue
            for _ in range(3):
                tn = r.submit(_prompt(4), 2, session="conv")
                while tn.replica is None and time.time() < deadline:
                    time.sleep(0.01)
                assert tn.replica == home
            # a session-less request pulls to the idle replica: home
            # is at its slot headroom with the 4 conv streams
            tf = r.submit(_prompt(4), 2)
            while tf.replica is None and time.time() < deadline:
                time.sleep(0.01)
            assert tf.replica != home
        finally:
            r.close()

    def test_dispatch_fault_retries_on_survivor(self):
        """Chaos point router.dispatch: a seeded injector kills the
        first dispatch — the replica is failed over and the request
        retries on the survivor; nothing is lost."""
        a, b = _FakeReplica("a"), _FakeReplica("b")
        inj = FaultInjector(seed=3).on("router.dispatch", at=(1,))
        with inj:
            r = _router([a, b])
            try:
                t = r.submit(_prompt(4), 3)
                r.wait([t], timeout=10)
                assert t.ok and t.retries == 1
                assert r.stats()["retries"] == 1
                # the faulted replica still answers health checks (the
                # fault was transient), so the poll loop may have
                # already RECOVERED it — the request must simply have
                # survived on the other replica in the meantime
                assert r.stats()["alive"] >= 1
                assert inj.fired["router.dispatch"] == 1
            finally:
                r.close()

    def test_all_replicas_down_is_typed_error(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b])
        try:
            a.dead = b.dead = True
            t = r.submit(_prompt(4), 2)  # dispatch discovers the deaths
            with pytest.raises(NoReplicasError):
                t.wait(timeout=10)
            # once marked dead, submit itself refuses
            with pytest.raises(NoReplicasError):
                r.submit(_prompt(4), 2)
        finally:
            r.close()

    def test_replica_death_reassigns_inflight(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        # fakes complete on DRAIN, so pause draining (long poll) only
        # until placement settles, then let the poll loop do the
        # detection + requeue + harvest end to end
        r = _router([a, b], poll_interval_s=0.05, health_fails=1)
        try:
            a.hold = b.hold = True
            ts = [r.submit(_prompt(4), 2) for _ in range(4)]
            deadline = time.time() + 10
            while any(t.replica is None for t in ts) \
                    and time.time() < deadline:
                time.sleep(0.01)
            victim = a if len(a.submits) else b
            dead_tickets = [t for t in ts if t.replica == victim.name]
            assert dead_tickets
            victim.dead = True
            a.hold = b.hold = False
            r.wait(ts, timeout=30)
            assert all(t.ok for t in ts)
            assert all(t.replica != victim.name for t in dead_tickets)
            assert r.stats()["retries"] >= len(dead_tickets)
        finally:
            r.close()

    def test_shed_and_degrade_ladder(self):
        a = _FakeReplica("a", slots=2)
        pol = SLOPolicy(degrade_at=0.5, shed_at=1.0)
        r = _router([a], policy=pol, poll_interval_s=30)
        try:
            t1 = r.submit(_prompt(4), 2)       # lf 0 -> admit
            assert not t1.shed
            deadline = time.time() + 10
            while t1.replica is None and time.time() < deadline:
                time.sleep(0.01)
            t2 = r.submit(_prompt(4), 2)       # lf 0.5 -> degrade
            assert not t2.shed
            assert a.degraded is True
            while t2.replica is None and time.time() < deadline:
                time.sleep(0.01)
            t3 = r.submit(_prompt(4), 2)       # lf 1.0 -> shed
            assert t3.shed and t3.done.is_set()
            with pytest.raises(RequestShedError):
                r.submit(_prompt(4), 2, raise_on_shed=True)
            assert r.stats()["shed"] == 2
            r._poll_once()                     # drain -> load falls
            r.wait([t1, t2], timeout=5)
            t4 = r.submit(_prompt(4), 2)       # lf 0 again -> admit
            assert not t4.shed
            assert a.degraded is False         # un-degraded on recovery
        finally:
            r.close()

    def test_transient_health_failure_recovers(self):
        """A replica that misses health checks (GC pause, slow
        compile) is failed over but NOT permanently removed: the poll
        loop keeps probing dead replicas, and the next successful
        answer restores it to the placement set."""
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], poll_interval_s=30, health_fails=1)
        try:
            b.dead = True
            r._poll_once()
            assert r.stats()["alive"] == 1
            b.dead = False
            r._poll_once()
            assert r.stats()["alive"] == 2
        finally:
            r.close()

    def test_hard_capacity_cap_rejects_with_cause(self):
        telemetry.enable()
        telemetry.registry().reset()
        a = _FakeReplica("a", slots=4)
        a.hold = True  # keep the first request in flight
        r = _router([a], max_in_flight=1, poll_interval_s=30)
        try:
            t1 = r.submit(_prompt(4), 2)
            assert not t1.shed
            deadline = time.time() + 10
            while t1.replica is None and time.time() < deadline:
                time.sleep(0.01)
            t2 = r.submit(_prompt(4), 2)
            assert t2.shed
            with pytest.raises(RequestShedError, match="capacity"):
                r.submit(_prompt(4), 2, raise_on_shed=True)
            cap = telemetry.registry().get(
                "pt_serving_admission_rejections_total",
                {"cause": "capacity"})
            assert cap is not None and cap.value == 2
        finally:
            r.close()
            telemetry.disable()

    def test_prefill_worker_failure_falls_back_to_replica(self):
        """A dead prefill worker must not be blamed on the decode
        replica: the request falls back to in-replica prefill, the
        worker leaves the rotation, and nothing is retried."""
        a = _FakeReplica("a")
        bad = _FakeReplica("pf")
        bad.dead = True
        r = _router([a], prefill_workers=[bad], disagg_min_tokens=2)
        try:
            t = r.submit(_prompt(8), 2)
            r.wait([t], timeout=10)
            assert t.ok and not t.disaggregated and t.retries == 0
            assert a.injects == 0 and len(a.submits) == 1
            assert r.stats()["alive"] == 1         # replica unharmed
            assert r.stats()["prefill_workers"] == 0  # worker dropped
        finally:
            r.close()

    def test_replicaz_fanout(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b])
        try:
            view = r.replicaz()
            assert set(view["replicas"]) == {"a", "b"}
            assert view["replicas"]["a"]["alive"]
            assert "router" in view
        finally:
            r.close()


# ---------------------------------------------------------------------------
# In-process e2e over real replicas (tiny GPT; one integration pass)
# ---------------------------------------------------------------------------

@pytest.mark.mid
def test_router_e2e_disaggregated_matches_solo():
    """2 replicas + 1 prefill worker, mixed short/long prompts: every
    request completes, long prompts go the handoff path, and every
    output is exactly the solo-decode output of the same prompt
    (placement is invisible in the tokens)."""
    reps = [LocalReplica(_decoder(pages=24), name=f"r{i}").start()
            for i in range(2)]
    pw = LocalReplica(_decoder(pages=24), name="pf0")
    for rep in reps:
        rep.warmup()
    pw.decoder.prefill_export(np.asarray([1, 2], np.int32))
    pw.decoder._warmed = True
    router = Router(reps, prefill_workers=[pw], disagg_min_tokens=32,
                    poll_interval_s=0.02)
    try:
        prompts = [_prompt(40 if i % 3 == 0 else 6, 20 + i)
                   for i in range(6)]
        ts = [router.submit(p, 8, session=f"s{i}")
              for i, p in enumerate(prompts)]
        router.wait(ts, timeout=300)
        assert all(t.ok for t in ts)
        assert all(t.disaggregated == (len(p) >= 32)
                   for t, p in zip(ts, prompts))
        for t, p in zip(ts, prompts):
            solo = _decoder(pages=24)
            rid = solo.submit(p, 8)
            np.testing.assert_array_equal(solo.run()[rid], t.tokens)
        assert router.stats()["served"] == 6
    finally:
        router.close()
        for rep in reps + [pw]:
            rep.close()


# ---------------------------------------------------------------------------
# Subprocess e2e: worker processes over HTTP (chaos tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_routed_profilez_one_capture_per_process(tmp_path):
    """POST /profilez against a routed 2-worker fleet: the router's
    fan-out returns one REAL XPlane capture per process (router + both
    workers, three distinct pids), and a worker mid-capture answers a
    second direct POST with 409 (one concurrent capture per process)."""
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, poll_interval_s=0.05)
    try:
        body = json.dumps({"duration_ms": 300}).encode()
        out = router.profilez_fanout(body)
        assert out["errors"] == {}, out["errors"]
        assert out["fleet"] == 3  # router + 2 workers
        pids = [c["pid"] for c in out["captures"]]
        assert len(set(pids)) == 3, pids
        assert os.getpid() in pids  # the router's own capture
        # every artifact the local process wrote is a real directory;
        # worker artifacts live in the WORKER's filesystem namespace
        # (same host here) — all must exist and be complete (atomic
        # rename means existing == capture finished)
        for c in out["captures"]:
            assert os.path.isdir(c["artifact"]), c
        # 409-while-busy, pinned against a live worker: hold a slow
        # capture on reps[0], then race a second direct POST into it
        slow = json.dumps({"duration_ms": 1500}).encode()
        errs = []

        def hold():
            req = urllib.request.Request(
                reps[0].url + "/profilez", data=slow,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                errs.append(r.status)

        t = threading.Thread(target=hold, name="pt-test-profilez")
        t.start()
        time.sleep(0.4)  # the slow capture is now holding the lock
        with pytest.raises(urllib.error.HTTPError) as e:
            req = urllib.request.Request(
                reps[0].url + "/profilez", data=body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 409
        t.join(timeout=30)
        assert errs == [200]  # the held capture itself completed
    finally:
        router.close(replicas=True)


@pytest.mark.slow
@pytest.mark.mid
@pytest.mark.chaos
def test_two_replica_http_router_smoke(tmp_path):
    """The ci.sh 'router smoke' stage body: 2 worker processes, real
    HTTP submit/drain, health+readiness probes, /podz-style fan-out."""
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, poll_interval_s=0.05)
    try:
        hz = reps[0].healthz()
        assert hz["ready"] is True  # warmed before spawn returned
        ts = [router.submit(_prompt(8 + i, 40 + i), 4,
                            session=f"s{i % 2}") for i in range(4)]
        router.wait(ts, timeout=300)
        assert all(t.ok and len(t.tokens) == 4 for t in ts)
        view = router.replicaz()
        assert len(view["replicas"]) == 2
        assert all(v["alive"] for v in view["replicas"].values())
        # the worker's debug plane serves the serving statusz section
        with urllib.request.urlopen(reps[0].url + "/statusz") as r:
            st = json.loads(r.read())
        assert st["status"]["serving"]["slots"] >= 1
    finally:
        router.close(replicas=True)


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_replica_mid_stream_retries_on_survivor(tmp_path):
    """SIGKILL one replica while its streams are in flight: the router
    health loop detects the death, retries the orphaned requests on
    the surviving replica, and NO request is lost. Killing the last
    replica yields the typed NoReplicasError. FaultInjector seeds the
    kill point (the 2nd drain poll of the victim) deterministically."""
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, poll_interval_s=0.05, health_fails=2)
    try:
        ts = [router.submit(_prompt(8 + i, 60 + i), 24)
              for i in range(6)]
        deadline = time.time() + 120
        while any(t.replica is None for t in ts) \
                and time.time() < deadline:
            time.sleep(0.02)
        # kill the replica holding ticket 0's stream (deterministic
        # victim selection; the seed fixes the workload)
        victim = next(r for r in reps if r.name == ts[0].replica)
        survivor = next(r for r in reps if r is not victim)
        os.kill(victim.proc.pid, signal.SIGKILL)
        router.wait(ts, timeout=300)
        assert all(t.ok for t in ts), "requests lost on replica death"
        dead_ts = [t for t in ts if t.retries]
        assert dead_ts, "no ticket was retried after the SIGKILL"
        assert all(t.replica == survivor.name for t in dead_ts)
        assert router.stats()["alive"] == 1
        # kill the survivor too: the typed all-down error
        os.kill(survivor.proc.pid, signal.SIGKILL)
        t = router.submit(_prompt(5, 99), 4)
        with pytest.raises(NoReplicasError):
            t.wait(timeout=120)
        with pytest.raises(NoReplicasError):
            router.submit(_prompt(5, 98), 4)
    finally:
        router.close(replicas=True)
