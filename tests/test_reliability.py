"""Request reliability plane (resilience/reliability.py + router wiring):
end-to-end deadlines, retry budgets, hedged dispatch, and gray-failure
quarantine.

Three tiers: pure units over the plane's primitives (Deadline /
RetryBudget / LatencyTracker / ReplicaHealth — no clock games beyond
time.time), deterministic router tests over stub replicas driven by
``_poll_once`` (no jax work), and slow-marked subprocess chaos e2e
(SIGSTOP a worker mid-stream → quarantine + hedge → SIGCONT half-open
restore). The zero-cost tripwire pins the telemetry-off discipline:
``Router(reliability=None)`` must execute NO reliability code on the
hot path."""

import os
import signal
import threading
import time

import numpy as np
import pytest

from paddle_tpu import telemetry
from paddle_tpu.resilience import FaultInjector
from paddle_tpu.resilience import reliability as rel
from paddle_tpu.resilience.reliability import (DEADLINE_HEADER, Deadline,
                                               DeadlineExceededError,
                                               LatencyTracker,
                                               ReliabilityConfig,
                                               ReliabilityPlane,
                                               ReplicaHealth, RetryBudget,
                                               RetryBudgetExhaustedError)
from paddle_tpu.serving import KVHandoff
from paddle_tpu.serving_router import (LocalReplica, Router, SLOPolicy,
                                       _trace_headers, spawn_replicas)

from replica_spec import SPEC, worker_env


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# Deadline (the end-to-end budget primitive)
# ---------------------------------------------------------------------------

class TestDeadline:
    def test_after_remaining_expired(self):
        d = Deadline.after(60.0)
        assert 59.0 < d.remaining() <= 60.0
        assert not d.expired()
        e = Deadline(time.time() - 1.0)
        assert e.expired() and e.remaining() < 0

    def test_check_raises_typed_504(self):
        Deadline.after(60.0).check()  # healthy: no-op
        with pytest.raises(DeadlineExceededError, match="prefill"):
            Deadline(time.time() - 0.5).check("prefill export")
        assert DeadlineExceededError.http_status == 504
        assert RetryBudgetExhaustedError.http_status == 503

    def test_header_roundtrip_and_garbage(self):
        d = Deadline.after(30.0)
        d2 = Deadline.from_header(d.to_header())
        assert d2 is not None and abs(d2.t_end - d.t_end) < 1e-9
        # garbage on the wire degrades to "no deadline", never a crash
        assert Deadline.from_header("not-a-float") is None
        assert Deadline.from_header(None) is None
        assert Deadline.from_header("") is None

    def test_bind_current(self):
        assert rel.current() is None
        d = Deadline.after(5.0)
        with rel.bind(d):
            assert rel.current() is d
            with rel.bind(None):
                assert rel.current() is None
            assert rel.current() is d
        assert rel.current() is None

    def test_trace_headers_stamp_deadline_without_telemetry(self):
        """The deadline is a CORRECTNESS header: it rides outbound HTTP
        hops whether or not telemetry is on."""
        assert _trace_headers({}) == {}
        d = Deadline.after(9.0)
        with rel.bind(d):
            h = _trace_headers({})
        assert DEADLINE_HEADER in h
        back = Deadline.from_header(h[DEADLINE_HEADER])
        assert abs(back.t_end - d.t_end) < 1e-9

    def test_kv_handoff_carries_deadline(self):
        """Disaggregated prefill inherits the REQUEST's remaining
        budget over the npz wire, not a fresh per-hop one."""
        d = Deadline.after(42.0)
        blocks = [(np.zeros((1, 64, 2, 4), np.float32),
                   np.zeros((1, 64, 2, 4), np.float32))]
        h = KVHandoff(_prompt(8), 8, np.zeros(4, np.float32), blocks,
                      64, deadline=d)
        h2 = KVHandoff.from_bytes(h.to_bytes())
        assert h2.deadline is not None
        assert abs(h2.deadline.t_end - d.t_end) < 1e-6
        bare = KVHandoff.from_bytes(
            KVHandoff(_prompt(8), 8, np.zeros(4, np.float32), blocks,
                      64).to_bytes())
        assert bare.deadline is None

    def test_statusz_section_documents_header(self):
        assert rel.statusz_section()["deadline_header"] == DEADLINE_HEADER


# ---------------------------------------------------------------------------
# Retry budget (SRE token bucket)
# ---------------------------------------------------------------------------

class TestRetryBudget:
    def test_spend_to_dry_then_counted_exhaustion(self):
        b = RetryBudget(capacity=2.0, refill_fraction=0.1)
        assert b.take() and b.take()
        assert not b.take()  # dry
        assert not b.take()
        s = b.snapshot()
        assert s["spent"] == 2 and s["exhausted"] == 2
        assert s["tokens"] == 0.0 and s["capacity"] == 2.0

    def test_successes_refill_fractionally_capped(self):
        b = RetryBudget(capacity=2.0, refill_fraction=0.5)
        b.take()
        b.take()
        b.note_success()
        assert not b.take()  # 0.5 token is not a whole retry yet
        b.note_success()
        assert b.take()  # 2 successes bought 1 retry
        for _ in range(20):
            b.note_success()
        assert b.snapshot()["tokens"] == 2.0  # capped at capacity


# ---------------------------------------------------------------------------
# Latency tracker (adaptive hedge threshold)
# ---------------------------------------------------------------------------

class TestLatencyTracker:
    def test_cold_then_quantile(self):
        t = LatencyTracker(window=64, min_samples=10, quantile=0.95)
        for i in range(9):
            t.observe(0.01)
        assert t.threshold() is None  # below min_samples: stay cold
        t.observe(0.01)
        assert t.threshold() == pytest.approx(0.01)
        # one outlier among 20 fast samples: p95 picks near the top
        for _ in range(9):
            t.observe(0.01)
        t.observe(5.0)
        assert t.threshold() == pytest.approx(5.0)

    def test_ring_evicts_old_samples(self):
        t = LatencyTracker(window=8, min_samples=4, quantile=0.5)
        for _ in range(8):
            t.observe(10.0)
        for _ in range(8):
            t.observe(0.1)  # full wrap: the slow era is gone
        assert t.threshold() == pytest.approx(0.1)
        assert t.count() == 8


# ---------------------------------------------------------------------------
# Replica health (per-replica circuit breaker)
# ---------------------------------------------------------------------------

class TestReplicaHealth:
    def test_ewma_and_timeout_reset(self):
        h = ReplicaHealth("r0", alpha=0.5)
        h.note_latency(1.0)
        assert h.latency_ewma == pytest.approx(1.0)
        h.note_latency(2.0)
        assert h.latency_ewma == pytest.approx(1.5)
        h.note_timeout()
        h.note_timeout()
        assert h.timeouts == 2
        h.note_latency(1.0)  # a successful dispatch breaks the streak
        assert h.timeouts == 0

    def test_breaker_state_machine(self):
        h = ReplicaHealth("r0")
        assert h.state == "closed"
        h.trip("timeouts=3")
        assert h.state == "open" and h.opened_count == 1
        assert h.last_reason == "timeouts=3"
        assert not h.probe_due(cooldown_s=3600.0)
        assert h.probe_due(cooldown_s=0.0)
        h.half_open()
        assert h.state == "half_open"
        assert not h.probe_due(cooldown_s=0.0)  # probe in flight
        h.reopen()  # failed probe: cooldown restarts
        assert h.state == "open"
        h.half_open()
        h.close()  # probe success: scores reset with the state
        assert h.state == "closed"
        assert h.latency_ewma is None and h.samples == 0
        snap = h.snapshot()
        assert snap["state"] == "closed" and snap["opened"] == 1


# ---------------------------------------------------------------------------
# ReliabilityPlane (aggregate: budgets, thresholds, quarantine scoring)
# ---------------------------------------------------------------------------

class TestReliabilityPlane:
    def test_deadline_for_precedence(self):
        p = ReliabilityPlane(ReliabilityConfig(deadline_factor=10.0))
        assert p.deadline_for() is None  # unbudgeted: no deadline
        d = p.deadline_for(target_ttft_s=0.5)
        assert 4.0 < d.remaining() <= 5.0  # factor x target TTFT
        p2 = ReliabilityPlane(ReliabilityConfig(deadline_s=20.0))
        assert 19.0 < p2.deadline_for(
            target_ttft_s=0.5).remaining() <= 20.0  # config default wins
        # an explicit per-class budget wins over everything
        assert 2.0 < p2.deadline_for(
            target_ttft_s=0.5, budget_s=3.0).remaining() <= 3.0

    def test_quarantine_reason_consecutive_timeouts(self):
        p = ReliabilityPlane(ReliabilityConfig(consecutive_timeouts=3))
        h = p.health("a")
        h.note_timeout()
        h.note_timeout()
        assert p.quarantine_reason(h) is None
        h.note_timeout()
        assert "timeouts=3" in p.quarantine_reason(h)

    def test_quarantine_reason_latency_outlier_needs_a_fleet(self):
        p = ReliabilityPlane(ReliabilityConfig(
            outlier_factor=3.0, min_outlier_latency_s=0.05))
        slow = p.health("slow")
        for _ in range(4):
            slow.note_latency(1.0)
        # a lone scored replica can never self-quarantine on outlier
        # math: there is no fleet median to be an outlier against
        assert p.quarantine_reason(slow) is None
        fast = p.health("fast")
        for _ in range(4):
            fast.note_latency(0.01)
        assert "latency_outlier" in p.quarantine_reason(slow)
        assert p.quarantine_reason(fast) is None  # the healthy one

    def test_latency_outlier_abs_floor(self):
        """A 3x outlier on a microsecond fleet median is noise, not
        gray failure: the absolute floor gates the trip."""
        p = ReliabilityPlane(ReliabilityConfig(min_outlier_latency_s=0.05))
        a, b = p.health("a"), p.health("b")
        for _ in range(4):
            a.note_latency(0.01)  # 10x the median, under the floor
            b.note_latency(0.001)
        assert p.quarantine_reason(a) is None

    def test_hedge_threshold_gating(self):
        off = ReliabilityPlane(ReliabilityConfig(hedge=False))
        off.latency.observe(1.0)
        assert off.hedge_threshold() is None  # disabled
        p = ReliabilityPlane(ReliabilityConfig(hedge_min_samples=4,
                                               hedge_factor=2.0))
        assert p.hedge_threshold() is None  # cold
        for _ in range(4):
            p.latency.observe(0.5)
        assert p.hedge_threshold() == pytest.approx(1.0)  # p95 x factor

    def test_statusz_shape(self):
        p = ReliabilityPlane()
        p.health("a").note_latency(0.1)
        s = p.statusz()
        assert s["budget"]["capacity"] == 10.0
        assert s["latency_samples"] == 0
        assert s["deadline_exceeded"] == 0 and s["hedges"] == 0
        assert s["replicas"]["a"]["state"] == "closed"


# ---------------------------------------------------------------------------
# Router wiring (deterministic, stub replicas, tests drive _poll_once)
# ---------------------------------------------------------------------------

class _FakeReplica:
    """Replica-interface stub: completes instantly on drain (or parks
    completions while ``hold``), dies on demand — reliability wiring is
    tested without any model in the loop."""

    def __init__(self, name, slots=2):
        self.name = name
        self.slots = slots
        self.dead = False
        self.hold = False
        self.submits = []
        self.cancels = []
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

    def cancel(self, rid):
        with self._mu:
            self.cancels.append(rid)
            return self._pending.pop(rid, None) is not None

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
    kw.setdefault("poll_interval_s", 30)  # tests drive _poll_once
    kw.setdefault("dispatchers", 1)
    return Router(replicas, **kw)


def _wait_dispatched(ts, timeout=10):
    deadline = time.time() + timeout
    while any(not t.t_dispatched and not t.done.is_set() for t in ts) \
            and time.time() < deadline:
        time.sleep(0.005)


class TestRouterReliability:
    def test_expired_deadline_never_dispatches(self):
        """The pre-dispatch tripwire: an expired request NEVER reaches
        a replica — zero device work, a typed counted drop."""
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], reliability=ReliabilityConfig(deadline_s=0.0))
        try:
            t = r.submit(_prompt(4), 4)
            assert t.deadline is not None
            with pytest.raises(DeadlineExceededError, match="before dispatch"):
                t.wait(timeout=10)
            assert a.submits == [] and b.submits == []
            st = r.stats()
            assert st["reliability"]["deadline_exceeded"] == 1
            assert st["in_flight"] == 0  # accounting drained
        finally:
            r.close()

    def test_deadline_minted_from_slo_class(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        # per-class explicit budget wins
        r = _router([a, b], policy=SLOPolicy(deadline_s=30.0),
                    reliability=True)
        try:
            t = r.submit(_prompt(4), 2)
            assert 29.0 < t.deadline.remaining() <= 30.0
        finally:
            r.close()
        # no explicit budget: deadline_factor x the class target TTFT
        r2 = _router([_FakeReplica("a"), _FakeReplica("b")],
                     policy=SLOPolicy(target_ttft_s=0.5),
                     reliability=ReliabilityConfig(deadline_factor=10.0))
        try:
            t2 = r2.submit(_prompt(4), 2)
            assert 4.0 < t2.deadline.remaining() <= 5.0
        finally:
            r2.close()
        # plane off: no deadline minted at all
        r3 = _router([_FakeReplica("a")])
        try:
            assert r3.submit(_prompt(4), 2).deadline is None
        finally:
            r3.close()

    def test_hedge_first_result_wins_loser_cancelled(self):
        """A short request stuck past the adaptive threshold hedges on
        the other replica; the first result wins, the loser's record
        is discarded and its rid best-effort cancelled."""
        reps = {"a": _FakeReplica("a"), "b": _FakeReplica("b")}
        reps["a"].hold = reps["b"].hold = True
        r = _router(list(reps.values()),
                    reliability=ReliabilityConfig(hedge_min_samples=4))
        try:
            plane = r._rel
            for _ in range(8):
                plane.latency.observe(1e-4)  # warm: threshold ~0.1ms
            t = r.submit(_prompt(4), 4)
            _wait_dispatched([t])
            assert t.replica is not None
            time.sleep(0.01)  # age the in-flight past the threshold
            r._poll_once()  # sweep: hedge fires
            assert t.hedged and t.hedge_replica is not None
            assert t.hedge_replica != t.replica
            primary, hedge = t.replica, t.hedge_replica
            reps[hedge].hold = False  # hedge side completes first
            r._poll_once()
            t.wait(timeout=10)
            assert t.ok
            assert plane.hedges == 1 and plane.hedge_wins == 1
            # the loser's duplicate record is discarded, not served
            reps[primary].hold = False
            r._poll_once()
            time.sleep(0.05)  # cancel runs on a daemon thread
            assert r.stats()["served"] == 1
            assert t.replica_rid in reps[primary].cancels
        finally:
            r.close()

    def test_quarantine_leaves_placement_half_open_probe_restores(self):
        reps = {"a": _FakeReplica("a"), "b": _FakeReplica("b")}
        r = _router(list(reps.values()),
                    reliability=ReliabilityConfig(
                        consecutive_timeouts=2,
                        quarantine_cooldown_s=0.05))
        try:
            plane = r._rel
            h = plane.health("a")
            h.note_timeout()
            h.note_timeout()
            r._poll_once()  # sweep trips the breaker
            assert r.stats()["quarantined"] == ["a"]
            assert plane.quarantines == 1
            assert h.state == "open"
            # quarantined replicas leave placement entirely (3 tickets
            # through a 2-slot survivor: drive polls until drained)
            n_a = len(reps["a"].submits)
            ts = [r.submit(_prompt(4, i), 2) for i in range(3)]
            deadline = time.time() + 10
            while not all(t.done.is_set() for t in ts) \
                    and time.time() < deadline:
                r._poll_once()
                time.sleep(0.005)
            r.wait(ts, timeout=1)
            assert all(t.replica == "b" for t in ts)
            assert len(reps["a"].submits) == n_a
            # autoscaler-visible capacity loss: the signals snapshot
            # counts the quarantined replica out of live slots
            sig = r.signals()
            assert sig["quarantined"] == 1 and sig["replicas"] == 1
            # cooldown expires -> half-open probe -> restored
            time.sleep(0.06)
            r._poll_once()  # launches the probe thread
            deadline = time.time() + 10
            while r.stats()["quarantined"] and time.time() < deadline:
                time.sleep(0.01)
            assert r.stats()["quarantined"] == []
            assert h.state == "closed"
            t2 = r.submit(_prompt(4, 9), 2)
            _wait_dispatched([t2])
            r._poll_once()
            assert t2.wait(timeout=10).ok
        finally:
            r.close()

    def test_lone_replica_never_self_quarantines(self):
        """Slow beats unservable: the last placeable replica stays in
        rotation no matter how gray it looks."""
        a = _FakeReplica("a")
        r = _router([a], reliability=ReliabilityConfig(
            consecutive_timeouts=2))
        try:
            h = r._rel.health("a")
            for _ in range(5):
                h.note_timeout()
            r._poll_once()
            assert r.stats()["quarantined"] == []
            t = r.submit(_prompt(4), 2)
            _wait_dispatched([t])
            r._poll_once()
            assert t.wait(timeout=10).ok
        finally:
            r.close()

    def test_zero_cost_when_disabled(self, monkeypatch):
        """Router(reliability=None) executes NO reliability code on the
        hot path — every plane entry point is patched to raise, and a
        full submit/complete/retry cycle must never touch one."""
        def boom(*a, **kw):
            raise AssertionError("reliability code ran on the "
                                 "disabled hot path")

        monkeypatch.setattr(rel.Deadline, "after", boom)
        monkeypatch.setattr(rel.Deadline, "check", boom)
        monkeypatch.setattr(rel.RetryBudget, "take", boom)
        monkeypatch.setattr(rel.RetryBudget, "note_success", boom)
        monkeypatch.setattr(rel.LatencyTracker, "observe", boom)
        monkeypatch.setattr(rel.ReplicaHealth, "note_latency", boom)
        monkeypatch.setattr(rel.ReplicaHealth, "note_timeout", boom)
        monkeypatch.setattr(rel.ReliabilityPlane, "statusz", boom)
        monkeypatch.setattr(rel, "bind", boom)
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], poll_interval_s=0.01)
        try:
            with FaultInjector().on("router.dispatch",
                                    error=OSError, at=(2,)):
                ts = [r.submit(_prompt(4, i), 2) for i in range(4)]
                done = r.wait(ts, timeout=30)
            assert all(t.ok for t in done.values())
            assert any(t.retries for t in done.values())
            assert r.stats()["reliability"] is None
        finally:
            r.close()


# ---------------------------------------------------------------------------
# Arena-side deadline enforcement (real decoder: queue sweep + per-tick)
# ---------------------------------------------------------------------------

def _decoder():
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder

    pt.seed(0)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    return BatchedDecoder(model, slots=2, capacity=128, pages=16,
                          page_size=64)


def test_prefill_export_checks_deadline_before_compute():
    """An expired request never reaches the prefill executable: the
    export path raises typed BEFORE any device work."""
    worker = _decoder()
    with rel.bind(Deadline(time.time() - 1.0)):
        with pytest.raises(DeadlineExceededError, match="prefill export"):
            worker.prefill_export(_prompt(40, 1))
    # unexpired: same call goes through
    with rel.bind(Deadline.after(60.0)):
        h = worker.prefill_export(_prompt(40, 1))
    assert h.deadline is not None  # the handoff carries it onward


def test_arena_expires_queued_and_slot_resident_requests_typed():
    """The decode arena drops expired work typed at both edges: the
    admit sweep (expired while QUEUED — zero prefill work) and the
    per-decode-tick sweep (expired while slot-resident)."""
    rep = LocalReplica(_decoder(), name="r0")
    # queued-expired: dropped before any prefill work
    with rel.bind(Deadline(time.time() - 1.0)):
        rid = rep.submit(_prompt(8, 5), 8)
    rep._tick_locked()
    rec = rep.drain_results()[rid]
    assert rec["deadline_exceeded"] and rec["tokens"] is None
    # slot-resident: admitted live (deadline healthy), then the
    # deadline passes mid-decode and the per-tick sweep tears it down
    dl = Deadline.after(60.0)
    with rel.bind(dl):
        rid2 = rep.submit(_prompt(8, 6), 32)
    rep._tick_locked()  # admit + prefill + first step
    assert rep.decoder._dl_active == 1
    dl.t_end = time.time() - 1.0  # the budget runs out mid-stream
    rec2 = None
    for _ in range(4):
        rep._tick_locked()
        got = rep.drain_results()
        if rid2 in got:
            rec2 = got[rid2]
            break
    assert rec2 is not None, "expired slot never drained"
    assert rec2["deadline_exceeded"] and rec2["tokens"] is None
    assert rep.decoder._dl_active == 0  # sweep re-disarms itself


# ---------------------------------------------------------------------------
# Chaos e2e (slow tier; ci.sh mid runs these as the "reliability smoke"
# stage via -m chaos)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_retry_budget_exhaustion_is_deterministic_e2e():
    """Every dispatch fails (seeded injector, no schedule = broken
    period): the request retries exactly ``capacity`` times, then dies
    with the ONE typed RetryBudgetExhaustedError — never a retry
    storm. Counters pin the exact token arithmetic."""
    reps = [_FakeReplica(n) for n in ("a", "b", "c")]
    r = _router(reps, poll_interval_s=0.01,
                reliability=ReliabilityConfig(retry_budget=2.0,
                                              retry_refill=0.0,
                                              hedge=False,
                                              consecutive_timeouts=100))
    try:
        with FaultInjector().on("router.dispatch", error=OSError):
            t = r.submit(_prompt(4), 2)
            with pytest.raises(RetryBudgetExhaustedError):
                t.wait(timeout=60)
        assert t.retries == 2  # capacity spent, then surfaced
        snap = r._rel.budget.snapshot()
        assert snap["spent"] == 2 and snap["exhausted"] == 1
        assert snap["tokens"] == 0.0
    finally:
        r.close()


@pytest.mark.chaos
def test_sigstop_worker_quarantined_hedge_completes_sigcont_restores(
        tmp_path):
    """SIGSTOP a worker process while its requests are in flight: the
    probe timeouts feed the breaker (gray, NOT dead — the socket
    accepts, then silence), the victim is quarantined within the
    consecutive-timeout window, stuck in-flight requests hedge onto
    the survivor and every request completes within its deadline with
    the retry budget intact. SIGCONT + cooldown: the half-open probe
    restores the victim to rotation."""
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    for rep in reps:
        rep.timeout_s = 3.0  # bound every blocked hop on the victim
    r = Router(reps, poll_interval_s=0.05, health_fails=100,
               reliability=ReliabilityConfig(
                   deadline_s=240.0, hedge_min_samples=4,
                   consecutive_timeouts=2, quarantine_cooldown_s=1.0,
                   probe_timeout_s=120.0))
    stopped = None
    try:
        # warm both replicas + the fleet latency tracker (>=4 samples)
        warm = [r.submit(_prompt(8 + i, i), 8) for i in range(6)]
        r.wait(warm, timeout=300)
        assert r._rel.hedge_threshold() is not None
        # longer decodes: a window where requests are IN FLIGHT
        ts = [r.submit(_prompt(10 + i, 50 + i), 48) for i in range(4)]
        deadline = time.time() + 120
        victim = None
        while time.time() < deadline:
            placed = [t.replica for t in ts if t.replica is not None
                      and not t.done.is_set()]
            if placed:
                victim = next(rp for rp in reps if rp.name == placed[0])
                break
            time.sleep(0.01)
        assert victim is not None, "no request observed in flight"
        os.kill(victim.proc.pid, signal.SIGSTOP)
        stopped = victim
        # every request still completes, within its deadline, typed
        # failures nowhere: hedges/retries rescue the stuck ones
        r.wait(ts, timeout=300)
        assert all(t.ok for t in ts), "requests lost under SIGSTOP"
        # the breaker needs consecutive probe timeouts (each bounded
        # by rep.timeout_s) to call the silence gray — give it the
        # outlier window, then pin the quarantine
        deadline = time.time() + 120
        while victim.name not in r.stats()["quarantined"] \
                and time.time() < deadline:
            time.sleep(0.1)
        stats = r.stats()
        relz = stats["reliability"]
        assert victim.name in stats["quarantined"], \
            f"victim not quarantined: {relz['replicas']}"
        assert relz["quarantines"] >= 1
        assert relz["hedges"] >= 1, "no stuck request was hedged"
        assert relz["budget"]["exhausted"] == 0  # retries under budget
        # SIGCONT -> cooldown -> half-open probe restores the replica
        os.kill(victim.proc.pid, signal.SIGCONT)
        stopped = None
        deadline = time.time() + 240
        while r.stats()["quarantined"] and time.time() < deadline:
            time.sleep(0.1)
        assert r.stats()["quarantined"] == [], \
            "half-open probe never restored the victim"
        assert r._rel.health(victim.name).state == "closed"
        # the restored replica serves again
        t2 = r.submit(_prompt(12, 99), 8)
        assert t2.wait(timeout=300).ok
    finally:
        if stopped is not None:
            os.kill(stopped.proc.pid, signal.SIGCONT)
        r.close(replicas=True)
