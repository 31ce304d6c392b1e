"""Autoscaling control plane (paddle_tpu/autoscale): the deterministic
hysteresis+cooldown policy, recorded-signal replay bit-identity, the
acting Scaler over a live router (spawn from the artifact shelf, drain
and retire on sustained headroom), drain fail-closed placement, and chaos
(spawn failure, SIGKILL mid-scale-up / mid-drain).

Three tiers, mirroring test_serving_router.py: pure-policy units and
stub-replica scaler tests (no jax work), an in-process e2e over real
tiny-GPT replicas, and slow-marked subprocess chaos e2es."""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import telemetry
from paddle_tpu.autoscale import (AutoscalePolicy, Scaler, SignalTrace,
                                  replay)
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.models import gpt as G
from paddle_tpu.resilience import FaultInjector
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.serving_router import (LocalReplica, NoReplicasError,
                                       Router, spawn_replicas)

from replica_spec import SPEC, worker_env


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _sig(t, **kw):
    """One synthetic Router.signals() row (+ scaler-derived fields)
    with quiet defaults — tests override the fields under test."""
    row = {"t": float(t), "queue_depth": 0, "in_flight": 0, "slots": 2,
           "ewma_wait_s": None, "replicas": 1, "ready": 1, "warming": 0,
           "draining": 0, "shed_delta": 0}
    row.update(kw)
    return row


def _policy(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("up_queue_wait_s", 0.25)
    kw.setdefault("up_load", 2.0)
    kw.setdefault("headroom_hold_s", 30.0)
    kw.setdefault("cooldown_up_s", 10.0)
    kw.setdefault("cooldown_down_s", 30.0)
    kw.setdefault("ttfr_hint_s", 5.0)
    return AutoscalePolicy(**kw)


# ---------------------------------------------------------------------------
# The policy (pure function of the signal row + its own cooldown state)
# ---------------------------------------------------------------------------

class TestPolicy:
    def test_knob_validation_is_typed(self):
        with pytest.raises(EnforceError, match="min_replicas"):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(EnforceError, match="down_load"):
            AutoscalePolicy(up_load=1.0, down_load=1.5)
        with pytest.raises(EnforceError, match="down_queue_wait_s"):
            AutoscalePolicy(up_queue_wait_s=0.1, down_queue_wait_s=0.2)
        with pytest.raises(EnforceError, match="windows"):
            AutoscalePolicy(cooldown_up_s=-1)

    def test_knobs_clone_roundtrip(self):
        p = _policy(min_replicas=2, max_replicas=5, up_load=3.0)
        assert AutoscalePolicy(**p.knobs()).knobs() == p.knobs()

    def test_hot_load_scales_up(self):
        p = _policy()
        d = p.decide(_sig(0.0, in_flight=6, slots=2))
        assert (d["action"], d["reason"], d["target"]) == ("up", "hot", 2)

    def test_shed_is_an_immediate_up_vote(self):
        d = _policy().decide(_sig(0.0, shed_delta=1))
        assert d["action"] == "up" and d["reason"] == "hot"

    def test_queue_wait_scales_up_only_while_busy(self):
        p = _policy()
        # a stale EWMA over an IDLE fleet is history, not pressure:
        # the wait vote needs work actually present
        d = p.decide(_sig(0.0, ewma_wait_s=5.0))
        assert d["action"] == "hold"
        d = p.decide(_sig(1.0, ewma_wait_s=5.0, in_flight=1))
        assert d["action"] == "up"

    def test_cooldown_uses_measured_ttfr(self):
        p = _policy(cooldown_up_s=10.0)
        hot = dict(in_flight=6, slots=2)
        assert p.decide(_sig(0.0, **hot))["action"] == "up"
        # measured TTFR 4s rides the row: effective cooldown 14s
        d = p.decide(_sig(12.0, ttfr_s=4.0, replicas=2, **hot))
        assert (d["action"], d["reason"]) == ("hold", "hot_cooldown")
        d = p.decide(_sig(14.5, ttfr_s=4.0, replicas=2, **hot))
        assert d["action"] == "up"

    def test_warming_gates_further_spawns(self):
        p = _policy()
        d = p.decide(_sig(0.0, in_flight=9, slots=2, warming=1,
                          replicas=2))
        assert (d["action"], d["reason"]) == ("hold", "hot_warming")

    def test_hot_at_max_holds(self):
        d = _policy(max_replicas=2).decide(
            _sig(0.0, in_flight=9, slots=4, replicas=2))
        assert (d["action"], d["reason"]) == ("hold", "hot_at_max")

    def test_below_min_repair_beats_cooldown(self):
        p = _policy(min_replicas=2, cooldown_up_s=100.0)
        assert p.decide(_sig(0.0, in_flight=9, slots=2,
                             replicas=2))["action"] == "up"
        # replica died at t=1: repair fires INSIDE the up-cooldown
        d = p.decide(_sig(1.0, replicas=1))
        assert (d["action"], d["reason"]) == ("up", "below_min")
        # ... but one spawn at a time
        d = p.decide(_sig(1.5, replicas=1, warming=1))
        assert d["reason"] == "below_min_warming"

    def test_above_max_drains(self):
        p = _policy(max_replicas=2)
        d = p.decide(_sig(0.0, replicas=3))
        assert (d["action"], d["reason"]) == ("down", "above_max")
        assert p.decide(_sig(0.1, replicas=3,
                             draining=1))["reason"] == \
            "above_max_draining"

    def test_sustained_headroom_scales_down(self):
        p = _policy(headroom_hold_s=30.0, cooldown_down_s=10.0)
        for t in (0.0, 10.0, 20.0, 29.0):
            assert p.decide(_sig(t, replicas=2))["action"] == "hold"
        d = p.decide(_sig(30.0, replicas=2))
        assert (d["action"], d["reason"]) == ("down",
                                              "sustained_headroom")

    def test_headroom_window_resets_on_load_blip(self):
        p = _policy(headroom_hold_s=30.0)
        for t in (0.0, 10.0, 20.0):
            p.decide(_sig(t, replicas=2))
        # one busy tick at t=25 restarts the clock: the window only
        # re-opens at the next cold tick (t=31), so the hold must
        # last until t=61
        p.decide(_sig(25.0, replicas=2, queue_depth=1))
        assert p.decide(_sig(31.0, replicas=2))["action"] == "hold"
        assert p.decide(_sig(55.1, replicas=2))["action"] == "hold"
        assert p.decide(_sig(61.1, replicas=2))["action"] == "down"

    def test_idle_with_stale_wait_ewma_is_still_cold(self):
        # the router's wait EWMA updates only on dispatches, so it
        # stays frozen-high after a burst: TRUE idleness (nothing in
        # flight, nothing queued) must read as headroom anyway, or
        # scale-down never fires on a real router
        p = _policy(headroom_hold_s=5.0, cooldown_down_s=1.0)
        for t in (0.0, 2.0, 4.0):
            assert p.decide(_sig(t, replicas=2,
                                 ewma_wait_s=9.9))["action"] == "hold"
        assert p.decide(_sig(5.0, replicas=2,
                             ewma_wait_s=9.9))["action"] == "down"

    def test_never_drains_below_min(self):
        p = _policy(min_replicas=2, headroom_hold_s=1.0)
        for t in range(0, 50, 5):
            d = p.decide(_sig(float(t), replicas=2))
            assert (d["action"], d["reason"]) == ("hold", "steady")

    def test_never_tears_down_what_a_spike_just_built(self):
        p = _policy(headroom_hold_s=5.0, cooldown_down_s=30.0,
                    cooldown_up_s=1.0, ttfr_hint_s=0.0)
        assert p.decide(_sig(0.0, in_flight=6,
                             slots=2))["action"] == "up"
        for t in (1.0, 3.0, 6.0, 20.0):
            d = p.decide(_sig(t, replicas=2))
            assert d["action"] == "hold", d
        assert p.decide(_sig(20.0, replicas=2))["reason"] == \
            "cold_post_up"
        assert p.decide(_sig(35.0, replicas=2))["action"] == "down"

    def test_max_events_is_the_cooldown_implied_ceiling(self):
        p = _policy(cooldown_up_s=10.0, ttfr_hint_s=5.0,
                    cooldown_down_s=30.0, headroom_hold_s=20.0)
        # 60s: up every 15s -> 4+1; down every max(30,20)=30s -> 2+1
        assert p.max_events(60.0) == 8
        # a measured TTFR overrides the hint
        assert p.max_events(60.0, ttfr_s=20.0) == 6


# ---------------------------------------------------------------------------
# Replay bit-identity + the trace substrate
# ---------------------------------------------------------------------------

def _diurnal_rows(n=240, dt=1.0):
    """A deterministic synthetic diurnal/spiky day: quiet, morning
    ramp, a 3x spike, decay back to quiet — every field decide()
    reads, derived from the tick index alone."""
    rows = []
    for i in range(n):
        t = i * dt
        if i < 60:
            in_flight = i % 2
        elif i < 90:            # ramp
            in_flight = 2 + (i - 60) // 6
        elif i < 130:           # spike
            in_flight = 9 + (i % 3)
        else:                   # decay to idle
            in_flight = max(0, 8 - (i - 130) // 4)
        rows.append(_sig(t, in_flight=in_flight,
                         queue_depth=max(0, in_flight - 4),
                         slots=4, replicas=2,
                         ewma_wait_s=0.05 * in_flight,
                         ttfr_s=1.5))
    return rows


class TestReplay:
    def test_replay_is_bit_identical_and_flap_bounded(self):
        rows = _diurnal_rows()
        p = _policy(min_replicas=1, max_replicas=4,
                    up_queue_wait_s=0.3, up_load=1.5,
                    headroom_hold_s=10.0, cooldown_up_s=5.0,
                    cooldown_down_s=15.0, ttfr_hint_s=1.0)
        d1 = replay(p, rows)
        d2 = replay(AutoscalePolicy(**p.knobs()), rows)
        assert json.dumps(d1, sort_keys=True) == \
            json.dumps(d2, sort_keys=True)
        acted = [d for d in d1 if d["action"] != "hold"]
        assert any(d["action"] == "up" for d in acted)
        assert any(d["action"] == "down" for d in acted)
        # the no-flap contract: cooldown-implied ceiling holds over
        # the whole diurnal trace
        assert len(acted) <= p.max_events(240.0, ttfr_s=1.5)

    def test_trace_jsonl_roundtrip_replays_identically(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        tr = SignalTrace(path)
        rows = _diurnal_rows(n=40)
        for r in rows:
            tr.append(r)
        tr.close()
        loaded = SignalTrace.load(path)
        assert len(loaded) == 40
        p = _policy(headroom_hold_s=5.0, cooldown_down_s=5.0)
        assert replay(p, loaded.rows) == replay(p, rows)


# ---------------------------------------------------------------------------
# The Scaler over stub replicas (no jax — deterministic ticks)
# ---------------------------------------------------------------------------

class _FakeReplica:
    """Replica-interface stub (test_serving_router idiom): completes
    on drain unless held, dies on demand."""

    def __init__(self, name, slots=2):
        self.name = name
        self.slots = slots
        self.dead = False
        self.hold = False
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
            self._pending[rid] = {
                "tokens": np.arange(max_new, dtype=np.int32),
                "ttft_s": 0.001, "itl_p99_s": 0.0005,
                "n_tokens": max_new}
        return rid

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
    kw.setdefault("poll_interval_s", 0.01)
    kw.setdefault("dispatchers", 1)
    return Router(replicas, **kw)


def _fast_policy(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 2)
    kw.setdefault("up_queue_wait_s", 0.2)
    kw.setdefault("up_load", 1.5)
    kw.setdefault("headroom_hold_s", 0.1)
    kw.setdefault("cooldown_up_s", 0.05)
    kw.setdefault("cooldown_down_s", 0.05)
    kw.setdefault("ttfr_hint_s", 0.0)
    return AutoscalePolicy(**kw)


def _until(pred, timeout=20.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


class TestScalerStub:
    def test_spike_spawns_then_headroom_retires(self):
        a = _FakeReplica("a")
        r = _router([a])
        sc = Scaler(r, _fast_policy(), lambda: _FakeReplica("b"),
                    interval_s=0.05)
        try:
            a.hold = True
            ts = [r.submit(np.arange(4, dtype=np.int32), 2)
                  for _ in range(6)]
            _until(lambda: r.signals()["in_flight"] >= 3,
                   msg="dispatches in flight")
            d = sc.tick()
            assert d["action"] == "up" and d["reason"] == "hot"
            _until(lambda: r.stats()["replicas"] == 2,
                   msg="spawned replica joined")
            # ttfr_s is stamped by the spawn thread just after the
            # replica joins — poll, don't assert the instant
            _until(lambda: sc.ttfr_s is not None, msg="ttfr measured")
            a.hold = False
            r.wait(ts, timeout=60)
            assert all(t.ok for t in ts)
            # idle ticks: sustained headroom -> drain -> remove
            _until(lambda: (sc.tick() is not None
                            and sc._live_count() == 1),
                   msg="fleet drained back to min")
            names = set(r.replicaz()["replicas"])
            assert len(names) == 1
            ups = [e for e in sc.scale_events()
                   if e["event"] == "scale_up"]
            downs = [e for e in sc.scale_events()
                     if e["event"] == "scale_down"]
            assert len(ups) == 1 and len(downs) == 1
            assert max(n for _, n in sc.timeline) == 2
            assert sc.timeline[-1][1] == 1
            assert sc.replica_seconds() > 0
            # the surviving fleet still serves
            t = r.submit(np.arange(4, dtype=np.int32), 2)
            r.wait([t], timeout=60)
            assert t.ok
        finally:
            sc.stop()
            r.close()

    def test_live_trace_replays_bit_identically(self):
        a = _FakeReplica("a")
        r = _router([a])
        sc = Scaler(r, _fast_policy(), lambda: _FakeReplica("b"),
                    interval_s=0.05)
        try:
            a.hold = True
            ts = [r.submit(np.arange(4, dtype=np.int32), 2)
                  for _ in range(6)]
            _until(lambda: r.signals()["in_flight"] >= 3,
                   msg="in flight")
            sc.tick()
            _until(lambda: r.stats()["replicas"] == 2, msg="spawn")
            a.hold = False
            r.wait(ts, timeout=60)
            for _ in range(8):
                sc.tick()
                time.sleep(0.02)
            twin = replay(AutoscalePolicy(**sc.policy.knobs()),
                          sc.trace.rows)
            assert json.dumps(twin, sort_keys=True) == \
                json.dumps(sc.decisions, sort_keys=True)
        finally:
            sc.stop()
            r.close()

    def test_spawn_failure_is_counted_and_retried(self):
        a = _FakeReplica("a")
        r = _router([a])
        built = []

        def spawn():
            built.append(1)
            return _FakeReplica("b")

        sc = Scaler(r, _fast_policy(), spawn, interval_s=0.05)
        inj = FaultInjector().on("autoscale.spawn", times=1)
        try:
            with inj:
                a.hold = True
                ts = [r.submit(np.arange(4, dtype=np.int32), 2)
                      for _ in range(6)]
                _until(lambda: r.signals()["in_flight"] >= 3,
                       msg="in flight")
                d = sc.tick()
                assert d["action"] == "up"
                _until(lambda: sc.spawn_failures == 1,
                       msg="spawn failure recorded")
                # the injected death never built a replica; the fleet
                # is unchanged and the failure event is typed
                assert not built
                assert r.stats()["replicas"] == 1
                assert any(e["event"] == "spawn_failed"
                           for e in sc.events)
                # past the cooldown the policy re-fires and the next
                # attempt (injector budget spent) succeeds
                time.sleep(0.1)
                _until(lambda: sc.tick() is not None
                       and r.stats()["replicas"] == 2,
                       msg="retry spawned")
                assert built
            a.hold = False
            r.wait(ts, timeout=60)
            assert all(t.ok for t in ts)
        finally:
            sc.stop()
            r.close()

    def test_victim_is_least_loaded_and_floor_guarded(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], poll_interval_s=30)
        sc = Scaler(r, _fast_policy(), lambda: None, interval_s=1.0)
        try:
            a.hold = b.hold = True
            ts = [r.submit(np.arange(4, dtype=np.int32), 2,
                           session="s0") for _ in range(2)]
            _until(lambda: any(t.replica for t in ts),
                   msg="placement")
            # the session pins both tickets to one replica; the
            # other idles and is the victim
            home = next(t.replica for t in ts if t.replica)
            idle = "b" if home == "a" else "a"
            r._poll_once()
            assert sc._pick_victim() == idle
            # at the floor there is no victim at all
            sc.policy.min_replicas = 2
            assert sc._pick_victim() is None
            a.hold = b.hold = False
            r.wait(ts, timeout=60)
        finally:
            sc.stop()
            r.close()

    def test_statusz_counters_and_trace_events(self):
        telemetry.enable()
        a = _FakeReplica("a")
        r = _router([a])
        sc = Scaler(r, _fast_policy(), lambda: _FakeReplica("b"),
                    interval_s=0.05)
        try:
            a.hold = True
            ts = [r.submit(np.arange(4, dtype=np.int32), 2)
                  for _ in range(6)]
            _until(lambda: r.signals()["in_flight"] >= 3,
                   msg="in flight")
            sc.tick()
            _until(lambda: r.stats()["replicas"] == 2, msg="spawn")
            a.hold = False
            r.wait(ts, timeout=60)
            st = sc.statusz()
            for key in ("policy", "ttfr_s", "spawning", "draining",
                        "spawn_failures", "decisions",
                        "last_decision", "scale_events", "events",
                        "replica_seconds", "timeline"):
                assert key in st, key
            assert st["policy"] == sc.policy.knobs()
            reg = telemetry.registry()
            assert reg.get("pt_autoscale_decisions_total",
                           {"action": "up"}).value >= 1
            assert reg.get("pt_autoscale_scale_ups_total").value >= 1
            assert reg.get("pt_autoscale_target_replicas").value >= 1
            assert reg.get("pt_autoscale_ttfr_seconds").value > 0
            from paddle_tpu.telemetry import tracing
            names = {s["name"] for s in tracing.spans()
                     if s["name"].startswith("autoscale.")}
            assert {"autoscale.decision", "autoscale.scale_up"} <= \
                names
        finally:
            sc.stop()
            r.close()


# ---------------------------------------------------------------------------
# Drain fail-closed: placement dies the moment draining flips
# ---------------------------------------------------------------------------

class TestDrainFailClosed:
    def test_drain_purges_affinity_and_blocks_new_placements(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b])
        try:
            t0 = r.submit(np.arange(4, dtype=np.int32), 2,
                          session="s0")
            r.wait([t0], timeout=60)
            home = t0.replica
            other = "b" if home == "a" else "a"
            # session stickiness holds pre-drain
            t1 = r.submit(np.arange(4, dtype=np.int32), 2,
                          session="s0")
            r.wait([t1], timeout=60)
            assert t1.replica == home
            r.drain_replica(home)
            # fail-closed: the NEXT same-session submit places away
            # immediately — no grace window on a draining replica
            t2 = r.submit(np.arange(4, dtype=np.int32), 2,
                          session="s0")
            r.wait([t2], timeout=60)
            assert t2.replica == other
            assert r.stats()["draining"] == 1
        finally:
            r.close()

    def test_prefix_home_moves_off_draining_replica(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b], prefix_hash_tokens=8,
                    disagg_min_tokens=None)
        try:
            prefix = np.arange(1, 33, dtype=np.int32)
            t0 = r.submit(prefix, 2, session="f0")
            r.wait([t0], timeout=60)
            home = t0.replica
            t1 = r.submit(prefix, 2, session="f1")
            r.wait([t1], timeout=60)
            assert t1.replica == home  # prefix-hash stickiness
            r.drain_replica(home)
            t2 = r.submit(prefix, 2, session="f2")
            r.wait([t2], timeout=60)
            assert t2.replica != home
        finally:
            r.close()

    def test_inflight_drains_on_same_replica_then_removal(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b])
        try:
            t0 = r.submit(np.arange(4, dtype=np.int32), 2,
                          session="s0")
            r.wait([t0], timeout=60)
            home_rep = a if t0.replica == "a" else b
            home_rep.hold = True
            t1 = r.submit(np.arange(4, dtype=np.int32), 8,
                          session="s0")
            _until(lambda: t1.replica == home_rep.name,
                   msg="in-flight dispatch on home")
            r.drain_replica(home_rep.name)
            assert not r.drain_done(home_rep.name)  # still in flight
            home_rep.hold = False
            r.wait([t1], timeout=60)
            # the in-flight request FINISHED on the draining replica:
            # same placement, zero retries — drain never tears streams
            assert t1.ok and t1.replica == home_rep.name
            assert t1.retries == 0
            _until(lambda: r.drain_done(home_rep.name),
                   msg="drain done")
            r.remove_replica(home_rep.name, close=True)
            assert r.stats()["replicas"] == 1
            t2 = r.submit(np.arange(4, dtype=np.int32), 2)
            r.wait([t2], timeout=60)
            assert t2.ok and t2.replica != home_rep.name
        finally:
            r.close()

    def test_remove_refuses_live_undrained_replica(self):
        a, b = _FakeReplica("a"), _FakeReplica("b")
        r = _router([a, b])
        try:
            with pytest.raises(EnforceError, match="drain"):
                r.remove_replica("a")
        finally:
            r.close()


# ---------------------------------------------------------------------------
# In-process e2e over real tiny-GPT replicas (the mid-tier smoke body)
# ---------------------------------------------------------------------------

def _decoder(slots=2, capacity=128, pages=16, seed=0, **kw):
    pt.seed(seed)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    return BatchedDecoder(model, slots=slots, capacity=capacity,
                          pages=pages, page_size=64, **kw)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


def test_scaler_spawn_retire_e2e_real_replicas():
    """The ci.sh 'scaler smoke' e2e body: a burst over one real
    replica trips the policy, a pre-warmed replica joins mid-load
    (the artifact-shelf path), every request completes, sustained
    headroom drains the fleet back to one, and the recorded trace
    replays bit-identically."""
    r0 = LocalReplica(_decoder(), name="r0").start()
    r0.warmup()
    shelf = [LocalReplica(_decoder(), name="r1").start()]
    shelf[0].warmup()
    router = Router([r0], poll_interval_s=0.02)
    policy = _fast_policy(headroom_hold_s=0.3, cooldown_up_s=0.1,
                          cooldown_down_s=0.2)
    sc = Scaler(router, policy, lambda: shelf.pop(0),
                interval_s=0.05).start()
    try:
        ts = [router.submit(_prompt(8 + i, i), 6, session=f"s{i}")
              for i in range(12)]
        router.wait(ts, timeout=300)
        assert all(t.ok for t in ts)
        assert any(e["event"] == "scale_up"
                   for e in sc.scale_events()), sc.events
        assert sc.ttfr_s is not None and sc.ttfr_s > 0
        # idle: the scaler retires the spawned replica
        _until(lambda: sc._live_count() == 1, timeout=30,
               msg="drained back to min")
        # the event is recorded AFTER the victim left the fleet and was
        # closed (Scaler._drain_bg), so wait for it as well
        _until(lambda: any(e["event"] == "scale_down"
                           for e in sc.scale_events()), timeout=30,
               msg="scale_down recorded")
        sc.stop()
        assert max(n for _, n in sc.timeline) == 2
        assert sc.timeline[-1][1] == 1
        assert sc.replica_seconds() > 0
        twin = replay(AutoscalePolicy(**policy.knobs()),
                      sc.trace.rows)
        assert json.dumps(twin, sort_keys=True) == \
            json.dumps(sc.decisions, sort_keys=True)
        # the shrunk fleet still serves
        t = router.submit(_prompt(6, 99), 4)
        router.wait([t], timeout=300)
        assert t.ok
    finally:
        sc.stop()
        router.close(replicas=True)


def test_retired_replica_inflight_stream_keeps_trace_id():
    """ISSUE 18 regression: a replica being scale-down-drained stops
    receiving session-affinity placements IMMEDIATELY, but its
    in-flight token stream finishes on the SAME replica under the
    SAME trace id with zero retries."""
    telemetry.enable()
    reps = [LocalReplica(_decoder(), name=f"r{i}").start()
            for i in range(2)]
    for rep in reps:
        rep.warmup()
    router = Router(reps, poll_interval_s=0.02)
    try:
        t0 = router.submit(_prompt(8, 1), 2, session="s0")
        router.wait([t0], timeout=300)
        home = t0.replica
        other = next(r.name for r in reps if r.name != home)
        t1 = router.submit(_prompt(10, 2), 24, session="s0",
                           stream=True)
        _until(lambda: t1.replica == home, timeout=60,
               msg="stream dispatched to the affinity home")
        tid = t1.trace.trace_id
        router.drain_replica(home)
        # new same-session work places away at once (fail-closed)
        t2 = router.submit(_prompt(8, 3), 2, session="s0")
        router.wait([t1, t2], timeout=300)
        assert t2.ok and t2.replica == other
        # the in-flight stream finished where it started, one trace
        assert t1.ok and t1.replica == home and t1.retries == 0
        assert t1.trace.trace_id == tid
        assert len(t1.tokens) == 24
        _until(lambda: router.drain_done(home), timeout=60,
               msg="drain settles")
        router.remove_replica(home, close=True)
        t3 = router.submit(_prompt(8, 4), 2, session="s0")
        router.wait([t3], timeout=300)
        assert t3.ok and t3.replica == other
    finally:
        router.close(replicas=True)


# ---------------------------------------------------------------------------
# Chaos: SIGKILL mid-scale-up and mid-drain (subprocess workers; slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_mid_scale_up_converges(tmp_path):
    """SIGKILL the worker a scale-up is booting: the spawn attempt
    fails typed (PT-AS-701 path), the fleet stays serving, and the
    policy's next window retries to convergence — no request lost."""
    reps = spawn_replicas(SPEC, 1,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, poll_interval_s=0.05, health_fails=2)
    attempts = []

    def spawn():
        idx = len(attempts) + 1
        attempts.append(idx)
        if idx > 1:
            # the retry: a normal boot — spawn_replicas blocks until
            # the worker warms and flips ready
            return spawn_replicas(SPEC, 1,
                                  spec_kw={"smoke": True},
                                  log_dir=str(tmp_path),
                                  env=worker_env(),
                                  start_index=idx)[0]
        # attempt 1 boots --no-warm (ready stays down until warmup,
        # giving a wide mid-boot window) and the chaos kills it there
        rep = spawn_replicas(SPEC, 1,
                             spec_kw={"smoke": True},
                             log_dir=str(tmp_path), warm=False,
                             env=worker_env(), start_index=idx)[0]
        os.kill(rep.proc.pid, signal.SIGKILL)
        deadline = time.time() + 300
        while time.time() < deadline:
            if rep.proc.poll() is not None:
                raise OSError(f"worker {rep.name} died mid-boot")
            time.sleep(0.2)
        raise OSError("worker never became ready")

    policy = _fast_policy(cooldown_up_s=0.2, headroom_hold_s=60.0,
                          cooldown_down_s=60.0)
    sc = Scaler(router, policy, spawn, interval_s=0.2).start()
    try:
        ts = [router.submit(_prompt(8 + i, i), 6, session=f"s{i}")
              for i in range(10)]
        router.wait(ts, timeout=600)
        assert all(t.ok for t in ts), "requests lost during chaos"
        _until(lambda: router.stats()["replicas"] == 2, timeout=300,
               msg="fleet converged to the policy target")
        assert sc.spawn_failures == 1
        assert any(e["event"] == "spawn_failed" for e in sc.events)
        assert len(attempts) == 2
        sc.stop()
        # all replicas down -> typed error, not a hang
        for rep in list(router.replicaz()["replicas"]):
            h = router._replicas[rep].replica
            os.kill(h.proc.pid, signal.SIGKILL)
        t = router.submit(_prompt(5, 99), 4)
        with pytest.raises(NoReplicasError):
            t.wait(timeout=120)
    finally:
        sc.stop()
        router.close(replicas=True)


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_drain_target_mid_drain(tmp_path):
    """SIGKILL the drain VICTIM mid-drain (a delay rule on the
    autoscale.drain point widens the window): the health loop requeues
    its in-flight work onto the survivor, drain_done reports true for
    the dead replica, the removal completes, and the fleet converges
    with no request lost."""
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, poll_interval_s=0.05, health_fails=2)
    policy = _fast_policy(headroom_hold_s=0.3, cooldown_up_s=60.0,
                          cooldown_down_s=0.3)
    sc = Scaler(router, policy, lambda: None, interval_s=0.1)
    inj = FaultInjector().on("autoscale.drain", delay_s=1.5, times=1)
    try:
        # warm traffic across both replicas
        ts = [router.submit(_prompt(8 + i, i), 4, session=f"s{i}")
              for i in range(4)]
        router.wait(ts, timeout=300)
        with inj:
            sc.start()
            # idle fleet of 2 over min 1 -> the scaler picks a victim
            # and enters the (delayed) drain
            _until(lambda: sc._draining_name is not None,
                   timeout=60, msg="drain began")
            victim = sc._draining_name
            vict_rep = next(r for r in reps if r.name == victim)
            # mid-drain: land work on the fleet, then kill the victim
            ts2 = [router.submit(_prompt(6 + i, 50 + i), 4,
                                 session=f"t{i}") for i in range(4)]
            os.kill(vict_rep.proc.pid, signal.SIGKILL)
            router.wait(ts2, timeout=600)
            assert all(t.ok for t in ts2), "requests lost mid-drain"
            survivor = next(r.name for r in reps if r.name != victim)
            assert all(t.replica == survivor for t in ts2)
            _until(lambda: victim not in
                   router.replicaz()["replicas"],
                   timeout=120, msg="dead victim removed")
            assert any(e["event"] == "scale_down"
                       and e["replica"] == victim
                       for e in sc.events), sc.events
        # fleet converged at the floor and still serves
        t = router.submit(_prompt(5, 99), 4)
        router.wait([t], timeout=300)
        assert t.ok and t.replica == survivor
    finally:
        sc.stop()
        router.close(replicas=True)
