"""Elastic fleet controller (paddle_tpu/resilience/controller.py):
coordination transports, the preempt-at-step agreement protocol, the
metadata notice watcher, /podz pod-level aggregation, typed
barrier-timeout diagnostics, and the launch.py fail-fast + --elastic
N-1 restart paths — unit tiers in-process, the multi-rank invariants
as deterministic subprocess e2e (chaos tier)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

import paddle_tpu.launch as launch_mod
from paddle_tpu import resilience, telemetry
from paddle_tpu import checkpoint as ckpt_mod
from paddle_tpu.resilience import (BarrierTimeoutError, FaultInjector,
                                   FleetController)
from paddle_tpu.resilience.controller import (ENV_FLEET_DIR,
                                              ENV_NOTICE, ENV_RUN_ID,
                                              FileNotice,
                                              FileTransport,
                                              HttpNotice,
                                              auto_transport,
                                              notice_source_from_env)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    yield
    telemetry.disable()


def _controller(tmp_path, rank, world, **kw):
    kw.setdefault("poll_interval_s", 0.0)
    kw.setdefault("hold_poll_s", 0.005)
    kw.setdefault("agree_timeout_s", 5.0)
    kw.setdefault("commit_timeout_s", 5.0)
    return FleetController(
        rank=rank, world=world,
        transport=FileTransport(str(tmp_path / "fleet"), "t1"), **kw)


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------

class TestTransports:
    def test_file_transport_roundtrip_and_namespacing(self, tmp_path):
        a = FileTransport(str(tmp_path), "runA")
        b = FileTransport(str(tmp_path), "runB")
        a.put("preempt.ack.0", "7")
        assert a.get("preempt.ack.0") == "7"
        # a different run's key namespace is invisible: a dead
        # attempt's acks can never read as live preemption state
        assert b.get("preempt.ack.0") is None
        assert a.get("nope") is None

    def test_sweep_removes_only_stale_foreign_keys(self, tmp_path):
        old = FileTransport(str(tmp_path), "runOld", stale_age_s=0.0)
        old.put("preempt.ack.0", "3")
        time.sleep(0.02)
        new = FileTransport(str(tmp_path), "runNew", stale_age_s=0.0)
        new.put("debug.0", "x")
        removed = new.sweep()
        assert removed == 1
        assert new.get("debug.0") == "x"  # own keys survive
        assert old.get("preempt.ack.0") is None

    def test_auto_transport_file_fallback_honors_env(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setenv(ENV_FLEET_DIR, str(tmp_path / "f"))
        monkeypatch.setenv(ENV_RUN_ID, "envrun")
        t = auto_transport()
        # no coordination client in a plain test process → file
        assert t.kind == "file"
        assert t.root == str(tmp_path / "f")
        assert t.run_id == "envrun"


# ---------------------------------------------------------------------------
# Notice sources + the metadata watcher
# ---------------------------------------------------------------------------

class TestNoticeSources:
    def test_from_env(self, monkeypatch):
        monkeypatch.delenv(ENV_NOTICE, raising=False)
        assert notice_source_from_env() is None
        monkeypatch.setenv(ENV_NOTICE, "/tmp/notice")
        src = notice_source_from_env()
        assert isinstance(src, FileNotice)
        assert src.describe() == "file:/tmp/notice"
        monkeypatch.setenv(ENV_NOTICE, "http://meta/x")
        src = notice_source_from_env()
        assert isinstance(src, HttpNotice)
        assert src.url == "http://meta/x"

    def test_watcher_raises_flag_on_file_notice(self, tmp_path):
        notice = tmp_path / "notice"
        ctl = FleetController(rank=0, world=1,
                              notice_source=FileNotice(str(notice)),
                              watch_interval_s=0.01)
        ctl.start()
        try:
            assert ctl.check(3) is None  # no notice yet
            notice.write_text("1")
            deadline = time.time() + 5
            while not ctl.handler.requested() and \
                    time.time() < deadline:
                time.sleep(0.01)
            assert ctl.handler.requested()
            assert ctl.request_reason == "notice"
            # the watcher is one-shot: it exits after raising the flag
            ctl._watcher.join(timeout=5)
            assert not ctl._watcher.is_alive()
            # and the next check starts the (world=1) agreement
            assert ctl.check(4) == 4
        finally:
            ctl.stop()

    def test_fleet_notice_injection_point_is_deterministic(self,
                                                           tmp_path):
        """A seeded FaultInjector corrupt rule at ``fleet.notice``
        injects a synthetic preemption notice on an exact watcher
        poll — the metadata path becomes a deterministic chaos test."""
        ctl = FleetController(
            rank=0, world=1,
            notice_source=FileNotice(str(tmp_path / "never")),
            watch_interval_s=0.01)
        inj = FaultInjector(seed=11).on("fleet.notice", at=(3,),
                                        corrupt=True)
        with inj:
            ctl.start()
            try:
                deadline = time.time() + 5
                while not ctl.handler.requested() and \
                        time.time() < deadline:
                    time.sleep(0.01)
                assert ctl.handler.requested()
                assert inj.fired["fleet.notice"] == 1
                assert inj.calls["fleet.notice"] == 3
            finally:
                ctl.stop()


# ---------------------------------------------------------------------------
# The preempt-at-step agreement
# ---------------------------------------------------------------------------

class TestAgreement:
    def test_world_one_agrees_on_own_step(self, tmp_path):
        ctl = FleetController(rank=0, world=1)
        assert ctl.check(5) is None
        ctl.request()
        assert ctl.check(5) == 5
        assert ctl.agreed_step == 5
        assert ctl.confirm_committed(5) == {0: 5}

    def test_two_ranks_agree_on_max_ack(self, tmp_path):
        c0 = _controller(tmp_path, 0, 2)
        c1 = _controller(tmp_path, 1, 2)
        c1.request()
        got = {}

        def rank1():
            got["c1"] = c1.check(7)  # acks 7, holds for rank 0

        t = threading.Thread(target=rank1, name="pt-test-rank1")
        t.start()
        try:
            deadline = time.time() + 5
            while c0.check(12) is None and time.time() < deadline:
                time.sleep(0.01)  # until rank 1's ack becomes visible
        finally:
            t.join(timeout=10)
        # agreed = max(acks): rank 0 was ahead, nobody rewinds — the
        # held rank catches up to 12 instead
        assert got["c1"] == 12
        assert c0.agreed_step == 12 and c1.agreed_step == 12
        assert c1.acked_step == 7

    def test_simultaneous_sigterm_both_ranks(self, tmp_path):
        """The launcher-relay case: every rank is signaled at once and
        proposes its own step; the agreement still lands on one max."""
        c0 = _controller(tmp_path, 0, 2)
        c1 = _controller(tmp_path, 1, 2)
        c0.request()
        c1.request()
        out = {}

        def run(name, ctl, step):
            out[name] = ctl.check(step)

        ts = [threading.Thread(target=run, args=("c0", c0, 5),
                               name="pt-test-r0"),
              threading.Thread(target=run, args=("c1", c1, 9),
                               name="pt-test-r1")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert out == {"c0": 9, "c1": 9}

    def test_agreement_timeout_is_typed_and_names_missing(self,
                                                          tmp_path):
        c1 = _controller(tmp_path, 1, 2, agree_timeout_s=0.3)
        c1.request()
        with pytest.raises(BarrierTimeoutError) as ei:
            c1.check(4)
        assert ei.value.missing == [0]
        assert ei.value.world == 2
        assert "preempt-agreement" in str(ei.value)

    def test_timeout_bumps_barrier_timeouts_counter(self, tmp_path):
        telemetry.enable()
        try:
            c1 = _controller(tmp_path, 1, 2, agree_timeout_s=0.2)
            c1.request()
            with pytest.raises(BarrierTimeoutError):
                c1.check(4)
            c = telemetry.registry().get("pt_barrier_timeouts_total")
            assert c is not None and c.value >= 1
        finally:
            telemetry.disable()

    def test_dead_rank_is_dropped_from_agreement(self, tmp_path):
        """The launcher's fail-fast marker: survivors agree among the
        live ranks instead of holding for a corpse (the satellite's
        'survivors hang in the next barrier' fix)."""
        c1 = _controller(tmp_path, 1, 2, agree_timeout_s=2.0)
        c1.transport.put("dead.0", "1")
        c1.request()
        assert c1.check(6) == 6  # live set is {1}: instant agreement
        assert c1.confirm_committed(6) == {1: 6}

    def test_dead_ranks_published_ack_still_bounds_the_max(
            self, tmp_path):
        """A rank that acked and THEN died still contributed its step:
        every survivor computes the same agreed max regardless of when
        the dead marker landed relative to its own wait — otherwise
        two survivors could commit DIFFERENT steps with rc 0."""
        c1 = _controller(tmp_path, 1, 3)
        c1.transport.put("preempt.ack.0", "4")
        c1.transport.put("preempt.ack.2", "10")
        c1.transport.put("dead.2", "1")  # rank 2 died after acking
        c1.request()
        assert c1.check(4) == 10  # not max(live acks) = 4

    def test_hot_path_peek_is_one_key(self, tmp_path):
        """The throttled no-preemption sample reads ONE well-known
        key, not world-1 per-peer keys — O(1) at any fleet size."""
        c0 = _controller(tmp_path, 0, 16, poll_interval_s=0.0)
        reads = []
        orig = c0.transport.get

        def spy(key):
            reads.append(key)
            return orig(key)

        c0.transport.get = spy
        assert c0.check(3) is None
        assert reads == ["preempt.flag"]

    def test_done_rank_is_dropped_from_agreement(self, tmp_path):
        """A rank that cleanly finished its data announces done.<rank>
        on exit; a later preemption agrees among the ranks still
        running instead of timing out on the one that left."""
        c0 = _controller(tmp_path, 0, 2)
        c1 = _controller(tmp_path, 1, 2)
        c1.note_done(11)
        c0.request()
        assert c0.check(4) == 4  # live set is {0}: instant agreement
        assert c0.confirm_committed(4) == {0: 4}
        assert c0.podz()["ranks"]["1"]["done_at_step"] == 11

    def test_launcher_file_markers_visible_on_client_transport(
            self, tmp_path, monkeypatch):
        """The launcher writes dead markers to the FILE root no matter
        which transport the workers coordinate over — a controller on
        the coordination-service KV must still see them."""
        class _KV:  # a stand-in coordination-service client store
            def __init__(self):
                self.d = {}

            def key_value_set(self, k, v):
                self.d[k] = v

            def key_value_try_get(self, k):
                return self.d.get(k)

        from paddle_tpu.resilience.controller import ClientTransport

        monkeypatch.setenv(ENV_FLEET_DIR, str(tmp_path / "fleet"))
        c1 = FleetController(
            rank=1, world=2, run_id="cx",
            transport=ClientTransport(_KV(), "cx"),
            agree_timeout_s=2.0, poll_interval_s=0.0,
            hold_poll_s=0.005)
        # the launcher-side marker (plain file, FileTransport layout)
        launch_mod._mark_dead(str(tmp_path / "fleet"), "cx", 0)
        c1.request()
        assert c1.check(8) == 8  # file marker dropped rank 0
        assert c1.confirm_committed(8) == {1: 8}

    def test_confirm_committed_gathers_all_ranks(self, tmp_path):
        c0 = _controller(tmp_path, 0, 2)
        c1 = _controller(tmp_path, 1, 2)
        out = {}

        def rank1():
            out["v"] = c1.confirm_committed(9)

        t = threading.Thread(target=rank1, name="pt-test-commit1")
        t.start()
        try:
            out["w"] = c0.confirm_committed(9)
        finally:
            t.join(timeout=10)
        assert out["v"] == {0: 9, 1: 9}
        assert out["w"] == {0: 9, 1: 9}
        assert c0.last_committed_step == 9

    def test_check_is_cheap_until_preempted(self, tmp_path):
        """Hot-path contract: with no preemption in flight, check() is
        an Event peek + a time-throttled transport sample."""
        c0 = _controller(tmp_path, 0, 2, poll_interval_s=3600.0)
        peeks = []
        orig = c0.transport.get

        def spy(key):
            peeks.append(key)
            return orig(key)

        c0.transport.get = spy
        for s in range(50):
            assert c0.check(s) is None
        assert peeks == []  # throttle never elapsed → zero transport IO


# ---------------------------------------------------------------------------
# Typed barrier diagnostics on the checkpoint transport
# ---------------------------------------------------------------------------

class TestBarrierDiagnostics:
    def test_file_barrier_timeout_names_missing_ranks(self, tmp_path):
        target = str(tmp_path / "ckpt" / "step_1")
        os.makedirs(os.path.dirname(target))
        before = ckpt_mod.barrier_stats()["timeouts"]
        with pytest.raises(BarrierTimeoutError) as ei:
            ckpt_mod._file_barrier(target, "diag1", rank=1, world=3,
                                   timeout_s=0.3)
        # ranks 0 and 2 never published; we (rank 1) did
        assert ei.value.missing == [0, 2]
        assert ei.value.world == 3
        assert ckpt_mod.barrier_stats()["timeouts"] == before + 1

    def test_file_barrier_timeout_counts_metric(self, tmp_path):
        telemetry.enable()
        try:
            target = str(tmp_path / "ckpt" / "step_1")
            os.makedirs(os.path.dirname(target))
            c = telemetry.registry().counter(
                "pt_barrier_timeouts_total")
            before = c.value
            with pytest.raises(BarrierTimeoutError):
                ckpt_mod._file_barrier(target, "diag2", rank=0,
                                       world=2, timeout_s=0.2)
            assert c.value == before + 1
        finally:
            telemetry.disable()

    def test_barrier_timeout_is_enforce_error(self):
        # drive loops must PROPAGATE it (never 'recover' a half-agreed
        # fleet into silent divergence) — EnforceError is the
        # non-recoverable class TrainLoop already excludes
        from paddle_tpu.core.enforce import EnforceError

        assert issubclass(BarrierTimeoutError, EnforceError)


# ---------------------------------------------------------------------------
# /statusz + /podz
# ---------------------------------------------------------------------------

class TestStatusAndPodz:
    def test_resilience_statusz_reports_controller_view(self, tmp_path):
        assert resilience.statusz()["controller"] == {"active": False}
        ctl = _controller(tmp_path, 0, 2,
                          notice_source=FileNotice(str(tmp_path / "n")))
        ctl.start()
        try:
            view = resilience.statusz()["controller"]
            assert view["active"] is True
            assert view["rank"] == 0 and view["world_size"] == 2
            assert view["transport"] == "file"
            assert view["notice_source"].startswith("file:")
            assert view["agreed_preempt_step"] is None
            assert "last_barrier_latency_s" in view
            ctl.note_checkpoint(15)
            assert resilience.statusz()["controller"][
                "last_checkpoint_step"] == 15
        finally:
            ctl.stop()
        assert resilience.statusz()["controller"] == {"active": False}

    def test_podz_aggregates_both_ranks(self, tmp_path):
        """Two debug servers + two controllers sharing one transport:
        any rank's /podz fans out to every rank's /healthz + /statusz
        + /memz and distills one fleet view."""
        from paddle_tpu.telemetry.server import DebugServer

        c0 = _controller(tmp_path, 0, 2)
        c1 = _controller(tmp_path, 1, 2)
        s0 = DebugServer(port=0, owned=True).start()
        s1 = DebugServer(port=0, owned=True).start()
        try:
            c0.start()
            c0.publish_endpoint(s0.host, s0.port)
            c1.publish_endpoint(s1.host, s1.port)
            s0.set_fleet(c0.podz)
            s0.note("step")
            s1.note("step")
            with urllib.request.urlopen(s0.url("/podz"),
                                        timeout=10) as r:
                pod = json.loads(r.read().decode())
            assert pod["world_size"] == 2
            assert pod["aggregator_rank"] == 0
            assert pod["agreed_preempt_step"] is None
            rows = pod["ranks"]
            assert set(rows) == {"0", "1"}
            for r_ in ("0", "1"):
                row = rows[r_]
                assert row["endpoint"] is not None
                assert row["dead"] is False
                assert row["heartbeat_age_s"] is not None
                assert "preempt" in row  # the /statusz controller view
                assert "peak_mem_bytes" in row
        finally:
            c0.stop()
            s0.stop()
            s1.stop()

    def test_podz_404_without_controller(self):
        from paddle_tpu.telemetry.server import DebugServer

        srv = DebugServer(port=0).start()
        try:
            with urllib.request.urlopen(srv.url("/")) as r:
                assert "/podz" not in json.loads(r.read().decode())[
                    "endpoints"]
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url("/podz"), timeout=10)
            assert ei.value.code == 404
        finally:
            srv.stop()

    def test_podz_row_carries_global_commit_columns(self, tmp_path):
        """Satellite: rank rows show ``last_committed_global`` (and the
        local staged step) next to the local last-committed step, so
        commit drift across the fleet is visible at a glance."""
        from paddle_tpu.telemetry.server import DebugServer

        c0 = _controller(tmp_path, 0, 1)
        c0.last_global_commit_step = 7
        c0.last_staged_step = 9
        s0 = DebugServer(port=0, owned=True).start()
        try:
            c0.start()
            c0.publish_endpoint(s0.host, s0.port)
            s0.set_fleet(c0.podz)
            pod = c0.podz()
            assert pod["last_committed_global"] == 7
            row = pod["ranks"]["0"]
            assert row["last_committed_global"] == 7
            assert row["last_staged_step"] == 9
            view = c0.statusz()
            assert view["last_global_commit_step"] == 7
            assert view["last_staged_step"] == 9
            assert "last_commit_barrier_s" in view
        finally:
            c0.stop()
            s0.stop()

    def test_commit_lag_gauge_tracks_drift(self, tmp_path):
        """``pt_checkpoint_commit_lag_steps``: staged-ahead-of-global
        distance; snaps back to 0 when the fleet commit catches up."""
        telemetry.enable()
        try:
            c0 = _controller(tmp_path, 0, 2)
            c0.note_stage(5)
            g = telemetry.registry().get(
                "pt_checkpoint_commit_lag_steps")
            assert g is not None and g.value == 5.0
            c0.transport.put("ckpt.staged.5.1", "5")
            c0.wait_global_commit(5)
            assert g.value == 0.0
        finally:
            telemetry.disable()

    def test_podz_marks_dead_and_unreachable_ranks(self, tmp_path):
        c0 = _controller(tmp_path, 0, 3)
        c0.transport.put("dead.2", "1")
        c0.transport.put("debug.1", "127.0.0.1:1")  # nothing listens
        pod = c0.podz()
        assert pod["ranks"]["2"]["dead"] is True
        assert pod["ranks"]["0"]["endpoint"] is None  # unpublished
        assert "error" in pod["ranks"]["1"]["healthz"]


# ---------------------------------------------------------------------------
# TrainLoop integration (in-process)
# ---------------------------------------------------------------------------

class TestTrainLoopCoordinated:
    def test_single_rank_commits_agreed_step(self, tmp_path):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_resilience import batches, make_loop

        ctl = FleetController(rank=0, world=1)
        loop = make_loop(tmp_path / "ckpt", checkpoint_every=100)

        def on_step(step, loss, metrics):
            if step == 3:
                ctl.request()

        n = loop.run(batches(20), on_step=on_step, controller=ctl)
        assert n == 3
        assert loop.status == "preempted"
        assert loop.history["preempt_agreed_step"] == 3
        assert loop.manager.latest_step() == 3
        assert ctl.last_committed_step == 3
        assert not ctl.started  # run() owned the start/stop pair

        # and maybe_resume lands on the agreed step
        loop2 = make_loop(tmp_path / "ckpt", checkpoint_every=100)
        assert loop2.maybe_resume() == 3

    def test_completed_loop_announces_done(self, tmp_path):
        """A loop that exhausts num_steps under a controller publishes
        done.<rank>, so peers never hold an agreement for it."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_resilience import batches, make_loop

        c0 = _controller(tmp_path, 0, 2)
        loop = make_loop(tmp_path / "ckpt", checkpoint_every=100)
        n = loop.run(batches(10), num_steps=2, controller=c0)
        assert n == 2 and loop.status == "completed"
        assert c0.transport.get("done.0") == "2"
        # the other rank now preempts alone, instantly
        c1 = _controller(tmp_path, 1, 2)
        c1.request()
        assert c1.check(5) == 5

    def test_explicit_preemption_handler_shares_controller_flag(
            self, tmp_path):
        """preemption= alongside controller=: the user's handler and
        the controller must share ONE flag, or a signal on the
        handler would never start the fleet agreement."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_resilience import batches, make_loop
        from paddle_tpu.resilience import PreemptionHandler

        h = PreemptionHandler()
        ctl = FleetController(rank=0, world=1)
        loop = make_loop(tmp_path / "ckpt", checkpoint_every=100)

        def on_step(step, loss, metrics):
            if step == 2:
                h.request()

        n = loop.run(batches(10), on_step=on_step, preemption=h,
                     controller=ctl)
        assert n == 2
        assert loop.status == "preempted"
        assert ctl.handler is h
        assert loop.manager.latest_step() == 2

    def test_two_inprocess_ranks_commit_same_agreed_step(self,
                                                         tmp_path):
        """The protocol end-to-end without subprocesses: two loops +
        two controllers over one file transport; a request on rank 0
        makes BOTH commit the same agreed step (rank 0 catches up to
        the faster rank's ack — max, never a rewind)."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_resilience import batches, make_loop

        c0 = _controller(tmp_path, 0, 2, poll_interval_s=0.01,
                         agree_timeout_s=30.0, commit_timeout_s=30.0)
        c1 = _controller(tmp_path, 1, 2, poll_interval_s=0.01,
                         agree_timeout_s=30.0, commit_timeout_s=30.0)
        loop0 = make_loop(tmp_path / "ckpt0", checkpoint_every=1000)
        loop1 = make_loop(tmp_path / "ckpt1", checkpoint_every=1000)
        err = []

        def rank1():
            try:
                loop1.run(batches(4000), controller=c1)
            except BaseException as e:  # surfaced in the assert below
                err.append(e)

        t = threading.Thread(target=rank1, name="pt-test-loop1")

        def on_step(step, loss, metrics):
            if step == 2:
                t.start()
            if step == 6:
                c0.request()

        loop0.run(batches(4000), on_step=on_step, controller=c0)
        t.join(timeout=120)
        assert not t.is_alive()
        assert not err, f"rank 1 failed: {err}"
        assert loop0.status == "preempted"
        assert loop1.status == "preempted"
        agreed = c0.agreed_step
        assert agreed is not None and agreed == c1.agreed_step
        assert loop0.manager.latest_step() == agreed
        assert loop1.manager.latest_step() == agreed
        assert loop0.history["preempt_agreed_step"] == agreed
        # commit confirmation saw both ranks at the same step
        assert c0.committed_view == {0: agreed, 1: agreed}


# ---------------------------------------------------------------------------
# launch.py: fail-fast + elastic (stdlib worker scripts — fast)
# ---------------------------------------------------------------------------

_STUBBORN_RANK0 = textwrap.dedent("""
    import os, signal, sys, time
    rank = os.environ["PADDLE_TRAINER_ID"]
    if rank == "1":
        sys.exit(3)  # the failing worker
    signal.signal(signal.SIGTERM, signal.SIG_IGN)  # a wedged survivor
    time.sleep(120)
""")

_ELASTIC_STUB = textwrap.dedent("""
    import os, signal, sys, time
    base = sys.argv[1]
    rank = os.environ["PADDLE_TRAINER_ID"]
    run_id = os.environ["PT_FLEET_RUN_ID"]
    flag = []
    # handler BEFORE the seen-file: rank 1 dies as soon as it sees the
    # file, and the launcher's SIGTERM must find the handler installed
    signal.signal(signal.SIGTERM, lambda *a: flag.append(1))
    with open(os.path.join(base, f"seen.{rank}.{run_id}"), "w") as f:
        f.write("1")
    if run_id.endswith("a1"):
        sys.exit(0)  # the restarted attempt completes
    t0 = time.time()
    if rank == "1":
        # first attempt: rank 1 dies — once rank 0 is demonstrably up
        # (on a loaded host it could otherwise be torn down mid-start)
        peer = os.path.join(base, f"seen.0.{run_id}")
        while not os.path.exists(peer) and time.time() - t0 < 30:
            time.sleep(0.02)
        sys.exit(5)
    while not flag and time.time() - t0 < 60:
        time.sleep(0.02)
    sys.exit(0)  # clean coordinated-style exit within grace
""")


class TestLaunchTeardown:
    def test_fail_fast_kills_stubborn_survivor_within_grace(
            self, tmp_path):
        """Satellite: a non-zero worker exit fail-fasts the peers —
        SIGTERM, then a hard kill when the grace window expires —
        instead of letting a survivor wedged in a dead rank's barrier
        hang the launcher forever."""
        script = tmp_path / "w.py"
        script.write_text(_STUBBORN_RANK0)
        log_dir = str(tmp_path / "logs")
        t0 = time.time()
        rc = launch_mod.launch(str(script), [], nproc=2,
                               log_dir=log_dir, grace=1.5)
        wall = time.time() - t0
        assert rc == 3  # the failing rank's code, not the kill's
        assert wall < 30, f"teardown took {wall:.1f}s"
        # the dead marker reached the fleet transport namespace
        fleet_dir = os.path.join(log_dir, "fleet")
        run_id = f"L{os.getpid()}a0"
        assert os.path.exists(
            os.path.join(fleet_dir, f"{run_id}.dead.1"))

    def test_elastic_respawns_on_n_minus_one(self, tmp_path):
        script = tmp_path / "w.py"
        script.write_text(_ELASTIC_STUB)
        base = str(tmp_path)
        rc = launch_mod.launch(str(script), [base], nproc=2,
                               log_dir=str(tmp_path / "logs"),
                               grace=10.0, elastic=True)
        assert rc == 0
        run0, run1 = (f"L{os.getpid()}a0", f"L{os.getpid()}a1")
        # attempt 0 ran both ranks; the restart ran ONE worker,
        # re-ranked 0, in a fresh coordination namespace
        assert os.path.exists(os.path.join(base, f"seen.0.{run0}"))
        assert os.path.exists(os.path.join(base, f"seen.1.{run0}"))
        assert os.path.exists(os.path.join(base, f"seen.0.{run1}"))
        assert not os.path.exists(os.path.join(base, f"seen.1.{run1}"))

    def test_elastic_respects_min_procs(self, tmp_path):
        script = tmp_path / "w.py"
        script.write_text("import sys; sys.exit(9)\n")
        rc = launch_mod.launch(str(script), [], nproc=2,
                               log_dir=str(tmp_path / "logs"),
                               grace=2.0, elastic=True, min_procs=2)
        assert rc == 9  # no restart below min_procs

    def test_worker_env_carries_fleet_transport(self):
        env = launch_mod.build_worker_env(
            1, 2, ["h:1", "h:2"], base_env={}, fleet_dir="/fd",
            run_id="rid")
        assert env["PT_FLEET_DIR"] == "/fd"
        assert env["PT_FLEET_RUN_ID"] == "rid"
        assert env["PADDLE_TRAINER_ID"] == "1"


# ---------------------------------------------------------------------------
# Subprocess e2e: the acceptance invariants (chaos tier)
# ---------------------------------------------------------------------------

_FLEET_WORKER = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)

    base = sys.argv[1]
    mode = sys.argv[2] if len(sys.argv) > 2 else "train"
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    run_id = os.environ.get("PT_FLEET_RUN_ID", "r0")

    def put(name, payload):
        path = os.path.join(base, name)
        with open(path + ".w", "w") as f:
            json.dump(payload, f)
        os.replace(path + ".w", path)

    from paddle_tpu import fleet
    from paddle_tpu.resilience import BarrierTimeoutError, FaultInjector

    ctl = fleet.controller(
        agree_timeout_s=float(os.environ.get("T_AGREE", "60")),
        commit_timeout_s=60.0, poll_interval_s=0.05,
        watch_interval_s=0.1)
    put(f"pid.{{rank}}.{{run_id}}", {{"pid": os.getpid()}})

    if mode == "stall":
        # the coordinator that never acks (chaos: killed mid-agreement)
        ctl.start()
        time.sleep(180)
        sys.exit(0)

    import numpy as np
    import jax, jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models import mnist as M
    from paddle_tpu.train_loop import TrainLoop

    # deterministic chaos substrate: pinned seed, every checkpoint
    # file write slowed so the commit window is real
    FaultInjector(seed=7).on("io.slow", delay_s=0.002).arm()
    pt.seed(0)
    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    tr = parallel.Trainer.supervised(
        M.MnistMLP(hidden1=16, hidden2=8), optimizer.Adam(1e-3),
        M.loss_fn, mesh=mesh)
    rng = np.random.default_rng(rank)

    def batches(n):
        for _ in range(n):
            yield {{"x": jnp.asarray(rng.normal(size=(4, 784))
                                     .astype(np.float32)),
                    "label": jnp.asarray(rng.integers(0, 10, 4))}}

    loop = TrainLoop(tr, os.path.join(base, f"ckpt.{{rank}}"),
                     checkpoint_every=5, max_to_keep=50)
    loop.manager.async_save = False
    pace = float(os.environ.get("T_STEP", "0.02"))

    def on_step(step, loss, metrics):
        put(f"step.{{rank}}", {{"step": step}})
        time.sleep(pace)

    try:
        n = loop.run(batches(100000), num_steps=100000,
                     on_step=on_step, controller=ctl)
        put(f"out.{{rank}}.{{run_id}}",
            {{"status": loop.status, "final_step": n,
              "world": ctl.world,
              "resumed_from": loop.history.get("resumed_from"),
              "agreed": loop.history.get("preempt_agreed_step")}})
    except BarrierTimeoutError as e:
        put(f"out.{{rank}}.{{run_id}}",
            {{"status": "barrier_timeout", "missing": e.missing,
              "error": str(e)}})
        sys.exit(7)
""")


def _wait_for(cond, timeout, what, proc=None):
    deadline = time.time() + timeout
    while not cond():
        if proc is not None and proc.poll() is not None:
            raise AssertionError(
                f"process died early waiting for {what}:\n"
                f"{proc.stdout.read().decode()}")
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.05)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _committed_steps(ckpt_dir):
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(
        int(n.split("_")[1]) for n in os.listdir(ckpt_dir)
        if n.startswith("step_") and "." not in n
        and os.path.exists(os.path.join(ckpt_dir, n, "COMMITTED")))


@pytest.mark.slow
@pytest.mark.chaos
def test_coordinated_sigterm_both_ranks_commit_same_step(tmp_path):
    """Acceptance e2e (1): SIGTERM to ONE rank of a 2-rank job makes
    BOTH ranks commit one consistent checkpoint at the same agreed
    step, the job exits 0, and maybe_resume() lands on that step."""
    worker = tmp_path / "worker.py"
    worker.write_text(_FLEET_WORKER.format(repo=REPO))
    base = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PT_PREEMPT_NOTICE", None)
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.launch", "--nproc", "2",
         "--grace", "60", "--log-dir", str(tmp_path / "logs"),
         str(worker), base],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    run_id = f"L{p.pid}a0"
    try:
        _wait_for(lambda: all(
            os.path.exists(os.path.join(base, f"step.{r}"))
            and _read_json(os.path.join(base, f"step.{r}"))["step"] >= 3
            for r in (0, 1)), 240, "both ranks stepping", p)
        pid1 = _read_json(os.path.join(base, f"pid.1.{run_id}"))["pid"]
        os.kill(pid1, signal.SIGTERM)  # ONE rank only
        rc = p.wait(timeout=180)
    finally:
        if p.poll() is None:
            p.kill()
        p.stdout.close()
    assert rc == 0, p.stdout and "launcher failed"
    out0 = _read_json(os.path.join(base, f"out.0.{run_id}"))
    out1 = _read_json(os.path.join(base, f"out.1.{run_id}"))
    assert out0["status"] == "preempted", out0
    assert out1["status"] == "preempted", out1
    agreed = out1["agreed"]
    assert agreed is not None and out0["agreed"] == agreed
    # ONE consistent committed checkpoint at the agreed step, per rank
    assert _committed_steps(os.path.join(base, "ckpt.0"))[-1] == agreed
    assert _committed_steps(os.path.join(base, "ckpt.1"))[-1] == agreed

    # and a fresh loop resumes exactly there
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_resilience import make_loop

    loop = make_loop(os.path.join(base, "ckpt.0"),
                     checkpoint_every=100)
    assert loop.maybe_resume() == agreed


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_coordinator_killed_mid_agreement_is_typed_error(
        tmp_path):
    """Chaos variant: the coordinator (rank 0) dies mid-agreement
    (it started its controller but never acks); the surviving rank's
    hold expires into a typed BarrierTimeoutError naming rank 0 —
    never a hang."""
    worker = tmp_path / "worker.py"
    worker.write_text(_FLEET_WORKER.format(repo=REPO))
    base = str(tmp_path)
    fleet_dir = str(tmp_path / "fleet")

    def spawn(rank, mode):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PADDLE_TRAINER_ID=str(rank),
                   PADDLE_TRAINERS_NUM="2",
                   PT_FLEET_DIR=fleet_dir, PT_FLEET_RUN_ID="chaos1",
                   T_AGREE="4")
        env.pop("XLA_FLAGS", None)
        env.pop("PT_PREEMPT_NOTICE", None)
        log = open(os.path.join(base, f"log.{rank}"), "w")
        return subprocess.Popen(
            [sys.executable, str(worker), base, mode], env=env,
            stdout=log, stderr=subprocess.STDOUT), log

    p0, log0 = spawn(0, "stall")
    p1, log1 = spawn(1, "train")
    try:
        _wait_for(lambda: os.path.exists(
            os.path.join(base, "step.1")) and _read_json(
            os.path.join(base, "step.1"))["step"] >= 2,
            240, "rank 1 stepping")
        _wait_for(lambda: os.path.exists(
            os.path.join(base, "pid.0.chaos1")), 60, "rank 0 up")
        p0.kill()  # SIGKILL the coordinator mid-agreement window
        p0.wait(timeout=30)
        os.kill(p1.pid, signal.SIGTERM)  # survivor starts agreeing
        rc1 = p1.wait(timeout=120)
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
        log0.close()
        log1.close()
    out1 = _read_json(os.path.join(base, "out.1.chaos1"))
    assert out1["status"] == "barrier_timeout", out1
    assert out1["missing"] == [0]
    assert "timed out" in out1["error"]
    assert rc1 == 7  # the typed-error exit path, not a kill


@pytest.mark.slow
@pytest.mark.chaos
def test_elastic_n_minus_one_restart_resumes_committed_step(tmp_path):
    """Acceptance e2e (2): SIGKILL one rank of a 2-rank --elastic job.
    The launcher marks it dead (survivor exits clean within grace,
    committing its progress), respawns ONE worker in a fresh
    coordination namespace, and that worker RESUMES from the last
    committed checkpoint; a metadata notice then winds the job down
    cleanly (rc 0)."""
    worker = tmp_path / "worker.py"
    worker.write_text(_FLEET_WORKER.format(repo=REPO))
    base = str(tmp_path)
    notice = os.path.join(base, "notice")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PT_PREEMPT_NOTICE=notice, T_STEP="0.03")
    env.pop("XLA_FLAGS", None)
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.launch", "--nproc", "2",
         "--elastic", "--grace", "60",
         "--log-dir", str(tmp_path / "logs"), str(worker), base],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    run0, run1 = f"L{p.pid}a0", f"L{p.pid}a1"
    try:
        # wait until rank 0 has committed progress worth resuming
        _wait_for(lambda: len(_committed_steps(
            os.path.join(base, "ckpt.0"))) >= 1, 300,
            "a committed checkpoint on rank 0", p)
        committed_at_kill = _committed_steps(
            os.path.join(base, "ckpt.0"))[-1]
        pid1 = _read_json(os.path.join(base, f"pid.1.{run0}"))["pid"]
        os.kill(pid1, signal.SIGKILL)
        # the restarted attempt comes up re-ranked 0, world 1
        _wait_for(lambda: os.path.exists(
            os.path.join(base, f"pid.0.{run1}")), 240,
            "the elastic restart", p)
        _wait_for(lambda: os.path.exists(
            os.path.join(base, f"out.0.{run0}")), 120,
            "attempt 0 survivor exit record", p)
        with open(notice, "w") as f:
            f.write("TERMINATE")  # metadata notice winds the job down
        rc = p.wait(timeout=300)
    finally:
        if p.poll() is None:
            p.kill()
        p.stdout.close()
    assert rc == 0
    # the attempt-0 survivor exited via the coordinated path (the dead
    # marker dropped rank 1 from its agreement)
    out0_a0 = _read_json(os.path.join(base, f"out.0.{run0}"))
    assert out0_a0["status"] == "preempted", out0_a0
    # the restarted worker resumed from committed progress and trained on
    out = _read_json(os.path.join(base, f"out.0.{run1}"))
    assert out["world"] == 1
    assert out["status"] == "preempted", out
    assert out["resumed_from"] is not None
    assert out["resumed_from"] >= committed_at_kill
    assert out["final_step"] >= out["resumed_from"]
