"""Live diagnostics plane (telemetry/server.py + telemetry/diag.py):
debug HTTP endpoints, device-memory monitor, FlightRecorder ring +
anomaly watch + atomic dump bundles, and the wiring into TrainLoop,
BatchedDecoder, and the static Executor — including the acceptance
pins: an injected NaN loss triggers a dump bundle and the configured
policy (skip_step vs halt) is observably applied; with telemetry
disabled the same run executes no recorder/server code path."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.telemetry as telemetry
from paddle_tpu.telemetry import diag as tdiag
from paddle_tpu.telemetry import server as tserver
from paddle_tpu.telemetry.diag import AnomalyHalt, FlightRecorder
from paddle_tpu.train_loop import TrainLoop


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


def _no_server_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not [t for t in threading.enumerate()
                if t.name.startswith("pt-debug-server")]:
            return True
        time.sleep(0.05)
    return False


class StubTrainer:
    """Host-only trainer: no jax, no compile — the loop machinery under
    test, not the math. ``nan_at`` injects a NaN loss at that step."""

    def __init__(self, nan_at=None):
        self.n = 0
        self.nan_at = nan_at
        self.w = np.zeros(2, np.float32)
        self.restored_to = []

    def train_step(self, batch):
        self.n += 1
        loss = (np.float32("nan") if self.n == self.nan_at
                else np.float32(0.5))
        return loss, {}

    def state(self):
        return {"w": self.w}

    def restore_checkpoint(self, manager, step):
        self.restored_to.append(step)


def _batches(n, bs=4):
    for i in range(n):
        yield {"x": np.full((bs, 3), i, np.float32)}


# ---------------------------------------------------------------------------
# device-memory monitor
# ---------------------------------------------------------------------------

class TestDeviceMemory:
    def test_reports_every_device_and_labels_accounting(self):
        import jax
        import jax.numpy as jnp

        keep = jnp.ones((256, 4), jnp.float32)  # noqa: F841 (live bytes)
        entries = tdiag.device_memory()
        assert len(entries) == len(jax.devices())
        for e in entries:
            assert {"id", "platform", "kind", "memory_stats"} <= set(e)
            if e["memory_stats"] is None:
                # CPU fallback: live-array aggregation, labeled as such
                assert "live_array_bytes" in e
        total_live = sum(e.get("live_array_bytes", 0) for e in entries)
        assert total_live >= keep.nbytes

    def test_peak_is_none_without_backend_stats(self):
        # the CPU backend has no memory_stats(): the live-array view
        # must never masquerade as a peak in recorded numbers
        import jax

        if all(d.memory_stats() is None for d in jax.devices()):
            assert tdiag.peak_memory_bytes() is None


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_capacity_and_clean_steps(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), capacity=4)
        for i in range(10):
            assert fr.record_step(i, loss=0.1, step_time=0.01) is None
        assert len(fr.ring) == 4
        assert [e["step"] for e in fr.ring] == [6, 7, 8, 9]
        assert fr.dumps == [] and fr.anomalies == []

    def test_nan_loss_triggers_dump_with_full_bundle(self, tmp_path):
        telemetry.enable()
        telemetry.registry().counter("pt_x_total", "d").inc(3)
        telemetry.recompile.record("site", np.zeros((2, 2)))
        fr = FlightRecorder(str(tmp_path), policy="record",
                            run_config={"job": "t"})
        fr.record_step(1, loss=0.5)
        assert fr.record_step(2, loss=float("nan")) == "record"
        assert len(fr.dumps) == 1
        bundle = json.load(open(fr.dumps[0]))
        assert bundle["reason"] == "nan_loss"
        assert bundle["run_config"] == {"job": "t"}
        assert [e["step"] for e in bundle["ring"]] == [1, 2]
        assert bundle["ring"][-1]["anomaly"] == "nan_loss"
        assert "pt_x_total" in bundle["metrics"]
        assert bundle["recompile"]["site"]["signatures"] == 1
        assert bundle["device_memory"]
        assert bundle["anomalies"][0]["kind"] == "nan_loss"
        # atomic write: no temp droppings next to the bundle
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".tmp")]

    def test_grad_spike_and_stall_detection_after_warmup(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), policy="record",
                            warmup_steps=5, grad_spike_factor=10.0,
                            stall_factor=10.0)
        for i in range(5):
            assert fr.record_step(i, grad_norm=1.0,
                                  step_time=0.01) is None
        assert fr.record_step(5, grad_norm=100.0, step_time=0.01) \
            == "record"
        assert fr.anomalies[-1]["kind"] == "grad_spike"
        # the spike did NOT poison the baseline: a normal step is clean,
        # and a stalled one still triggers
        assert fr.record_step(6, grad_norm=1.1, step_time=0.01) is None
        assert fr.record_step(7, grad_norm=1.0, step_time=5.0) \
            == "record"
        assert fr.anomalies[-1]["kind"] == "step_stall"

    def test_no_spike_before_warmup(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), warmup_steps=10)
        for i in range(5):
            assert fr.record_step(i, grad_norm=10.0 ** i,
                                  step_time=0.01) is None

    def test_regime_change_flags_bounded_then_adapts(self, tmp_path):
        """A legitimate shift to a higher grad-norm regime flags a
        bounded number of times: flagged finite samples still feed the
        running mean, so the baseline catches up instead of freezing
        and flagging every later step forever."""
        fr = FlightRecorder(str(tmp_path), policy="record",
                            warmup_steps=3, grad_spike_factor=5.0,
                            max_dumps=1)
        for i in range(3):
            assert fr.record_step(i, grad_norm=1.0) is None
        flagged = [fr.record_step(10 + i, grad_norm=10.0) is not None
                   for i in range(20)]
        assert flagged[0] is True      # the shift itself is flagged
        assert not any(flagged[1:])    # ...then the baseline adapts

    def test_anomaly_log_is_bounded(self, tmp_path):
        """A run flagging every step keeps only the most recent
        MAX_ANOMALIES records; anomalies_total still counts them all."""
        fr = FlightRecorder(str(tmp_path), policy="record", max_dumps=0)
        n = FlightRecorder.MAX_ANOMALIES + 50
        for i in range(n):
            fr.record_step(i, loss=float("nan"))
        assert len(fr.anomalies) == FlightRecorder.MAX_ANOMALIES
        assert fr.anomalies_total == n
        assert fr.anomalies[0]["step"] == 50  # oldest dropped
        assert fr.dumps == []  # max_dumps=0: log only, no bundles

    def test_dump_rate_limit(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), policy="record", max_dumps=2)
        for i in range(5):
            assert fr.record_step(i, loss=float("nan")) == "record"
        assert len(fr.dumps) == 2
        assert len(fr.anomalies) == 5  # every anomaly still logged

    def test_bad_policy_is_loud(self, tmp_path):
        with pytest.raises(ValueError, match="policy"):
            FlightRecorder(str(tmp_path), policy="explode")

    def test_manual_dump(self, tmp_path):
        fr = FlightRecorder(str(tmp_path))
        fr.record_step(1, loss=0.25)
        path = fr.dump()
        bundle = json.load(open(path))
        assert bundle["reason"] == "manual"
        assert bundle["last_step"] == 1

    def test_dump_failure_never_kills_the_run(self, tmp_path,
                                              monkeypatch):
        """The recorder observes the run, it must not take it down: an
        unwritable dump_dir degrades to a noted failure and the policy
        still applies."""
        fr = FlightRecorder(str(tmp_path), policy="record")

        def boom(reason="manual"):
            raise OSError("disk full")

        monkeypatch.setattr(fr, "dump", boom)
        assert fr.record_step(1, loss=float("nan")) == "record"
        assert "disk full" in fr.anomalies[-1]["dump_error"]
        assert fr.dumps == []

    def test_peak_memory_requires_true_peak_key(self, monkeypatch):
        """bytes_in_use is a scrape-time snapshot, not a high-water
        mark — it must never be reported as peak_mem_bytes."""
        import jax

        class _Dev:
            def __init__(self, stats):
                self._stats = stats

            def memory_stats(self):
                return self._stats

        monkeypatch.setattr(jax, "devices", lambda: [
            _Dev({"bytes_in_use": 123}), _Dev(None)])
        assert tdiag.peak_memory_bytes() is None
        monkeypatch.setattr(jax, "devices", lambda: [
            _Dev({"peak_bytes_in_use": 77}),
            _Dev({"peak_bytes_in_use": 99})])
        assert tdiag.peak_memory_bytes() == 99


# ---------------------------------------------------------------------------
# debug server endpoints
# ---------------------------------------------------------------------------

class TestDebugServer:
    def test_endpoints_and_heartbeats(self):
        telemetry.registry().counter("pt_smoke_total", "d").inc()
        srv = tserver.DebugServer(port=0,
                                  run_config={"role": "test"}).start()
        try:
            assert telemetry.enabled()  # the port IS the opt-in
            code, body = _get(srv.url("/healthz"))
            h = json.loads(body)
            assert code == 200 and h["status"] == "ok"
            assert h["last_step_age_s"] is None
            tserver.note("step")
            tserver.note("request")
            h = json.loads(_get(srv.url("/healthz"))[1])
            assert h["last_step_age_s"] is not None
            assert h["last_request_age_s"] is not None

            code, body = _get(srv.url("/metrics"))
            assert code == 200 and "pt_smoke_total 1" in body

            s = json.loads(_get(srv.url("/statusz"))[1])
            assert s["backend"] == "cpu"
            assert s["device_count"] == len(s["devices"])
            assert s["telemetry_enabled"] is True
            assert s["run_config"] == {"role": "test"}
            assert "recompile" in s

            m = json.loads(_get(srv.url("/memz"))[1])
            assert len(m["devices"]) == s["device_count"]

            t = json.loads(_get(srv.url("/tracez"))[1])
            assert t["spans"] == [] and t["tracing"] is False
        finally:
            bound = srv.port
            srv.stop()
        assert _no_server_threads()
        # the bound port survives stop() for post-run inspection
        assert srv.port == bound and bound > 0

    def test_tracez_shows_completed_spans(self):
        telemetry.trace.start_profiler()
        try:
            with telemetry.span("diag-span"):
                pass
            srv = tserver.DebugServer(port=0).start()
            try:
                t = json.loads(_get(srv.url("/tracez"))[1])
                assert t["tracing"] is True
                assert any(s["name"] == "diag-span" for s in t["spans"])
            finally:
                srv.stop()
        finally:
            telemetry.trace.stop_profiler()

    def test_statusz_provider_failure_never_500s(self):
        srv = tserver.DebugServer(port=0).start()
        try:
            srv.add_status("ok", lambda: {"v": 1})
            srv.add_status("broken", lambda: 1 / 0)
            code, body = _get(srv.url("/statusz"))
            s = json.loads(body)
            assert code == 200
            assert s["status"]["ok"] == {"v": 1}
            assert "failed" in s["status"]["broken"]
        finally:
            srv.stop()

    def test_unknown_path_is_404_and_stop_joins_thread(self):
        srv = tserver.DebugServer(port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url("/nope"))
            assert ei.value.code == 404
        finally:
            srv.stop()
        assert not srv.running
        assert _no_server_threads()
        # note() with no active server: one list check, no effect
        tserver.note("step")
        assert tserver.active() == []

    def test_owner_scoped_heartbeat_no_cross_talk(self):
        """Two servers in one process (train + serving): stamping one
        server's clock must not reset the other's — a wedged loop has
        to stay visibly stale on its own /healthz."""
        a = tserver.DebugServer(port=0).start()
        b = tserver.DebugServer(port=0).start()
        try:
            a.note("step")
            ha = json.loads(_get(a.url("/healthz"))[1])
            hb = json.loads(_get(b.url("/healthz"))[1])
            assert ha["last_step_age_s"] is not None
            assert hb["last_step_age_s"] is None  # untouched
            tserver.note("request")  # module-level broadcast hits both
            ha = json.loads(_get(a.url("/healthz"))[1])
            hb = json.loads(_get(b.url("/healthz"))[1])
            assert ha["last_request_age_s"] is not None
            assert hb["last_request_age_s"] is not None
            # a loop-OWNED server is immune to broadcasts: a busy
            # Executor next door cannot reset its stall clock
            c = tserver.DebugServer(port=0, owned=True).start()
            try:
                tserver.note("step")
                hc = json.loads(_get(c.url("/healthz"))[1])
                assert hc["last_step_age_s"] is None
                c.note("step")  # the owner still can
                hc = json.loads(_get(c.url("/healthz"))[1])
                assert hc["last_step_age_s"] is not None
            finally:
                c.stop()
        finally:
            a.stop()
            b.stop()

    def test_failed_bind_does_not_enable_telemetry(self):
        """A taken port must fail WITHOUT flipping the process-wide
        telemetry switch for a server that never ran."""
        srv = tserver.DebugServer(port=0).start()
        try:
            taken = srv.port
            telemetry.disable()
            with pytest.raises(OSError):
                tserver.DebugServer(port=taken).start()
            assert not telemetry.enabled()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# TrainLoop wiring — the ISSUE acceptance pins
# ---------------------------------------------------------------------------

class TestTrainLoopWiring:
    def test_nan_dump_and_skip_step_policy(self, tmp_path):
        """Injected NaN loss → dump bundle on disk (ring + metrics +
        recompile report) and the step observably skipped."""
        telemetry.enable()
        fr = FlightRecorder(str(tmp_path / "dumps"), policy="skip_step")
        loop = TrainLoop(StubTrainer(nan_at=4), str(tmp_path / "ckpt"),
                         checkpoint_every=2, nan_policy="off")
        final = loop.run(_batches(8), flight_recorder=fr)
        assert final == 7            # 8 batches, one skipped
        assert loop.history["skipped_steps"] == [3]
        assert loop.trainer.restored_to  # rolled back to last snapshot
        # counter parity with the _guard nan-skip this path subsumes
        assert telemetry.registry().get(
            "pt_train_nan_skips_total").value == 1
        assert len(fr.dumps) == 1
        bundle = json.load(open(fr.dumps[0]))
        assert bundle["reason"] == "nan_loss"
        assert bundle["ring"][-1]["anomaly"] == "nan_loss"
        assert "metrics" in bundle and "recompile" in bundle
        assert bundle["run_config"]["nan_policy"] == "off"
        # ring carried per-step host scalars up to the anomaly
        assert all("step_time_s" in e for e in bundle["ring"])

    def test_halt_policy_raises_and_keeps_last_good_checkpoint(
            self, tmp_path):
        telemetry.enable()
        fr = FlightRecorder(str(tmp_path / "dumps"), policy="halt")
        loop = TrainLoop(StubTrainer(nan_at=3), str(tmp_path / "ckpt"),
                         checkpoint_every=2, nan_policy="off")
        with pytest.raises(AnomalyHalt, match="nan_loss"):
            loop.run(_batches(8), flight_recorder=fr)
        assert len(fr.dumps) == 1
        # close() must NOT have snapshotted the poisoned post-anomaly
        # state: the only checkpoint is the periodic step-2 one
        assert loop.manager.all_steps() == [2]

    def test_skip_step_without_checkpoint_escalates_nan_to_halt(
            self, tmp_path):
        """A nan anomaly under skip_step with NOTHING to roll back to
        must not silently keep training on the poisoned update — same
        latest-is-None-is-fatal stance as elastic recovery."""
        telemetry.enable()
        fr = FlightRecorder(str(tmp_path / "dumps"), policy="skip_step")
        loop = TrainLoop(StubTrainer(nan_at=2), str(tmp_path / "ckpt"),
                         checkpoint_every=100, nan_policy="off")
        with pytest.raises(AnomalyHalt, match="no checkpoint"):
            loop.run(_batches(6), flight_recorder=fr)
        assert len(fr.dumps) == 1
        # the step halted — it must not be recorded as "skipped"
        assert loop.history["skipped_steps"] == []
        # a finite-state anomaly (spike) under skip_step NEVER rolls
        # back — the applied update is numerically sound, and a
        # rollback would destroy up to checkpoint_every steps of real
        # progress; the anomaly is recorded + dumped and the run
        # proceeds at full step count
        # (the stall watch is disarmed: it reads the wall clock, and a
        # stub step is 20 us, so after two warm-up steps any 0.3 ms
        # hiccup of a loaded host in step 5 or 6 is a "step_stall" that
        # becomes the LAST anomaly; one run in 200 on an idle host)
        telemetry.reset()
        fr2 = FlightRecorder(str(tmp_path / "d2"), policy="skip_step",
                             warmup_steps=2, grad_spike_factor=5.0,
                             stall_factor=float("inf"))
        loop2 = TrainLoop(StubTrainer(), str(tmp_path / "c2"),
                          checkpoint_every=100, nan_policy="off")

        class SpikyTrainer(StubTrainer):
            def train_step(self, batch):
                self.n += 1
                loss = np.float32(0.5)
                return loss, {"grad_norm": 100.0 if self.n == 4
                              else 1.0}

        loop2.trainer = SpikyTrainer()
        final = loop2.run(_batches(6), flight_recorder=fr2)
        assert final == 6  # nothing rolled back, nothing skipped
        assert loop2.history["skipped_steps"] == []
        assert loop2.trainer.restored_to == []
        assert fr2.anomalies[-1]["kind"] == "grad_spike"

    def test_telemetry_disabled_short_circuits_recorder(self, tmp_path):
        """The enabled-flag contract: same run, telemetry off — the
        recorder is never consulted and no dump is written."""
        assert not telemetry.enabled()
        fr = FlightRecorder(str(tmp_path / "dumps"), policy="halt")
        loop = TrainLoop(StubTrainer(nan_at=3), str(tmp_path / "ckpt"),
                         checkpoint_every=100, nan_policy="off")
        final = loop.run(_batches(6), flight_recorder=fr)
        assert final == 6            # nothing skipped, nothing halted
        assert len(fr.ring) == 0 and fr.dumps == []
        assert not os.path.exists(str(tmp_path / "dumps"))

    def test_debug_server_lifecycle_and_healthz_during_run(self,
                                                           tmp_path):
        seen = {}

        def scrape(step, loss, metrics):
            if step == 2:
                srv = seen["loop"].debug_server
                seen["healthz"] = json.loads(
                    _get(srv.url("/healthz"))[1])
                seen["statusz"] = json.loads(
                    _get(srv.url("/statusz"))[1])

        loop = TrainLoop(StubTrainer(), str(tmp_path / "ckpt"),
                         checkpoint_every=100, nan_policy="off")
        seen["loop"] = loop
        final = loop.run(_batches(4), debug_port=0, on_step=scrape)
        assert final == 4
        assert seen["healthz"]["last_step_age_s"] is not None
        assert seen["statusz"]["run_config"]["role"] == "train_loop"
        assert not loop.debug_server.running
        assert _no_server_threads()


# ---------------------------------------------------------------------------
# Executor wiring
# ---------------------------------------------------------------------------

class TestExecutorWiring:
    def _prog(self):
        import paddle_tpu.static as static

        prog = static.Program()
        with static.program_guard(prog):
            x = prog.data("x", (-1, 4))
            loss = static.layers.mean(x)
        return prog, loss

    def test_recorder_sees_runs_and_halts_on_nan(self, tmp_path):
        import paddle_tpu.static as static

        telemetry.enable()
        prog, loss = self._prog()
        exe = static.Executor(scope=static.Scope())
        fr = FlightRecorder(str(tmp_path), policy="halt")
        exe.attach_flight_recorder(fr)
        exe.run(prog, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
        assert len(fr.ring) == 1
        assert fr.ring[-1]["loss"] == pytest.approx(1.0)
        bad = np.full((2, 4), np.nan, np.float32)
        with pytest.raises(AnomalyHalt, match="nan_loss"):
            exe.run(prog, feed={"x": bad}, fetch_list=[loss])
        assert len(fr.dumps) == 1

    def test_disabled_telemetry_skips_recorder(self, tmp_path):
        import paddle_tpu.static as static

        prog, loss = self._prog()
        exe = static.Executor(scope=static.Scope())
        fr = FlightRecorder(str(tmp_path), policy="halt")
        exe.attach_flight_recorder(fr)
        exe.run(prog, feed={"x": np.full((2, 4), np.nan, np.float32)},
                fetch_list=[loss])
        assert len(fr.ring) == 0 and fr.dumps == []


# ---------------------------------------------------------------------------
# serving wiring (slow: compiles a tiny GPT) + e2e train smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestServingWiring:
    def test_run_serves_endpoints_and_records_ticks(self, tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.models import gpt as G
        from paddle_tpu.serving import BatchedDecoder

        telemetry.enable()
        pt.seed(0)
        model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
        dec = BatchedDecoder(model, slots=2, capacity=64)
        rng = np.random.default_rng(3)
        fr = FlightRecorder(str(tmp_path), policy="record")
        scraped = {}
        orig_step = dec._step

        def step_and_scrape():
            orig_step()
            if "statusz" not in scraped and dec.debug_server is not None:
                scraped["statusz"] = json.loads(
                    _get(dec.debug_server.url("/statusz"))[1])
                scraped["healthz"] = json.loads(
                    _get(dec.debug_server.url("/healthz"))[1])

        dec._step = step_and_scrape
        for _ in range(3):
            dec.submit(rng.integers(1, 512, (5,)).astype(np.int32), 6)
        outs = dec.run(debug_port=0, flight_recorder=fr)
        assert len(outs) == 3
        st = scraped["statusz"]["status"]["serving"]
        assert st["slots"] == 2 and st["active_slots"] >= 1
        assert scraped["healthz"]["last_request_age_s"] is not None
        assert len(fr.ring) >= 1
        assert all("queue_depth" in e for e in fr.ring)
        assert not dec.debug_server.running
        assert _no_server_threads()


@pytest.mark.slow
def test_e2e_debug_server_over_real_train_run(tmp_path):
    """CI smoke (ISSUE satellite): a real CPU train run with the debug
    server on an ephemeral port; /healthz, /metrics, /statusz scraped
    live via urllib; the server thread is gone after run() returns
    (reader-hygiene standard — no leaked daemon threads)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models import mnist as M

    telemetry.enable()
    pt.seed(0)
    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    trainer = parallel.Trainer.supervised(
        M.MnistMLP(hidden1=16, hidden2=8), optimizer.Adam(1e-3),
        M.loss_fn, mesh=mesh)
    rng = np.random.default_rng(0)

    def batches(n, bs=8):
        for _ in range(n):
            yield {"x": jnp.asarray(rng.normal(size=(bs, 784))
                                    .astype(np.float32)),
                   "label": jnp.asarray(rng.integers(0, 10, bs))}

    loop = TrainLoop(trainer, str(tmp_path / "ckpt"),
                     checkpoint_every=100)
    scraped = {}

    def scrape(step, loss, metrics):
        if step != 3:
            return
        srv = loop.debug_server
        assert srv.running and srv.port > 0
        scraped["healthz"] = json.loads(_get(srv.url("/healthz"))[1])
        scraped["metrics"] = _get(srv.url("/metrics"))[1]
        scraped["statusz"] = json.loads(_get(srv.url("/statusz"))[1])

    final = loop.run(batches(5), debug_port=0, on_step=scrape)
    assert final == 5
    assert scraped["healthz"]["status"] == "ok"
    assert scraped["healthz"]["last_step_age_s"] is not None
    assert "pt_train_steps_total" in scraped["metrics"]
    assert "pt_train_step_seconds" in scraped["metrics"]
    assert scraped["statusz"]["backend"] == "cpu"
    assert scraped["statusz"]["device_count"] >= 1
    # recompile tracker visible through the endpoint
    assert "train_loop.step" in scraped["statusz"]["recompile"]
    # hygiene: endpoint down, thread joined
    assert not loop.debug_server.running
    assert _no_server_threads()
    with pytest.raises(Exception):
        _get(loop.debug_server.url("/healthz"), timeout=2)
