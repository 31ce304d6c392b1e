"""Performance-attribution plane (telemetry.costs + telemetry.profiling):
program cost ledger + roofline, goodput accounting, the bounded
/profilez device capture (404 -> 409 -> 200), and the PT-PERF-80x
regression sentinel — unit tests plus the TrainLoop/serving e2e the
acceptance criteria pin (seeded slow step trips exactly ONE
PT-PERF-801, and everything is zero-cost with telemetry off —
tripwire-monkeypatched)."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.telemetry as telemetry
from paddle_tpu import optimizer, parallel
from paddle_tpu.models import mnist as M
from paddle_tpu.telemetry import costs
from paddle_tpu.telemetry import profiling
from paddle_tpu.telemetry.server import DebugServer
from paddle_tpu.train_loop import TrainLoop

RNG = np.random.default_rng(81)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


class _FakeDevice:
    """Stand-in for a device; the defaults are what a TPU v5e reports."""

    def __init__(self, platform="tpu", device_kind="TPU v5 lite"):
        self.platform = platform
        self.device_kind = device_kind


def _pretend_v5e(monkeypatch):
    """MFU math needs a peak and the CPU has none: answer peak lookups
    as a v5e would."""
    from paddle_tpu.utils import flops

    real = flops.device_peak_flops
    monkeypatch.setattr(
        flops, "device_peak_flops",
        lambda device=None, dtype="bf16": real(_FakeDevice(), dtype))


def _matmul_jit(n=64):
    return jax.jit(lambda a, b: a @ b), (jnp.ones((n, n)),
                                         jnp.ones((n, n)))


# ---------------------------------------------------------------------------
# cost ledger
# ---------------------------------------------------------------------------

class TestCostLedger:
    def test_analyze_callable_registers_xla_numbers(self):
        fn, args = _matmul_jit()
        rec = costs.analyze_callable("t.matmul", fn, *args)
        assert rec["program"] == "t.matmul"
        assert rec["origin"] == "bench"
        # 64^3 matmul: 2*n^3 = 524288 FLOPs from the XLA cost model
        assert rec["flops"] == pytest.approx(2 * 64**3, rel=0.05)
        # the CPU has no peak: intensity is recorded, the verdict is not
        assert rec["roofline"]["intensity_flops_per_byte"] > 0
        assert rec["roofline"]["verdict"] == "unknown"
        # memoized: the second call returns the registered record
        # without re-analysis, and get() hands out copies
        again = costs.analyze_callable("t.matmul", fn, *args)
        assert again["flops"] == rec["flops"]
        snap = costs.get("t.matmul")
        snap["flops"] = -1
        assert costs.get("t.matmul")["flops"] == rec["flops"]

    def test_ensure_program_is_telemetry_gated(self):
        fn, args = _matmul_jit()
        costs.ensure_program("t.gated", fn, args)
        assert costs.get("t.gated") is None  # disabled -> no work
        telemetry.enable()
        costs.ensure_program("t.gated", fn, args)
        rec = costs.get("t.gated")
        assert rec is not None and rec["analyzed"]
        assert rec["flops"] and rec["flops"] > 0
        # the per-program gauges landed
        text = telemetry.prometheus_text()
        assert "pt_program_flops" in text and "t.gated" in text

    def test_aot_stub_merges_with_first_dispatch_analysis(self):
        telemetry.enable()
        costs.note_aot_program("t.aot", artifact_id="art-123")
        stub = costs.get("t.aot")
        assert stub["origin"] == "aot" and stub["flops"] is None
        fn, args = _matmul_jit()
        costs.ensure_program("t.aot", fn, args)
        rec = costs.get("t.aot")
        assert rec["analyzed"] and rec["flops"] > 0
        # provenance survives the merge
        assert rec["origin"] == "aot"
        assert rec["artifact_id"] == "art-123"

    def test_roofline_verdicts(self):
        v5e = _FakeDevice()
        assert costs.roofline(1e12, 1e3, v5e)["verdict"] == "compute_bound"
        assert costs.roofline(1e3, 1e12, v5e)["verdict"] == "hbm_bound"
        assert costs.roofline(None, 1e6, v5e)["verdict"] == "unknown"

    def test_backend_peaks_v5e_row_and_no_cpu_peak(self):
        assert costs.backend_peaks() is None  # the CPU has no peak
        peaks = costs.backend_peaks(_FakeDevice())
        assert peaks["backend"] == "tpu"
        assert peaks["peak_flops"] == 197e12
        assert peaks["peak_hbm_bytes_per_s"] == 819e9
        assert peaks["ridge_flops_per_byte"] == pytest.approx(240.5, rel=1e-3)

    def test_peak_table_is_keyed_by_exact_device_kind(self):
        """ONE table (utils.flops.DEVICE_PEAKS) keyed by the exact
        device_kind the chip reports: the published v5e peaks resolve, a
        substring of a known kind does not, the CPU has no peak."""
        from paddle_tpu.core import NotFoundError
        from paddle_tpu.utils import flops

        v5e = _FakeDevice()  # "TPU v5 lite", what a v5e reports
        assert flops.device_peak_flops(v5e) == 197e12
        assert flops.device_peak_flops(v5e, dtype="int8") == 393e12
        peaks = costs.backend_peaks(v5e)
        assert peaks["peak_flops"] == 197e12
        assert peaks["peak_hbm_bytes_per_s"] == 819e9
        assert costs.roofline(1e12, 1e3, v5e)["verdict"] == "compute_bound"
        assert costs.roofline(1e3, 1e12, v5e)["verdict"] == "hbm_bound"
        for kind in ("TPU v5", "tpu v5 lite", "TPU v5 lite pod", "TPU v99"):
            with pytest.raises(NotFoundError, match="no published peaks"):
                flops.device_peaks(_FakeDevice(device_kind=kind))
        cpu = _FakeDevice(platform="cpu", device_kind="cpu")
        assert flops.device_peaks(cpu) is None
        assert flops.device_peak_flops(cpu) is None
        assert costs.backend_peaks(cpu) is None
        assert costs.roofline(1e12, 1e3, cpu)["verdict"] == "unknown"

    def test_derive_mfu_from_ledger_not_caller_estimate(self,
                                                        monkeypatch):
        fn, args = _matmul_jit()
        rec = costs.analyze_callable("t.mfu", fn, *args)
        # CPU has no peak: MFU is omitted, not faked
        assert costs.derive_mfu("t.mfu", 0.001) is None
        # on a chip with a peak, MFU = flops / (dt * peak)
        _pretend_v5e(monkeypatch)
        got = costs.derive_mfu("t.mfu", 0.001)
        assert got == pytest.approx(rec["flops"] / (0.001 * 197e12))
        assert costs.derive_mfu("t.unknown", 0.001) is None

    def test_observe_step_sets_mfu_gauge(self, monkeypatch):
        telemetry.enable()
        _pretend_v5e(monkeypatch)
        fn, args = _matmul_jit()
        costs.analyze_callable("t.obs", fn, *args)
        m = costs.observe_step("t.obs", 0.001)
        assert m is not None and m > 0
        assert "pt_step_mfu" in telemetry.prometheus_text()

    def test_statusz_section_carries_ledger_and_peaks(self):
        fn, args = _matmul_jit()
        costs.analyze_callable("t.statusz", fn, *args)
        sec = costs.statusz_section()
        assert "t.statusz" in sec["programs"]
        assert sec["peaks"] is None  # CPU: no peak row


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

class TestGoodput:
    def test_train_bucket_math(self):
        g = profiling.GoodputLedger()
        g.note_step(input_wait=0.2, dispatch=0.1, device_compute=0.6)
        g.note_checkpoint_stall(0.1)
        snap = g.snapshot()
        assert snap["steps"] == 1
        assert snap["buckets_s"]["input_wait"] == pytest.approx(0.2)
        assert snap["buckets_s"]["checkpoint_stall"] == pytest.approx(0.1)
        # useful = dispatch + compute over everything
        assert snap["train_goodput_ratio"] == pytest.approx(0.7)

    def test_serving_tick_math_and_gauge(self):
        telemetry.enable()
        g = profiling.GoodputLedger()
        g.note_tick(6, 8)
        g.note_tick(2, 8)
        snap = g.snapshot()
        assert snap["serving_ticks"] == 2
        assert snap["active_slot_tokens"] == 8
        assert snap["capacity_tokens"] == 16
        assert snap["serving_goodput_ratio"] == pytest.approx(0.5)
        assert "pt_goodput_ratio" in telemetry.prometheus_text()

    def test_empty_ledger_reports_no_ratio(self):
        snap = profiling.GoodputLedger().snapshot()
        assert "train_goodput_ratio" not in snap
        assert "serving_goodput_ratio" not in snap


# ---------------------------------------------------------------------------
# regression sentinel
# ---------------------------------------------------------------------------

class TestSentinel:
    def _seeded(self, **kw):
        s = profiling.RegressionSentinel(band=0.5, min_samples=2, **kw)
        for _ in range(3):
            assert s.observe("prog", "tpu", 0.010) is None
        return s

    def test_trips_once_per_program_backend(self):
        telemetry.enable()
        s = self._seeded()
        d = s.observe("prog", "tpu", 0.030)
        assert d is not None and d.code == "PT-PERF-801"
        assert d.severity == "warning"
        assert "regressed" in d.message
        # warn-once per (program, backend)
        assert s.observe("prog", "tpu", 0.050) is None
        assert len(s.diagnostics()) == 1
        # a different backend key arms independently
        for _ in range(3):
            s.observe("prog", "cpu", 0.010)
        assert s.observe("prog", "cpu", 0.030).code == "PT-PERF-801"
        ctr = telemetry.registry().counter("pt_perf_regressions_total")
        assert ctr.value == 2

    def test_itl_kind_emits_802(self):
        s = profiling.RegressionSentinel(band=0.5, min_samples=2)
        for _ in range(3):
            s.observe("serving.step[k=4]", "tpu", 0.005, kind="itl")
        d = s.observe("serving.step[k=4]", "tpu", 0.020, kind="itl")
        assert d.code == "PT-PERF-802"
        assert "inter-token" in d.message

    def test_regression_not_folded_into_baseline(self):
        s = self._seeded()
        ewma_before = s.baselines()["prog|tpu"]["ewma"]
        s.observe("prog", "tpu", 10.0)
        assert s.baselines()["prog|tpu"]["ewma"] == ewma_before

    def test_degraded_rows_never_touch_the_math(self):
        s = profiling.RegressionSentinel(band=0.5, min_samples=2)
        for _ in range(5):
            assert s.observe("prog", "cpu", 9.0, degraded=True) is None
        assert s.baselines() == {}
        # an armed baseline is not alarmed by a degraded spike either
        s2 = self._seeded()
        assert s2.observe("prog", "tpu", 99.0, degraded=True) is None
        assert s2.diagnostics() == []

    def test_baselines_persist_and_reload(self, tmp_path):
        path = str(tmp_path / "perf_baselines.json")
        s = self._seeded()
        s.attach(path)
        s.save()
        s2 = profiling.RegressionSentinel(band=0.5, min_samples=2)
        s2.attach(path)
        assert "prog|tpu" in s2.baselines()
        # the reloaded baseline alarms immediately — no re-seeding
        assert s2.observe("prog", "tpu", 0.050).code == "PT-PERF-801"

    def test_torn_baseline_file_never_fails_a_run(self, tmp_path):
        path = str(tmp_path / "perf_baselines.json")
        with open(path, "w") as f:
            f.write("{torn")
        s = profiling.RegressionSentinel()
        s.attach(path)  # must not raise
        assert s.baselines() == {}


# ---------------------------------------------------------------------------
# /profilez: bounded on-demand device capture
# ---------------------------------------------------------------------------

class TestProfilez:
    def test_real_capture_lands_atomic_artifact(self, tmp_path):
        out = str(tmp_path / "cap")
        res = profiling.capture_device_trace(out, duration_ms=50)
        assert res["artifact"] == out
        assert res["pid"] == os.getpid()
        assert os.path.isdir(out)
        # atomic rename: no half-written temp dir left behind
        assert not [p for p in os.listdir(str(tmp_path))
                    if ".tmp-" in p]

    def test_busy_raises_409_typed_error(self, tmp_path):
        assert profiling.capture_busy() is False
        assert profiling._capture_lock.acquire(blocking=False)
        try:
            assert profiling.capture_busy() is True
            with pytest.raises(profiling.CaptureBusyError):
                profiling.capture_device_trace(str(tmp_path / "x"), 10)
        finally:
            profiling._capture_lock.release()
        assert profiling.CaptureBusyError.http_status == 409

    def test_duration_hard_cap(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PT_PROFILEZ_CAP_MS", "20")
        res = profiling.capture_device_trace(str(tmp_path / "cap"),
                                             duration_ms=60000)
        assert res["duration_ms"] <= 20
        assert res["wall_ms"] < 30000  # a fat finger can't hang us

    def test_http_state_machine_404_409_200(self, tmp_path):
        srv = DebugServer().start()
        try:
            def post(body=b"{}"):
                req = urllib.request.Request(
                    srv.url("/profilez"), data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())

            # not mounted -> the stock 404
            with pytest.raises(urllib.error.HTTPError) as e:
                post()
            assert e.value.code == 404
            srv.add_post("/profilez", profiling.make_profilez(
                default_dir=str(tmp_path / "cap")))
            code, res = post(json.dumps(
                {"duration_ms": 50}).encode())
            assert code == 200
            assert res["pid"] == os.getpid()
            assert os.path.isdir(res["artifact"])
            # busy -> 409, not 400 (the handler's typed http_status)
            assert profiling._capture_lock.acquire(blocking=False)
            try:
                with pytest.raises(urllib.error.HTTPError) as e:
                    post()
                assert e.value.code == 409
                assert "already in flight" in e.value.read().decode()
            finally:
                profiling._capture_lock.release()
        finally:
            srv.stop()

    def test_fanout_merges_and_degrades(self, tmp_path):
        srv = DebugServer().start()
        srv.add_post("/profilez", profiling.make_profilez(
            default_dir=str(tmp_path / "peer")))
        try:
            local = profiling.make_profilez(
                default_dir=str(tmp_path / "local"))(b"{}")
            dead = "http://127.0.0.1:9"  # discard port: unreachable
            out = profiling.profilez_fanout(
                [srv.url(""), dead],
                json.dumps({"duration_ms": 30}).encode(),
                local_result=local)
            assert out["fleet"] == 2
            arts = [c["artifact"] for c in out["captures"]]
            assert str(tmp_path / "local") in arts
            assert str(tmp_path / "peer") in arts
            assert list(out["errors"]) == [dead]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# /statusz surfaces
# ---------------------------------------------------------------------------

class TestStatusz:
    def test_statusz_carries_attribution_sections(self):
        fn, args = _matmul_jit()
        costs.analyze_callable("t.sz", fn, *args)
        profiling.goodput().note_step(dispatch=0.1, device_compute=0.4)
        st = DebugServer().statusz()
        assert "t.sz" in st["costs"]["programs"]
        assert st["goodput"]["steps"] == 1
        assert st["perf"]["baselines"] == 0
        assert st["perf"]["capture_busy"] is False
        # PT-TUNE-501 staleness surfaced without grepping logs
        assert isinstance(st["tuning"]["stale_dtype_findings"], list)


# ---------------------------------------------------------------------------
# TrainLoop e2e: goodput buckets + ledger + sentinel wiring
# ---------------------------------------------------------------------------

def _make_trainer():
    pt.seed(0)
    mesh = pt.build_mesh(dp=1, devices=jax.devices()[:1])
    model = M.MnistMLP(hidden1=16, hidden2=8)
    return parallel.Trainer.supervised(model, optimizer.Adam(1e-3),
                                       M.loss_fn, mesh=mesh)


def _batches(n, bs=8):
    for _ in range(n):
        yield {"x": jnp.asarray(RNG.normal(size=(bs, 784))
                                .astype(np.float32)),
               "label": jnp.asarray(RNG.integers(0, 10, bs))}


def _seed_baseline(ckpt_dir, ewma=1e-5):
    """Plant an armed train-step baseline the loop will load via
    attach() — the seeded slow-step injection: every real CPU step is
    orders of magnitude above a 10us baseline."""
    os.makedirs(ckpt_dir, exist_ok=True)
    with open(os.path.join(ckpt_dir, "perf_baselines.json"), "w") as f:
        json.dump({"baselines": {"train.step|cpu": {
            "ewma": ewma, "n": 5, "kind": "step"}}}, f)


class TestTrainLoopAttribution:
    def test_loop_feeds_ledger_goodput_and_baselines(self, tmp_path):
        telemetry.enable()
        loop = TrainLoop(_make_trainer(), str(tmp_path),
                         checkpoint_every=2)
        loop.run(_batches(4))
        rec = costs.get("train.step")
        assert rec is not None and rec["analyzed"]
        assert rec["origin"] == "train_loop"
        assert rec["flops"] and rec["flops"] > 0
        snap = profiling.goodput().snapshot()
        assert snap["steps"] == 4
        assert snap["buckets_s"]["device_compute"] > 0
        assert snap["buckets_s"]["checkpoint_stall"] > 0  # 2 saves
        assert 0 < snap["train_goodput_ratio"] <= 1
        # the sentinel recorded a train-step baseline and persisted it
        assert "train.step|cpu" in profiling.sentinel().baselines()
        with open(str(tmp_path / "perf_baselines.json")) as f:
            saved = json.load(f)
        assert "train.step|cpu" in saved["baselines"]

    def test_seeded_slow_step_trips_exactly_one_801(self, tmp_path):
        telemetry.enable()
        _seed_baseline(str(tmp_path))
        loop = TrainLoop(_make_trainer(), str(tmp_path),
                         checkpoint_every=100)
        loop.run(_batches(4))
        diags = profiling.sentinel().diagnostics()
        assert [d.code for d in diags] == ["PT-PERF-801"]  # ONE trip
        assert "train.step" in diags[0].message
        ctr = telemetry.registry().counter("pt_perf_regressions_total")
        assert ctr.value == 1

    def test_disabled_loop_runs_zero_attribution_code(self, tmp_path,
                                                      monkeypatch):
        """The tripwire: with telemetry OFF, none of the attribution
        plane may execute — every entry point is rigged to detonate."""
        def boom(*a, **k):
            raise AssertionError("attribution code ran while disabled")

        monkeypatch.setattr(profiling.GoodputLedger, "note_step", boom)
        monkeypatch.setattr(profiling.GoodputLedger, "note_tick", boom)
        monkeypatch.setattr(profiling.GoodputLedger,
                            "note_checkpoint_stall", boom)
        monkeypatch.setattr(profiling.RegressionSentinel, "observe",
                            boom)
        monkeypatch.setattr(profiling.RegressionSentinel, "attach",
                            boom)
        monkeypatch.setattr(costs, "_analyze", boom)
        monkeypatch.setattr(costs, "_register", boom)
        monkeypatch.setattr(costs, "derive_mfu", boom)
        assert not telemetry.enabled()
        loop = TrainLoop(_make_trainer(), str(tmp_path),
                         checkpoint_every=2)
        assert loop.run(_batches(3)) == 3


# ---------------------------------------------------------------------------
# serving e2e: program registration + tick accounting
# ---------------------------------------------------------------------------

class TestServingAttribution:
    def test_decoder_registers_programs_and_ticks(self):
        from paddle_tpu.models import gpt as G
        from paddle_tpu.serving import BatchedDecoder

        telemetry.enable()
        pt.seed(0)
        m = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
        dec = BatchedDecoder(m, slots=2, capacity=64)
        rng = np.random.default_rng(3)
        for i in range(2):
            dec.submit(rng.integers(1, 512, (5 + i,)).astype(np.int32),
                       max_new=4)
        outs = dec.run()
        assert len(outs) == 2
        names = sorted(costs.ledger())
        assert any(n.startswith("serving.step[") for n in names)
        assert any(n.startswith("serving.prefill[") for n in names)
        step = next(n for n in names if n.startswith("serving.step["))
        assert costs.get(step)["origin"] == "serving"
        # plain tick counters (harness-readable without telemetry)
        assert dec.tick_count > 0
        assert 0 < dec.tick_tokens <= dec.tick_capacity
        snap = profiling.goodput().snapshot()
        assert snap["serving_ticks"] == dec.tick_count
        assert 0 < snap["serving_goodput_ratio"] <= 1
