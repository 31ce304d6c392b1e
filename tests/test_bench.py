"""Bench harness contract tests (reference: benchmark/fluid/
fluid_benchmark.py role): the driver's one-JSON-line contract on success,
misuse, and error paths; K-step dispatch fusion; profile trace output.
Each case shells out exactly as the driver does."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _load_bench(name="bench_mod"):
    """Load bench.py as a fresh module (its module state — _MODE,
    _EXPLICIT_BATCH — must not leak between tests)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(*extra, timeout=520):
    r = subprocess.run([sys.executable, BENCH, "--platform", "cpu", *extra],
                       capture_output=True, text=True, timeout=timeout)
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no JSON line: {r.stdout}\n{r.stderr}"
    return json.loads(lines[-1])


def test_smoke_emits_metric_line():
    d = _run("--smoke", "--steps", "8", "--batch-size", "64")
    # an explicit --batch-size is a different workload: own history key
    assert d["metric"] == "mnist_mlp_throughput_b64"
    assert d["value"] > 0 and d["unit"] == "examples/sec"
    # FLOPs accounting: TFLOP/s reported when the XLA cost model
    # resolves; these tests force --platform cpu, where MFU must be null
    # (no chip peak to divide by)
    if "tflops_per_sec" in d:  # cost model can be absent on a backend
        assert d["tflops_per_sec"] > 0
        assert d["mfu"] is None


def test_regression_contract():
    """vs_baseline compares to the best recorded accelerator number;
    >10% below it on an accelerator flags a regression; CPU runs are
    never recorded (the perf-freeze contract)."""
    bench = _load_bench()
    ev = bench.evaluate_against_history

    hist = {"m_throughput": 100.0}  # legacy bare-float entry
    # accelerator regression: >10% below record
    vs, reg = ev("m_throughput", 80.0, dict(hist), on_accelerator=True,
                 record=True)
    assert vs == 0.8 and reg
    # within 10% = no regression
    _, reg = ev("m_throughput", 95.0, dict(hist), on_accelerator=True,
                record=True)
    assert not reg
    # CPU run never regresses and never records
    h = dict(hist)
    vs, reg = ev("m_throughput", 10.0, h, on_accelerator=False, record=True)
    assert not reg and h["m_throughput"] == 100.0
    # new accelerator record is kept (entries are metadata dicts now)
    h = dict(hist)
    ev("m_throughput", 150.0, h, on_accelerator=True, record=True,
       device_kind="TPU v5e", config_hash="abc", now="2026-08-01T00:00:00")
    e = h["m_throughput"]
    assert bench.hist_value(e) == 150.0
    assert e["device"] == "TPU v5e" and e["config_hash"] == "abc"
    assert e["ts"] == "2026-08-01T00:00:00"
    # a slower run against a legacy float keeps the record, upgraded to
    # the dict form (marked legacy: its provenance is unknown)
    h = dict(hist)
    ev("m_throughput", 80.0, h, on_accelerator=True, record=True)
    assert h["m_throughput"] == {"value": 100.0, "legacy": True}
    # first-ever number: baseline 1.0, recorded
    h = {}
    vs, reg = ev("m_throughput", 50.0, h, on_accelerator=True, record=True)
    assert vs == 1.0 and not reg and bench.hist_value(h["m_throughput"]) == 50.0


def test_history_like_for_like_gate():
    """VERDICT r4 weak #4: vs_baseline never compares across device or
    workload config silently — a mismatched run is no baseline (1.0, no
    regression) and records NON-destructively under metric@hash, so the
    true record keeps its key and later matching runs still regress
    against it."""
    bench = _load_bench("bench_mod2")
    ev = bench.evaluate_against_history

    v5e = {"value": 100.0, "device": "TPU v5e", "config_hash": "cfgA",
           "ts": "t0"}
    # same device + config: normal comparison, record stands
    h = {"m": dict(v5e)}
    vs, reg = ev("m", 50.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v5e", config_hash="cfgA")
    assert vs == 0.5 and reg and bench.hist_value(h["m"]) == 100.0
    # different workload fingerprint (e.g. a 24-step fast-sweep run vs
    # the 100-step record): no comparison, and the record is untouched —
    # the fast number lands under its own variant key
    h = {"m": dict(v5e)}
    vs, reg = ev("m", 30.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v5e", config_hash="cfgB",
                 config={"steps": 24})
    assert vs == 1.0 and not reg
    assert h["m"] == v5e  # headline record not demoted
    assert bench.hist_value(h["m@cfgB"]) == 30.0
    # ...and a LATER matching run still regresses against the original
    # record (the alternating-config masking scenario)
    vs, reg = ev("m", 50.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v5e", config_hash="cfgA")
    assert vs == 0.5 and reg
    # the fast variant compares against its own baseline on repeat
    vs, reg = ev("m", 33.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v5e", config_hash="cfgB",
                 config={"steps": 24})
    assert vs == 1.1 and bench.hist_value(h["m@cfgB"]) == 33.0
    # a non-headline run never claims a VACANT headline key either
    h = {}
    ev("m", 30.0, h, on_accelerator=True, record=True,
       device_kind="TPU v5e", config_hash="cfgB", config={"steps": 24})
    assert "m" not in h and bench.hist_value(h["m@cfgB"]) == 30.0
    # a legacy float upgraded in place ({"legacy": True}) KEEPS the
    # headline-length gate: a later fast run neither compares against
    # nor overwrites it
    h = {"m": 100.0}
    ev("m", 80.0, h, on_accelerator=True, record=True,
       device_kind="TPU v5e", config_hash="cfgA")  # upgrade, record stands
    assert h["m"] == {"value": 100.0, "legacy": True}
    vs, reg = ev("m", 500.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v5e", config_hash="cfgB",
                 config={"steps": 24})
    assert vs == 1.0 and not reg
    assert h["m"] == {"value": 100.0, "legacy": True}  # untouched
    assert bench.hist_value(h["m@cfgB"]) == 500.0
    # a different chip generation takes a device-qualified key: both
    # devices keep their own records, neither thrashes the other's
    h = {"m": dict(v5e),
         "m@cfgB": {"value": 20.0, "device": "TPU v5e",
                    "config_hash": "cfgB"}}
    ev("m", 40.0, h, on_accelerator=True, record=True,
       device_kind="TPU v6e", config_hash="cfgB", config={"steps": 24})
    assert h["m@cfgB"]["device"] == "TPU v5e"  # v5e record untouched
    assert bench.hist_value(h["m@cfgB@TPU v6e"]) == 40.0
    # ...and the v6e run regresses against its OWN record next time
    vs, reg = ev("m", 20.0, h, on_accelerator=True, record=True,
                 device_kind="TPU v6e", config_hash="cfgB",
                 config={"steps": 24})
    assert vs == 0.5 and reg
    # a v5e rerun still compares to the v5e variant record
    vs, _ = ev("m", 30.0, h, on_accelerator=True, record=True,
               device_kind="TPU v5e", config_hash="cfgB",
               config={"steps": 24})
    assert vs == 1.5 and bench.hist_value(h["m@cfgB"]) == 30.0


def test_run_config_fingerprint_identity():
    """Knob sweeps sharing a metric key + steps hash identically (they
    compete for one record); a different measurement length forks the
    hash (fast-sweep isolation)."""
    import argparse

    bench = _load_bench("bench_mod3")

    def ns(**kw):
        base = dict(model="bert_base", steps=None, batch_size=None,
                    amp="mixed_bf16", fused_ce=True, remat=None,
                    scan_layers=False, scan_unroll=None,
                    steps_per_call=None, vocab=None, window=None,
                    kv_cache=True, layout=None, dp=1, infer=False,
                    gamma=None, weight_only=False, paged=False)
        base.update(kw)
        return argparse.Namespace(**base)

    h1, c1 = bench.run_config_fingerprint("bert_base_throughput", ns(),
                                          100)
    h2, c2 = bench.run_config_fingerprint("bert_base_throughput",
                                          ns(remat="dots"), 100)
    assert h1 == h2  # remat is a knob, not workload identity
    assert c2["remat"] == "dots"  # but it IS recorded as provenance
    h3, _ = bench.run_config_fingerprint("bert_base_throughput", ns(),
                                         24)
    assert h3 != h1  # fast-sweep steps fork the hash (own variant key)


def test_input_pipeline_ab_contract():
    """The built-in prefetch A/B (PR 2 tentpole): one line carrying both
    arms + the overlap speedup, value = prefetch-ON throughput."""
    d = _run("--model", "input_pipeline", "--smoke", "--steps", "6",
             "--batch-size", "64")
    assert d["metric"] == "input_pipeline_throughput_b64"
    assert d["value"] > 0 and d["unit"] == "examples/sec"
    assert d["prefetch_on"] > 0 and d["prefetch_off"] > 0
    assert d["overlap_speedup"] > 0
    assert d["value"] == d["prefetch_on"]
    assert d["step_time_ms"] > 0


def test_every_line_carries_mfu_step_time_backend():
    """PR 2 schema: every success line says which backend produced it
    and the fenced per-step time next to mfu (null on CPU — no peak).
    PR 4 adds peak_mem_bytes from the device-memory monitor — null on
    CPU (no memory_stats(); the live-array fallback is an allocation
    view, never a peak)."""
    d = _run("--smoke", "--steps", "4", "--batch-size", "32")
    assert d["backend"] == "cpu"
    assert d["step_time_ms"] > 0
    assert "mfu" in d and d["mfu"] is None  # cpu: honest null
    assert "peak_mem_bytes" in d and d["peak_mem_bytes"] is None


def test_skip_line_exits_nonzero(capsys):
    """Infra failures (too few devices, no device trace, a failed aot
    artifact) emit "skipped": true with the error and NO value key —
    never a value-0.0 row that drags trend plots to zero — and the
    process exits NON-ZERO: a run that measured nothing is a failure to
    whoever started it."""
    import bench

    with pytest.raises(SystemExit) as exc:
        bench._emit_skip("m_tp", "needs 8 devices, have 1",
                         cause="insufficient_devices")
    assert exc.value.code == 1
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert d["skipped"] is True and "value" not in d
    assert d["cause"] == "insufficient_devices"
    assert d["metric"] == "m_tp"


@pytest.mark.slow
def test_e2e_bench_smoke_validates_schema():
    """End-to-end CI gate: run bench.py once on CPU (a real smoke run,
    no step/batch overrides) and validate the full JSON schema so bench
    breakage is caught before the round snapshot. A broken line here
    means every BENCH_r*.json of the round is unusable."""
    d = _run("--smoke")
    for key in ("metric", "value", "unit", "vs_baseline", "backend",
                "step_time_ms", "mfu", "peak_mem_bytes"):
        assert key in d, f"schema key missing: {key} in {d}"
    assert d["metric"] == "mnist_mlp_throughput"
    assert isinstance(d["value"], float) and d["value"] > 0
    assert d["unit"] == "examples/sec"
    assert d["backend"] == "cpu"
    assert d["step_time_ms"] > 0
    assert d["mfu"] is None  # cpu: no chip peak to divide by
    assert "skipped" not in d and "error" not in d


def test_dp_misuse_keeps_json_contract():
    d = _run("--model", "resnet50", "--dp", "2", "--smoke",
             "--steps", "1", "--batch-size", "2")
    assert d["value"] == 0.0 and "--dp is not supported" in d["error"]
    # error rows carry the full schema too (null where unmeasurable)
    assert d["backend"] is None and d["mfu"] is None
    assert d["step_time_ms"] is None
    assert d["peak_mem_bytes"] is None


def test_unwritable_profile_keeps_json_contract():
    d = _run("--smoke", "--steps", "1", "--batch-size", "8",
             "--profile", "/no/such/dir/x.json")
    assert d["value"] == 0.0 and "unwritable" in d["error"]


def test_steps_per_call_fuses_and_traces(tmp_path):
    trace = str(tmp_path / "t.json")
    d = _run("--model", "deepfm", "--smoke", "--steps", "4",
             "--batch-size", "16", "--steps-per-call", "2",
             "--profile", trace)
    assert d["value"] > 0
    t = json.load(open(trace))
    names = {e["name"] for e in t["traceEvents"]}
    assert any("[2]" in n for n in names), names


def test_cpu_runs_do_not_write_history():
    hist = os.path.join(REPO, "BENCH_HISTORY.json")
    before = os.path.exists(hist) and open(hist).read()
    _run("--steps", "2", "--batch-size", "32")  # NON-smoke cpu run
    after = os.path.exists(hist) and open(hist).read()
    assert before == after  # cpu runs never touch the recorded trajectory


class _FakeDevice:
    def __init__(self, platform="tpu", device_kind="TPU v5 lite"):
        self.platform = platform
        self.device_kind = device_kind


def test_accelerator_report_path_end_to_end(tmp_path, monkeypatch):
    """The full on-chip reporting contract, exercised BEFORE the first
    real chip session: history recording, best-run
    retention, regression flag + warning, MFU vs the v5e peak table."""
    import io
    from contextlib import redirect_stderr

    import bench

    hist = str(tmp_path / "BENCH_HISTORY.json")
    dev = _FakeDevice()
    extras = {"flops_per_sec": 98.5e12}  # 0.5 of the 197 TF v5e peak

    line = bench.report_line("bert_base_throughput", 1000.0,
                             "examples/sec", extras, history_path=hist,
                             smoke=False, device=dev)
    assert line["vs_baseline"] == 1.0 and "regression" not in line
    assert line["mfu"] == 0.5
    assert line["tflops_per_sec"] == 98.5
    with open(hist) as f:
        e = json.load(f)["bert_base_throughput"]
    assert bench.hist_value(e) == 1000.0
    assert e["device"] == "TPU v5 lite" and e["ts"]  # metadata rides along

    # a faster run replaces the record
    line = bench.report_line("bert_base_throughput", 1200.0,
                             "examples/sec", extras, history_path=hist,
                             smoke=False, device=dev)
    assert line["vs_baseline"] == 1.2
    with open(hist) as f:
        assert bench.hist_value(json.load(f)["bert_base_throughput"]) == 1200.0

    # a >10% drop flags regression, warns, and keeps the best record
    err = io.StringIO()
    with redirect_stderr(err):
        line = bench.report_line("bert_base_throughput", 900.0,
                                 "examples/sec", extras,
                                 history_path=hist, smoke=False,
                                 device=dev)
    assert line.get("regression") is True
    assert "regressed" in err.getvalue()
    with open(hist) as f:
        assert bench.hist_value(json.load(f)["bert_base_throughput"]) == 1200.0

    # smoke runs never record, even on the accelerator
    line = bench.report_line("other_metric", 50.0, "examples/sec", {},
                             history_path=hist, smoke=True, device=dev)
    with open(hist) as f:
        assert "other_metric" not in json.load(f)


def test_mfu_scales_by_dp_and_unknown_chip_raises(tmp_path):
    import bench
    from paddle_tpu.core import NotFoundError

    hist = str(tmp_path / "h.json")
    extras = {"flops_per_sec": 197e12}
    line = bench.report_line("m", 1.0, "x/s", extras, history_path=hist,
                             smoke=True, dp=4,
                             device=_FakeDevice())
    assert line["mfu"] == 0.25  # global flops over 4 chips' peak
    # a TPU the peak table does not list is an error, not a guess
    with pytest.raises(NotFoundError, match="TPU v99"):
        bench.report_line("m", 1.0, "x/s", extras, history_path=hist,
                          smoke=True,
                          device=_FakeDevice(device_kind="TPU v99"))


def test_peak_table_is_keyed_by_exact_device_kind():
    """ONE table (utils.flops.DEVICE_PEAKS) keyed by the exact
    device_kind the chip reports: the published v5e peaks resolve, a
    substring of a known kind does not, the CPU has no peak."""
    from paddle_tpu.core import NotFoundError
    from paddle_tpu.telemetry import costs
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


def test_cpu_device_never_writes_history_via_report(tmp_path):
    import bench

    hist = str(tmp_path / "h.json")
    line = bench.report_line("m", 10.0, "x/s",
                             {"flops_per_sec": 1e12},
                             history_path=hist, smoke=False,
                             device=_FakeDevice(platform="cpu",
                                                device_kind="cpu"))
    assert not os.path.exists(hist)
    assert line["mfu"] is None


def test_infer_mode_emits_latency_line():
    """--infer (the reference inference/tests/api latency-harness role):
    one JSON line with examples/sec + p50/p99 latency, suffixed metric."""
    d = _run("--infer", "--smoke", "--steps", "8", "--batch-size", "32")
    assert d["metric"] == "mnist_mlp_infer_throughput_b32"
    assert d["value"] > 0 and d["unit"] == "examples/sec"
    assert d["latency_ms_p50"] > 0
    assert d["latency_ms_p99"] >= d["latency_ms_p50"]


def test_infer_deepfm_sparse_redirects():
    d = _run("--infer", "--model", "deepfm_sparse", "--smoke")
    assert d["value"] == 0.0
    assert "use --model deepfm" in d["error"]


def test_nmt_decode_bench_contract():
    """Decode bench: cached and no-cache variants emit distinct metric
    keys (same workload, different implementation — the comparison must
    stay visible in history)."""
    d = _run("--model", "nmt_decode", "--smoke", "--steps", "4",
             "--batch-size", "2")
    assert d["metric"] == "nmt_decode_throughput_b2"
    assert d["unit"] == "tokens/sec" and d["value"] > 0
    d2 = _run("--model", "nmt_decode", "--no-kv-cache", "--smoke",
              "--steps", "4", "--batch-size", "2", timeout=900)
    assert d2["metric"] == "nmt_decode_throughput_nocache_b2"
    assert d2["value"] > 0


def test_gpt_decode_bench_contract():
    """GPT decode bench: greedy and speculative variants emit distinct
    metric keys; the speculative line carries the acceptance stats that
    turn machinery tokens/sec into the real-pair speedup formula."""
    d = _run("--model", "gpt_decode", "--smoke", "--steps", "4",
             "--batch-size", "2")
    assert d["metric"] == "gpt_decode_throughput_b2"
    assert d["unit"] == "tokens/sec" and d["value"] > 0
    d2 = _run("--model", "gpt_decode", "--gamma", "2", "--smoke",
              "--steps", "4", "--batch-size", "2", timeout=900)
    assert d2["metric"] == "gpt_decode_throughput_g2_b2"
    assert d2["value"] > 0
    assert "accept_per_round" in d2 and "rounds" in d2


def test_gpt_serve_bench_contract():
    """Continuous-batching serving bench emits tokens/sec; the W8A16
    variant forks its history key (else fill runs would clobber the
    bf16 headline record)."""
    d = _run("--model", "gpt_serve", "--smoke", "--steps", "50",
             "--batch-size", "2", timeout=900)
    assert d["metric"] == "gpt_serve_throughput_b2"
    assert d["unit"] == "tokens/sec" and d["value"] > 0
    d2 = _run("--model", "gpt_serve", "--smoke", "--steps", "50",
              "--batch-size", "2", "--weight-only", timeout=900)
    assert d2["metric"] == "gpt_serve_throughput_w8_b2"
    assert d2["value"] > 0


def test_gpt_serve_paged_key():
    d = _run("--model", "gpt_serve", "--smoke", "--steps", "50",
             "--batch-size", "2", "--paged", timeout=900)
    assert d["metric"] == "gpt_serve_throughput_paged_b2"
    assert d["value"] > 0


def test_gpt_serve_new_knob_keys():
    """The r5 serving knobs fork their own history keys — and
    --decode-steps 1 is the BASELINE (identical run, no _ds1 fork)."""
    d = _run("--model", "gpt_serve", "--smoke", "--steps", "50",
             "--batch-size", "2", "--decode-steps", "4", timeout=900)
    assert d["metric"] == "gpt_serve_throughput_ds4_b2"
    assert d["unit"] == "tokens/sec" and d["value"] > 0
    # minimal steps: this run exists only to pin the NO-FORK key (the
    # identical-workload property); its throughput number is discarded
    d1 = _run("--model", "gpt_serve", "--smoke", "--steps", "4",
              "--batch-size", "2", "--decode-steps", "1", timeout=900)
    assert d1["metric"] == "gpt_serve_throughput_b2"
    d2 = _run("--model", "gpt_serve", "--smoke", "--steps", "50",
              "--batch-size", "2", "--gamma", "2", "--prefill-chunk",
              "16", timeout=900)
    assert d2["metric"] == "gpt_serve_throughput_g2_pc16_b2"
    assert "accept_per_round" in d2
