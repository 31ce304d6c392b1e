"""``trace_reduce`` on hand-made events and on recordings of real v5e
traces (``data/*.trace.json``: slices of PR 24's chip traces as
``tools/trace_summary.py`` wrote them, names shortened)."""

import json
import os

import pytest

from benchmark.harness import manifest, trace_reduce as T
from benchmark.tests import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1000


def dev(ops, modules=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": T.OPS_LINE, "events": list(ops)},
            {"name": T.MODULES_LINE, "events": list(modules)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": list(host)}]}]}


def test_busy_is_a_union_and_idle_its_complement():
    ops = [["%a fusion", 0, 100 * US], ["%b fusion", 50 * US, 100 * US],
           ["%c fusion", 400 * US, 100 * US]]
    assert T.merged(ops) == [(0, 150 * US), (400 * US, 500 * US)]
    r = T.reduce(dev(ops))
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["span_s"] == pytest.approx(500e-6)


def test_nested_events_are_not_counted_twice():
    ops = [["%while.1 = (s32[]) while(s32[] %x)", 0, 100 * US],
           ["%body.1 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
            10 * US, 30 * US],
           ["%body.2 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop",
            50 * US, 30 * US]]
    assert T.busy_seconds(ops) == pytest.approx(100e-6)
    assert dict(T.top_ops(ops)) == {"%while while": pytest.approx(40e-6),
                                    "%body fusion": pytest.approx(60e-6)}


def test_a_kernel_is_found_by_its_custom_call_target():
    name = ('%closed_call.33 = f32[16,32,128]{2,1,0:T(8,128)} custom-call('
            's32[16]{0:T(128)} %t.1), custom_call_target="tpu_custom_call"')
    assert T.short_name(name) == \
        "%closed_call.33 custom-call:tpu_custom_call"
    assert T.family(name) == "%closed_call custom-call:tpu_custom_call"
    ops = [[name, 0, 7 * US], ["%copy.1 = f32[8]{0} copy(f32[8]{0} %p)",
                               10 * US, 5 * US]]
    assert sum(e[2] for e in T.kernel_events(ops, "tpu_custom_call")) \
        == 7 * US


def test_gaps_are_labelled_by_what_the_host_was_doing():
    ops = [["%a fusion", 0, 100 * US], ["%b fusion", 400 * US, 100 * US],
           ["%c fusion", 505 * US, 100 * US],
           ["%d fusion", 1000 * US, 10 * US]]
    host = [["bench.fetch", 0, 450 * US],
            ["np.asarray(jax.Array)", 90 * US, 330 * US]]
    gaps = dict(T.idle_gaps(ops, host))
    # the shortest span covering half the gap wins; a gap nobody covers
    # is named after the operation before it
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(300e-6)
    assert gaps["gaps under 20 us"] == pytest.approx(5e-6)
    assert gaps["after %c fusion"] == pytest.approx(395e-6)


def test_whole_runs_counts_the_main_program():
    mods = [["jit__step(1)", 0, 700], ["jit__unstack(2)", 710, 1],
            ["jit__step(1)", 720, 700]]
    assert T.whole_runs(mods) == 2 and T.whole_runs([]) == 0


def recording(name):
    with open(os.path.join(DATA, name + ".trace.json")) as f:
        return json.load(f)


def test_recorded_train_trace():
    """A slice of the v5e trace of internlm2-1.8b.pretrain_2k: one chip
    plane; the flash kernels are the only ``tpu_custom_call`` events."""
    t = recording("train")
    assert [p["name"] for p in T.device_planes(t)] == ["/device:TPU:0"]
    r = T.reduce(t)
    assert 0.9 < r["busy_s"] / r["span_s"] <= 1.0
    kernels = T.kernel_events(r["ops"], "tpu_custom_call")
    fams = {T.family(e[0]).split(" ")[0] for e in kernels}
    assert fams <= {"%jvp__", "%rematted_computation", "%checkpoint"}
    assert len(kernels) >= 16 and T.whole_runs(r["modules"]) >= 1
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert any(n.startswith("bench.") for n, _ in
               r["breakdown"]["idle_gaps"])
    # the reader gives a share of the roofline between 0 and 100
    cell = tiny.cell(tiny.TRAIN)
    full = manifest.Cell(manifest.load_manifest(), tiny.TRAIN)
    run = {"kind": "train", "trace": r, "traffic": full.traffic,
           "family": full.family,
           "dims": full.family.Dims.from_config(full.config),
           "device": {"peaks": {"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9}}}
    share = manifest.load_reader("flash_roofline_pct")(run)
    assert 0.5 < share < 100.0
    assert cell.kind == "train"


def test_recorded_serve_trace():
    """A slice of the v5e trace of mistral-7b-v0.1.chat_closed16: the
    synchronous tick leaves the chip idle between steps."""
    r = T.reduce(recording("chat"))
    assert 0.05 < 1.0 - r["busy_s"] / r["span_s"] < 0.6
    kernels = T.kernel_events(r["ops"], "tpu_custom_call")
    assert kernels and {T.family(e[0]) for e in kernels} == {
        "%closed_call custom-call:tpu_custom_call"}
    steps = [m for m in r["modules"] if m[0].startswith("jit_step(")]
    assert len(steps) >= 2 and any(
        m[0].startswith("jit_prefill(") for m in r["modules"])
    full = manifest.Cell(manifest.load_manifest(), tiny.CHAT)
    run = {"kind": "serve", "trace": r, "ticks": 100, "tick_tokens": 1400,
           "mean_context_tokens": 450.0, "family": full.family,
           "dims": full.family.Dims.from_config(full.config),
           "device": {"peaks": {"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9}}}
    share = manifest.load_reader("decode_attn_roofline_pct")(run)
    assert 1.0 < share < 100.0
