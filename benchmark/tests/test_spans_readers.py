"""``program_spans`` and the seven readers built on it, on hand-made
events, on recordings of real v5e traces (``data/*.spans.json``: one
``pt_train_step`` run and 0.7 s of the chat cell's host spans and
``XLA Modules`` line, as ``program_spans.read_xplane`` gave them on the
chip in PR 25, operation names shortened;
``data/train.opnames.xplane.pb``: the same trace's first TPU plane cut
to the METADATA of the operations under ``linear_ce`` and of the flash
kernels, no lines), on a hand-encoded ``XSpace`` and on one real capture
of the CPU backend."""

import json
import os

import pytest

from benchmark.harness import manifest, program_spans as P
from benchmark.tests import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000
TRAIN_METRICS = ("flash_fwd_ms", "flash_bwd_ms", "ce_head_ms")
CHAT_METRICS = ("tick_host_ms", "prefill_ms", "arena_queue_wait_ms",
                "replica_lock_wait_ms")


def ev(name, start, dur, line=1, **stats):
    return {"name": name, "start": start, "dur": dur, "line": line,
            "stats": stats}


def recorded(name):
    with open(os.path.join(DATA, name + ".spans.json")) as f:
        return json.load(f)


@pytest.fixture
def use(monkeypatch):
    """Readers get ``trace`` from ``load`` for a traced run, as they
    would from the newest file under ``.bench_trace/``."""
    def install(trace):
        monkeypatch.setattr(
            P, "load", lambda run, root=None: trace if run.get("trace")
            else None)
    return install


def read(metric, run):
    return manifest.load_reader(metric)(run)


# -- the reductions, on hand-made events ------------------------------------

def test_inside_is_containment_on_one_line():
    tick = ev("serve.tick", 0, 100)
    host = [tick, ev("serve.admit", 5, 10), ev("serve.prefill", 6, 8),
            ev("serve.step.fetch", 20, 60),
            ev("replica.lock_wait.submit", 10, 50, line=2),
            ev("serve.step.emit", 95, 10)]           # ends after the tick
    assert [e["name"] for e in P.inside(host, tick)] == [
        "serve.admit", "serve.prefill", "serve.step.fetch"]
    assert P.inside(host, tick, ("serve.prefill",)) == [host[2]]


def test_children_split_counts_a_grandchild_once():
    host = [ev("serve.tick", 0, 100), ev("serve.admit", 0, 40),
            ev("serve.prefill", 5, 30), ev("serve.step.fetch", 40, 50),
            ev("serve.tick", 200, 10), ev("serve.admit", 200, 4)]
    total, split = P.children_split(host, "serve.tick")
    assert total == 110
    assert split == {"serve.admit": 44, "serve.step.fetch": 50}


def test_covered_is_a_union():
    assert P.covered_ns([ev("a", 0, 10), ev("b", 5, 10),
                         ev("c", 30, 5)]) == 20
    assert P.covered_ns([]) == 0


def test_self_time_leaves_out_nested_operations():
    ops = [ev("%while.1", 0, 100), ev("%fusion.1", 10, 30),
           ev("%fusion.2", 50, 30), ev("%copy.1", 120, 5)]
    assert P.self_ns(ops) == [40, 30, 30, 5]


def test_clocks_agree_counts_ticks_with_a_step_between_dispatch_and_fetch():
    host = []
    for k in range(3):
        t = k * 100
        host += [ev("serve.tick", t, 90), ev("serve.step.dispatch", t + 5, 10),
                 ev("serve.step.fetch", t + 15, 60)]
    host.append(ev("serve.tick", 300, 50))          # admit only: no step
    modules = [{"name": "jit_pt_decode_step(1)", "start": 12, "dur": 60},
               {"name": "jit_pt_prefill_256(2)", "start": 120, "dur": 5},
               {"name": "jit_pt_decode_step(1)", "start": 290, "dur": 60}]
    # tick 0 has its step; tick 1 only a prefill; tick 2's step starts
    # after its fetch ended
    assert P.clocks_agree(host, modules) == (3, 1)


def test_kernel_and_scope_time_a_step():
    trace = {"host": [], "modules": [
        {"name": "jit_pt_train_step(9)", "start": 0, "dur": 1000 * MS},
        {"name": "jit__unstack(3)", "start": 1001 * MS, "dur": 1},
        {"name": "jit_pt_train_step(9)", "start": 1002 * MS,
         "dur": 1000 * MS}],
        "ops": [ev("%pt_flash_fwd.1 custom-call", 0, 10 * MS),
                ev("%pt_flash_fwd.2 custom-call", 20 * MS, 14 * MS),
                ev("%pt_flash_dq.1 custom-call", 40 * MS, 6 * MS),
                ev("%while.3 while", 100 * MS, 50 * MS,
                   tf_op="jit(pt_train_step)/jvp(linear_ce)/while"),
                ev("%fusion.7 fusion", 110 * MS, 30 * MS,
                   tf_op="jit(pt_train_step)/jvp(linear_ce)/while/body/dot"),
                ev("%fusion.8 fusion", 200 * MS, 8 * MS)]}
    assert P.kernel_ms_a_step(trace, ("pt_flash_fwd",)) == (12.0, 2, 2)
    assert P.kernel_ms_a_step(trace, ("pt_flash_dq", "pt_flash_dkdv")) \
        == (3.0, 1, 2)
    assert P.kernel_ms_a_step(trace, ("pt_flash_decode",)) is None
    # the while's 50 ms hold the fusion's 30: self time counts them once
    assert P.scope_ms_a_step(trace, "linear_ce") == (25.0, 2, 2)
    assert P.scope_ms_a_step(trace, "attn") is None
    assert P.kernel_ms_a_step(None, ("pt_flash_fwd",)) is None


def test_a_program_is_matched_by_its_whole_name():
    modules = [{"name": n, "start": i, "dur": 1} for i, n in enumerate((
        "jit_pt_train_step(9)", "jit_pt_train_steps_4(8)",
        "jit_pt_train_accum_step(7)", "jit_pt_decode_step(1)",
        "jit_pt_decode_step_k4(2)", "jit_pt_train_step(9)"))]
    # a run of pt_train_steps_4 holds four steps: counting it as one
    # would make "ms a step" four times too large
    assert len(P.step_runs(modules, "pt_train_step")) == 2
    assert len(P.step_runs(modules, "pt_decode_step")) == 1
    assert len(P.step_runs(modules, "pt_decode_step_k4")) == 1
    assert P.step_runs(modules, "pt_train") == []


# -- the op_name of an operation: the file's own messages -------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    return _varint(no << 3 | 2) + _varint(len(value)) + bytes(value)


def _plane(name, stat_names, metadata):
    body = _field(1, 7) + _field(2, name.encode())
    body += _field(3, _field(1, 1) + _field(2, b"XLA Ops"))     # a line
    for sid, sname in stat_names.items():
        body += _field(5, _field(1, sid) + _field(
            2, _field(1, sid) + _field(2, sname.encode())))
    for mid, (mname, stats) in metadata.items():
        msg = _field(1, mid) + _field(2, mname.encode())
        for stat in stats:
            msg += _field(5, stat)
        body += _field(4, _field(1, mid) + _field(2, msg))
    return body


def test_op_names_reads_the_metadata_stat(tmp_path):
    stats = {1: "hlo_category", 2: "tf_op", 3: "jit(f)/mlp/dot_general:"}
    device = _plane("/device:TPU:0", stats, {
        10: ("%while.3 = while(...)", [
            _field(1, 1) + _field(5, b"while"),
            _field(1, 2) + _field(5, b"jit(f)/jvp(linear_ce)/while:")]),
        11: ("%fusion.9 = fusion(...)", [_field(1, 2) + _field(7, 3)]),
        12: ("%copy.1 = copy(...)", [_field(1, 1) + _field(5, b"copy")])})
    other = _plane("/device:TPU:1", stats, {
        10: ("%elsewhere", [_field(1, 2) + _field(5, b"jit(g)/x:")])})
    host = _plane("/host:CPU", {}, {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, other) + _field(1, device))
    assert P.op_names(str(path)) == {
        "%while.3 = while(...)": "jit(f)/jvp(linear_ce)/while:",
        "%fusion.9 = fusion(...)": "jit(f)/mlp/dot_general:"}
    only_host = tmp_path / "h.xplane.pb"
    only_host.write_bytes(_field(1, host))
    assert P.op_names(str(only_host)) == {}


def test_op_names_of_a_real_v5e_trace():
    """The decoder's field numbers against what jaxlib's profiler wrote
    on the chip: an empty map here means ``ce_head_ms`` reads None."""
    got = P.op_names(os.path.join(DATA, "train.opnames.xplane.pb"))
    head = [k for k, v in got.items() if "linear_ce" in v]
    assert len(head) >= 20, sorted(got)
    scopes = {got[k] for k in head}                 # forward and backward
    assert any("/jvp(linear_ce)/while/body/" in v for v in scopes)
    assert any("/transpose(jvp(linear_ce))/" in v for v in scopes)
    for kernel in ("pt_flash_fwd", "pt_flash_dq", "pt_flash_dkdv"):
        mine = [v for k, v in got.items() if k.startswith("%" + kernel)]
        assert mine and all(f"/{kernel}/" in v for v in mine), kernel


def test_scope_time_reads_the_op_names_once_and_only_when_asked(monkeypatch):
    path = os.path.join(DATA, "train.opnames.xplane.pb")
    names, calls = P.op_names(path), []
    monkeypatch.setattr(P, "op_names",
                        lambda p: calls.append(p) or names)
    under = next(k for k, v in names.items() if "linear_ce" in v)
    trace = {"host": [], "path": path, "modules": [
        {"name": "jit_pt_train_step(9)", "start": 0, "dur": 100 * MS}],
        "ops": [ev(under, 0, 10 * MS), ev("%fusion.1 = fusion()", 20 * MS, MS),
                ev(next(k for k in names if k.startswith("%pt_flash_fwd")),
                   40 * MS, 6 * MS)]}
    assert P.kernel_ms_a_step(trace, ("pt_flash_fwd",)) == (6.0, 1, 1)
    assert calls == []                     # kernels are named by the event
    assert P.scope_ms_a_step(trace, "linear_ce") == (10.0, 1, 1)
    assert P.scope_ms_a_step(trace, "linear_ce") == (10.0, 1, 1)
    assert calls == [path]


# -- the readers on the recordings ------------------------------------------

def test_train_readers_on_the_recorded_step(use):
    use(recorded("train"))
    run = {"kind": "train", "trace": {"ops": []}}
    fwd, bwd = read("flash_fwd_ms", run), read("flash_bwd_ms", run)
    ce = read("ce_head_ms", run)
    # one step of internlm2-1.8b.pretrain_2k on a v5e (PR 25): remat's
    # second forward makes 8 forward calls for 4 layers
    assert fwd == pytest.approx(126.2, abs=0.5)
    assert bwd == pytest.approx(123.6, abs=0.5)
    assert ce == pytest.approx(145.3, abs=0.5)
    # what flash_roofline_pct divides by in the same step: every
    # tpu_custom_call of the step is one of the three kernels
    kernels = [e for e in recorded("train")["ops"]
               if "tpu_custom_call" in e["name"]]
    assert len(kernels) == 16
    assert sum(e["dur"] for e in kernels) / MS == pytest.approx(fwd + bwd)


def test_chat_readers_on_the_recorded_slice(use):
    trace = recorded("chat")
    use(trace)
    run = {"kind": "serve", "trace": {"ops": []}}
    values = {m: read(m, run) for m in CHAT_METRICS}
    assert all(v is not None for v in values.values()), values
    ticks = P.named(trace["host"], "serve.tick")
    whole = P.median_ms([t["dur"] for t in ticks])
    # the host's part is what is left of a tick outside the fetch and
    # the prefills: a few milliseconds of a tick of 25 to 35
    assert 1.0 < values["tick_host_ms"] < 0.5 * whole
    assert 20.0 < values["prefill_ms"] < 120.0
    assert values["arena_queue_wait_ms"] >= 0.0
    assert values["replica_lock_wait_ms"] > 0.0
    n, hits = P.clocks_agree(trace["host"], trace["modules"])
    assert n == len(ticks) and hits == n
    total, split = P.children_split(trace["host"], "serve.tick")
    fetch = split["serve.step.fetch"]
    assert (sum(split.values()) - fetch) / (total - fetch) > 0.9


@pytest.mark.parametrize("metric", TRAIN_METRICS + CHAT_METRICS)
def test_readers_return_none_without_a_trace_or_without_spans(use, metric):
    kind = "train" if metric in TRAIN_METRICS else "serve"
    use(recorded(kind if kind == "train" else "chat"))
    # not a traced run
    assert read(metric, {"kind": kind, "trace": None}) is None
    # a traced run of a program without spans or pt_* names (the parent
    # of the PR that added them)
    use({"host": [], "ops": [ev("%checkpoint.3 custom-call", 0, MS)],
         "modules": [{"name": "jit__step(1)", "start": 0, "dur": MS}]})
    assert read(metric, {"kind": kind, "trace": {"ops": []}}) is None
    # no trace file under .bench_trace/
    use(None)
    assert read(metric, {"kind": kind, "trace": {"ops": []}}) is None


@pytest.mark.parametrize("metric", TRAIN_METRICS)
def test_train_readers_leave_a_serving_run_alone(use, metric):
    use(recorded("train"))
    assert read(metric, {"kind": "serve", "trace": {"ops": []}}) is None


def test_the_seven_are_registered_for_their_cells():
    m = manifest.load_manifest()
    rows = {r["name"]: r for r in m["per_layer"]}
    for name in TRAIN_METRICS:
        assert rows[name]["workloads"] == [tiny.TRAIN]
        assert rows[name]["source"] == "device_trace"
        assert rows[name]["moves"] == "train_tokens_per_s"
    for name in CHAT_METRICS:
        assert rows[name]["workloads"] == [tiny.CHAT]
        assert rows[name]["source"] == "program_span"
    # new entries go to the end of the list, behind PR 24's thirteen
    assert [r["name"] for r in m["per_layer"]][13:] == list(
        CHAT_METRICS + TRAIN_METRICS)


# -- one real capture, CPU backend ------------------------------------------

def test_load_finds_the_newest_trace_and_parses_it_once(tmp_path,
                                                        monkeypatch):
    # a serving run never pays for the pass over the file's METADATA
    monkeypatch.setattr(P, "op_names", lambda path: 1 / 0)
    job = tiny.run_job(tiny.cell(tiny.CHAT), seconds=1.0, trace=True)
    assert job["xplane"] == P.newest_xplane()
    run = dict(job["run"], trace={"ops": []})
    trace = P.load(run)
    assert P.load(run) is trace                      # parsed once
    assert P.load(dict(run, trace=None)) is None
    assert P.load(run, root=str(tmp_path)) is None   # nothing there
    names = {e["name"] for e in trace["host"]}
    assert {"serve.tick", "serve.admit", "serve.prefill",
            "serve.step.dispatch", "serve.step.fetch", "serve.step.emit",
            "serve.step.cursor", "replica.lock_wait.submit"} <= names
    pre = P.named(trace["host"], "serve.prefill")[0]
    assert {"rid", "plen", "bucket", "queued_us"} <= set(pre["stats"])
    # the CPU has no device plane: the host's metrics read, the
    # device's do not
    assert trace["ops"] == [] and trace["modules"] == []
    for metric in CHAT_METRICS:
        assert read(metric, run) is not None, metric

    # the hand decoder against jax's own reader on the file this jaxlib
    # just wrote: the planes, a plane's event and stat METADATA, and an
    # event METADATA's own stats (where a chip's trace keeps ``tf_op``)
    from jax.profiler import ProfileData

    planes = P._planes(job["xplane"])
    theirs = {p.name: p for p in
              ProfileData.from_file(job["xplane"]).planes}
    assert set(planes) == set(theirs) and "/host:CPU" in planes
    stat_names, events = P._metadata(planes["/host:CPU"])
    seen = [e for line in theirs["/host:CPU"].lines for e in line.events]
    assert {e.name for e in seen} <= {name for name, _ in events}
    assert {str(k) for e in seen[:2000] for k, _ in e.stats} \
        <= set(stat_names.values())
    stat_names, events = P._metadata(planes["/host:metadata"])
    assert any(stat_names.get(row.get(1)) == "Hlo Proto"
               for _, stats in events for row in stats)
