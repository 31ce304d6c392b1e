"""Distributed request tracing plane (telemetry.tracing + wiring):
trace contexts minted at router admission and propagated through every
hop (in-process binding, the X-PT-Trace HTTP header, the KVHandoff
wire form), per-process span rings with a clock-offset handshake,
fleet /tracez fan-in merging one chrome-trace across OS processes, and
tail-latency exemplars linking histogram buckets to trace ids.

Tiers: deterministic unit tests (context/sampling/merge/lint), an
in-process disaggregated-serving trace e2e over real tiny-GPT
replicas, failure-path propagation over stub replicas, the
zero-cost-when-disabled pin, and a slow+chaos 2-worker-process HTTP
e2e (the ci.sh 'trace smoke' stage: one routed request -> ONE merged
chrome-trace spanning >= 2 pids on one trace id)."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import telemetry
from paddle_tpu.models import gpt as G
from paddle_tpu.resilience import FaultInjector
from paddle_tpu.serving import BatchedDecoder, KVHandoff
from paddle_tpu.serving_router import (LocalReplica, Router,
                                       spawn_replicas)
from paddle_tpu.telemetry import tracing

from replica_spec import SPEC, worker_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    tracing.set_sample_rate(1.0)
    yield
    telemetry.disable()
    telemetry.reset()
    tracing.set_sample_rate(1.0)


def _decoder(seed=0, **kw):
    pt.seed(seed)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    kw.setdefault("slots", 2)
    kw.setdefault("capacity", 128)
    kw.setdefault("pages", 24)
    kw.setdefault("page_size", 64)
    return BatchedDecoder(model, **kw)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 512, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# context + wire form
# ---------------------------------------------------------------------------

class TestTraceContext:
    def test_header_roundtrip(self):
        ctx = tracing.new_trace()
        h = ctx.to_header()
        back = tracing.from_header(h)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == ctx.span_id
        assert back.sampled is True

    def test_unsampled_flag_survives_the_wire(self):
        ctx = tracing.new_trace(sampled=False)
        assert ctx.to_header().endswith("-00")
        assert tracing.from_header(ctx.to_header()).sampled is False

    def test_malformed_header_degrades_to_none(self):
        for bad in (None, "", "zzz", "a-b", "a-b-c-d"):
            assert tracing.from_header(bad) is None

    def test_sampling_rates(self):
        assert tracing.new_trace(rate=1.0).sampled is True
        assert tracing.new_trace(rate=0.0).sampled is False
        tracing.set_sample_rate(0.0)
        assert tracing.new_trace().sampled is False

    def test_kvhandoff_carries_trace_over_the_wire(self):
        ctx = tracing.new_trace()
        h = KVHandoff(_prompt(4), 4, np.zeros(8, np.float32),
                      [(np.zeros((1, 64, 2, 8), np.float32),
                        np.zeros((1, 64, 2, 8), np.float32))],
                      64, trace=ctx)
        back = KVHandoff.from_bytes(h.to_bytes())
        assert back.trace.trace_id == ctx.trace_id
        # traceless handoffs stay traceless
        h2 = KVHandoff(_prompt(4), 4, np.zeros(8, np.float32),
                       [(np.zeros((1, 64, 2, 8), np.float32),
                         np.zeros((1, 64, 2, 8), np.float32))], 64)
        assert KVHandoff.from_bytes(h2.to_bytes()).trace is None


class TestSpansAndRing:
    def test_span_records_only_enabled_and_sampled(self):
        ctx = tracing.new_trace()
        with tracing.span("off", ctx=ctx):      # telemetry disabled
            pass
        assert tracing.spans(ctx.trace_id) == []
        telemetry.enable()
        with tracing.span("no_ctx"):            # nothing bound
            pass
        assert all(s["name"] != "no_ctx" for s in tracing.spans())
        cold = tracing.new_trace(sampled=False)
        with tracing.span("unsampled", ctx=cold):
            pass
        assert tracing.spans(cold.trace_id) == []
        with tracing.span("hot", ctx=ctx, k=1):
            pass
        (s,) = tracing.spans(ctx.trace_id)
        assert s["name"] == "hot" and s["args"]["k"] == 1
        assert s["parent_id"] == ctx.span_id
        assert s["pid"] == os.getpid() and s["thread"]

    def test_nesting_parents_through_bind(self):
        telemetry.enable()
        ctx = tracing.new_trace()
        with tracing.bind(ctx):
            with tracing.span("outer") as outer:
                assert tracing.current() is outer.context
                with tracing.span("inner"):
                    pass
                tracing.event("marker", note="x")
        by_name = {s["name"]: s for s in tracing.spans(ctx.trace_id)}
        assert by_name["outer"]["parent_id"] == ctx.span_id
        assert by_name["inner"]["parent_id"] == \
            by_name["outer"]["span_id"]
        assert by_name["marker"]["parent_id"] == \
            by_name["outer"]["span_id"]
        assert by_name["marker"]["instant"] is True
        assert tracing.current() is None  # fully unwound

    def test_untraced_event_records_with_null_trace_id(self):
        """The fleet preempt-agreement form: rank-tagged instants with
        no per-request trace still land on the ring (and the fleet
        fan-in shows them on the rank's lane)."""
        telemetry.enable()
        tracing.event("fleet.preempt.ack", rank=3, step=7)
        recs = [s for s in tracing.spans()
                if s["name"] == "fleet.preempt.ack"]
        assert recs and recs[0]["trace_id"] is None
        assert recs[0]["args"] == {"rank": 3, "step": 7}


# ---------------------------------------------------------------------------
# clock-aligned merge
# ---------------------------------------------------------------------------

class TestMergeChromeTrace:
    def _coll(self, pid, proc, wall0, perf0, spans):
        return {"pid": pid, "proc": proc,
                "clock": {"wall_ns": wall0, "perf_ns": perf0},
                "spans": spans}

    def test_clock_offsets_align_processes(self):
        """Two processes whose monotonic clocks disagree by a huge
        offset: the SAME wall instant must merge to the SAME chrome
        timestamp."""
        wall = 1_700_000_000_000_000_000
        a = self._coll(1, "router", wall, 1_000, [
            {"name": "a", "trace_id": "t", "span_id": "s1",
             "parent_id": None, "ts_ns": 1_000, "dur_ns": 2_000,
             "pid": 1, "tid": 11, "thread": "MainThread", "args": {}}])
        b = self._coll(2, "decode0", wall, 999_999_000, [
            {"name": "b", "trace_id": "t", "span_id": "s2",
             "parent_id": "s1", "ts_ns": 999_999_000, "dur_ns": 1_000,
             "pid": 2, "tid": 22, "thread": "pt-replica", "args": {}}])
        doc = tracing.merge_chrome_trace([a, b])
        ev = {e["name"]: e for e in doc["traceEvents"]
              if e["ph"] == "X"}
        assert ev["a"]["ts"] == ev["b"]["ts"] == wall / 1e3
        assert ev["a"]["pid"] == 1 and ev["b"]["pid"] == 2

    def test_lane_metadata_and_tracez_payload_shape(self):
        rows = [{"name": "x", "trace_id": "t", "span_id": "s",
                 "parent_id": None, "ts_ns": 5, "dur_ns": 1, "pid": 9,
                 "tid": 90, "thread": "pt-reader-0", "args": {}}]
        # a replica's /tracez JSON uses "trace_spans" — accepted as-is
        doc = tracing.merge_chrome_trace([
            {"pid": 9, "proc": "decode0",
             "clock": {"wall_ns": 10, "perf_ns": 0},
             "trace_spans": rows}])
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {(e["name"], e["args"]["name"]) for e in meta} == {
            ("process_name", "decode0"),
            ("thread_name", "pt-reader-0")}

    def test_instant_events_render_as_instants(self):
        telemetry.enable()
        tracing.event("mark", ctx=tracing.new_trace(), a=1)
        doc = tracing.merge_chrome_trace([tracing.collection()])
        marks = [e for e in doc["traceEvents"] if e["name"] == "mark"]
        assert marks and marks[0]["ph"] == "i"


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

class TestExemplars:
    def test_observe_with_exemplar_and_openmetrics_rendering(self):
        telemetry.enable()
        h = telemetry.registry().histogram(
            "pt_t_ttft_seconds", "d", unit="s", buckets=(0.1, 1.0))
        h.observe(0.05)                    # no exemplar: plain line
        h.observe(5.0, exemplar="cafe01")  # top bucket carries it
        top = h.top_exemplar()
        assert top["trace_id"] == "cafe01" and top["value"] == 5.0
        text = telemetry.openmetrics_text()
        assert text.endswith("# EOF\n")
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("pt_t_ttft_seconds_bucket")]
        assert lines[0].endswith("} 1")  # no exemplar suffix
        assert '# {trace_id="cafe01"} 5.0' in lines[2]
        # the CLASSIC exposition never carries the syntax — one
        # suffixed line would make a strict text/plain parser (the
        # node-exporter textfile collector) drop the whole scrape
        assert "# {" not in telemetry.prometheus_text()

    def test_statusz_surfaces_top_bucket_exemplar(self):
        telemetry.enable()
        h = telemetry.registry().histogram(
            "pt_t_lat_seconds", "d", unit="s", buckets=(0.1, 1.0))
        h.observe(0.5, exemplar="feed02")
        from paddle_tpu.telemetry.server import DebugServer

        st = DebugServer().statusz()
        assert st["exemplars"]["pt_t_lat_seconds"]["trace_id"] == \
            "feed02"


# ---------------------------------------------------------------------------
# PT-LINT-306 (trace-header propagation lint)
# ---------------------------------------------------------------------------

class TestLint306:
    def _codes(self, src, path):
        from paddle_tpu.analysis.lint import lint_source

        return [d.code for d in lint_source(src, path)]

    def test_post_without_header_flags_in_trace_files(self):
        src = ("import urllib.request\n"
               "def post(url, body):\n"
               "    req = urllib.request.Request(url, data=body,"
               " method='POST')\n"
               "    return urllib.request.urlopen(req)\n")
        assert "PT-LINT-306" in self._codes(
            src, "paddle_tpu/serving_router.py")
        # same code elsewhere is not a trace-plane hop
        assert "PT-LINT-306" not in self._codes(src, "tools/foo.py")

    def test_helper_call_satisfies_the_rule(self):
        src = ("import urllib.request\n"
               "def post(url, body):\n"
               "    h = _trace_headers({})\n"
               "    req = urllib.request.Request(url, data=body,"
               " headers=h, method='POST')\n"
               "    return urllib.request.urlopen(req)\n")
        assert "PT-LINT-306" not in self._codes(
            src, "paddle_tpu/serving_router.py")

    def test_do_post_handler_must_consult_the_header(self):
        src = ("class H:\n"
               "    def do_POST(self):\n"
               "        return self.handle()\n")
        assert "PT-LINT-306" in self._codes(
            src, "paddle_tpu/telemetry/server.py")
        src_ok = ("class H:\n"
                  "    def do_POST(self):\n"
                  "        ctx = from_header(self.headers.get(h))\n"
                  "        return self.handle(ctx)\n")
        assert "PT-LINT-306" not in self._codes(
            src_ok, "paddle_tpu/telemetry/server.py")

    def test_repo_trace_files_lint_clean(self):
        from paddle_tpu.analysis.lint import lint_paths

        root = os.path.join(REPO, "paddle_tpu")
        found = [d for d in lint_paths(
            [os.path.join(root, "serving_router.py"),
             os.path.join(root, "telemetry", "server.py")])
            if d.code == "PT-LINT-306"]
        assert found == [], [str(d) for d in found]


# ---------------------------------------------------------------------------
# in-process serving e2e: one trace across the disaggregated pipeline
# ---------------------------------------------------------------------------

def test_disaggregated_request_yields_one_span_tree():
    """One routed long-prompt request through a prefill worker and a
    decode replica (all in-process): every hop's span shares ONE trace
    id — admission, dispatch, disagg prefill, prefill export, handoff
    import, first token, decode ticks, done — and the TTFT histograms
    (router AND replica side) carry that trace id as their top-bucket
    exemplar."""
    telemetry.enable()
    reps = [LocalReplica(_decoder(), name=f"r{i}").start()
            for i in range(2)]
    pw = LocalReplica(_decoder(), name="pf0")
    for rep in reps:
        rep.warmup()
    pw.decoder.prefill_export(np.asarray([1, 2], np.int32))
    pw.decoder._warmed = True
    router = Router(reps, prefill_workers=[pw], disagg_min_tokens=32,
                    poll_interval_s=0.02)
    try:
        t = router.submit(_prompt(40, 7), 6, session="s0")
        router.wait([t], timeout=300)
        assert t.ok and t.disaggregated and t.trace is not None
        tid = t.trace.trace_id
        names = {s["name"] for s in tracing.spans(tid)}
        assert {"router.admit", "router.dispatch",
                "router.disagg_prefill", "serve.prefill.export",
                "serve.handoff.import", "serve.first_token",
                "serve.decode.tick", "serve.done"} <= names
        # exemplars: both TTFT histograms point at this trace
        for metric in ("pt_router_ttft_seconds",
                       "pt_serving_ttft_seconds"):
            top = telemetry.registry().get(metric).top_exemplar()
            assert top["trace_id"] == tid, metric
        # parentage: every span's parent is another span of the SAME
        # trace (or the admission root)
        ids = {s["span_id"] for s in tracing.spans(tid)}
        ids.add(t.trace.span_id)
        assert all(s["parent_id"] in ids for s in tracing.spans(tid))
        # fan-in merge over in-process replicas: one collection, one
        # coherent chrome-trace
        fan = router.trace_fanin(tid)
        assert fan["errors"] == {}
        evs = [e for e in fan["trace"]["traceEvents"]
               if e["ph"] != "M"]
        assert len(evs) == len(tracing.spans(tid))
    finally:
        router.close()
        for rep in reps + [pw]:
            rep.close()


def test_short_prompt_submit_path_is_traced_too():
    telemetry.enable()
    rep = LocalReplica(_decoder(), name="r0").start()
    rep.warmup()
    router = Router([rep], poll_interval_s=0.02)
    try:
        t = router.submit(_prompt(6, 3), 4)
        router.wait([t], timeout=300)
        assert t.ok
        names = {s["name"] for s in tracing.spans(t.trace.trace_id)}
        assert {"router.admit", "router.dispatch", "serve.prefill",
                "serve.first_token", "serve.done"} <= names
    finally:
        router.close()
        rep.close()


# ---------------------------------------------------------------------------
# failure-path propagation (stub replicas — no model in the loop)
# ---------------------------------------------------------------------------

class _StubReplica:
    def __init__(self, name):
        self.name = name
        self.dead = False
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

    def inject(self, handoff, max_new, session=None):
        return self.submit(handoff.prompt, max_new, session)

    def prefill(self, prompt):
        self._check()
        return KVHandoff(prompt, len(prompt),
                         np.zeros(4, np.float32), [], 64)

    def drain_results(self):
        self._check()
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
        return {"queue_depth": 0, "active_slots": 0,
                "prefilling": 0, "slots": 2}

    def close(self):
        pass


def test_dispatch_failure_retry_keeps_one_trace_id():
    """A replica death mid-dispatch: the retry lands on the survivor
    with the SAME trace id, annotated by a router.retry event naming
    the failed replica and the retry count."""
    telemetry.enable()
    a, b = _StubReplica("a"), _StubReplica("b")
    inj = FaultInjector(seed=3).on("router.dispatch", times=1,
                                   match="a").arm()
    router = Router([a, b], poll_interval_s=0.01, dispatchers=1,
                    session_affinity=False)
    try:
        # session affinity off + least-loaded tie: dispatch may pick
        # either first — the injected fault fires on the first 'a'
        # dispatch; submit until one ticket rode the retry path
        t = None
        for i in range(8):
            cand = router.submit(_prompt(4, i), 3)
            router.wait([cand], timeout=60)
            if cand.retries:
                t = cand
                break
        assert t is not None, "no dispatch hit the injected fault"
        tid = t.trace.trace_id
        recs = tracing.spans(tid)
        retries = [s for s in recs if s["name"] == "router.retry"]
        assert retries and retries[0]["args"]["retries"] == 1
        dispatches = [s for s in recs
                      if s["name"] == "router.dispatch"]
        assert len(dispatches) >= 2  # original + retry, one trace
        assert {s["trace_id"] for s in recs} == {tid}
    finally:
        inj.disarm()
        router.close()


def test_trace_fanin_degrades_unreachable_replica_to_error_row():
    from paddle_tpu.serving_router import HttpReplica

    telemetry.enable()
    ok = _StubReplica("ok")
    gone = HttpReplica("http://127.0.0.1:9", name="gone",
                       timeout_s=0.2)
    router = Router([ok, gone], poll_interval_s=5.0, health_fails=1)
    try:
        fan = router.trace_fanin("deadbeefdeadbeef")
        assert "gone" in fan["errors"]          # degraded, not raised
        assert fan["sources"] == ["router"]
        assert "traceEvents" in fan["trace"]    # merge still produced
    finally:
        router.close()


def test_fleet_tracez_fanout_merges_ranks_without_recursion(tmp_path):
    """Every fleet rank mounts the SAME tracez fan-out on its own
    /tracez — the fan-out must fetch each peer's LOCAL ring (local=1),
    never the peer's fan-in, or two aggregators recurse into each
    other. Two rank servers in one process: rank 0's aggregation must
    return rank 1 as a merged source (not an error row) and the merged
    trace must carry the rank-tagged step spans + preempt events."""
    from paddle_tpu.resilience.controller import (FileTransport,
                                                  FleetController)
    from paddle_tpu.telemetry.server import DebugServer

    telemetry.enable()
    c0 = FleetController(rank=0, world=2,
                         transport=FileTransport(str(tmp_path), "r1"))
    c1 = FleetController(rank=1, world=2,
                         transport=FileTransport(str(tmp_path), "r1"))
    s0, s1 = DebugServer(), DebugServer()
    s0.set_trace_fanin(c0.tracez_fanout)
    s1.set_trace_fanin(c1.tracez_fanout)  # BOTH ranks aggregate
    s0.start()
    s1.start()
    try:
        c0.publish_endpoint(s0.host, s0.port)
        c1.publish_endpoint(s1.host, s1.port)
        tracing.event("fleet.preempt.ack", rank=1, step=5)
        with tracing.span("train.step", ctx=tracing.new_trace(),
                          rank=1, step=5):
            pass
        with urllib.request.urlopen(s0.url("/tracez?fanin=1"),
                                    timeout=30) as r:
            out = json.loads(r.read().decode())
        assert "error" not in out["ranks"]["1"], out["ranks"]
        names = {e["name"] for e in out["trace"]["traceEvents"]
                 if e["ph"] != "M"}
        assert {"fleet.preempt.ack", "train.step"} <= names
    finally:
        s0.stop()
        s1.stop()


def test_router_poll_loop_writes_node_exporter_textfile(tmp_path):
    """Router(textfile_path=...) re-writes the whole exposition from
    its poll loop — pt_router_* series reach scrape-less deployments
    through the same node-exporter file as everything else."""
    telemetry.enable()
    path = str(tmp_path / "router.prom")
    a = _StubReplica("a")
    router = Router([a], poll_interval_s=0.02, dispatchers=1,
                    textfile_path=path)
    try:
        t = router.submit(_prompt(4, 5), 3)
        router.wait([t], timeout=60)
        deadline = time.monotonic() + 30
        while not os.path.exists(path) and time.monotonic() < deadline:
            time.sleep(0.02)
        text = open(path).read()
        assert "pt_router_requests_total" in text
        assert "pt_router_replicas_healthy" in text
    finally:
        router.close()


def test_zero_tracing_code_when_disabled(monkeypatch):
    """The acceptance pin: with telemetry disabled, the request path
    executes NO tracing code — every tracing entry point is replaced
    with a tripwire and a full submit/serve/route cycle must never
    touch one."""
    def boom(*a, **k):
        raise AssertionError("tracing code ran while disabled")

    for fn in ("span", "event", "new_trace", "bind", "current",
               "from_header"):
        monkeypatch.setattr(tracing, fn, boom)
    assert not telemetry.enabled()
    dec = _decoder()
    dec.submit(_prompt(5, 1), 3)
    out = dec.run()
    assert all(len(v) == 3 for v in out.values())
    # the router path too (stub replicas; dispatch+drain+finish)
    a = _StubReplica("a")
    router = Router([a], poll_interval_s=0.01, dispatchers=1)
    try:
        t = router.submit(_prompt(4, 2), 3)
        router.wait([t], timeout=60)
        assert t.ok and t.trace is None
    finally:
        router.close()
    # and the handoff wire form stays traceless without tracing calls
    h = KVHandoff(_prompt(4), 4, np.zeros(8, np.float32),
                  [(np.zeros((1, 64, 2, 8), np.float32),
                    np.zeros((1, 64, 2, 8), np.float32))], 64)
    assert KVHandoff.from_bytes(h.to_bytes()).trace is None


# ---------------------------------------------------------------------------
# subprocess e2e: >= 2 OS processes, one merged clock-aligned trace
# (the ci.sh "trace smoke" stage; acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_trace_smoke_two_process_merged_trace(tmp_path):
    """One routed request through disaggregated prefill over REAL
    worker processes: the router's /tracez?trace_id= fan-in returns
    ONE merged chrome-trace whose request spans come from >= 2 OS
    processes (router + prefill worker + decode worker), all sharing a
    single trace id, with clock-aligned wall timestamps; the TTFT
    histogram's top bucket carries that trace id as an exemplar."""
    telemetry.enable()
    reps = spawn_replicas(SPEC, 2,
                          spec_kw={"smoke": True},
                          log_dir=str(tmp_path), env=worker_env())
    pfs = spawn_replicas(SPEC, 1,
                         role="prefill", spec_kw={"smoke": True},
                         log_dir=str(tmp_path), env=worker_env())
    router = Router(reps, prefill_workers=pfs, disagg_min_tokens=32,
                    poll_interval_s=0.05)
    srv = router.start_server(port=0)
    try:
        t_wall0 = time.time()
        t = router.submit(_prompt(48, 11), 5, session="s0")
        short = router.submit(_prompt(6, 12), 5, session="s1")
        router.wait([t, short], timeout=300)
        assert t.ok and t.disaggregated and short.ok
        tid = t.trace.trace_id

        # the aggregation endpoint end-to-end: GET the router's own
        # debug server, exactly what an operator would curl
        with urllib.request.urlopen(
                srv.url(f"/tracez?trace_id={tid}"), timeout=30) as r:
            fan = json.loads(r.read().decode())
        assert fan["errors"] == {}
        evs = [e for e in fan["trace"]["traceEvents"]
               if e["ph"] != "M"]
        assert evs and all(e["args"]["trace_id"] == tid for e in evs)

        # >= 2 OS processes on one trace (the acceptance criterion):
        # the router pid plus at least one worker pid
        pids = {e["pid"] for e in evs}
        assert os.getpid() in pids and len(pids) >= 2, pids
        worker_pids = {p.proc.pid for p in reps + pfs}
        assert pids & worker_pids

        # clock alignment: every merged timestamp is wall-clock µs
        # within this test's run window (a process merged on its raw
        # monotonic clock would land decades off)
        t_wall1 = time.time()
        for e in evs:
            assert t_wall0 - 60 <= e["ts"] / 1e6 <= t_wall1 + 60
        # and causality holds across processes: admission precedes
        # the decode-side completion
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], e)
        assert by_name["router.admit"]["ts"] <= \
            by_name["serve.done"]["ts"]
        # prefill-worker and decode-worker hops both present
        assert "serve.prefill.export" in by_name
        assert "serve.handoff.import" in by_name

        # the exemplar loop: the router TTFT histogram's top bucket
        # names a trace this fleet can actually render
        top = telemetry.registry().get(
            "pt_router_ttft_seconds").top_exemplar()
        assert top is not None
        with urllib.request.urlopen(
                srv.url(f"/tracez?trace_id={top['trace_id']}"),
                timeout=30) as r:
            fan2 = json.loads(r.read().decode())
        assert [e for e in fan2["trace"]["traceEvents"]
                if e["ph"] != "M"]
        # /metrics exposes the OpenMetrics exemplar syntax
        with urllib.request.urlopen(srv.url("/metrics"),
                                    timeout=30) as r:
            text = r.read().decode()
        assert '# {trace_id="' in text
    finally:
        router.close(replicas=True)
