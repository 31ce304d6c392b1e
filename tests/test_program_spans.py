"""Program spans on the profiler's clock.

``telemetry.trace.Span`` enters a ``jax.profiler.TraceAnnotation``
whether or not anything of ``paddle_tpu.telemetry`` is switched on, so
the serving tick's phases, the waits for the replica's lock and the
train step show in every profiler session, whoever started it; the
jitted programs and the Pallas kernels carry ``pt_*`` names of the
program's own. Pinned here: the helper's contract against a recording
fake, one REAL capture on the CPU backend (``jax.profiler`` traces the
host there too) of a tiny arena behind a ``LocalReplica`` that finds
every span with its arguments and its nesting, and the names in the
lowered programs.
"""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import telemetry
from paddle_tpu.models import gpt as G
from paddle_tpu.serving import BatchedDecoder
from paddle_tpu.serving_router import LocalReplica
from paddle_tpu.telemetry import trace as ttrace

TICK_CHILDREN = ("serve.admit", "serve.step.dispatch", "serve.step.fetch",
                 "serve.step.emit", "serve.step.cursor", "replica.harvest")
LOCK_WAITS = ("replica.lock_wait.submit", "replica.lock_wait.drain",
              "replica.lock_wait.other")


# ---------------------------------------------------------------------------
# the helper, against a recording fake
# ---------------------------------------------------------------------------

class _FakeProfiler:
    """Stands in for ``jax.profiler``: records every annotation."""

    def __init__(self):
        self.log = []
        fake = self

        class TraceAnnotation:
            def __init__(self, name, **kwargs):
                self.name, self.kwargs = name, kwargs

            def __enter__(self):
                fake.log.append(("enter", self.name, self.kwargs))
                return self

            def __exit__(self, *exc):
                fake.log.append(("exit", self.name))
                return False

        self.TraceAnnotation = TraceAnnotation


@pytest.fixture
def fake_profiler(monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    return fake


def test_span_annotates_with_collection_and_telemetry_off(fake_profiler):
    assert not ttrace.tracing() and not telemetry.enabled()
    ttrace.reset()
    with ttrace.Span("serve.tick"):
        with ttrace.Span("serve.admit"):
            pass
    assert fake_profiler.log == [
        ("enter", "serve.tick", {}), ("enter", "serve.admit", {}),
        ("exit", "serve.admit"), ("exit", "serve.tick")]
    # nothing host-side: the event list fills only while
    # start_profiler() collects
    assert ttrace.get_events() == []


def test_span_arguments_ride_the_annotation(fake_profiler):
    with ttrace.Span("serve.prefill", rid=7, plen=300, bucket=512):
        pass
    assert fake_profiler.log[0] == (
        "enter", "serve.prefill", {"rid": 7, "plen": 300, "bucket": 512})


def test_span_still_collects_host_events_while_profiling(fake_profiler):
    ttrace.start_profiler()
    try:
        with ttrace.Span("outer"):
            with ttrace.Span("inner", n=1):
                pass
    finally:
        events = ttrace.stop_profiler()
    by = {e["name"]: e for e in events}
    assert by["inner"]["args"]["parent"] == "outer"
    assert by["inner"]["args"]["depth"] == 1
    assert [x[:2] for x in fake_profiler.log] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("exit", "outer")]


def test_span_exits_its_annotation_when_the_body_raises(fake_profiler):
    with pytest.raises(ValueError):
        with ttrace.Span("serve.tick"):
            raise ValueError("boom")
    assert fake_profiler.log[-1] == ("exit", "serve.tick")


def test_named_gives_the_jitted_program_its_name():
    def step(x):
        return x + 1

    fn = ttrace.named(step, "pt_unit_step")
    assert fn.__name__ == "pt_unit_step" and fn(1) == 2
    text = jax.jit(fn).lower(jnp.zeros((2,))).as_text()
    assert "@jit_pt_unit_step" in text


# ---------------------------------------------------------------------------
# one real capture: a tiny arena behind a LocalReplica, CPU backend
# ---------------------------------------------------------------------------

def _decoder(**kw):
    pt.seed(0)
    model = G.GPTForCausalLM(G.GPTConfig.tiny()).eval()
    kw.setdefault("slots", 2)
    kw.setdefault("capacity", 128)
    return BatchedDecoder(model, **kw)


def _host_spans(trace_dir):
    """Every program span of the capture: (name, start, end, line
    number, stats), from every host line (lines are named after the
    process, so spans are selected by name)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane artifact under {trace_dir}"
    out, n_line = [], 0
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n_line += 1
            for e in line.events:
                if e.name.startswith(("serve.", "replica.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, n_line,
                                {k: v for k, v in e.stats}))
    return out


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """Telemetry is OFF throughout: a profiler session started by
    anyone (here the test, as a benchmark's tracer or POST /profilez
    would) sees the program's spans. Three replicas share the session:
    a monolithic and a chunked-prefill contiguous arena, whose step
    runs one ahead of the host, and a paged one, whose tick is
    synchronous."""
    telemetry.disable()
    out = str(tmp_path_factory.mktemp("xplane"))
    mono = LocalReplica(_decoder(), name="mono")
    chunked = LocalReplica(_decoder(prefill_chunk=16), name="chunked")
    paged = LocalReplica(_decoder(pages=8, page_size=64), name="paged")
    for rep in (mono, chunked, paged):
        rep.warmup()
        rep.start()
    rng = np.random.default_rng(0)
    jax.profiler.start_trace(out)
    try:
        with mono._locked("other"):     # a caller that is neither
            time.sleep(0.01)
        want = {}
        for rep in (mono, chunked, paged):
            want[rep] = {rep.submit(rng.integers(1, 500, (n,)).astype(
                np.int32), 6) for n in (5, 23, 40)}
        for rep, rids in want.items():
            got, deadline = {}, time.monotonic() + 120
            while not rids <= set(got) and time.monotonic() < deadline:
                got.update(rep.drain_results())
                time.sleep(0.002)
            assert rids <= set(got), (rep.name, rids, set(got))
    finally:
        jax.profiler.stop_trace()
        for rep in (mono, chunked, paged):
            rep.stop()
    return _host_spans(out)


@pytest.mark.parametrize("name", TICK_CHILDREN + LOCK_WAITS + (
    "serve.tick", "serve.prefill", "serve.prefill_tick"))
def test_capture_holds_every_program_span(capture, name):
    assert any(s[0] == name for s in capture), sorted({s[0] for s in capture})


@pytest.mark.parametrize("name", TICK_CHILDREN + (
    "serve.prefill", "serve.prefill_tick"))
def test_each_phase_lies_inside_a_tick_of_its_thread(capture, name):
    ticks = [s for s in capture if s[0] == "serve.tick"]
    spans = [s for s in capture if s[0] == name]
    assert spans
    for _, a, b, line, _ in spans:
        assert any(t[3] == line and t[1] <= a and b <= t[2]
                   for t in ticks), (name, a, b)


def test_prefill_lies_inside_admit(capture):
    admits = [s for s in capture if s[0] == "serve.admit"]
    for _, a, b, line, _ in (s for s in capture if s[0] == "serve.prefill"):
        assert any(t[3] == line and t[1] <= a and b <= t[2] for t in admits)


def test_the_phases_tile_a_stepping_tick(capture):
    """No child overlaps another. A synchronous tick (the paged arena)
    holds dispatch, fetch, emit and cursor in that order: the host sets
    the cursor from the tokens it fetched. A tick of a contiguous arena
    dispatches the NEXT step and the program that leaves its cursor on
    the device, and only then fetches the step in flight; the first
    tick after an empty arena only dispatches, and the tick with no row
    left to step only fetches and emits."""
    d, f, e, c = ("serve.step.dispatch", "serve.step.fetch",
                  "serve.step.emit", "serve.step.cursor")
    seen = {}
    for _, a, b, line, _ in (s for s in capture if s[0] == "serve.tick"):
        kids = sorted((s for s in capture
                       if s[3] == line and s[0] in TICK_CHILDREN
                       and a <= s[1] and s[2] <= b), key=lambda s: s[1])
        for x, y in zip(kids, kids[1:]):
            assert x[2] <= y[1], (x, y)
        steps = tuple(k[0] for k in kids if k[0] in (d, f, e, c))
        if steps:
            seen[steps] = seen.get(steps, 0) + 1
    assert set(seen) == {(d, f, e, c), (d, c, f, e), (d, c), (f, e)}, seen
    assert seen[d, f, e, c] >= 5                # the paged replica's
    assert seen[d, c, f, e] >= 5                # a step ahead, one read
    assert seen[d, c] >= 2 and seen[f, e] >= 2


def test_only_the_synchronous_tick_fetches_the_cursor(capture):
    """``serve.step.cursor`` on the two contiguous replicas is one
    dispatch; on the paged one it is two fetches and two uploads, after
    the emit. The first is never the last child of its tick."""
    last = {}
    for _, a, b, line, _ in (s for s in capture if s[0] == "serve.tick"):
        kids = [s for s in capture if s[3] == line and a <= s[1] <= b
                and s[0].startswith("serve.step.")]
        if kids:
            last.setdefault(line, set()).add(
                max(kids, key=lambda s: s[1])[0])
    assert len(last) == 3
    assert sorted(map(sorted, last.values())) == [
        ["serve.step.cursor"],
        ["serve.step.cursor", "serve.step.emit"],
        ["serve.step.cursor", "serve.step.emit"]]


def test_span_arguments_come_back_as_stats(capture):
    prefills = [s for s in capture if s[0] == "serve.prefill"]
    assert len(prefills) == 6          # the monolithic and the paged three
    rids, plens = set(), set()
    for *_, stats in prefills:
        assert {"rid", "plen", "bucket", "queued_us"} <= set(stats)
        assert int(stats["queued_us"]) >= 0
        assert int(stats["bucket"]) >= int(stats["plen"])
        rids.add(int(stats["rid"]))
        plens.add(int(stats["plen"]))
    assert plens == {5, 23, 40} and len(rids) == 3
    tick = next(s for s in capture if s[0] == "serve.tick")
    assert {"n_active", "queued"} <= set(tick[4])


@pytest.mark.parametrize("name", LOCK_WAITS)
def test_lock_waits_are_the_callers_never_the_replicas_thread(capture, name):
    """The serve loop takes ``_mu`` with a plain ``with``: a span there
    would sit in the window between its release and its next acquire,
    which decides how often a waiting caller gets in."""
    loop_lines = {s[3] for s in capture if s[0] == "serve.tick"}
    lines = {s[3] for s in capture if s[0] == name}
    assert lines and loop_lines and not (lines & loop_lines)


# ---------------------------------------------------------------------------
# names in the lowered programs
# ---------------------------------------------------------------------------

def _kernel_names(jitted, *args):
    """The Mosaic kernel names of a program lowered for the TPU on this
    CPU host (``jax.export`` runs the Pallas lowering, nothing runs)."""
    text = jax.export.export(jitted, platforms=["tpu"])(*args).mlir_module()
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


@pytest.mark.parametrize("fused", [True, False], ids=["one_kernel", "pair"])
def test_flash_kernels_carry_their_names(fused, monkeypatch):
    """The backward is one kernel, dq beside dk and dv under dk / dv's
    name; past the rule ``bwd_is_fused`` (here: a ceiling of nothing) it
    is the pair, each kernel under its own name."""
    import importlib

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    if not fused:
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"),
            "FUSED_BWD_VMEM_LIMIT", 0)
    q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    assert _kernel_names(fwd, q, q, q) == {"pt_flash_fwd"}
    bwd = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    assert _kernel_names(bwd, q, q, q) == {
        "pt_flash_fwd", "pt_flash_dkdv"} | (set() if fused
                                            else {"pt_flash_dq"})


def test_decode_kernels_carry_their_names():
    from paddle_tpu.ops.pallas.flash_decode import (flash_decode,
                                                    flash_decode_paged)

    slots, cap, page, h, h_kv, d = 4, 512, 64, 4, 2, 64
    q = jnp.zeros((slots, 1, h, d), jnp.float32)
    kv = jnp.zeros((slots, cap, h_kv, d), jnp.float32)
    t = jnp.zeros((slots,), jnp.int32)
    dec = jax.jit(lambda q, k, v, t: flash_decode(q, k, v, t,
                                                  interpret=False))
    assert _kernel_names(dec, q, kv, kv, t) == {"pt_flash_decode"}
    n_log = cap // page
    pool = jnp.zeros((slots * n_log, page, h_kv, d), jnp.float32)
    table = jnp.zeros((slots, n_log), jnp.int32)
    paged = jax.jit(lambda q, kp, vp, tb, t: flash_decode_paged(
        q, kp, vp, tb, t, interpret=False))
    assert _kernel_names(paged, q, pool, pool, table, t) == {
        "pt_flash_decode_paged"}


@pytest.mark.parametrize("kernel, fused", [
    ("pt_flash_fwd", True), ("pt_flash_dkdv", True),
    ("pt_flash_dq", False), ("pt_flash_dkdv", False)])
def test_a_scope_of_the_kernels_name_is_the_innermost_around_it(
        kernel, fused, monkeypatch):
    """The compiled ``custom-call`` instruction, and with it the
    profiler's ``XLA Ops`` event, is named after the innermost scope of
    its ``op_name``: that has to be the kernel's own name, not the JAX
    transform around it (``checkpoint``, ``jvp``). The one-kernel
    backward and the pair (forced by a ceiling of nothing) alike."""
    import importlib

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    if not fused:
        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"),
            "FUSED_BWD_VMEM_LIMIT", 0)
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        with jax.named_scope("outer"):
            return jax.checkpoint(lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=True))(q, k, v).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    assert re.search(rf'/{kernel}/pallas_call"', text), kernel
    assert ("/pt_flash_dq/" in text) == (not fused)


def test_step_programs_carry_their_names():
    dec = _decoder()
    assert "@jit_pt_decode_step" in dec.lower_step().as_text()
    assert dec._build_multi_step(4).lower(
        *dec._step_call()[1]).as_text().count("@jit_pt_decode_step_k4")
    padded = jnp.zeros((32,), jnp.int32)
    text = dec._prefill_fn(32).lower(dec._mstate, dec.caches, padded, 5,
                                     0).as_text()
    assert "@jit_pt_prefill_32" in text


def test_paged_and_chunked_builders_carry_their_names():
    dec = _decoder(pages=8, page_size=64)
    row = jnp.zeros((dec.n_log,), jnp.int32)
    padded = jnp.zeros((32,), jnp.int32)
    assert "@jit_pt_prefill_paged_32" in dec._prefill_fn_paged(32).lower(
        dec._mstate, dec.pools, row, padded, 5).as_text()
    chunk_fn, restep_fn = dec._suffix_fns(32)
    assert "@jit_pt_prefill_suffix_32" in chunk_fn.lower(
        dec._mstate, dec.pools, row, padded, 0).as_text()
    assert "@jit_pt_prefill_restep" in restep_fn.lower(
        dec._mstate, dec.pools, row, jnp.int32(1), 4).as_text()
    contig = _decoder(prefill_chunk=16)
    assert "@jit_pt_prefill_chunk_16" in contig._chunk_fn_contig(16).lower(
        contig._mstate, contig.caches, jnp.zeros((16,), jnp.int32),
        jnp.int32(0), jnp.int32(0)).as_text()


def test_train_step_carries_its_name_and_its_scopes():
    cfg = G.GPTConfig.tiny()
    pt.seed(0)
    model = G.GPTForCausalLM(cfg)

    def loss_builder(params, buffers, rng, batch):
        loss, new_buffers = model.functional_call(
            params, batch, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    trainer = pt.parallel.Trainer(
        model, pt.optimizer.Adam(learning_rate=1e-3), loss_builder)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = trainer.lower_step(ids).as_text(debug_info=True)
    assert "@jit_pt_train_step" in text
    # a scope under a transform reads jvp(linear_ce),
    # transpose(jvp(linear_ce))
    assert re.search(r'[/(]linear_ce\)*/', text)
