"""Continuous-batching LM serving loop (slot-based, static shapes).

A fixed arena of ``slots`` KV caches decodes in lockstep — every jitted
step advances ALL active slots one token, each at its OWN cursor (the
per-row machinery speculative decoding uses: vmapped single-row
attention with per-slot positions). Requests queue host-side; when a
slot finishes (eos or its max_len), the next prompt is prefilled into
that slot between steps and the batch keeps moving — no padding the
whole batch to the slowest request, no recompiles (prompt lengths pad
to fixed buckets; everything else is static). On the contiguous arena
the step runs one ahead of the host (``BatchedDecoder._step_multi``).

This is the serving-runtime capstone over the decode stack: generate()
semantics per request (greedy or temperature/top-k/top-p sampling, eos
freezing), the KV-cache mixin underneath, and it composes with
quant.apply_weight_only_int8 (buffers ride the same functional step).
Opt-in refinements: paged KV (pages=N, vLLM-style page pool + prefix
caching), CHUNKED PREFILL (prefill_chunk=C — C prompt tokens per
serving tick instead of whole-prompt admission stalls), and
SPECULATIVE DECODING over the arena (draft=model, gamma=g — per-row
draft steps + ONE per-row verify chunk per round; greedy mode matches
the plain arena up to near-tie argmax flips — the verify chunk and the
step loop reduce in different orders, so a near-tie can break either
way; ``TestSpeculativeArena`` pins exactly this).

Telemetry (``paddle_tpu.telemetry``, off by default): TTFT and
per-token decode latency histograms, queue depth / page-pool occupancy
gauges, admission rejections, speculative accept rate, and recompile
tracking of the step + per-bucket prefill signatures. All host-side
scalars recorded outside jit; every hook short-circuits on the enabled
flag.

Green-field vs the reference (its serving is the one-request-at-a-time
predictor, /root/reference/paddle/fluid/inference/api/api_impl.cc role;
continuous batching is the modern LM-serving analog of that
capability).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import telemetry
from .core.enforce import enforce

__all__ = ["ArenaCounters", "ArenaLostError", "BatchedDecoder",
           "PagedKVPool", "Request", "KVHandoff", "TokenStream",
           "reject_cause"]
from .nn.layer import inject_state
from .resilience import reliability as _reliability
from .ops import paged_kv as paged_ops
from .ops.sampling import sample_from_logits
from .telemetry import costs as _costs
from .telemetry import profiling as _profiling
from .telemetry import recompile as _recompile
from .telemetry import server as _dbg_server
from .telemetry import tracing as _tracing
from .telemetry.scopes import scope as _scope
from .telemetry.trace import Span, named as _named
from .utils.memory import owned_on_device

# reusable inert context manager: span call-sites gate on
# telemetry.enabled() (the zero-cost contract — a disabled run must
# execute NO tracing code, pinned by test) and fall back to this
_NULL_CM = contextlib.nullcontext()


def _arena_jit(fn, name: str, arena_argnums):
    """``fn`` jitted under the stable program name ``name`` with the
    arena arguments DONATED: every serving program takes the arena
    (contiguous caches, page pools, the draft's caches) and returns the
    new one, and only a donated argument may be written in place —
    undonated, XLA copies the whole arena into the output and updates
    the copy. The one way a serving program is compiled: the weights,
    cursors, tokens and page table are never in ``arena_argnums``, and
    a program that takes no arena (``pt_step_cursor``) donates
    nothing."""
    return jax.jit(_named(fn, name), donate_argnums=arena_argnums)


def _advance_cursor(tok, t, toks, live):
    """The cursor a decode step leaves, computed where the step's
    tokens are (program ``pt_step_cursor``): a ``live`` row goes on
    from the last of its k tokens at the position after them, any other
    row stays where it was. Its own small program and not two more
    outputs of ``pt_decode_step``: that program's arguments and outputs
    are what the benchmark's compile rehearsal, the AOT artifacts and
    their tests call it with."""
    return (jnp.where(live, toks[:, -1], tok),
            jnp.where(live, t + toks.shape[1], t))


_advance_cursor = _arena_jit(_advance_cursor, "pt_step_cursor", ())


class _StepInFlight(NamedTuple):
    """A decode step dispatched and not yet read by the host."""
    toks: Any          # (slots, k) tokens, on the device
    counted: Any       # () or (the step's counters,), on the device
    live: np.ndarray   # the rows it stepped for a request
    owners: list       # the request of every slot at dispatch
    kd: int            # tokens a row
    t_start: float     # its dispatch, or the step before it read, if later


class ArenaLostError(RuntimeError):
    """A serving program failed after it had consumed the arena: the
    decoder holds no keys, values or state any more and refuses every
    further tick. Not an ``EnforceError`` (a request's fault): the
    router reads it as the replica's death."""


@telemetry.cached_instruments
def _serving_metrics(reg):
    """Serving instrument set, memoized against the registry generation
    (run() touches this every tick — rebuilding 12 get-or-create
    lookups per tick is pure waste). Only reached when telemetry is
    enabled."""
    return {
        "requests": reg.counter(
            "pt_serving_requests_total", "requests submitted"),
        "completed": reg.counter(
            "pt_serving_completed_total", "requests completed"),
        "tokens": reg.counter(
            "pt_serving_tokens_total", "tokens emitted"),
        "ttft": reg.histogram(
            "pt_serving_ttft_seconds",
            "submit-to-first-token latency (includes queue wait)",
            unit="s"),
        "decode_latency": reg.histogram(
            "pt_serving_decode_latency_seconds",
            "per-token decode latency (dispatch wall time / tokens "
            "emitted that dispatch)", unit="s"),
        "queue_depth": reg.gauge(
            "pt_serving_queue_depth", "requests waiting for a slot"),
        "rejections": reg.counter(
            "pt_serving_admission_rejections_total",
            "admissions rejected or deferred (all causes; see the "
            "cause-labeled series for the split)"),
        # cause-labeled split of the same total (unlabeled series kept
        # for dashboard compat): pool_exhausted = paged admission
        # deferred on page exhaustion, capacity = hard queue-depth cap,
        # shed = SLO load-shed (router-side policy), deadline =
        # end-to-end deadline expired before/while serving (the
        # reliability plane's typed drop — never silently computed)
        "rejections_by_cause": {
            cause: reg.counter(
                "pt_serving_admission_rejections_total",
                "admissions rejected or deferred, by cause",
                labels={"cause": cause})
            for cause in ("pool_exhausted", "capacity", "shed",
                          "deadline")},
        "page_occupancy": reg.gauge(
            "pt_serving_page_occupancy_ratio",
            "allocated fraction of the KV page pool"),
        "kv_pool_bytes": reg.gauge(
            "pt_serving_kv_pool_bytes",
            "device bytes held by the paged KV pools (all blocks, "
            "K+V, scales included for kv_dtype=int8) — the "
            "concurrent-session HBM denominator"),
        "kv_pool_live_bytes": reg.gauge(
            "pt_serving_kv_pool_live_bytes",
            "KV pool bytes backing ALLOCATED pages (occupancy x pool "
            "bytes)"),
        "spec_rounds": reg.counter(
            "pt_serving_spec_row_rounds_total",
            "speculative verify rounds (per active row)"),
        "spec_accepted": reg.counter(
            "pt_serving_spec_accepted_total",
            "draft tokens accepted by target verify"),
        "spec_accept_rate": reg.gauge(
            "pt_serving_spec_accept_rate",
            "mean accepted draft tokens per verify round (0..gamma)"),
        "streams": reg.counter(
            "pt_serving_streams_total",
            "requests served with a per-token stream attached"),
        "stream_stalled": reg.counter(
            "pt_stream_stalled_seconds",
            "cumulative seconds streams spent stalled on a full "
            "client buffer (the backpressure that pauses a stream, "
            "never the arena tick)", unit="s"),
    }


class PagedKVPool:
    """Shared page pool for paged-KV attention (vLLM-style): K and V
    live in (pages, page_size, kv_heads, head_dim) pools shared by all
    requests; each request owns a PAGE TABLE (its logical cache = the
    page sequence), so memory scales with live tokens, not
    slots x max-capacity. The attention side is
    ops.pallas.flash_decode.flash_decode_paged (the scalar-prefetched
    table drives the page DMA) with an XLA gather fallback.

    Host-side alloc/free here; the pools are functional arrays — step
    functions thread them like any cache (write_rows/write_chunk return
    updated pools). Serving integration (BatchedDecoder paged mode) is
    the round-6 hook; the building blocks are tested now
    (tests/test_paged_kv.py)."""

    def __init__(self, pages: int, page_size: int, kv_heads: int,
                 head_dim: int, dtype=None, arrays: bool = True,
                 kv_dtype=None):
        enforce(page_size in (64, 128, 256),
                "page_size must be one of (64, 128, 256), got %s",
                page_size)
        enforce(pages >= 1, "pages must be >= 1, got %s", pages)
        from .core.dtypes import default_dtype

        # kv_dtype="int8": QUANTIZED pools (ops.paged_kv.QuantizedPool
        # — int8 values + per-vector f32 scales, quantize-on-append /
        # dequantize-in-attention). ~(1 + 4/head_dim)/itemsize the
        # bytes per cached token of the float pool, which is what sets
        # max concurrent sessions at a fixed page-pool HBM budget.
        enforce(kv_dtype in (None, "int8", jnp.int8),
                'kv_dtype must be None or "int8", got %r', kv_dtype)
        self.quantized = kv_dtype is not None
        self.kv_dtype = "int8" if self.quantized else None
        self.dtype = dtype or default_dtype()
        self.shape = (pages, page_size, kv_heads, head_dim)
        # arrays=False: allocator-only (callers that thread their own
        # functional pools — BatchedDecoder — must not pin two extra
        # pool-sized device buffers here for the decoder's lifetime)
        self.kpool = self.empty_pool() if arrays else None
        self.vpool = self.empty_pool() if arrays else None
        self.page_size = page_size
        self.pages = pages
        self._free = list(range(pages - 1, -1, -1))
        self._free_set = set(self._free)
        # reference counts (prefix caching: a page shared by N live
        # requests + the registry has ref N+1 and only returns to the
        # free list at 0)
        self._ref = np.zeros(pages, np.int64)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def empty_pool(self):
        """Mint one zeroed functional pool array in this pool's storage
        form (float array, or QuantizedPool when ``kv_dtype="int8"``) —
        what BatchedDecoder threads per block."""
        if self.quantized:
            return paged_ops.QuantizedPool(
                jnp.zeros(self.shape, jnp.int8),
                jnp.zeros(self.shape[:3], jnp.float32))
        return jnp.zeros(self.shape, self.dtype)

    @property
    def pool_nbytes(self) -> int:
        """Device bytes ONE pool array costs (K or V side) — the
        serving-density denominator: sessions/HBM scales with
        1/pool_nbytes at fixed pages."""
        if self.quantized:
            return paged_ops.quantized_pool_nbytes(self.shape)
        return int(np.prod(self.shape)) * jnp.dtype(self.dtype).itemsize

    def alloc(self, n: int) -> np.ndarray:
        """Claim n pages (typed error when exhausted — the admission
        backpressure signal)."""
        enforce(n <= len(self._free),
                "page pool exhausted: want %s, free %s", n,
                len(self._free))
        got = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(got)
        for i in got:
            self._ref[i] = 1
        return np.asarray(got, np.int32)

    def share(self, ids) -> None:
        """Take an extra reference on live pages (prefix caching)."""
        for i in np.asarray(ids).reshape(-1):
            i = int(i)
            enforce(0 <= i < self.pages,
                    "page id %s outside pool (%s pages)", i, self.pages)
            enforce(self._ref[i] > 0,
                    "share of unallocated page %s", i)
            self._ref[i] += 1

    def free(self, ids) -> None:
        """Drop one reference per page; a page returns to the free list
        at refcount 0. Over-freeing would hand the same physical page
        to two requests (silent KV cross-contamination), so it is a
        typed error instead."""
        for i in np.asarray(ids).reshape(-1):
            i = int(i)
            enforce(0 <= i < self.pages,
                    "page id %s outside pool (%s pages)", i, self.pages)
            enforce(i not in self._free_set and self._ref[i] > 0,
                    "double free of page %s", i)
            self._ref[i] -= 1
            if self._ref[i] == 0:
                self._free.append(i)
                self._free_set.add(i)

    # --- functional array ops (jit-safe; thread the returned pools;
    # ONE definition in ops/paged_kv.py, re-exported here) ------------

    write_rows = staticmethod(paged_ops.write_rows)
    write_chunk = staticmethod(paged_ops.write_chunk)
    attend = staticmethod(paged_ops.attend)


def _row_apply(caches, s, fn):
    """Slice slot ``s`` of every leaf of the arena (one pytree a block,
    each leaf (slots, ...): an attention block's K/V pair, a state-space
    block's convolution tail and state) as a batch-1 row, run
    ``fn(row) -> (result, new_row)``, write the row back (dtype-cast) —
    the ONE definition of the per-slot slice/run/write-back boilerplate
    every contiguous prefill piece (full, chunk, restep, draft) shares.
    jit-safe: callers close over it inside their traced functions."""
    row = jax.tree_util.tree_map(
        lambda c: lax.dynamic_slice_in_dim(c, s, 1, axis=0), caches)
    out, row = fn(row)
    return out, jax.tree_util.tree_map(
        lambda c, r: lax.dynamic_update_slice_in_dim(
            c, r.astype(c.dtype), s, axis=0), caches, row)


class ArenaCounters:
    """Plain counters of one arena, kept apart from it so that they
    outlive it (``last_counters`` is the newest arena's: a benchmark
    frees the decoder before it reads). ``state_bytes``: device bytes of
    the arena by kind of state, ``kv`` (addressed by position) and
    ``recurrent`` (a fixed size a slot), and, for a model with window
    layers, ``ring`` apart from ``kv``: the rings of a window's
    positions, whatever the capacity. ``sums``: the running sum of
    whatever the model's decode step counts (``step_counters``), over
    ``steps`` steps; ``expert_tokens`` is the one a model with routed
    experts gives, the (held,) (token, pick) pairs each held expert
    got, every row of the step counted (an idle slot's junk row too),
    and None for any other model (the windows of held pairs a grouped
    expert layer ran follow from it on the host, for a step's mean
    layer: ``nn.DroplessMoE.windows_run(expert_tokens.sum() / (steps x
    expert layers), rows)``; no step counts them). ``prefills``: the
    prompts this arena
    prefilled itself (a handoff's import is none); ``prefill_resteps``:
    those whose first token came from a step of the last prompt token
    through the whole model, a second read of every weight, and not
    from the prefill's own pass (0 on the contiguous arena with whole
    prompts; the paged, prefix-hit and chunked paths still re-step).
    ``prefill_expert_layers``: the routed-expert layers of the one-pass
    prefills among them, and ``prefill_dense_layers`` those that took
    the dense body of ``nn.moe.dropless_moe`` (static a bucket: the
    model's ``expert_layers`` says them from the shape; both stay 0 for
    a model without routed experts).
    ``steps`` counts every decode step the host has read, whether or
    not the model counts anything in it. ``steps_ahead``: those of them
    that were dispatched while the step before was still unread (the
    contiguous arena's look-ahead; 0 on a paged or speculative arena,
    whose tick is synchronous); ``rows_dropped``: the rows of such
    steps whose tokens were thrown away, because the row had ended on
    ``eos`` or was torn down while the step was in flight."""

    def __init__(self, state_bytes: Dict[str, int]):
        self.state_bytes = state_bytes
        self.sums: Dict[str, np.ndarray] = {}
        self.steps = 0
        self.prefills = 0
        self.prefill_resteps = 0
        self.prefill_expert_layers = 0
        self.prefill_dense_layers = 0
        self.steps_ahead = 0
        self.rows_dropped = 0

    def add(self, counted: Dict[str, Any]) -> None:
        """One decode step read, with what it counted ({}: nothing)."""
        for name, value in counted.items():
            value = np.asarray(value, np.int64)
            self.sums[name] = self.sums.get(name, 0) + value
        self.steps += 1

    @property
    def expert_tokens(self) -> Optional[np.ndarray]:
        return self.sums.get("expert_tokens")


last_counters: Optional[ArenaCounters] = None


def reject_cause(cause: str) -> None:
    """Bump the admission-rejection counters (unlabeled total + the
    cause-labeled series) — the ONE place the split is recorded, shared
    by the arena's pool backpressure and the router's shed policy.
    No-op while telemetry is disabled."""
    if not telemetry.enabled():
        return
    m = _serving_metrics()
    m["rejections"].inc()
    by = m["rejections_by_cause"].get(cause)
    if by is not None:
        by.inc()


class TokenStream:
    """Bounded per-client token buffer — the per-token streaming sink.

    Tokens leave the arena the TICK they are sampled (not at request
    completion): the arena's host loop calls :meth:`offer` with the
    request's emitted-token list each tick, and records append from the
    stream's own high-water index while the buffer has room. ``offer``
    NEVER blocks — a stalled client (full buffer) pauses ITS OWN stream
    (stall seconds accumulate on ``pt_stream_stalled_seconds``) and the
    stream catches back up from the same list on a later tick once the
    client drains; the arena tick cadence is never throttled by any one
    consumer (pinned by test).

    The router's fan-in pump feeds a CLIENT-side instance through
    :meth:`put`, which MAY wait (bounded) for room — the pump is a
    per-request thread, so client backpressure propagates upstream to
    the replica-side buffer, never to the arena.

    Records are dicts. Tokens: ``{"i": index, "tok": id, "t":
    perf_counter-or-None}``. Control records ride the same queue and
    bypass the cap (they are O(retries), not O(tokens)):
    ``{"event": "resume", "retries": n, ...}`` (replica died mid-stream,
    the request re-dispatched on a survivor — same trace id, already-
    delivered tokens stay valid), ``{"event": "end", "n": total}``,
    ``{"event": "error", "error": repr}`` (typed terminal failure —
    a client NEVER sees a silent stall). Consume via :meth:`get` or
    iteration; ``None`` from ``get`` means timeout (stream still live)
    — iteration ends only at end/error."""

    def __init__(self, maxlen: int = 256):
        enforce(maxlen >= 1, "stream maxlen must be >= 1, got %s",
                maxlen)
        self.maxlen = int(maxlen)
        self._buf: List[Dict[str, Any]] = []
        self._cond = threading.Condition()
        self._src = 0                 # next emitted index to buffer
        self._final = None            # completion record's token array
        self._end_sent = False
        self.closed = False
        self.error: Optional[BaseException] = None
        self.stalled_s = 0.0
        self._stall_t0: Optional[float] = None

    # -- producer side ------------------------------------------------------

    def _note_stall_end(self, now: float) -> None:
        if self._stall_t0 is not None:
            d = max(0.0, now - self._stall_t0)
            self.stalled_s += d
            self._stall_t0 = None
            if d and telemetry.enabled():
                _serving_metrics()["stream_stalled"].inc(d)

    def offer(self, toks, now: Optional[float] = None) -> None:
        """Arena side: buffer token records for ``toks[src:]`` while
        the client buffer has room. Never blocks (see class doc)."""
        if now is None:
            now = time.perf_counter()
        with self._cond:
            if self.closed:
                return
            progressed = False
            while self._src < len(toks) and len(self._buf) < self.maxlen:
                self._buf.append({"i": self._src,
                                  "tok": int(toks[self._src]),
                                  "t": now})
                self._src += 1
                progressed = True
            if self._src < len(toks):
                if self._stall_t0 is None:
                    self._stall_t0 = now   # stall starts
            else:
                self._note_stall_end(now)
            if progressed:
                self._cond.notify_all()

    def put(self, rec: Dict[str, Any],
            timeout: Optional[float] = None) -> bool:
        """Pump side: append ONE record, waiting (bounded) for room.
        Returns False when the stream closed or the wait expired —
        the caller's signal that the client went away."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while len(self._buf) >= self.maxlen and not self.closed:
                w = 0.05
                if deadline is not None:
                    w = min(w, deadline - time.monotonic())
                    if w <= 0:
                        return False
                t0 = time.monotonic()
                self._cond.wait(w)
                d = time.monotonic() - t0
                self.stalled_s += d
                if d and telemetry.enabled():
                    _serving_metrics()["stream_stalled"].inc(d)
            if self.closed:
                return False
            if "i" in rec and int(rec["i"]) < self._src:
                # already delivered — a finish()-driven tail (or an
                # earlier pump) outran this record; a lagging pump
                # near completion must not hand the client the same
                # index twice. Dropped-as-delivered, not a failure.
                return True
            self._buf.append(dict(rec))
            if "i" in rec:
                # keep the high-water index in sync so a later
                # finish() serves only the not-yet-forwarded tail
                self._src = max(self._src, int(rec["i"]) + 1)
            self._cond.notify_all()
            return True

    def control(self, event: str, **kv: Any) -> None:
        """Append a control record (resume markers and the like) —
        bypasses the cap so backpressure can't delay the very record
        that explains the stream's state."""
        with self._cond:
            if self.closed:
                return
            self._buf.append({"event": event, **kv})
            self._cond.notify_all()

    def finish(self, result, now: Optional[float] = None) -> None:
        """Producer epilogue: the request completed with ``result``
        tokens. Any tokens a stalled client has not buffered yet are
        served CONSUMER-driven from this record (no producer thread
        lingers for a slow reader), then the typed end record."""
        if now is None:
            now = time.perf_counter()
        with self._cond:
            self._note_stall_end(now)
            self._final = np.asarray(result, np.int32)
            self._cond.notify_all()

    def fail(self, err: BaseException) -> None:
        """Terminal failure: the typed error record, then closed —
        a consumer blocked in ``get`` wakes to it immediately."""
        with self._cond:
            self._note_stall_end(time.perf_counter())
            self.error = err
            self._buf.append({"event": "error", "error": repr(err)})
            self.closed = True
            self._cond.notify_all()

    # -- consumer side ------------------------------------------------------

    @property
    def done(self) -> bool:
        with self._cond:
            return (not self._buf
                    and (self.closed
                         or (self._final is not None and self._end_sent
                             and self._src >= len(self._final))))

    def get(self, timeout: Optional[float] = None):
        """Next record, or None on timeout (stream still live) or when
        the stream is fully drained after end/error."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while True:
                if self._buf:
                    rec = self._buf.pop(0)
                    self._cond.notify_all()   # room freed: wake put()
                    return rec
                if self._final is not None:
                    if self._src < len(self._final):
                        i = self._src
                        self._src += 1
                        return {"i": i, "tok": int(self._final[i]),
                                "t": None}
                    if not self._end_sent:
                        self._end_sent = True
                        self.closed = True
                        return {"event": "end",
                                "n": int(len(self._final))}
                if self.closed:
                    return None
                w = 0.1
                if deadline is not None:
                    w = min(w, deadline - time.monotonic())
                    if w <= 0:
                        return None
                self._cond.wait(w)

    def __iter__(self):
        """Yield records until the end/error record has been consumed
        (the end/error record itself IS yielded)."""
        while True:
            rec = self.get(timeout=1.0)
            if rec is None:
                if self.done:
                    return
                continue
            yield rec
            if rec.get("event") in ("end", "error"):
                return


class KVHandoff:
    """Prefilled KV pages + next-token logits for ONE prompt — the
    prefill→decode disaggregation wire unit. A dedicated prefill worker
    produces it (:meth:`BatchedDecoder.prefill_export`), a decode
    replica consumes it (:meth:`BatchedDecoder.inject_prefilled`), so a
    long prompt's whole-prompt prefill never runs inside a decode
    replica's serving loop.

    ``blocks`` holds one ``(k_payload, v_payload)`` per transformer
    block: ``(m, page_size, kv_heads, head_dim)`` float arrays, or
    ``(q, scale)`` tuples for int8 pools (the storage form crosses the
    wire intact — no dequant/requant round trip). ``to_bytes`` /
    ``from_bytes`` are the npz wire format the HTTP handoff uses."""

    def __init__(self, prompt, plen: int, logits, blocks,
                 page_size: int, kv_dtype=None, trace=None,
                 deadline=None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.plen = int(plen)
        self.logits = np.asarray(logits, np.float32)
        self.blocks = blocks
        self.page_size = int(page_size)
        self.kv_dtype = kv_dtype
        # trace context (telemetry.tracing.TraceContext) riding the
        # wire form: in-process disaggregation hands the producer's
        # context straight to the decode replica — no HTTP header hop
        self.trace = trace
        # end-to-end deadline (resilience.reliability.Deadline) riding
        # the same wire: the decode replica inherits the REQUEST's
        # remaining budget, not a fresh per-hop one
        self.deadline = deadline

    @property
    def pages(self) -> int:
        """Pages per block the payload covers."""
        first = self.blocks[0][0]
        return (first[0] if isinstance(first, tuple)
                else first).shape[0]

    @property
    def nbytes(self) -> int:
        n = 0
        for kp, vp in self.blocks:
            for p in (kp, vp):
                arrs = p if isinstance(p, tuple) else (p,)
                n += sum(int(a.nbytes) for a in arrs)
        return n

    def to_bytes(self) -> bytes:
        import io

        quant = self.kv_dtype is not None

        def stack(side):
            if quant:
                return (np.stack([np.asarray(b[side][0])
                                  for b in self.blocks]),
                        np.stack([np.asarray(b[side][1])
                                  for b in self.blocks]))
            return (np.stack([np.asarray(b[side])
                              for b in self.blocks]),)

        arrays = {"prompt": self.prompt,
                  "logits": self.logits,
                  "meta": np.asarray([self.plen, self.page_size,
                                      int(quant)], np.int64)}
        if self.trace is not None:
            # the trace context crosses the wire in header form
            arrays["trace"] = np.asarray(self.trace.to_header())
        if self.deadline is not None:
            # absolute wall-clock epoch — meaningful across processes
            arrays["deadline"] = np.asarray(self.deadline.to_header())
        for side, name in ((0, "k"), (1, "v")):
            payload = stack(side)
            if quant:
                arrays[name + "q"], arrays[name + "s"] = payload
            else:
                arrays[name] = payload[0]
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()

    @staticmethod
    def from_bytes(data: bytes) -> "KVHandoff":
        import io

        z = np.load(io.BytesIO(data))
        plen, page_size, quant = (int(x) for x in z["meta"])
        blocks = []
        if quant:
            n = z["kq"].shape[0]
            blocks = [((z["kq"][i], z["ks"][i]),
                       (z["vq"][i], z["vs"][i])) for i in range(n)]
        else:
            blocks = [(z["k"][i], z["v"][i])
                      for i in range(z["k"].shape[0])]
        trace = (_tracing.from_header(str(z["trace"]))
                 if "trace" in z.files else None)
        deadline = (_reliability.Deadline.from_header(str(z["deadline"]))
                    if "deadline" in z.files else None)
        return KVHandoff(z["prompt"], plen, z["logits"], blocks,
                         page_size, "int8" if quant else None,
                         trace=trace, deadline=deadline)


class Request:
    """One generation request; ``result`` is filled on completion."""

    def __init__(self, rid: int, prompt_ids, max_new: int):
        self.rid = rid
        self.prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.result: Optional[np.ndarray] = None
        self.t_submit = 0.0   # stamped at submit (always — the router
        self.t_first = 0.0    # latency accounting reads these even
        self.t_done = 0.0     # with telemetry off; three float stores)
        self.t_tokens: List[float] = []  # per-token emission stamps
        self.handoff: Optional[KVHandoff] = None  # pre-filled KV pages
        self.trace = None  # TraceContext (telemetry on + traced hop)
        self.stream: Optional[TokenStream] = None  # per-token sink
        self.deadline = None  # reliability.Deadline (router-minted)
        self.deadline_exceeded = False  # dropped typed, never computed


class BatchedDecoder:
    """Slot-based continuous batching over a causal LM: anything
    exposing ``_step_logits``/``_chunk_logits`` (with ``valid_len`` and
    ``head_at``: a prefill is one chunk that ends in the head at its
    own last prompt row)/``_step_logits_rows``
    and ``init_cache(slots, capacity)``, the arena as a list with one
    pytree a block whose leaves all lead with the slot axis. A model
    may declare ``cache_kinds`` (a block: ``"kv"``, addressed by
    position, or ``"recurrent"``, a state of fixed size). A ``"kv"``
    entry need not be keys and values by head: the contiguous arena
    takes any pytree whose leaves lead with (slots, capacity), and a
    model whose ``cache_records`` names a ``"latent"`` entry (one
    compressed record a position, ``nn.LatentAttention``) is served
    from it, while the modes that assume keys and values by head
    (pages, prefix reuse, a quantised pool, handoff, speculative
    verify, chunked prefill) are refused for it by name. So is a model
    with a ``"ring"`` entry (a window layer's keys and values for the
    last ``window`` positions alone, written round and round,
    ``nn.GatedAttention``): its leaf leads with (slots, window) beside
    the full layers' (slots, capacity) in the one arena, and the same
    modes, which assume ``capacity`` positions a block, are refused for
    it by name. With
    recurrent state a prefill starts the slot's state from zeros and
    advances it over exactly the prompt, and every mode that addresses
    the cache by position (pages, prefix reuse, handoff, speculative
    verify, chunked prefill) is refused.

    **The arena is consumed by every program that takes it.** The
    decode step, every prefill piece, the speculative round and the
    handoff import are compiled with the arena donated (``_arena_jit``)
    and write keys, values and recurrent state in place; the decoder
    rebinds ``caches`` / ``pools`` / ``caches_d`` to what the program
    returns, and the arrays it passed in are deleted. A caller who
    wants to keep an arena (to compare against, to snapshot) copies it
    first (``jax.tree_util.tree_map(jnp.copy, dec.caches)``); one who
    drives a program by hand passes ``dec.caches`` and assigns the
    result back, as the decoder does. The leaves must be buffers the
    runtime owns: ``analysis/donation`` checks that once at
    construction (``FLAGS_static_verify``). A program that fails after
    it consumed the arena marks the decoder ``arena_lost``
    (``_arena_guard``).

    **On a contiguous arena the decode step runs one ahead of the
    host** (``_step_multi``): the step's cursor (``tok``, ``t``) stays
    on the device, step N+1 is dispatched before step N's tokens are
    fetched, and the host's part of a tick (emit, harvest, admit, the
    replica's lock going round) runs under the device's next step. The
    tokens are the synchronous loop's. A request's tokens reach it one
    step later in absolute time; a slot freed when step N is read is
    prefilled after step N+1 and joins step N+2; a budget's end costs
    no surplus step (the host knows it before the dispatch), an ``eos``
    costs one row of one step, dropped and counted
    (``counters.rows_dropped``); a device fault shows when the step is
    read, one tick after its dispatch. A paged pool and a speculative
    draft need the cursor set by the host before the next dispatch
    (freed pages are handed on; a round's accepted count moves the
    cursor) and keep the synchronous tick (``_step_sync``).

    ``submit()`` enqueues; ``run()`` drives to completion and returns
    {request_id: np.ndarray of generated ids (prompt excluded)}.
    Sampling params apply to every request (temperature=0 = greedy);
    eos_id ends a request early. Per-(slot-generation, position) keys
    derive by fold_in, so a request's draw stream is independent of
    which slot served it only via the admission counter — deterministic
    for a fixed submission order.

    **The weights are a snapshot.** The parameters and buffers are read
    from the model at construction and passed to every compiled program
    as arguments; ``run()`` (and ``prefill_export``) read them again.
    A change made to the model afterwards
    (``quant.apply_weight_only_int8``, a LoRA merge, a loaded
    checkpoint) therefore takes effect at the next ``run()``, or with a
    new decoder, and not in a program driven by hand in between.
    """

    def __init__(self, model, slots: int, capacity: int, *,
                 eos_id: Optional[int] = None, key=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, prompt_bucket: int = 16,
                 pages: Optional[int] = None, page_size: int = 128,
                 prefix_cache: bool = False, kv_dtype=None,
                 prefill_chunk: Optional[int] = None,
                 draft=None, gamma: int = 4, decode_steps: int = 1):
        enforce(slots >= 1, "slots must be >= 1, got %s", slots)
        enforce(capacity >= prompt_bucket,
                "capacity %s < prompt bucket %s", capacity,
                prompt_bucket)
        self.model = model
        kinds = getattr(model, "cache_kinds", None)
        self._recurrent = bool(kinds) and "recurrent" in kinds
        records = getattr(model, "cache_records", None)
        self._latent = bool(records) and "latent" in records
        self._ring = bool(records) and "ring" in records
        # a recurrent state is one value a slot, not a value a position:
        # nothing below can page it, share a prefix of it, hand it over
        # as pages, roll it back after a rejected draft, or resume it
        # mid-prompt without a snapshot. A latent record is addressed by
        # position, but it is not keys and values by head: everything
        # below that pages, quantises, shares, hands over or verifies
        # against a cache goes through ops/paged_kv.attend, which is; a
        # chunk that continues a cache is not written for a record
        # (LatentAttention.forward_chunk takes the offset 0 alone). A
        # ring holds a window's positions and not the capacity's: a page
        # table, a shared prefix, a handoff's pages and a verify chunk's
        # roll-back all address positions the ring has overwritten, and
        # a chunk that continues a ring is not written either
        # (GatedAttention.forward_chunk)
        refused = (
            "recurrent state: the state is not addressable by position, "
            "and snapshots of it are not kept" if self._recurrent else
            "a latent record: the cache holds one compressed record a "
            "position, not keys and values by head, and the paged pool, "
            "its quantised form, the handoff and the verify chunk assume "
            "those" if self._latent else
            "a ring: a window layer's cache holds its last window of "
            "positions and not the capacity's, and the paged pool, its "
            "quantised form, the prefix registry, the handoff, the verify "
            "chunk and a chunk that continues a cache address positions "
            "a ring has overwritten" if self._ring else None)
        for what, on in (("pages=", pages is not None),
                         ("prefix_cache", prefix_cache),
                         ("kv_dtype", kv_dtype is not None),
                         ("draft= (speculative verify)", draft is not None),
                         ("prefill_chunk", prefill_chunk is not None)):
            enforce(not (on and refused), "%s is refused for a model "
                    "with %s", what, refused)
        # CHUNKED PREFILL (opt-in): admission only ALLOCATES; the
        # prompt then prefills prefill_chunk tokens per serving-loop
        # tick (one chunk per tick across all admitting slots), so
        # active slots keep emitting at decode cadence instead of
        # stalling for a whole long-prompt prefill (Sarathi-style
        # throughput smoothing). Token-identical to monolithic
        # prefill: chunk boundaries don't change the attention math.
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            enforce(prefill_chunk >= 1, "prefill_chunk must be >= 1")
            enforce(prefill_chunk <= capacity,
                    "prefill_chunk %s > capacity %s", prefill_chunk,
                    capacity)
            if pages is not None:
                # the chunk grid must never overrun the allocated
                # pages into an unallocated table entry (= physical
                # page 0): with C | page_size, the padded chunk
                # frontier (smallest multiple of C >= plen) is <= the
                # page demand ceil((plen+max_new)/ps)*ps
                enforce(page_size % prefill_chunk == 0,
                        "prefill_chunk %s must divide page_size %s",
                        prefill_chunk, page_size)
        # MULTI-TOKEN DECODE STEPS (opt-in, decode_steps=k): the jitted
        # step scans k single-token steps with the token picks moved
        # IN-DEVICE, so every dispatch advances all slots k tokens:
        # one dispatch and one host fetch per k tokens (what that buys
        # is not measured on the chip; every benchmark cell runs k=1).
        # Semantics: token-identical to k=1
        # (same fold_in key chain); admission/eos granularity coarsens
        # to k (a row hitting eos mid-window discards the tail
        # host-side and never emits past eos or its budget).
        self.decode_steps = int(decode_steps)
        enforce(self.decode_steps >= 1,
                "decode_steps must be >= 1, got %s", decode_steps)
        # SPECULATIVE DECODING over the arena (opt-in): a small draft
        # model proposes ``gamma`` tokens per round at every slot's own
        # cursor; the target verifies all gamma+1 in ONE per-row chunk
        # (_chunk_logits_rows / _chunk_logits_paged_rows) and a
        # modified rejection test accepts a prefix — output tokens are
        # distributed EXACTLY as the target's own sampling chain
        # (greedy mode matches the plain arena up to near-tie argmax
        # flips; see the module docstring). The
        # draft keeps a contiguous (slots, capacity) cache arena of
        # its own; in paged mode only the TARGET is paged.
        self.draft = draft
        self.gamma = int(gamma)
        if draft is not None:
            enforce(gamma >= 1, "gamma must be >= 1, got %s", gamma)
            enforce(model.cfg.vocab_size == draft.cfg.vocab_size,
                    "vocab mismatch: target %s vs draft %s",
                    model.cfg.vocab_size, draft.cfg.vocab_size)
            enforce(self.decode_steps == 1,
                    "decode_steps composes with the plain arena only; "
                    "speculative rounds already emit multiple tokens "
                    "per dispatch")
        # overrun margin budgeted at admission: spec verify-chunks
        # write up to cursor+gamma; a decode_steps window can write up
        # to k-1 positions past a mid-window finish. Without the
        # margin those writes would scatter into UNALLOCATED table
        # entries (= physical page 0) in paged mode, or clamp-corrupt
        # the contiguous row tail
        self._extra = (self.gamma if draft is not None
                       else self.decode_steps - 1)
        self.slots, self.capacity = slots, capacity
        self.eos_id = eos_id
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        self.sampled = float(temperature) != 0.0
        if self.sampled:
            enforce(key is not None,
                    "temperature > 0 samples and needs a PRNG key")
        self.key = key if key is not None else jax.random.key(0)
        self.bucket = prompt_bucket
        # what one tick may prefill whole, in padded prompt tokens: two
        # buckets, or one prompt however long (``_admit``)
        self.prefill_budget = 2 * prompt_bucket
        # PAGED mode (pages=N): K/V live in per-block SHARED page pools
        # + one page table — memory scales with live tokens (pages
        # actually allocated), not slots x capacity; admission
        # backpressures on pool exhaustion. Contiguous mode (default):
        # per-block (slots, cap, h_kv, hd) arenas.
        self.paged = pages is not None
        if self.paged:
            enforce(capacity % page_size == 0,
                    "capacity %s not divisible by page_size %s",
                    capacity, page_size)
            enforce(page_size % prompt_bucket == 0,
                    "page_size %s must be a multiple of prompt_bucket "
                    "%s (bucket round-up must never overrun the "
                    "allocated pages into another request's page 0)",
                    page_size, prompt_bucket)
            attn0 = model.blocks[0].self_attn
            # kv_dtype="int8": quantized page pools (quantize-on-append
            # K/V, dequantize-in-attention) — ~(4*hd)/(hd+4) more pages
            # per HBM byte than fp32, which is the max-sessions lever
            self._allocator = PagedKVPool(
                pages, page_size, attn0.num_kv_heads, attn0.head_dim,
                arrays=False, kv_dtype=kv_dtype)
            self.page_size = page_size
            self.n_log = capacity // page_size
            al = self._allocator
            self.pools = [(al.empty_pool(), al.empty_pool())
                          for _ in model.blocks]
            self.table = np.zeros((slots, self.n_log), np.int32)
            self._slot_pages: List[Optional[np.ndarray]] = \
                [None] * slots
            # prefix caching (opt-in): completed requests REGISTER
            # their page-aligned prompt-prefix pages (one registry
            # reference via the allocator's refcounts); a later request
            # sharing that exact token prefix reuses the pages and
            # prefills only its suffix. Insertion-ordered dict = LRU
            # (hits re-insert); eviction frees registry references when
            # admission runs dry. K/V in a shared page are a pure
            # function of (tokens, positions, weights), so reuse is
            # exact.
            self.prefix_cache = prefix_cache
            self._prefix_registry: Dict[tuple, np.ndarray] = {}
            self.prefix_hits = 0
            self.prefix_lookups = 0  # admissions that consulted it
        else:
            enforce(not prefix_cache,
                    "prefix_cache requires paged mode (pages=N)")
            enforce(kv_dtype is None,
                    "kv_dtype requires paged mode (pages=N) — the "
                    "contiguous arena has no quantized form")
            self.caches = model.init_cache(slots, capacity)
        if draft is not None:
            self.caches_d = draft.init_cache(slots, capacity)
        # a program that failed after it consumed the arena leaves
        # nothing to serve from (``_arena_guard``)
        self.arena_lost = False
        self._check_arena_donation()
        self._kinds = (list(kinds) if kinds and not self.paged
                       else ["kv"] * len(model.blocks))
        self._records = (list(records) if records and not self.paged
                         else [None] * len(self._kinds))
        self._counted = (hasattr(model, "step_counters")
                         and not self.paged)
        global last_counters
        self.counters = last_counters = ArenaCounters(
            self._state_bytes())
        self.tok = jnp.zeros((slots,), jnp.int32)      # last token/slot
        # cursors: paged mode parks EVERY not-yet-admitted slot past
        # capacity — an idle slot's table row is zeros, and a cursor of
        # 0 would scatter its junk K/V into physical page 0, which the
        # allocator hands to the first real request (write_rows drops
        # OOB cursors instead). Contiguous slots own private rows, so
        # 0 is harmless there.
        self.t = jnp.full((slots,),
                          capacity if self.paged else 0, jnp.int32)
        self.active = np.zeros((slots,), bool)         # host-side
        self.budget = np.zeros((slots,), np.int64)     # tokens left
        self.owner: List[Optional[Request]] = [None] * slots
        # per-slot trace context of the ACTIVE request (None unless
        # telemetry was on at submit and the request is traced) — the
        # decode tick's span/exemplar source; one list store per
        # activation, so the disabled path never touches tracing
        self._slot_trace: List[Optional[Any]] = [None] * slots
        self.emitted: List[List[int]] = [[] for _ in range(slots)]
        self.gen_count = 0                             # admission counter
        self._slot_gen = np.zeros((slots,), np.int64)
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self._next_rid = 0
        self._prefill_cache: Dict[int, object] = {}
        # jitted arena steps keyed by tokens-per-dispatch k: degraded
        # mode drops to k=1 without retracing the k=decode_steps fn
        self._step_fns: Dict[int, object] = {}
        self._spec_fn = None
        # the decode step dispatched and not yet read (contiguous arena
        # without a draft only: ``_step_multi``)
        self._ahead: Optional[_StepInFlight] = None
        # SLO degrade lever (router-driven): forces decode_steps=1 and
        # bypasses speculative rounds until cleared — see set_degraded
        self.degraded = False
        # readiness (router placement signal, distinct from liveness):
        # False until the serving step has dispatched once (jit warm),
        # False again while draining on preemption
        self._warmed = False
        # tick accounting (plain counters, harness-readable without
        # telemetry): ticks run, tokens actually emitted, and the
        # token capacity (slots x k per tick) — the serving goodput
        # ratio is tick_tokens / tick_capacity
        self.tick_count = 0
        self.tick_tokens = 0
        self.tick_capacity = 0
        self._weights_fp = None  # stamped per run() when telemetry on
        # weights/buffers snapshot, passed to every jitted fn as REAL
        # arguments (inject_state): compiled programs stay weight-free,
        # which also lets all prefill buckets + the step share one
        # on-device copy of the weights
        self._mstate = (dict(model.named_parameters()),
                        dict(model.named_buffers()))
        self._dstate = (None if draft is None else
                        (dict(draft.named_parameters()),
                         dict(draft.named_buffers())))
        # spec-mode stats: mean accepted per target verify per row =
        # spec_accepted / spec_row_rounds; tokens per target call =
        # 1 + that (the real-pair speedup formula)
        self.spec_rounds = 0
        self.spec_row_rounds = 0
        self.spec_accepted = 0
        # chunked-prefill state: slot -> {padded, plen, off, request};
        # _pf_order is admission-FIFO so ticks are fair
        self._pf: List[Optional[dict]] = [None] * slots
        self._pf_order: List[int] = []
        self.debug_server = None  # last run(debug_port=)'s server
        # (live during that run; kept stopped afterwards for port/
        # status inspection)
        self.preempted = False  # last run() exited on a grace signal
        # (in-flight drained; self.queue holds the unserved remainder)
        # slot-resident requests carrying a deadline: the per-tick
        # expiry sweep is gated on this count, so an undeadlined run
        # (reliability off) executes no deadline code per tick
        self._dl_active = 0

    # ----- host API --------------------------------------------------------

    def submit(self, prompt_ids, max_new: int,
               stream: Optional[TokenStream] = None) -> int:
        """Enqueue one request. ``stream=`` attaches a
        :class:`TokenStream`: tokens leave the arena the tick they are
        sampled (offered per serving tick) instead of only at
        completion — the per-token streaming sink."""
        enforce(len(np.asarray(prompt_ids).reshape(-1)) >= 1,
                "empty prompt")
        enforce(max_new >= 1, "max_new must be >= 1, got %s", max_new)
        enforce(stream is None or isinstance(stream, TokenStream),
                "stream= takes a serving.TokenStream, got %s",
                type(stream).__name__)
        r = Request(self._next_rid, prompt_ids, max_new)
        r.stream = stream
        # spec/multi-step modes reserve extra positions (see _extra):
        # overrun writes past an unreserved capacity would corrupt K/V
        # below a live cursor (contiguous clamp) or another request's
        # pages (paged unallocated-entry scatter)
        enforce(len(r.prompt) + max_new + self._extra <= self.capacity,
                "prompt %s + max_new %s (+%s speculative/window margin) "
                "exceeds slot capacity %s",
                len(r.prompt), max_new, self._extra, self.capacity)
        if self.paged:
            # a demand beyond the WHOLE pool could never be admitted —
            # _admit would re-queue it forever (silent run() hang)
            need = ((len(r.prompt) + max_new + self._extra
                     + self.page_size - 1) // self.page_size)
            enforce(need <= self._allocator.pages,
                    "request needs %s pages but the pool only has %s",
                    need, self._allocator.pages)
        self._next_rid += 1
        r.t_submit = time.perf_counter()
        # ambient end-to-end deadline (the router's dispatch / the
        # debug server's POST edge binds it — one contextvar read, the
        # reliability analog of the telemetry enabled-flag gate)
        r.deadline = _reliability.current()
        if telemetry.enabled():
            _serving_metrics()["requests"].inc()
            if stream is not None:
                _serving_metrics()["streams"].inc()
            # request-scoped tracing: adopt the caller's bound context
            # (the router's dispatch / the debug server's POST edge
            # binds it) so the whole decode life of this request lands
            # on ONE trace
            r.trace = _tracing.current()
            # /healthz last-request age (owner-scoped while run() has
            # our server up; submits outside a live run broadcast — a
            # stopped server kept for post-run inspection must not
            # swallow the heartbeat)
            srv = self.debug_server
            if srv is not None and srv.running:
                srv.note("request")
            else:
                _dbg_server.note("request")
        self.queue.append(r)
        return r.rid

    def run(self, debug_port: Optional[int] = None,
            flight_recorder=None,
            preemption=None) -> Dict[int, np.ndarray]:
        """Drive until every submitted request completes.

        Live diagnostics (opt-in): ``debug_port=P`` serves the debug
        endpoints (/metrics /healthz /statusz /tracez /memz) on
        127.0.0.1:P for the duration of the drive (0 = ephemeral;
        ``self.debug_server`` holds the running server; starting it
        enables telemetry; the thread is joined before run() returns).
        ``flight_recorder=`` records one entry per serving tick
        (tick wall time, queue depth, active slots) into a
        :class:`telemetry.diag.FlightRecorder` — its ``step_stall``
        watch catches a wedged arena; policy ``halt`` raises
        :class:`telemetry.diag.AnomalyHalt`, ``skip_step`` downgrades to ``record``
        (a serving tick is not an optimizer update; there is nothing
        to roll back). Only consulted while telemetry is enabled.

        Preemption grace (opt-in, ``resilience``): ``preemption=True``
        installs a SIGTERM/SIGINT handler for the drive (or pass an
        existing :class:`resilience.PreemptionHandler`). On signal the
        arena stops ADMITTING queued requests but keeps ticking until
        every in-flight request (active or mid-prefill) completes —
        drained results are returned, ``self.preempted`` is True, and
        unserved requests stay in ``self.queue`` for a successor
        process. Default ``preemption=None``: no handler, no per-tick
        resilience code (the zero-cost contract)."""
        # refresh the weight snapshot: the jitted fns take weights as
        # REAL arguments, so post-construction mutation of the model
        # (quant.apply_weight_only_int8, a LoRA merge, a hot-swapped
        # checkpoint) must be re-snapshotted here or it would be
        # silently ignored by every step. Unchanged weights rebuild a
        # dict of the SAME arrays — no retrace, no transfer.
        self._mstate = (dict(self.model.named_parameters()),
                        dict(self.model.named_buffers()))
        if self.draft is not None:
            self._dstate = (dict(self.draft.named_parameters()),
                            dict(self.draft.named_buffers()))
        if telemetry.enabled():
            # fingerprint the weight pytrees ONCE per run (they only
            # change here): per-tick records pass the hash as an Opaque
            # token, so a quant/LoRA swap between runs still registers
            # as a retrace without re-walking every leaf per dispatch
            self._weights_fp = _recompile.Opaque(hash(
                telemetry.fingerprint(
                    (self._mstate, getattr(self, "_dstate", None)))))
        self.debug_server = None
        if debug_port is not None:
            self.debug_server = _dbg_server.DebugServer(
                port=debug_port, owned=True,
                run_config={"role": "serving", "slots": self.slots,
                            "capacity": self.capacity,
                            "paged": self.paged,
                            "kv_dtype": (self._allocator.kv_dtype
                                         if self.paged else None),
                            "spec": self.draft is not None,
                            "decode_steps": self.decode_steps}).start()
            self.debug_server.add_status("serving", self._statusz)
            # on-demand bounded device capture (404->409->200 state
            # machine; one concurrent capture, hard duration cap)
            self.debug_server.add_post(
                "/profilez", _profiling.make_profilez())
            # readiness is distinct from liveness: a draining or
            # not-yet-warmed arena answers ready=false on /healthz +
            # /readyz so a router stops PLACING sessions here without
            # concluding the process is dead
            self.debug_server.set_ready(lambda: self.ready)
            if self.queue or self._pf_order or self.active.any():
                # requests submitted before the server came up: seed the
                # last-request clock now (a lower bound on the true age)
                self.debug_server.note("request")
        # preemption grace (resolved once — zero per-tick cost when
        # None): on signal, stop admitting and drain in-flight slots
        pre = None
        own_pre = False
        self.preempted = False
        if preemption is not None and preemption is not False:
            from .resilience.preemption import PreemptionHandler

            pre = (PreemptionHandler() if preemption is True
                   else preemption)
            if not pre.installed:
                pre.install()
                own_pre = True
        tick = 0
        try:
            while self.queue or self._pf_order or self.active.any():
                if pre is not None and not self.preempted \
                        and pre.requested():
                    self.preempted = True
                if self.preempted and not (self._pf_order
                                           or self.active.any()):
                    # in-flight work drained; queued requests stay in
                    # self.queue for a successor process
                    break
                telem = telemetry.enabled()
                if telem:
                    m = _serving_metrics()
                    m["queue_depth"].set(len(self.queue))
                    if self.paged:
                        al = self._allocator
                        occ = (al.pages - al.free_pages) / al.pages
                        m["page_occupancy"].set(occ)
                        pool_b = (2 * len(self.pools)
                                  * al.pool_nbytes)
                        m["kv_pool_bytes"].set(pool_b)
                        m["kv_pool_live_bytes"].set(occ * pool_b)
                    t_tick = time.perf_counter()
                with Span("serve.tick",
                          n_active=int(self.active.sum()),
                          queued=len(self.queue)):
                    self._tick(admit=not self.preempted)
                if telem:
                    tick += 1
                    # stamp OUR server when we own one (owner-scoped
                    # heartbeat — see telemetry.server.note)
                    if self.debug_server is not None:
                        self.debug_server.note("step")
                    else:
                        _dbg_server.note("step")
                    if flight_recorder is not None:
                        action = flight_recorder.record_step(
                            tick,
                            step_time=time.perf_counter() - t_tick,
                            queue_depth=len(self.queue),
                            active_slots=int(self.active.sum()))
                        if action == "halt":
                            raise flight_recorder.halt_error(
                                f"serving tick {tick}")
        finally:
            if own_pre:
                pre.uninstall()
            if self.debug_server is not None:
                self.debug_server.stop()
        if self.preempted and telemetry.enabled():
            from .resilience.preemption import _preempt_metrics

            _preempt_metrics()["clean_exits"].inc()
        out = {rid: r.result for rid, r in self.done.items()}
        self.done = {}
        return out

    def _tick(self, admit: bool = True) -> None:
        """The phases of one serving tick — admit, one prefill chunk,
        one decode step — each under its program span. The caller
        opens ``serve.tick`` around this and whatever else its tick
        holds (``run``'s loop; ``LocalReplica._tick_locked`` with its
        harvest), so the children tile a busy tick."""
        with self._arena_guard():
            if admit:
                with Span("serve.admit"):
                    self._admit()
            if self._pf_order:
                with Span("serve.prefill_tick"):
                    self._prefill_tick()
            self._step()

    def _arena(self):
        """Every donated leaf the decoder holds: the target's arena and
        the draft's."""
        return (self.pools if self.paged else self.caches,
                self.caches_d if self.draft is not None else None)

    def _check_arena_donation(self) -> None:
        """Construction-time donation-provenance check of the arena
        (analysis/donation, as ``Trainer._check_donation_safety``):
        every program donates it, so every leaf must be a buffer the
        runtime owns and no two leaves may share one. Once a decoder,
        skippable via FLAGS_static_verify=0."""
        from .core.config import FLAGS

        if not FLAGS.get("static_verify"):
            return
        from .analysis.diagnostics import format_diagnostics
        from .analysis.donation import check_donation

        diags = [d for d in check_donation(self._arena(), (0, 1))
                 if d.severity == "error"]
        enforce(not diags, "the serving arena failed the donation-"
                "safety check (FLAGS_static_verify=0 skips):\n%s",
                format_diagnostics(diags))

    @contextlib.contextmanager
    def _arena_guard(self):
        """Around whatever dispatches arena programs. Each consumes the
        arena it is given; one that raises after that (a device fault,
        an allocation that fails at run time) leaves deleted or failed
        buffers behind, and a tick on them could only raise "Array has
        been deleted" for ever. So on the way out of an exception the
        arena is waited for once; if that raises too the decoder is
        marked ``arena_lost``: it reports not ready, refuses every
        further tick with :class:`ArenaLostError`, and
        ``LocalReplica`` fails its health probe so that the router
        places the requests it held on a surviving replica. The rows
        are not rebuilt in place: their keys, values and state are
        gone, and only the router knows the prompts to replay."""
        if self.arena_lost:
            raise ArenaLostError(
                "the serving arena was consumed by a program that then "
                "failed; this decoder serves nothing more (build a new "
                "one, or let the router fail the replica over)")
        try:
            yield
        except BaseException:
            try:
                jax.block_until_ready(self._arena())
            except Exception:
                self.arena_lost = True
            raise

    def _statusz(self) -> Dict[str, Any]:
        """Arena view for /statusz (host-side fields only — reading it
        mid-tick may tear across fields, fine for monitoring)."""
        st = {"slots": self.slots, "capacity": self.capacity,
              "active_slots": int(self.active.sum()),
              "queue_depth": len(self.queue),
              "completed": len(self.done),
              "prefilling": len(self._pf_order),
              "preempted": self.preempted}
        if self.paged:
            al = self._allocator
            st["pages"] = al.pages
            st["free_pages"] = al.free_pages
            st["kv_dtype"] = al.kv_dtype or str(al.dtype)
            st["kv_pool_bytes"] = 2 * len(self.pools) * al.pool_nbytes
            if self.prefix_cache:
                st["prefix_hits"] = self.prefix_hits
        if self.draft is not None:
            st["spec_rounds"] = self.spec_rounds
            st["spec_accepted"] = self.spec_accepted
        st["ready"] = self.ready
        st["degraded"] = self.degraded
        return st

    # ----- router surface (readiness, degrade, KV handoff) -----------------

    @property
    def ready(self) -> bool:
        """Readiness (placement signal): True once the arena has
        dispatched a step (jit warm) and it is not draining. Liveness
        stays /healthz's heartbeat clocks — a not-ready replica is
        healthy, just not placeable."""
        return (self._warmed and not self.preempted
                and not self.arena_lost)

    def _step_call(self):
        """(jitted decode step, its arguments) for the CURRENT tokens-
        per-dispatch (k=1 while degraded) and arena state — the one
        place the step's calling convention lives. Calling the step
        consumes the arena among the arguments (argument 1, donated):
        whoever dispatches assigns the returned arena back, and uses
        the arguments afterwards for their shapes at most (lowering
        reads no buffer). ``self.tok`` / ``self.t`` among them are, on
        a contiguous arena, the arrays the last step's
        ``_advance_cursor`` returned, patched by ``_activate``: the
        host never fetches them, so they may still be in the making
        when the next step is dispatched on them."""
        kd = self._kd()
        step_fn = self._step_fns.get(kd)
        if step_fn is None:
            step_fn = self._step_fns[kd] = self._build_multi_step(kd)
        gens = jnp.asarray(self._slot_gen.astype(np.uint32))
        if self.paged:
            return step_fn, (self._mstate, self.pools,
                             jnp.asarray(self.table), self.tok, self.t,
                             gens)
        return step_fn, (self._mstate, self.caches, self.tok, self.t,
                         gens)

    def _kd(self) -> int:
        """Tokens a row the next dispatch produces."""
        return 1 if self.degraded else self.decode_steps

    def lower_step(self):
        """``jax.stages.Lowered`` of the decode-step program the arena
        dispatches (nothing runs) — for cost analysis and compiled-text
        checks. Its ``compile()`` rides the persistent compile cache."""
        step_fn, args = self._step_call()
        return step_fn.lower(*args)

    def warm_step(self) -> None:
        """EXPLICIT arena warmup: compile + dispatch the decode step
        executable once over the (idle) arena and mark the replica
        warmed — no sacrificial decode required. Replaces the old
        "max_new=2 warmup" workaround (a max_new=1 request finishes at
        activation without ever dispatching the arena step, and a
        2-token one burned a decode tick just to touch the
        executable). Safe on an idle arena: paged cursors are parked
        past capacity so the junk writes DROP (write_rows' OOB
        semantics); contiguous junk lands at positions a later prefill
        fully overwrites and no attention ever reads (nothing is
        active, and prefill rewrites [0, bucket) wholesale; in a ring
        the junk lands at ``t mod ring``, an entry the ring's own mask
        hides until the sequence writes it); a
        recurrent state advanced by junk is zeroed by the slot's next
        prefill. Like every dispatch it consumes the arena and rebinds
        the decoder to the one the programs return. On a contiguous
        arena it also runs the cursor's program (``_advance_cursor``)
        with no row live, which leaves the cursor as it was; the step
        is waited for, so nothing stays in flight."""
        with self._arena_guard():
            step_fn, args = self._step_call()
            if self.paged:
                self.pools, toks = step_fn(*args)
            else:
                self.caches, toks, *_ = step_fn(*args)
                self.tok, self.t = _advance_cursor(
                    self.tok, self.t, toks,
                    jnp.zeros((self.slots,), bool))
            jax.block_until_ready(toks)
            if self.draft is not None and not self.degraded:
                # spec arenas serve through the spec round: warm that
                # executable too (same idle-arena safety argument; the
                # draft cache junk is likewise overwritten at prefill)
                if self._spec_fn is None:
                    self._spec_fn = self._build_spec_step()
                table = jnp.asarray(self.table) if self.paged else None
                out = self._spec_fn(
                    self._mstate, self._dstate,
                    self.pools if self.paged else self.caches, table,
                    self.caches_d, self.tok, self.t, args[-1])
                if self.paged:
                    self.pools, self.caches_d = out[0], out[1]
                else:
                    self.caches, self.caches_d = out[0], out[1]
                jax.block_until_ready(out[2])
        self._warmed = True

    def set_degraded(self, on: bool) -> None:
        """SLO degrade lever (the router's load-shed precursor): while
        on, every dispatch emits ONE token (decode_steps forced to 1 —
        eos/budget granularity tightens, so no mid-window tail is ever
        computed just to be discarded) and speculative rounds are
        bypassed (no draft steps, no gamma+1 verify chunk per tick).
        Output correctness is unaffected either way: the plain step
        emits the target's own picks, and on re-enable the rejection
        test keeps outputs target-distributed even against a stale
        draft cache (stale drafts only lower the accept rate)."""
        self.degraded = bool(on)

    def prefill_export(self, prompt_ids) -> KVHandoff:
        """Run the bucketed prefill for ``prompt_ids`` and EXPORT the
        resulting KV pages + next-token logits instead of activating a
        slot — the prefill-worker half of prefill/decode
        disaggregation. Pages are allocated, written, gathered to host,
        and freed again, so a prefill worker's pool only ever holds
        in-flight prompts. Requires paged mode (the page payload IS the
        wire format; contiguous arenas chunk-prefill locally instead)."""
        enforce(not self._recurrent, "prefill_export is refused for a "
                "model with recurrent state: a KVHandoff carries pages "
                "of keys and values, and the state is neither")
        enforce(not self._latent, "prefill_export is refused for a model "
                "with a latent record: a KVHandoff carries pages of keys "
                "and values by head, and the record is one compressed "
                "vector a position")
        enforce(not self._ring, "prefill_export is refused for a model "
                "with a ring: a KVHandoff carries pages of keys and values "
                "for every position, and a window layer keeps its last "
                "window alone")
        enforce(self.paged, "prefill_export requires paged mode "
                "(pages=N) — the handoff payload is KV pages")
        # deadline check BEFORE the prefill compute: an expired request
        # must never burn device work (the typed-drop contract)
        dl = _reliability.current()
        if dl is not None and dl.expired():
            reject_cause("deadline")
            dl.check("prefill export")  # raises DeadlineExceededError
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        plen = len(prompt)
        enforce(plen >= 1, "empty prompt")
        enforce(plen <= self.capacity,
                "prompt %s exceeds prefill capacity %s", plen,
                self.capacity)
        # weights may have been swapped since construction (LoRA/quant)
        self._mstate = (dict(self.model.named_parameters()),
                        dict(self.model.named_buffers()))
        ps = self.page_size
        m = (plen + ps - 1) // ps
        ids = self._allocator.alloc(m)  # typed error when exhausted
        telem = telemetry.enabled()
        ctx = _tracing.current() if telem else None
        cm = (_tracing.span("serve.prefill.export", ctx=ctx,
                            plen=plen, pages=int(m))
              if telem else _NULL_CM)
        try:
            with self._arena_guard(), cm:
                row = np.zeros((self.n_log,), np.int32)
                row[:m] = ids
                lb = self._bucket_len(plen)
                padded = np.zeros((lb,), np.int32)
                padded[:plen] = prompt
                if telem:
                    _recompile.record("serving.prefill", padded)
                self.pools, logits = self._prefill_fn_paged(lb)(
                    self._mstate, self.pools, jnp.asarray(row),
                    jnp.asarray(padded), plen)
                self.counters.prefills += 1
                self.counters.prefill_resteps += 1
                al = self._allocator
                blocks = []
                for kp, vp in self.pools:
                    payload = []
                    for pool in (kp, vp):
                        got = paged_ops.export_pages(pool,
                                                     jnp.asarray(ids))
                        payload.append(
                            tuple(np.asarray(a) for a in got)
                            if al.kv_dtype else np.asarray(got))
                    blocks.append(tuple(payload))
                return KVHandoff(prompt, plen, np.asarray(logits),
                                 blocks, ps, al.kv_dtype, trace=ctx,
                                 deadline=dl)
        finally:
            self._allocator.free(ids)

    def inject_prefilled(self, handoff: KVHandoff, max_new: int,
                         stream: Optional[TokenStream] = None) -> int:
        """Admit a request whose prompt KV arrives PRE-FILLED (a
        :class:`KVHandoff` from a prefill worker): the decode replica
        allocates pages, imports the payload, and activates the slot
        from the handoff's logits — no prompt token ever runs through
        this replica's prefill, so whole-prompt admission can't stall a
        decode tick. Queues like :meth:`submit` (paged backpressure
        applies); returns the request id."""
        enforce(not self._recurrent, "inject_prefilled is refused for a "
                "model with recurrent state: a KVHandoff carries pages "
                "of keys and values, and the state is neither")
        enforce(not self._latent, "inject_prefilled is refused for a "
                "model with a latent record: a KVHandoff carries pages of "
                "keys and values by head, and the record is one "
                "compressed vector a position")
        enforce(not self._ring, "inject_prefilled is refused for a model "
                "with a ring: a KVHandoff carries pages of keys and values "
                "for every position, and a window layer keeps its last "
                "window alone")
        enforce(self.paged, "inject_prefilled requires paged mode "
                "(pages=N) on the decode replica")
        enforce(isinstance(handoff, KVHandoff),
                "inject_prefilled takes a KVHandoff, got %s",
                type(handoff).__name__)
        enforce(handoff.page_size == self.page_size,
                "handoff page_size %s != replica page_size %s",
                handoff.page_size, self.page_size)
        al = self._allocator
        enforce(handoff.kv_dtype == al.kv_dtype,
                "handoff kv_dtype %r != replica kv_dtype %r — the "
                "storage form crosses the wire intact",
                handoff.kv_dtype, al.kv_dtype)
        enforce(len(handoff.blocks) == len(self.pools),
                "handoff has %s blocks, replica model has %s",
                len(handoff.blocks), len(self.pools))
        enforce(max_new >= 1, "max_new must be >= 1, got %s", max_new)
        r = Request(self._next_rid, handoff.prompt, max_new)
        enforce(len(r.prompt) + max_new + self._extra <= self.capacity,
                "prompt %s + max_new %s (+%s speculative/window margin) "
                "exceeds slot capacity %s",
                len(r.prompt), max_new, self._extra, self.capacity)
        need = ((len(r.prompt) + max_new + self._extra
                 + self.page_size - 1) // self.page_size)
        enforce(need <= al.pages,
                "request needs %s pages but the pool only has %s",
                need, al.pages)
        enforce(stream is None or isinstance(stream, TokenStream),
                "stream= takes a serving.TokenStream, got %s",
                type(stream).__name__)
        r.handoff = handoff
        r.stream = stream
        self._next_rid += 1
        r.t_submit = time.perf_counter()
        # the handoff carries the REQUEST's deadline (absolute epoch —
        # remaining budget, not a per-hop reset); a bound ambient
        # deadline wins, same precedence as the trace context below
        r.deadline = _reliability.current() or handoff.deadline
        if telemetry.enabled():
            _serving_metrics()["requests"].inc()
            if stream is not None:
                _serving_metrics()["streams"].inc()
            # the handoff carries its producer's context (in-process
            # disaggregation); an HTTP hop's bound header context wins
            # — both are the same trace when the router did its job
            r.trace = _tracing.current() or handoff.trace
            srv = self.debug_server
            if srv is not None and srv.running:
                srv.note("request")
            else:
                _dbg_server.note("request")
        self.queue.append(r)
        return r.rid

    def _import_handoff(self, s: int, r: Request) -> None:
        """Write the handoff payload into this slot's freshly allocated
        pages and activate from the handoff logits (admission epilogue
        for pre-filled requests)."""
        h = r.handoff
        plen = h.plen
        cm = (_tracing.span("serve.handoff.import", ctx=r.trace,
                            plen=plen, slot=s)
              if telemetry.enabled() else _NULL_CM)
        with cm:
            m = (plen + self.page_size - 1) // self.page_size
            ids = jnp.asarray(self._slot_pages[s][:m])
            # the payload is host memory, which the CPU client may
            # alias and not copy: every leaf that goes into a donated
            # program is first a buffer the runtime owns
            blocks = jax.tree_util.tree_map(
                lambda a: owned_on_device(jnp.asarray(a)), list(h.blocks))
            self.pools = self._import_fn()(self.pools, ids, blocks)
            self._activate(s, r, self._first_token(
                s, jnp.asarray(h.logits), plen), plen)

    def _import_fn(self):
        """Jitted page import: the handoff's pages written into the
        pools in place (one compile a page count)."""
        fn = self._prefill_cache.get(("import",))
        if fn is None:
            def imp(pools, ids, blocks):
                return [(paged_ops.import_pages(kp, ids, pk),
                         paged_ops.import_pages(vp, ids, pv))
                        for (kp, vp), (pk, pv) in zip(pools, blocks)]

            fn = _arena_jit(imp, "pt_handoff_import", (0,))
            self._prefill_cache[("import",)] = fn
        return fn

    # ----- internals -------------------------------------------------------

    def _state_bytes(self) -> Dict[str, int]:
        """Device bytes of the arena by kind of state; a window
        layer's ring is counted apart from ``kv``, under ``ring``."""
        out = {"kv": 0, "recurrent": 0}
        arena = self.pools if self.paged else self.caches
        for kind, record, block in zip(self._kinds, self._records, arena):
            name = "ring" if record == "ring" else kind
            out[name] = out.get(name, 0) + sum(
                int(leaf.nbytes) for leaf in
                jax.tree_util.tree_leaves(block))
        return out

    def _fresh_row(self, row):
        """The sliced row with every recurrent block's state zeroed:
        whatever the slot's last request (or an idle slot's junk steps)
        left there, a new sequence starts from nothing, and the
        prefill's one chunk advances it over the whole prompt. Keys and
        values stay: the cursor masks them, and a ring's own mask (its
        entries up to the cursor while the cursor is inside it) masks a
        longer request's leftovers the same way."""
        return [jax.tree_util.tree_map(jnp.zeros_like, r)
                if kind == "recurrent" else r
                for kind, r in zip(self._kinds, row)]

    def _bucket_len(self, n: int) -> int:
        b = self.bucket
        # clamp to capacity: bucket rounding past the arena would hand
        # forward_chunk a write window it silently clamps (its
        # documented caller contract); any admissible prompt fits since
        # submit enforces plen + max_new <= capacity
        return min(max(b, ((n + b - 1) // b) * b), self.capacity)

    def _prefill_fn(self, lb: int):
        """Jitted prefill for bucket length lb: ONE pass of the padded
        prompt through the model at positions [0, lb), writing slot
        ``s`` of the arena and returning the logits of position
        ``plen - 1`` (the first token's). One compile per bucket."""
        fn = self._prefill_cache.get(lb)
        if fn is not None:
            return fn
        model = self.model

        def prefill(mstate, caches, padded, plen, s):
            # the FULL bucket (static shape) as one chunk, of which the
            # first plen positions are the prompt (``valid_len``): keys
            # and values are written for the whole bucket (positions
            # >= plen land above the cursor, masked + overwritten
            # later; a ring takes the last window of the plen valid
            # positions and nothing of the padding), a recurrence
            # advances over those plen tokens and no further, from
            # zeros. The first token's logits are the
            # head applied to the chunk's own row plen - 1 (causal: it
            # has seen positions <= plen - 1 and no padding): an
            # (lb, vocab) head would be the dominant prefill FLOP, and
            # a step of the last token through the model for them would
            # read every weight a second time.
            def body(row):
                return model._chunk_logits(
                    padded[None], self._fresh_row(row), 0,
                    valid_len=plen, head_at=plen - 1)

            with inject_state((model, *mstate)):
                logits, new = _row_apply(caches, s, body)
            return new, logits[0]

        fn = _arena_jit(prefill, f"pt_prefill_{lb}", (1,))
        self._prefill_cache[lb] = fn
        return fn

    def _prefill_fn_paged(self, lb: int):
        """Jitted paged prefill for bucket length lb: chunk-write the
        prompt into the row's pages cache-only, then one re-step of the
        last token for the next-token logits (kept: no cell runs the
        paged arena yet; ``_prefill_fn`` shows the one-pass form)."""
        fn = self._prefill_cache.get(("paged", lb))
        if fn is not None:
            return fn
        model = self.model

        def prefill(mstate, pools, table_row, padded, plen):
            with inject_state((model, *mstate)):
                _, pools = model._chunk_logits_paged(
                    padded[None], pools, table_row, 0, head=False)
                last = lax.dynamic_index_in_dim(padded, plen - 1,
                                                keepdims=False)
                logits, pools = model._step_logits_paged(
                    last[None], pools, table_row[None],
                    jnp.full((1,), plen - 1, jnp.int32))
            return pools, logits[0]

        fn = _arena_jit(prefill, f"pt_prefill_paged_{lb}", (1,))
        self._prefill_cache[("paged", lb)] = fn
        return fn

    def _suffix_fns(self, lb: int):
        """Prefix-hit prefill pieces: cache-only chunk of the SUFFIX at
        a page-aligned offset (one compile per bucket) and the
        lb-independent last-token re-step (compiled ONCE; also used
        alone when the whole prompt is cached: then there is no chunk
        to take the last row from, so this path keeps the re-step)."""
        model = self.model
        chunk_fn = self._prefill_cache.get(("suffix", lb))
        if chunk_fn is None:
            def chunk(mstate, pools, table_row, padded, t0):
                with inject_state((model, *mstate)):
                    _, pools = model._chunk_logits_paged(
                        padded[None], pools, table_row, t0, head=False)
                return pools

            chunk_fn = _arena_jit(chunk, f"pt_prefill_suffix_{lb}", (1,))
            self._prefill_cache[("suffix", lb)] = chunk_fn
        restep_fn = self._prefill_cache.get(("restep",))
        if restep_fn is None:
            def restep(mstate, pools, table_row, tok, pos):
                with inject_state((model, *mstate)):
                    logits, pools = model._step_logits_paged(
                        tok[None], pools, table_row[None],
                        jnp.full((1,), pos, jnp.int32))
                return pools, logits[0]

            restep_fn = _arena_jit(restep, "pt_prefill_restep", (1,))
            self._prefill_cache[("restep",)] = restep_fn
        return chunk_fn, restep_fn

    def _chunk_fn_contig(self, c: int):
        """Jitted cache-only contiguous-prefill piece: run chunk tokens
        (c,) at [t0, t0+c) through slot ``s``'s row (one compile per
        chunk size — the chunk size is fixed, so one total)."""
        fn = self._prefill_cache.get(("cchunk", c))
        if fn is not None:
            return fn
        model = self.model

        def chunk(mstate, caches, toks, t0, s):
            with inject_state((model, *mstate)):
                _, new = _row_apply(
                    caches, s, lambda row: model._chunk_logits(
                        toks[None], row, t0, head=False))
            return new

        fn = _arena_jit(chunk, f"pt_prefill_chunk_{c}", (1,))
        self._prefill_cache[("cchunk", c)] = fn
        return fn

    def _restep_contig(self):
        """Jitted last-token re-step for slot ``s`` (chunked-prefill
        finish): idempotent K/V rewrite at pos, single-row head (kept:
        no cell runs chunked prefill yet)."""
        fn = self._prefill_cache.get(("crestep",))
        if fn is not None:
            return fn
        model = self.model

        def restep(mstate, caches, tok, pos, s):
            with inject_state((model, *mstate)):
                logits, new = _row_apply(
                    caches, s,
                    lambda row: model._step_logits(tok[None], row, pos))
            return new, logits[0]

        fn = _arena_jit(restep, "pt_prefill_restep", (1,))
        self._prefill_cache[("crestep",)] = fn
        return fn

    def _prefill_tick(self):
        """Advance chunked prefill by ONE chunk (FIFO across admitting
        slots) — bounds the prefill work added to any serving-loop
        iteration, so active slots keep their decode cadence. On the
        final chunk the slot activates via the last-token re-step."""
        if not self._pf_order:
            return
        s = self._pf_order[0]
        st = self._pf[s]
        padded, plen, off, r = (st["padded"], st["plen"], st["off"],
                                st["r"])
        c = self.prefill_chunk
        if off < plen:
            t0 = off
            if t0 + c > self.capacity:
                # slide the final chunk back so the write can't clamp
                # below the frontier (the overlap re-writes the same
                # real tokens — idempotent); paged mode never triggers
                # this (page demand >= the chunk frontier)
                t0 = self.capacity - c
            toks = jnp.asarray(padded[t0:t0 + c])
            if self.paged:
                chunk_fn, _ = self._suffix_fns(c)
                self.pools = chunk_fn(
                    self._mstate, self.pools,
                    jnp.asarray(self.table[s]), toks, t0)
            else:
                self.caches = self._chunk_fn_contig(c)(
                    self._mstate, self.caches, toks,
                    jnp.asarray(t0, jnp.int32),
                    jnp.asarray(s, jnp.int32))
            st["off"] = t0 + c
            if st["off"] < plen:
                return
        # all chunks written: re-step the last prompt token for the
        # next-token logits and go live
        self.counters.prefill_resteps += 1
        last = jnp.asarray(int(padded[plen - 1]), jnp.int32)
        if self.paged:
            _, restep_fn = self._suffix_fns(self.bucket)
            self.pools, logits = restep_fn(
                self._mstate, self.pools, jnp.asarray(self.table[s]),
                last, plen - 1)
        else:
            self.caches, logits = self._restep_contig()(
                self._mstate, self.caches, last,
                jnp.asarray(plen - 1, jnp.int32),
                jnp.asarray(s, jnp.int32))
        self._pf[s] = None
        self._pf_order.pop(0)
        self._activate(s, r, self._first_token(s, logits, plen), plen)

    def _prefix_key(self, prompt: np.ndarray, n: int) -> bytes:
        return np.ascontiguousarray(prompt[:n], np.int32).tobytes()

    def _lookup_prefix(self, prompt: np.ndarray):
        """Longest registered page-aligned prefix of ``prompt`` ->
        (pages, cached_len); LRU-touches the hit. Keys are the raw
        token bytes (one memcpy + C-level hash, not per-int boxing)."""
        if not self._prefix_registry:
            return None, 0
        ps = self.page_size
        for k in range(min(len(prompt) // ps, self.n_log), 0, -1):
            key_t = self._prefix_key(prompt, k * ps)
            e = self._prefix_registry.pop(key_t, None)
            if e is not None:
                self._prefix_registry[key_t] = e      # LRU re-insert
                return e, k * ps
        return None, 0

    def _evict_prefixes(self, want: int):
        """Drop oldest registry entries until ``want`` pages are free
        (pages still referenced by live requests stay allocated)."""
        while (self._prefix_registry
               and self._allocator.free_pages < want):
            key_t = next(iter(self._prefix_registry))
            self._allocator.free(self._prefix_registry.pop(key_t))

    def _try_alloc_paged(self, s: int, r: Request):
        """Paged admission allocation (prefix lookup + pin + evict +
        alloc); installs the slot's table row. Returns the cached
        prefix length, or None when the pool can't satisfy the demand
        yet (caller requeues — backpressure)."""
        plen = len(r.prompt)
        # handoff requests never take a prefix hit: their payload is
        # IMPORTED over the allocated pages, and importing onto pages
        # shared with the registry (or a live request) would corrupt
        # every other holder's KV
        if self.prefix_cache and r.handoff is None:
            self.prefix_lookups += 1
            hit, cached = self._lookup_prefix(r.prompt)
        else:
            hit, cached = None, 0
        if hit is not None:
            # PIN before any eviction: _evict_prefixes may drop the
            # hit's own registry entry, and an unpinned hit would be
            # freed and handed straight back by alloc() — the same
            # physical page twice in one table (silent KV corruption)
            self._allocator.share(hit)
        need = ((plen + r.max_new + self._extra + self.page_size - 1)
                // self.page_size)
        need_new = need - cached // self.page_size
        if need_new > self._allocator.free_pages:
            self._evict_prefixes(need_new)
        if need_new > self._allocator.free_pages:
            if hit is not None:
                self._allocator.free(hit)       # unpin
            return None                         # wait for completions
        new_ids = self._allocator.alloc(need_new)
        if hit is not None:
            self.prefix_hits += 1
            ids = np.concatenate([hit, new_ids])
        else:
            ids = new_ids
        row = np.zeros((self.n_log,), np.int32)
        row[:need] = ids
        self.table[s] = row
        self._slot_pages[s] = ids
        return cached

    def _draft_prefill_fn(self, lb: int):
        """Jitted cache-only draft prefill for bucket lb (spec mode):
        the draft arena needs the prompt's K/V at [0, plen) — the spec
        round's first draft step feeds the last emitted token, so no
        restep/logits here."""
        fn = self._prefill_cache.get(("draft", lb))
        if fn is not None:
            return fn
        draft = self.draft

        def prefill(dstate, caches, padded, s):
            with inject_state((draft, *dstate)):
                _, new = _row_apply(
                    caches, s, lambda row: draft._chunk_logits(
                        padded[None], row, 0, head=False))
            return new

        fn = _arena_jit(prefill, f"pt_draft_prefill_{lb}", (1,))
        self._prefill_cache[("draft", lb)] = fn
        return fn

    def _first_token(self, s: int, logits, plen: int) -> int:
        """The first token of slot ``s``, picked and fetched: where the
        host has waited out the prefill's device work."""
        return int(self._pick(logits[None], s, plen)[0])

    def _activate(self, s: int, r: Request, tok: int, plen: int):
        """Shared admission epilogue: the slot goes live on its first
        token."""
        self.active[s] = True
        self._slot_trace[s] = r.trace
        self.emitted[s] = [tok]
        r.t_first = time.perf_counter()
        r.t_tokens.append(r.t_first)
        if telemetry.enabled():
            m = _serving_metrics()
            traced = r.trace is not None and r.trace.sampled
            if r.t_submit:
                # TTFT exemplar: a traced sample stamps its trace id
                # onto the bucket it lands in — the p99 row's link to
                # the cross-process timeline that produced it
                m["ttft"].observe(
                    r.t_first - r.t_submit,
                    exemplar=r.trace.trace_id if traced else None)
            if traced:
                _tracing.event("serve.first_token", ctx=r.trace,
                               rid=r.rid, slot=s)
            m["tokens"].inc()
        self.budget[s] = r.max_new - 1
        self.tok = self.tok.at[s].set(tok)
        self.t = self.t.at[s].set(plen)
        if r.stream is not None:
            # the first token leaves the arena at activation, not at
            # completion — the streaming-TTFT edge
            r.stream.offer(self.emitted[s], r.t_first)
        self._maybe_finish(s)

    def _admit(self):
        """Fill free slots from the queue, in its order. Monolithic
        mode runs the whole prefill (+ first token) here, up to
        ``prefill_budget`` padded prompt tokens a tick or one prompt
        however long; chunked mode (prefill_chunk=C) only allocates and
        queues the slot for _prefill_tick. Paged mode backpressures: a
        request whose page demand exceeds the free pool stays queued
        until completions free pages."""
        spent = 0  # padded prompt tokens prefilled whole in this tick
        for s in range(self.slots):
            if (self.active[s] or self._pf[s] is not None
                    or not self.queue):
                continue
            r = self.queue.pop(0)
            # a request that expired while QUEUED is dropped typed
            # before any prefill work — never silently computed
            while r.deadline is not None and r.deadline.expired():
                self._expire_request(r, where="queue")
                if not self.queue:
                    r = None
                    break
                r = self.queue.pop(0)
            if r is None:
                break
            plen = len(r.prompt)
            lb = self._bucket_len(plen)
            if r.handoff is None and self.prefill_chunk is None:
                # every decoding row waits out every prefill of its
                # tick: past the budget the queue keeps the rest for
                # the next tick, one tick later for them and a bounded
                # gap for all the others
                if spent and spent + lb > self.prefill_budget:
                    self.queue.insert(0, r)
                    break
                spent += lb
            padded = np.zeros((lb,), np.int32)
            padded[:plen] = r.prompt
            cached = 0
            if self.paged:
                cached = self._try_alloc_paged(s, r)
                if cached is None:
                    reject_cause("pool_exhausted")
                    self.queue.insert(0, r)
                    break
            if r.deadline is not None:
                # slot-resident from here on: the per-tick expiry
                # sweep (gated on this count) owns the deadline now
                self._dl_active += 1
            self.owner[s] = r
            self._slot_gen[s] = self.gen_count
            self.gen_count += 1
            if self.draft is not None:
                # draft cache needs the FULL prompt regardless of the
                # target's prefix hit (prefix pages cache only the
                # target's K/V); draft prefill is the cheap side
                self.caches_d = self._draft_prefill_fn(lb)(
                    self._dstate, self.caches_d, jnp.asarray(padded),
                    jnp.asarray(s, jnp.int32))
            if r.handoff is not None:
                # pre-filled KV arrived with the request: import the
                # pages and go live — no local prefill work at all
                # (chunked-prefill deferral included)
                self._import_handoff(s, r)
                continue
            self.counters.prefills += 1
            if self.prefill_chunk is not None:
                # defer: chunk grid starts at the cached frontier
                # (page-aligned, hence chunk-aligned); park the cursor
                # so arena steps can't land junk below the frontier.
                # The tick reads fixed-size chunks, so pad the prompt
                # to the CHUNK grid (not the prompt bucket)
                c = self.prefill_chunk
                grid = np.zeros((max(1, -(-plen // c)) * c,), np.int32)
                grid[:plen] = r.prompt
                self._pf[s] = {"padded": grid, "plen": plen,
                               "off": cached, "r": r}
                self._pf_order.append(s)
                self.t = self.t.at[s].set(self.capacity)
                continue
            telem = telemetry.enabled()
            if telem:
                # one compile per prompt bucket: a new padded shape
                # here IS a new monolithic-prefill executable. Chunked
                # mode bailed out above — it compiles per CHUNK size,
                # so recording the bucket there would count compiles
                # that never happen
                _recompile.record("serving.prefill", padded)
            pf_cm = (_tracing.span("serve.prefill", ctx=r.trace,
                                   plen=plen, slot=s, cached=cached)
                     if telem else _NULL_CM)
            with pf_cm:
                # the program span ends where the host holds the first
                # token: what every decoding row waits out when a
                # prefill falls into its tick
                with Span("serve.prefill", rid=r.rid, plen=plen,
                          bucket=lb, queued_us=int(
                              (time.perf_counter() - r.t_submit) * 1e6)):
                    if self.paged:
                        self.counters.prefill_resteps += 1
                        row = self.table[s]
                        if cached == 0:
                            pf = self._prefill_fn_paged(lb)
                            self.pools, logits = pf(
                                self._mstate, self.pools, jnp.asarray(row),
                                jnp.asarray(padded), plen)
                            if telem:
                                _costs.ensure_program(
                                    f"serving.prefill[paged,{lb}]", pf,
                                    (self._mstate, self.pools,
                                     jnp.asarray(row), jnp.asarray(padded),
                                     plen), origin="serving")
                        else:
                            # prefill only the uncached suffix (page-aligned
                            # t0), then the usual last-token re-step for the
                            # next-token logits — handles a fully-cached
                            # prompt (empty suffix) too
                            suf = r.prompt[cached:]
                            if len(suf):
                                slb = self._bucket_len(len(suf))
                                spad = np.zeros((slb,), np.int32)
                                spad[:len(suf)] = suf
                                chunk_fn, restep_fn = self._suffix_fns(slb)
                                self.pools = chunk_fn(
                                    self._mstate, self.pools,
                                    jnp.asarray(row),
                                    jnp.asarray(spad), cached)
                            else:
                                _, restep_fn = self._suffix_fns(self.bucket)
                            self.pools, logits = restep_fn(
                                self._mstate, self.pools, jnp.asarray(row),
                                jnp.asarray(r.prompt[plen - 1], jnp.int32),
                                plen - 1)
                    else:
                        pf = self._prefill_fn(lb)
                        self.caches, logits = pf(
                            self._mstate, self.caches, jnp.asarray(padded),
                            plen, s)
                        if hasattr(self.model, "expert_layers"):
                            # the bucket as one chunk, cut to one row
                            # before the last block's channel mix
                            n, dense = self.model.expert_layers(lb, 1)
                            self.counters.prefill_expert_layers += n
                            self.counters.prefill_dense_layers += dense
                        if telem:
                            _costs.ensure_program(
                                f"serving.prefill[{lb}]", pf,
                                (self._mstate, self.caches,
                                 jnp.asarray(padded), plen, s),
                                origin="serving")
                    tok = self._first_token(s, logits, plen)
                self._activate(s, r, tok, plen)

    @_scope("head")
    def _pick(self, logits, s: int, pos: int):
        """Admission-time single-row pick (the steady-state loop picks
        batched in _step); caller sets _slot_gen[s] first."""
        if not self.sampled:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(
            jax.random.fold_in(self.key, int(self._slot_gen[s])), pos)
        return sample_from_logits(logits, k, self.temperature,
                                  self.top_k, self.top_p).astype(jnp.int32)

    def _build_multi_step(self, kd: int):
        """decode_steps=k jitted step: scan k single-token steps with
        the picks IN-DEVICE (same fold_in key chain as the host picks,
        so outputs are token-identical to k=1) — every dispatch
        advances all slots k tokens (one dispatch and one host fetch
        per k tokens; not measured on the chip).
        Inactive/parked rows compute junk the host discards; their
        writes drop (paged) or land above any attended position.
        ``kd`` is a parameter (not ``self.decode_steps``) so the SLO
        degrade lever can hold a k=1 executable next to the full-k one."""
        model = self.model
        sampled, temp = self.sampled, self.temperature
        top_k, top_p, key = self.top_k, self.top_p, self.key
        paged = self.paged

        @_scope("head")
        def pick(logits, gens, poss):
            if not sampled:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            keys = jax.vmap(lambda g, p: jax.random.fold_in(
                jax.random.fold_in(key, g), p))(
                gens, poss.astype(jnp.uint32))
            return jax.vmap(lambda lg, kk: sample_from_logits(
                lg[None], kk, temp, top_k,
                top_p)[0])(logits, keys).astype(jnp.int32)

        if paged:
            def step(mstate, pools, table, tok, t, gens):
                with inject_state((model, *mstate)):
                    def body(c, _):
                        pools, tok, t = c
                        logits, pools = model._step_logits_paged(
                            tok, pools, table, t)
                        nxt = pick(logits, gens, t + 1)
                        return (pools, nxt, t + 1), nxt

                    (pools, _, _), toks = lax.scan(
                        body, (pools, tok, t), None, length=kd)
                return pools, jnp.swapaxes(toks, 0, 1)   # (B, k)
        else:
            # a model that counts (``step_counters``: the tokens each
            # held expert got) has the step return the sums as a third
            # output, fetched with the tokens
            counted = self._counted

            def step(mstate, caches, tok, t, gens):
                with inject_state((model, *mstate)):
                    def body(c, _):
                        caches, tok, t = c
                        logits, caches = model._step_logits_rows(
                            tok, caches, t, decode_kernel=True)
                        nxt = pick(logits, gens, t + 1)
                        return (caches, nxt, t + 1), (
                            (nxt, model.step_counters()) if counted
                            else nxt)

                    (caches, _, _), out = lax.scan(
                        body, (caches, tok, t), None, length=kd)
                if not counted:
                    return caches, jnp.swapaxes(out, 0, 1)
                toks, got = out
                return (caches, jnp.swapaxes(toks, 0, 1),
                        jax.tree_util.tree_map(
                            lambda a: jnp.sum(a, axis=0), got))

        return _arena_jit(
            step, "pt_decode_step" if kd == 1 else f"pt_decode_step_k{kd}",
            (1,))

    def _step_multi(self):
        """The contiguous arena's decode step, run ONE AHEAD of the
        host: dispatch step N+1 on the cursor step N left on the device,
        then settle step N (fetch its tokens, emit, finish rows), so the
        host's part of a tick runs under the device's next step and not
        between two. ``_ahead`` is the step in flight.

        What the order rests on: a row whose budget the tokens in flight
        exhaust is not stepped again (``live``), so a budget's end never
        costs a surplus step; a row that ends on ``eos`` is learned one
        step late, and the one surplus row of tokens is dropped at
        settle (``rows_dropped``), as is the row of a request torn down
        meanwhile (a row is emitted only to the request it was
        dispatched for). A slot freed at settle is prefilled by the next
        tick's ``_admit`` into the arena step N+1 returned, so the
        device orders the prefill after N+1 and the row joins N+2. A
        step that fails on the device surfaces at its settle, inside
        that tick's ``_arena_guard``. With no row live nothing is
        dispatched, and a step left in flight with no row active is
        settled at once: ``run()`` and ``LocalReplica`` stop on
        ``active`` and leave nothing in flight."""
        prev = self._ahead
        if prev is None and not self.active.any():
            return
        tick_ctx, tick_cm = self._decode_tick_span()
        with tick_cm:
            # a failed dispatch leaves ``prev`` in flight
            self._ahead = self._dispatch_ahead(prev)
            if prev is not None:
                self._settle(prev, tick_ctx)
                if self._ahead is not None:
                    # the device began it when ``prev`` ended: the time
                    # a token of it took is counted from here
                    self._ahead = self._ahead._replace(
                        t_start=time.perf_counter())
            if self._ahead is not None and not self.active.any():
                # every row it stepped ended at this settle (``eos``,
                # a teardown): read it now, for its counters and its
                # failures, and drop its rows
                nxt, self._ahead = self._ahead, None
                self._settle(nxt, tick_ctx)

    def _decode_tick_span(self):
        """(trace context, span) of one decode tick. One dispatch
        advances every active slot, so the tick rides the first SAMPLED
        slot's context (an unsampled context must not shadow a sampled
        neighbor — it would starve that request's timeline of its
        decode ticks); inert while telemetry is off."""
        if not telemetry.enabled():
            return None, _NULL_CM
        ctx = next((c for c in self._slot_trace
                    if c is not None and c.sampled), None)
        if ctx is None:
            return None, _NULL_CM
        return ctx, _tracing.span("serve.decode.tick", ctx=ctx,
                                  k=self._kd(),
                                  n_active=int(self.active.sum()))

    def _dispatch_ahead(self, prev: Optional[_StepInFlight]):
        """Dispatch the decode step over the rows that have a token
        left to produce beyond those in flight in ``prev``, and leave
        the cursor it ends on on the device; None where no row has."""
        pending = np.zeros((self.slots,), np.int64)
        if prev is not None:
            same = np.fromiter(
                (a is b for a, b in zip(prev.owners, self.owner)),
                bool, self.slots)
            pending[prev.live & same] = prev.kd
        live = self.active & (self.budget - pending > 0)
        if not live.any():
            return None
        step = self._dispatch_step(live)
        # the cursor is never fetched: its program is dispatched behind
        # the step, and the next step reads it where it is
        with Span("serve.step.cursor"):
            self.tok, self.t = _advance_cursor(
                self.tok, self.t, step.toks, jnp.asarray(live))
        if prev is not None:
            self.counters.steps_ahead += 1
        return step

    def _dispatch_step(self, live: np.ndarray) -> _StepInFlight:
        """Dispatch the decode step (k=1 while degraded: a separate
        cache entry, so toggling retraces nothing) and rebind the arena
        to the one it returns; ``live`` are the rows it steps for a
        request."""
        telem = telemetry.enabled()
        if telem:
            # the weight token participates: run()'s weight re-snapshot
            # means a post-construction quant/LoRA swap changes the
            # weight pytree and genuinely retraces — a fingerprint of
            # just (tok, t) would never see it
            _recompile.record("serving.step", self.tok, self.t,
                              weights=self._weights_fp)
        with Span("serve.step.dispatch"):
            step_fn, args = self._step_call()
            kd = self._kd()
            if telem:
                # cost-ledger registration, once per step variant
                # (set lookup after the first tick), BEFORE the
                # dispatch: the step consumes the arena in ``args``
                _costs.ensure_program(f"serving.step[k={kd}]",
                                      step_fn, args, origin="serving")
            t_start = time.perf_counter()
            counted = ()
            if self.paged:
                self.pools, toks = step_fn(*args)
            else:
                self.caches, toks, *counted = step_fn(*args)
        return _StepInFlight(toks, counted, live, list(self.owner), kd,
                             t_start)

    def _settle(self, step: _StepInFlight, tick_ctx) -> np.ndarray:
        """Read a dispatched step (the host blocks on the device here;
        what the step counted comes over in the same fetch) and emit
        its tokens to the requests it stepped; returns the tokens."""
        with Span("serve.step.fetch"):
            toks, counted = jax.device_get((step.toks, step.counted))
            toks = np.asarray(toks).astype(np.int32)
        self.counters.add(counted[0] if counted else {})
        rows = [s for s in np.flatnonzero(step.live)
                if self.owner[s] is step.owners[s]]
        self.counters.rows_dropped += int(step.live.sum()) - len(rows)
        self._emit_step(toks, rows, step.kd, step.t_start, tick_ctx)
        return toks

    def _emit_step(self, toks, rows, kd: int, t_start: float,
                   tick_ctx) -> None:
        """A fetched step's host side: append each of ``rows``' k
        tokens in order with per-TOKEN budget/eos finishing (nothing
        emits past eos or budget; a mid-window finish discards the
        tail), then the tick's accounting; ``t_start`` is when the
        step's own time began."""
        self._warmed = True
        now = time.perf_counter()
        n_emitted = 0
        with Span("serve.step.emit"):
            for s in rows:
                r = self.owner[s]
                for j in range(kd):
                    self.emitted[s].append(int(toks[s, j]))
                    r.t_tokens.append(now)
                    n_emitted += 1
                    self.budget[s] -= 1
                    self._maybe_finish(s)
                    if not self.active[s]:
                        break
                if r.stream is not None and r.result is None:
                    # per-tick streaming: this tick's tokens leave NOW
                    # (completion already streamed via finish above)
                    r.stream.offer(self.emitted[s], now)
        # tick accounting (plain ints — the bench harness reads these
        # without enabling telemetry)
        self.tick_count += 1
        self.tick_tokens += n_emitted
        self.tick_capacity += self.slots * kd
        if telemetry.enabled() and n_emitted:
            m = _serving_metrics()
            m["tokens"].inc(n_emitted)
            itl = (time.perf_counter() - t_start) / n_emitted
            m["decode_latency"].observe(
                itl,
                exemplar=(tick_ctx.trace_id
                          if tick_ctx is not None else None))
            # serving goodput (active-slot-tokens vs capacity) + the
            # ITL regression sentinel; a degraded arena (router SLO
            # lever) never feeds a baseline
            _profiling.goodput().note_tick(n_emitted, self.slots * kd)
            _profiling.sentinel().observe(
                f"serving.step[k={kd}]", self._backend(), itl,
                kind="itl",
                degraded=self.degraded)

    def _step_sync(self):
        """The synchronous decode step of a paged or speculative arena:
        dispatch, fetch, emit, and only then the cursor, set from the
        fetched tokens on the host. These arenas need that order: a
        retired row's pages are freed and may be handed to another
        request, which is safe because the host parks the row's cursor
        (``_maybe_finish``, ``_expire_slots``) BEFORE the next dispatch,
        and a speculative round sets every cursor from its accepted
        count on the host (``_step_spec``)."""
        if not self.active.any():
            return
        was_active = self.active.copy()
        tick_ctx, tick_cm = self._decode_tick_span()
        with tick_cm:
            step = self._dispatch_step(was_active)
            toks = self._settle(step, tick_ctx)
        # retired rows keep what _maybe_finish left (paged parking);
        # np.asarray of self.t and self.tok are two more device fetches
        with Span("serve.step.cursor"):
            keep = was_active & self.active
            cur_t = np.asarray(self.t)
            self.tok = jnp.asarray(np.where(
                keep, toks[:, -1], np.asarray(self.tok)).astype(np.int32))
            self.t = jnp.asarray(np.where(
                keep, cur_t + step.kd, cur_t).astype(np.int32))

    def _build_spec_step(self):
        """One speculative ROUND over the whole arena, jitted: gamma
        per-row draft steps (lax.scan), ONE per-row target verify
        chunk, and the Leviathan/Chen modified rejection test — all at
        per-row cursors, fixed shapes. Greedy mode (temperature=0)
        matches the plain arena step loop up to near-tie argmax flips
        (the verify chunk and the step loop reduce in different orders;
        ``TestSpeculativeArena`` pins at least 90% agreement, not
        identity); sampled mode draws from the target's own filtered
        distribution (the same construction models/speculative.py pins
        with a frequency test). Inactive/parked rows compute junk that
        the host discards; their writes drop (paged) or land above any
        attended position (contiguous clamp)."""
        from .ops.sampling import filter_logits

        model, draft, gamma = self.model, self.draft, self.gamma
        sampled, temp = self.sampled, self.temperature
        top_k, top_p, key = self.top_k, self.top_p, self.key
        paged = self.paged

        def _flp(logits):
            return jax.nn.log_softmax(
                filter_logits(logits, temp, top_k, top_p), axis=-1)

        def spec(tstate, table, caches_d, tok, t, gens):
            # per-row key chain: (admission generation, round nonce=t —
            # strictly increasing per slot-generation, so draws never
            # collide across rounds)
            kb = jax.vmap(lambda g, tt: jax.random.fold_in(
                jax.random.fold_in(key, g), tt))(
                gens, t.astype(jnp.uint32))

            def draft_step(c, i):
                tokc, cd = c
                logits, cd = draft._step_logits_rows(tokc, cd, t + i)
                if sampled:
                    lq = _flp(logits)                        # (B, V)
                    ki = jax.vmap(
                        lambda kk: jax.random.fold_in(kk, i))(kb)
                    d = jax.vmap(jax.random.categorical)(ki, lq)
                    q = jnp.exp(lq)
                else:
                    d = jnp.argmax(logits, axis=-1)
                    q = jnp.zeros_like(logits, jnp.float32)
                d = d.astype(jnp.int32)
                return (d, cd), (d, q)

            (_, caches_d), (drafts, q_all) = lax.scan(
                draft_step, (tok, caches_d), jnp.arange(gamma))
            # cache d_{gamma-1}'s K/V at t+gamma (logits unused): on a
            # fully-accepted round no later write covers that position
            # before draft queries attend it (models/speculative.py's
            # argument, per row here)
            _, caches_d = draft._step_logits_rows(
                drafts[-1], caches_d, t + gamma)

            # target scores [last, d_0..d_{gamma-1}] per row in ONE
            # per-row chunk: logits for positions t+1 .. t+gamma+1
            drafts_b = jnp.swapaxes(drafts, 0, 1)      # (B, gamma)
            chunk = jnp.concatenate([tok[:, None], drafts_b], axis=1)
            if paged:
                logits_t, tstate = model._chunk_logits_paged_rows(
                    chunk, tstate, table, t)
            else:
                logits_t, tstate = model._chunk_logits_rows(
                    chunk, tstate, t)

            if sampled:
                p_all = jnp.exp(_flp(logits_t))    # (B, gamma+1, V)
                q_b = jnp.swapaxes(q_all, 0, 1)    # (B, gamma, V)
                pi = jnp.take_along_axis(
                    p_all[:, :gamma], drafts_b[..., None],
                    axis=2)[..., 0]
                qi = jnp.take_along_axis(
                    q_b, drafts_b[..., None], axis=2)[..., 0]
                ku = jax.vmap(
                    lambda kk: jax.random.fold_in(kk, gamma))(kb)
                u = jax.vmap(
                    lambda kk: jax.random.uniform(kk, (gamma,)))(ku)
                accept = u * qi < pi           # u < p/q without the /0
                n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32),
                                        axis=1), axis=1)
                # residual max(p_n - q_n, 0) normalized; at n == gamma
                # q is all-zero so this IS the bonus draw from p_gamma
                p_n = jnp.take_along_axis(
                    p_all, n[:, None, None], axis=1)[:, 0]
                q_n = jnp.take_along_axis(
                    q_b, jnp.minimum(n, gamma - 1)[:, None, None],
                    axis=1)[:, 0]
                q_n = jnp.where((n < gamma)[:, None], q_n, 0.0)
                res = jnp.clip(p_n - q_n, 0.0, None)
                norm = jnp.sum(res, axis=1, keepdims=True)
                res = jnp.where(norm > 0, res / norm, p_n)
                kc = jax.vmap(
                    lambda kk: jax.random.fold_in(kk, gamma + 1))(kb)
                corr = jax.vmap(jax.random.categorical)(
                    kc, jnp.where(res > 0, jnp.log(res), -jnp.inf))
            else:
                tgt = jnp.argmax(logits_t, axis=-1)  # (B, gamma+1)
                accept = drafts_b == tgt[:, :gamma]
                n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32),
                                        axis=1), axis=1)
                corr = jnp.take_along_axis(tgt, n[:, None],
                                           axis=1)[:, 0]
            corr = corr.astype(jnp.int32)
            slot = jnp.arange(gamma + 1)[None, :]
            ext = jnp.concatenate([drafts_b, drafts_b[:, -1:]],
                                  axis=1)
            emitted = jnp.where(
                slot < n[:, None], ext,
                jnp.where(slot == n[:, None], corr[:, None],
                          0)).astype(jnp.int32)
            return tstate, caches_d, emitted, n, corr, t + n + 1

        def spec_injected(mstate, dstate, tstate, table, caches_d, tok,
                          t, gens):
            with inject_state((model, *mstate), (draft, *dstate)):
                return spec(tstate, table, caches_d, tok, t, gens)

        # the target's arena (caches, or pools) and the draft's caches
        return _arena_jit(spec_injected, "pt_spec_step", (2, 4))

    def _step_spec(self):
        """One speculative round (host side): run the jitted round,
        then append each row's accepted prefix + correction in order —
        budget/eos finishing applies per TOKEN, so a row never emits
        past its budget or beyond eos."""
        if not self.active.any():
            return
        if self._spec_fn is None:
            self._spec_fn = self._build_spec_step()
        was_active = self.active.copy()
        telem = telemetry.enabled()
        if telem:
            _recompile.record("serving.spec_step", self.tok, self.t,
                              weights=self._weights_fp)
            t_dispatch = time.perf_counter()
        with Span("serve.step.dispatch"):
            gens = jnp.asarray(self._slot_gen.astype(np.uint32))
            if self.paged:
                (self.pools, self.caches_d, emitted, n, new_tok,
                 new_t) = self._spec_fn(self._mstate, self._dstate,
                                        self.pools,
                                        jnp.asarray(self.table),
                                        self.caches_d, self.tok, self.t,
                                        gens)
            else:
                (self.caches, self.caches_d, emitted, n, new_tok,
                 new_t) = self._spec_fn(self._mstate, self._dstate,
                                        self.caches, None, self.caches_d,
                                        self.tok, self.t, gens)
        # ONE batched transfer for the round's four host-side scalars
        # (per-array device_get would pay four sync round trips in the
        # serving hot loop)
        with Span("serve.step.fetch"):
            emitted, n_np, new_tok, new_t = jax.device_get(
                (emitted, n, new_tok, new_t))
        self._warmed = True
        now = time.perf_counter()
        self.spec_rounds += 1
        self.spec_row_rounds += int(was_active.sum())
        self.spec_accepted += int(n_np[was_active].sum())
        n_emitted = 0
        with Span("serve.step.emit"):
            for s in range(self.slots):
                if not was_active[s]:
                    continue
                r = self.owner[s]
                for j in range(int(n_np[s]) + 1):
                    self.emitted[s].append(int(emitted[s, j]))
                    r.t_tokens.append(now)
                    n_emitted += 1
                    self.budget[s] -= 1
                    self._maybe_finish(s)
                    if not self.active[s]:
                        break
                if r.stream is not None and r.result is None:
                    r.stream.offer(self.emitted[s], now)
        if telem:
            m = _serving_metrics()
            m["spec_rounds"].inc(int(was_active.sum()))
            m["spec_accepted"].inc(int(n_np[was_active].sum()))
            if self.spec_row_rounds:
                m["spec_accept_rate"].set(
                    self.spec_accepted / self.spec_row_rounds)
            if n_emitted:
                # first SAMPLED slot (same rule as the plain tick)
                spec_ctx = next((c for c in self._slot_trace
                                 if c is not None and c.sampled), None)
                m["tokens"].inc(n_emitted)
                m["decode_latency"].observe(
                    (time.perf_counter() - t_dispatch) / n_emitted,
                    exemplar=(spec_ctx.trace_id
                              if spec_ctx is not None else None))
        # retired rows keep what _maybe_finish left (paged parking);
        # live rows advance by their accepted count + 1
        with Span("serve.step.cursor"):
            keep = was_active & self.active
            self.tok = jnp.asarray(
                np.where(keep, new_tok, np.asarray(self.tok)))
            self.t = jnp.asarray(
                np.where(keep, new_t,
                         np.asarray(self.t)).astype(np.int32))

    def _step(self):
        if self._dl_active:
            # per-decode-tick deadline check (tentpole contract): an
            # expired slot is torn down BEFORE the next dispatch, so
            # no device tick is ever spent on a request nobody is
            # waiting for. Gated on the count — zero per-tick cost
            # while no slot-resident request carries a deadline.
            self._expire_slots()
        if self.draft is not None and not self.degraded:
            return self._step_spec()
        # k == 1 rides the same generalized scan path (length-1 scan,
        # in-device pick — pinned token-identical to the historical
        # host-pick loop by TestMultiStepDecode): ONE epilogue for
        # emit/budget/eos (``_emit_step``) and one key chain, never two
        # copies to keep in lockstep. Which body runs is a fact of the
        # arena, not an option: a paged pool and a draft need the host
        # to set the cursor before the next dispatch
        if self.paged or self.draft is not None:
            return self._step_sync()
        return self._step_multi()

    def _backend(self) -> str:
        """First device's platform, resolved once (sentinel key)."""
        name = getattr(self, "_backend_name", None)
        if name is None:
            devs = jax.devices()
            name = devs[0].platform if devs else "unknown"
            self._backend_name = name
        return name

    def _expire_request(self, r: Request, where: str = "queue") -> None:
        """Drop an expired request TYPED (cause-labeled shed): a done
        record with ``deadline_exceeded`` set and no tokens — the drain
        wire carries the flag so the router fails the ticket with
        :class:`~paddle_tpu.resilience.reliability.DeadlineExceededError`
        instead of inventing a result."""
        reject_cause("deadline")
        r.result = None
        r.deadline_exceeded = True
        r.t_done = time.perf_counter()
        self.done[r.rid] = r
        if r.stream is not None:
            r.stream.fail(_reliability.DeadlineExceededError(
                f"request {r.rid} deadline expired in {where}"))
        if (telemetry.enabled() and r.trace is not None
                and r.trace.sampled):
            _tracing.event("serve.deadline_exceeded", ctx=r.trace,
                           rid=r.rid, where=where)

    def _expire_slots(self) -> None:
        """Tear down every slot-resident request whose deadline passed
        (active slots AND parked chunked-prefill slots)."""
        now = time.time()
        for s in range(self.slots):
            st = self._pf[s]
            r = st["r"] if st is not None else self.owner[s]
            if r is None or r.deadline is None:
                continue
            if now < r.deadline.t_end:
                continue
            self._expire_request(
                r, where="prefill" if st is not None else "decode")
            self._dl_active -= 1
            if st is not None:
                self._pf[s] = None
                self._pf_order.remove(s)
            self.owner[s] = None
            self._slot_trace[s] = None
            self.active[s] = False
            self.emitted[s] = []
            if self.paged and self._slot_pages[s] is not None:
                # freed pages may be REALLOCATED: park the cursor past
                # capacity so the retired slot's stale writes drop
                # (same argument as _maybe_finish's teardown)
                self._allocator.free(self._slot_pages[s])
                self._slot_pages[s] = None
                self.t = self.t.at[s].set(self.capacity)

    def _maybe_finish(self, s: int):
        r = self.owner[s]
        hit_eos = (self.eos_id is not None
                   and self.emitted[s][-1] == self.eos_id)
        if hit_eos or self.budget[s] <= 0:
            r.result = np.asarray(self.emitted[s], np.int32)
            r.t_done = time.perf_counter()
            self.done[r.rid] = r
            if r.stream is not None:
                # remaining un-buffered tokens serve consumer-driven
                # from the completion record; then the typed end mark
                r.stream.finish(r.result, r.t_done)
            if telemetry.enabled():
                _serving_metrics()["completed"].inc()
                if r.trace is not None and r.trace.sampled:
                    _tracing.event("serve.done", ctx=r.trace,
                                   rid=r.rid,
                                   n_tokens=len(r.result),
                                   eos=bool(hit_eos))
            if r.deadline is not None:
                self._dl_active -= 1
            self.owner[s] = None
            self._slot_trace[s] = None
            self.active[s] = False
            self.emitted[s] = []
            if self.paged and self._slot_pages[s] is not None:
                if self.prefix_cache:
                    # register this prompt's page-aligned prefix for
                    # reuse (one registry reference; idempotent when
                    # the key is already present)
                    ps_ = self.page_size
                    m = len(r.prompt) // ps_
                    if m >= 1:
                        key_t = self._prefix_key(r.prompt, m * ps_)
                        if key_t not in self._prefix_registry:
                            pref = self._slot_pages[s][:m]
                            self._allocator.share(pref)
                            self._prefix_registry[key_t] = \
                                np.asarray(pref)
                # freed pages may be REALLOCATED to another request, so
                # the retired slot's stale step-writes must DROP: park
                # its cursor past capacity (write_rows' OOB semantics)
                self._allocator.free(self._slot_pages[s])
                self._slot_pages[s] = None
                self.t = self.t.at[s].set(self.capacity)
