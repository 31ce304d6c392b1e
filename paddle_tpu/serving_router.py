"""Production serving plane: a multi-replica STREAMING router over
``serving.BatchedDecoder`` arenas — the millions-of-users story on top
of the single-replica serving runtime.

The request path is a streaming data plane (the PR 13 rebuild):

- **Per-token streaming.** ``Router.submit(stream=True)`` returns a
  ticket whose :class:`serving.TokenStream` receives tokens the TICK
  they are sampled: the arena offers per-tick, the replica serves them
  as chunked SSE (``POST /stream``, flushed per token, ``X-PT-Trace``
  echoed — PT-LINT-307), and a per-request fan-in pump forwards them
  into the client's bounded buffer. The FIRST token stamps the same
  TTFT histogram the non-streaming path uses, so streaming vs not is
  one bench column apart; client stalls pause only that client's
  stream (backpressure never reaches the arena tick loop). A replica
  death mid-stream surfaces a typed ``resume`` record on the SAME
  trace id — already-delivered tokens stay valid (greedy re-decode is
  deterministic; the pump dedupes by token index) — and an all-down
  fleet surfaces a typed ``error`` record: a client NEVER sees a
  silent stall.

- **Replica-PULL dispatch (work stealing).** Admitted tickets land on
  ONE central dispatch queue; ready replicas pull from it (a lane per
  replica) whenever they have slot headroom. A warming/slow replica
  simply pulls less — nothing is parked on it by a stale placement
  guess — and queue depth/wait becomes the shed signal
  (:class:`SLOPolicy` reads the MEASURED dispatch-queue wait). A
  replica death re-QUEUES its in-flight tickets rather than
  re-placing them. ``dispatch="push"`` keeps the PR 10 least-loaded
  push path for A/B (the bench gates pull's p99 win under one slow
  replica).

- **Prefix-hash routing.** Tickets carry a rolling hash of their
  first-N prompt tokens; fleets of sessions sharing a system prompt
  hash alike and land where that prefix's KV pages already live (the
  arena's prefix cache) — a SOFT pull-queue hint: the prefix's home
  replica claims it first, a STARVING replica steals it
  (``pt_router_steals_total``) and becomes the new home. Session
  affinity stays the STRONG hint (never stolen while the home is
  placeable) and both tables are LRU-bounded (the PR 10 unbounded
  ``_affinity`` leak is closed).

Plus the PR 10 levers, unchanged in spirit:

- **Prefill/decode disaggregation.** Dedicated prefill workers run the
  bucketed prefill and hand the resulting KV pages (float or int8
  ``QuantizedPool`` pages alike) to a decode replica as a
  :class:`serving.KVHandoff` — whole-prompt admission never stalls a
  decode tick. Chunked prefill remains the single-replica fallback;
  the router only disaggregates prompts past ``disagg_min_tokens``.

- **SLO-aware admission + load shedding.** An :class:`SLOPolicy` fed
  by the router's live in-flight count and the observed TTFT EWMA
  degrades first (``BatchedDecoder.set_degraded``: decode_steps→1,
  speculative rounds off) and SHEDS before p99 TTFT blows through
  target — shed admissions bump the cause-labeled
  ``pt_serving_admission_rejections_total{cause="shed"}`` next to the
  arena's own ``pool_exhausted`` series.

Resilience: a replica that dies mid-stream (health-check failures or a
dispatch error — chaos point ``router.dispatch``) has its in-flight
requests retried on a surviving replica; requests are only lost to a
typed :class:`NoReplicasError` when EVERY replica is down.

Process bring-up: ``python -m paddle_tpu.serving_router --worker``
runs one replica/prefill worker (model from ``--spec module:fn``);
:func:`spawn_replicas` forks N of them; ``python -m paddle_tpu.launch
--serve`` is the one-command front end.

Green-field vs the reference (its serving is a one-request-at-a-time
predictor per process; cross-replica routing/disaggregation is the
modern LM-serving analog of its multi-instance deployment story).
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from . import telemetry
from .core.enforce import EnforceError, enforce
from .resilience import reliability as _reliability
from .serving import (ArenaLostError, BatchedDecoder, KVHandoff,
                      TokenStream, reject_cause)
from .telemetry import server as _dbg_server
from .telemetry import tracing as _tracing
from .telemetry.trace import Span

_NULL_CM = contextlib.nullcontext()


def _trace_headers(base: Dict[str, str]) -> Dict[str, str]:
    """Stamp the bound trace context onto outbound HTTP headers — the
    ONE helper every cross-process hop in this file rides (pt-lint
    PT-LINT-306 flags HTTP POSTs here that skip it). No-op when
    telemetry is off or no sampled context is bound. The bound
    end-to-end deadline rides the SAME helper (``X-PT-Deadline`` beside
    ``X-PT-Trace``) — but deadlines are a CORRECTNESS header, stamped
    whether or not telemetry is on."""
    if telemetry.enabled():
        ctx = _tracing.current()
        if ctx is not None and ctx.sampled:
            base[_tracing.TRACE_HEADER] = ctx.to_header()
    dl = _reliability.current()
    if dl is not None:
        base[_reliability.DEADLINE_HEADER] = dl.to_header()
    return base

__all__ = ["Router", "SLOPolicy", "LocalReplica", "HttpReplica",
           "Ticket", "NoReplicasError", "RequestShedError",
           "prefix_hash", "spawn_replicas", "serve_main", "main"]


def prefix_hash(prompt, n: int) -> Optional[int]:
    """Rolling hash of the first ``n`` prompt tokens — the prefix-hash
    routing key. Prompts sharing their first-n tokens (a fleet of
    sessions on one system prompt) hash alike and route to the replica
    whose prefix-cache pages already hold that prefix. ``None`` for
    prompts shorter than ``n``: too short to carry a shared system
    prompt, and a short-prefix collision would fake affinity."""
    p = np.asarray(prompt).reshape(-1)
    if len(p) < n:
        return None
    h = 0
    for t in p[:n]:
        h = (h * 1000003 + int(t)) & 0xFFFFFFFFFFFFFFFF
    return h


class _LRU:
    """Bounded touch-ordered map (session-affinity and prefix-home
    tables): ``get`` touches, ``set`` past the cap evicts the
    least-recently-used entry — the PR 10 unbounded ``Router._affinity``
    growth closed at the type. Not thread-safe on its own; callers hold
    the router lock."""

    def __init__(self, cap: int):
        enforce(cap >= 1, "LRU cap must be >= 1, got %s", cap)
        self.cap = int(cap)
        self._d: "OrderedDict[Any, Any]" = OrderedDict()

    def get(self, key, default=None):
        v = self._d.get(key, default)
        if key in self._d:
            self._d.move_to_end(key)
        return v

    def set(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)

    def pop(self, key, default=None):
        return self._d.pop(key, default)

    def items(self):
        return list(self._d.items())

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)


def _swallow(fn, *args) -> None:
    """Run a fire-and-forget call, discarding any outcome (the hedge
    loser's best-effort cancel: a wedged loser may time out — that
    must never surface anywhere)."""
    try:
        fn(*args)
    except Exception:
        pass


def _is_timeout_error(e: BaseException) -> bool:
    """Gray-vs-dead discriminator for transport errors: a TIMEOUT
    (socket accepted, then silence — the SIGSTOP/GC-stall signature)
    feeds the circuit breaker; anything else (connection refused,
    reset) is the plain-death path. urllib wraps socket timeouts in
    URLError, so check ``.reason`` too."""
    if isinstance(e, TimeoutError):
        return True
    return isinstance(getattr(e, "reason", None), TimeoutError)


class NoReplicasError(EnforceError):
    """Every replica is down (or none was ever ready): the one
    condition under which the router LOSES a request. Anything short
    of this retries on a survivor."""


class RequestShedError(EnforceError):
    """Raised (opt-in, ``submit(raise_on_shed=True)``) when the SLO
    policy sheds the admission; default is a ``Ticket`` with
    ``shed=True`` so open-loop callers count sheds without exception
    overhead."""


@telemetry.cached_instruments
def _router_metrics(reg):
    return {
        "requests": reg.counter(
            "pt_router_requests_total", "requests routed"),
        "shed": reg.counter(
            "pt_router_shed_total",
            "admissions shed by the SLO policy"),
        "retries": reg.counter(
            "pt_router_retries_total",
            "in-flight requests re-dispatched after a replica "
            "failure"),
        "replica_deaths": reg.counter(
            "pt_router_replica_deaths_total",
            "replicas marked dead by the health loop"),
        "disagg": reg.counter(
            "pt_router_disagg_prefills_total",
            "prompts prefilled on a dedicated worker and handed "
            "off as KV pages"),
        "healthy": reg.gauge(
            "pt_router_replicas_healthy", "replicas alive and ready"),
        "degraded": reg.gauge(
            "pt_router_degraded",
            "1 while the SLO policy holds the fleet degraded"),
        "ttft": reg.histogram(
            "pt_router_ttft_seconds",
            "router-side submit-to-first-token latency", unit="s"),
        "queue_wait": reg.histogram(
            "pt_router_dispatch_wait_seconds",
            "router submit-to-replica-dispatch wait", unit="s"),
        "queue_depth": reg.gauge(
            "pt_router_dispatch_queue_depth",
            "tickets waiting on the central pull-dispatch queue — "
            "the shed signal"),
        "steals": reg.counter(
            "pt_router_steals_total",
            "pull dispatches where a starving replica took a ticket "
            "hinted at another replica (work stealing)"),
        "itl": reg.histogram(
            "pt_router_itl_seconds",
            "router-side inter-token latency under streaming "
            "(gap between consecutive streamed tokens)", unit="s"),
        "prefix_ratio": reg.gauge(
            "pt_router_prefix_cache_hit_ratio",
            "fleet prefix-cache hit rate: sum(prefix hits) / "
            "sum(prefix lookups) over live replicas' pool stats"),
        # mode-labeled cold-start split (the reject_cause idiom):
        # aot = trace-free boot from a serialized artifact, traced =
        # ordinary trace path, traced_fallback = an artifact was asked
        # for but rejected (fingerprint/load) and the trace path ran
        "cold_starts": {
            mode: reg.counter(
                "pt_aot_cold_starts_total",
                "serving replica cold starts by boot mode",
                labels={"mode": mode})
            for mode in ("aot", "traced", "traced_fallback")},
        # -- reliability plane (Router(reliability=...)) ----------------
        "deadline_exceeded": reg.counter(
            "pt_deadline_exceeded_total",
            "requests dropped router-side because their end-to-end "
            "deadline expired (pre-dispatch, on requeue, or reported "
            "back by a replica)"),
        "retry_budget_exhausted": reg.counter(
            "pt_retry_budget_exhausted_total",
            "request failures surfaced UN-retried: the retry token "
            "bucket was dry (retry-storm brake)"),
        "hedges": {
            won: reg.counter(
                "pt_hedges_total",
                "hedged dispatches by outcome (won=true: the hedge's "
                "result completed the request before the primary's)",
                labels={"won": won})
            for won in ("true", "false")},
        "quarantines": reg.counter(
            "pt_replica_quarantines_total",
            "replicas quarantined by the gray-failure circuit "
            "breaker (left placement but kept draining)"),
    }


# ---------------------------------------------------------------------------
# SLO policy
# ---------------------------------------------------------------------------

class SLOPolicy:
    """Deadline/queue-depth admission policy.

    Decision inputs: ``in_flight`` (router-tracked dispatched+queued
    requests), ``slots`` (live replica capacity), and a wait estimate.
    Two ladders, most-degraded wins:

    - load factor = in_flight / slots: ``>= degrade_at`` → degrade
      (decode_steps=1, spec off), ``>= shed_at`` → shed. Queue growth
      is the EARLY signal — it predicts TTFT before TTFT blows.
    - ``target_ttft_s`` (optional): a wait estimate past the target →
      shed; past half the target → degrade. Under PULL dispatch the
      estimate is ``queue_wait_s`` — the MEASURED dispatch-queue wait
      EWMA (a queue property, not a placement guess); the legacy push
      path estimates load factor x observed TTFT EWMA.

    Pure function of its inputs (no clock, no I/O) — the unit tests pin
    the ladder deterministically."""

    def __init__(self, target_ttft_s: Optional[float] = None,
                 degrade_at: float = 1.5, shed_at: float = 3.0,
                 classes: Optional[Dict[str, "SLOPolicy"]] = None,
                 deadline_s: Optional[float] = None):
        enforce(shed_at >= degrade_at,
                "shed_at %s < degrade_at %s (shedding is the deeper "
                "degradation)", shed_at, degrade_at)
        self.target_ttft_s = target_ttft_s
        self.degrade_at = float(degrade_at)
        self.shed_at = float(shed_at)
        # per-class END-TO-END deadline budget (reliability plane):
        # requests admitted under this class get a Deadline minted with
        # this budget; None defers to the ReliabilityConfig default
        # (deadline_s, else deadline_factor x target_ttft_s)
        self.deadline_s = deadline_s
        # per-model SLO classes (multi-model routing): model id ->
        # its own policy; unlisted models (and untagged requests) use
        # THIS policy's ladder as the fleet-wide default
        for m, p in (classes or {}).items():
            enforce(isinstance(p, SLOPolicy),
                    "SLO class for model %r must be an SLOPolicy, "
                    "got %s", m, type(p).__name__)
        self.classes: Dict[str, "SLOPolicy"] = dict(classes or {})

    def resolve(self, model: Optional[str]) -> "SLOPolicy":
        """The policy governing ``model``'s admissions: its registered
        SLO class, else this (fleet-default) policy."""
        if model is not None:
            got = self.classes.get(model)
            if got is not None:
                return got
        return self

    def admit(self, in_flight: int, slots: int,
              ewma_ttft_s: Optional[float] = None,
              queue_wait_s: Optional[float] = None) -> str:
        """-> "admit" | "degrade" | "shed" for one arriving request.
        ``queue_wait_s`` (the measured dispatch-wait EWMA) wins over
        the ``ewma_ttft_s`` load-factor estimate when both are given."""
        if slots <= 0:
            return "shed"
        lf = in_flight / slots
        est = (queue_wait_s if queue_wait_s is not None
               else lf * ewma_ttft_s if ewma_ttft_s else None)
        if lf >= self.shed_at or (
                self.target_ttft_s and est is not None
                and est > self.target_ttft_s):
            return "shed"
        if lf >= self.degrade_at or (
                self.target_ttft_s and est is not None
                and est > 0.5 * self.target_ttft_s):
            return "degrade"
        return "admit"


# ---------------------------------------------------------------------------
# Replicas
# ---------------------------------------------------------------------------

class LocalReplica:
    """One in-process replica: a :class:`serving.BatchedDecoder` driven
    by a background serve thread (admit → prefill tick → step, exactly
    ``run()``'s loop body) with a lock around every arena touch, so
    router dispatch threads and the serve loop interleave safely: a
    caller waits out the tick it arrives in and holds the lock before
    the next one starts (``_locked``).

    Also the PREFILL-worker form: a replica that only ever receives
    :meth:`prefill` calls ticks nothing and just runs bucketed prefills
    under the same lock. ``warmup()`` drives one tiny request to
    compile the step + prefill bucket before the replica reports
    ready.

    Each in-process replica needs its OWN model instance (same seed =
    identical weights): the jitted arena passes weights via
    ``inject_state``, which temporarily rebinds the model's parameters
    — two replicas tracing one shared model from different threads
    would leak tracers into each other. Worker processes get this
    isolation for free."""

    def __init__(self, decoder: BatchedDecoder, name: str = "replica0",
                 idle_s: float = 0.002, model: Optional[str] = None):
        self.decoder = decoder
        self.name = name
        # model tag (multi-model routing): tagged tickets only place on
        # replicas serving their model; None = the single-model fleet
        self.model = model
        self.idle_s = idle_s
        self._mu = threading.RLock()
        # the handoff: callers waiting for ``_mu`` are counted, and the
        # serve loop starts no tick while one of them still waits
        self._callers_mu = threading.Lock()
        self._callers = 0
        self._no_callers = threading.Event()
        self._no_callers.set()
        self._done: Dict[int, Dict[str, Any]] = {}
        # replica-side per-request token streams (stream=True submits)
        # keyed by rid until the router's fan-in pump claims them;
        # bounded so an abandoned stream can't leak forever
        self._streams: Dict[int, TokenStream] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @contextlib.contextmanager
    def _locked(self, who: str):
        """Hold ``_mu``; the program span ``replica.lock_wait.<who>``
        covers the wait for it on the caller's thread, not the hold.
        For callers only. A caller is counted while it waits, and
        ``_loop`` starts no tick until the count is back at zero: the
        loop retakes a lock it has just dropped within a microsecond,
        long before a woken caller can, so without the count a caller
        gets in once in several ticks, and by chance."""
        with self._callers_mu:
            self._callers += 1
            self._no_callers.clear()
        try:
            with Span("replica.lock_wait." + who):
                self._mu.acquire()
        finally:
            with self._callers_mu:
                self._callers -= 1
                if not self._callers:
                    self._no_callers.set()
        try:
            yield
        finally:
            self._mu.release()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "LocalReplica":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"pt-replica-{self.name}")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def close(self) -> None:
        self.stop()

    def warmup(self, vocab_hint: int = 8) -> None:
        """Warm the replica BEFORE it reports ready, so the router
        never places a real session onto a cold jit cache: one 1-token
        request to completion compiles the prefill bucket + activation
        (a max_new=1 request finishes AT activation), then the
        EXPLICIT :meth:`serving.BatchedDecoder.warm_step` compiles and
        dispatches the arena step executable over the idle arena — no
        sacrificial decode tick (the old max_new=2 workaround)."""
        rid = self.submit(np.asarray([1, min(2, vocab_hint - 1)],
                                     np.int32), 1)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if rid in self.drain_results(keep=True):
                break
            if self._thread is None:  # not started: tick inline
                with self._locked("other"):
                    self._tick_locked()
            else:
                time.sleep(0.005)
        else:
            raise EnforceError(f"replica {self.name} warmup timed out")
        with self._locked("other"):
            self.decoder.warm_step()

    # -- serving API (router-facing) ----------------------------------------

    def _register_stream(self, rid: int, ts: TokenStream) -> None:
        self._streams[rid] = ts
        if len(self._streams) > 1024:  # abandoned-stream bound
            # prefer evicting streams that already ended (claimed-or-
            # finished leftovers); a LIVE unclaimed stream goes only
            # when the map is full of live ones — and then with the
            # typed error record so a late open_stream/holder sees a
            # failure, never a silent downgrade
            for rid_old in list(self._streams):
                old = self._streams[rid_old]
                if old.closed or old.done:
                    del self._streams[rid_old]
                    if len(self._streams) <= 1024:
                        return
            while len(self._streams) > 1024:
                rid_old = next(iter(self._streams))
                self._streams.pop(rid_old).fail(EnforceError(
                    f"stream for rid {rid_old} evicted: replica "
                    f"stream registry overflow (unclaimed streams)"))

    def submit(self, prompt, max_new: int,
               session: Optional[str] = None,
               stream: bool = False) -> int:
        with self._locked("submit"):
            if not stream:
                return self.decoder.submit(prompt, max_new)
            ts = TokenStream()
            rid = self.decoder.submit(prompt, max_new, stream=ts)
            self._register_stream(rid, ts)
            return rid

    def inject(self, handoff: KVHandoff, max_new: int,
               session: Optional[str] = None,
               stream: bool = False) -> int:
        with self._locked("submit"):
            if not stream:
                return self.decoder.inject_prefilled(handoff, max_new)
            ts = TokenStream()
            rid = self.decoder.inject_prefilled(handoff, max_new,
                                                stream=ts)
            self._register_stream(rid, ts)
            return rid

    def open_stream(self, rid: int):
        """Claim the replica-side token stream for ``rid`` (one
        consumer per stream) — an iterator of token/control records.
        Typed error when no stream was registered for the rid."""
        with self._locked("other"):
            ts = self._streams.pop(rid, None)
        enforce(ts is not None,
                "no token stream registered for rid %s on replica %s",
                rid, self.name)
        return iter(ts)

    def prefill(self, prompt) -> KVHandoff:
        with self._locked("other"):
            return self.decoder.prefill_export(prompt)

    def drain_results(self, keep: bool = False) -> Dict[int, Dict]:
        """Completed requests since the last drain:
        ``{rid: {tokens, ttft_s, itl_p99_s, t_first, t_done}}``.
        ``keep=True`` peeks without consuming (warmup)."""
        with self._locked("drain"):
            out = dict(self._done)
            if not keep:
                self._done.clear()
            return out

    def cancel(self, rid: int) -> bool:
        """Best-effort cancel (the hedge loser's path): drop ``rid``
        from the arena queue if it has not been admitted to a slot yet
        — an admitted request runs to completion and its result is
        simply discarded (greedy decode is bounded by max_new, so the
        waste is bounded too). Returns True when dequeued."""
        with self._locked("other"):
            q = self.decoder.queue
            for i, r in enumerate(q):
                if r.rid == rid:
                    del q[i]
                    return True
        return False

    def set_degraded(self, on: bool) -> None:
        with self._locked("other"):
            self.decoder.set_degraded(on)

    def _require_arena(self) -> None:
        """A decoder whose arena a failed program consumed serves
        nothing more: the probe (``healthz`` in process, ``/load`` over
        HTTP) raises, the router counts the replica dead after
        ``health_fails`` probes and places what it held elsewhere."""
        if self.decoder.arena_lost:
            raise ArenaLostError(
                f"replica {self.name}: the serving arena was lost")

    def healthz(self) -> Dict[str, Any]:
        self._require_arena()
        return {"status": "ok", "ready": self.decoder.ready,
                "pid": os.getpid()}

    def load(self) -> Dict[str, Any]:
        self._require_arena()
        d = self.decoder
        with self._locked("other"):
            out = {"queue_depth": len(d.queue),
                   "active_slots": int(d.active.sum()),
                   "prefilling": len(d._pf_order),
                   "slots": d.slots}
            if d.paged:
                out["free_pages"] = d._allocator.free_pages
                if d.prefix_cache:
                    # the pool-stat truth the router's fleet hit-rate
                    # gauge is counter-verified against
                    out["prefix_hits"] = d.prefix_hits
                    out["prefix_lookups"] = d.prefix_lookups
            return out

    # -- serve loop ---------------------------------------------------------

    def _tick_locked(self) -> bool:
        """One serving tick (caller holds the lock). Returns True when
        any work happened (idle loops back off otherwise). A busy tick
        is the program span ``serve.tick``, tiled by the arena's phase
        spans and ``replica.harvest``."""
        d = self.decoder
        busy = bool(d.queue or d._pf_order or d.active.any())
        if not busy:
            return False
        with Span("serve.tick", n_active=int(d.active.sum()),
                  queued=len(d.queue)):
            from .resilience import faults as _faults
            inj = _faults.active()
            if inj is not None:
                # chaos point replica.wedge: a delay_s rule freezes
                # THIS serve tick — the in-process stand-in for SIGSTOP
                # (only fired while busy, so the idle loop doesn't burn
                # the schedule clock)
                inj.fire("replica.wedge", path=self.name)
            d._tick()
            if d.done:
                with Span("replica.harvest"):
                    self._harvest_locked()
        return True

    def _harvest_locked(self) -> None:
        """Move the arena's finished requests into ``_done`` records
        (caller holds the lock)."""
        d = self.decoder
        for rid, r in d.done.items():
            if getattr(r, "deadline_exceeded", False) \
                    or r.result is None:
                # expired in the arena (queue/prefill/decode sweep):
                # the record carries the typed cause, never a fake
                # token list
                self._done[rid] = {
                    "tokens": None, "ttft_s": None,
                    "itl_p99_s": None, "t_first": r.t_first,
                    "t_done": r.t_done, "n_tokens": 0,
                    "deadline_exceeded": True,
                }
                continue
            ts = r.t_tokens
            itl = np.diff(ts) if len(ts) > 1 else np.asarray([0.0])
            self._done[rid] = {
                "tokens": r.result,
                "ttft_s": r.t_first - r.t_submit,
                "itl_p99_s": float(np.quantile(itl, 0.99)),
                "t_first": r.t_first, "t_done": r.t_done,
                "n_tokens": len(r.result),
            }
        d.done.clear()

    def _loop(self) -> None:
        while not self._stop.is_set():
            # callers first: whoever waited out the tick has ``_mu``
            # before the next one starts (the limit only bounds what a
            # caller stuck behind another caller's long hold can cost)
            self._no_callers.wait(1.0)
            with self._mu:
                busy = self._tick_locked()
            if not busy:
                time.sleep(self.idle_s)


class HttpReplica:
    """Client handle for one replica WORKER PROCESS (the
    ``--worker`` CLI below): the serving API over the worker's debug
    server port — ``/healthz``/``/readyz``/``/statusz`` for placement,
    POST ``/submit`` ``/inject`` ``/prefill`` ``/drain`` ``/config``
    for the data path. Transport errors raise ``OSError`` — the
    router's failover signal."""

    def __init__(self, url: str, name: Optional[str] = None,
                 timeout_s: float = 60.0,
                 proc: Optional[subprocess.Popen] = None,
                 model: Optional[str] = None):
        self.url = url.rstrip("/")
        self.name = name or url
        self.model = model  # multi-model routing tag (see LocalReplica)
        self.timeout_s = timeout_s
        self.proc = proc  # when spawn_replicas owns the process

    def _get(self, path: str) -> Dict[str, Any]:
        with urllib.request.urlopen(self.url + path,
                                    timeout=self.timeout_s) as r:
            return json.loads(r.read().decode())

    def _post(self, path: str, body: bytes,
              ctype: str = "application/json") -> bytes:
        req = urllib.request.Request(
            self.url + path, data=body, method="POST",
            headers=_trace_headers({"Content-Type": ctype}))
        try:
            with urllib.request.urlopen(req,
                                        timeout=self.timeout_s) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            # 400 = the handler rejected the REQUEST (typed enforce
            # error worker-side); surface it as such, not as replica
            # death
            detail = e.read().decode(errors="replace")
            raise EnforceError(
                f"replica {self.name} rejected {path}: {detail}") \
                from None

    def _post_json(self, path: str, obj: Any) -> Dict[str, Any]:
        return json.loads(self._post(
            path, json.dumps(obj).encode()).decode())

    def submit(self, prompt, max_new: int,
               session: Optional[str] = None,
               stream: bool = False) -> int:
        out = self._post_json("/submit", {
            "prompt": np.asarray(prompt, np.int32).tolist(),
            "max_new": int(max_new), "stream": bool(stream)})
        return int(out["rid"])

    def inject(self, handoff: KVHandoff, max_new: int,
               session: Optional[str] = None,
               stream: bool = False) -> int:
        # wire layout: 8-byte big-endian max_new, 1 flag byte (bit 0 =
        # stream), then the npz payload (the npz body is opaque bytes;
        # scalars can't ride inside it without a second parse, and the
        # stdlib handler drops query strings before dispatch)
        body = (int(max_new).to_bytes(8, "big")
                + bytes([1 if stream else 0]) + handoff.to_bytes())
        out = json.loads(self._post(
            "/inject", body, "application/octet-stream").decode())
        return int(out["rid"])

    def open_stream(self, rid: int):
        """Generator over the worker's ``POST /stream`` SSE events —
        one token/control record per ``data:`` line, delivered as the
        worker flushes them (per-token). The trace header rides the
        request (PT-LINT-306) so replica-side stream spans stay on the
        request's trace."""
        req = urllib.request.Request(
            self.url + "/stream",
            data=json.dumps({"rid": int(rid)}).encode(),
            method="POST",
            headers=_trace_headers(
                {"Content-Type": "application/json"}))

        def gen():
            with urllib.request.urlopen(req,
                                        timeout=self.timeout_s) as r:
                for line in r:
                    line = line.strip()
                    if line.startswith(b"data: "):
                        yield json.loads(line[6:].decode())

        return gen()

    def prefill(self, prompt) -> KVHandoff:
        body = self._post("/prefill", json.dumps({
            "prompt": np.asarray(prompt, np.int32).tolist()}).encode())
        return KVHandoff.from_bytes(body)

    def drain_results(self) -> Dict[int, Dict]:
        out = self._post_json("/drain", {})
        # tokens=None marks a replica-side deadline expiry (typed
        # record, never a fake token list) — keep it None, don't cast
        return {int(rid): {**rec, "tokens": (
            np.asarray(rec["tokens"], np.int32)
            if rec.get("tokens") is not None else None)}
            for rid, rec in out["done"].items()}

    def cancel(self, rid: int) -> bool:
        """Best-effort cancel of a queued request (hedge loser)."""
        out = self._post_json("/cancel", {"rid": int(rid)})
        return bool(out.get("cancelled"))

    def set_degraded(self, on: bool) -> None:
        self._post_json("/config", {"degraded": bool(on)})

    def healthz(self) -> Dict[str, Any]:
        return self._get("/healthz")

    def load(self) -> Dict[str, Any]:
        # the dedicated lightweight endpoint — the health poll hits
        # this tens of times a second, and the full /statusz renders
        # device inventory + recompile report per scrape
        return self._post_json("/load", {})

    def close(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------

class Ticket:
    """One routed request. ``shed=True`` = never dispatched (SLO
    policy); otherwise ``wait()``/``Router.wait`` fills ``tokens`` and
    the latency fields, or ``error`` when every replica died.

    Streaming (``Router.submit(stream=True)``): ``stream`` is the
    client-side :class:`serving.TokenStream` — tokens arrive as the
    arena samples them, control records mark retries (``resume``) and
    terminal failure (``error``), and ``ttft_s`` is stamped from the
    FIRST streamed token (the streaming TTFT edge) instead of the
    completion record."""

    def __init__(self, rid: int, prompt, max_new: int,
                 session: Optional[str],
                 model: Optional[str] = None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.session = session
        self.model = model  # model-id routing key (None = any replica)
        self.trace = None  # TraceContext minted at admission
        self.shed = False
        self.t_submit = time.perf_counter()
        self.t_dispatched = 0.0
        self.replica: Optional[str] = None
        self.replica_rid: Optional[int] = None
        self.retries = 0
        self.disaggregated = False
        self.stolen = False  # pull dispatch ignored a placement hint
        self.prefix: Optional[int] = None  # prefix-hash routing key
        # reliability plane: end-to-end deadline minted at admission
        # (None = unbudgeted), and hedged-dispatch state — the hedge's
        # (replica, rid) pair so the first result wins and the loser's
        # in-flight entry can be dropped + best-effort cancelled
        self.deadline = None
        self.hedged = False
        self.hedge_replica: Optional[str] = None
        self.hedge_rid: Optional[int] = None
        self.stream: Optional[TokenStream] = None  # client-side sink
        self.t_first_stream: Optional[float] = None
        self._stream_next = 0  # next token index to deliver (dedupe
        self._pump_gen = 0     # across retries) / live pump generation
        self.tokens: Optional[np.ndarray] = None
        self.ttft_s: Optional[float] = None
        self.itl_p99_s: Optional[float] = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()

    @property
    def ok(self) -> bool:
        return self.tokens is not None

    def wait(self, timeout: Optional[float] = None) -> "Ticket":
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} still in flight after {timeout}s "
                f"(replica={self.replica})")
        if self.error is not None:
            raise self.error
        return self


class _ReplicaState:
    def __init__(self, replica):
        self.replica = replica
        self.name = replica.name
        self.model = getattr(replica, "model", None)
        self.alive = True
        self.ready = False
        # draining: retiring under the autoscaler — placement stops
        # IMMEDIATELY (fail-closed: affinity/prefix hints are purged
        # the moment the flag flips) but in-flight work keeps draining
        # on the same trace ids; removed: the terminal state, its pull
        # lanes exit
        self.draining = False
        self.removed = False
        # quarantined: the gray-failure breaker tripped — placement
        # stops exactly like draining (fail-closed), but the state is
        # REVERSIBLE: a successful half-open probe returns the replica
        # to rotation. In-flight work keeps draining meanwhile.
        self.quarantined = False
        self.claimed = 0  # pulled off the queue, not yet registered
        self.fails = 0
        self.load: Dict[str, Any] = {"queue_depth": 0,
                                     "active_slots": 0, "slots": 1}
        self.inflight: Dict[int, Ticket] = {}  # replica_rid -> ticket
        # results drained before their dispatcher registered the rid
        # (the fast-completion race) park here until the registration
        # catches up; bounded, insertion-ordered (oldest evicted)
        self.orphans: Dict[int, Dict] = {}


class Router:
    """Spread sessions over N replicas; health-check, shed, fail over.

    ``replicas``: :class:`LocalReplica` / :class:`HttpReplica` handles
    (started/spawned by the caller — the router routes, it does not own
    model processes unless asked to ``close(replicas=True)``).
    ``prefill_workers``: replicas whose only job is
    :meth:`~LocalReplica.prefill`; prompts of at least
    ``disagg_min_tokens`` tokens are prefilled there and handed off as
    KV pages. ``policy``: an :class:`SLOPolicy` (None = admit always).

    Submission is NON-blocking (open-loop): ``submit`` sheds or
    enqueues; dispatcher threads place the request (running the
    disaggregated prefill when eligible); a poll loop drains completed
    results and health-checks replicas, retrying the in-flight load of
    a dead replica on the survivors."""

    def __init__(self, replicas: Sequence, prefill_workers: Sequence = (),
                 policy: Optional[SLOPolicy] = None,
                 session_affinity: bool = True,
                 disagg_min_tokens: Optional[int] = 64,
                 poll_interval_s: float = 0.05,
                 health_fails: int = 2,
                 dispatchers: Optional[int] = None,
                 max_in_flight: Optional[int] = None,
                 trace_sample: Optional[float] = None,
                 textfile_path: Optional[str] = None,
                 textfile_interval_s: float = 5.0,
                 dispatch: str = "pull",
                 pull_lanes: int = 2,
                 steal_age_s: float = 0.05,
                 affinity_max_sessions: int = 4096,
                 prefix_hash_tokens: Optional[int] = 64,
                 prefix_homes_max: int = 4096,
                 stream_buffer: int = 256,
                 reliability=None):
        enforce(len(replicas) >= 1, "router needs >= 1 replica")
        enforce(dispatch in ("pull", "push"),
                'dispatch must be "pull" (work-stealing replica pull) '
                'or "push" (legacy least-loaded placement), got %r',
                dispatch)
        enforce(prefix_hash_tokens is None or prefix_hash_tokens >= 1,
                "prefix_hash_tokens must be None or >= 1, got %s",
                prefix_hash_tokens)
        # reliability plane (deadlines / retry budget / hedging /
        # quarantine): OFF by default — self._rel is None and the hot
        # path keeps only `is None` checks (the telemetry-off
        # discipline, pinned by the zero-cost tripwire test).
        # Accepts True (defaults), a ReliabilityConfig, or a
        # pre-built ReliabilityPlane.
        if reliability is None or reliability is False:
            self._rel = None
        elif reliability is True:
            self._rel = _reliability.ReliabilityPlane()
        elif isinstance(reliability, _reliability.ReliabilityPlane):
            self._rel = reliability
        elif isinstance(reliability, _reliability.ReliabilityConfig):
            self._rel = _reliability.ReliabilityPlane(reliability)
        else:
            raise EnforceError(
                "reliability= must be None/False, True, a "
                "ReliabilityConfig, or a ReliabilityPlane, got "
                f"{type(reliability).__name__}")
        self._replicas: Dict[str, _ReplicaState] = {}
        for r in replicas:
            enforce(r.name not in self._replicas,
                    "duplicate replica name %r", r.name)
            self._replicas[r.name] = _ReplicaState(r)
        # multi-model fleet: the model tags present across replicas —
        # submit(model=) is validated against this set so a typo'd
        # model id fails typed at admission, not as a forever-parked
        # ticket no replica will ever claim
        self._models = sorted({st.model
                               for st in self._replicas.values()
                               if st.model is not None})
        self._prefill = list(prefill_workers)
        self._pf_rr = 0
        self.policy = policy
        self.session_affinity = session_affinity
        self.disagg_min_tokens = disagg_min_tokens
        self.poll_interval_s = poll_interval_s
        self.health_fails = int(health_fails)
        # hard queue-depth cap, independent of the SLO policy: past it
        # admissions reject with cause="capacity" (the policy's
        # load-factor shed keeps cause="shed" — the /metrics split)
        self.max_in_flight = max_in_flight
        # head-based trace sampling for requests admitted HERE (None =
        # the process-wide telemetry.tracing rate, default 1.0); the
        # decision rides the context to every replica/worker hop
        self.trace_sample = trace_sample
        # node-exporter textfile sink: the poll loop re-writes the
        # whole registry (pt_router_* included) every
        # textfile_interval_s — the scrape-less deployment path
        # (env PT_ROUTER_TEXTFILE works for the CLI bring-up)
        self._textfile = (textfile_path
                          or os.environ.get("PT_ROUTER_TEXTFILE"))
        self._textfile_interval_s = float(textfile_interval_s)
        self._textfile_t = 0.0
        self._mu = threading.RLock()
        # LRU-bounded placement-hint tables (the PR 10 unbounded
        # _affinity leak): sessions evict least-recently-touched past
        # the cap, and replica death drops its entries
        self._affinity = _LRU(affinity_max_sessions)
        self._prefix_home = _LRU(prefix_homes_max)
        self.prefix_hash_tokens = prefix_hash_tokens
        self.stream_buffer = int(stream_buffer)
        self._dispatch_mode = dispatch
        # a steal waits this long before ignoring a soft hint: fresh
        # tickets get their warm home a beat to claim them; anything
        # older (incl. requeues after a death, whose submit stamp is
        # old by construction) is immediately stealable
        self.steal_age_s = float(steal_age_s)
        self._tickets: Dict[int, Ticket] = {}
        self._next_rid = 0
        self._queued = 0            # accepted, not yet dispatched
        # per-model split of _queued (multi-model SLO ladders read
        # their own model's queue pressure, not the fleet total)
        self._queued_by: Dict[str, int] = {}
        self._degraded = False
        self._degraded_by: Dict[Optional[str], bool] = {}
        self._ewma_ttft: Optional[float] = None
        self._ewma_wait: Optional[float] = None  # dispatch-queue wait
        self._shed_count = 0
        self._served_count = 0
        self._retry_count = 0
        self._steal_count = 0
        self._stop = threading.Event()
        # central pull-dispatch queue (pull mode): replicas CLAIM from
        # it under self._work; its depth is the shed signal
        self._pending: "deque[Ticket]" = deque()
        self._work = threading.Condition(threading.Lock())
        self._dispatch_q: "queue.Queue[Optional[Ticket]]" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._pull_lanes = max(1, int(pull_lanes))
        self._probe_all()
        if dispatch == "pull":
            # one pull-worker per (replica, lane): a replica pulls
            # work whenever IT has slot headroom — a warming or slow
            # replica simply pulls less, and nothing is parked on it
            # by a stale placement guess. Two lanes per replica so one
            # blocking disaggregated prefill can't idle the replica.
            for name in self._replicas:
                self._start_lanes(self._replicas[name])
        else:
            if dispatchers is None:
                # a dispatcher BLOCKS for the whole synchronous prefill
                # of a disaggregated request: without a lane per prefill
                # worker, two long prompts in a row would park every
                # dispatcher and short requests would queue behind a
                # prefill — the exact tail disaggregation exists to
                # remove
                dispatchers = 2 + len(self._prefill)
            for i in range(max(1, int(dispatchers))):
                t = threading.Thread(target=self._dispatch_loop,
                                     daemon=True,
                                     name=f"pt-router-dispatch-{i}")
                t.start()
                self._threads.append(t)
        t = threading.Thread(target=self._poll_loop, daemon=True,
                             name="pt-router-poll")
        t.start()
        self._threads.append(t)
        self.server: Optional[_dbg_server.DebugServer] = None

    def _start_lanes(self, st: "_ReplicaState") -> None:
        """Spawn the pull lanes for one replica (bring-up AND
        scale-up: an added replica gets its own lanes under live
        traffic)."""
        for lane in range(self._pull_lanes):
            t = threading.Thread(
                target=self._pull_loop, args=(st,), daemon=True,
                name=f"pt-router-pull-{st.name}-{lane}")
            t.start()
            self._threads.append(t)

    # -- public API ---------------------------------------------------------

    def submit(self, prompt, max_new: int,
               session: Optional[str] = None,
               raise_on_shed: bool = False,
               stream: bool = False,
               model: Optional[str] = None) -> Ticket:
        """Route one request (non-blocking). SLO shed returns a
        ``shed=True`` ticket (or raises :class:`RequestShedError` when
        asked); :class:`NoReplicasError` when no replica is alive.

        ``model=``: model-id routing on a multi-model fleet — the
        ticket only places on replicas tagged with that model (their
        own arenas, so per-model page pools come with the placement),
        and its admission runs under that model's SLO class
        (:meth:`SLOPolicy.resolve`). An unknown model id is a typed
        error at admission. ``model=None`` places anywhere (the
        single-model fleet, unchanged).

        ``stream=True``: the returned ticket carries a client-side
        :class:`serving.TokenStream` — tokens arrive per decode tick,
        the first one stamps ``ttft_s`` and the router TTFT histogram,
        and terminal failure/retry surface as typed control records on
        the stream (never a silent stall)."""
        enforce(model is None or model in self._models,
                "unknown model %r: this fleet serves %s", model,
                self._models or "an untagged single-model fleet")
        with self._mu:
            t = Ticket(self._next_rid, prompt, max_new, session,
                       model=model)
            self._next_rid += 1
        if stream:
            t.stream = TokenStream(maxlen=self.stream_buffer)
        if self.prefix_hash_tokens is not None:
            # prefix-hash routing key: sessions sharing a system
            # prompt hash alike and hint at the replica whose prefix
            # cache already holds those pages
            t.prefix = prefix_hash(t.prompt, self.prefix_hash_tokens)
        if self._rel is not None:
            # the end-to-end Deadline is MINTED here — admission is
            # the one edge every request crosses exactly once (the
            # trace-mint discipline); budget priority: the SLO class's
            # deadline_s, then the config default, then
            # deadline_factor x the class target TTFT
            pol = (self.policy.resolve(model)
                   if self.policy is not None else None)
            t.deadline = self._rel.deadline_for(
                target_ttft_s=(None if pol is None
                               else pol.target_ttft_s),
                budget_s=(None if pol is None
                          else getattr(pol, "deadline_s", None)))
        if telemetry.enabled():
            _router_metrics()["requests"].inc()
            # the trace is MINTED here — admission is the one edge
            # every request crosses exactly once, so the head-based
            # sampling draw happens here and nowhere else
            t.trace = _tracing.new_trace(rate=self.trace_sample)
            _tracing.event("router.admit", ctx=t.trace, rid=t.rid,
                           session=session, plen=int(t.prompt.size),
                           max_new=t.max_new)
        if not self._alive_names(t.model):
            self._probe_all()
            if not self._alive_names(t.model):
                raise NoReplicasError(
                    "no replica alive to place the request on"
                    + (f" (model {t.model!r})" if t.model else ""))
        cause = None
        if self.max_in_flight is not None:
            with self._mu:
                if self._in_flight_locked() >= self.max_in_flight:
                    cause = "capacity"  # hard queue-depth cap
        if cause is None and self._policy_action(t.model) == "shed":
            cause = "shed"
        if cause is not None:
            t.shed = True
            err = RequestShedError(
                f"admission rejected ({cause}: "
                + ("hard in-flight cap reached" if cause == "capacity"
                   else "SLO load/queue-wait past shed_at") + ")")
            if t.stream is not None:
                t.stream.fail(err)  # typed, never a silent stall
            t.done.set()
            with self._mu:
                self._shed_count += 1
            if telemetry.enabled():
                _router_metrics()["shed"].inc()
                _tracing.event("router.shed", ctx=t.trace,
                               rid=t.rid, cause=cause)
            reject_cause(cause)
            if raise_on_shed:
                raise err
            return t
        with self._mu:
            self._tickets[t.rid] = t
            self._q_adj(t, +1)
        if self._dispatch_mode == "pull":
            with self._work:
                self._pending.append(t)
                if telemetry.enabled():
                    _router_metrics()["queue_depth"].set(
                        len(self._pending))
                self._work.notify_all()
        else:
            self._dispatch_q.put(t)
        return t

    def wait(self, tickets: Sequence[Ticket],
             timeout: Optional[float] = None) -> Dict[int, Ticket]:
        """Block until every non-shed ticket completes (or ``timeout``
        per ticket); raises the first ticket error (NoReplicasError
        when the fleet died under the request)."""
        out = {}
        for t in tickets:
            if not t.shed:
                t.wait(timeout)
            out[t.rid] = t
        return out

    def stats(self) -> Dict[str, Any]:
        with self._mu:
            alive = self._alive_names()
            return {
                "replicas": len(self._replicas),
                "alive": len(alive),
                "draining": sum(1 for st in self._replicas.values()
                                if st.alive and st.draining),
                "prefill_workers": len(self._prefill),
                "in_flight": self._in_flight_locked(),
                "served": self._served_count,
                "shed": self._shed_count,
                "retries": self._retry_count,
                "degraded": self._degraded,
                "ewma_ttft_s": self._ewma_ttft,
                "affinity_sessions": len(self._affinity),
                "dispatch": self._dispatch_mode,
                "dispatch_queue_depth": len(self._pending),
                "ewma_queue_wait_s": self._ewma_wait,
                "steals": self._steal_count,
                "prefix_homes": len(self._prefix_home),
                "prefix_cache": self._prefix_stats(),
                "models": list(self._models),
                "queued_by_model": dict(self._queued_by),
                "degraded_by": {str(k): v for k, v in
                                self._degraded_by.items() if v},
                "quarantined": [n for n, st in self._replicas.items()
                                if st.alive and st.quarantined],
                "reliability": (self._rel.statusz()
                                if self._rel is not None else None),
            }

    def _prefix_stats(self) -> Dict[str, Any]:
        """Fleet prefix-cache hit rate, counter-verified from the
        replicas' own POOL stats (the load-poll `prefix_hits`/
        `prefix_lookups` rows), never inferred from routing
        decisions."""
        hits = lookups = 0
        for st in self._replicas.values():
            hits += int(st.load.get("prefix_hits", 0) or 0)
            lookups += int(st.load.get("prefix_lookups", 0) or 0)
        return {"hits": hits, "lookups": lookups,
                "hit_ratio": (hits / lookups if lookups else None)}

    def replicaz(self) -> Dict[str, Any]:
        """Per-replica fan-out (the /podz pattern over serving
        replicas): live health + load + in-flight, one row each."""
        rows = {}
        for name, st in list(self._replicas.items()):
            row: Dict[str, Any] = {"alive": st.alive,
                                   "ready": st.ready,
                                   "draining": st.draining,
                                   "inflight": len(st.inflight)}
            if st.alive:
                try:
                    row["healthz"] = st.replica.healthz()
                    row["load"] = st.replica.load()
                except Exception as e:
                    row["error"] = repr(e)
            rows[name] = row
        return {"replicas": rows, "router": self.stats()}

    # -- scale plane (the autoscale control loop's contract) ----------------

    def signals(self) -> Dict[str, Any]:
        """One snapshot of the MEASURED load signals the autoscaler
        policy reads — queue depth, dispatch-wait EWMA, TTFT EWMA,
        in-flight vs slots, shed/served counters — plus the fleet
        shape (live / warming / draining counts). Pure read, no I/O:
        everything here is maintained by the poll and dispatch paths.
        The scaler records these rows verbatim as its replayable
        signal trace, so the snapshot IS the policy's whole world."""
        with self._mu:
            # a quarantined replica is NOT capacity: the autoscaler
            # must read quarantine as lost slots (and may scale up to
            # cover it) exactly like a draining replica
            live = [st for st in self._replicas.values()
                    if st.alive and not st.draining
                    and not st.quarantined]
            ready = sum(1 for st in live if st.ready)
            slots = sum(max(1, int(st.load.get("slots", 1) or 1))
                        for st in live if st.ready)
            return {
                "t": time.monotonic(),
                "queue_depth": len(self._pending),
                "in_flight": self._in_flight_locked(),
                "slots": slots,
                "ewma_wait_s": self._ewma_wait,
                "ewma_ttft_s": self._ewma_ttft,
                "replicas": len(live),
                "ready": ready,
                "warming": len(live) - ready,
                "draining": sum(1 for st in self._replicas.values()
                                if st.alive and st.draining),
                "quarantined": sum(1 for st in self._replicas.values()
                                   if st.alive and st.quarantined),
                "shed_total": self._shed_count,
                "served_total": self._served_count,
            }

    def add_replica(self, replica) -> None:
        """Scale-up under live traffic: register a started/spawned
        replica handle, probe it (readiness gates placement exactly as
        at bring-up), and give it pull lanes. The next claim cycle
        starts feeding it — no restart, no queue disruption."""
        with self._mu:
            enforce(replica.name not in self._replicas,
                    "duplicate replica name %r", replica.name)
            st = _ReplicaState(replica)
            self._replicas[replica.name] = st
            if st.model is not None and st.model not in self._models:
                self._models = sorted(set(self._models) | {st.model})
        self._probe(st)
        if self._dispatch_mode == "pull":
            self._start_lanes(st)
        with self._work:
            self._work.notify_all()
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))

    def drain_replica(self, name: str) -> None:
        """Begin retiring replica ``name`` — FAIL-CLOSED: the draining
        flag stops all NEW placement the moment it flips (claims,
        least-loaded picks, and both hint tables), and its session-
        affinity + prefix-home entries are purged HERE, not lazily, so
        a multi-turn session's next request re-homes instead of
        chasing a leaving replica. In-flight work is untouched: the
        poll loop keeps harvesting it and open streams finish on the
        same trace id. :meth:`drain_done` reports when it's empty."""
        with self._mu:
            st = self._replicas.get(name)
            enforce(st is not None, "no replica %r to drain", name)
            st.draining = True
            for s, n in self._affinity.items():
                if n == name:
                    self._affinity.pop(s)
            for h, n in self._prefix_home.items():
                if n == name:
                    self._prefix_home.pop(h)
        with self._work:
            self._work.notify_all()  # hinted tickets re-resolve now
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))

    def drain_done(self, name: str) -> bool:
        """True once a draining replica holds no work — router-side
        in-flight AND its own last-polled arena load are empty (or the
        replica died: its in-flight was already requeued, nothing left
        to wait for)."""
        with self._mu:
            st = self._replicas.get(name)
            if st is None or not st.alive:
                return True
            if not st.draining:
                return False
            if st.inflight or st.claimed:
                return False
            ld = st.load
            return not (ld.get("queue_depth", 0)
                        or ld.get("active_slots", 0)
                        or ld.get("prefilling", 0))

    def remove_replica(self, name: str, close: bool = False) -> Any:
        """Drop a drained (or dead) replica from the fleet; its pull
        lanes exit on the removed flag. ``close=True`` also closes the
        handle (terminating a worker process it owns). Returns the
        replica handle so a caller that keeps it open can repool it.
        Removing a replica that still holds in-flight work is a typed
        error — drain first."""
        with self._mu:
            st = self._replicas.get(name)
            enforce(st is not None, "no replica %r to remove", name)
            enforce(not st.alive or (st.draining and not st.inflight),
                    "replica %r still live with in-flight work: drain "
                    "it first (drain_replica + drain_done)", name)
            st.removed = True
            st.alive = False
            del self._replicas[name]
            self._models = sorted({s.model
                                   for s in self._replicas.values()
                                   if s.model is not None})
        with self._work:
            self._work.notify_all()
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))
        if close:
            try:
                st.replica.close()
            except Exception:
                pass
        return st.replica

    def loads(self) -> Dict[str, Dict[str, Any]]:
        """Per-replica last-polled load view (no I/O — the poll loop's
        cached rows): the autoscaler's victim-selection input."""
        with self._mu:
            return {n: {"alive": st.alive, "ready": st.ready,
                        "draining": st.draining,
                        "inflight": len(st.inflight),
                        "load": dict(st.load)}
                    for n, st in self._replicas.items()}

    def trace_fanin(self,
                    trace_id: Optional[str] = None) -> Dict[str, Any]:
        """Fleet trace aggregation — the ``/tracez?trace_id=`` payload
        on the router's debug server: collect matching spans from this
        process's own ring (router spans + any in-process replicas)
        and every worker process's /tracez, align timestamps via each
        process's clock-offset handshake, and merge into ONE
        chrome-trace with per-process lanes. Unreachable workers
        degrade to ``errors`` rows — a dead replica never fails the
        merge of what the fleet can still tell us."""
        from concurrent.futures import ThreadPoolExecutor

        collections: List[Dict[str, Any]] = [
            _tracing.collection(trace_id, proc="router")]
        sources = ["router"]
        errors: Dict[str, str] = {}
        peers = [(n, st.replica)
                 for n, st in list(self._replicas.items())]
        peers += [(getattr(w, "name", f"prefill{i}"), w)
                  for i, w in enumerate(list(self._prefill))]
        seen = set()
        targets = []
        for name, rep in peers:
            url = getattr(rep, "url", None)
            if url is None or url in seen:
                continue  # in-process replica: spans ride OUR ring
            seen.add(url)
            targets.append((name, url))
        # ``local=1``: ask each peer for its LOCAL ring, never its own
        # fan-in (aggregators must not recurse into each other)
        q = (f"?trace_id={trace_id}&local=1" if trace_id
             else "?local=1")

        def fetch(target):
            name, url = target
            try:
                with urllib.request.urlopen(url + "/tracez" + q,
                                            timeout=2) as r:
                    j = json.loads(r.read().decode())
                j["proc"] = name
                return name, j, None
            except Exception as e:
                return name, None, repr(e)

        if targets:
            # CONCURRENT fan-out: a scrape of a partially-wedged fleet
            # is bounded near ONE peer's timeout, not peers x timeout
            # serialized on the debug-server handler thread
            with ThreadPoolExecutor(
                    max_workers=min(8, len(targets)),
                    thread_name_prefix="pt-tracez-fetch") as ex:
                for name, j, err in ex.map(fetch, targets):
                    if j is not None:
                        collections.append(j)
                        sources.append(name)
                    else:
                        errors[name] = err
        merged = _tracing.merge_chrome_trace(collections)
        return {"trace_id": trace_id, "sources": sources,
                "errors": errors, "trace": merged}

    def profilez_fanout(self, body: bytes) -> Dict[str, Any]:
        """Fleet device capture — the router's ``POST /profilez``: one
        bounded capture in THIS process plus one per worker process,
        all overlapping in time (the /tracez fan-out pattern with a
        duration-sized timeout instead of the 2s scrape). A busy or
        unreachable peer degrades to an ``errors`` row; the router's
        own capture raising busy propagates (409 — the caller asked
        this process and it said no)."""
        from .telemetry import profiling as _profiling

        seen = set()
        urls: List[str] = []
        peers = [st.replica for st in list(self._replicas.values())]
        peers += list(self._prefill)
        for rep in peers:
            url = getattr(rep, "url", None)
            if url is None or url in seen:
                continue  # in-process replica: OUR capture covers it
            seen.add(url)
            urls.append(url)
        local = _profiling.make_profilez()(body)
        local["proc"] = "router"
        return _profiling.profilez_fanout(urls, body,
                                          local_result=local)

    def start_server(self, port: int = 0,
                     host: str = "127.0.0.1") -> _dbg_server.DebugServer:
        """Serve the router's own debug plane: /statusz gains a
        ``router`` section, /podz fans out over the replicas (the
        fleet-controller pattern reused), /tracez?trace_id= merges the
        fleet's spans for one request, /readyz = any replica
        placeable."""
        srv = _dbg_server.DebugServer(
            port=port, host=host,
            run_config={"role": "router",
                        "replicas": sorted(self._replicas)})
        srv.add_status("router", self.stats)
        srv.set_fleet(self.replicaz)
        srv.set_trace_fanin(self.trace_fanin)
        srv.set_ready(lambda: bool(self._alive_names()))
        srv.add_post("/submit", self._http_submit)
        srv.add_post("/drain", self._http_drain)
        srv.add_post("/profilez", self.profilez_fanout)
        srv.add_sse("/stream", self._http_stream)
        self.server = srv.start()
        return self.server

    def close(self, replicas: bool = False) -> None:
        self._stop.set()
        if self._dispatch_mode == "push":
            for _ in self._threads:
                self._dispatch_q.put(None)
        with self._work:
            self._work.notify_all()
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        # a silently dropped ticket would hang its waiter: fail
        # anything still on the central queue typed
        with self._work:
            leftover = list(self._pending)
            self._pending.clear()
        for t in leftover:
            with self._mu:
                self._queued = max(0, self._queued - 1)
            self._fail_ticket(t, NoReplicasError(
                f"router closed before request {t.rid} was "
                "dispatched"))
        if self.server is not None:
            self.server.stop()
            self.server = None
        if replicas:
            for st in self._replicas.values():
                try:
                    st.replica.close()
                except Exception:
                    pass
            for w in self._prefill:
                try:
                    w.close()
                except Exception:
                    pass
        # a closed router dispatches nothing: let go of the replicas, so
        # that whoever still holds the router (a caller's last ticket, a
        # client thread's frame) does not keep an in-process replica's
        # weights and arena, gigabytes of device memory, alive with it
        with self._mu:
            self._replicas = {}
            self._prefill = []

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- router HTTP front-end (start_server) -------------------------------

    def _http_submit(self, body: bytes) -> Dict[str, Any]:
        req = json.loads(body.decode() or "{}")
        t = self.submit(np.asarray(req["prompt"], np.int32),
                        int(req["max_new"]),
                        session=req.get("session"),
                        stream=bool(req.get("stream")),
                        model=req.get("model"))
        return {"rid": t.rid, "shed": t.shed}

    def _http_stream(self, body: bytes):
        """Router front-end SSE: fan a streamed ticket's client stream
        out over HTTP (one consumer per ticket)."""
        rid = int(json.loads(body.decode() or "{}")["rid"])
        with self._mu:
            t = self._tickets.get(rid)
        enforce(t is not None and t.stream is not None,
                "no streaming ticket %s (submit with stream=true "
                "first)", rid)
        if telemetry.enabled():
            _tracing.event("stream.open", ctx=t.trace, rid=rid)
        return iter(t.stream)

    def _http_drain(self, body: bytes) -> Dict[str, Any]:
        done = {}
        with self._mu:
            for rid, t in list(self._tickets.items()):
                if t.done.is_set():
                    done[rid] = {
                        "tokens": (t.tokens.tolist() if t.ok else None),
                        "ttft_s": t.ttft_s,
                        "itl_p99_s": t.itl_p99_s,
                        "shed": t.shed,
                        "error": repr(t.error) if t.error else None}
                    del self._tickets[rid]
        return {"done": done}

    # -- policy -------------------------------------------------------------

    def _alive_names(self, model: Optional[str] = None) -> List[str]:
        # PLACEABLE names: alive, not draining, not quarantined — a
        # draining/quarantined replica finishes what it holds but must
        # never receive new work, and every can-this-ticket-ever-be-
        # served check shares this definition (fail-closed)
        return [n for n, st in self._replicas.items()
                if st.alive and not st.draining and not st.quarantined
                and (model is None or st.model == model)]

    @staticmethod
    def _model_ok(st: "_ReplicaState", t: Ticket) -> bool:
        """Model routing filter: an untagged ticket places anywhere; a
        tagged one only on replicas serving its model (each replica's
        own arena = its own page pool, so per-model pools ride the
        placement)."""
        return t.model is None or st.model == t.model

    def _q_adj(self, t: Ticket, delta: int) -> None:
        """Queued-count accounting (caller holds ``self._mu``): the
        fleet scalar plus the per-model split the per-model SLO
        ladders read."""
        self._queued = max(0, self._queued + delta)
        if t.model is not None:
            cur = self._queued_by.get(t.model, 0)
            self._queued_by[t.model] = max(0, cur + delta)

    def _in_flight_locked(self, model: Optional[str] = None) -> int:
        if model is None:
            return self._queued + sum(len(st.inflight)
                                      for st in self._replicas.values())
        return (self._queued_by.get(model, 0)
                + sum(len(st.inflight)
                      for st in self._replicas.values()
                      if st.model == model))

    def _policy_action(self, model: Optional[str] = None) -> str:
        if self.policy is None:
            return "admit"
        # the model's OWN ladder over the model's OWN queue pressure
        # and slot pool: one model blowing through its SLO class
        # degrades/sheds only itself, never its neighbors
        pol = self.policy.resolve(model)
        with self._mu:
            in_flight = self._in_flight_locked(model)
            slots = sum(st.load.get("slots", 1)
                        for st in self._replicas.values()
                        if st.alive and not st.draining
                        and not st.quarantined
                        and (model is None or st.model == model))
            ewma = self._ewma_ttft
            wait = self._ewma_wait
        if self._dispatch_mode == "pull":
            # the shed signal is the QUEUE: depth rides in_flight, and
            # the deadline ladder reads the measured dispatch-queue
            # wait EWMA — a queue property, not a placement guess
            action = pol.admit(in_flight, slots, queue_wait_s=wait)
        else:
            action = pol.admit(in_flight, slots, ewma)
        want_degraded = action in ("degrade", "shed")
        if want_degraded != self._degraded_by.get(model, False):
            # hysteresis-free toggle is fine: set_degraded is
            # idempotent and cheap (a bool; the k=1 step fn caches).
            # model=None (the fleet-wide ladder) toggles every
            # replica; a tagged ladder toggles only its model's.
            with self._mu:
                self._degraded_by[model] = want_degraded
                self._degraded = any(self._degraded_by.values())
            if telemetry.enabled():
                _router_metrics()["degraded"].set(int(self._degraded))
            for st in list(self._replicas.values()):
                if st.alive and (model is None or st.model == model):
                    try:
                        st.replica.set_degraded(want_degraded)
                    except Exception:
                        pass  # health loop will catch a dead replica
        return action

    # -- placement + dispatch -----------------------------------------------

    def _pick_replica(self, t: Ticket) -> Optional[_ReplicaState]:
        with self._mu:
            if (self.session_affinity and t.session is not None):
                # affinity holds only while the replica is PLACEABLE
                # (alive AND ready) — a draining home replica loses the
                # session to least-loaded placement
                name = self._affinity.get(t.session)
                if name is not None:
                    st = self._replicas.get(name)
                    if (st is not None and st.alive and st.ready
                            and not st.draining and not st.quarantined
                            and self._model_ok(st, t)):
                        return st

            def pick(require_ready: bool):
                best, best_load = None, None
                for st in self._replicas.values():
                    if (not st.alive or st.draining or st.quarantined
                            or (require_ready and not st.ready)):
                        continue
                    if not self._model_ok(st, t):
                        continue
                    load = (len(st.inflight)
                            + st.load.get("queue_depth", 0)
                            + st.load.get("prefilling", 0))
                    if best_load is None or load < best_load:
                        best, best_load = st, load
                return best

            # ready replicas first; an all-cold fleet (nothing warmed
            # yet) still places on an alive one rather than failing
            return pick(True) or pick(False)

    def _fail_ticket(self, t: Ticket, err: BaseException) -> None:
        """Terminal ticket failure — the ONE place a ticket dies, so a
        streaming client always gets the typed error record (never a
        silent stall)."""
        t.error = err
        if t.stream is not None:
            t.stream.fail(err)
        t.done.set()

    def _deadline_fail(self, t: Ticket, where: str) -> None:
        """Drop an expired request typed + counted (caller guarantees
        the ticket is still in pre-dispatch accounting)."""
        with self._mu:
            self._q_adj(t, -1)
            if self._rel is not None:
                self._rel.deadline_exceeded += 1
        if telemetry.enabled():
            _router_metrics()["deadline_exceeded"].inc()
            _tracing.event("router.deadline_exceeded", ctx=t.trace,
                           rid=t.rid, where=where)
        over = (-t.deadline.remaining() * 1e3
                if t.deadline is not None else 0.0)
        self._fail_ticket(t, _reliability.DeadlineExceededError(
            f"request {t.rid} deadline expired {where} "
            f"({over:.1f} ms past budget)"))

    # -- pull dispatch (work stealing) --------------------------------------

    def _hint_for(self, t: Ticket):
        """Resolve the ticket's placement hint NOW -> (replica_name,
        strong) or (None, False). Session affinity is STRONG (a
        multi-turn conversation's KV lives on its home; never stolen
        while the home is placeable); the prefix-hash home is SOFT (a
        warm preference a starving replica may steal). A hint whose
        replica is dead or not ready resolves to None — re-queue means
        re-queue, not a wait on a corpse. Caller holds self._mu."""
        if self.session_affinity and t.session is not None:
            name = self._affinity.get(t.session)
            if name is not None:
                st = self._replicas.get(name)
                if (st is not None and st.alive and st.ready
                        and not st.draining and not st.quarantined
                        and self._model_ok(st, t)):
                    return name, True
        if t.prefix is not None:
            name = self._prefix_home.get(t.prefix)
            if name is not None:
                st = self._replicas.get(name)
                if (st is not None and st.alive and st.ready
                        and not st.draining and not st.quarantined
                        and self._model_ok(st, t)):
                    return name, False
        return None, False

    def _claim_locked(self, st: "_ReplicaState"):
        """One claim attempt by replica ``st`` against the central
        queue -> (ticket, stolen) or None. Claims honor hints: a
        ticket hinted HERE (or unhinted) goes first; a soft-hinted
        ticket parked for another replica is stolen only when this
        replica is STARVING (nothing in flight or claimed) and the
        ticket has waited past ``steal_age_s`` — the work-stealing
        rule: honor the hint when warm, ignore it when starving.
        ``st.claimed`` counts pulls not yet registered in-flight, so
        racing lanes can't over-claim past the slot cap. Caller holds
        self._work."""
        if (self._stop.is_set() or not st.alive or st.draining
                or st.removed or st.quarantined):
            return None
        if not st.ready and any(
                s.alive and s.ready and not s.draining
                and not s.quarantined
                for s in self._replicas.values()):
            # cold replica with warm peers available: don't pull —
            # but an all-cold fleet still serves (bring-up)
            return None
        steal_i = None
        with self._mu:
            cap = max(1, int(st.load.get("slots", 1) or 1))
            if len(st.inflight) + st.claimed >= cap:
                return None  # no headroom: the queue holds the rest
            starving = not st.inflight and not st.claimed
            now = time.perf_counter()
            # bounded scan: past this depth the backlog is effectively
            # unhinted FIFO (2 LRU lookups per ticket under the global
            # lock, times lanes x 50Hz idle wakeups, would otherwise
            # inflate the very queue wait the SLO policy sheds on); a
            # 128-deep hinted-only prefix already means severe
            # overload, where shedding — not perfect hint honoring —
            # is the design response
            limit = min(len(self._pending), 128)
            for i in range(limit):
                t = self._pending[i]
                if not self._model_ok(st, t):
                    continue  # another model's ticket: not ours to
                    # claim (its own replicas pull it)
                hint, strong = self._hint_for(t)
                if hint is None or hint == st.name:
                    del self._pending[i]
                    st.claimed += 1
                    return t, False
                if strong:
                    continue  # pinned session: home is placeable
                if (starving and steal_i is None
                        and now - t.t_submit >= self.steal_age_s):
                    steal_i = i
            if steal_i is not None:
                t = self._pending[steal_i]
                del self._pending[steal_i]
                st.claimed += 1
                return t, True
        return None

    def _pull_loop(self, st: "_ReplicaState") -> None:
        """One pull lane for one replica: claim work whenever the
        replica has slot headroom, dispatch it, repeat. The replica's
        own pace gates its intake — a slow or warming replica pulls
        less and the fleet's fast replicas absorb the queue."""
        while not self._stop.is_set():
            if st.removed:
                return  # replica scaled away: this lane retires too
            with self._work:
                got = self._claim_locked(st)
                if got is None:
                    self._work.wait(0.02)
                    got = self._claim_locked(st)
                if got is not None and telemetry.enabled():
                    _router_metrics()["queue_depth"].set(
                        len(self._pending))
            if got is None:
                continue
            t, stolen = got
            self._dispatch_to(t, st, stolen=stolen, claimed=True)

    def _dispatch_loop(self) -> None:
        while True:
            try:
                # bounded get: close() posts one None sentinel per
                # thread, but a dispatcher must exit on _stop even if
                # its sentinel is lost — a wedged dispatcher would pin
                # close()'s join budget for nothing
                t = self._dispatch_q.get(timeout=0.5)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            if t is None:
                return
            if self._stop.is_set():
                # closing: a silently dropped ticket would hang its
                # waiter — fail it typed and keep draining the queue
                with self._mu:
                    self._q_adj(t, -1)
                self._fail_ticket(t, NoReplicasError(
                    f"router closed before request {t.rid} was "
                    "dispatched"))
                continue
            self._dispatch(t)

    def _dispatch(self, t: Ticket) -> None:
        st = self._pick_replica(t)
        if st is None:
            with self._mu:
                self._q_adj(t, -1)
            self._fail_ticket(t, NoReplicasError(
                "all replicas down; request cannot be placed"
                + (f" (model {t.model!r})" if t.model else "")))
            return
        self._dispatch_to(t, st)

    def _dispatch_to(self, t: Ticket, st: "_ReplicaState",
                     stolen: bool = False,
                     claimed: bool = False) -> None:
        telem = telemetry.enabled()
        if stolen:
            t.stolen = True
            with self._mu:
                self._steal_count += 1
            if telem:
                _router_metrics()["steals"].inc()
        # bind the request's context for the whole placement: every
        # hop below (prefill-worker POST, replica submit/inject —
        # HTTP header or in-process thread-local alike) parents onto
        # this dispatch span, and a retry re-enters here with the
        # SAME trace id (retry count annotated)
        cm_bind = _tracing.bind(t.trace) if telem else _NULL_CM
        # the deadline binds beside the trace: in-process replica
        # submits read it via reliability.current(), HTTP hops stamp
        # X-PT-Deadline through _trace_headers
        cm_dl = (_reliability.bind(t.deadline)
                 if t.deadline is not None else _NULL_CM)
        cm_span = (_tracing.span("router.dispatch", ctx=t.trace,
                                 rid=t.rid,
                                 replica=st.replica.name,
                                 retry=t.retries, stolen=stolen)
                   if telem else _NULL_CM)
        try:
            with cm_bind, cm_dl, cm_span:
                self._dispatch_on(t, st, telem)
        finally:
            if claimed:
                # claim settled (registered in-flight, failed, or
                # requeued): release the headroom reservation
                with self._mu:
                    st.claimed = max(0, st.claimed - 1)

    def _dispatch_on(self, t: Ticket, st: "_ReplicaState",
                     telem: bool) -> None:
        from .resilience import faults as _faults

        if t.deadline is not None and t.deadline.expired():
            # the pre-dispatch tripwire: an expired request NEVER
            # reaches a replica (no device work is ever dispatched
            # for it) — it dies here, typed and counted
            self._deadline_fail(t, where="before dispatch")
            return
        t0 = time.perf_counter()
        try:
            inj = _faults.active()
            if inj is not None:
                inj.fire("router.dispatch", path=st.replica.name)
            handoff = None
            if (self._prefill and self.disagg_min_tokens is not None
                    and len(t.prompt) >= self.disagg_min_tokens):
                # a prefill-worker failure must not be blamed on the
                # decode replica picked above: drop the worker from the
                # rotation and FALL BACK to in-replica prefill (chunked
                # prefill / monolithic — the documented fallback path)
                with self._mu:
                    # model filter first: a tagged prompt must prefill
                    # on ITS model's weights — wrong-model KV pages
                    # would be silent garbage
                    workers = [w for w in self._prefill
                               if t.model is None
                               or getattr(w, "model", None) == t.model]
                    # round-robin cursor under the lock: two racing
                    # dispatchers must not pick the SAME worker and
                    # serialize on its replica lock while another
                    # worker idles
                    if workers:
                        worker = workers[self._pf_rr % len(workers)]
                        self._pf_rr += 1
                if workers:
                    pf_cm = (_tracing.span("router.disagg_prefill",
                                           ctx=t.trace,
                                           worker=worker.name,
                                           plen=int(t.prompt.size))
                             if telem else _NULL_CM)
                    try:
                        with pf_cm:
                            handoff = worker.prefill(t.prompt)
                        t.disaggregated = True
                        if telem:
                            _router_metrics()["disagg"].inc()
                    except EnforceError:
                        raise  # typed rejection: the REQUEST's fault
                    except Exception:
                        with self._mu:
                            if worker in self._prefill:
                                self._prefill.remove(worker)
            # stream= only when asked: replica stubs predating the
            # streaming plane keep working un-streamed
            kw = ({"session": t.session, "stream": True}
                  if t.stream is not None else {"session": t.session})
            if inj is not None:
                # chaos point router.latency: a seeded delay_s rule
                # matched to ONE replica simulates a gray (slow-but-
                # alive) replica — fired INSIDE the t0 window, so the
                # injected stall lands in the measured dispatch
                # latency exactly like a real one
                inj.fire("router.latency", path=st.replica.name)
            if handoff is not None:
                rid = st.replica.inject(handoff, t.max_new, **kw)
            else:
                rid = st.replica.submit(t.prompt, t.max_new, **kw)
        except EnforceError:
            # typed replica-side rejection (bad request): the caller's
            # error, not a replica death
            with self._mu:
                self._q_adj(t, -1)
            self._fail_ticket(t, sys.exc_info()[1])
            return
        except Exception:
            # transport/dispatch failure: fail the replica over and
            # retry the request on a survivor. A TIMEOUT additionally
            # feeds the gray-failure score first — consecutive
            # timeouts are a breaker signal
            if self._rel is not None:
                with self._mu:
                    self._rel.health(st.name).note_timeout()
            self._fail_replica(st, reason=repr(sys.exc_info()[1]))
            self._requeue(t)
            return
        if self._rel is not None:
            # dispatch latency (submit round-trip incl. any injected
            # gray stall) feeds the per-replica breaker EWMA — the
            # latency-outlier-vs-fleet-median quarantine signal
            with self._mu:
                self._rel.health(st.name).note_latency(
                    time.perf_counter() - t0)
        t.t_dispatched = time.perf_counter()
        t.replica, t.replica_rid = st.replica.name, rid
        wait = max(0.0, t.t_dispatched - t.t_submit)
        with self._mu:
            self._q_adj(t, -1)
            a = 0.2  # EWMA over recent dispatches — the policy's
            self._ewma_wait = (wait if self._ewma_wait is None  # input
                               else (1 - a) * self._ewma_wait + a * wait)
            # the poll thread may have drained this rid's result
            # BEFORE we registered it (a request can finish at its
            # first serve tick) — the parked orphan record completes
            # the ticket right here instead of hanging its waiter
            rec = st.orphans.pop(rid, None)
            if rec is None:
                st.inflight[rid] = t
            if self.session_affinity and t.session is not None:
                self._affinity.set(t.session, st.replica.name)
            if t.prefix is not None:
                # stamp (or re-stamp after a steal) the prefix's home:
                # the NEXT prompt sharing this prefix lands where the
                # pages now live, so the fleet converges on one warm
                # replica per system prompt
                self._prefix_home.set(t.prefix, st.replica.name)
        if t.stream is not None and rec is None:
            self._start_pump(t, st)
        if rec is not None:
            self._finish(t, rec, replica=st.name)
        if telemetry.enabled():
            _router_metrics()["queue_wait"].observe(
                wait,
                exemplar=(t.trace.trace_id
                          if t.trace is not None and t.trace.sampled
                          else None))

    def _requeue(self, t: Ticket) -> None:
        """Re-QUEUE after a replica failure — the request goes back on
        the central queue (pull mode: any survivor with headroom picks
        it up; no re-placement guess) and survives as long as ANY
        replica does. A streaming client sees a typed ``resume``
        record on the SAME trace id: tokens already delivered stay
        valid — greedy re-decode is deterministic and the new pump
        skips past the delivered index."""
        if t.deadline is not None and t.deadline.expired():
            # no point retrying work nobody is waiting for — and an
            # expired retry must never spend retry-budget tokens
            self._deadline_fail(t, where="on requeue")
            return
        if self._rel is not None and not self._rel.budget.take():
            # retry budget dry: degrade to ONE typed failure instead
            # of amplifying a replica failure into a retry storm
            with self._mu:
                self._q_adj(t, -1)
            if telemetry.enabled():
                _router_metrics()["retry_budget_exhausted"].inc()
                _tracing.event("router.retry_budget_exhausted",
                               ctx=t.trace, rid=t.rid,
                               retries=t.retries)
            self._fail_ticket(
                t, _reliability.RetryBudgetExhaustedError(
                    f"request {t.rid} failed on replica {t.replica} "
                    f"and the retry budget is exhausted "
                    f"({self._rel.budget.snapshot()})"))
            return
        t.retries += 1
        prev = t.replica
        t.replica = t.replica_rid = None
        with self._mu:
            self._retry_count += 1
            t._pump_gen += 1  # supersede any pump still draining prev
        if t.stream is not None:
            t.stream.control(
                "resume", retries=t.retries, failed_replica=prev,
                resume_at=t._stream_next,
                trace_id=(t.trace.trace_id if t.trace is not None
                          else None))
        if telemetry.enabled():
            _router_metrics()["retries"].inc()
            # the retry stays on the SAME trace id — the merged
            # timeline shows the death and the re-dispatch as one
            # request's story, annotated here
            _tracing.event("router.retry", ctx=t.trace, rid=t.rid,
                           retries=t.retries, failed_replica=prev)
            if t.stream is not None:
                _tracing.event("stream.resume", ctx=t.trace,
                               rid=t.rid, retries=t.retries,
                               resume_at=t._stream_next)
        if not self._alive_names(t.model):
            with self._mu:
                self._q_adj(t, -1)
            self._fail_ticket(t, NoReplicasError(
                f"request {t.rid} lost: all replicas down"
                + (f" for model {t.model!r}" if t.model else "")
                + f" (after {t.retries} retries)"))
            return
        if self._dispatch_mode == "pull":
            with self._work:
                self._pending.appendleft(t)  # retries jump the queue
                self._work.notify_all()
        else:
            self._dispatch_q.put(t)

    # -- streaming fan-in ---------------------------------------------------

    def _start_pump(self, t: Ticket, st: "_ReplicaState") -> None:
        with self._mu:
            t._pump_gen += 1
            gen = t._pump_gen
        threading.Thread(target=self._pump, args=(t, st, gen),
                         daemon=True,
                         name=f"pt-router-stream-{t.rid}").start()

    def _pump(self, t: Ticket, st: "_ReplicaState", gen: int) -> None:
        """Fan ONE replica-side token stream into the ticket's client
        stream. First token stamps ``ttft_s`` + the router TTFT
        histogram (the streaming edge — same series the non-streaming
        path feeds at completion); later gaps feed the ITL histogram,
        exemplars riding the request's trace. Token records dedupe by
        index across retries (re-decode is deterministic; token i IS
        token i), and a superseded pump (its ticket re-dispatched)
        stops forwarding the moment it notices. Transport death here
        is NOT terminal — the health loop owns failover, and the
        client's resume/error records come from the requeue path."""
        telem = telemetry.enabled()
        traced = (telem and t.trace is not None and t.trace.sampled)
        cm = (_tracing.bind(t.trace) if traced else _NULL_CM)
        try:
            with cm:
                if traced:
                    _tracing.event("stream.fanin", ctx=t.trace,
                                   rid=t.rid,
                                   replica=st.replica.name,
                                   retry=t.retries)
                source = st.replica.open_stream(t.replica_rid)
                last_t: Optional[float] = None
                for rec in source:
                    if self._stop.is_set() or t._pump_gen != gen:
                        return  # superseded by a retry / shutdown
                    if "i" in rec:
                        now = time.perf_counter()
                        if rec["i"] < t._stream_next:
                            continue  # delivered before the retry
                        t._stream_next = rec["i"] + 1
                        ex = (t.trace.trace_id if traced else None)
                        first = False
                        if t.ttft_s is None:
                            # claim the TTFT under the lock: the
                            # harvest thread's _finish races this on
                            # fast completions, and the histogram must
                            # see exactly ONE observation per request
                            with self._mu:
                                first = t.ttft_s is None
                                if first:
                                    t.t_first_stream = now
                                    t.ttft_s = now - t.t_submit
                        if first:
                            if telem:
                                _router_metrics()["ttft"].observe(
                                    t.ttft_s, exemplar=ex)
                        elif telem and last_t is not None:
                            _router_metrics()["itl"].observe(
                                now - last_t, exemplar=ex)
                        last_t = now
                        t.stream.put(
                            {"i": rec["i"], "tok": rec["tok"],
                             "t": now}, timeout=300.0)
                    elif rec.get("event") == "end":
                        return  # completion record closes the client
                        # stream via _finish (harvest path)
        except Exception:
            return  # transport death: health loop + requeue own it

    # -- health + results ---------------------------------------------------

    def _probe_all(self) -> None:
        for st in list(self._replicas.values()):
            self._probe(st)
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))

    def _probe(self, st: _ReplicaState) -> None:
        try:
            hz = st.replica.healthz()
            st.load = st.replica.load()
            st.fails = 0
            # ready=False is NOT death: placement stops (pick requires
            # ready) but in-flight work keeps draining and nothing is
            # retried — a draining replica finishes what it holds
            st.ready = bool(hz.get("ready", True))
            if not st.alive:
                st.alive = True  # answered again: recovered
        except Exception as e:
            if self._rel is not None and st.alive \
                    and _is_timeout_error(e):
                # a TIMEOUT is the gray-failure signature (the process
                # accepted the connection, then went silent — SIGSTOP,
                # GC stall, compile storm); a refused connection is
                # plain death. Feed the breaker; once it trips, the
                # half-open probe owns recovery — don't ALSO count the
                # replica toward health-fail death while quarantined
                with self._mu:
                    h = self._rel.health(st.name)
                    h.note_timeout()
                    reason = self._rel.quarantine_reason(h)
                if reason is not None and not st.quarantined:
                    self._maybe_quarantine(st, reason)
                if st.quarantined:
                    return
                if reason is None:
                    # breaker still counting consecutive timeouts:
                    # not death yet (health_fails would otherwise
                    # race the breaker and always win)
                    return
                # trip declined (last placeable replica): fall through
                # to ordinary death accounting
            st.fails += 1
            if st.fails >= self.health_fails and st.alive:
                self._fail_replica(st, reason="health check failed "
                                   f"{st.fails}x")

    def _fail_replica(self, st: _ReplicaState, reason: str = "") -> None:
        with self._mu:
            if not st.alive and not st.inflight:
                return
            st.alive = False
            orphans = list(st.inflight.values())
            st.inflight.clear()
            # a dead replica's placement hints die with it: sessions
            # AND prefix homes (the next claim re-homes them)
            for s, name in self._affinity.items():
                if name == st.replica.name:
                    self._affinity.pop(s)
            for h, name in self._prefix_home.items():
                if name == st.replica.name:
                    self._prefix_home.pop(h)
        if telemetry.enabled():
            _router_metrics()["replica_deaths"].inc()
            _router_metrics()["healthy"].set(len(self._alive_names()))
        for t in orphans:
            with self._mu:
                self._q_adj(t, +1)  # back to pre-dispatch accounting
            self._requeue(t)
        # a queued ticket whose claim can never come dies typed, never
        # parked forever: the whole fleet down fails everything; a
        # MODEL's last replica down fails that model's tickets (claims
        # are model-filtered, so no other replica will ever take them)
        alive_models = {self._replicas[n].model
                        for n in self._alive_names()}
        fleet_dead = not alive_models
        with self._work:
            leftover = [lt for lt in self._pending
                        if fleet_dead or (lt.model is not None
                                          and lt.model
                                          not in alive_models)]
            for lt in leftover:
                self._pending.remove(lt)
        for lt in leftover:
            with self._mu:
                self._q_adj(lt, -1)
            self._fail_ticket(lt, NoReplicasError(
                f"request {lt.rid} lost: all replicas down before "
                "any could claim it"
                + (f" (model {lt.model!r})" if lt.model else "")))

    def _finish(self, t: Ticket, rec: Dict,
                replica: Optional[str] = None) -> None:
        """Complete a ticket from its replica-side result record.
        ``replica``: which replica produced the record — the hedge
        winner/loser discriminator. First result wins; a later record
        for a done ticket is discarded here (the hedge-loser path)."""
        with self._mu:
            if t.done.is_set():
                return  # hedge loser / duplicate record: already won
        if t.hedged:
            self._resolve_hedge(t, replica)
        if rec.get("deadline_exceeded") or rec.get("tokens") is None:
            # the replica's arena dropped it at the per-tick deadline
            # sweep: surface the SAME typed error the router-side
            # drops use (never a fake token list)
            if self._rel is not None:
                with self._mu:
                    self._rel.deadline_exceeded += 1
            if telemetry.enabled():
                _router_metrics()["deadline_exceeded"].inc()
                _tracing.event("router.deadline_exceeded",
                               ctx=t.trace, rid=t.rid,
                               where=f"on replica "
                                     f"{replica or t.replica}")
            self._fail_ticket(t, _reliability.DeadlineExceededError(
                f"request {t.rid} deadline expired on replica "
                f"{replica or t.replica}"))
            return
        if self._rel is not None:
            # a completed request refills the retry budget (the SRE
            # fraction-of-successes rule) and its dispatch→done
            # latency feeds the adaptive hedge threshold
            self._rel.budget.note_success()
            if t.t_dispatched:
                self._rel.latency.observe(
                    time.perf_counter() - t.t_dispatched)
        t.tokens = np.asarray(rec["tokens"], np.int32)
        with self._mu:
            # claim under the lock (the stream pump races this on fast
            # completions): a STREAMED ticket that already stamped
            # ttft_s from its first token keeps the streaming
            # measurement; otherwise replica-side TTFT (measured from
            # ITS submit) + the router-side dispatch wait = end-to-end
            streamed_first = t.ttft_s is not None
            if not streamed_first:
                wait = max(0.0, t.t_dispatched - t.t_submit)
                t.ttft_s = float(rec["ttft_s"]) + wait
        t.itl_p99_s = float(rec.get("itl_p99_s") or 0.0)
        with self._mu:
            self._served_count += 1
            a = 0.2  # EWMA over recent completions
            self._ewma_ttft = (t.ttft_s if self._ewma_ttft is None
                               else (1 - a) * self._ewma_ttft
                               + a * t.ttft_s)
        if telemetry.enabled() and not streamed_first:
            _router_metrics()["ttft"].observe(
                t.ttft_s,
                exemplar=(t.trace.trace_id
                          if t.trace is not None and t.trace.sampled
                          else None))
        if t.stream is not None:
            # any tokens the pump has not forwarded yet serve
            # consumer-driven from the completion record, then the
            # typed end mark — the stream can't outlive its ticket.
            # Supersede the pump: a lagging fan-in must stop
            # forwarding (its late records are dropped-as-delivered
            # by the client stream's high-water check anyway)
            with self._mu:
                t._pump_gen += 1
            t.stream.finish(t.tokens)
        t.done.set()

    def _resolve_hedge(self, t: Ticket, winner: Optional[str]) -> None:
        """First result arrived for a hedged ticket: count the
        outcome, drop the loser's in-flight registration, and
        best-effort cancel its queued work (fire-and-forget on a
        daemon thread — a wedged loser must not block the harvest)."""
        won = winner is not None and winner == t.hedge_replica
        if won:
            loser_name, loser_rid = t.replica, t.replica_rid
        else:
            loser_name, loser_rid = t.hedge_replica, t.hedge_rid
        if self._rel is not None and won:
            with self._mu:
                self._rel.hedge_wins += 1
        if telemetry.enabled():
            _router_metrics()["hedges"][
                "true" if won else "false"].inc()
            _tracing.event("router.hedge_resolved", ctx=t.trace,
                           rid=t.rid, won=won, winner=winner)
        lst = self._replicas.get(loser_name) if loser_name else None
        if lst is None or loser_rid is None:
            return
        with self._mu:
            lst.inflight.pop(loser_rid, None)
        cancel = getattr(lst.replica, "cancel", None)
        if cancel is not None:
            threading.Thread(
                target=_swallow, args=(cancel, loser_rid),
                daemon=True,
                name=f"pt-router-hedge-cancel-{t.rid}").start()

    def _harvest(self, st: _ReplicaState) -> None:
        if not st.inflight:
            return
        try:
            done = st.replica.drain_results()
        except Exception:
            return  # the probe path owns failure counting
        self._absorb(st, done)

    def _absorb(self, st: _ReplicaState, done: Dict[int, Dict]) -> None:
        """Complete tickets from drained result records (the harvest
        body; the half-open probe reuses it for records that drained
        alongside its probe request)."""
        for rid, rec in done.items():
            with self._mu:
                t = st.inflight.pop(rid, None)
                if t is None:
                    # drained before the dispatcher registered the rid
                    # (fast completion) or a stale record (warmup, a
                    # retried duplicate's original): park it for the
                    # registration to claim; bound the buffer so stale
                    # entries can't accumulate
                    st.orphans[rid] = rec
                    while len(st.orphans) > 256:
                        st.orphans.pop(next(iter(st.orphans)))
                    continue
            self._finish(t, rec, replica=st.name)

    # -- reliability sweep (quarantine + hedging + half-open probes) --------

    def _maybe_quarantine(self, st: _ReplicaState, reason: str) -> None:
        """Trip the breaker on ``st`` UNLESS it is the last placeable
        replica for its model — a fleet must never quarantine itself
        to zero (the lone gray replica stays in rotation: slow beats
        unservable)."""
        others = [n for n in self._alive_names(st.model)
                  if n != st.name]
        if not others:
            return
        self._quarantine(st, reason)

    def _quarantine(self, st: _ReplicaState, reason: str) -> None:
        """Open the breaker: ``st`` leaves placement and affinity
        (fail-closed, the drain_replica pattern) but keeps draining
        its in-flight work. REVERSIBLE — a successful half-open probe
        returns it to rotation."""
        with self._mu:
            if st.quarantined:
                return
            st.quarantined = True
            self._rel.health(st.name).trip(reason)
            self._rel.quarantines += 1
            for s, n in self._affinity.items():
                if n == st.name:
                    self._affinity.pop(s)
            for h, n in self._prefix_home.items():
                if n == st.name:
                    self._prefix_home.pop(h)
        if telemetry.enabled():
            _router_metrics()["quarantines"].inc()
            _router_metrics()["healthy"].set(len(self._alive_names()))
            _tracing.event("router.quarantine", replica=st.name,
                           reason=reason)
        with self._work:
            self._work.notify_all()  # hinted tickets re-resolve now

    def _reliability_sweep(self) -> None:
        """One pass of the reliability plane's periodic work (runs on
        the poll cadence, only when the plane is on): feed queue-depth
        EWMAs, trip breakers on gray outliers, launch half-open
        probes when cooldowns expire, and hedge stuck requests."""
        cfg = self._rel.config
        states = list(self._replicas.values())
        med = self._rel.fleet_median_latency()
        for st in states:
            if not st.alive:
                continue
            if st.quarantined:
                h = self._rel.health(st.name)
                if h.probe_due(cfg.quarantine_cooldown_s):
                    with self._mu:
                        h.half_open()
                    threading.Thread(
                        target=self._half_open_probe, args=(st,),
                        daemon=True,
                        name=f"pt-router-probe-{st.name}").start()
                continue
            if st.draining:
                continue
            with self._mu:
                h = self._rel.health(st.name)
                h.note_queue(st.load.get("queue_depth", 0) or 0)
                reason = self._rel.quarantine_reason(
                    h, fleet_median=med)
            if reason is not None:
                self._maybe_quarantine(st, reason)
        thr = self._rel.hedge_threshold()
        if thr is not None:
            now = time.perf_counter()
            with self._mu:
                stuck = [t for st in states
                         for t in list(st.inflight.values())
                         if (not t.hedged and t.stream is None
                             and not t.done.is_set()
                             and t.max_new <= cfg.hedge_max_new
                             and t.t_dispatched
                             and now - t.t_dispatched > thr)]
            for t in stuck:
                self._hedge(t)

    def _hedge(self, t: Ticket) -> None:
        """Issue the hedge: dispatch a DUPLICATE of a stuck request to
        the least-loaded OTHER placeable replica, same trace id under
        a ``router.hedge`` span. First result wins (_finish's done
        guard); the loser is dropped + best-effort cancelled."""
        with self._mu:
            best, best_load = None, None
            for st in self._replicas.values():
                if (not st.alive or not st.ready or st.draining
                        or st.quarantined or st.name == t.replica
                        or not self._model_ok(st, t)):
                    continue
                load = (len(st.inflight)
                        + (st.load.get("queue_depth", 0) or 0))
                if best_load is None or load < best_load:
                    best, best_load = st, load
        if best is None:
            return  # nowhere to hedge: the primary still owns it
        telem = telemetry.enabled()
        cm_bind = _tracing.bind(t.trace) if telem else _NULL_CM
        cm_dl = (_reliability.bind(t.deadline)
                 if t.deadline is not None else _NULL_CM)
        cm_span = (_tracing.span("router.hedge", ctx=t.trace,
                                 rid=t.rid, primary=t.replica,
                                 hedge=best.name)
                   if telem else _NULL_CM)
        try:
            with cm_bind, cm_dl, cm_span:
                rid2 = best.replica.submit(t.prompt, t.max_new,
                                           session=t.session)
        except Exception:
            return  # hedging is opportunistic, never a new failure
        with self._mu:
            self._rel.hedges += 1
            t.hedged = True
            t.hedge_replica = best.name
            t.hedge_rid = rid2
            best.inflight[rid2] = t
        if telem:
            _tracing.event("router.hedged", ctx=t.trace, rid=t.rid,
                           replica=best.name)

    def _half_open_probe(self, st: _ReplicaState) -> None:
        """One cheap warmed request through the quarantined replica
        (the breaker's half-open state): success closes the breaker
        and returns the replica to rotation; failure reopens it and
        the cooldown restarts."""
        h = self._rel.health(st.name)
        deadline = time.monotonic() + self._rel.config.probe_timeout_s
        try:
            hz = st.replica.healthz()
            enforce(hz.get("status") == "ok",
                    "probe healthz not ok: %r", hz)
            rid = st.replica.submit(np.asarray([1, 2], np.int32), 1)
            ok = False
            while time.monotonic() < deadline:
                done = st.replica.drain_results()
                if rid in done:
                    done.pop(rid)
                    ok = True
                self._absorb(st, done)  # in-flight that drained along
                if ok:
                    break
                time.sleep(0.05)
            enforce(ok, "probe request did not complete within "
                    "probe_timeout_s")
        except Exception:
            with self._mu:
                h.reopen()
            if telemetry.enabled():
                _tracing.event("router.probe_failed", replica=st.name)
            return
        with self._mu:
            h.close()
            st.quarantined = False
            st.fails = 0
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))
            _tracing.event("router.quarantine_lifted",
                           replica=st.name)
        with self._work:
            self._work.notify_all()

    def _poll_once(self) -> None:
        """One health+results sweep (the poll loop's body; tests drive
        it directly for deterministic schedules). Probes EVERY replica
        — including dead ones, so a transient failure (GC pause, slow
        compile) recovers the replica on its next successful answer
        instead of removing it from the fleet forever."""
        for st in list(self._replicas.values()):
            self._probe(st)
            if st.inflight:
                self._harvest(st)
        if self._rel is not None:
            self._reliability_sweep()
        if self._dispatch_mode == "pull" and self._pending:
            # probes/harvests may have freed headroom or flipped
            # readiness: wake the pull lanes
            with self._work:
                self._work.notify_all()
        if telemetry.enabled():
            _router_metrics()["healthy"].set(len(self._alive_names()))
            stats = self._prefix_stats()
            if stats["lookups"]:
                _router_metrics()["prefix_ratio"].set(
                    stats["hit_ratio"])
            if self._textfile:
                # node-exporter textfile path: re-write the whole
                # registry (pt_router_* included) on a bounded cadence
                # — scrape-less deployments read the same series a
                # /metrics scrape would
                now = time.monotonic()
                if now - self._textfile_t >= self._textfile_interval_s:
                    self._textfile_t = now
                    try:
                        telemetry.write_textfile(self._textfile)
                    except Exception:
                        pass  # a full disk must not kill the poll loop

    def _poll_loop(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            self._poll_once()


# ---------------------------------------------------------------------------
# Worker process + spawning
# ---------------------------------------------------------------------------

def _resolve_spec(spec: str, spec_kw: Optional[dict]):
    """``module:fn`` → the BatchedDecoder the callable builds (the
    worker-process model contract: the function must be importable in
    a FRESH process and return a ready-to-serve decoder)."""
    mod, _, fn = spec.partition(":")
    enforce(mod and fn, "--spec must be module:function, got %r", spec)
    import importlib

    f = getattr(importlib.import_module(mod), fn)
    dec = f(**(spec_kw or {}))
    enforce(isinstance(dec, BatchedDecoder),
            "spec %r must return a serving.BatchedDecoder, got %s",
            spec, type(dec).__name__)
    return dec


_aot_fallback_warned = False


def _boot_decoder(spec: Optional[str], spec_kw: Optional[dict],
                  from_artifact: Optional[str]):
    """Worker decoder bring-up -> ``(decoder, mode, diagnostic)`` with
    mode in ``aot`` (trace-free from the serialized artifact) |
    ``traced`` (ordinary spec path) | ``traced_fallback`` (artifact
    asked for but rejected — fingerprint mismatch / torn / unreadable
    — so the trace path ran instead, with the warn-once typed
    PT-AOT-601 diagnostic). The fallback NEVER crashes the worker as
    long as a ``spec`` exists to trace from."""
    global _aot_fallback_warned
    if from_artifact is None:
        return _resolve_spec(spec, spec_kw), "traced", None
    from . import aot as _aot

    try:
        return _aot.load_decoder(from_artifact), "aot", None
    except _aot.AotError as e:
        diag = (f"[PT-AOT-601] artifact boot fell back to the trace "
                f"path: {e}")
        if spec is None:
            # nothing to fall back TO: artifact-only boot, typed error
            raise
        if not _aot_fallback_warned:
            _aot_fallback_warned = True
            print(diag, file=sys.stderr)
        return _resolve_spec(spec, spec_kw), "traced_fallback", diag


def run_worker(spec: Optional[str], role: str = "decode", port: int = 0,
               port_file: Optional[str] = None,
               spec_kw: Optional[dict] = None, warm: bool = True,
               from_artifact: Optional[str] = None,
               model: Optional[str] = None,
               _ready_evt: Optional[threading.Event] = None) -> None:
    """One replica worker: build the decoder from ``spec`` (or
    trace-free from ``from_artifact`` — an aot artifact dir or a
    checkpoint root, with warn-once PT-AOT-601 fallback to ``spec`` on
    a rejected artifact), serve the router API + debug endpoints on
    ``port``, run until SIGTERM/SIGINT. ``model=`` tags the replica
    for model-id routing. ``role="prefill"``: no serve loop — the
    worker only answers /prefill (and reports ready after its prefill
    bucket warms)."""
    import signal as _signal

    from .utils import compat as _compat

    t_start = time.perf_counter()
    decoder, boot_mode, boot_diag = _boot_decoder(spec, spec_kw,
                                                  from_artifact)
    name = f"{model + '-' if model else ''}{role}-{os.getpid()}"
    rep = LocalReplica(decoder, name=name, model=model)
    if role == "decode":
        rep.start()
    srv = _dbg_server.DebugServer(
        port=port, owned=True,
        run_config={"role": f"serving-{role}", "spec": spec,
                    "model": model, "boot": boot_mode,
                    "slots": decoder.slots,
                    "capacity": decoder.capacity,
                    "paged": decoder.paged})
    srv.add_status("serving", decoder._statusz)
    # /statusz "aot" section: how THIS process booted (trace-free vs
    # traced), under which artifact/fingerprint, and its TTFR —
    # time-to-first-ready, stamped once warm flips ready below
    aot_status: Dict[str, Any] = {
        "mode": boot_mode, "ttfr_ms": None, "model": model,
        "fingerprint": _compat.runtime_fingerprint()}
    if boot_diag is not None:
        aot_status["diagnostic"] = boot_diag
    if boot_mode == "aot":
        info = getattr(decoder, "aot_info", {})
        aot_status.update(
            artifact=info.get("artifact"),
            artifact_id=info.get("artifact_id"),
            fingerprint=info.get("fingerprint"),
            programs=info.get("programs"))
    srv.add_status("aot", lambda: dict(aot_status))
    srv.set_ready(lambda: decoder.ready)
    if role == "decode":
        # arena endpoints only where a serve loop actually ticks — a
        # /submit accepted by a prefill worker would enqueue into an
        # arena nothing drives (silent forever-pending instead of 404)
        def _submit(b: bytes) -> Dict[str, Any]:
            req = json.loads(b.decode())
            return {"rid": rep.submit(
                np.asarray(req["prompt"], np.int32),
                int(req["max_new"]),
                stream=bool(req.get("stream")))}

        def _stream(b: bytes):
            # SSE per-token stream for one rid: the iterator IS the
            # replica-side TokenStream, served chunked with per-token
            # flush + trace-header echo by DebugServer.add_sse
            rid = int(json.loads(b.decode())["rid"])
            it = rep.open_stream(rid)
            if telemetry.enabled():
                _tracing.event("stream.open", rid=rid)
            return it

        srv.add_post("/submit", _submit)
        srv.add_sse("/stream", _stream)
        srv.add_post("/drain", lambda b: {"done": {
            rid: {**rec, "tokens": (
                np.asarray(rec["tokens"]).tolist()
                if rec.get("tokens") is not None else None)}
            for rid, rec in rep.drain_results().items()}})
        srv.add_post("/cancel", lambda b: {"cancelled": rep.cancel(
            int(json.loads(b.decode())["rid"]))})
        srv.add_post("/inject", _make_inject(rep))
    srv.add_post("/config", lambda b: _worker_config(rep, b))
    srv.add_post("/load", lambda b: rep.load())
    # on-demand device capture for THIS worker process — the router's
    # /profilez fans out here, so every process in the fleet lands its
    # own XPlane artifact (plain handler, no fan-out: workers have no
    # peers, hence no recursion)
    from .telemetry import profiling as _profiling
    srv.add_post("/profilez", _profiling.make_profilez())
    srv.add_post("/prefill", lambda b: (
        "application/octet-stream",
        rep.prefill(np.asarray(
            json.loads(b.decode())["prompt"], np.int32)).to_bytes()))
    srv.start()
    if port_file:
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(srv.port))
        os.replace(tmp, port_file)
    if warm:
        if role == "prefill":
            # compile the prefill bucket so the first real handoff
            # isn't a cold trace, then report ready
            decoder.prefill_export(np.asarray([1, 2], np.int32))
            decoder._warmed = True
        else:
            rep.warmup()
        # TTFR (time-to-first-ready): worker entry -> ready flipped.
        # The AOT win lives here — an aot boot dispatched serialized
        # executables where a traced boot re-traced + re-compiled
        aot_status["ttfr_ms"] = (time.perf_counter() - t_start) * 1e3
        if telemetry.enabled():
            _router_metrics()["cold_starts"][boot_mode].inc()
    stop = threading.Event()
    for sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            _signal.signal(sig, lambda *a: stop.set())
        except ValueError:
            pass  # not the main thread (in-process tests)
    if _ready_evt is not None:
        _ready_evt.set()
    try:
        while not stop.wait(0.1):
            pass
    finally:
        rep.stop()
        srv.stop()


def _worker_config(rep: LocalReplica, body: bytes) -> Dict[str, Any]:
    cfg = json.loads(body.decode() or "{}")
    if "degraded" in cfg:
        rep.set_degraded(bool(cfg["degraded"]))
    return {"ok": True, "degraded": rep.decoder.degraded}


def _make_inject(rep: LocalReplica):
    """/inject POST handler: the npz handoff payload carries everything
    but the scalars, which ride a leading header (8-byte big-endian
    max_new + 1 flag byte, bit 0 = stream — the stdlib handler gives
    us only the body)."""
    def handler(body: bytes) -> Dict[str, Any]:
        enforce(len(body) > 9, "inject body too short")
        max_new = int.from_bytes(body[:8], "big")
        stream = bool(body[8] & 1)
        h = KVHandoff.from_bytes(body[9:])
        return {"rid": rep.inject(h, max_new, stream=stream)}

    return handler


def spawn_replicas(spec: Optional[str], n: int, role: str = "decode",
                   spec_kw: Optional[dict] = None,
                   log_dir: Optional[str] = None,
                   env: Optional[dict] = None,
                   timeout_s: float = 300.0,
                   warm: bool = True,
                   model: Optional[str] = None,
                   from_artifact: Optional[str] = None,
                   start_index: int = 0
                   ) -> List[HttpReplica]:
    """Fork ``n`` replica worker processes (``--worker`` CLI) and wait
    until each is serving (and warm, unless ``warm=False``). Returns
    connected :class:`HttpReplica` handles owning their process
    (``close()`` terminates it). ``model=`` tags the replicas for
    model-id routing; ``from_artifact=`` boots them trace-free from an
    aot artifact (``spec`` stays the traced fallback when given).
    ``start_index=`` offsets the worker names/port-files — the
    autoscaler spawns later workers into a fleet whose ``{role}0..``
    names are taken."""
    import tempfile

    enforce(spec is not None or from_artifact is not None,
            "spawn_replicas needs a spec, an artifact, or both")
    workdir = log_dir or tempfile.mkdtemp(prefix="pt-router-")
    os.makedirs(workdir, exist_ok=True)
    stem = f"{model + '-' if model else ''}{role}"
    procs = []
    for i in range(start_index, start_index + n):
        pf = os.path.join(workdir, f"{stem}{i}.port")
        if os.path.exists(pf):
            os.remove(pf)
        log = open(os.path.join(workdir, f"{stem}{i}.log"), "w")
        cmd = [sys.executable, "-m", "paddle_tpu.serving_router",
               "--worker", "--role", role,
               "--port", "0", "--port-file", pf]
        if spec:
            cmd += ["--spec", spec]
        if from_artifact:
            cmd += ["--from-artifact", from_artifact]
        if model:
            cmd += ["--model", model]
        if spec_kw:
            cmd += ["--spec-kw", json.dumps(spec_kw)]
        if not warm:
            cmd += ["--no-warm"]
        # workers inherit the caller's platform: the chip in production,
        # JAX_PLATFORMS=cpu where the caller (the test suite) set it
        wenv = dict(os.environ if env is None else env)
        procs.append((i, subprocess.Popen(
            cmd, env=wenv, stdout=log, stderr=subprocess.STDOUT), pf,
            log))
    out = []
    try:
        for i, p, pf, log in procs:
            # per-WORKER deadline: the workers boot in parallel, so by
            # the time worker i's wait starts, it has been warming all
            # along — a shared deadline would let a slow first warmup
            # starve the later waits
            deadline = time.monotonic() + timeout_s
            port = None
            while time.monotonic() < deadline:
                if p.poll() is not None:
                    raise EnforceError(
                        f"{role} worker {i} exited rc={p.returncode} "
                        f"before serving (log: {log.name})")
                if os.path.exists(pf):
                    with open(pf) as f:
                        port = int(f.read().strip())
                    break
                time.sleep(0.05)
            enforce(port is not None,
                    "%s worker %s did not serve within %ss (log: %s)",
                    role, i, timeout_s, log.name)
            rep = HttpReplica(f"http://127.0.0.1:{port}",
                              name=f"{stem}{i}", proc=p, model=model)
            if warm:
                is_ready = False
                while time.monotonic() < deadline:
                    try:
                        is_ready = bool(rep.healthz().get("ready"))
                    except OSError:
                        is_ready = False
                    if is_ready:
                        break
                    enforce(p.poll() is None,
                            "%s worker %s died during warmup (log: %s)",
                            role, i, log.name)
                    time.sleep(0.1)
                enforce(is_ready,
                        "%s worker %s never became ready within %ss "
                        "(warmup wedged? log: %s)", role, i, timeout_s,
                        log.name)
            out.append(rep)
    except BaseException:
        for _, p, _, _ in procs:
            if p.poll() is None:
                p.kill()
        raise
    finally:
        for _, _, _, log in procs:
            log.close()
    return out


def _parse_specs(spec: Optional[str]):
    """``--spec`` grammar -> ``[(model_tag, module:fn)]``:
    ``module:fn`` is the single untagged model (unchanged);
    ``name=module:fn,name2=module2:fn2`` is the multi-model fleet —
    each ``name`` tags its replicas for model-id routing
    (``Router.submit(model="name")``), and each worker process builds
    its OWN decoder, so per-model page pools come with the split."""
    if spec is None:
        return [(None, None)]
    if "=" not in spec:
        return [(None, spec)]
    out = []
    for part in spec.split(","):
        name, sep, s = part.partition("=")
        enforce(sep and name.strip() and s.strip(),
                "multi-model --spec must be name=module:fn[,name2=...]"
                ", got %r", part)
        out.append((name.strip(), s.strip()))
    names = [n for n, _ in out]
    enforce(len(set(names)) == len(names),
            "duplicate model name in --spec %r", spec)
    return out


def serve_main(spec: Optional[str], replicas: int = 2,
               prefill_workers: int = 0,
               port: int = 0, spec_kw: Optional[dict] = None,
               log_dir: Optional[str] = None,
               policy: Optional[SLOPolicy] = None,
               disagg_min_tokens: Optional[int] = 64,
               trace_sample: Optional[float] = None,
               textfile_path: Optional[str] = None,
               dispatch: str = "pull",
               prefix_hash_tokens: Optional[int] = 64,
               from_artifact: Optional[str] = None,
               autoscale: Optional[Sequence[int]] = None,
               reliability=None) -> Router:
    """One-command serving bring-up (``python -m paddle_tpu.launch
    --serve``): spawn the replica (and prefill) worker processes, build
    the router over them, and serve the router front-end (POST /submit
    /stream /drain + /statusz + /podz replica fan-out) on ``port``.
    ``spec`` may be multi-model (see :func:`_parse_specs`): replicas
    spawn per model, tagged for model-id routing. ``from_artifact``
    boots the replicas trace-free from an aot artifact (single-model
    fleets; ``spec`` stays the traced fallback).

    ``autoscale=(min, max)`` runs the autoscaling control plane: the
    initial fleet is clamped into ``[min, max]`` and a
    :class:`~paddle_tpu.autoscale.Scaler` (attached as
    ``router.scaler`` and as the /statusz "autoscale" section) grows
    and shrinks it against the router's measured signals — new
    replicas spawn through the SAME artifact pre-warm path the
    bring-up used. Returns the running router — the caller owns
    ``close(replicas=True)`` (and ``router.scaler.stop()`` first when
    autoscaled)."""
    pairs = _parse_specs(spec)
    enforce(from_artifact is None or len(pairs) == 1,
            "--from-artifact boots a single-model fleet (one artifact "
            "holds one model's programs); got %s model specs",
            len(pairs))
    if autoscale is not None:
        amin, amax = (int(autoscale[0]), int(autoscale[1]))
        enforce(len(pairs) == 1,
                "--autoscale manages a single-model fleet; got %s "
                "model specs", len(pairs))
        enforce(1 <= amin <= amax,
                "--autoscale needs 1 <= min <= max, got %s,%s",
                amin, amax)
        replicas = min(max(replicas, amin), amax)
    reps, pfs = [], []
    for m, sp in pairs:
        reps += spawn_replicas(sp, replicas, spec_kw=spec_kw,
                               log_dir=log_dir, model=m,
                               from_artifact=from_artifact)
        if prefill_workers:
            pfs += spawn_replicas(sp, prefill_workers, role="prefill",
                                  spec_kw=spec_kw, log_dir=log_dir,
                                  model=m)
    router = Router(reps, prefill_workers=pfs, policy=policy,
                    disagg_min_tokens=disagg_min_tokens,
                    trace_sample=trace_sample,
                    textfile_path=textfile_path,
                    dispatch=dispatch,
                    prefix_hash_tokens=prefix_hash_tokens,
                    reliability=reliability)
    router.start_server(port=port)
    if autoscale is not None:
        from .autoscale import AutoscalePolicy, Scaler

        model0, spec0 = pairs[0]
        counter = iter(range(replicas, 1_000_000))

        def _spawn():
            # the artifact pre-warm path: each scale-up boots exactly
            # like bring-up did (trace-free when an artifact is given,
            # ready-gated either way), under a fresh worker index
            return spawn_replicas(spec0, 1, spec_kw=spec_kw,
                                  log_dir=log_dir, model=model0,
                                  from_artifact=from_artifact,
                                  start_index=next(counter))[0]

        scaler = Scaler(router,
                        AutoscalePolicy(min_replicas=amin,
                                        max_replicas=amax),
                        _spawn)
        scaler.attach(router.server)
        router.scaler = scaler.start()
    return router


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving_router",
        description="serving replica worker / router front-end")
    ap.add_argument("--worker", action="store_true",
                    help="run ONE replica worker (spawned by "
                    "spawn_replicas / launch --serve)")
    ap.add_argument("--spec", default=None,
                    help="module:function returning the replica's "
                    "BatchedDecoder; router mode also accepts the "
                    "multi-model form name=module:fn,name2=module2:fn2"
                    " (optional when --from-artifact boots trace-free)")
    ap.add_argument("--from-artifact", dest="from_artifact",
                    default=None,
                    help="aot artifact directory (or checkpoint root "
                    "holding aot_step_N) — boot the replica(s) "
                    "trace-free from serialized programs; --spec "
                    "becomes the traced fallback on fingerprint "
                    "mismatch")
    ap.add_argument("--model", default=None,
                    help="(worker mode) model tag for model-id "
                    "routing; set by the router spawner for "
                    "multi-model fleets")
    ap.add_argument("--spec-kw", default=None,
                    help="JSON kwargs for the spec function")
    ap.add_argument("--role", default="decode",
                    choices=("decode", "prefill"))
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--port-file", default=None,
                    help="write the bound port here once serving")
    ap.add_argument("--no-warm", dest="warm", action="store_false",
                    help="skip the warmup request (report ready only "
                    "after the first real dispatch)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="(router mode) decode worker processes")
    ap.add_argument("--prefill-workers", type=int, default=0,
                    help="(router mode) dedicated prefill workers")
    ap.add_argument("--trace-sample", dest="trace_sample", type=float,
                    default=None,
                    help="(router mode) head-based request-trace "
                    "sampling rate 0..1 (default: PT_TRACE_SAMPLE or "
                    "1.0)")
    ap.add_argument("--textfile", dest="textfile", default=None,
                    help="(router mode) write the metrics exposition "
                    "here periodically (node-exporter textfile "
                    "collector; also env PT_ROUTER_TEXTFILE)")
    ap.add_argument("--dispatch", default="pull",
                    choices=("pull", "push"),
                    help="(router mode) pull = replicas pull from the "
                    "central work-stealing queue (default); push = "
                    "legacy least-loaded placement")
    ap.add_argument("--prefix-hash-tokens", dest="prefix_hash_tokens",
                    type=int, default=64,
                    help="(router mode) route by a rolling hash of "
                    "the first N prompt tokens so shared system "
                    "prompts land on one warm replica (0 disables)")
    ap.add_argument("--autoscale", default=None, metavar="MIN,MAX",
                    help="(router mode) run the autoscaling control "
                    "plane: grow/shrink the fleet between MIN and MAX "
                    "replicas against the measured load signals "
                    "(spawns ride --from-artifact when given)")
    ap.add_argument("--reliability", action="store_true",
                    help="(router mode) turn on the request "
                    "reliability plane: end-to-end deadlines, retry "
                    "budgets, hedged dispatch, gray-failure "
                    "quarantine")
    ap.add_argument("--deadline-s", dest="deadline_s", type=float,
                    default=None,
                    help="(router mode) default end-to-end request "
                    "deadline budget in seconds (implies "
                    "--reliability)")
    args = ap.parse_args(argv)
    autoscale = None
    if args.autoscale:
        parts = args.autoscale.split(",")
        enforce(len(parts) == 2, "--autoscale must be MIN,MAX, got %r",
                args.autoscale)
        autoscale = (int(parts[0]), int(parts[1]))
    enforce(args.spec or args.from_artifact,
            "need --spec module:fn and/or --from-artifact DIR")
    kw = json.loads(args.spec_kw) if args.spec_kw else None
    if args.worker:
        run_worker(args.spec, role=args.role, port=args.port,
                   port_file=args.port_file, spec_kw=kw,
                   warm=args.warm, from_artifact=args.from_artifact,
                   model=args.model)
        return 0
    reliability = None
    if args.reliability or args.deadline_s is not None:
        from .resilience import reliability as _rel_mod

        reliability = _rel_mod.ReliabilityConfig(
            deadline_s=args.deadline_s)
    router = serve_main(args.spec, replicas=args.replicas,
                        prefill_workers=args.prefill_workers,
                        port=args.port, spec_kw=kw,
                        trace_sample=args.trace_sample,
                        textfile_path=args.textfile,
                        dispatch=args.dispatch,
                        prefix_hash_tokens=(args.prefix_hash_tokens
                                            or None),
                        from_artifact=args.from_artifact,
                        autoscale=autoscale,
                        reliability=reliability)
    print(f"[router] serving on {router.server.url()} over "
          f"{args.replicas} replica(s)"
          + (f", autoscaling {autoscale[0]}..{autoscale[1]}"
             if autoscale else ""), file=sys.stderr)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        scaler = getattr(router, "scaler", None)
        if scaler is not None:
            scaler.stop()
        router.close(replicas=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
