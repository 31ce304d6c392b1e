"""A serving cell: one process; ``BatchedDecoder`` behind an in-process
``Router`` + ``LocalReplica``; closed-loop clients that block on their
token streams.

Stamps are the host's: a request's submit time is taken by the client
just before ``Router.submit``; a token's time is the stamp the router's
stream record carries (taken after the tick's ``device_get``), or the
client's receipt where the record has none. A request that fails, is
shed, or is unfinished when the window and the drain limit end counts in
``failed`` and has no latency.
"""

from __future__ import annotations

import gc
import statistics
import sys
import threading
import time

import jax
import numpy as np

from . import check, loadgen, program, runtime, stats

TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


class Client(threading.Thread):
    """One closed-loop caller: submit, read the stream to its end, then
    the next request. Holds no JAX state and never spins."""

    def __init__(self, c: int, plan, router, stop: threading.Event):
        super().__init__(daemon=True, name=f"bench-client-{c}")
        self.plan, self.router, self.stop_ev = plan, router, stop
        self.records = []

    def one(self, prompt, max_new) -> dict:
        rec = {"prompt": prompt, "max_new": max_new, "stamps": [],
               "tokens": [], "ok": False, "t_submit": time.perf_counter(),
               "wait_s": None}
        self.records.append(rec)
        try:
            ticket = self.router.submit(prompt, max_new, stream=True)
            if ticket.shed:
                return rec
            for ev in ticket.stream:
                now = time.perf_counter()
                if "i" in ev:
                    rec["tokens"].append(int(ev["tok"]))
                    rec["stamps"].append(ev.get("t") or now)
                elif ev.get("event") == "end":
                    rec["ok"] = len(rec["tokens"]) == max_new
                elif ev.get("event") == "error":
                    rec["error"] = ev.get("error")
            if ticket.t_dispatched:
                rec["wait_s"] = ticket.t_dispatched - ticket.t_submit
        except Exception as e:  # noqa: BLE001 — a failed request, counted
            rec["error"] = repr(e)
        rec["t_done"] = time.perf_counter()
        return rec

    def run(self):
        while not self.stop_ev.is_set():
            self.one(*self.plan.next_request())


def window_numbers(every, t0: float, t1: float):
    """(requests submitted in [t0, t1), those of them that finished,
    their times to first token, every gap that closed inside the window,
    tokens stamped inside it)."""
    recs = [r for r in every if t0 <= r["t_submit"] < t1]
    done = [r for r in recs if r["ok"]]
    ttft = [r["stamps"][0] - r["t_submit"] for r in done]
    gaps, tokens_in = [], 0
    for r in every:
        st = r["stamps"]
        tokens_in += sum(1 for t in st if t0 <= t <= t1)
        gaps += [b - a for a, b in zip(st, st[1:]) if t0 <= b <= t1]
    return recs, done, ttft, gaps, tokens_in


def warm_up(mix, dims, serve, router, seed) -> None:
    """Every slot, every prompt bucket this mix can produce and the
    decode step run once before the window: ``slots`` concurrent
    requests whose prompts cycle through the buckets, a few tokens each.
    Junk prompts from a seed of their own."""
    buckets = loadgen.prompt_buckets(mix, serve["prompt_bucket"],
                                     serve["capacity"])
    lo = mix["prompt_tokens"]["min"]
    rng = np.random.default_rng([int(seed), 4])
    tickets = []
    for s in range(max(serve["slots"], len(buckets))):
        b = buckets[s % len(buckets)]
        plen = max(lo, b - serve["prompt_bucket"] + 1)
        tickets.append(router.submit(
            rng.integers(0, dims.vocab, plen).astype(np.int32), 4,
            stream=True))
    for t in tickets:
        for _ in t.stream:
            pass
        t.wait(timeout=600)
    log(f"warmed buckets {buckets} over {len(tickets)} requests")


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float, control: bool = False, break_decoder=None) -> dict:
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    dims = fam.Dims.from_config(cfg)
    serve = cfg["serve"]
    model = program.build_model(fam, cfg, dims, seed, cfg["dtype"],
                                serve["capacity"], remat=False)
    dec, replica, router = program.build_serving(model, serve)
    if break_decoder is not None:
        break_decoder(dec)
    plan = loadgen.ClosedLoopPlan(mix, dims.vocab, seed)
    try:
        warm_up(mix, dims, serve, router, seed)
        stop = threading.Event()
        clients = [Client(c, plan, router, stop)
                   for c in range(plan.clients)]
        compiles = runtime.CompileCounter()
        for c in clients:
            c.start()
        # the closed loop starts before the window: sixteen callers
        # submitting at once is a start-up burst no steady deployment
        # sees, so it passes during set-up
        time.sleep(float(mix["ramp_s"]))
        compiles.active = True
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        log(f"setup_s {setup_s:.3f}")
        ticks0 = (dec.tick_count, dec.tick_tokens, dec.tick_capacity)
        time.sleep(seconds)
        t1 = time.perf_counter()
        ticks1 = (dec.tick_count, dec.tick_tokens, dec.tick_capacity)
        # the traced seconds follow the window, under the same load, so
        # the window's own numbers never carry the profiler
        xplane = None
        if trace:
            tracer = runtime.Tracer(cell.name)
            tracer.start()
            time.sleep(TRACE_SECONDS)
            xplane = tracer.stop()
        stop.set()
        deadline = time.perf_counter() + float(mix["drain_s"])
        for c in clients:
            c.join(timeout=max(0.0, deadline - time.perf_counter()))
        hung = sum(c.is_alive() for c in clients)
        compiles.active = False
        compiles.close()
        mem = runtime.memory_peak_bytes(cell.chips)
    finally:
        router.close()
        replica.stop()

    window = t1 - t0
    every = [r for c in clients for r in list(c.records)]
    recs, done, ttft, gaps, tokens_in = window_numbers(every, t0, t1)
    for part in (1 / 3, 2 / 3):     # informational: shorter windows
        _, _, tt, gg, tok = window_numbers(every, t0, t0 + part * window)
        if tt and gg:
            log(f"first {part * window:.0f} s alone: "
                f"{tok / (part * window):.2f} tokens/s, ttft p95 "
                f"{stats.quantile(tt, 0.95) * 1e3:.1f} ms (n={len(tt)}), "
                f"itl p95 {stats.quantile(gg, 0.95) * 1e3:.2f} ms")
    waits = [r["wait_s"] for r in done if r["wait_s"] is not None]
    d_ticks, d_tok, d_cap = (b - a for a, b in zip(ticks0, ticks1))
    log(f"{len(recs)} requests submitted in {window:.2f} s, {len(done)} "
        f"finished, {hung} clients hung at the drain limit; "
        f"{tokens_in} tokens, {d_ticks} ticks; ttft p50 "
        f"{statistics.median(ttft) * 1e3 if ttft else float('nan'):.1f} ms "
        f"(n={len(ttft)}), itl p50 "
        f"{statistics.median(gaps) * 1e3 if gaps else float('nan'):.2f} ms "
        f"(n={len(gaps)}); compiles in window {compiles.count} "
        f"{compiles.names[:4]}")

    # the reference runs after the program's state is freed
    finished = [(r["prompt"], np.asarray(r["tokens"], np.int32))
                for r in done]
    live_ctx = [len(r["prompt"]) + len(r["tokens"]) / 2 for r in done]
    del dec, replica, router, model, clients
    gc.collect()
    n_check = int(mix["check_requests"])
    max_out = int(mix["output_tokens"]["max"])
    idx = check.pick_sample(finished, n_check, seed)
    sample = [finished[i] for i in idx]
    numbers = {"served_gap_max": float("inf")}
    t_ref = time.perf_counter()
    if sample:
        lg, served, mask = check.serve_reference(
            seed, fam, dims, cfg["dtype"], sample, n_check,
            serve["capacity"], max_out, "f32")
        gap, same, n = check.serve_gap(lg, served, mask)
        numbers["served_gap_max"] = gap
        log(f"reference over {len(sample)} requests, {n} served tokens, "
            f"{same} equal to the reference's best, widest gap {gap:.4g} "
            f"sd, in {time.perf_counter() - t_ref:.1f} s")
    ok = check.judge(numbers, check.load_limits(cell), "check")
    ok &= check.judge({"compiles_in_window": compiles.count},
                      {"compiles_in_window": 0})
    ok &= len(done) > 0
    out = {
        "correct": bool(ok), "attempted": len(recs),
        "failed": len(recs) - len(done), "numbers": numbers,
        "end_to_end": {
            "serve_tokens_per_s": tokens_in / window,
            "itl_p95_ms": (stats.quantile(gaps, 0.95) * 1e3
                           if gaps else None),
            "setup_s": setup_s},
        "memory_peak_bytes": mem, "xplane": xplane,
        "run": {"kind": "serve", "family": fam, "dims": dims, "config": cfg,
                "traffic": mix, "window_s": window, "ticks": d_ticks,
                "tick_tokens": d_tok, "tick_capacity": d_cap,
                "router_wait_s": waits, "ttft_s": ttft, "gaps_s": gaps,
                "mean_context_tokens": (float(np.mean(live_ctx))
                                        if live_ctx else None),
                "memory_peak_bytes": mem, "device": device},
    }
    if control and sample:
        lc, _, _ = check.serve_reference(
            seed, fam, dims, cfg["dtype"], sample, n_check,
            serve["capacity"], max_out, "fp8")
        ctl_tokens = np.asarray(jax.device_get(lc.argmax(-1)), np.int32)
        out["control_numbers"] = {
            "served_gap_max": check.serve_gap(lg, ctl_tokens, mask)[0]}
    return out
