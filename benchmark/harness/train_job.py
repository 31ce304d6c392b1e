"""A training cell: one process, one ``parallel.Trainer``, fenced steps.

Set-up builds the trainer, drives it through its first steps (which
compile, and whose losses, first gradient and parameter change are what
``correct`` compares) and hands that same object to the window. Every
step of the window is dispatched, then fenced by a host fetch of its
loss; a new batch from a host-side ring is placed each step. With
``trace`` a few more steps of the same loop run under the profiler once
the window has closed.
"""

from __future__ import annotations

import gc
import statistics
import sys
import time

import jax
import numpy as np

from . import check, loadgen, program, runtime

TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(f"[train] {msg}", file=sys.stderr, flush=True)


def run(cell, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float, control: bool = False, break_step=None) -> dict:
    """Run the cell and return the job's result (see ``run.py``).
    ``control`` also computes the lower-precision control's readings;
    ``break_step`` (tests only) wraps the trainer's step call."""
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    dims = fam.Dims.from_config(cfg)
    rows, seq, lr = int(mix["rows"]), int(mix["seq"]), float(mix["lr"])
    k_check = int(mix["check_steps"])
    warm = max(int(mix["warmup_steps"]), k_check)

    model = program.build_model(fam, cfg, dims, seed, cfg["dtype"], seq,
                                remat=bool(cfg["train"]["remat"]))
    trainer = program.build_trainer(model, lr, cfg["train"]["amp"])
    step_call = trainer.train_step
    if break_step is not None:
        step_call = break_step(trainer)
    ring = loadgen.train_ring(mix, dims.vocab, seed)
    sharding = trainer.data_sharding()
    feed = lambda i: jax.device_put(ring[i % len(ring)], sharding)

    got = {"losses": []}
    for i in range(warm):
        loss, _ = step_call(feed(i))
        if i < k_check:
            got["losses"].append(float(loss))
        if i == 0:
            names = sorted(trainer.params)
            got["grad_norms"] = {
                n: v / (1.0 - check.ADAM_B1) for n, v in check.leaf_norms(
                    {n: s["m"] for n, s in
                     zip(names, trainer.opt_state["leaf"])}).items()}
        if i == k_check - 1:
            got["delta_norms"] = check.delta_norms(trainer.params, seed,
                                                   fam.leaf_rule)
    jax.block_until_ready(loss)
    log(f"first losses {got['losses']}")

    compiles = runtime.CompileCounter()
    step_s, dispatch_s = [], []
    annotate = jax.profiler.TraceAnnotation

    def one_step(i):
        t_a = time.perf_counter()
        with annotate("bench.feed"):
            batch = feed(i)
        with annotate("bench.dispatch"):
            loss, _ = step_call(batch)
            t_b = time.perf_counter()
        with annotate("bench.fetch"):
            value = float(loss)
        return value, t_b - t_a, time.perf_counter()

    compiles.active = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    log(f"setup_s {setup_s:.3f}")
    i, t_end, last = warm, t0, float("nan")
    while time.perf_counter() - t0 < seconds:
        t_a = time.perf_counter()
        last, d, t_end = one_step(i)
        dispatch_s.append(d)
        step_s.append(t_end - t_a)
        i += 1
    # the traced steps follow the window: same loop, same feed, so the
    # window's own numbers never carry the profiler
    xplane = None
    if trace:
        tracer = runtime.Tracer(cell.name)
        tracer.start()
        t_trace = time.perf_counter()
        while time.perf_counter() - t_trace < TRACE_SECONDS:
            one_step(i)
            i += 1
        xplane = tracer.stop()
    compiles.active = False
    compiles.close()
    steps = len(step_s)
    window = t_end - t0
    tokens = steps * rows * seq
    mem = runtime.memory_peak_bytes(cell.chips)
    log(f"{steps} steps in {window:.3f} s, last loss {last:.4f}, "
        f"median step {statistics.median(step_s) * 1e3:.2f} ms, compiles in "
        f"window {compiles.count} {compiles.names[:4]}")

    # the reference runs after the program's state is freed, outside
    # both set-up and window
    del trainer, model, step_call, feed, one_step, loss
    gc.collect()
    t_ref = time.perf_counter()
    ref = check.train_reference(seed, fam, dims, ring[:k_check], lr, "f32")
    numbers = check.compare_train(got, ref)
    log(f"reference losses {ref['losses']} in "
        f"{time.perf_counter() - t_ref:.1f} s")
    ok = check.judge(numbers, check.load_limits(cell), "check")
    ok &= check.judge({"compiles_in_window": compiles.count},
                      {"compiles_in_window": 0})
    ok &= bool(np.isfinite(last)) and steps > 0
    out = {
        "correct": bool(ok), "attempted": steps, "failed": 0,
        "numbers": numbers,
        "end_to_end": {"train_tokens_per_s": tokens / window,
                       "setup_s": setup_s},
        "memory_peak_bytes": mem, "xplane": xplane,
        "run": {"kind": "train", "family": fam, "dims": dims, "config": cfg,
                "traffic": mix, "window_s": window, "steps": steps,
                "step_s": step_s, "dispatch_s": dispatch_s,
                "tokens_per_s": tokens / window,
                "memory_peak_bytes": mem, "device": device},
    }
    if control:
        ctl = check.train_reference(seed, fam, dims, ring[:k_check], lr,
                                    "fp8")
        out["control_numbers"] = check.compare_train(ctl, ref)
    return out
