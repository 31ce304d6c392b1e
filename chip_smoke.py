#!/usr/bin/env python
"""On-chip smoke: GPT-small trains, serves and answers HTTP on one TPU.

    python chip_smoke.py             # one chip: train, serve, http
    python chip_smoke.py --chips 4   # four chips: the dp=4 trainer only

The quickest proof that the system still starts on the chip. It drives
the main path once through the entry points a user calls, at the full
width and depth of ``GPTConfig.small()`` with random weights from a
fixed seed, and checks what comes out by the repo's own means. It
measures nothing: the step times and rates it prints are informational,
one run each.

Rules it keeps:

- NO fallback. A phase that finds ``jax.devices()[0].platform != "tpu"``
  fails at once, before it builds a model; any failed check fails the
  run; the last line ``{"ok": true, "device": {...}}`` is printed only
  when every phase passed.
- One process owns the chip at a time. This parent never initialises a
  JAX backend: each phase runs in a child process, one after the other
  (``http``'s child is ``python -m paddle_tpu.launch --serve``, whose
  single replica worker owns the chip).
- The compile cache is placed by ``utils.flops.enable_compile_cache``
  (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``)
  in every process, so the phases share one cache.

Nothing happens at import: the ``http`` replica worker imports this
module for :func:`serve_replica`.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0
# train: GPTConfig.small() at max_position 1024, batch 8 x 1024 tokens
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
TRAIN_WARMUP, TRAIN_STEPS = 2, 5
# serve: the same config behind an 8-slot arena of 2048 positions
SERVE_SLOTS, SERVE_CAPACITY = 8, 2048
SERVE_REQUESTS, SERVE_MAX_NEW = 16, 32
PROMPT_MIN, PROMPT_MAX = 32, 512
# prompts pad up to a multiple of this before prefill: one compile per
# bucket, so 128 bounds the 32..512 range to four prefill programs
PROMPT_BUCKET = 128
N_REFERENCE = 2          # prompts re-decoded by model.greedy_decode
HTTP_REQUESTS = 3
HTTP_SPEC = "chip_smoke:serve_replica"   # the worker's decoder factory
# dp=4: global batch 8, 3 steps on four chips vs the same on one
DP_STEPS, DP_REL_TOL = 3, 1e-2

PHASE_TIMEOUT_S = 900.0  # a hung child must not outlive the run


class Checks:
    """Named pass/fail checks of one phase; every one is printed, and
    the phase fails if any did."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{self.phase}] check {name}: {'ok' if ok else 'FAIL'}"
              f"{' — ' + detail if detail else ''}", flush=True)
        if not ok:
            self.failed.append(name)
        return bool(ok)

    def info(self, msg: str) -> None:
        print(f"[{self.phase}] {msg}", flush=True)


def require_tpu(phase: str, count: int = 1):
    """The no-fallback guard: the devices, or exit non-zero before any
    model is built. Prints the DEVICE line the parent reads."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"[{phase}] FAIL: jax.devices()[0].platform is "
              f"{d.platform!r}, not 'tpu' — chip_smoke.py does not run "
              "on anything else", flush=True)
        sys.exit(3)
    if len(devs) < count:
        print(f"[{phase}] FAIL: needs {count} TPU devices, found "
              f"{len(devs)}", flush=True)
        sys.exit(3)
    print("DEVICE " + json.dumps({"platform": d.platform,
                                  "kind": d.device_kind,
                                  "count": len(devs)}), flush=True)
    return devs


def model_config(max_position: int):
    """GPTConfig.small() — 12 layers, hidden 768, 12 q / 4 kv heads,
    head dim 64, SwiGLU 2048, vocab 32000 — at ``max_position``."""
    from paddle_tpu.models import gpt as G

    cfg = G.GPTConfig.small()
    cfg.max_position = max_position
    return cfg


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for e in os.listdir(cache_dir)
                   if e.endswith("-cache"))
    except OSError:
        return 0


def _on_tpu(x) -> bool:
    return all(d.platform == "tpu" for d in x.devices())


def _make_trainer(mesh=None):
    """``parallel.Trainer`` (the README quick-start entry) over
    GPT-small with remat, mixed_bf16 and the fused linear-CE loss."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer, parallel
    from paddle_tpu.models import gpt as G

    pt.seed(SEED)
    cfg = model_config(TRAIN_SEQ)
    cfg.remat = True
    model = G.GPTForCausalLM(cfg)

    def loss_builder(params, buffers, rng, ids):
        loss, new_buffers = model.functional_call(
            params, ids, buffers=buffers, rng=rng, training=True,
            method="forward_loss")
        return loss, ({}, new_buffers)

    trainer = parallel.Trainer(model, optimizer.Adam(1e-3), loss_builder,
                               mesh=mesh, amp="mixed_bf16")
    return cfg, trainer


def _token_batch(cfg, trainer, batch: int):
    import jax
    import numpy as np

    ids = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (batch, TRAIN_SEQ)).astype(np.int32)
    return jax.device_put(ids, trainer.data_sharding())


def _fenced_steps(trainer, batch, n: int):
    """``n`` train steps on the same batch, each fenced by a host fetch
    of the loss. Returns (losses, seconds per step, last loss array)."""
    losses, secs, loss = [], [], None
    for _ in range(n):
        t0 = time.perf_counter()
        loss, _ = trainer.train_step(batch)
        losses.append(float(loss))
        secs.append(time.perf_counter() - t0)
    return losses, secs, loss


# ---------------------------------------------------------------------------
# phases (each runs in its own child process)
# ---------------------------------------------------------------------------

def phase_train() -> int:
    c = Checks("train")
    require_tpu("train")
    import numpy as np

    from paddle_tpu.telemetry.diag import peak_memory_bytes
    from paddle_tpu.utils.flops import enable_compile_cache

    c.info(f"compile cache: {enable_compile_cache()}")
    cfg, trainer = _make_trainer()
    batch = _token_batch(cfg, trainer, TRAIN_BATCH)
    warm, warm_s, _ = _fenced_steps(trainer, batch, TRAIN_WARMUP)
    losses, secs, loss = _fenced_steps(trainer, batch, TRAIN_STEPS)
    text = trainer.lower_step(batch).compile().as_text()

    med = float(np.median(secs))
    c.info(f"first step (compile included) {warm_s[0]:.1f} s; "
           f"{TRAIN_STEPS} steps of {TRAIN_BATCH}x{TRAIN_SEQ} tokens: "
           f"median {med * 1e3:.1f} ms/step, "
           f"{TRAIN_BATCH * TRAIN_SEQ / med:.0f} tokens/s (one run)")
    c.info(f"losses: warm-up {[round(x, 4) for x in warm]}, "
           f"then {[round(x, 4) for x in losses]}")
    c.info(f"peak device memory: {peak_memory_bytes()} bytes")
    c.check("losses_finite", bool(np.all(np.isfinite(warm + losses))))
    c.check("loss_decreases", losses[-1] < losses[0] < warm[0],
            f"{warm[0]:.4f} -> {losses[0]:.4f} -> {losses[-1]:.4f}")
    c.check("flash_kernel_in_step", "tpu_custom_call" in text,
            f"{text.count('tpu_custom_call')} tpu_custom_call in the "
            "compiled train step")
    c.check("loss_on_tpu", _on_tpu(loss), str(sorted(
        str(d) for d in loss.devices())))
    return 1 if c.failed else 0


def _serve_prompts(vocab: int, n: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, n)
    return [rng.integers(0, vocab, int(k)).astype(np.int32) for k in lens]


def _make_decoder():
    """The full-size serving arena — shared by the ``serve`` phase and
    the ``http`` replica worker (same weights: fixed seed)."""
    import paddle_tpu as pt
    from paddle_tpu.models import gpt as G
    from paddle_tpu.serving import BatchedDecoder

    pt.seed(SEED)
    model = G.GPTForCausalLM(model_config(SERVE_CAPACITY)).eval()
    return BatchedDecoder(model, slots=SERVE_SLOTS,
                          capacity=SERVE_CAPACITY,
                          prompt_bucket=PROMPT_BUCKET)


def serve_replica():
    """``--spec chip_smoke:serve_replica``: the decoder factory the
    ``http`` phase's replica worker calls. Same guard as every phase —
    a worker that is not on the TPU exits instead of serving."""
    require_tpu("http-worker")
    from paddle_tpu.utils.flops import enable_compile_cache

    enable_compile_cache()
    return _make_decoder()


def phase_serve() -> int:
    c = Checks("serve")
    require_tpu("serve")
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.attention import decode_flash_ok
    from paddle_tpu.telemetry.diag import peak_memory_bytes
    from paddle_tpu.utils.flops import enable_compile_cache

    c.info(f"compile cache: {enable_compile_cache()}")
    dec = _make_decoder()
    cfg = dec.model.cfg
    prompts = _serve_prompts(cfg.vocab_size, SERVE_REQUESTS)
    t0 = time.perf_counter()
    rids = [dec.submit(p, SERVE_MAX_NEW) for p in prompts]
    outs = dec.run()
    dt = time.perf_counter() - t0
    gen = [np.asarray(outs[r]).reshape(-1) for r in rids]
    c.info(f"{SERVE_REQUESTS} requests (prompts "
           f"{min(map(len, prompts))}..{max(map(len, prompts))} tokens, "
           f"{SERVE_MAX_NEW} new each) in {dt:.1f} s, compiles included: "
           f"{sum(map(len, gen)) / dt:.1f} tokens/s (one run); "
           f"{dec.tick_count} ticks")
    c.check("all_requests_answered",
            all(len(g) == SERVE_MAX_NEW for g in gen),
            f"lengths {sorted(set(map(len, gen)))}")
    c.check("tokens_in_vocab", all(
        g.size and 0 <= g.min() and g.max() < cfg.vocab_size
        for g in gen))
    # the plain path on the same chip: greedy KV-cached generation
    for i in range(N_REFERENCE):
        p = prompts[i]
        ref = np.asarray(dec.model.greedy_decode(
            jnp.asarray(p)[None], len(p) + SERVE_MAX_NEW))[0, len(p):]
        same = int(np.sum(ref == gen[i][:len(ref)]))
        c.check(f"matches_greedy_decode[{i}]",
                len(ref) == len(gen[i]) and same == len(ref),
                f"{same}/{len(ref)} tokens equal (prompt {len(p)})")
    text = dec.lower_step().compile().as_text()
    want_kernel = decode_flash_ok(
        SERVE_CAPACITY, cfg.hidden_size // cfg.num_heads)
    has_kernel = "tpu_custom_call" in text
    c.check("decode_kernel_in_step", want_kernel == has_kernel,
            f"decode_flash_ok={want_kernel}, "
            f"{text.count('tpu_custom_call')} tpu_custom_call in the "
            "compiled decode step")
    c.info(f"peak device memory: {peak_memory_bytes()} bytes")
    return 1 if c.failed else 0


def phase_dp4() -> int:
    """Trainer over build_mesh(dp=4) of the four local chips against the
    same steps on one chip, both in this process."""
    c = Checks("dp4")
    devs = require_tpu("dp4", count=4)[:4]
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.utils.flops import enable_compile_cache

    c.info(f"compile cache: {enable_compile_cache()}")
    cfg, one = _make_trainer(pt.build_mesh(dp=1, devices=devs[:1]))
    ref, ref_s, _ = _fenced_steps(
        one, _token_batch(cfg, one, TRAIN_BATCH), DP_STEPS)
    del one
    cfg, tr = _make_trainer(pt.build_mesh(dp=4, devices=devs))
    batch = _token_batch(cfg, tr, TRAIN_BATCH)
    got, got_s, loss = _fenced_steps(tr, batch, DP_STEPS)
    text = tr.lower_step(batch).compile().as_text()

    c.info(f"1 chip: losses {[round(x, 5) for x in ref]}, last step "
           f"{ref_s[-1] * 1e3:.1f} ms; dp=4: losses "
           f"{[round(x, 5) for x in got]}, last step "
           f"{got_s[-1] * 1e3:.1f} ms (one run)")
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    c.check("losses_finite", bool(np.all(np.isfinite(got + ref))))
    c.check("losses_agree", rel <= DP_REL_TOL,
            f"max relative difference {rel:.2e} (limit {DP_REL_TOL})")
    want = {d.id for d in devs}
    on_all = [name for name, p in tr.params.items()
              if {s.device.id for s in p.addressable_shards} != want]
    c.check("params_on_all_four", not on_all,
            f"{len(tr.params)} parameters, {len(on_all)} not on all "
            f"of devices {sorted(want)}")
    shards = [(s.device.id, s.data.shape[0])
              for s in batch.addressable_shards]
    c.check("batch_quarter_per_chip",
            {d for d, _ in shards} == want
            and all(n == TRAIN_BATCH // 4 for _, n in shards),
            f"(device, rows) {sorted(shards)}")
    c.check("all_reduce_in_step", "all-reduce" in text,
            f"{text.count('all-reduce(')} all-reduce in the compiled "
            "step")
    c.check("flash_kernel_in_step", "tpu_custom_call" in text,
            f"{text.count('tpu_custom_call')} tpu_custom_call")
    c.check("loss_on_tpu", _on_tpu(loss))
    return 1 if c.failed else 0


PHASES = {"train": phase_train, "serve": phase_serve, "dp4": phase_dp4}


# ---------------------------------------------------------------------------
# parent: runs the phases one child at a time; never touches a backend
# ---------------------------------------------------------------------------

def _run_child(cmd, timeout_s: float = PHASE_TIMEOUT_S):
    """Run ``cmd`` echoing its stdout; returns (rc, DEVICE dict|None).
    The child is killed at ``timeout_s``."""
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            text=True)
    killer = threading.Timer(timeout_s, proc.kill)
    killer.start()
    device = None
    try:
        for line in proc.stdout:
            if line.startswith("DEVICE "):
                device = json.loads(line[len("DEVICE "):])
            print(line, end="", flush=True)
        return proc.wait(), device
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def _http(url: str, body=None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return r.status, json.loads(r.read().decode() or "{}")


def _wait_for(what: str, fn, timeout_s: float, proc, every: float = 1.0):
    """Poll ``fn`` until it returns a truthy value; fails when the
    deadline passes or the server process exits."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server exited rc={proc.returncode} "
                               f"while waiting for {what}")
        try:
            out = fn()
            if out:
                return out
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(every)
    raise RuntimeError(f"timed out after {timeout_s:.0f} s waiting for "
                       f"{what}")


def phase_http():
    """``launch --serve`` with ONE replica worker (one chip, one owner):
    /readyz, three /submit + /drain, the worker's /statusz must name the
    TPU, SIGTERM by exact pid, rc 0. Runs in the parent, which only
    speaks HTTP. Returns (rc, device dict as the worker reports it)."""
    c = Checks("http")
    vocab = model_config(SERVE_CAPACITY).vocab_size
    log_dir = os.path.join(HERE, "chiprun_out", "chip_smoke_http")
    os.makedirs(log_dir, exist_ok=True)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, "-m", "paddle_tpu.launch", "--serve",
           "--spec", HTTP_SPEC, "--nproc", "1",
           "--prefill-workers", "0", "--port", str(port),
           "--log-dir", log_dir]
    c.info(" ".join(cmd[1:]))
    t0 = time.perf_counter()
    # its own process group: whatever happens, the workers go with it
    proc = subprocess.Popen(cmd, cwd=HERE, start_new_session=True)
    base = f"http://127.0.0.1:{port}"
    device = None
    try:
        _wait_for("/readyz", lambda: _http(base + "/readyz")[0] == 200,
                  PHASE_TIMEOUT_S, proc)
        c.info(f"ready after {time.perf_counter() - t0:.1f} s")
        import numpy as np   # host arrays only: no backend in this process

        rng = np.random.default_rng(SEED + 1)
        rids = []
        for _ in range(HTTP_REQUESTS):
            n = int(rng.integers(PROMPT_MIN, PROMPT_MAX + 1))
            _, out = _http(base + "/submit", {
                "prompt": rng.integers(0, vocab, n).tolist(),
                "max_new": SERVE_MAX_NEW})
            rids.append(str(out["rid"]))
        done = {}

        def drained():
            done.update(_http(base + "/drain", {})[1]["done"])
            return all(r in done for r in rids)

        t1 = time.perf_counter()
        _wait_for("/drain", drained, PHASE_TIMEOUT_S, proc, every=0.2)
        c.info(f"{HTTP_REQUESTS} requests drained in "
               f"{time.perf_counter() - t1:.1f} s (one run)")
        toks = [done[r].get("tokens") or [] for r in rids]
        c.check("all_requests_answered",
                all(len(t) == SERVE_MAX_NEW and not done[r].get("error")
                    for r, t in zip(rids, toks)),
                f"lengths {[len(t) for t in toks]}, errors "
                f"{[done[r].get('error') for r in rids]}")
        c.check("tokens_in_vocab",
                all(t and 0 <= min(t) and max(t) < vocab for t in toks))
        with open(os.path.join(log_dir, "decode0.port")) as f:
            wport = int(f.read().strip())
        st = _http(f"http://127.0.0.1:{wport}/statusz")[1]
        device = {"platform": st.get("backend"),
                  "kind": (st.get("devices") or [{}])[0].get("kind"),
                  "count": st.get("device_count")}
        c.check("worker_on_tpu", device["platform"] == "tpu",
                f"worker /statusz device {device}")
        os.kill(proc.pid, signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            rc = None
        c.check("sigterm_rc_0", rc == 0, f"rc={rc}")
    except Exception as e:  # noqa: BLE001 — reported as a failed check
        c.check("completed", False, f"{type(e).__name__}: {e}")
        wlog = os.path.join(log_dir, "decode0.log")
        if os.path.exists(wlog):
            with open(wlog, errors="replace") as f:
                c.info("worker log tail:\n" + f.read()[-3000:])
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    return (1 if c.failed else 0), device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve, http (default). 4: the dp=4 "
                    "trainer and its one-chip comparison, nothing else")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)   # internal: run one child
    args = ap.parse_args(argv)
    if args.phase:
        return PHASES[args.phase]()

    # config only — placing the cache initialises no backend
    from paddle_tpu.utils.flops import enable_compile_cache

    cache_dir = enable_compile_cache()
    phases = ["dp4"] if args.chips == 4 else ["train", "serve", "http"]
    device = None
    for name in phases:
        print(f"[cache] {cache_dir}: {_cache_entries(cache_dir)} entries "
              f"before {name}", flush=True)
        t0 = time.perf_counter()
        if name == "http":
            rc, dev = phase_http()
        else:
            rc, dev = _run_child([sys.executable,
                                  os.path.join(HERE, "chip_smoke.py"),
                                  "--phase", name])
        print(f"[cache] {cache_dir}: {_cache_entries(cache_dir)} entries "
              f"after {name}", flush=True)
        print(f"[{name}] {'passed' if rc == 0 else f'FAILED rc={rc}'} "
              f"in {time.perf_counter() - t0:.1f} s", flush=True)
        if rc != 0:
            return 1
        if dev is None or (device is not None and dev != device):
            print(f"[{name}] FAILED: device {dev} (earlier phases: "
                  f"{device})", flush=True)
            return 1
        device = dev
    if device["platform"] != "tpu" or device["count"] < args.chips:
        print(f"FAILED: device {device} is not {args.chips} TPU chip(s)",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
