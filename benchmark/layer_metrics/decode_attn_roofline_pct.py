"""Kernels (`ops/pallas/flash_decode.py`): the least time the chip could
take to read the live keys and values of a tick (bytes from the run's
family, `benchmark/families/`, bf16 KV; memory-bound) over the time of
the decode kernel in the device trace. On a v5e trace the kernel is the
`XLA Ops` event `%closed_call.N`, a `custom-call` to `tpu_custom_call`,
once per layer per tick; serving's prefill programs hold no Pallas
kernel, so every such event is a decode kernel. The live context of a
call is the window's mean: active slots per tick (`tick_tokens` over
ticks) x the token-weighted mean context of the finished requests."""

import sys

from benchmark.harness import flops, trace_reduce


def read(run):
    t = run.get("trace")
    if not t or run.get("kind") != "serve" or not run.get("ticks"):
        return None
    kernels = trace_reduce.kernel_events(t["ops"], "tpu_custom_call")
    ctx = run.get("mean_context_tokens")
    if not kernels or not ctx:
        return None
    live = run["tick_tokens"] / run["ticks"] * ctx
    s, bound = flops.roofline_seconds(
        run["family"].decode_attention_flops(run["dims"], live),
        run["family"].decode_attention_bytes(run["dims"], live, 2),
        run["device"]["peaks"])
    spent = sum(e[2] for e in kernels) / 1e9 / len(kernels)
    print(f"[decode_attn_roofline_pct] {bound}-bound; {len(kernels)} "
          f"kernel events of {spent * 1e6:.1f} us against "
          f"{s * 1e6:.1f} us needed for {live:.0f} live positions",
          file=sys.stderr)
    return 100.0 * s / spent
