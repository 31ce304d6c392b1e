#!/usr/bin/env python3
"""``benchmark/run.py`` of the checkout this is run FROM, with what the
harness does not log about a serving cell on standard error beside it:

    cd <checkout> && python3 <this file> --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``[gaps]``: the window's gaps between streamed tokens as clusters (bins
of 3 ms that hold at least 0.5% of them, with their share), because
``itl_p95_ms`` reads whichever cluster the 95% point falls in (PERF.md
section 7) and a comparison should see the clusters move; ``[arena]``:
``serving.last_counters`` (``steps``, ``prefills``, ``prefill_resteps``,
and of the look-ahead ``steps_ahead``, the decode steps dispatched while
the one before was unread, and ``rows_dropped``, the rows of such steps
whose tokens were thrown away; ``prefill_expert_layers``, the routed
expert layers of the prefills, ``prefill_dense_layers``, those of them
that took the dense body, and their quotient ``prefill_dense_share``;
"None" where that checkout's arena does not count one). The result line
and every number in it are ``benchmark/run.py``'s own: the job is run
by it, unchanged, and this only reads what it returns."""

import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run as bench  # noqa: E402  (its clock starts here)

BIN_MS, LEAST_SHARE = 3.0, 0.005


def clusters(gaps_s):
    """[(bin's lower edge in ms, share of the gaps)] of the bins that
    hold at least ``LEAST_SHARE`` of them."""
    bins = {}
    for g in gaps_s:
        lo = int(g * 1e3 // BIN_MS) * BIN_MS
        bins[lo] = bins.get(lo, 0) + 1
    return [(lo, n / len(gaps_s)) for lo, n in sorted(bins.items())
            if n >= LEAST_SHARE * len(gaps_s)]


def main():
    from benchmark.harness import serve_job, stats

    inner = serve_job.run

    def run(*args, **kw):
        job = inner(*args, **kw)
        from paddle_tpu import serving

        gaps = job["run"]["gaps_s"]
        if gaps:
            print(f"[gaps] n={len(gaps)}; p50 / p90 / p95 / p99 ms: "
                  + " / ".join(f"{stats.quantile(gaps, q) * 1e3:.2f}"
                               for q in (0.5, 0.9, 0.95, 0.99))
                  + "; clusters: " + ", ".join(
                      f"{lo:.0f}-{lo + BIN_MS:.0f} ms {share:.1%}"
                      for lo, share in clusters(gaps)), file=sys.stderr)
        c = serving.last_counters
        layers = getattr(c, "prefill_expert_layers", None)
        share = (round(c.prefill_dense_layers / layers, 4) if layers
                 else None)
        print("[arena] " + ", ".join(
            f"{k} {getattr(c, k, None)}"
            for k in ("steps", "prefills", "prefill_resteps",
                      "steps_ahead", "rows_dropped",
                      "prefill_expert_layers", "prefill_dense_layers"))
            + f", prefill_dense_share {share}",
            file=sys.stderr, flush=True)
        return job

    serve_job.run = run
    return bench.main()


if __name__ == "__main__":
    sys.exit(main())
