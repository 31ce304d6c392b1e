"""Serving arena (`serving.py` `BatchedDecoder._admit`): median duration
of the traced `serve.prefill` program spans, one per admitted request,
from the dispatch of the prefill program to the first token on the host.
The tick is synchronous, so this is what every decoding row waits when a
prefill falls into its tick."""

import sys

from benchmark.harness import program_spans as P


def read(run):
    t = P.load(run)
    spans = P.named(t["host"], "serve.prefill") if t else []
    if not spans:
        return None
    by_bucket = {}
    for s in spans:
        by_bucket.setdefault(s["stats"].get("bucket"), []).append(s["dur"])
    print(f"[prefill_ms] n={len(spans)}; median ms by bucket: "
          + ", ".join(f"{b}: {P.median_ms(v):.2f} (n={len(v)})"
                      for b, v in sorted(by_bucket.items(),
                                         key=lambda kv: str(kv[0]))),
          file=sys.stderr)
    return P.median_ms([s["dur"] for s in spans])
