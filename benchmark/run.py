#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip. Finds the cell's files by the names in
``BENCHMARK.json`` (``harness/manifest.py``), runs the job its traffic
mix's ``kind`` names, and prints one JSON object as the last line of
standard output; everything else goes to standard error. Exits 3 with no
result where the first JAX device is not a TPU in the benchmark's peak
table or fewer chips are attached than the cell asks for: no flag and no
environment variable changes that.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def result_line(cell, job: dict, device: dict, trace: bool) -> dict:
    """The contract's last line from a job's result."""
    from benchmark.harness import manifest, trace_reduce

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": job["memory_peak_bytes"]}
    out = {"correct": job["correct"], "attempted": job["attempted"],
           "failed": job["failed"]}
    if not trace:
        values = {k: job["end_to_end"].get(k) for k in cell.end_to_end}
    else:
        reduced = None
        if job.get("xplane"):
            reduced = trace_reduce.reduce(
                trace_reduce.read_xplane(job["xplane"]), cell.chips)
        job["run"]["trace"] = reduced
        values = manifest.read_layer_metrics(cell.per_layer, job["run"],
                                             cell.root)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["span_s"]
            out["breakdown"] = reduced["breakdown"]
    missing = [k for k, v in values.items() if v is None]
    if missing:
        print(f"benchmark: no value for {missing}", file=sys.stderr)
        out["correct"] = False
    out["metrics"] = {k: {"value": float(v), "unit": cell.units[k]}
                      for k, v in values.items() if v is not None}
    out["device"] = dev
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest, runtime

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    device = runtime.require_chips(cell.chips)
    cache = runtime.place_compile_cache()
    print(f"[run] {cell.name} seed {args.seed} on {device['kind']} x "
          f"{device['count']}; compile cache {cache}", file=sys.stderr,
          flush=True)
    if cell.kind == "train":
        from benchmark.harness import train_job as job_mod
    elif cell.kind == "serve":
        from benchmark.harness import serve_job as job_mod
    else:
        raise SystemExit(f"benchmark: unknown traffic kind {cell.kind!r}")
    job = job_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                      device, T_START)
    line = result_line(cell, job, device, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
