#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers ``correct``
compares: the program's (sound) and the control's — the reference put
in the program's place in float8, the precision below the bfloat16 the
configurations state. The limits in ``limits/<cell>.json`` are set from
these readings; the benchmark's own runs never run the control.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 5

One process, one seed after the other (set-up is paid once per seed but
the compile cache is warm). Prints one JSON line per seed.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest, runtime, serve_job, train_job

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    device = runtime.require_chips(cell.chips)
    runtime.place_compile_cache()
    mod = train_job if cell.kind == "train" else serve_job
    for seed in (int(s) for s in args.seeds.split(",")):
        job = mod.run(cell, seed, args.seconds, False, device,
                      time.perf_counter(), control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": job["correct"],
                          "sound": job["numbers"],
                          "control": job.get("control_numbers"),
                          "end_to_end": job["end_to_end"]}), flush=True)
        del job
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
