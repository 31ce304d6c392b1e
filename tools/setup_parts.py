#!/usr/bin/env python
"""What a training cell's set-up consists of, by parts, on the chip.

    chiprun -- python tools/setup_parts.py --workload \
        kanana-2-30b-a3b-instruct-2601.pretrain_8k --seed 2147499101

Builds the cell's model and trainer as ``benchmark/harness/train_job.py``
does (same weights, same ring, same compile cache) and runs its warm-up
steps, timing on the host clock: ``import_s`` (the process up to the
model), ``build_s`` (weights made and placed, the trainer), each warm-up
step fenced (``step_s``: the first holds the trace, the lowering and the
compile or the cache's load), and inside them JAX's own durations by
event (``jax.monitoring``: ``jaxpr_trace``, ``jaxpr_to_mlir_module``,
``backend_compile`` which is the cache's load where the program was
found there, ``cache_retrieval``) summed over the process. Then takes the lowered
``pt_train_step`` for ``lowered_bytes``, the size of the text the
compiler is handed, and prints what the routers counted in
the last step (``Trainer.router_telemetry()``: the held pairs a layer
and the ``windows`` they filled). One JSON line. Run it twice
in one call, from an empty compile cache and again, for cold and cached.
It reads the tree it is started FROM (``cd build/parent && python3
/root/repo/tools/setup_parts.py ...`` reads the parent).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.getcwd())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark.harness import loadgen, manifest, program, runtime

    events = collections.defaultdict(float)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.__setitem__(
            name, events[name] + secs))
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    device = runtime.require_chips(cell.chips)
    cache = runtime.place_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    dims = fam.Dims.from_config(cfg)
    t_import = time.perf_counter()
    model = program.build_model(fam, cfg, dims, args.seed, cfg["dtype"],
                                int(mix["seq"]),
                                remat=bool(cfg["train"]["remat"]))
    trainer = program.build_trainer(model, float(mix["lr"]),
                                    cfg["train"]["amp"])
    ring = loadgen.train_ring(mix, dims.vocab, args.seed)
    feed = lambda i: jax.device_put(ring[i % len(ring)],
                                    trainer.data_sharding())
    jax.block_until_ready(trainer.params)
    t_build = time.perf_counter()
    step_s = []
    for i in range(max(int(mix["warmup_steps"]), int(mix["check_steps"]))):
        t0 = time.perf_counter()
        loss, _ = trainer.train_step(feed(i))
        float(loss)
        step_s.append(time.perf_counter() - t0)
    setup_s = time.perf_counter() - T_START
    in_steps = dict(events)
    text = trainer.lower_step(feed(0)).as_text()
    short = lambda name: name.rsplit("/", 1)[-1].replace("_duration", "")
    routers = {
        layer: {k: (np.asarray(v).tolist() if k == "pairs" else v)
                for k, v in got.items()}
        for layer, got in trainer.router_telemetry().items()}
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "device": device["kind"],
        "cache_entries_at_start": entries,
        "import_s": t_import - T_START, "build_s": t_build - t_import,
        "step_s": step_s, "setup_s": setup_s,
        "events_s": {short(k): v for k, v in sorted(in_steps.items())},
        "lowered_bytes": len(text),
        "ragged_dot_in_text": text.count("ragged_dot"),
        "while_in_text": text.count("stablehlo.while"),
        "routers": {n: {"held_pairs": int(sum(r["pairs"])),
                        **{k: v for k, v in r.items() if k != "pairs"}}
                    for n, r in routers.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
