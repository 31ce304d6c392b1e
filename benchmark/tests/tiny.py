"""The real cells' files shrunk in memory to ``GPTConfig.tiny()``-like
sizes, and a device description for the CPU. The chip guard is not
called: the jobs are handed this description as an argument."""

import importlib.util
import os
import time

from benchmark.harness import manifest, runtime

CPU_DEVICE = {"platform": "cpu", "kind": "cpu-for-tests", "count": 1,
              "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                        "int8_ops_per_s": 2e12, "hbm_bytes": 1e9}}
TRAIN, CHAT = "internlm2-1.8b.pretrain_2k", "mistral-7b-v0.1.chat_closed16"


def cell(name: str) -> manifest.Cell:
    c = manifest.Cell(manifest.load_manifest(), name)
    c.config.update(hidden_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, intermediate_size=256, vocab_size=512)
    if c.kind == "train":
        c.traffic.update(rows=4, seq=64, ring=4)
    else:
        c.config["serve"].update(slots=4, capacity=128, prompt_bucket=16)
        c.traffic.update(
            clients=4, pool=16, check_requests=3, drain_s=20, ramp_s=0.5,
            prompt_tokens={"dist": "lognormal", "median": 30,
                           "sigma": 0.6, "min": 8, "max": 64},
            output_tokens={"dist": "lognormal", "median": 10,
                           "sigma": 0.6, "min": 4, "max": 32})
    return c


def run_job(c, seed=7, seconds=2.0, trace=False, **kw):
    from benchmark.harness import serve_job, train_job

    mod = train_job if c.kind == "train" else serve_job
    return mod.run(c, seed, seconds, trace, CPU_DEVICE,
                   time.perf_counter(), **kw)


def load_run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_run_py", os.path.join(runtime.ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
