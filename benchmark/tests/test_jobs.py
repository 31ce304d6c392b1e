"""Both job kinds at a tiny size on the CPU, driven through the job
functions with a device description of the test's own (``run.py`` has
no switch that bypasses the chip guard); the result line's shape; a
failed request lands in ``failed``; the lower-precision control reads
far from sound; a broken timed path makes ``correct`` false."""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import runtime
from benchmark.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def run_py():
    return tiny.load_run_py()


@pytest.fixture(scope="module")
def train_job():
    return tiny.run_job(tiny.cell(tiny.TRAIN), control=True)


@pytest.fixture(scope="module")
def serve_job():
    return tiny.run_job(tiny.cell(tiny.CHAT), seconds=3.0, control=True)


def check_line(run_py, cell, job, trace):
    line = run_py.result_line(cell, job, tiny.CPU_DEVICE, trace)
    line = json.loads(json.dumps(line))
    assert KEYS <= set(line) <= KEYS | {"breakdown"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = cell.per_layer if trace else cell.end_to_end
    for name, m in line["metrics"].items():
        assert name in want and set(m) == {"value", "unit"}
        assert m["unit"] == cell.units[name]
    return line


def test_train_job_ends_and_is_sound(run_py, train_job):
    cell = tiny.cell(tiny.TRAIN)
    line = check_line(run_py, cell, train_job, trace=False)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(np.isfinite(v) for v in train_job["numbers"].values())
    traced = check_line(run_py, cell, train_job, trace=True)
    assert {"trainer_step_ms", "trainer_dispatch_ms",
            "model_mfu_pct"} <= set(traced["metrics"])


def test_serve_job_ends_and_is_sound(run_py, serve_job):
    cell = tiny.cell(tiny.CHAT)
    line = check_line(run_py, cell, serve_job, trace=False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["attempted"] > 4 and line["failed"] == 0
    assert serve_job["numbers"]["served_gap_max"] < 0.05
    traced = check_line(run_py, cell, serve_job, trace=True)
    assert {"router_wait_ms", "closed_ttft_p95_ms", "arena_tick_ms",
            "arena_occupancy_pct"} <= set(traced["metrics"])


def test_control_reads_far_from_sound(train_job, serve_job):
    """The reference in float8 in the program's place: at this size its
    first gradient is off by over three times what the program's is,
    and it picks tokens the reference puts well below its best."""
    t, c = train_job["numbers"], train_job["control_numbers"]
    assert c["grad_norm_gap"] > 3 * t["grad_norm_gap"]
    s, c = serve_job["numbers"], serve_job["control_numbers"]
    assert c["served_gap_max"] > max(3 * s["served_gap_max"], 0.02)


def test_a_rejected_request_lands_in_failed():
    cell = tiny.cell(tiny.CHAT)
    # prompts up to the whole arena: prompt + answer cannot fit, the
    # arena refuses those, and each counts as failed
    cell.traffic["prompt_tokens"] = {"dist": "uniform", "min": 100,
                                     "max": 128}
    job = tiny.run_job(cell, seconds=2.0)
    assert job["failed"] > 0 and job["attempted"] >= job["failed"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    def broken(trainer):
        def step(batch):
            return trainer.eval_step(batch)[0], {}
        return step

    job = tiny.run_job(tiny.cell(tiny.TRAIN), break_step=broken)
    assert job["correct"] is False
    assert job["numbers"]["param_change_gap"] > 0.9


def test_a_token_altered_where_it_is_produced_is_not_correct():
    def broken(dec):
        build = dec._build_multi_step

        def altered(kd):
            fn = build(kd)

            def step(*a):
                caches, toks = fn(*a)
                return caches, (toks + 1) % dec.model.cfg.vocab_size
            return step
        dec._build_multi_step = altered

    job = tiny.run_job(tiny.cell(tiny.CHAT), seconds=2.0,
                       break_decoder=broken)
    assert job["correct"] is False


def test_run_py_refuses_anything_but_a_tpu():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", tiny.TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=runtime.ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 3 and out.stdout.strip() == ""
    assert "not 'tpu'" in out.stderr
