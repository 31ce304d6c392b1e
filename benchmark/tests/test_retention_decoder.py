"""The ``retention_decoder`` family at a tiny size on the CPU: its
reference against its program through ``serve_job.run`` (prefill, decode
through the arena with no keys and values, slots reused), the float8
control far from sound, the result line with the cell's metrics, and
the new configuration's widths against the catalog row beside the
``model-configs`` guide. The shrink is this file's own (``tests/tiny.py``
shrinks by the decoder's keys, and head_dim 8 here keeps the state of a
slot at 40 x 9 numbers a head)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import manifest, weights as W
from benchmark.tests import tiny

CELL = "Brumby-14B-Base.longgen_closed16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell() -> manifest.Cell:
    """Three retention blocks, 10 q / 2 kv heads of 8 (five query heads
    a key-value head, as published); widths cut for the CPU only."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(hidden_size=80, num_hidden_layers=3,
                    num_attention_heads=10, num_key_value_heads=2,
                    head_dim=8, intermediate_size=96, vocab_size=512,
                    dtype="float32")
    c.config["serve"].update(slots=4, capacity=128, prompt_bucket=16)
    c.traffic.update(
        clients=4, pool=16, check_requests=3, drain_s=30, ramp_s=0.5,
        prompt_tokens={"dist": "lognormal", "median": 30, "sigma": 0.6,
                       "min": 8, "max": 64},
        output_tokens={"dist": "lognormal", "median": 10, "sigma": 0.6,
                       "min": 4, "max": 32})
    return c


@pytest.fixture(scope="module")
def job():
    return tiny.run_job(tiny_cell(), seconds=3.0, control=True)


def test_the_program_declares_the_familys_leaves():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    assert dims.degree == 2 and dims.retention_eps == 1e-6
    model = fam.build_model(c.config, dims, "float32", 128, False)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cache_kinds == ["recurrent"] * 3
    with pytest.raises(ValueError, match="head is a matrix"):
        fam.Dims.from_config(dict(c.config, tie_word_embeddings=True))


def test_served_tokens_are_the_references_best(job):
    """float32 on both sides: a served token may lie below the
    reference's best only by rounding (a near-tie broken the other
    way). Every slot is reused: the window serves several requests a
    slot. A state advanced past its prompt, a token applied twice, a
    rotary embedding at the wrong cursor or a state left over from the
    slot's last request reads tenths and more."""
    assert job["attempted"] > 4 and job["failed"] == 0
    assert job["numbers"]["served_gap_max"] < 0.02
    assert job["run"]["ticks"] > 0


def test_control_reads_far_from_sound(job):
    s, c = job["numbers"], job["control_numbers"]
    assert c["served_gap_max"] > max(10 * s["served_gap_max"], 0.05)


def test_result_line_has_the_cells_metrics(job):
    run_py = tiny.load_run_py()
    c = tiny_cell()
    line = json.loads(json.dumps(
        run_py.result_line(c, job, tiny.CPU_DEVICE, False)))
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(c.per_layer) >= {
        "retention_step_ms", "retention_step_roofline_pct",
        "retention_scan_ms", "arena_tick_ms", "arena_copy_ms"}
    assert "decode_attn_roofline_pct" not in c.per_layer
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    # no trace on the CPU: the device readers leave their metrics out,
    # the counters' readers give theirs
    assert {"arena_tick_ms", "arena_occupancy_pct",
            "closed_ttft_p95_ms"} <= set(traced["metrics"])
    assert not {"retention_step_ms", "retention_step_roofline_pct",
                "retention_scan_ms"} & set(traced["metrics"])


def test_the_arena_is_all_recurrent_and_the_step_counts(job):
    from paddle_tpu import serving

    counters = serving.last_counters
    c = tiny_cell()
    # 3 blocks x 4 slots x 2 kv heads x 40 products x (8 + 1) float32
    assert counters.state_bytes == {"kv": 0,
                                    "recurrent": 3 * 4 * 2 * 40 * 9 * 4}
    assert counters.steps >= job["run"]["ticks"] > 0
    assert "retention_small_norm" in counters.sums
    assert counters.expert_tokens is None
    del c


def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    shapes = W.leaf_shapes(fam, dims)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(params - 4.199e9) < 0.001e9          # the issue's count
    layer = sum(int(np.prod(s)) for s in fam.layer_shapes(dims, 0).values())
    assert abs(layer - 330.35e6) < 0.01e6
    assert fam.kinds(dims, "retention") == 8 and fam.kinds(
        dims, "mamba") == 0
    # 34.08 MB a layer a slot: 8 kv heads x 8256 x (128 + 1) float32,
    # whatever the program's tiling (8320 products a head)
    assert fam.state_width(dims) == 8256
    assert fam.retention_state_bytes(dims, 1) == 8 * 8256 * 129 * 4
    assert abs(fam.retention_state_bytes(dims, 1) - 34.08e6) < 0.01e6
    assert fam.retention_step_bytes(dims, 16) == (
        2 * 16 * 8 * 8256 * 129 * 4 + fam.mixer_weights(dims) * 2)
    assert abs(fam.mixer_weights(dims) - 62.96e6) < 0.01e6
    # a token a block: the projections' 2 x 62.96 M and (3 x 8 + 2 x 40)
    # state cells of 8256 x 129
    assert fam.retention_scan_flops(dims, 1) == (
        2 * (fam.mixer_weights(dims) - 256) + 104 * 8256 * 129)
    from paddle_tpu.ops import retention

    assert retention.phi_dim(128) == 8320 >= fam.state_width(dims)


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    c = manifest.Cell(manifest.load_manifest(), CELL)
    assert c.config["source"] == row["source_url"]
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers"}
    assert cut["num_hidden_layers"]["published"] == row["config"][
        "num_hidden_layers"] == 40
    for k, v in row["config"].items():
        if k not in cut:
            assert c.config[k] == v, k
    assert c.config["num_hidden_layers"] == 8
    assert c.config["serve"]["slots"] == c.traffic["clients"] == 16
