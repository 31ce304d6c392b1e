"""The ``hybrid_moe`` family at a tiny size on the CPU: its reference
against its program through ``serve_job.run`` (prefill, decode through
the arena, slots reused), the float8 control far from sound, the result
line with the cell's metrics, and the new configuration's widths against
the catalog row beside the ``model-configs`` guide. The shrink is this
file's own (``tests/tiny.py`` shrinks by the decoder's keys)."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import manifest, weights as W
from benchmark.tests import tiny

CELL = "granite-4.0-h-small.chat_closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell() -> manifest.Cell:
    """Two periods of (mamba, mamba, attention); 12 experts of which the
    middle 6 are held, 4 a token; widths cut for the CPU only."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(
        hidden_size=64, num_hidden_layers=6,
        layer_types=["mamba", "mamba", "attention"] * 2,
        num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=32, shared_intermediate_size=48,
        num_local_experts=6, num_experts_per_tok=4, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
        vocab_size=512, attention_multiplier=1.0 / 16, dtype="float32",
        # weights of deviation 0.02 at width 64 add next to nothing to
        # a stream that starts at 12 times the embedding: start it at 1
        # and add the layers whole, so that they decide the tokens
        embedding_multiplier=1.0, residual_multiplier=1.0)
    for row in c.config["reduced"]:
        if row["key"] == "num_local_experts":
            row.update(published=12, here=6, first=3)
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
    assert dims.experts == 12 and dims.held == (3, 6)
    model = fam.build_model(c.config, dims, "float32", 128, False)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cache_kinds == ["recurrent", "recurrent", "kv"] * 2


def test_served_tokens_are_the_references_best(job):
    """float32 on both sides: a served token may lie below the
    reference's best only by rounding (a near-tie broken the other way;
    a flipped expert pick at a near-tie of router logits moves a logit
    by more, which is why this is not 1e-4). Every slot is reused: the
    window serves several requests a slot. A state advanced past its
    prompt, a token applied twice or a state left over from the slot's
    last request reads tenths and more."""
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
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    # no trace on the CPU: the device readers leave their metrics out,
    # the counters' readers give theirs
    assert {"arena_tick_ms", "arena_occupancy_pct", "closed_ttft_p95_ms",
            "expert_load_peak_pct"} <= set(traced["metrics"])
    assert traced["metrics"]["expert_load_peak_pct"]["value"] >= 100.0
    assert not {"ssm_step_ms", "moe_experts_ms", "ssm_scan_ms"} & set(
        traced["metrics"])


def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    shapes = W.leaf_shapes(fam, dims)
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(params - 4.96e9) < 0.02e9          # the issue's count
    assert fam.kinds(dims, "mamba") == 9 and fam.kinds(
        dims, "attention") == 1
    # 36 experts x 3 x 4096 x 768 in bf16
    assert fam.expert_step_bytes(dims) == 36 * 3 * 4096 * 768 * 2
    state = 32 * 128 * 64 * 128 * 4
    assert 2 * state < fam.ssm_step_bytes(dims, 32) < 2 * state + 3e8
    assert fam.expert_flops(dims, 72) == 72 * 10 // 2 * 6 * 4096 * 768


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    c = manifest.Cell(manifest.load_manifest(), CELL)
    assert c.config["source"] == row["source_url"]
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers", "layer_types",
                        "num_local_experts"}
    for k, v in row["config"].items():
        if k in cut:
            assert cut[k]["published"] == v or k == "layer_types"
        else:
            assert c.config[k] == v, k
    assert c.config["layer_types"] == row["config"]["layer_types"][:10]
    assert c.config["num_hidden_layers"] == 10
    assert c.config["num_local_experts"] == 36
