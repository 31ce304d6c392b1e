"""The ``latent_moe`` family at a tiny size on the CPU: its reference
(non-absorbed attention, Sinkhorn as a loop, no cache) against its
program through ``serve_job.run`` (a decompressed prefill, absorbed
decode steps over the latent arena, slots reused), the float8 control
far from sound and over a limit set between them, the result line with the
cell's metrics, the formulas at the published sizes and the
configuration's widths against the catalog row beside the
``model-configs`` guide. The shrink is this file's own."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import check, manifest, weights as W
from benchmark.tests import tiny

CELL = "Xing4.0-29B-A4B.longctx_closed16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell() -> manifest.Cell:
    """Three blocks (one dense, two with 16 experts of which 8 are
    held), hidden 128, 4 heads of 16 + 8 / 16, ranks 48 and 64, 4
    streams, YaRN by 4 over 32 positions; widths cut for the CPU only
    (at hidden 64 a layer adds less than the embedding it is added to,
    and the float8 control reads 0.004 to 0.1; at 128 it reads 0.10 to
    0.12)."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(hidden_size=128, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=64,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=256, moe_intermediate_size=64,
                    vocab_size=512, dtype="float32")
    c.config["rope_scaling"] = dict(
        c.config["rope_scaling"], factor=4,
        original_max_position_embeddings=32)
    for row in c.config["reduced"]:
        if row["key"] == "n_routed_experts":
            row["published"] = 16
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
    """The tiny cell with NO position left undecided (``PICK_MARGIN``
    0: in float32 at this size a pick does not flip), so that the
    comparison here holds every served token to the reference's best
    and sees a wrong gate, pick or routed product at any of them."""
    c = tiny_cell()
    R = c.family.reference
    shipped, R.PICK_MARGIN = R.PICK_MARGIN, 0.0
    try:
        return tiny.run_job(c, seconds=3.0, control=True)
    finally:
        R.PICK_MARGIN = shipped


def test_every_matrix_is_seeded_the_routed_down_projection_too():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    rules = {k: fam.leaf_rule(k, s)
             for k, s in W.leaf_shapes(fam, dims).items()}
    assert {k for k, r in rules.items() if r == "zeros"} == {
        k for k in rules if k.endswith(".bias")}
    assert all(r == "uniform" for k, r in rules.items()
               if len(W.leaf_shapes(fam, dims)[k]) > 1)
    assert rules["blocks.1.moe.w_down"] == rules["blocks.1.moe.w_gate"] == \
        rules["blocks.1.moe.score_bias"] == rules["norm_f.weight"] == "uniform"
    assert rules["blocks.0.res1.gain"] == rules["blocks.0.norm1.weight"] == \
        rules["blocks.0.mixer.kv_a_norm.weight"] == "ones"


def router_only(scores, bias=None):
    """(dims, leaves, tokens) whose router gives each token the sigmoid
    ``scores`` (T, 16) exactly: token ``t`` is the ``t``-th unit vector
    and the router's row ``t`` the scores' logits."""
    c = tiny_cell()
    dims = c.family.Dims.from_config(c.config)
    scores = np.asarray(scores, np.float64)
    router = np.zeros((dims.hidden, dims.experts), np.float32)
    router[:len(scores)] = np.log(scores / (1 - scores))
    w = {"m.router.weight": router,
         "m.score_bias": np.zeros(dims.experts, np.float32)
         if bias is None else np.asarray(bias, np.float32)}
    return dims, w, np.eye(dims.hidden, dtype=np.float32)[:len(scores)]


def test_the_margin_is_the_nearest_held_experts_distance_from_the_edge():
    """16 experts, 4 picks, experts 0 to 7 held; the bias lifts held
    expert 3 by 0.3. Token 0: the picks are held experts 0 and 1 and
    absent ones 8 and 9; the edge lies between 0.60 (lowest picked) and
    0.50 (highest unpicked, absent); held 1 is picked at 0.64, 0.14
    above the highest unpicked, held 2 is unpicked at 0.45, 0.15 below
    the lowest picked: margin 0.14. Token 1: the edge is between two
    ABSENT experts 0.001 apart and the nearest held one (3, at 0.1 +
    0.3) is 0.2 from it: that near-tie moves nothing here. Token 2 is
    token 0 with expert 3 scored 0.296: the bias carries it to 0.004
    under the lowest picked, margin 0.004."""
    base = np.full(16, 0.1)
    t0 = base.copy()
    t0[[0, 1, 8, 9]] = 0.9, 0.64, 0.8, 0.60
    t0[[2, 10]] = 0.45, 0.50
    t1 = base.copy()
    t1[[8, 9, 10, 11, 12]] = 0.9, 0.8, 0.7, 0.600, 0.599
    t1[0] = 0.3
    t2 = t0.copy()
    t2[3] = 0.296
    bias = np.zeros(16)
    bias[3] = 0.3
    dims, w, u = router_only([t0, t1, t2], bias)
    R = tiny_cell().family.reference
    top_i, gates, margin = R.route(u, w, "m.", dims, "f32")
    for t in (0, 2):
        assert sorted(np.asarray(top_i[t])) == [0, 1, 8, 9]
    assert sorted(np.asarray(top_i[1])) == [8, 9, 10, 11]
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(margin), [0.14, 0.2, 0.004],
                               atol=2e-6)


def test_an_undecided_position_is_levelled_and_no_other():
    R = tiny_cell().family.reference
    rng = np.random.default_rng(3)
    lg = rng.standard_normal((2, 3, 512)).astype(np.float32)
    margin = np.array([[0.5, 0.001, np.inf], [0.019, 0.021, 0.0]],
                      np.float32)
    out = np.asarray(R.hold_undecided(lg, margin, 0.02, 2.5))
    under = margin < 0.02
    np.testing.assert_array_equal(out[~under], lg[~under])
    for row, was in zip(out[under], lg[under]):
        level = was.max() - 2.5 * was.std()
        assert row.max() == pytest.approx(level)
        np.testing.assert_array_equal(row[was < level], was[was < level])
        # the near-best all pass, a token far below them does not
        gaps = (row.max() - row) / row.std()
        assert np.all(gaps[was >= level] == 0)
        assert gaps[np.argmin(was)] > 2.0


def test_what_serve_gap_compares_is_held_and_the_control_is_not(
        monkeypatch):
    """``layerwise_logits`` in float32 is ``layerwise`` with the hold;
    the control's logits, read for their best token, stand as they
    are. With a margin no position reaches, nothing is held."""
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    R = fam.reference
    rng = np.random.default_rng(4)
    toks = rng.integers(0, dims.vocab, (2, 24)).astype(np.int32)
    pos = np.tile(np.arange(8, 20, dtype=np.int32), (2, 1))
    args = dict(get=lambda shapes: W.make_leaves(5, shapes, "float32",
                                                 fam.leaf_rule),
                shapes_of_layer=lambda i: fam.layer_shapes(dims, i),
                top_shapes=fam.top_shapes(dims))
    lg, margin = R.layerwise(toks, pos, dims, "f32", **args)
    assert margin.shape == (2, 12) and np.all(np.asarray(margin) >= 0)
    monkeypatch.setattr(R, "PICK_MARGIN", float(np.median(margin)))
    held = np.asarray(R.layerwise_logits(toks, pos, dims, "f32", **args))
    under = np.asarray(margin) < R.PICK_MARGIN
    assert 0 < under.sum() < under.size
    np.testing.assert_array_equal(held[~under], np.asarray(lg)[~under])
    assert np.all(held[under].max(-1) < np.asarray(lg)[under].max(-1))
    ctl, _ = R.layerwise(toks, pos, dims, "fp8", **args)
    np.testing.assert_array_equal(
        np.asarray(R.layerwise_logits(toks, pos, dims, "fp8", **args)),
        np.asarray(ctl))
    monkeypatch.setattr(R, "PICK_MARGIN", 0.0)
    np.testing.assert_array_equal(
        np.asarray(R.layerwise_logits(toks, pos, dims, "f32", **args)),
        np.asarray(lg))


def test_the_program_declares_the_familys_leaves():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    assert dims.held == (0, 8) and dims.experts == 16 and dims.top_k == 4
    assert dims.streams == 4 and dims.sinkhorn_iters == 20
    assert dims.clamp == (-30.0, 30.0) and dims.scaling == 2.0
    model = fam.build_model(c.config, dims, "float32", 128, False)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cache_kinds == ["kv"] * 3
    assert model.cache_records == ["latent"] * 3
    assert [b.moe is None for b in model.blocks] == [True, False, False]
    with pytest.raises(ValueError, match="scoring_func"):
        fam.Dims.from_config(dict(c.config, scoring_func="softmax"))


def test_served_tokens_are_the_references_best(job):
    """float32 on both sides: a served token may lie below the
    reference's best only by rounding (a near-tie broken the other
    way). Every slot is reused. A record written at the wrong cursor, a
    rotary key at the wrong position, an absorbed read that differs
    from the heads' attention, a hyper-connection applied in the wrong
    order or a gate weighed by its bias reads tenths and more."""
    assert job["attempted"] > 4 and job["failed"] == 0
    assert job["numbers"]["served_gap_max"] < 0.02
    assert job["run"]["ticks"] > 0


def test_control_reads_far_from_sound_and_fails_a_limit_between(job):
    """The cell's own limit is set on the chip from bfloat16 readings at
    the published widths; at this size and in float32 the same rule (a
    limit between the sound reading and the control's, with room on both
    sides) passes the program and fails the control."""
    s, c = job["numbers"], job["control_numbers"]
    assert c["served_gap_max"] > max(10 * s["served_gap_max"], 0.03)
    between = {"served_gap_max": 0.4 * c["served_gap_max"]}
    assert check.judge(s, between, "sound")
    assert not check.judge(c, between, "control")
    assert check.load_limits(tiny_cell())["served_gap_max"] > 0


def test_result_line_has_the_cells_metrics(job):
    run_py = tiny.load_run_py()
    c = tiny_cell()
    line = json.loads(json.dumps(
        run_py.result_line(c, job, tiny.CPU_DEVICE, False)))
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(c.per_layer) >= {
        "mla_decode_ms", "mla_decode_roofline_pct", "mla_prefill_ms",
        "mhc_mix_ms", "expert_load_peak_pct", "arena_tick_ms",
        "arena_copy_ms"}
    # not the experts' time nor their roofline: the compiler moves a
    # third of this cell's expert bytes by asynchronous slices outside
    # the scope both readers time (PERF.md section 7)
    assert not {"decode_attn_roofline_pct", "ssm_step_ms",
                "retention_step_ms", "moe_experts_ms",
                "moe_experts_roofline_pct", "prefill_ms",
                "arena_queue_wait_ms"} & set(c.per_layer)
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    # no trace on the CPU: the device readers leave their metrics out,
    # the counters' readers give theirs
    assert {"arena_tick_ms", "arena_occupancy_pct", "closed_ttft_p95_ms",
            "expert_load_peak_pct"} <= set(traced["metrics"])
    assert not {"mla_decode_ms", "mla_decode_roofline_pct",
                "mla_prefill_ms", "mhc_mix_ms"} & set(traced["metrics"])


def test_the_arena_is_the_latent_record_and_the_step_counts(job):
    from paddle_tpu import serving

    counters = serving.last_counters
    # 3 blocks x 4 slots x 128 positions x (64 + 8) float32
    assert counters.state_bytes == {"kv": 3 * 4 * 128 * 72 * 4,
                                    "recurrent": 0}
    assert counters.steps >= job["run"]["ticks"] > 0
    assert counters.sums["mhc_unbalanced"] == 0
    # two expert layers, 4 rows x 4 picks a step, half the experts held
    assert counters.expert_tokens.shape == (8,)
    assert 0 < counters.expert_tokens.sum() < counters.steps * 2 * 16
    assert counters.prefill_resteps == 0 < counters.prefills


def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())
    assert abs(count(W.leaf_shapes(fam, dims)) - 2.223e9) < 0.001e9
    assert abs(count(fam.layer_shapes(dims, 0)) - 128.19e6) < 0.01e6
    assert abs(count(fam.layer_shapes(dims, 2)) - 128.43e6) < 0.01e6
    assert abs(fam.mixer_weights(dims) - 28.41e6) < 0.01e6
    assert abs(2 * fam.mhc_weights(dims) - 0.69e6) < 0.01e6
    assert fam.kinds(dims, "latent") == 10
    assert fam.kinds(dims, "experts") == 8
    assert dims.held == (0, 8) and dims.experts == 64 and dims.top_k == 4
    assert abs(dims.score_scale - 192 ** -0.5 * 1.4159 ** 2) < 1e-5
    # 1152 bytes a position a layer; 16 x 16384 x 10 of them are 3.02 GB
    assert fam.record_bytes(dims) == 1152
    serve = c.config["serve"]
    arena = (serve["slots"] * serve["capacity"] * dims.layers
             * fam.record_bytes(dims))
    assert abs(arena - 3.02e9) < 0.005e9
    # 69.6 kFLOP to 1152 bytes a live position
    assert fam.mla_decode_flops(dims, 0, 1) == 69632
    assert fam.mla_decode_bytes(dims, 1) - fam.mla_decode_bytes(
        dims, 0) == 1152
    assert fam.mla_decode_bytes(dims, 0) == 2 * fam.mixer_weights(dims)
    # dims.layers x expert_step_bytes is the 8 expert layers' held bytes
    assert round(dims.layers * fam.expert_step_bytes(dims)) == (
        8 * 8 * 3 * 3584 * 1024 * 2)
    assert fam.mla_prefill_flops(dims, 2) == 2 * 2 * (
        fam.mixer_weights(dims) - 768 - 512) + 3 * 2 * 32 * 320


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    c = manifest.Cell(manifest.load_manifest(), CELL)
    assert c.config["source"] == row["source_url"]
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers", "n_routed_experts"}
    for k, r in cut.items():
        assert r["published"] == row["config"][k]
        assert r["here"] == c.config[k]
    for k, v in row["config"].items():
        if k not in cut:
            assert c.config[k] == v, k
    assert c.config["num_hidden_layers"] == 10
    assert c.config["n_routed_experts"] == 8
    assert c.config["serve"]["slots"] == c.traffic["clients"] == 16
    lo, hi = c.traffic["prompt_tokens"], c.traffic["output_tokens"]
    assert hi["max"] + lo["max"] == c.config["serve"]["capacity"]
