"""The ``sparse_latent_moe`` family at a tiny size on the CPU: its
reference (non-absorbed attention under a pick made by a sort, no
cache) against its program through ``serve_job.run`` (a prefill under
per-query picks, absorbed decode steps that read the picked records
over an arena of three arrays a layer, slots reused), with ``index_topk``
below every context; the float8 control and the program WITH ITS
SELECTION SWITCHED OFF from outside the library both far from sound and
over a limit set between them; the result line with the cell's metrics;
the formulas at the published sizes; the expert shares against the
uncut reference; and the configuration's widths against the catalog
row beside the ``model-configs`` guide. The shrink is this file's own."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.harness import check, manifest, weights as W
from benchmark.tests import tiny

CELL = "GLM-5.longctx32k_closed16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny_cell() -> manifest.Cell:
    """Three blocks (one dense, two with 16 experts of which 8 are
    held), hidden 128, 4 heads of 16 + 8 / 24, ranks 48 and 64, an
    indexer of 4 heads of 16 that picks 8 positions where a prompt has
    8 to 64; widths cut for the CPU only."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(hidden_size=128, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=64,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
                    v_head_dim=24, index_n_heads=4, index_head_dim=16,
                    index_topk=8, intermediate_size=256,
                    moe_intermediate_size=64, n_routed_experts=8,
                    num_experts_per_tok=4, vocab_size=512, dtype="float32")
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


def run_tiny(**kw):
    """The tiny cell with NO position left undecided (``PICK_MARGIN``
    and ``POSITION_MARGIN`` 0: in float32 at this size neither an
    expert pick nor a position pick flips), so that the comparison
    holds every served token to the reference's best."""
    c = tiny_cell()
    R = c.family.reference
    shipped = R.PICK_MARGIN, R.POSITION_MARGIN
    R.PICK_MARGIN = R.POSITION_MARGIN = 0.0
    try:
        return tiny.run_job(c, seconds=3.0, **kw)
    finally:
        R.PICK_MARGIN, R.POSITION_MARGIN = shipped


@pytest.fixture(scope="module")
def job():
    """The run, with its arena's counters (``last_counters`` is the
    newest arena's, and ``unselected`` makes another)."""
    from paddle_tpu import serving

    return dict(run_tiny(control=True), counters=serving.last_counters)


@pytest.fixture(scope="module")
def unselected():
    """The same run with the selection switched off from outside the
    library: every live position attended, on the step path and on the
    chunk path."""
    from paddle_tpu.ops import latent_attention as LA

    shipped = LA.pick_mask
    LA.pick_mask = lambda scores, live, k: live & (scores == scores)
    try:
        return run_tiny()
    finally:
        LA.pick_mask = shipped


def test_served_tokens_are_the_references_best(job):
    """float32 on both sides: a served token may lie below the
    reference's best only by rounding. Every slot is reused. An index
    key written at the wrong cursor, a rotary part on the wrong half, a
    weight that reads the wrong input, a pick of the wrong positions or
    a read of other records than the picked reads tenths and more."""
    assert job["attempted"] > 4 and job["failed"] == 0
    assert job["numbers"]["served_gap_max"] < 0.02
    assert job["run"]["ticks"] > 0


def test_control_and_no_selection_read_far_from_sound(job, unselected):
    """The cell's own limit is set on the chip; at this size and in
    float32 the same rule (a limit between the sound reading and the
    others, with room on both sides) passes the program and fails both
    the float8 control and the program without its selection: the
    comparison sees the mechanism."""
    s, c = job["numbers"], job["control_numbers"]
    off = unselected["numbers"]
    assert c["served_gap_max"] > max(10 * s["served_gap_max"], 0.03)
    assert off["served_gap_max"] > max(10 * s["served_gap_max"], 0.03)
    between = {"served_gap_max": 0.4 * min(c["served_gap_max"],
                                           off["served_gap_max"])}
    assert check.judge(s, between, "sound")
    assert not check.judge(c, between, "control")
    assert not check.judge(off, between, "unselected")
    assert check.load_limits(tiny_cell())["served_gap_max"] > 0


def test_the_program_declares_the_familys_leaves():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    assert dims.held == (0, 8) and dims.experts == 16 and dims.top_k == 4
    assert (dims.index_heads, dims.index_dim, dims.index_topk) == (4, 16, 8)
    model = fam.build_model(c.config, dims, "float32", 128, False)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cache_kinds == ["kv"] * 3
    assert model.cache_records == ["latent"] * 3
    assert [len(cache) for cache in model.init_cache(1, 8)] == [3, 3, 3]
    assert [b.moe is None for b in model.blocks] == [True, False, False]
    assert model.blocks[0].res1.settle
    rules = {k: fam.leaf_rule(k, s)
             for k, s in W.leaf_shapes(fam, dims).items()}
    assert {k for k, r in rules.items() if r == "zeros"} == {
        k for k in rules if k.endswith("index_k_norm.bias")}
    assert all(r == "uniform" for k, r in rules.items()
               if len(W.leaf_shapes(fam, dims)[k]) > 1)
    with pytest.raises(ValueError, match="scoring_func"):
        fam.Dims.from_config(dict(c.config, scoring_func="softmax"))
    with pytest.raises(ValueError, match="unscaled rotary"):
        fam.Dims.from_config(dict(c.config, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}))


def test_the_shares_of_the_experts_are_the_uncut_references_layer():
    """The guide's share test at the cell's own split: 16 chips hold 16
    of the 256 experts each (here: 4 chips, 4 of 16). The reference told
    it holds one share computes that share's part; the 16 parts, with
    the shared expert and attention counted once, are the reference told
    it holds every expert: the whole layer."""
    c = tiny_cell()
    fam = c.family
    R = fam.reference
    whole = fam.Dims.from_config(c.config)
    whole = type(whole)(**{**whole.__dict__, "held": (0, 16)})
    shapes = fam.layer_shapes(whole, 1)
    w = W.make_leaves(9, shapes, "float32", fam.leaf_rule)
    x = np.random.default_rng(2).standard_normal((24, 128)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        full = R.layer(x, w, 1, whole, "f32")[0]
        base = R.layer(x, {**w, **{
            k: v * 0 for k, v in w.items()
            if k.endswith(("moe.w_down",))}}, 1, whole, "f32")[0]
        parts = 0
        for first in range(0, 16, 4):
            share = type(whole)(**{**whole.__dict__, "held": (first, 4)})
            cut = {k: (v[first:first + 4] if ".moe.w_" in k else v)
                   for k, v in w.items()}
            parts = parts + (R.layer(x, cut, 1, share, "f32")[0] - base)
    # attention and the shared expert once (base) + every share's part
    np.testing.assert_allclose(np.asarray(base + parts), np.asarray(full),
                               atol=1e-5 * float(np.std(full)))
    assert float(np.abs(np.asarray(parts)).max()) > 0


def test_the_position_margin_is_the_edge_of_the_pick_in_deviations():
    """Row 0: scores 9, 8, ..., 0 over ten live positions, the pick of
    4 ends between 6 and 5: one apart, over the deviation of the ten
    (2.872). Row 1: six live positions and a pick of 4: between 2 and
    1. Row 2: three live positions, all picked: no edge. Then the hold:
    a position under either margin is levelled at that margin's depth
    under its OWN best, the deeper level where both say so."""
    R = tiny_cell().family.reference
    scores = np.tile(np.arange(9, -1, -1, dtype=np.float32), (3, 1))
    scores[1] = [5, 0, 4, 1, 3, 2, 9, 9, 9, 9]
    live = np.arange(10)[None, :] <= np.array([9, 5, 2])[:, None]
    keep, margin = R.pick(scores, live, 4)
    np.testing.assert_array_equal(
        np.asarray(keep).astype(int),
        [[1, 1, 1, 1, 0, 0, 0, 0, 0, 0], [1, 0, 1, 0, 1, 1, 0, 0, 0, 0],
         [1, 1, 1, 0, 0, 0, 0, 0, 0, 0]])
    np.testing.assert_allclose(
        np.asarray(margin), [1 / np.std(np.arange(10)),
                             1 / np.std(np.arange(6)), np.inf], rtol=1e-6)
    depth = R.undecided_depth(
        np.array([0.5, 0.5 * R.PICK_MARGIN, 0.5 * R.PICK_MARGIN, 0.5]),
        np.array([0.5 * R.POSITION_MARGIN, 0.5 * R.POSITION_MARGIN, np.inf,
                  2 * R.POSITION_MARGIN]))
    np.testing.assert_allclose(np.asarray(depth), [
        R.POSITION_DEPTH, max(R.POSITION_DEPTH, R.UNDECIDED_DEPTH),
        R.UNDECIDED_DEPTH, 0.0])
    lg = np.random.default_rng(5).standard_normal((4, 64)).astype(np.float32)
    held = np.asarray(R.hold(lg, depth))
    np.testing.assert_array_equal(held[3], lg[3])
    for row, was, d in zip(held[:3], lg, np.asarray(depth)):
        assert row.max() == pytest.approx(was.max() - d * was.std(),
                                          rel=1e-5)
        np.testing.assert_array_equal(row[was < row.max()],
                                      was[was < row.max()])


def test_result_line_has_the_cells_metrics(job):
    run_py = tiny.load_run_py()
    c = tiny_cell()
    line = json.loads(json.dumps(
        run_py.result_line(c, job, tiny.CPU_DEVICE, False)))
    # not serve_tokens_per_s: two thirds of a window are prefills and
    # the deck's order decides how many (the traffic file's notes)
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    new = {"dsa_index_ms", "dsa_index_roofline_pct", "dsa_read_roofline_pct",
           "dsa_prefill_index_ms", "dsa_read_share_pct"}
    assert set(c.per_layer) >= new | {
        "mla_decode_ms", "mla_prefill_ms", "arena_tick_ms", "tick_host_ms",
        "step_mlp_ms", "step_head_ms", "step_unscoped_ms"}
    # every metric of the cell moves the one end-to-end metric it reports
    rows = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert {rows[m]["moves"] for m in c.per_layer} == {"itl_p95_ms"}
    # the older share states the need of EVERY live record, which a
    # mixer with an indexer does not have
    assert not {"mla_decode_roofline_pct", "mhc_mix_ms", "moe_experts_ms",
                "moe_experts_roofline_pct", "prefill_ms",
                "arena_queue_wait_ms", "replica_lock_wait_ms",
                "prefill_run_ms", "decode_attn_roofline_pct"} & set(
                    c.per_layer)
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    # no trace on the CPU: the device readers leave their metrics out,
    # the counters' readers give theirs
    assert {"arena_tick_ms", "dsa_read_share_pct"} <= set(
        traced["metrics"])
    assert not (new - {"dsa_read_share_pct"}) & set(traced["metrics"])
    # every context here is past index_topk = 8 by far
    assert 5 < traced["metrics"]["dsa_read_share_pct"]["value"] < 60


def test_the_arena_holds_three_arrays_a_layer_and_the_step_counts(job):
    counters = job["counters"]
    # 3 blocks x 4 slots x 128 positions x (64 + 8 + 16) float32
    assert counters.state_bytes == {"kv": 3 * 4 * 128 * 88 * 4,
                                    "recurrent": 0}
    assert counters.steps >= job["run"]["ticks"] > 0
    live = counters.sums["dsa_positions_live"]
    read = counters.sums["dsa_positions_read"]
    assert 0 < read <= counters.steps * 3 * 4 * 8 and read < live
    assert counters.expert_tokens.shape == (8,)
    assert counters.prefill_resteps == 0 < counters.prefills


def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())
    assert abs(count(W.leaf_shapes(fam, dims)) - 3.91e9) < 0.005e9
    assert abs(count(fam.layer_shapes(dims, 0)) - 400.9e6) < 0.05e6
    assert abs(count(fam.layer_shapes(dims, 1)) - 817.7e6) < 0.05e6
    assert abs(fam.mixer_weights(dims) - 165.02e6) < 0.01e6
    assert abs(fam.index_weights(dims) - 9.37e6) < 0.01e6
    assert fam.kinds(dims, "latent") == 5
    assert fam.kinds(dims, "experts") == 4
    assert dims.held == (0, 16) and dims.experts == 256 and dims.top_k == 8
    assert dims.score_scale == 256 ** -0.5 and dims.scaling == 2.5
    assert dims.index_scale == 32 ** -0.5 * 128 ** -0.5
    # 1408 bytes a position a layer; 16 x 32768 x 5 of them are 3.69 GB
    assert fam.record_bytes(dims) + fam.index_key_bytes(dims) == 1408
    serve = c.config["serve"]
    arena = serve["slots"] * serve["capacity"] * dims.layers * 1408
    assert abs(arena - 3.69e9) < 0.005e9
    # a row reads min(context, 2048) records of 1152 bytes
    assert fam.read_tokens(dims, [100, 2048, 30000]) == 100 + 2048 + 2048
    assert fam.mla_decode_bytes(dims, 1) - fam.mla_decode_bytes(
        dims, 0) == 1152
    assert fam.mla_decode_bytes(dims, 0) == 2 * fam.mixer_weights(dims)
    assert fam.dsa_index_bytes(dims, 1) - fam.dsa_index_bytes(dims, 0) == 256
    assert fam.dsa_index_bytes(dims, 0) == 2 * fam.index_weights(dims)
    assert fam.dsa_index_flops(dims, 0, 1) == 2 * 32 * 129
    # a prefill's pairs: causal up to 2048, 2048 a query after
    assert fam.attended_pairs(dims, 3) == 6
    assert fam.attended_pairs(dims, 4096) == 2048 * 2049 // 2 + 2048 * 2048
    assert fam.dsa_prefill_index_flops(dims, 2) == 2 * 2 * (
        fam.index_weights(dims) - 256) + 3 * 2 * 32 * 129


def test_every_cells_files_are_found_and_no_width_is_reduced():
    """``test_manifest.py::test_files_are_found_by_name`` with the
    widths as the contract lists them (a hidden, intermediate, latent,
    state or projection size, a key that ends in ``_dim`` or ``_rank``,
    a head size, the experts a token): the vocabulary's slice is a cut
    of scale, the guide's floor for embedding and head."""
    import re

    from benchmark.harness import runtime

    man = manifest.load_manifest()
    used = set()
    for w in man["workloads"]:
        cell = manifest.Cell(man, w["name"])
        used.add(w["config"])
        assert cell.kind in ("train", "serve")
        for metric in cell.per_layer:
            assert callable(manifest.load_reader(metric))
        assert check.load_limits(cell)
    assert used == {c["name"] for c in man["configs"]}
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    width = re.compile(r"(_dim$|_rank$|head|hidden_size$|intermediate_size$"
                       r"|num_experts_per_tok$|_topk$|expand)")
    for c in man["configs"]:
        with open(os.path.join(runtime.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert {r["key"] for r in cfg["reduced"]} == set(c["reduced"])
        assert not [k for k in c["reduced"] if width.search(k)]


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    man = manifest.load_manifest()
    c = manifest.Cell(man, CELL)
    assert c.config["source"] == row["source_url"]
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers", "first_k_dense_replace",
                        "n_routed_experts", "vocab_size"}
    entry = next(e for e in man["configs"] if e["name"] == "GLM-5")
    assert set(entry["reduced"]) == set(cut)
    for k, r in cut.items():
        assert r["published"] == row["config"][k]
        assert r["here"] == c.config[k]
    for k, v in row["config"].items():
        if k not in cut:
            assert c.config[k] == v, k
    assert c.config["serve"]["slots"] == c.traffic["clients"] == 16
    lo, hi = c.traffic["prompt_tokens"], c.traffic["output_tokens"]
    assert lo == {"dist": "lognormal", "median": 12288, "sigma": 0.4,
                  "min": 5120, "max": 28672}
    assert hi == {"dist": "lognormal", "median": 512, "sigma": 0.4,
                  "min": 256, "max": 1024}
    assert hi["max"] + lo["max"] <= c.config["serve"]["capacity"] == 32768
    assert (c.traffic["pool"], c.traffic["length_seed"],
            c.traffic["check_requests"], c.traffic["loop"]) == (
                64, 0, 4, "closed")
