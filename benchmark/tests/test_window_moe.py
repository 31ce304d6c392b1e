"""The ``window_moe`` family at a tiny size on the CPU: its reference
(a banded mask over the whole sequence, no cache, no ring) against its
program through ``serve_job.run`` (banded prefills that write the last
window into the rings, decode steps over rings and full caches in one
arena, slots reused), the float8 control and the program without its
windows both over a limit the sound program passes, the result line
with the cell's metrics, the six readers the family brings on a
hand-made trace, the family's shapes against the program's at the tiny
and at the published size, the formulas at the published sizes and the
configuration against the catalog row beside the ``model-configs``
guide. The shrink is this file's own."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark.harness import check, manifest, program_spans as P
from benchmark.harness import weights as W
from benchmark.tests import tiny

CELL = "Laguna-XS.2.longctx_closed16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1_000_000
NEW = ("gqa_full_step_ms", "gqa_window_step_ms", "gqa_full_roofline_pct",
       "gqa_window_roofline_pct", "gqa_prefill_ms", "window_kv_share_pct")


def tiny_cell() -> manifest.Cell:
    """Two periods (layer 0 dense, seven with 16 experts of which 8 are
    held), hidden 128, heads of 16 (6 query heads on a full layer, 8 on
    a sliding one, 2 key-value heads), a window of 8, YaRN by 4 over 16
    positions on the first 8 numbers of a full layer's heads; widths cut
    for the CPU only."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(hidden_size=128, num_hidden_layers=8,
                    num_attention_heads=6, num_key_value_heads=2,
                    head_dim=16, sliding_window=8, intermediate_size=256,
                    moe_intermediate_size=64,
                    shared_expert_intermediate_size=64, num_experts=8,
                    num_experts_per_tok=4, vocab_size=512, dtype="float32")
    c.config["num_attention_heads_per_layer"] = [
        6 if k == "full_attention" else 8 for k in c.config["layer_types"]]
    rope = json.loads(json.dumps(c.config["rope_parameters"]))
    rope["full_attention"].update(factor=4,
                                  original_max_position_embeddings=16,
                                  beta_fast=8)
    c.config["rope_parameters"] = rope
    for row in c.config["reduced"]:
        if row["key"] == "num_experts":
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
    comparison holds every served token to the reference's best."""
    c = tiny_cell()
    R = c.family.reference
    shipped, R.PICK_MARGIN = R.PICK_MARGIN, 0.0
    try:
        out = tiny.run_job(c, seconds=3.0, control=True)
    finally:
        R.PICK_MARGIN = shipped
    from paddle_tpu import serving
    out["counters"] = serving.last_counters     # a later job's replace them
    return out


def test_served_tokens_are_the_references_best(job):
    """float32 on both sides: a served token may lie below the
    reference's best only by rounding. Every slot is reused, prompts run
    from under the window (8) to eight times it. A key at the wrong
    place of a ring, a stale or padded entry read, the band off by one
    or a gate left out reads tenths and more."""
    assert job["attempted"] > 4 and job["failed"] == 0
    assert job["numbers"]["served_gap_max"] < 0.02
    assert job["run"]["ticks"] > 0


def test_control_reads_far_from_sound_and_fails_a_limit_between(job):
    s, c = job["numbers"], job["control_numbers"]
    assert c["served_gap_max"] > max(10 * s["served_gap_max"], 0.03)
    between = {"served_gap_max": 0.4 * c["served_gap_max"]}
    assert check.judge(s, between, "sound")
    assert not check.judge(c, between, "control")
    assert check.load_limits(tiny_cell())["served_gap_max"] > 0


def test_the_program_without_its_windows_fails_the_comparison():
    """The control of the mechanism, made from outside the library as
    ``tools/window_control.py`` makes it on the chip: every sliding
    mixer's window is taken away before the arena is built, so that its
    cache is full-length and its queries read every live position. The
    served tokens are another model's and the comparison says so."""
    import tools.window_control as control

    c = tiny_cell()
    R = c.family.reference
    shipped, R.PICK_MARGIN = R.PICK_MARGIN, 0.0
    try:
        with control.every_position():
            got = tiny.run_job(c, seconds=2.0)
    finally:
        R.PICK_MARGIN = shipped
    assert got["failed"] == 0
    assert got["numbers"]["served_gap_max"] > 0.3
    assert not got["correct"]
    from paddle_tpu import serving
    assert "ring" not in serving.last_counters.state_bytes


def test_result_line_has_the_cells_metrics(job, monkeypatch):
    from paddle_tpu import serving

    monkeypatch.setattr(serving, "last_counters", job["counters"])
    run_py = tiny.load_run_py()
    c = tiny_cell()
    line = json.loads(json.dumps(
        run_py.result_line(c, job, tiny.CPU_DEVICE, False)))
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(c.per_layer) >= set(NEW) | {
        "router_wait_ms", "closed_ttft_p95_ms", "arena_tick_ms",
        "arena_occupancy_pct", "tick_host_ms", "device_idle_pct.serve",
        "hbm_peak_gb.serve", "expert_load_peak_pct", "arena_copy_ms",
        "step_mlp_ms", "step_head_ms", "step_unscoped_ms"}
    # every tpu_custom_call of the trace is a decode kernel to the one,
    # and the experts' scope was under its bytes in the other cell of
    # this deck
    assert not {"decode_attn_roofline_pct", "moe_experts_ms",
                "moe_experts_roofline_pct", "mla_decode_ms",
                "step_attn_ms"} & set(c.per_layer)
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    # no trace on the CPU: the device readers leave their metrics out,
    # the counters' readers give theirs
    assert {"arena_tick_ms", "arena_occupancy_pct", "closed_ttft_p95_ms",
            "expert_load_peak_pct",
            "window_kv_share_pct"} <= set(traced["metrics"])
    assert not set(NEW[:5]) & set(traced["metrics"])
    assert 0 < traced["metrics"]["window_kv_share_pct"]["value"] < 100


def test_the_arena_holds_rings_beside_full_caches_and_the_step_counts(job):
    counters = job["counters"]
    one = 4 * 2 * 16 * 4 * 2       # 4 slots, keys and values, float32
    assert counters.state_bytes == {"kv": 2 * 128 * one, "recurrent": 0,
                                    "ring": 6 * 8 * one}
    assert counters.steps >= job["run"]["ticks"] > 0
    ring, full = (counters.sums[k] for k in ("kv_positions_window",
                                             "kv_positions_full"))
    # a row reads at most the window in each of 6 rings
    assert 0 < ring <= counters.steps * 4 * 6 * 8
    assert full > 0
    assert counters.expert_tokens.shape == (8,)
    assert counters.prefill_resteps == 0 < counters.prefills


# --------------------------------------------------------------------------
# the family's description of the program
# --------------------------------------------------------------------------

def test_the_program_declares_the_familys_leaves_at_the_tiny_size():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    assert dims.held == (0, 8) and dims.experts == 16 and dims.top_k == 4
    assert dims.kinds[:4] == ("full_attention",) + ("sliding_attention",) * 3
    assert dims.heads == (6, 8, 8, 8) * 2 and dims.window == 8
    assert dims.full_rotary == 8 and dims.sliding_rotary == 16
    model = fam.build_model(c.config, dims, "float32", 128, False)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cache_kinds == ["kv"] * 8
    assert model.cache_records == ["heads", "ring", "ring", "ring"] * 2
    assert [b.moe is None for b in model.blocks] == [True] + [False] * 7
    with pytest.raises(ValueError, match="gating"):
        fam.Dims.from_config(dict(c.config, gating=False))
    mixed = dict(c.config, num_attention_heads_per_layer=[6, 8, 8, 6] * 2)
    with pytest.raises(ValueError, match="heads"):
        fam.build_model(mixed, fam.Dims.from_config(mixed), "float32",
                        128, False)


def test_the_program_declares_the_familys_leaves_at_the_published_size():
    """Under ``jax.eval_shape``: nothing is allocated."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    box = {}

    def construct():
        box["model"] = fam.build_model(c.config, dims, "bfloat16", 16384,
                                       False)
        return dict(box["model"].named_parameters())

    import paddle_tpu as pt
    pt.seed(0)
    shapes = jax.eval_shape(construct)
    pt.seed(0)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in shapes.items()))
    arena = jax.eval_shape(lambda: box["model"].init_cache(16, 16384,
                                                           "bfloat16"))
    assert [a[0].shape for a in arena] == [
        (16, 512 if dims.is_sliding(i) else 16384, 8, 128)
        for i in range(20)]
    assert box["model"].cache_records.count("ring") == 15


def test_every_matrix_is_seeded():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    shapes = W.leaf_shapes(fam, dims)
    rules = {k: fam.leaf_rule(k, s) for k, s in shapes.items()}
    assert "zeros" not in rules.values()
    assert all(r == "uniform" for k, r in rules.items()
               if len(shapes[k]) > 1)
    assert rules["blocks.1.moe.w_down"] == rules["blocks.1.moe.score_bias"] \
        == rules["norm_f.weight"] == rules["blocks.1.mixer.gate_proj.weight"] \
        == "uniform"
    assert rules["blocks.0.norm1.weight"] == "ones"


def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())
    assert abs(count(W.leaf_shapes(fam, dims)) - 3.159e9) < 0.001e9
    assert abs(count(fam.layer_shapes(dims, 0)) - 79.79e6) < 0.01e6
    assert abs(count(fam.layer_shapes(dims, 4)) - 133.80e6) < 0.01e6
    assert abs(count(fam.layer_shapes(dims, 1)) - 142.22e6) < 0.01e6
    full, slide = fam.FULL, fam.SLIDING
    assert abs(fam.mixer_weights(dims, full) - 29.46e6) < 0.01e6
    assert abs(fam.mixer_weights(dims, slide) - 37.88e6) < 0.01e6
    assert (fam.kinds(dims, full), fam.kinds(dims, slide),
            fam.kinds(dims, "experts")) == (5, 15, 19)
    assert dims.held == (0, 32) and dims.experts == 256 and dims.top_k == 8
    assert dims.heads_of(full) == 48 and dims.heads_of(slide) == 64
    assert dims.full_rotary == 64 and dims.sliding_rotary == 128
    # 4096 bytes a position a layer: five full caches and fifteen rings
    assert fam.kv_bytes(dims) == 4096
    serve = c.config["serve"]
    caches, rings = fam.arena_bytes(dims, serve["slots"], serve["capacity"])
    assert abs(caches - 5.369e9) < 0.001e9 and abs(rings - 0.503e9) < 0.001e9
    every = 20 * serve["slots"] * serve["capacity"] * 4096
    assert abs(every - 21.5e9) < 0.05e9 and every > 16e9 > caches + rings
    # a position read costs 4096 bytes and 4 H d operations
    assert fam.gqa_step_bytes(dims, full, 1) - fam.gqa_step_bytes(
        dims, full, 0) == 4096
    assert fam.gqa_step_bytes(dims, slide, 0) == 2 * fam.mixer_weights(
        dims, slide)
    assert fam.gqa_step_flops(dims, full, 0, 1) == 4 * 48 * 128
    assert fam.gqa_step_flops(dims, slide, 1, 0) == 2 * fam.mixer_weights(
        dims, slide)
    # a band: every query past the window attends exactly 512 positions
    assert fam.attended_pairs(dims, full, 4096) == 4096 * 4097 // 2
    assert fam.attended_pairs(dims, slide, 4096) == (
        512 * 513 // 2 + (4096 - 512) * 512)
    assert fam.attended_pairs(dims, slide, 100) == 100 * 101 // 2
    assert fam.gqa_prefill_flops(dims, slide, 2) == 2 * 2 * (
        fam.mixer_weights(dims, slide)) + 3 * 4 * 64 * 128


# --------------------------------------------------------------------------
# the readers on a hand-made trace
# --------------------------------------------------------------------------

def ev(name, start, dur, **stats):
    return {"name": name, "start": start, "dur": dur, "line": 1,
            "stats": stats}


def trace(scoped=True):
    """Two decode steps of 12 ms around one prefill of bucket 4096. A
    step holds 2 ms under ``gqa_full_step`` (1.5 of them the kernel,
    nested in a 2 ms loop: self time counts it once), 3 ms under
    ``gqa_window_step`` and 2 under ``moe_experts``; the prefill holds
    30 ms under ``gqa_full_prefill`` and 10 under
    ``gqa_window_prefill``."""
    op = (lambda s: f"jit(pt_decode_step)/while/body/{s}/dot_general"
          ) if scoped else (lambda s: "jit(pt_decode_step)/while/body/dot")
    pre = (lambda s: f"jit(pt_prefill_4096)/{s}/dot_general"
           ) if scoped else (lambda s: "jit(pt_prefill_4096)/dot")
    ops = []
    for t0 in (0, 100 * MS):
        ops += [ev("%while.1 while", t0 + 1 * MS, 2 * MS,
                   tf_op=op("gqa_full_step")),
                ev("%pt_flash_decode.3 custom-call", t0 + 1 * MS,
                   int(1.5 * MS),
                   tf_op=op("gqa_full_step/pt_flash_decode")),
                ev("%fusion.9 fusion", t0 + 4 * MS, 3 * MS,
                   tf_op=op("gqa_window_step")),
                ev("%fusion.5 fusion", t0 + 8 * MS, 2 * MS,
                   tf_op=op("moe_experts"))]
    ops += [ev("%fusion.20 fusion", 20 * MS, 30 * MS,
               tf_op=pre("gqa_full_prefill")),
            ev("%fusion.21 fusion", 51 * MS, 10 * MS,
               tf_op=pre("gqa_window_prefill"))]
    ops.sort(key=lambda e: e["start"])
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_decode_step(1)", "start": 0, "dur": 12 * MS},
        {"name": "jit_pt_prefill_4096(7)", "start": 19 * MS,
         "dur": 50 * MS},
        {"name": "jit_pt_decode_step(1)", "start": 100 * MS,
         "dur": 12 * MS}]}


@pytest.fixture
def use(monkeypatch):
    def install(tr, sums=None, steps=1000):
        from paddle_tpu import serving

        monkeypatch.setattr(
            P, "load", lambda run, root=None: tr if run.get("trace")
            else None)
        counters = serving.ArenaCounters({"kv": 0, "recurrent": 0})
        counters.sums, counters.steps = dict(sums or {}), steps
        monkeypatch.setattr(serving, "last_counters", counters)
    return install


# 16 rows at 7000 positions in 5 full layers, at 512 in 15 rings
SUMS = {"kv_positions_full": 1000 * 5 * 16 * 7000,
        "kv_positions_window": 1000 * 15 * 16 * 512}


def a_run(**over):
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    fam = cell.family
    run = {"kind": "serve", "trace": {"some": "trace"}, "family": fam,
           "dims": fam.Dims.from_config(cell.config),
           "config": cell.config, "traffic": cell.traffic,
           "device": tiny.CPU_DEVICE, "ticks": 2000,
           "tick_tokens": 2000 * 15.5, "mean_context_tokens": 7000.0}
    run.update(over)
    return run


def read(metric, run):
    return manifest.load_reader(metric)(run)


def test_each_scope_is_read_inside_its_own_program(use):
    use(trace(), SUMS)
    run = a_run()
    assert read("gqa_full_step_ms", run) == pytest.approx(2.0)
    assert read("gqa_window_step_ms", run) == pytest.approx(3.0)
    assert read("moe_experts_ms", run) == pytest.approx(2.0)


def test_the_rooflines_are_the_familys_need_over_the_scopes_time(use):
    use(trace(), SUMS)
    run = a_run()
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    for metric, kind, layers, a_layer, ms in (
            ("gqa_full_roofline_pct", fam.FULL, 5, 16 * 7000, 2.0),
            ("gqa_window_roofline_pct", fam.SLIDING, 15, 16 * 512, 3.0)):
        by_bytes = layers * (a_layer * 4096 + 2 * fam.mixer_weights(
            dims, kind)) / peaks["hbm_bytes_per_s"]
        by_flops = layers * fam.gqa_step_flops(dims, kind, 16, a_layer
                                               ) / peaks["bf16_flops_per_s"]
        want = 100.0 * max(by_bytes, by_flops) * 1e3 / ms
        assert read(metric, run) == pytest.approx(want)
    # twice the positions read, more need, the same time: a larger share
    first = read("gqa_full_roofline_pct", run)
    use(trace(), dict(SUMS, kv_positions_full=2 * SUMS["kv_positions_full"]))
    assert read("gqa_full_roofline_pct", run) > 1.5 * first
    use(trace(), SUMS, steps=0)
    assert read("gqa_full_roofline_pct", run) is None


def test_the_share_of_the_read_that_lies_in_rings(use):
    use(trace(), SUMS)
    want = 100.0 * 15 * 512 / (15 * 512 + 5 * 7000)
    assert read("window_kv_share_pct", a_run()) == pytest.approx(want)
    assert read("window_kv_share_pct", a_run(trace=None)) == pytest.approx(
        want)       # a counter, not a trace
    use(trace(), {})
    assert read("window_kv_share_pct", a_run()) is None
    assert read("gqa_window_roofline_pct", a_run()) is None


def test_a_prefills_time_is_given_for_the_median_prompts_bucket(use):
    """Both scopes together; the trace's one prefill is of bucket 4096
    and the median prompt pads to 6144: its 40 ms are scaled by both
    kinds' operations at 6144 over those at 4096. A second prefill of
    bucket 8192 that is as efficient leaves the number where it was."""
    tr = trace()
    use(tr, SUMS)
    run = a_run()
    fam, dims = run["family"], run["dims"]
    f = lambda b: sum(fam.kinds(dims, k) * fam.gqa_prefill_flops(dims, k, b)
                      for k in (fam.FULL, fam.SLIDING))
    want = 40.0 * f(6144) / f(4096)
    assert 1.4 * 40.0 < want < 2.25 * 40.0
    assert read("gqa_prefill_ms", run) == pytest.approx(want)
    dur = int(40 * MS * f(8192) / f(4096))
    tr["ops"].append(ev("%fusion.30 fusion", 200 * MS, dur,
                        tf_op="jit(pt_prefill_8192)/gqa_full_prefill/dot"))
    tr["modules"].append({"name": "jit_pt_prefill_8192(9)",
                          "start": 199 * MS, "dur": dur + 2 * MS})
    assert read("gqa_prefill_ms", run) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("metric", NEW[:5])
def test_none_without_the_scope_or_without_a_trace(use, metric):
    use(trace(scoped=False), SUMS)           # the parent's program
    assert read(metric, a_run()) is None
    use(trace(), SUMS)
    assert read(metric, a_run(trace=None)) is None
    assert read(metric, a_run(kind="train")) is None
    # a recording of a real v5e serving trace (the chat cell's, PR 24):
    # no operation under any of these scopes
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "chat.spans.json")) as f:
        use(json.load(f), SUMS)
    assert read(metric, a_run()) is None


def test_no_scope_of_the_list_holds_another():
    """The older readers match a scope as a substring of an
    ``op_name``."""
    from paddle_tpu.telemetry import scopes

    names = list(scopes.SCOPES)
    assert {"gqa_full_step", "gqa_window_step", "gqa_full_prefill",
            "gqa_window_prefill"} <= set(names)
    assert not [(a, b) for a in names for b in names
                if a != b and a in b]


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_is_registered_for_the_cell_and_moves_the_gap(metric):
    man = manifest.load_manifest()
    row = {m["name"]: m for m in man["per_layer"]}[metric]
    assert row["workloads"] == [CELL]
    assert row["moves"] == "itl_p95_ms" and row["layer"] == "kernels"
    assert row["source"] == ("program_counter" if metric.startswith(
        "window") else "device_trace")
    assert row["unit"] == ("%" if metric.endswith("_pct") else "ms")
    assert os.path.exists(manifest.Cell(man, CELL).path(
        "layer_metrics", metric + ".py"))


# --------------------------------------------------------------------------
# the cell's files and the catalog row
# --------------------------------------------------------------------------

def test_every_file_of_the_cell_is_found_by_name():
    man = manifest.load_manifest()
    c = manifest.Cell(man, CELL)
    assert c.chips == 1 and c.kind == "serve"
    assert c.row["traffic"] == "longctx_closed16"
    assert c.config["family"] == "window_moe"
    assert c.family.reference.__name__.endswith("window_moe_f32")
    assert set(c.end_to_end) == {"serve_tokens_per_s", "itl_p95_ms",
                                 "setup_s"}
    assert check.load_limits(c)["served_gap_max"] > 0
    for metric in c.per_layer:
        assert callable(manifest.load_reader(metric))
    row = next(r for r in man["configs"] if r["name"] == "Laguna-XS.2")
    assert row["reduced"] == ["num_hidden_layers", "num_experts"]
    assert [r["key"] for r in c.config["reduced"]] == row["reduced"]
    # the deck is the Xing4.0 cell's, as it stands
    other = manifest.Cell(man, "Xing4.0-29B-A4B.longctx_closed16")
    assert other.traffic == c.traffic
    assert c.config["serve"]["slots"] == c.traffic["clients"] == 16
    lo, hi = c.traffic["prompt_tokens"], c.traffic["output_tokens"]
    assert hi["max"] + lo["max"] == c.config["serve"]["capacity"]
    assert len(c.config["assumed"]) >= 6 and c.config["stands_for"]


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    c = manifest.Cell(manifest.load_manifest(), CELL)
    assert c.config["source"] == row["source_url"]
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers", "num_experts"}
    for k, r in cut.items():
        assert r["published"] == row["config"][k]
        assert r["here"] == c.config[k]
    for k, v in row["config"].items():
        if k not in cut:
            assert c.config[k] == v, k
    assert c.config["num_hidden_layers"] == 20
    assert c.config["num_experts"] == 32
    # floors: five whole periods, 19 layers after the dense one, 32
    # experts, the whole vocabulary
    dims = c.family.Dims.from_config(c.config)
    assert dims.kinds == tuple(row["config"]["layer_types"][:20])
    assert dims.heads == tuple(
        row["config"]["num_attention_heads_per_layer"][:20])
    assert dims.mixes == ("dense",) + ("sparse",) * 19
    assert dims.vocab == row["config"]["vocab_size"]
