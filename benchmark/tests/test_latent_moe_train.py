"""The ``latent_moe_train`` family at a tiny size on the CPU: the
program's leaves are the family's; the cell through ``train_job.run``
(the one ``Trainer`` over ``forward_loss``, remat, the routers' buffers
travelling as ``new_buffers``) against its float32 reference, with the
float8 control over a limit set between the two; the result line with
the cell's metrics; the four new readers on a hand-made trace
(``test_spans_readers.py``'s way); the formulas at the published sizes;
the configuration's widths against the catalog row beside the
``model-configs`` guide. The shrink is this file's own."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import check, manifest, program_spans as P
from benchmark.harness import weights as W
from benchmark.tests import tiny

CELL = "kanana-2-30b-a3b-instruct-2601.pretrain_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MS = 1_000_000
NEW = ("moe_train_route_ms", "moe_train_experts_ms",
       "moe_train_experts_roofline_pct", "mla_flash_roofline_pct")
JOINED = ("trainer_step_ms", "trainer_dispatch_ms", "model_mfu_pct",
          "device_idle_pct.train", "hbm_peak_gb.train", "flash_fwd_ms",
          "flash_bwd_ms", "ce_head_ms", "block_attn_ms", "block_mlp_ms",
          "remat_forward_ms", "optimizer_ms", "train_unscoped_ms")


def tiny_cell() -> manifest.Cell:
    """Three blocks (one dense, two with 16 experts of which 4 are
    held), hidden 64, 4 heads of 16 + 8 / 16, a latent of 32, the query
    projected directly, a vocabulary of 256; widths cut for the CPU
    only."""
    c = manifest.Cell(manifest.load_manifest(), CELL)
    c.config.update(hidden_size=64, num_hidden_layers=3,
                    num_attention_heads=4, num_key_value_heads=4,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
                    head_dim=8, intermediate_size=96,
                    moe_intermediate_size=24, n_routed_experts=4,
                    num_experts_per_tok=4, vocab_size=256)
    for row in c.config["reduced"]:
        if row["key"] == "n_routed_experts":
            row["published"] = 16
    c.traffic.update(rows=2, seq=32, ring=4)
    return c


@pytest.fixture(scope="module")
def job():
    return tiny.run_job(tiny_cell(), control=True)


def test_the_program_declares_the_familys_leaves():
    c = tiny_cell()
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    assert dims.held == (0, 4) and dims.experts == 16 and dims.top_k == 4
    assert dims.q_rank == 0 and dims.gamma == 0.001
    assert dims.shared_width == 2 * 24
    model = fam.build_model(c.config, dims, "float32", 32, True)
    W.check_names(W.leaf_shapes(fam, dims),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    assert model.cfg.remat and model.cfg.hc_mult == 1
    assert [b.moe is None for b in model.blocks] == [True, False, False]
    assert sorted(model.named_buffers()) == [
        f"blocks.{i}.moe.{b}" for i in (1, 2)
        for b in ("bias_shift", "expert_load")]
    low = fam.Dims.from_config(dict(c.config, q_lora_rank=24))
    model = fam.build_model(c.config, low, "float32", 32, False)
    W.check_names(W.leaf_shapes(fam, low),
                  ((k, v.shape) for k, v in
                   model.named_parameters().items()))
    rules = {k: fam.leaf_rule(k, s)
             for k, s in W.leaf_shapes(fam, dims).items()}
    assert {k for k, r in rules.items() if r == "ones"} == {
        k for k in rules if "norm" in k}
    with pytest.raises(ValueError, match="rope_scaling"):
        fam.Dims.from_config(dict(c.config, rope_scaling={"type": "yarn"}))


def test_the_cell_trains_and_the_control_fails_a_limit(job):
    """The program computes in bfloat16 under the configuration's
    ``mixed_bf16`` and the reference in float32: the program's gaps are
    bfloat16's. The float8 control reads several times further from the
    reference, and a limit set between them (with room on both sides,
    the rule the chip's limits follow) passes the program and fails the
    control. The bias rule has run by then (two warm-up steps) and
    ``score_bias`` has not moved: its parameter change is 0 on both
    sides."""
    s, c = job["numbers"], job["control_numbers"]
    assert job["attempted"] > 0 and job["failed"] == 0
    assert all(np.isfinite(v) for v in s.values())
    assert s["loss_gap"] < 1e-4 and s["grad_norm_gap"] < 1e-2
    assert c["grad_norm_gap"] > 4 * s["grad_norm_gap"]
    between = dict(s, grad_norm_gap=(s["grad_norm_gap"]
                                     * c["grad_norm_gap"]) ** 0.5)
    between = {k: 1.5 * v for k, v in between.items()}
    assert check.judge(s, between, "sound")
    assert not check.judge(c, between, "control")
    assert set(check.load_limits(tiny_cell())) == set(s)


def test_result_line_has_the_cells_metrics(job):
    run_py = tiny.load_run_py()
    c = tiny_cell()
    line = json.loads(json.dumps(
        run_py.result_line(c, job, tiny.CPU_DEVICE, False)))
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(c.per_layer) == set(NEW) | set(JOINED)
    rows = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert {rows[m]["moves"] for m in c.per_layer} == {"train_tokens_per_s"}
    # every tpu_custom_call is not a flash kernel here: the grouped
    # expert products are such calls too
    assert "flash_roofline_pct" not in c.per_layer
    traced = run_py.result_line(c, job, tiny.CPU_DEVICE, True)
    assert {"trainer_step_ms", "trainer_dispatch_ms",
            "model_mfu_pct"} <= set(traced["metrics"])
    assert not set(NEW) & set(traced["metrics"])    # no trace on the CPU


# --------------------------------------------------------------------------
# the new readers on a hand-made trace
# --------------------------------------------------------------------------

def ev(name, start, dur, **stats):
    return {"name": name, "start": start, "dur": dur, "line": 1,
            "stats": stats}


def trace(scoped=True):
    """Two train steps of 100 ms. A step holds, under ``moe_route``, 2 ms
    forward + 1 recomputed + 3 backward; under ``moe_experts`` 4 + 4 + 8
    and two scope-less ``%ragged-dot`` calls of 1 ms; 5 ms under
    ``moe_shared``, which neither reader counts; flash kernels of 10
    (fwd, twice: remat), 12 (dq) and 18 (dkdv), the kernels nested in a
    30 ms ``attn`` region that no reader here counts."""
    f = "jit(pt_train_step)/jvp({s})/dot_general"
    r = ("jit(pt_train_step)/transpose(jvp())/checkpoint/"
         "rematted_computation/{s}/dot_general")
    b = "jit(pt_train_step)/transpose(jvp())/checkpoint/{s}/dot_general"
    if not scoped:
        f = r = b = "jit(pt_train_step)/dot_general"
    ops = []
    for t0 in (0, 200 * MS):
        at = [t0 + MS]

        def put(name, ms, op):
            ops.append(ev(name, at[0], int(ms * MS), tf_op=op))
            at[0] += int(ms * MS)

        for tpl, route, experts in ((f, 2, 4), (r, 1, 4), (b, 3, 8)):
            put("%fusion.1 fusion", route, tpl.format(s="moe_route"))
            put("%fusion.2 fusion", experts, tpl.format(s="moe_experts"))
        put("%ragged-dot.7 custom-call", 1, "")
        put("%ragged_dot.8 custom-call", 1, "")
        put("%fusion.3 fusion", 5, f.format(s="moe_shared"))
        for name, ms in (("%pt_flash_fwd.1", 10), ("%pt_flash_fwd.2", 10),
                         ("%pt_flash_dq.3", 12), ("%pt_flash_dkdv.4", 18)):
            put(name + " custom-call", ms, f.format(s="attn/mla_prefill"))
    ops.sort(key=lambda e: e["start"])
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_train_step(5)", "start": t0, "dur": 100 * MS}
        for t0 in (0, 200 * MS)]}


@pytest.fixture
def use(monkeypatch):
    def install(tr):
        monkeypatch.setattr(
            P, "load", lambda run, root=None: tr if run.get("trace")
            else None)
    return install


def a_run(**over):
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    fam = cell.family
    run = {"kind": "train", "trace": {"some": "trace"}, "family": fam,
           "dims": fam.Dims.from_config(cell.config),
           "config": cell.config, "traffic": cell.traffic,
           "device": tiny.CPU_DEVICE}
    run.update(over)
    return run


def read(metric, run):
    return manifest.load_reader(metric)(run)


def test_route_and_experts_are_read_in_all_three_passes(use):
    use(trace())
    run = a_run()
    assert read("moe_train_route_ms", run) == pytest.approx(6.0)
    assert read("moe_train_experts_ms", run) == pytest.approx(18.0)


def test_the_experts_share_is_the_expected_pairs_need(use):
    use(trace())
    run = a_run()
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    # 5 layers x 9 products x 2 x 2048 x 768 x (16384 tokens x 0.75 pairs)
    ops = 5 * 9 * 2 * 2048 * 768 * 16384 * 0.75
    assert 5 * fam.expert_train_flops(dims, 16384) == pytest.approx(ops)
    # 16 experts' 4.72 M numbers: bf16 three times, a float32 gradient
    nbytes = 5 * 16 * 3 * 2048 * 768 * (3 * 2 + 4)
    assert 5 * fam.expert_train_bytes(dims) == nbytes
    need = max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
    assert read("moe_train_experts_roofline_pct", run) == pytest.approx(
        100.0 * need * 1e3 / 18.0)


def test_the_flash_share_counts_useful_work_over_the_kernels_alone(use):
    use(trace())
    run = a_run()
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    pairs = 8192 * 8193 // 2
    fwd = 2 * 32 * (192 + 128) * pairs
    assert fam.attention_flops(dims, 8192, False) == fwd
    assert fam.attention_flops(dims, 8192, True) == 2 * fwd
    moved = 2 * 8192 * 32 * (192 + 128) * 2
    assert fam.attention_bytes(dims, 8192, 2, False) == moved
    assert fam.attention_bytes(dims, 8192, 2, True) == 2 * moved
    need = 6 * 2 * 3 * fwd / peaks["bf16_flops_per_s"]     # compute-bound
    assert read("mla_flash_roofline_pct", run) == pytest.approx(
        100.0 * need * 1e3 / 50.0)
    # the share of the accepted reader would count the ragged-dot calls
    assert "flash_roofline_pct" not in manifest.Cell(
        manifest.load_manifest(), CELL).per_layer


@pytest.mark.parametrize("metric", NEW)
def test_none_without_the_scope_or_without_a_trace(use, metric):
    tr = trace(scoped=False)                 # a program without them
    tr["ops"] = [e for e in tr["ops"] if "custom-call" not in e["name"]]
    use(tr)
    assert read(metric, a_run()) is None
    use(trace())
    assert read(metric, a_run(trace=None)) is None
    assert read(metric, a_run(kind="serve")) is None
    # the dense train cell's family has no expert formulas: the share
    # is left out and does not raise; the flash share needs `kinds`
    dense = manifest.Cell(manifest.load_manifest(),
                          "internlm2-1.8b.pretrain_2k")
    if metric.endswith("roofline_pct"):
        assert read(metric, a_run(family=dense.family)) is None


def test_they_are_registered_for_the_cell_and_move_the_rate():
    man = manifest.load_manifest()
    rows = {m["name"]: m for m in man["per_layer"]}
    for metric in NEW:
        assert rows[metric]["workloads"] == [CELL]
        assert rows[metric]["moves"] == "train_tokens_per_s"
        assert rows[metric]["source"] == "device_trace"
        assert rows[metric]["layer"] == "kernels"
    for metric in JOINED:
        assert rows[metric]["workloads"][-1] == CELL
    assert [m["name"] for m in man["per_layer"]][-4:] == list(NEW)
    cell = manifest.Cell(man, CELL)
    assert set(cell.end_to_end) == {"train_tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.kind == "train"
    assert man["workloads"][-1]["name"] == CELL


# --------------------------------------------------------------------------
# the sizes
# --------------------------------------------------------------------------

def test_formulas_at_the_published_sizes():
    c = manifest.Cell(manifest.load_manifest(), CELL)
    fam, dims = c.family, c.family.Dims.from_config(c.config)
    count = lambda shapes: sum(int(np.prod(s)) for s in shapes.values())
    assert abs(count(W.leaf_shapes(fam, dims)) - 687.5e6) < 0.05e6
    assert abs(count(fam.layer_shapes(dims, 0)) - 64.1e6) < 0.05e6
    assert abs(count(fam.layer_shapes(dims, 1)) - 111.55e6) < 0.05e6
    assert abs(fam.mixer_matmul_params(dims) - 26.35e6) < 0.01e6
    assert fam.expert_params(dims) == 3 * 2048 * 768
    assert fam.held_pairs_per_token(dims) == 0.75
    assert fam.kinds(dims, "latent") == 6
    assert fam.kinds(dims, "experts") == 5
    assert dims.held == (0, 16) and dims.experts == 128 and dims.top_k == 6
    assert dims.scaling == 2.448 and dims.q_rank == 0
    assert dims.vocab == 16032 and dims.shared_width == 1536
    # 1.77 GFLOP of products + 1.51 of causal attention a token
    per = fam.train_flops_per_token(dims, 8192)
    assert abs(6 * fam.matmul_params(dims) - 1.77e9) < 0.01e9
    assert abs(per - 3.28e9) < 0.01e9
    assert (c.traffic["rows"], c.traffic["seq"], c.traffic["ring"],
            c.traffic["check_steps"], c.traffic["warmup_steps"]) == (
                2, 8192, 16, 1, 2)
    assert c.config["train"] == {
        "amp": "mixed_bf16", "remat": True, "optimizer": "adam",
        "loss": "fused linear cross-entropy (forward_loss)",
        "router_bias_update_rate": 0.001}


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the guide's catalog is not on this machine")
def test_no_width_differs_from_the_catalog_row():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    man = manifest.load_manifest()
    c = manifest.Cell(man, CELL)
    assert c.config["source"] == row["source_url"]
    assert len(c.config["source"]) <= 200
    cut = {r["key"]: r for r in c.config["reduced"]}
    assert set(cut) == {"num_hidden_layers", "n_routed_experts",
                        "vocab_size"}
    entry = next(e for e in man["configs"]
                 if e["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert set(entry["reduced"]) == set(cut)
    for k, r in cut.items():
        assert r["published"] == row["config"][k]
        assert r["here"] == c.config[k]
    for k, v in row["config"].items():
        if k not in cut:
            assert c.config[k] == v, k
    assert "stands_for" in c.config
    assert any("gamma" in a for a in c.config["assumed"])
