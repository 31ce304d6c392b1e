"""The five readers the ``sparse_latent_moe`` family brings
(``dsa_index_ms``, ``dsa_index_roofline_pct``, ``dsa_read_roofline_pct``,
``dsa_prefill_index_ms``, ``dsa_read_share_pct``) on a hand-made serving
trace, ``test_latent_readers.py``'s way: ``dsa_index`` is a SIBLING of
``mla_decode`` / ``mla_prefill``, so the indexer's time and the mixer's
are read apart inside the runs of one program; a program without the
scope or the counters (the parent of the PR that added them), a run
without a trace and a recorded trace of another cell give None, not an
error."""

import json
import os
import types

import pytest

from benchmark.harness import manifest, program_spans as P
from benchmark.tests import tiny

CELL = "GLM-5.longctx32k_closed16"
MS = 1_000_000
TRACED = ("dsa_index_ms", "dsa_index_roofline_pct", "dsa_read_roofline_pct",
          "dsa_prefill_index_ms")
NEW = TRACED + ("dsa_read_share_pct",)


def ev(name, start, dur, **stats):
    return {"name": name, "start": start, "dur": dur, "line": 1,
            "stats": stats}


def trace(scoped=True):
    """Two decode steps of 14 ms around one prefill of bucket 8192. A
    step holds 2 ms under ``dsa_index`` (a 1.5 ms loop with a 0.5 ms
    fusion nested in it and 0.5 ms beside: self time counts each once),
    4 ms under ``mla_decode`` (1 of them the read's kernel) and 3 under
    ``moe_experts``; the prefill holds 30 ms under ``dsa_index`` (10 of
    them ``%pt_dsa_scores``) and 200 under ``mla_prefill``."""
    op = (lambda s: f"jit(pt_decode_step)/while/body/{s}/dot_general"
          ) if scoped else (lambda s: "jit(pt_decode_step)/while/body/dot")
    pre = (lambda s: f"jit(pt_prefill_8192)/{s}/dot_general"
           ) if scoped else (lambda s: "jit(pt_prefill_8192)/dot")
    ops = []
    for t0 in (0, 400 * MS):
        ops += [ev("%while.1 while", t0 + 1 * MS, int(1.5 * MS),
                   tf_op=op("dsa_index")),
                ev("%fusion.2 fusion", t0 + int(1.5 * MS), int(0.5 * MS),
                   tf_op=op("dsa_index")),
                ev("%fusion.3 fusion", t0 + 3 * MS, int(0.5 * MS),
                   tf_op=op("dsa_index")),
                ev("%fusion.4 fusion", t0 + 4 * MS, 3 * MS,
                   tf_op=op("mla_decode")),
                ev("%pt_mla_decode.3 custom-call", t0 + 7 * MS, 1 * MS,
                   tf_op=op("mla_decode/pt_mla_decode")),
                ev("%fusion.5 fusion", t0 + 9 * MS, 3 * MS,
                   tf_op=op("moe_experts"))]
    ops += [ev("%fusion.20 fusion", 20 * MS, 20 * MS, tf_op=pre("dsa_index")),
            ev("%pt_dsa_scores.7 custom-call", 41 * MS, 10 * MS,
               tf_op=pre("dsa_index/pt_dsa_scores")),
            ev("%pt_dsa_prefill.8 custom-call", 60 * MS, 200 * MS,
               tf_op=pre("mla_prefill/pt_dsa_prefill"))]
    ops.sort(key=lambda e: e["start"])
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_decode_step(1)", "start": 0, "dur": 14 * MS},
        {"name": "jit_pt_prefill_8192(7)", "start": 19 * MS,
         "dur": 300 * MS},
        {"name": "jit_pt_decode_step(1)", "start": 400 * MS,
         "dur": 14 * MS}]}


@pytest.fixture
def use(monkeypatch):
    def install(tr):
        monkeypatch.setattr(
            P, "load", lambda run, root=None: tr if run.get("trace")
            else None)
    return install


@pytest.fixture
def counted(monkeypatch):
    """What ``serving.last_counters`` holds after a run of the cell."""
    def install(sums, steps=100):
        from paddle_tpu import serving
        monkeypatch.setattr(serving, "last_counters", types.SimpleNamespace(
            sums=sums, steps=steps))
    return install


def a_run(**over):
    cell = manifest.Cell(manifest.load_manifest(), CELL)
    fam = cell.family
    run = {"kind": "serve", "trace": {"some": "trace"}, "family": fam,
           "dims": fam.Dims.from_config(cell.config),
           "config": cell.config, "traffic": cell.traffic,
           "device": tiny.CPU_DEVICE,
           "ticks": 2000, "tick_tokens": 2000 * 15.5,
           "mean_context_tokens": 13000.0}
    run.update(over)
    return run


def read(metric, run):
    return manifest.load_reader(metric)(run)


def test_the_indexer_and_the_mixer_are_read_apart(use):
    use(trace())
    run = a_run()
    assert read("dsa_index_ms", run) == pytest.approx(2.0)
    assert read("mla_decode_ms", run) == pytest.approx(4.0)
    assert read("mla_prefill_ms", run) is not None


def test_a_prefills_index_time_is_given_for_the_median_prompts_bucket(use):
    """The trace's one prefill is of bucket 8192 and the median prompt
    pads to 12288: its 30 ms are scaled by the indexers' operations at
    12288 over those at 8192."""
    use(trace())
    run = a_run()
    fam, dims = run["family"], run["dims"]
    f = lambda b: fam.dsa_prefill_index_flops(dims, b)
    want = 30.0 * f(12288) / f(8192)
    assert 1.5 * 30.0 < want < 2.25 * 30.0
    assert read("dsa_prefill_index_ms", run) == pytest.approx(want)


def test_the_shares_are_the_familys_need_over_the_scopes_time(use):
    use(trace())
    run = a_run()
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    context = 15.5 * 13000.0
    need = max(5 * (context * 256 + 2 * fam.index_weights(dims))
               / peaks["hbm_bytes_per_s"],
               5 * fam.dsa_index_flops(dims, 16, context)
               / peaks["bf16_flops_per_s"])
    assert read("dsa_index_roofline_pct", run) == pytest.approx(
        100.0 * need * 1e3 / 2.0)
    picked = 15.5 * 2048
    need = max(5 * (picked * 1152 + 2 * fam.mixer_weights(dims))
               / peaks["hbm_bytes_per_s"],
               5 * fam.mla_decode_flops(dims, 16, picked)
               / peaks["bf16_flops_per_s"])
    assert read("dsa_read_roofline_pct", run) == pytest.approx(
        100.0 * need * 1e3 / 4.0)
    # a longer context asks more of the indexer and nothing more of the
    # read: the pick is 2048 records either way
    far = a_run(mean_context_tokens=26000.0)
    assert read("dsa_index_roofline_pct", far) > read(
        "dsa_index_roofline_pct", run)
    assert read("dsa_read_roofline_pct", far) == pytest.approx(
        read("dsa_read_roofline_pct", run))
    assert read("dsa_read_roofline_pct", a_run(ticks=0)) is None
    assert read("dsa_index_roofline_pct", a_run(ticks=0)) is None


def test_the_read_share_is_the_counters_quotient(counted):
    counted({"dsa_positions_live": 1_000_000, "dsa_positions_read": 157_000})
    assert read("dsa_read_share_pct", a_run(trace=None)) == pytest.approx(
        15.7)
    counted({"expert_tokens": [1, 2]})          # the parent's counters
    assert read("dsa_read_share_pct", a_run()) is None
    counted({"dsa_positions_live": 0, "dsa_positions_read": 0})
    assert read("dsa_read_share_pct", a_run()) is None
    assert read("dsa_read_share_pct", a_run(kind="train")) is None


@pytest.mark.parametrize("metric", TRACED)
def test_none_without_the_scope_or_without_a_trace(use, metric):
    use(trace(scoped=False))                 # the parent's program
    assert read(metric, a_run()) is None
    use(trace())
    assert read(metric, a_run(trace=None)) is None
    assert read(metric, a_run(kind="train")) is None
    # a recording of a real v5e serving trace (the chat cell's, PR 24):
    # programs named with their hashes, prefills of other buckets, no
    # operation under any of these scopes
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "chat.spans.json")) as f:
        use(json.load(f))
    assert read(metric, a_run()) is None


def test_another_familys_cell_reads_none_of_the_shares(use):
    """The readers ask the FAMILY for the need: a family without the
    formulas (the other latent cell's) gives None, not an error."""
    use(trace())
    other = manifest.Cell(manifest.load_manifest(),
                          "Xing4.0-29B-A4B.longctx_closed16")
    fam = other.family
    run = a_run(family=fam, dims=fam.Dims.from_config(other.config),
                config=other.config, traffic=other.traffic)
    assert read("dsa_read_roofline_pct", run) is None
    assert read("dsa_prefill_index_ms", run) is None


def test_they_are_registered_for_the_cell_and_move_the_gap():
    man = manifest.load_manifest()
    rows = {m["name"]: m for m in man["per_layer"]}
    for metric in NEW:
        assert rows[metric]["workloads"] == [CELL]
        assert rows[metric]["moves"] == "itl_p95_ms"
        assert rows[metric]["layer"] == "kernels"
    assert all(rows[m]["source"] == "device_trace" for m in TRACED)
    assert rows["dsa_read_share_pct"]["source"] == "program_counter"
    assert {rows[m]["unit"] for m in NEW if m.endswith("_pct")} == {"%"}
    cell = manifest.Cell(man, CELL)
    assert set(NEW) <= set(cell.per_layer)
    assert {"itl_p95_ms", "setup_s"} == set(cell.end_to_end)
    assert [m["name"] for m in man["per_layer"]][-5:] == list(
        TRACED[:1] + TRACED[1:3] + TRACED[3:] + ("dsa_read_share_pct",))
