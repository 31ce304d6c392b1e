"""The four readers the ``latent_moe`` family brings (``mla_decode_ms``,
``mla_decode_roofline_pct``, ``mla_prefill_ms``, ``mhc_mix_ms``) on a
hand-made serving trace, ``test_spans_readers.py``'s way: a scope is
read inside the runs of ONE program (the decode step and a prefill
share scopes), by self time; a program without the scope (the parent of
the PR that added it) and a run without a trace give None, not an
error."""

import json
import os

import pytest

from benchmark.harness import manifest, program_spans as P
from benchmark.tests import tiny

CELL = "Xing4.0-29B-A4B.longctx_closed16"
MS = 1_000_000
NEW = ("mla_decode_ms", "mla_decode_roofline_pct", "mla_prefill_ms",
       "mhc_mix_ms")


def ev(name, start, dur, **stats):
    return {"name": name, "start": start, "dur": dur, "line": 1,
            "stats": stats}


def trace(scoped=True):
    """Two decode steps of 12 ms around one prefill of bucket 4096. A
    step holds 3 ms under ``mla_decode`` (1 of them the kernel, nested
    in a 2.5 ms loop: self time counts it once), 1 ms under ``mhc_mix``
    and 2 under ``moe_experts``; the prefill holds 40 ms under
    ``mla_prefill`` and 5 under ``mhc_mix``, which no decode reader may
    count."""
    op = (lambda s: f"jit(pt_decode_step)/while/body/{s}/dot_general"
          ) if scoped else (lambda s: "jit(pt_decode_step)/while/body/dot")
    pre = (lambda s: f"jit(pt_prefill_4096)/{s}/dot_general"
           ) if scoped else (lambda s: "jit(pt_prefill_4096)/dot")
    ops = []
    for t0 in (0, 100 * MS):
        ops += [ev("%while.1 while", t0 + 1 * MS, int(2.5 * MS),
                   tf_op=op("mla_decode")),
                ev("%pt_mla_decode.3 custom-call", t0 + 2 * MS, 1 * MS,
                   tf_op=op("mla_decode/pt_mla_decode")),
                ev("%fusion.9 fusion", t0 + 4 * MS, int(0.5 * MS),
                   tf_op=op("mla_decode")),
                ev("%fusion.4 fusion", t0 + 5 * MS, 1 * MS,
                   tf_op=op("mhc_mix")),
                ev("%fusion.5 fusion", t0 + 7 * MS, 2 * MS,
                   tf_op=op("moe_experts"))]
    ops += [ev("%fusion.20 fusion", 20 * MS, 40 * MS,
               tf_op=pre("mla_prefill")),
            ev("%fusion.21 fusion", 61 * MS, 5 * MS, tf_op=pre("mhc_mix"))]
    ops.sort(key=lambda e: e["start"])
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_decode_step(1)", "start": 0, "dur": 12 * MS},
        {"name": "jit_pt_prefill_4096(7)", "start": 19 * MS,
         "dur": 50 * MS},
        {"name": "jit_pt_decode_step(1)", "start": 100 * MS,
         "dur": 12 * MS}]}


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
    run = {"kind": "serve", "trace": {"some": "trace"}, "family": fam,
           "dims": fam.Dims.from_config(cell.config),
           "config": cell.config, "traffic": cell.traffic,
           "device": tiny.CPU_DEVICE,
           "ticks": 2000, "tick_tokens": 2000 * 15.5,
           "mean_context_tokens": 7000.0}
    run.update(over)
    return run


def read(metric, run):
    return manifest.load_reader(metric)(run)


def test_each_scope_is_read_inside_its_own_program(use):
    use(trace())
    run = a_run()
    assert read("mla_decode_ms", run) == pytest.approx(3.0)
    assert read("mhc_mix_ms", run) == pytest.approx(1.0)
    assert read("moe_experts_ms", run) == pytest.approx(2.0)


def test_a_prefills_time_is_given_for_the_median_prompts_bucket(use):
    """The trace's one prefill is of bucket 4096 and the median prompt
    pads to 6144: its 40 ms are scaled by the mixers' operations at
    6144 over those at 4096. A second prefill of bucket 8192 that is as
    efficient (its time in proportion to its operations) leaves the
    number where it was, whatever the draw of buckets."""
    tr = trace()
    use(tr)
    run = a_run()
    fam, dims = run["family"], run["dims"]
    f = lambda b: fam.mla_prefill_flops(dims, b)
    want = 40.0 * f(6144) / f(4096)
    assert 1.5 * 40.0 < want < 2.25 * 40.0
    assert read("mla_prefill_ms", run) == pytest.approx(want)
    dur = int(40 * MS * f(8192) / f(4096))
    tr["ops"].append(ev("%fusion.30 fusion", 200 * MS, dur,
                        tf_op="jit(pt_prefill_8192)/mla_prefill/dot"))
    tr["modules"].append({"name": "jit_pt_prefill_8192(9)",
                          "start": 199 * MS, "dur": dur + 2 * MS})
    assert read("mla_prefill_ms", run) == pytest.approx(want, rel=1e-6)


def test_the_roofline_is_the_familys_need_over_the_scopes_time(use):
    use(trace())
    run = a_run()
    fam, dims, peaks = run["family"], run["dims"], run["device"]["peaks"]
    context = 15.5 * 7000.0
    by_bytes = 10 * (context * 1152 + 2 * fam.mixer_weights(dims)) / peaks[
        "hbm_bytes_per_s"]
    by_flops = 10 * fam.mla_decode_flops(dims, 16, context) / peaks[
        "bf16_flops_per_s"]
    want = 100.0 * max(by_bytes, by_flops) * 1e3 / 3.0
    assert read("mla_decode_roofline_pct", run) == pytest.approx(want)
    # twice the live positions, more need, the same time: a larger share
    assert read("mla_decode_roofline_pct",
                a_run(mean_context_tokens=14000.0)) > 1.5 * want
    assert read("mla_decode_roofline_pct", a_run(ticks=0)) is None


@pytest.mark.parametrize("metric", NEW)
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


def test_they_are_registered_for_the_cell_and_move_the_gap():
    man = manifest.load_manifest()
    rows = {m["name"]: m for m in man["per_layer"]}
    for metric in NEW:
        assert rows[metric]["workloads"] == [CELL]
        assert rows[metric]["moves"] == "itl_p95_ms"
        assert rows[metric]["source"] == "device_trace"
    assert rows["mla_decode_roofline_pct"]["unit"] == "%"
    cell = manifest.Cell(man, CELL)
    assert set(NEW) <= set(cell.per_layer)
    assert {"serve_tokens_per_s", "itl_p95_ms", "setup_s"} == set(
        cell.end_to_end)
