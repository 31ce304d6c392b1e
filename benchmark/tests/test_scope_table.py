"""``harness/scope_table.py`` and the ten metrics that read it, on
hand-made traces (``test_spans_readers.py``'s way): an operation belongs
to the outermost LISTED scope that is a whole component of its
``op_name``, the pass comes from the same path, a loop and its body
count once, the scopes and the remainder add up to the runs' self time
to the nanosecond, one program's runs are read and no other's, and a
program without the list (the parent of the PR that brought it) gives
None, not an error."""

import sys

import pytest

from benchmark.harness import manifest, program_spans as P, scope_table

MS = 1_000_000
TRAIN = "jit(pt_train_step)/"
BWD = TRAIN + "transpose(jvp(jvp()))/checkpoint/"
TRAIN_CELL = "internlm2-1.8b.pretrain_2k"
MISTRAL = "mistral-7b-v0.1.chat_closed16"
HYBRID = "granite-4.0-h-small.chat_closed32"
BRUMBY = "Brumby-14B-Base.longgen_closed16"
XING = "Xing4.0-29B-A4B.longctx_closed16"
SERVING = [MISTRAL, HYBRID, BRUMBY, XING]
# metric -> (its cells, what it reads on the traces below)
NEW = {
    "block_attn_ms": ([TRAIN_CELL], 10 + 4 + 2 + 20),
    "block_mlp_ms": ([TRAIN_CELL], 30 + 25 + 60),
    "remat_forward_ms": ([TRAIN_CELL], 4 + 2 + 25),
    "optimizer_ms": ([TRAIN_CELL], 22),
    "train_unscoped_ms": ([TRAIN_CELL], 3 + 1 + 2 + 5),
    "step_attn_ms": ([MISTRAL, HYBRID], 3.0),
    "step_mlp_ms": ([MISTRAL, BRUMBY, XING], 6.0),
    "step_head_ms": (SERVING, 0.5),
    "step_unscoped_ms": (SERVING, 1.5),
    "prefill_run_ms": ([MISTRAL, HYBRID], 31.0),
}


def ev(name, start, dur, op=""):
    return {"name": name, "start": start, "dur": dur, "line": 1,
            "stats": {"tf_op": op} if op else {}}


def train_trace():
    """Two steps of 200 ms. A step: ``attn`` forward 10, recomputed 4 +
    2 (the flash kernel under its own name inside the scope), backward
    20; ``mlp`` 30 / 25 / 60; ``linear_ce`` a 40 ms loop whose body's
    product takes 30 of them; ``optimizer`` 22; ``embed`` 1 + 2. Under
    no scope: a product under ``latent_attention`` (3), a norm under
    ``heads`` (1), one inside a function called ``mlp`` (2) and a copy
    the compiler made, with no ``op_name`` at all (5)."""
    ops = []
    for t0 in (0, 300 * MS):
        at = lambda ms: t0 + int(ms * MS)
        ops += [
            ev("%fusion.1 fusion", at(0), 1 * MS,
               TRAIN + "jvp(embed)/gather"),
            ev("%fusion.2 fusion", at(1), 10 * MS,
               TRAIN + "jvp(attn)/dot_general"),
            ev("%fusion.3 fusion", at(11), 30 * MS,
               TRAIN + "jvp(mlp)/dot_general"),
            ev("%while.4 while", at(41), 40 * MS,
               TRAIN + "jvp(linear_ce)/while"),
            ev("%fusion.5 fusion", at(45), 30 * MS,
               TRAIN + "jvp(linear_ce)/while/body/dot_general"),
            ev("%fusion.6 fusion", at(81), 25 * MS,
               BWD + "rematted_computation/mlp/dot_general"),
            ev("%fusion.7 fusion", at(106), 4 * MS,
               BWD + "rematted_computation/attn/dot_general"),
            ev("%pt_flash_fwd.8 custom-call", at(110), 2 * MS, BWD +
               "rematted_computation/attn/pt_flash_fwd/pt_flash_fwd"),
            ev("%fusion.9 fusion", at(112), 60 * MS,
               BWD + "mlp/dot_general"),
            ev("%fusion.10 fusion", at(172), 20 * MS,
               BWD + "attn/transpose"),
            ev("%fusion.11 fusion", at(192), 2 * MS,
               TRAIN + "transpose(jvp(embed))/scatter-add"),
            ev("%fusion.12 fusion", at(194), 22 * MS,
               TRAIN + "optimizer/mul"),
            ev("%fusion.13 fusion", at(216), 3 * MS,
               TRAIN + "jvp(latent_attention)/dot_general"),
            ev("%fusion.14 fusion", at(219), 1 * MS,
               TRAIN + "jvp(heads)/rsqrt"),
            ev("%fusion.15 fusion", at(220), 2 * MS,
               TRAIN + "jvp(jit(mlp))/dot_general"),
            ev("%copy.16 copy", at(222), 5 * MS)]
    ops.sort(key=lambda e: (e["start"], -e["dur"]))
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_train_step(5)", "start": 0, "dur": 230 * MS},
        {"name": "jit_pt_train_step(5)", "start": 300 * MS,
         "dur": 230 * MS}]}


def serve_trace():
    """Two decode steps of 12 ms around one prefill of bucket 512 (31 ms)
    and two of bucket 256 (17 and 19). A step: ``attn`` 3 (its kernel 1
    of them, and a ``mhc_mix`` nested in it 0.5: the outer scope has
    it), ``mlp`` 6, ``head`` 0.5 with the pick, unscoped a norm 1 and a
    copy 0.5. The prefill holds 20 ms under ``mlp``, which no reader of
    the step may count."""
    step = lambda s: f"jit(pt_decode_step)/while/body/{s}"
    ops = []
    for t0 in (0, 100 * MS):
        at = lambda ms: t0 + int(ms * MS)
        ops += [ev("%fusion.1 fusion", at(0), int(1.5 * MS),
                   step("attn/dot_general")),
                ev("%pt_flash_decode.2 custom-call", at(1.5), 1 * MS,
                   step("attn/pt_flash_decode/pt_flash_decode")),
                ev("%fusion.3 fusion", at(2.5), int(0.5 * MS),
                   step("attn/mhc_mix/mul")),
                ev("%fusion.4 fusion", at(3), 6 * MS,
                   step("mlp/dot_general")),
                ev("%fusion.5 fusion", at(9), int(0.4 * MS),
                   step("head/dot_general")),
                ev("%fusion.6 fusion", at(9.4), int(0.1 * MS),
                   step("head/argmax")),
                ev("%fusion.7 fusion", at(9.5), 1 * MS,
                   step("rms_norm/mul")),
                ev("%copy.8 copy", at(10.5), int(0.5 * MS))]
    ops.append(ev("%fusion.20 fusion", 20 * MS, 20 * MS,
                  "jit(pt_prefill_512)/mlp/dot_general"))
    ops.sort(key=lambda e: (e["start"], -e["dur"]))
    return {"host": [], "ops": ops, "modules": [
        {"name": "jit_pt_decode_step(1)", "start": 0, "dur": 12 * MS},
        {"name": "jit_pt_prefill_512(7)", "start": 19 * MS,
         "dur": 31 * MS},
        {"name": "jit_pt_prefill_256(6)", "start": 51 * MS,
         "dur": 17 * MS},
        {"name": "jit_pt_prefill_256(6)", "start": 70 * MS,
         "dur": 19 * MS},
        {"name": "jit_pt_decode_step(1)", "start": 100 * MS,
         "dur": 12 * MS}]}


@pytest.fixture
def use(monkeypatch):
    def install(tr):
        monkeypatch.setattr(
            P, "load", lambda run, root=None: tr if run.get("trace")
            else None)
    return install


def a_run(kind, cell=MISTRAL):
    c = manifest.Cell(manifest.load_manifest(), cell)
    return {"kind": kind, "trace": {"some": "trace"}, "config": c.config,
            "traffic": c.traffic}


def read(metric, run):
    return manifest.load_reader(metric)(run)


# ---------------------------------------------------------------------------
# the path of an operation
# ---------------------------------------------------------------------------

LISTED = ("attn", "mlp", "head", "mhc_mix", "linear_ce")


@pytest.mark.parametrize("op, scope, which", [
    (TRAIN + "jvp(mlp)/dot_general", "mlp", "forward"),
    (BWD + "mlp/mul", "mlp", "backward"),
    (BWD + "rematted_computation/mlp/dot_general", "mlp", "recompute"),
    (TRAIN + "transpose(jvp(linear_ce))/while/body/dot_general",
     "linear_ce", "backward"),
    ("jit(pt_decode_step)/while/body/attn/pt_flash_decode", "attn",
     "forward"),
    (TRAIN + "jvp(vmap(attn))/mul", "attn", "forward"),
    # a transform wraps every scope that was open: both are components
    (TRAIN + "jvp(attn/mhc_mix)/mul", "attn", "forward"),
    ("jit(pt_decode_step)/mhc_mix/attn/mul", "mhc_mix", "forward"),
    # whole components only, and a function's name is no scope
    (TRAIN + "jvp(latent_attention)/dot_general", None, "forward"),
    (TRAIN + "jvp(heads)/mul", None, "forward"),
    (TRAIN + "jvp(jit(mlp))/dot_general", None, "forward"),
    ("jit(mlp_helper)/dot_general", None, "forward"),
    ("jit(attn)/transpose(jvp())/mul", None, "backward"),
    ("", None, "forward"),
])
def test_an_operation_is_placed_by_whole_components_of_its_path(
        op, scope, which):
    assert scope_table.place(op, LISTED) == (scope, which)


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def test_the_train_table_splits_by_scope_and_pass_and_closes():
    tab = scope_table.table(train_trace(), "pt_train_step")
    ms = lambda ns: ns / 2 / MS
    got = {s: {p: ms(ns) for p, ns in row.items()}
           for s, row in tab["scopes"].items()}
    assert got == {
        "embed": {"forward": 1, "backward": 2},
        "attn": {"forward": 10, "recompute": 6, "backward": 20},
        "mlp": {"forward": 30, "recompute": 25, "backward": 60},
        # the loop and its body once: 40 ms, not 70
        "linear_ce": {"forward": 40},
        "optimizer": {"forward": 22}}
    assert {f: ms(row[0]) for f, row in tab["unscoped"].items()} == {
        "%fusion fusion": 6, "%copy copy": 5}
    # an operation without an ``op_name`` is listed by its instruction
    assert tab["unscoped"]["%copy copy"][1] == "%copy.16 copy"
    assert tab["unscoped"]["%fusion fusion"][1].startswith(TRAIN + "jvp(")
    scoped = sum(ns for row in tab["scopes"].values()
                 for ns in row.values())
    rest = sum(row[0] for row in tab["unscoped"].values())
    assert scoped + rest == tab["self_ns"] == 2 * 227 * MS
    assert tab["module_ns"] == 2 * 230 * MS and len(tab["runs"]) == 2


def test_a_table_holds_one_programs_runs_and_is_made_once(capsys):
    tr = serve_trace()
    assert scope_table.scope_ms(tr, "pt_decode_step", "mlp") == 6.0
    assert scope_table.unscoped_ms(tr, "pt_decode_step") == 1.5
    tab = scope_table.table(tr, "pt_decode_step")
    assert tab["scopes"]["mlp"] == {"forward": 2 * 6 * MS}     # no prefill
    assert tab["scopes"]["attn"] == {"forward": 2 * 3 * MS}    # outer wins
    assert "mhc_mix" not in tab["scopes"]
    assert scope_table.table(tr, "pt_decode_step") is tab
    printed = capsys.readouterr().err
    assert printed.count("[scope_table] pt_decode_step: 2 runs") == 1
    assert "unscoped %fusion fusion: 1.000" in printed
    assert scope_table.table(tr, "pt_prefill_")["scopes"] == {
        "mlp": {"forward": 20 * MS}}
    assert scope_table.table(tr, "pt_spec_step") is None
    assert scope_table.table(None, "pt_decode_step") is None


def test_a_program_without_the_list_has_no_table(monkeypatch, use):
    import paddle_tpu.telemetry

    monkeypatch.delattr(paddle_tpu.telemetry, "scopes")
    monkeypatch.setitem(sys.modules, "paddle_tpu.telemetry.scopes", None)
    assert scope_table.listed_scopes() is None
    assert scope_table.table(serve_trace(), "pt_decode_step") is None
    use(serve_trace())
    for metric in NEW:
        if metric != "prefill_run_ms":
            kind = "train" if NEW[metric][0] == [TRAIN_CELL] else "serve"
            assert read(metric, a_run(kind)) is None, metric


# ---------------------------------------------------------------------------
# the ten metrics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_metric_reads_its_part_of_the_table(metric, use):
    cells, want = NEW[metric]
    train = cells == [TRAIN_CELL]
    use(train_trace() if train else serve_trace())
    kind, other = ("train", "serve") if train else ("serve", "train")
    assert read(metric, a_run(kind)) == pytest.approx(want)
    assert read(metric, a_run(other)) is None
    assert read(metric, dict(a_run(kind), trace=None)) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_metric_is_registered_for_cells_that_exist(metric):
    man = manifest.load_manifest()
    entry = next(m for m in man["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == NEW[metric][0]
    assert set(entry["workloads"]) <= {w["name"] for w in man["workloads"]}
    assert entry["source"] == "device_trace" and entry["unit"] == "ms"
    reports = {e["name"]: e.get("workloads") for e in man["end_to_end"]}
    assert set(entry["workloads"]) <= set(reports[entry["moves"]])


def test_a_scope_the_program_lacks_reads_none_and_a_full_one_zero(use):
    tr = serve_trace()
    tr["ops"] = [e for e in tr["ops"] if "/attn/" not in
                 e["stats"].get("tf_op", "") and e["stats"]]
    for e in tr["ops"]:
        if "rms_norm" in e["stats"]["tf_op"]:
            e["stats"]["tf_op"] = "jit(pt_decode_step)/while/body/mlp/mul"
    use(tr)
    assert read("step_attn_ms", a_run("serve")) is None
    assert read("step_unscoped_ms", a_run("serve")) == 0.0


def test_prefill_run_ms_is_the_median_run_of_the_median_prompts_bucket(
        use, capsys):
    use(serve_trace())
    run = a_run("serve")
    assert run["traffic"]["prompt_tokens"]["median"] == 300
    assert run["config"]["serve"]["prompt_bucket"] == 256
    assert read("prefill_run_ms", run) == pytest.approx(31.0)
    assert "256 18.000 (2), 512 31.000 (1)" in capsys.readouterr().err
    tr = serve_trace()
    tr["modules"] = [m for m in tr["modules"] if "512" not in m["name"]]
    use(tr)
    assert read("prefill_run_ms", run) is None
