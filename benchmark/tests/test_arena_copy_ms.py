"""``arena_copy_ms`` on hand-made events: the copies that start inside a
decode step count, by self time, over the steps; a step without one reads
0.0 and only a trace without a step (or no trace) reads None."""

import pytest

from benchmark.harness import manifest, program_spans as P

MS = 1_000_000
RUN = {"kind": "serve", "trace": {"ops": []}}


def op(name, start, dur):
    return {"name": name, "start": start, "dur": dur, "stats": {}}


def module(name, start, dur):
    return {"name": name, "start": start, "dur": dur}


def read(trace, run=RUN, monkeypatch=None):
    monkeypatch.setattr(P, "load", lambda run, root=None: trace
                        if run.get("trace") else None)
    return manifest.load_reader("arena_copy_ms")(run)


STEPS = [module("jit_pt_decode_step(123)", 0, 20 * MS),
         module("jit_pt_prefill_256(7)", 30 * MS, 40 * MS),
         module("jit_pt_decode_step(123)", 80 * MS, 20 * MS)]


def test_copies_inside_decode_steps_count_by_self_time(monkeypatch):
    ops = [op("%copy.3", 1 * MS, 4 * MS),
           op("%copy-start.1", 6 * MS, 1 * MS),
           op("%copy-done.1", 8 * MS, 2 * MS),
           op("%fusion.9", 11 * MS, 5 * MS),            # not a copy
           op("%copy.7", 35 * MS, 9 * MS),              # a prefill's
           # a loop of the second step: 6 ms, of which a copy takes 3
           op("%while.2", 81 * MS, 6 * MS),
           op("%copy_bitcast_fusion.4", 82 * MS, 3 * MS),
           op("%copy.8", 120 * MS, 5 * MS)]             # after every run
    got = read({"host": [], "ops": ops, "modules": STEPS},
               monkeypatch=monkeypatch)
    assert got == pytest.approx((4 + 1 + 2 + 3) / 2)


def test_a_step_without_a_copy_reads_zero_not_none(monkeypatch):
    ops = [op("%fusion.9", 1 * MS, 5 * MS), op("%copy.7", 35 * MS, 9 * MS)]
    got = read({"host": [], "ops": ops, "modules": STEPS},
               monkeypatch=monkeypatch)
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("trace, run", [
    (None, RUN),                                          # no trace file
    ({"host": [], "ops": [], "modules": STEPS}, RUN),     # no device line
    ({"host": [], "ops": [op("%copy.1", 0, MS)],          # no decode step
      "modules": [module("jit__step(1)", 0, MS)]}, RUN),
    ({"host": [], "ops": [op("%copy.1", 0, MS)], "modules": STEPS},
     {"kind": "serve", "trace": None}),                   # not traced
    ({"host": [], "ops": [op("%copy.1", 0, MS)], "modules": STEPS},
     {"kind": "train", "trace": {"ops": []}}),            # a train run
])
def test_nothing_to_read_is_none(monkeypatch, trace, run):
    assert read(trace, run, monkeypatch) is None


def test_it_is_registered_for_both_serving_cells():
    m = manifest.load_manifest()
    row = m["per_layer"][-1]
    assert row["name"] == "arena_copy_ms"
    assert row["layer"] == "serving arena"
    assert row["source"] == "device_trace"
    assert row["moves"] == "serve_tokens_per_s"
    serving = [w["name"] for w in m["workloads"] if w["traffic"].startswith(
        "chat_")]
    assert row["workloads"] == serving
