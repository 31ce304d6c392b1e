"""BENCHMARK.json against the contract's rules that a test can hold."""

import json
import os
import re

import pytest

from benchmark.harness import manifest, runtime

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def man():
    return manifest.load_manifest()


def test_keys_and_sizes(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(
        os.path.join(runtime.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= man["run_seconds"] <= 51
    assert isinstance(man["run_seconds"], int)
    assert all(PATH.match(p) for p in man["paths"])
    assert len(man["command"]) <= 32


def test_names_units_and_lines(man):
    metrics = man["end_to_end"] + man["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for row in man["configs"] + man["workloads"]:
        assert NAME.match(row["name"])
        assert 1 <= len(row["why"]) <= 200
        assert "\n" not in row["why"] and "\t" not in row["why"]
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)


def test_every_moves_target_is_reported_where_the_metric_is(man):
    for w in man["workloads"]:
        cell = manifest.Cell(man, w["name"])
        assert "setup_s" in cell.end_to_end
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in man["per_layer"]:
            if m["name"] in cell.per_layer:
                assert m["moves"] in cell.end_to_end, (w["name"], m)


def test_files_are_found_by_name(man):
    used = set()
    for w in man["workloads"]:
        cell = manifest.Cell(man, w["name"])
        used.add(w["config"])
        assert cell.kind in ("train", "serve")
        for metric in cell.per_layer:
            assert callable(manifest.load_reader(metric))
        with open(os.path.join(manifest.BENCH_DIR, "limits",
                               w["name"] + ".json")) as f:
            assert json.load(f)["limits"]
    assert used == {c["name"] for c in man["configs"]}
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    for c in man["configs"]:
        with open(os.path.join(runtime.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert {r["key"] for r in cfg["reduced"]} == set(c["reduced"])
        # no width is ever reduced
        for k in c["reduced"]:
            assert not re.search(r"(_dim$|_rank$|_size$|head)", k)


def test_no_width_differs_from_the_published(man):
    published = {
        "internlm2-1.8b": dict(hidden_size=2048, num_attention_heads=16,
                               num_key_value_heads=8, head_dim=128,
                               intermediate_size=8192, vocab_size=92544,
                               rope_theta=1e6, tie_word_embeddings=False),
        "mistral-7b-v0.1": dict(hidden_size=4096, num_attention_heads=32,
                                num_key_value_heads=8, head_dim=128,
                                intermediate_size=14336, vocab_size=32000,
                                rope_theta=1e4, tie_word_embeddings=False),
    }
    for c in man["configs"]:
        with open(os.path.join(runtime.ROOT, c["file"])) as f:
            cfg = json.load(f)
        for k, v in published[c["name"]].items():
            assert cfg[k] == v, (c["name"], k)
