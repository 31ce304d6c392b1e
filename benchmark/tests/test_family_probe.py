"""Files are enough: a family that is not the dense decoder brings its
cell by adding files and manifest entries alone, and the unedited
harness trains it, compares it and reads its metrics. And the seeded
generator's bits: rank 2 as ever, higher ranks by their rank-2 reshape.

The probe's files live in ``tests/probe/``; the test lays them over a
copy of the tree's benchmark in a root of its own."""

import filecmp
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest, runtime, weights as W
from benchmark.tests import tiny

PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "probe")
CELL = "probe-mix.probe_steps"
SKIP = shutil.ignore_patterns("tests", "__pycache__", ".*")


def files_under(top):
    return {os.path.relpath(os.path.join(d, f), top)
            for d, _, fs in os.walk(top) for f in fs
            if "__pycache__" not in d}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tree's benchmark (its tests left out) plus the probe's files
    and its entries in ``BENCHMARK.json``; nothing else differs."""
    root = str(tmp_path_factory.mktemp("probe_root"))
    shutil.copytree(os.path.join(runtime.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"), ignore=SKIP)
    before = files_under(os.path.join(root, "benchmark"))
    shutil.copytree(os.path.join(PROBE, "benchmark"),
                    os.path.join(root, "benchmark"), dirs_exist_ok=True)
    man = manifest.load_manifest()
    with open(os.path.join(PROBE, "entries.json")) as f:
        add = json.load(f)
    man["configs"] += add["configs"]
    man["workloads"] += add["workloads"]
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in add["metric_workloads"]:
            m["workloads"] = m["workloads"] + [w["name"]
                                               for w in add["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root, before


@pytest.fixture(scope="module")
def job(root):
    cell = manifest.Cell(manifest.load_manifest(root[0]), CELL, root[0])
    return cell, tiny.run_job(cell, seconds=1.0, control=True)


def test_the_root_differs_by_added_files_and_entries_alone(root):
    root, before = root
    added = files_under(os.path.join(PROBE, "benchmark"))
    now = files_under(os.path.join(root, "benchmark"))
    assert not added & before and now == before | added
    same, diff, errors = filecmp.cmpfiles(
        os.path.join(runtime.ROOT, "benchmark"),
        os.path.join(root, "benchmark"), sorted(before), shallow=False)
    assert not diff and not errors and len(same) == len(before)
    # the manifest less the probe's rows, and less its cell's name in
    # the metrics' ``workloads`` lists, is the tree's
    tree, mine = manifest.load_manifest(), manifest.load_manifest(root)
    assert mine != tree
    for key in ("configs", "workloads"):
        mine[key] = [r for r in mine[key]
                     if r["name"] not in ("probe-mix", CELL)]
    for m in mine["end_to_end"] + mine["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    assert mine == tree


def test_the_probe_is_not_the_decoder(job):
    cell, _ = job
    fam = cell.family
    assert fam.__file__.startswith(cell.root)
    assert fam.reference.__file__.startswith(cell.root)
    dims = fam.Dims.from_config(cell.config)
    shapes = W.leaf_shapes(fam, dims)
    assert any(len(s) == 3 for s in shapes.values())
    assert [fam.leaf_rule(k, s) for k, s in shapes.items()
            if len(s) == 1].count("zeros") == 2
    assert set(k.split(".", 2)[2] for k in fam.layer_shapes(dims, 0)) != \
        set(k.split(".", 2)[2] for k in fam.layer_shapes(dims, 1))
    w = W.make_all(3, fam, dims, jnp.float32)
    assert not np.any(np.asarray(w["blocks.1.router_bias"]))
    assert np.all(np.asarray(w["blocks.1.norm.weight"]) == 1.0)
    assert abs(float(jnp.std(w["blocks.2.up"])) - W.INIT_STD) < 0.002


def test_the_unedited_train_job_ends_correct(job):
    cell, out = job
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["numbers"]) == {"loss_gap", "grad_norm_gap",
                                   "param_change_gap"}
    assert all(np.isfinite(v) for v in out["numbers"].values())
    # the control: the reference in float8 in the program's place
    assert out["control_numbers"]["grad_norm_gap"] > \
        3 * out["numbers"]["grad_norm_gap"]


def test_its_metrics_are_read_through_its_family(job):
    cell, out = job
    line = tiny.load_run_py().result_line(cell, out, tiny.CPU_DEVICE, True)
    assert set(line["metrics"]) == {"trainer_step_ms", "trainer_dispatch_ms",
                                    "model_mfu_pct"}
    fam, run = cell.family, out["run"]
    assert run["family"] is fam
    want = 100.0 * fam.train_flops_per_token(run["dims"], 32) * \
        run["tokens_per_s"] / 1e12
    assert line["metrics"]["model_mfu_pct"]["value"] == pytest.approx(want)
    line = tiny.load_run_py().result_line(cell, out, tiny.CPU_DEVICE, False)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


# the parent's bits (commit 41a96da, ``leaf`` indexed by iota0 * shape[1] +
# iota1), as uint32 views of float32
PARENT_BITS = [
    (7, "embed.weight", (2, 3),
     [0xba8fe398, 0xbcdee3f0, 0x3c209751, 0x3ca7d602, 0x3cd03fa0,
      0xbb47c5fd]),
    (2**31 + 12345, "blocks.1.ffn.up.weight", (4, 2),
     [0x3a2d1223, 0x3d0bdabd, 0xbb1d0251, 0x3c2fd023, 0x3c58ed4f,
      0x3c95b232, 0xbcd2c1bb, 0x3bc2da56]),
    (1, "lm_head", (3, 5),
     [0x3ace4942, 0xbc36d357, 0x3c4add7c, 0x3bbf95d3, 0x3bf23826,
      0x3c9c12c0, 0x3ca901b6, 0x3cb3d2db, 0x3bea2908, 0xbc231abd,
      0xbc32e104, 0x3b84882c, 0xbcc28e44, 0xbd056330, 0x3ce08ebe]),
]


@pytest.mark.parametrize("seed,name,shape,bits", PARENT_BITS)
def test_a_rank_2_leaf_keeps_its_bits(seed, name, shape, bits):
    got = W.leaf(W.seed_arg(seed), name, shape, jnp.float32, "uniform")
    assert np.asarray(got).view(np.uint32).ravel().tolist() == bits


@pytest.mark.parametrize("shape", [(4, 3, 5), (2, 3, 4, 5), (6,)])
def test_a_leaf_of_any_rank_is_its_rank_2_reshape(shape):
    seed, name = W.seed_arg(2**31 + 7), "blocks.3.experts.up"
    got = W.leaf(seed, name, shape, jnp.float32, "uniform")
    flat = W.leaf(seed, name, (int(np.prod(shape[:-1])), shape[-1]),
                  jnp.float32, "uniform")
    np.testing.assert_array_equal(np.asarray(got).reshape(flat.shape),
                                  np.asarray(flat))
    assert not np.any(np.asarray(
        W.leaf(seed, name, shape, jnp.float32, "zeros")))
    with pytest.raises(ValueError):
        W.leaf(seed, name, shape, jnp.float32, "normal")
