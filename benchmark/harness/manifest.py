"""How a cell's files are found: by the names in ``BENCHMARK.json``.

``workloads[i]`` names a ``config`` and a ``traffic``; the configuration
is the ``file`` its ``configs`` entry gives, the traffic mix is
``benchmark/traffic/<traffic>.json``, the limits of ``correct`` are
``benchmark/limits/<cell>.json``, a per-layer metric ``m`` is read by
``benchmark/layer_metrics/<m>.py``, and the configuration's ``family``
key names ``benchmark/families/<family>.py``: the leaves, the program's
constructor, the plain reference and the shape formulas of that kind of
model (:func:`load_family`). All under the cell's root. Nothing else
decides a cell and no file of ``harness/`` names a family, so a later PR
adds files and manifest entries and edits nothing under ``harness/``.
"""

from __future__ import annotations

import functools
import importlib
import importlib.util
import json
import os
import sys
import zlib
from typing import Callable, Dict, List

from .runtime import ROOT

BENCH_DIR = os.path.join(ROOT, "benchmark")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its files opened."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        rows = [w for w in manifest["workloads"] if w["name"] == name]
        if len(rows) != 1:
            known = [w["name"] for w in manifest["workloads"]]
            raise SystemExit(f"benchmark: no workload {name!r}; "
                             f"known: {known}")
        self.row = rows[0]
        self.name = name
        self.root = root
        self.chips = int(self.row["chips"])
        cfg_row = next(c for c in manifest["configs"]
                       if c["name"] == self.row["config"])
        with open(os.path.join(root, cfg_row["file"])) as f:
            self.config = json.load(f)
        with open(self.path("traffic", self.row["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.kind = self.traffic["kind"]
        self.end_to_end = [m["name"] for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [
            m["name"] for m in manifest["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in self.end_to_end)]
        self.units = {m["name"]: m["unit"] for m in
                      manifest["end_to_end"] + manifest["per_layer"]}

    @functools.cached_property
    def family(self):
        return load_family(self.config["family"], self.root)

    def path(self, *parts: str) -> str:
        """A file of this cell's ``benchmark/`` directory."""
        return os.path.join(self.root, "benchmark", *parts)


def load_file(path: str):
    """The module in the file at ``path``, executed once a process. A
    file of this checkout with an importable name is the module
    ``import`` gives; any other (a metric's name may hold a dot, a test
    builds a root of its own) goes under a name made from its path."""
    path = os.path.realpath(path)
    stem = os.path.relpath(path, ROOT)[:-len(".py")]
    if not stem.startswith(os.pardir) and "." not in stem:
        return importlib.import_module(stem.replace(os.sep, "."))
    name = "benchmark_file_%08x" % zlib.crc32(path.encode())
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up there
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_family(name: str, root: str = ROOT):
    """The module ``<root>/benchmark/families/<name>.py``. A family is
    what the harness has to know of one kind of model and knows of none
    by name. The file gives:

    - ``Dims.from_config(config)``: the sizes as a frozen, hashable
      object with at least ``.vocab`` (token ids are drawn below it) and
      ``.layers``;
    - ``top_shapes(dims)`` and ``layer_shapes(dims, i)``: leaf name ->
      shape, together exactly the program's ``named_parameters()``; a
      layer's shapes may depend on its index;
    - ``leaf_rule(name, shape)``: ``"ones"``, ``"zeros"`` or
      ``"uniform"`` (seeded, ``harness/weights.py``), the value both
      sides start that leaf from;
    - ``build_model(config, dims, dtype, max_position, remat)``: the
      program's model, a ``paddle_tpu.nn.Layer`` with ``forward_loss``
      (training) or what ``BatchedDecoder`` serves. The harness calls it
      under ``jax.eval_shape`` and fills the leaves itself; the trainer
      and the serving stack around it are the harness's, not a family's;
    - ``reference``: the module of its plain reference,
      ``load_reference(__file__, "<name>")``. It imports nothing of the
      program and
      gives ``loss(w, batch, dims, mode, remat)`` for a training cell
      and ``layerwise_logits(tokens, positions, dims, mode, get,
      shapes_of_layer, top_shapes)`` for a served one, ``mode`` one of
      ``"f32"`` (the reference) and ``"fp8"`` (the control). **The loss
      is a mean over rows that do not see each other**:
      ``check.train_reference`` calls it one row of the batch at a time
      and averages losses and gradients, so that the reference fits
      beside nothing else. ``layerwise_logits`` holds one layer's leaves
      at a time for the same reason;
    - the formulas its cells' readers ask for, counted from shapes
      alone: ``train_flops_per_token(dims, seq)``, ``attention_flops`` /
      ``attention_bytes(dims, seq[, itemsize], backward)``,
      ``decode_attention_flops`` / ``decode_attention_bytes(dims,
      context_tokens[, itemsize])``. A reader reaches them through
      ``run["family"]``, so a cell of another family joins a metric by
      its name in that metric's ``workloads`` list.
    """
    return load_file(os.path.join(root, "benchmark", "families",
                                  name + ".py"))


def load_reference(family_file: str, name: str):
    """``reference/<name>.py`` of the benchmark directory that holds the
    family file ``family_file``."""
    return load_file(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(family_file))),
        "reference", name + ".py"))


def load_reader(metric: str, root: str = ROOT) -> Callable[[dict], object]:
    """``read(run)`` of ``<root>/benchmark/layer_metrics/<metric>.py``."""
    return load_file(os.path.join(root, "benchmark", "layer_metrics",
                                  metric + ".py")).read


def read_layer_metrics(names: List[str], run: dict,
                       root: str = ROOT) -> Dict[str, float]:
    """Each reader's number; a reader that finds nothing to read returns
    None and its metric is left out of the line."""
    out = {}
    for n in names:
        v = load_reader(n, root)(run)
        if v is not None:
            out[n] = float(v)
    return out
