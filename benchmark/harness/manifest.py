"""How a cell's files are found: by the names in ``BENCHMARK.json``.

``workloads[i]`` names a ``config`` and a ``traffic``; the configuration
is the ``file`` its ``configs`` entry gives, the traffic mix is
``<dir of run.py>/traffic/<traffic>.json``, and a per-layer metric
``m`` is read by ``layer_metrics/<m>.py``. Nothing else decides a cell,
so a later PR adds files and manifest entries and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
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
        self.chips = int(self.row["chips"])
        cfg_row = next(c for c in manifest["configs"]
                       if c["name"] == self.row["config"])
        with open(os.path.join(root, cfg_row["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(root, "benchmark", "traffic",
                               self.row["traffic"] + ".json")) as f:
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


def load_reader(metric: str) -> Callable[[dict], object]:
    """``read(run)`` of ``layer_metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(names: List[str], run: dict) -> Dict[str, float]:
    """Each reader's number; a reader that finds nothing to read returns
    None and its metric is left out of the line."""
    out = {}
    for n in names:
        v = load_reader(n)(run)
        if v is not None:
            out[n] = float(v)
    return out
