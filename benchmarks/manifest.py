"""``BENCHMARK.json`` and the data files it names, found by name.

Layout under ``benchmarks/`` (a later PR adds files and manifest entries,
and edits none that is there):

- ``configs/<config>.json``: one model configuration, naming its
  ``family`` (a module under ``families/``) and its ``reference`` (a
  module beside it);
- ``traffic/<traffic>.json``: one federated job mix (clients, shard
  sizes, local epochs, batch), read by the family's generator;
- ``workloads/<cell>.json``: what belongs to one cell: how many rounds
  the check follows, how many are traced, and the limits of ``correct``;
- ``end_to_end/<metric>.json`` and ``layer_metrics/<metric>.json``: one
  reader each (``readers.py`` has the kinds).
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, root=ROOT):
        self.root = root
        self.data = _load(os.path.join(root, "BENCHMARK.json"))
        self.home = os.path.join(root, self.data["paths"][0])

    def cell(self, name):
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name):
        for c in self.data["configs"]:
            if c["name"] == name:
                cfg = _load(os.path.join(self.root, c["file"]))
                cfg["_file"] = os.path.join(self.root, c["file"])
                return cfg
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name):
        return _load(os.path.join(self.home, "traffic", name + ".json"))

    def cell_file(self, name):
        return _load(os.path.join(self.home, "workloads", name + ".json"))

    def metrics(self, group, cell):
        """The manifest entries of ``group`` (``end_to_end`` or
        ``per_layer``) that this cell reports, each with its reader's file
        under the key ``reader``."""
        folder = {"end_to_end": "end_to_end",
                  "per_layer": "layer_metrics"}[group]
        out = []
        for m in self.data[group]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            reader = _load(os.path.join(self.home, folder,
                                        m["name"] + ".json"))
            out.append({**m, "reader": reader})
        return out

    def reference(self, config):
        """The configuration's plain reference, loaded from the file
        beside the configuration (it imports nothing of the program)."""
        path = os.path.join(os.path.dirname(config["_file"]),
                            config["reference"])
        name = "benchmarks_reference_" + os.path.splitext(
            os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
