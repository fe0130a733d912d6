"""BENCHMARK.json keeps to the contract's limits and every file it names
is found by name."""

import json
import os
import re

import pytest

from benchmarks import readers
from benchmarks.manifest import ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|n_embd|n_inner|widths|expansion|"
                   r"experts_per_tok)")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def test_top_level_keys(man):
    assert set(man.data) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= man.data["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert man.data["paths"] == ["benchmarks"]
    assert man.data["command"] == ["python3", "benchmarks/run.py"]


def test_names_units_and_lines(man):
    d = man.data
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in d[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), n
    for w in d["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in d["workloads"]} \
        == {c["name"] for c in d["configs"]}
    four = sum(w["chips"] == 4 for w in d["workloads"])
    assert four <= max(1, len(d["workloads"]) // 4)


def test_metrics(man):
    d = man.data
    cells = {w["name"] for w in d["workloads"]}
    e2e = {m["name"]: m for m in d["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in d["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        # every cell that reads the metric reports what it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", moved)) <= set(moved), m["name"]
        if "workloads" not in m:
            assert set(moved) == cells or "workloads" in e2e[m["moves"]]
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    for cell in cells:
        mine = [m["name"] for m in man.metrics("end_to_end", cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert man.metrics("per_layer", cell)
    assert any("mfu" in m["name"] for m in d["per_layer"])


def test_every_file_is_found_by_name(man):
    for w in man.data["workloads"]:
        config = man.config(w["config"])
        assert config["name"] == w["config"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "families", config["family"] + ".py"))
        reference = man.reference(config)
        assert "f32" in reference.VARIANTS and len(reference.VARIANTS) > 1
        assert man.traffic(w["traffic"])["name"] == w["traffic"]
        cell = man.cell_file(w["name"])
        assert cell["why"] == w["why"] and cell["closed_loop"] is True
        assert {"loss_r1", "first_update_gap", "change_gap"} \
            <= set(cell["limits"])
    for group in ("end_to_end", "per_layer"):
        for cell in (w["name"] for w in man.data["workloads"]):
            for m in man.metrics(group, cell):
                spec = m["reader"]
                assert spec["kind"] in readers.KINDS, m["name"]
                # the file says what the manifest says; the list of cells
                # is the manifest's alone, so that a new cell edits no file
                for key in ("unit", "better", "source", "layer", "moves"):
                    assert spec.get(key) == m.get(key), (m["name"], key)
                assert "workloads" not in spec, m["name"]


def test_configs_state_their_cut(man):
    for c in man.data["configs"]:
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for key in ("assumed", "departures", "memory_reckoning", "as_run"):
            assert key in cfg, (c["name"], key)
        for key in c["reduced"]:
            assert key in cfg.get("published", {}), key
