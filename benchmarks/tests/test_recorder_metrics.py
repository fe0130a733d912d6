"""The three per-layer metrics of ISSUE 36: their files load, name a kind
``readers.py`` has, are reported by every cell, and the two device
metrics' patterns tell the fold's and the server's programs from the
client update's."""

import re

import pytest

from benchmarks import readers
from benchmarks.manifest import ROOT, Manifest

NEW = {"prepare.host_ms": ("host_time", "program_span", "round loop"),
       "fold.device_ms": ("trace_time", "device_trace", "aggregation fold"),
       "server.device_ms": ("trace_time", "device_trace",
                            "aggregation fold")}
#: device programs of a bucketed round, as the profiler names them
MODULES = ("jit_chunk_fn(8562704209710296947)",
           "jit_fold_first(1317713336154422825)",
           "jit_fold_next(17960482758947311160)",
           "jit_fold_quotient(2405153947246156992)",
           "jit_advance_fn(9407288363656763691)",
           "jit__threefry_split(5092362887110886962)")


@pytest.fixture(scope="module")
def metrics():
    man = Manifest(ROOT)
    cells = [w["name"] for w in man.data["workloads"]]
    return {cell: {m["name"]: m for m in man.metrics("per_layer", cell)}
            for cell in cells}


@pytest.mark.parametrize("name", sorted(NEW))
def test_file_loads_and_names_a_reader_kind(metrics, name):
    kind, source, layer = NEW[name]
    for cell, by_name in metrics.items():  # no list: every cell reports it
        entry = by_name[name]
        assert "workloads" not in entry, cell
        spec = entry["reader"]
        assert spec["kind"] == kind and kind in readers.KINDS
        assert (entry["source"], entry["layer"]) == (source, layer)
        assert entry["moves"] == "rounds_per_hour"
        assert (spec["unit"], spec["scale"]) == ("ms", 1000.0)
        re.compile(spec["pattern"])


def test_the_new_entries_are_the_lists_last(metrics):
    man = Manifest(ROOT)
    assert [m["name"] for m in man.data["per_layer"][-3:]] == [
        "prepare.host_ms", "fold.device_ms", "server.device_ms"]


def _matched(metrics, name):
    spec = next(iter(metrics.values()))[name]["reader"]
    assert spec.get("line") == "modules"
    return {m.split("(")[0] for m in MODULES
            if re.search(spec["pattern"], m)}


def test_device_patterns_leave_the_client_update_out(metrics):
    assert _matched(metrics, "fold.device_ms") == {
        "jit_fold_first", "jit_fold_next", "jit_fold_quotient"}
    assert _matched(metrics, "server.device_ms") == {"jit_advance_fn"}
    update = next(iter(metrics.values()))["update.busy_ms"]["reader"]
    assert {m.split("(")[0] for m in MODULES
            if re.search(update["pattern"], m)} == {"jit_chunk_fn"}


def test_prepare_matches_the_span_alone(metrics):
    pattern = next(iter(metrics.values()))["prepare.host_ms"][
        "reader"]["pattern"]
    assert re.search(pattern, "prepare")
    for other in ("pack", "h2d", "$engine.py:560 prepare", "prepared"):
        assert not re.search(pattern, other)


def test_a_program_without_the_span_reports_nothing():
    """The parent's traced run: no ``prepare`` event on the host plane."""
    from benchmarks.trace_reader import TraceSummary

    summary = TraceSummary(
        [{"ops": [], "modules": [(MODULES[0], 0.1, 0.4),
                                 (MODULES[4], 0.5, 0.6)]}],
        [("bench_round", 0.0, 1.0)], (0.0, 1.0),
        host=[("bench_round", 0.0, 1.0), ("pack", 0.0, 0.05)])
    man = Manifest(ROOT)
    by_name = {m["name"]: m for m in man.metrics(
        "per_layer", man.data["workloads"][0]["name"])}
    ctx = {"trace": summary, "traced_rounds": 1}
    assert readers.read(by_name["prepare.host_ms"], ctx) is None
    assert readers.read(by_name["fold.device_ms"], ctx) is None
    assert readers.read(by_name["server.device_ms"], ctx) \
        == pytest.approx(100.0)
