"""The reduction from a trace to device numbers, on a small recorded
xplane (TPU v5e, jax 0.9.0: three steps of flash attention forward and
backward plus one matmul, each under a ``bench_round`` annotation)."""

import json
import os

import pytest

from benchmarks import readers, trace_reader
from benchmarks.manifest import ROOT

XPLANE = os.path.join(ROOT, "benchmarks", "testdata",
                      "flash_matmul_3steps.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reader.read(XPLANE)


def _pattern(metric):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def test_window_busy_idle(summary):
    assert len(summary.devices) == 1 and len(summary.annotations) == 3
    assert summary.window_s == pytest.approx(0.036509426, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.031767457, rel=1e-6)
    idle = readers.trace_idle({}, {"trace": summary})
    assert idle == pytest.approx(12.9883, abs=1e-3)
    gaps = summary.idle_gaps(lambda a, b: "gap")
    assert gaps[0][1] == pytest.approx(summary.window_s - summary.busy_s,
                                       rel=1e-6)


def test_kernels_are_found_by_the_metric_files_patterns(summary):
    fwd, bwd = _pattern("flash_fwd_roofline"), _pattern("flash_bwd_roofline")
    assert summary.time_of(fwd["pattern"]) == (
        pytest.approx(0.009973502, rel=1e-6), 3.0)
    assert summary.time_of(bwd["pattern"]) == (
        pytest.approx(0.01587217, rel=1e-6), 6.0)
    seconds, count = summary.time_of(r"^jit_", "modules")
    assert count == 3.0 and seconds == pytest.approx(0.031769187, rel=1e-6)
    top = summary.top_ops(3)
    assert top[0][0] == "jvp_vmap_vmap____.1 custom-call"
    assert [name for name, _ in top if name.endswith(" while")] == []


def test_roofline_reader(summary):
    spec = _pattern("flash_fwd_roofline")
    flops = 2.0 * 2 * 16 * 2048 * 2048 * 128
    ctx = {"trace": summary, "traced_rounds": 3,
           "shapes": {"kernels": {"flash_fwd": {"flops": flops,
                                                "bytes": 1.0}}},
           "peaks": {"flops": 197.0e12, "hbm_bytes_per_s": 819.0e9}}
    share = readers.roofline(spec, ctx)
    assert share == pytest.approx(
        100.0 * 3 * flops / 197.0e12 / 0.009973502, rel=1e-6)
    assert 0 < share < 100
    ctx["shapes"] = {}
    assert readers.roofline(spec, ctx) is None  # nothing to read: no 0
    assert readers.trace_time({"pattern": "nothing-matches"}, ctx) is None


def test_host_events_and_share_of_peak(summary):
    """The program's Python calls are read from the host plane by the
    profiler's names; the step's share of the peak is taken over the
    device time of its programs."""
    wait = r"^\$api\.py:\d+ block_until_ready$"
    seconds, count = summary.host_time_of(wait)
    assert count == 3 and seconds == pytest.approx(0.0349, abs=1e-4)
    # a call inside a matching call is not counted twice
    both = summary.host_time_of(r"^(bench_round|\$api\.py:\d+ block_)")
    assert both[1] == 6 and both[0] == pytest.approx(
        summary.host_time_of("^bench_round$")[0], rel=1e-9)
    ctx = {"trace": summary, "traced_rounds": 3, "chips": 1,
           "counters": {"rounds": 6}, "work": {"useful_flops": 6 * 1.0e12},
           "peaks": {"flops": 197.0e12}}
    assert readers.host_time({"pattern": wait, "scale": 1e3}, ctx) \
        == pytest.approx(1e3 * seconds / 3)
    assert readers.host_time({"pattern": "nothing-matches"}, ctx) is None
    spec = {"pattern": r"^jit_", "line": "modules"}
    assert readers.share_of_peak(spec, ctx) == pytest.approx(
        100.0 * 3 * 1.0e12 / (0.031769187 * 197.0e12), rel=1e-6)
    assert readers.share_of_peak({"pattern": "nothing-matches"}, ctx) is None
    assert readers.share_of_peak(spec, {**ctx, "trace": None}) is None


def test_no_annotation_is_an_error(tmp_path):
    with pytest.raises(ValueError):
        trace_reader.read(XPLANE, window_annotation="no_such_annotation")
    assert trace_reader.find_xplane(str(tmp_path)) is None
