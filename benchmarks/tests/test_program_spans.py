"""The per-layer metrics that read the program's own spans (a ``Tracer``
span is a ``jax.profiler.TraceAnnotation`` of its name): a traced toy run
on the CPU reports all six, and the inside of the fold's calls adds up to
their outside (``fold.host_ms``, read from the profiler's Python-call
events of the same calls)."""

import pytest

from benchmarks import harness
from benchmarks.tests import toyroot

SPAN_METRICS = ("feed.host_ms", "fold.wait_ms", "fold.d2h_ms",
                "fold.accumulate_ms", "fold.finalize_ms", "fold.apply_ms")
#: the spans inside ``fold_oldest`` and ``apply_avg``; ``fold.finalize``
#: lies between the two calls
INSIDE = ("fold.wait_ms", "fold.d2h_ms", "fold.accumulate_ms",
          "fold.apply_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = toyroot.make_root(str(tmp_path_factory.mktemp("toy")))
    code, result = harness.run(toyroot.LM_CELL, 2_345_678_903, 0.5, True,
                               root=root, require_chip=False)
    assert code == 0 and result["correct"] is True
    return result


def test_six_span_metrics_are_on_the_traced_line(traced):
    for name in SPAN_METRICS:
        assert traced["metrics"][name]["unit"] == "ms", name
        assert traced["metrics"][name]["value"] > 0, name


def test_inside_of_the_fold_adds_up_to_its_outside(traced):
    value = {k: m["value"] for k, m in traced["metrics"].items()}
    inside = sum(value[name] for name in INSIDE)
    assert inside == pytest.approx(value["fold.host_ms"], rel=0.10)
    assert inside <= value["fold.host_ms"]  # spans lie inside the calls
    whole = inside + value["fold.finalize_ms"] + value["feed.host_ms"]
    assert whole < 1e3 * value["round_s.max"]
