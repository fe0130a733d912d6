"""A whole run of the harness at toy size on the CPU, under each of the
program's two schedule generators: the result line, the planted faults
and the control.

The harness's look for a chip is skipped (``require_chip=False``); the
rest of a run is the real one. Each fault breaks the timed path
underneath the harness and ``correct`` has to come out false:

- a step that returns its state unchanged;
- half of every batch left out of the loss, the mean taken over the rest.

The exchange between chips and an altered token do not exist in a
one-chip training cell. The control is the reference computed in fp8 in
the program's place (``tools/control.py``); it has to fail as well.
"""

import json

import pytest

from benchmarks import harness
from benchmarks.tests import toyroot
from benchmarks.tools import control

CELL = toyroot.LM_CELL
BACKENDS = ("native", "python")
SEED = 2_345_678_901  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toyroot.make_root(str(tmp_path_factory.mktemp("toy")))


@pytest.fixture(params=BACKENDS)
def backend(request, monkeypatch):
    """The program resolves its generator on every call, so the
    environment decides here; a run of the benchmark leaves it alone."""
    monkeypatch.setenv("FEDML_TPU_PACKING", request.param)
    return request.param


def _run(root, capsys, trace=False, **kw):
    code, result = harness.run(CELL, SEED, 0.5, trace, root=root,
                               require_chip=False, **kw)
    out = capsys.readouterr()
    return code, result, out


def test_result_line(root, backend, capsys):
    code, result, out = _run(root, capsys)
    assert code == 0 and result["info"]["feed_backend"] == backend
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line == json.loads(json.dumps(result))
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert list(line)[-1] == "checks"  # the numbers compared come last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"setup_s", "rounds_per_hour",
                                    "tokens_per_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # each number compared beside its limit, as the last lines of stderr
    tail = out.err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)
    assert line["info"]["setup_compiles"] > 0


def test_traced_line(root, capsys):
    code, result, _ = _run(root, capsys, trace=True)
    assert code == 0 and result["correct"] is True
    names = set(result["metrics"])
    # the fold's time is read from the profiler's Python-call events,
    # which a CPU trace has too
    assert {"setup.compile_s", "window.compiles", "round_s.max",
            "fold.host_ms"} <= names
    assert "setup_s" not in names
    assert result["metrics"]["window.compiles"]["value"] == 0
    assert 0 < result["metrics"]["fold.host_ms"]["value"] \
        < 1e3 * result["metrics"]["round_s.max"]["value"]
    # no device plane on the CPU: nothing to read, so no number (never 0)
    assert not {"flash_fwd_roofline", "flash_bwd_roofline",
                "update.busy_ms", "update.mfu_pct"} & names


def _unchanged(cell):
    """The round runs, and hands back the state it was given."""
    import jax
    import jax.numpy as jnp

    api, real = cell.api, cell.api.train_one_round

    def broken():
        before = jax.tree.map(jnp.copy, api.global_state)
        metrics = real()
        api.global_state = before
        return metrics

    api.train_one_round = broken


def _half_batch(monkeypatch):
    """The second half of every batch is masked out where the program
    builds its schedule, so its loss is the mean over the first half."""
    from fedml_tpu.algorithms import fedavg
    from fedml_tpu.parallel import packing

    real = packing.pack_schedule

    def broken(*args, **kw):
        sched = real(*args, **kw)
        sched["mask"][:, :, (sched["mask"].shape[2] + 1) // 2:] = 0.0
        return sched

    monkeypatch.setattr(packing, "pack_schedule", broken)
    monkeypatch.setattr(fedavg, "pack_schedule", broken)


def test_state_left_unchanged_is_not_correct(root, capsys):
    code, result, _ = _run(root, capsys, cell_hook=_unchanged)
    assert code == 0 and result["correct"] is False
    by = {c["name"]: c["value"] for c in result["checks"]}
    assert by["first_update_gap"] == pytest.approx(1.0, abs=1e-6)


def test_half_batch_is_not_correct(root, backend, capsys, monkeypatch):
    _half_batch(monkeypatch)
    code, result, _ = _run(root, capsys)
    assert code == 0 and result["correct"] is False


def test_control_and_reference_fault_fail(root, capsys):
    assert control.main(["--workload", CELL, "--seeds", str(SEED),
                         "--root", root]) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert {x["variant"] for x in lines} == {"fp8", "half_batch"}
    assert not any(x["correct"] for x in lines)


def test_no_chip_no_result(capsys):
    """On this machine JAX finds no TPU: exit code other than 0 and no
    result line (the real manifest, the real look for a chip)."""
    code, result = harness.run("cgpt1.3b-silo4-long", 1, 1.0, False)
    assert code != 0 and result is None
    assert capsys.readouterr().out.strip() == ""
