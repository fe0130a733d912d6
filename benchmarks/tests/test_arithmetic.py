"""The functions of shapes agree with the program's constants and with a
hand count; the peaks table refuses a device it does not know."""

import importlib.util
import math
import os

import pytest

from benchmarks import compare, feed, peaks
from benchmarks.families import gpt2_lm
from benchmarks.manifest import ROOT, Manifest


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_for_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lm_flops_hand_count(man):
    config = man.config("cerebras-gpt-1.3b")
    d, inner, v, t = 2048, 8192, 50257, 2048
    # one block: qkv 3 d^2, proj d^2, MLP 2 d inner multiply-adds a token
    block = 4 * d * d + 2 * d * inner
    assert block == 50_331_648
    matmul_params = config["n_layer"] * block + d * v
    assert matmul_params == 4 * 50_331_648 + 102_926_336
    # causal attention: QK^T and PV over half the square, per token and
    # layer 2 * (t / 2) * d multiply-adds
    attention = config["n_layer"] * t * d
    fwd = 2 * (matmul_params + attention)
    assert gpt2_lm.train_flops_per_token(config, t) == 3.0 * fwd
    assert 1.9e9 < 3.0 * fwd < 2.0e9  # ISSUE 23: 1.93 GFLOP a token
    bench = _bench()
    # bench.py's count fixes the MLP at 4 d, which is this model's n_inner
    assert gpt2_lm.train_flops_per_token(config, t) == pytest.approx(
        bench._lm_analytic_flops_per_token(d, config["n_layer"], t, v),
        rel=1e-12)
    shapes = man.reference(config).param_shapes(config).values()
    assert 411e6 < sum(math.prod(s) for s in shapes) < 412e6


def test_flash_kernel_costs(man):
    config = man.config("cerebras-gpt-1.3b")
    traffic = man.traffic("silo4-long")
    costs = gpt2_lm.kernel_costs(config, traffic)
    b, h, t, d = 2, 16, 2048, 128
    assert costs["flash_fwd"]["flops"] == 2.0 * b * h * t * t * d
    assert costs["flash_bwd"]["flops"] == 2.5 * costs["flash_fwd"]["flops"]
    row = peaks.peaks_of("TPU v5 lite")
    for k in costs.values():  # both are bound by FLOPs at these shapes
        assert k["flops"] / row["flops"] \
            > k["bytes"] / row["hbm_bytes_per_s"]


def test_peaks_table():
    assert peaks.peaks_of("TPU v5 lite")["flops"] == 197.0e12
    assert peaks.peaks_of("TPU v5e")["hbm_bytes_per_s"] == 819.0e9
    with pytest.raises(ValueError):
        peaks.peaks_of("cpu")


@pytest.mark.parametrize("backend", feed.BACKENDS)
@pytest.mark.parametrize("chunk", (1, 2))
def test_feed_rule_is_the_programs(backend, chunk, monkeypatch):
    """``feed.py`` restates the order in which each of the program's
    schedule generators feeds rows; this pins both copies, for schedules
    of one client (the cells' chunk) and of two, over two epochs."""
    import numpy as np

    from fedml_tpu.parallel import packing

    monkeypatch.setenv("FEDML_TPU_PACKING", backend)
    assert packing.packing_backend() == backend
    ns, batch, epochs, seed = [20, 33, 48, 17, 5], 8, 2, 12345
    order = [4, 0, 3, 1, 2]  # ascending by steps (6, 10, 12, 6, 2), stable
    rng = np.random.default_rng(seed)
    for want in feed.streamed(ns, batch, epochs, chunk, seed, 2, backend):
        assert [len(steps) for steps in want] == [6, 10, 12, 6, 2]
        for c0 in range(0, len(ns), chunk):
            members = order[c0:c0 + chunk]
            sched = packing.pack_schedule([ns[c] for c in members], batch,
                                          epochs, rng=rng, s_max=16)
            for i, c in enumerate(members):
                for step, rows in enumerate(want[c]):
                    k = len(rows)
                    assert (sched["idx"][i, step, :k] == rows).all()
                    assert sched["mask"][i, step].sum() == k
                assert sched["mask"][i, len(want[c]):].sum() == 0


def test_compare_reads_an_unmoved_state_as_one():
    ref = [{"a": 2.0, "b": 1.0, "c": 3.0}, {"a": 4.0, "b": 2.0, "c": 6.0}]
    limits = {"loss_r1": 1e-3, "loss_r2": 1e-3, "first_update_gap": 0.01,
              "change_gap": 0.01}
    still = [{k: 0.0 for k in r} for r in ref]
    checks = compare.training_checks([1.0, 1.0], still, [1.0, 1.0], ref,
                                     limits)
    by = {c["name"]: c for c in checks}
    assert by["first_update_gap"]["value"] == 1.0 and not \
        by["first_update_gap"]["ok"]
    assert by["change_gap"]["value"] == 1.0
    sound = compare.training_checks([1.0, 1.0], ref, [1.0, 1.0], ref, limits)
    assert all(c["ok"] for c in sound)
    nan = compare.training_checks([float("nan"), 1.0], ref, [1.0, 1.0], ref,
                                  limits)
    assert not nan[0]["ok"]
