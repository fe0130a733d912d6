"""CPU smoke coverage for the measurement harnesses.

``scripts/convergence.py``, ``scripts/profile_lane_step.py`` and
``scripts/bench_lm.py`` are meant for a machine with a chip; with no CI
reference they could silently rot before the moment they matter. Each
smoke runs the real script in a subprocess at ``--cpu
--tiny``-class shapes and asserts its JSON output contract -- the same
contract the committed evidence files are parsed by.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=900):
    r = subprocess.run([sys.executable] + cmd, capture_output=True,
                       text=True, cwd=REPO, timeout=timeout)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r


@pytest.mark.slow
def test_profile_lane_step_smoke():
    r = _run(["scripts/profile_lane_step.py", "--cpu", "--tiny", "--fp32",
              "--repeats", "2"])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    names = {k for ln in lines for k in ln}
    for want in ("A_one_model_bs512", "B_vmap_lanes", "C_plus_augment",
                 "D_full_lane_body", "E_one_model_frozen_bn", "breakdown"):
        assert want in names, (want, names)
    (bd,) = [ln["breakdown"] for ln in lines if "breakdown" in ln]
    for k in ("conv_ceiling_ms", "lane_penalty_ms", "augment_ms",
              "opt_flush_ms", "lane_penalty_x"):
        assert k in bd
    # the inversion contract: any negative derived component must be
    # flagged, never silently printed as a cost (r4 advisor finding)
    negative = [k for k in ("lane_penalty_ms", "augment_ms",
                            "opt_flush_ms") if bd[k] < 0]
    assert set(negative) <= set(bd.get("inversions", [])), (negative, bd)


@pytest.mark.slow
def test_bench_lm_smoke():
    r = _run(["scripts/bench_lm.py", "--cpu", "--tiny", "--repeats", "2"])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, r.stdout[-2000:]
    rec = lines[-1]
    for k in ("metric", "mfu", "achieved_tflops"):
        assert k in rec, rec
    assert rec["achieved_tflops"] >= 0 and rec["mfu"] is None  # --cpu


@pytest.mark.slow
def test_convergence_smoke(tmp_path):
    # 2 configs x 4 rounds at toy shapes, incl. the plateau-agreement
    # assert (exit code 1 = diverged; _run asserts 0)
    r = _run(["scripts/convergence.py", "--rounds", "4", "--clients", "2",
              "--n_train", "128", "--image", "8", "--depth", "8",
              "--tail", "2", "--tol", "0.5",
              "--configs", "fp32_lanes,fp32_flat",
              "--outdir", str(tmp_path)], timeout=1200)
    summary = json.loads((tmp_path / "summary.json").read_text())
    assert summary["agree"] is True
    assert {x["name"] for x in summary["results"]} == {"fp32_lanes",
                                                       "fp32_flat"}
    for cfg in ("fp32_lanes", "fp32_flat"):
        curve = [json.loads(ln) for ln in
                 (tmp_path / f"{cfg}.jsonl").read_text().splitlines()]
        assert len(curve) == 4
        assert all("train_acc" in c and "train_loss" in c for c in curve)


def _write_curve(path, rounds, acc):
    with open(path, "w") as f:
        for r in range(rounds):
            f.write(json.dumps({"round": r, "train_acc": acc,
                                "train_loss": 2.0 - acc}) + "\n")


def test_convergence_summarize_partial_run(tmp_path):
    # the tool exists for KILLED runs (convergence.py writes summary.json
    # only when every config finishes): curves alone must yield an
    # honestly-labeled summary
    _write_curve(tmp_path / "bf16_lanes3.jsonl", 12, 0.41)
    _write_curve(tmp_path / "fp32_lanes.jsonl", 12, 0.42)
    _write_curve(tmp_path / "fp32_flat.jsonl", 5, 0.40)  # killed early
    r = subprocess.run(
        [sys.executable, "scripts/convergence_summarize.py",
         "--outdir", str(tmp_path), "--tail", "3", "--tol", "0.05",
         "--min_rounds", "10"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    # agreement holds but one curve is short of min_rounds -> exit 1,
    # summary.json written anyway
    assert r.returncode == 1, (r.stdout, r.stderr)
    summary = json.loads((tmp_path / "summary.json").read_text())
    by_name = {x["name"]: x for x in summary["results"]}
    assert by_name["bf16_lanes3"]["mode"] == "lanes3"
    assert by_name["fp32_lanes"]["mode"] == "lanes"
    assert by_name["fp32_flat"]["mode"] == "flat"
    assert by_name["fp32_flat"]["complete"] is False
    assert by_name["fp32_lanes"]["complete"] is True
    assert summary["agree"] is True
    assert summary["all_complete"] is False


def test_convergence_summarize_complete_agreeing(tmp_path):
    _write_curve(tmp_path / "bf16_lanes.jsonl", 10, 0.41)
    _write_curve(tmp_path / "bf16_flat.jsonl", 10, 0.42)
    r = subprocess.run(
        [sys.executable, "scripts/convergence_summarize.py",
         "--outdir", str(tmp_path), "--tail", "3", "--tol", "0.05",
         "--min_rounds", "10"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    summary = json.loads((tmp_path / "summary.json").read_text())
    assert summary["agree"] is True and summary["all_complete"] is True


@pytest.mark.slow
def test_bench_cpu_smoke():
    # the explicit CPU smoke of the bench path: platform forcing, the
    # mode-3 MXU-packed round, the FedOpt server step, and the
    # one-JSON-line contract -- with no MFU, since there is no chip.
    r = _run(["bench.py", "--smoke", "--platform", "cpu", "--clients", "4",
              "--client_chunk", "2", "--batch_size", "16",
              "--algo", "fedopt", "--mode", "3"], timeout=900)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("{")][-1]
    out = json.loads(line)
    assert out["value"] > 0, out
    assert out["vs_baseline"] == 0.0  # CPU numbers are not comparable
    assert out["mfu"] is None and out["assumed_peak_tflops"] is None
    assert "FedOpt" in out["metric"] and "SMOKE" in out["metric"]
    assert out["exec_mode"] == "mxu-lanes", out.get("exec_mode")


@pytest.mark.slow
def test_bench_gkt_smoke():
    # the split/distill path's perf harness must not rot unexercised
    r = _run(["scripts/bench_gkt.py", "--cpu", "--tiny", "--rounds", "1"])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, r.stdout[-2000:]
    rec = lines[-1]
    for k in ("metric", "value", "unit", "rounds_per_hour"):
        assert k in rec, rec
    assert rec["value"] > 0


@pytest.mark.slow
def test_bench_lane_conv_smoke():
    # the lowering shoot-out harness (scripts/bench_lane_conv.py): tiny
    # single-stage matrix incl. the numerics gate over every candidate
    r = _run(["scripts/bench_lane_conv.py", "--cpu", "--tiny"])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    errors = [ln for ln in lines if "ERROR" in ln or "SKIP" in ln]
    assert not errors, errors  # a rotted candidate hides behind fwd-only
    done = {(ln["cand"], ln["pass"]) for ln in lines
            if "cand" in ln and "ms" in ln}
    # every candidate must survive the numerics gate and time BOTH
    # passes -- the gradient path is the one the shoot-out exists for
    for cand in ("vmap", "packed", "packed_all", "bgc", "im2col",
                 "shared"):
        assert (cand, "fwd") in done and (cand, "fwd+bwd") in done, (
            cand, done)


def _hlo_names():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "hlo_names", os.path.join(REPO, "scripts", "hlo_names.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_HLO = """HloModule jit_f, entry_computation_layout={(f32[8,16]{1,0})->f32[8]{0}}

%fused_computation (param_0.1: f32[8,16]) -> f32[8,16] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  ROOT %exp.9 = f32[8,16]{1,0} exponential(%param_0.1), metadata={op_name="jit(f)/inside"}
}

ENTRY %main.7 (Arg_0.1: f32[8,16]) -> f32[8] {
  %Arg_0.1 = f32[8,16]{1,0:T(8,128)} parameter(0)
  %fusion.3 = f32[8,16]{1,0:T(8,128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/exp" stack_frame_id=1}, backend_config={"window_config":{"estimated_cycles":"3720","iteration_bounds":["1"]}}
  ROOT %reduce_fusion.1 = (f32[8]{0}, s32[8]{0}) fusion(%fusion.3, %Arg_0.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/reduce_sum"}
}
"""


def test_hlo_names_reads_an_optimized_module():
    """``scripts/hlo_names.py``'s reading of an optimized HLO text: the
    instructions a device trace shows (the insides of fusions left out),
    each with its ``op_name``, output and operands by name."""
    names = _hlo_names()
    instrs = names.instructions(_HLO)
    assert sorted(instrs) == ["Arg_0.1", "fusion.3", "reduce_fusion.1"]
    assert instrs["fusion.3"][1:] == ("fusion", ["Arg_0.1"],
                                      "jit(f)/while/body/exp", 3720)
    names.LARGE = 256
    assert names.describe("reduce_fusion.1", instrs) == {
        "name": "reduce_fusion.1", "opcode": "fusion",
        "op_name": "jit(f)/while/body/reduce_sum",
        "output": "(f32[8], s32[8])",
        "large_operands": ["f32[8,16]", "f32[8,16]"],
        "estimated_cycles": 0}


def test_hlo_names_takes_its_names_from_the_newest_breakdown(tmp_path):
    names = _hlo_names()
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text("\n".join(json.dumps(rec) for rec in [
        {"workload": "a", "breakdown": {"device_ops": [["old.1_fusion", 1]]}},
        {"workload": "a", "breakdown": {"device_ops": [
            ["fusion.720_fusion", 0.3], ["convert.9_convert", 0.2],
            ["vmap_flash_fwd__.2_custom-call", 0.1]]}},
        {"workload": "a"},
        {"workload": "b", "breakdown": {"device_ops": [["x_fusion", 1]]}},
    ]))
    assert names.ledger_names("a", str(ledger)) == [
        "fusion.720", "convert.9", "vmap_flash_fwd__.2"]
    with pytest.raises(SystemExit, match="no ledger line"):
        names.ledger_names("c", str(ledger))
