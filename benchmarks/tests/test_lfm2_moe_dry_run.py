"""A whole run of the harness over a toy configuration of the
``lfm2_moe_lm`` family on the CPU: the real harness, readers, family and
reference over a toy root of this file's own (``toyroot``'s writer with
this file's tables), the result line, the layer mix and the routing's
counters on the traced round's span, and the planted faults, which have
to come out not ``correct``."""

import json

import pytest

from benchmarks import compare, harness
from benchmarks.manifest import Manifest
from benchmarks.tests import toyroot
from benchmarks.tests.test_dry_run import _unchanged

CELL = "toy-lfm2.toy2doc"
SEED = 3_445_678_907  # more than 32 signed bits hold

CONFIGS = {
    "toy-lfm2": {
        "name": "toy-lfm2", "family": "lfm2_moe_lm",
        "reference": "lfm2_moe_lm_reference.py", "model_type": "lfm2_moe",
        "hidden_size": 128, "num_attention_heads": 8,
        "num_key_value_heads": 2, "intermediate_size": 192,
        "moe_intermediate_size": 32, "num_experts": 16,
        "n_routed_experts": 4, "router_experts": 16,
        "experts_held": [4, 4], "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "n_layer": 3, "num_dense_layers": 1,
        "layer_types": ["conv", "conv", "full_attention"] + ["conv"] * 21,
        "layer_types_as_run": ["conv", "full_attention", "conv"],
        "conv_L_cache": 3, "conv_bias": False, "use_expert_bias": True,
        "vocab_size": 256, "norm_eps": 1e-5, "rope_theta": 1000000,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "as_run": {"compute_dtype": "bfloat16"}},
}
TRAFFIC = {
    "toy2doc": {"sequences_per_client": [5, 7], "seq_len": 48,
                "batch_size": 1, "epochs": 1, "client_chunk": 1, "lr": 0.1,
                "wd": 0.0},
}
CELLS = {
    CELL: {"config": "toy-lfm2", "traffic": "toy2doc", "check_rounds": 2,
           "trace_rounds": 1,
           "limits": {"loss_r1": 3e-4, "loss_r2": 3e-4,
                      "first_update_gap": 2e-2, "change_gap": 2e-2}},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        for name, table in (("CONFIGS", CONFIGS), ("TRAFFIC", TRAFFIC),
                            ("CELLS", CELLS), ("LM_CELL", CELL)):
            mp.setattr(toyroot, name, table)
        return toyroot.make_root(str(tmp_path_factory.mktemp("toy_lfm2")))


def _run(root, capsys, trace=False, **kw):
    code, result = harness.run(CELL, SEED, 0.5, trace, root=root,
                               require_chip=False, **kw)
    return code, result, capsys.readouterr()


def test_the_cells_files_load():
    """The real cell's files, found by name as the harness finds them."""
    man = Manifest()
    entry = man.cell("lfm2-a1b-ep4-silo2-doc4k")
    config = man.config(entry["config"])
    assert config["family"] == "lfm2_moe_lm"
    reference = man.reference(config)
    assert reference.VARIANTS == ("f32", "fp8", "half_batch", "center_tap")
    assert man.traffic(entry["traffic"])["seq_len"] == 4096
    assert set(man.cell_file(entry["name"])["limits"]) == {
        "loss_r1", "loss_r2", "first_update_gap", "change_gap"}
    assert reference.sizes(config)["types"] == (
        "conv", "full_attention", "conv", "conv", "conv")


def test_result_line(root, capsys):
    code, result, out = _run(root, capsys)
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "rounds_per_hour",
                                    "tokens_per_s"}
    assert [c["name"] for c in line["checks"]] == [
        "loss_r1", "loss_r2", "first_update_gap", "change_gap"]


def test_traced_line_and_the_spans_counters(root, capsys, monkeypatch):
    from fedml_tpu.observability import tracing

    spans = []
    real = tracing.Tracer.finished_spans

    def keep(self):
        spans[:] = real(self)
        return spans

    monkeypatch.setattr(tracing.Tracer, "finished_spans", keep)
    code, result, _ = _run(root, capsys, trace=True)
    assert code == 0 and result["correct"] is True
    assert {"window.compiles", "round_s.max", "fold.host_ms"} \
        <= set(result["metrics"])
    assert result["metrics"]["window.compiles"]["value"] == 0
    # no device plane on the CPU: the kernels' metrics have nothing to
    # read and are left out, the new one among them
    assert not {n for n in result["metrics"]
                if n.endswith("_roofline") or n in ("attn.busy_ms",
                                                    "conv.busy_ms")}
    trains = [s for s in spans if s.name == "local-train"]
    assert trains
    positions = sum(TRAFFIC["toy2doc"]["sequences_per_client"]) * 48
    for s in trains:
        assert s.attrs["mode"] == "bucketed" and s.attrs["fold"] == "device"
        # 2 conv layers and 1 attention layer as run
        assert s.attrs["conv.layer_positions"] == 2 * positions
        assert s.attrs["attn.layer_positions"] == positions
        assert s.attrs["moe_dropped"] == 0
        # 2 routed layers; a uniform router lands 4 * 4 / 16 of an
        # assignment a position on the held experts
        assert 0.5 < s.attrs["moe_rows_held"] \
            / (2 * positions * 4 * 4 / 16) < 1.5


def test_state_left_unchanged_is_not_correct(root, capsys):
    code, result, _ = _run(root, capsys, cell_hook=_unchanged)
    assert code == 0 and result["correct"] is False
    by = {c["name"]: c["value"] for c in result["checks"]}
    assert by["first_update_gap"] == pytest.approx(1.0, abs=1e-6)


@pytest.mark.parametrize("variant", ["half_batch", "center_tap"])
def test_the_planted_faults_are_not_correct(root, variant):
    """The reference's own faults in the program's place, as
    ``tools/control.py`` reads them on the chip: half of every batch left
    out (of its one row's positions, the batch being one row), and the
    convolution without its earlier taps: the comparison sees the new
    mechanism."""
    man = Manifest(root)
    config, traffic = man.config("toy-lfm2"), man.traffic("toy2doc")
    reference = man.reference(config)
    from benchmarks.families import lfm2_moe_lm as family

    feed = family.feed_of(config, traffic, SEED, 2, reference)
    ref = reference.run_rounds(config, traffic, SEED, 2, feed)
    alt = reference.run_rounds(config, traffic, SEED, 2, feed,
                               variant=variant)
    checks = compare.training_checks(
        alt["loss"], alt["change_norms"], ref["loss"], ref["change_norms"],
        CELLS[CELL]["limits"])
    assert not all(c["ok"] for c in checks), checks
