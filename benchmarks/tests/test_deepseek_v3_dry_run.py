"""A whole run of the harness over a toy configuration of the
``deepseek_v3_lm`` family on the CPU: the real harness, readers, family
and reference over a toy root of this file's own (``toyroot``'s writer
with this file's tables), the result line, the routing counters on the
traced round's span, and the planted faults, which have to come out not
``correct``."""

import json

import pytest

from benchmarks import harness
from benchmarks.tests import toyroot
from benchmarks.tests.test_dry_run import _half_batch, _unchanged

CELL = "toy-dsv3.toy2"
SEED = 2_345_678_907  # more than 32 signed bits hold

CONFIGS = {
    "toy-dsv3": {
        "name": "toy-dsv3", "family": "deepseek_v3_lm",
        "reference": "deepseek_v3_lm_reference.py",
        "hidden_size": 64, "num_attention_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "router_experts": 32, "experts_held": [8, 8],
        "n_shared_experts": 1, "num_experts_per_tok": 3,
        "first_k_dense_replace": 1, "num_hidden_layers": 48, "n_layer": 3,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "norm_topk_prob": True,
        "q_lora_rank": None, "rope_interleave": True,
        "as_run": {"compute_dtype": "bfloat16",
                   "reference_rows_at_a_time": 1}},
}
TRAFFIC = {
    "toy2": {"sequences_per_client": [6, 10], "seq_len": 32,
             "batch_size": 2, "epochs": 1, "client_chunk": 1, "lr": 0.1,
             "wd": 0.0},
}
CELLS = {
    CELL: {"config": "toy-dsv3", "traffic": "toy2", "check_rounds": 2,
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
        return toyroot.make_root(str(tmp_path_factory.mktemp("toy_dsv3")))


def _run(root, capsys, trace=False, **kw):
    code, result = harness.run(CELL, SEED, 0.5, trace, root=root,
                               require_chip=False, **kw)
    return code, result, capsys.readouterr()


def test_result_line(root, capsys):
    code, result, out = _run(root, capsys)
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "rounds_per_hour",
                                    "tokens_per_s"}
    assert [c["name"] for c in line["checks"]] == [
        "loss_r1", "loss_r2", "first_update_gap", "change_gap"]


def test_traced_line_and_the_routing_counters(root, capsys, monkeypatch):
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
    # no device plane on the CPU: the kernels' shares have nothing to read
    assert not {n for n in result["metrics"] if n.endswith("_roofline")}
    trains = [s for s in spans if s.name == "local-train"]
    assert trains
    tokens_a_round = sum(TRAFFIC["toy2"]["sequences_per_client"]) * 32
    for s in trains:
        assert s.attrs["moe_dropped"] == 0
        # 2 expert layers; a uniform router lands 3 * 8 / 32 of an
        # assignment a token on the held experts
        assert 0.5 < s.attrs["moe_rows_held"] \
            / (2 * tokens_a_round * 3 * 8 / 32) < 1.5
        assert s.attrs["moe_load_max_over_mean"] >= 1.0


def test_state_left_unchanged_is_not_correct(root, capsys):
    code, result, _ = _run(root, capsys, cell_hook=_unchanged)
    assert code == 0 and result["correct"] is False
    by = {c["name"]: c["value"] for c in result["checks"]}
    assert by["first_update_gap"] == pytest.approx(1.0, abs=1e-6)


def test_half_batch_is_not_correct(root, capsys, monkeypatch):
    _half_batch(monkeypatch)
    code, result, _ = _run(root, capsys)
    assert code == 0 and result["correct"] is False
