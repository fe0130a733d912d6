"""The ``lfm2_moe_lm`` family's functions of shapes against hand counts at
the published widths of ``lfm2-8b-a1b-ep4``, and the configuration's file
against what ISSUE 34 fixed."""

import json
import os

import numpy as np
import pytest

from benchmarks import peaks
from benchmarks.families import lfm2_moe_lm as family
from benchmarks.manifest import ROOT, Manifest

CELL = "lfm2-a1b-ep4-silo2-doc4k"
PERIOD = ["full_attention", "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def config(man):
    return man.config("lfm2-8b-a1b-ep4")


def test_the_configuration_keeps_every_published_key(config):
    """The catalog's ``config`` of LFM2-8B-A1B, every key under its name
    and value but ``num_dense_layers`` and ``vocab_size`` (reduced); the
    other two cuts have keys of the file's own, and the published list of
    24 layer types stays whole beside the 5 as run."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert config[key] == value, key
    types = config["layer_types"]
    assert len(types) == 24 and types.count("full_attention") == 6
    assert types[:2] == ["conv", "conv"] and types[2:18] == PERIOD * 4
    # layers 1 to 5 of the published list are the 5 that run
    assert config["layer_types_as_run"] == types[1:6] \
        == ["conv"] + PERIOD
    assert config["reduced"] == ["n_layer", "num_dense_layers",
                                 "n_routed_experts", "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [5, 1, 8, 16384]
    assert config["published"] == {
        "n_layer": 24, "num_dense_layers": 2, "n_routed_experts": 32,
        "vocab_size": 65536}
    assert config["router_experts"] == 32
    assert config["experts_held"] == [0, 8]
    assert config["vocab_size"] * 4 == 65536
    assert "head_dim" not in config          # derived: hidden / heads
    assert "4 chips share each layer" in config["deployment"]
    assert {"head_dim", "qk_norm", "norm_topk_eps", "in_proj_thirds",
            "tie_word_embeddings", "initializer_range", "weights",
            "which_experts"} <= set(config["assumed"])
    assert len(config["departures"]) >= 5
    assert set(config["key_mapping"]) >= {"n_layer", "layer_types_as_run",
                                          "n_routed_experts"}
    assert config["memory_reckoning"]["parameters"] == 541_374_720
    entry = [c for c in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["configs"]
        if c["name"] == "lfm2-8b-a1b-ep4"][0]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_parameter_count_by_hand(man, config):
    d = 2048
    conv = d * 3 * d + d * d + d * 3
    attn = 2 * d * d + 2 * d * 512 + 2 * 64
    dense = 3 * d * 7168
    expert = 3 * d * 1792
    routed = 8 * expert + d * 32 + 32
    norms = 2 * d
    assert (conv, attn, dense, expert) \
        == (16_783_360, 10_485_888, 44_040_192, 11_010_048)
    layer0 = conv + dense + norms
    layer1 = attn + routed + norms
    layer24 = conv + routed + norms
    assert (layer0, layer1, layer24) \
        == (60_827_648, 98_635_936, 104_933_408)
    total = layer0 + layer1 + 3 * layer24 + 2 * 16384 * d + d
    assert total == 541_374_720
    shapes = man.reference(config).param_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == total


def test_train_flops_hand_count(config):
    """Per layer BY ITS TYPE, multiply-adds of one token forward, times 6."""
    d, t = 2048, 4096
    conv = d * 3 * d + d * d + 3 * d
    attn = 2 * d * d + 2 * d * 512 + 32 * (t + 1) / 2 * (64 + 64)
    dense = 3 * d * 7168
    sparse = d * 32 + (4 * 8 / 32) * 3 * d * 1792
    fwd = (conv + dense) + (attn + sparse) + 3 * (conv + sparse) \
        + d * 16384
    assert family.train_flops_per_token(config, t) == pytest.approx(6 * fwd)
    # ISSUE 34's reckoning: about 5.1 TFLOP a step of 4,096 positions
    assert 6 * fwd * t == pytest.approx(5.11e12, rel=2e-3)
    assert family.held_rows_per_token(config) == 1.0


def test_kernel_costs_hand_count(man, config):
    traffic = man.traffic("silo2-doc4k")
    costs = family.kernel_costs(config, traffic)
    n, h, t, hd, d = 1, 32, 4096, 64, 2048
    pairs = t * (t + 1) / 2
    assert costs["flash_fwd"]["flops"] == 2 * pairs * (hd + hd) * n * h
    assert costs["flash_bwd"]["flops"] \
        == 2 * pairs * (3 * hd + 2 * hd) * n * h
    wide = n * t * 32 * hd * 2          # q, o, dO, dq: bf16, 32 heads
    narrow = n * t * 8 * hd * 2         # k, v, dk, dv: their own 8 heads
    lse = n * h * t * 4
    assert costs["flash_fwd"]["bytes"] == 2 * wide + 2 * narrow + lse
    assert costs["flash_bwd"]["bytes"] == 4 * wide + 4 * narrow + lse
    rows = n * t * 4 * 8 / 32
    assert rows == 4096                 # 512 rows an expert a step
    assert costs["moe_gmm_fwd"]["flops"] == 3 * 2 * rows * d * 1792
    assert costs["moe_gmm_bwd"]["flops"] == 2 * costs["moe_gmm_fwd"]["flops"]
    weights, moved = 8 * d * 1792 * 2, rows * (d + 1792) * 2
    assert costs["moe_gmm_fwd"]["bytes"] == 3 * (weights + moved)
    assert costs["moe_gmm_bwd"]["bytes"] == 2 * costs["moe_gmm_fwd"]["bytes"]
    # the short convolution: three bf16 thirds in, y out; the backward
    # reads the thirds and dy and writes three thirds and [d, 3] float32.
    # Byte-bound; no metric reads the two entries yet (in the cell the
    # operands wait in VMEM, so the events are shorter than their arrays'
    # HBM time: the family's docstring), the counts are held here
    assert costs["short_conv_fwd"]["bytes"] \
        == (3 + 1) * n * t * d * 2 == 67_108_864
    assert costs["short_conv_fwd"]["flops"] == 7 * n * t * d
    assert costs["short_conv_bwd"]["bytes"] \
        == (3 + 1 + 3) * n * t * d * 2 + d * 3 * 4 == 117_465_088
    assert costs["short_conv_bwd"]["flops"] == 21 * n * t * d
    row = peaks.peaks_of("TPU v5 lite")
    for name, k in costs.items():
        by_flops = k["flops"] / row["flops"]
        by_bytes = k["bytes"] / row["hbm_bytes_per_s"]
        assert (by_flops > by_bytes) == (k["bound"] == "flops"), name
    # 0.23 ms a conv layer-step if its arrays moved at the HBM's peak
    # (ISSUE 34's sizing); the chip ran the pair in 0.21 ms
    least = (costs["short_conv_fwd"]["bytes"]
             + costs["short_conv_bwd"]["bytes"]) / row["hbm_bytes_per_s"]
    assert least == pytest.approx(0.000225, rel=0.02)


def test_work_of_a_round_is_the_issues_letter(man):
    traffic = man.traffic("silo2-doc4k")
    assert traffic["sequences_per_client"] == [24, 40]
    assert (traffic["seq_len"], traffic["batch_size"], traffic["epochs"],
            traffic["client_chunk"], traffic["optimizer"], traffic["wd"],
            traffic["bucket_edges"]) == (4096, 1, 1, 1, "sgd", 0.0,
                                         "geometric")
    assert sum(traffic["sequences_per_client"]) == 64           # steps
    assert sum(traffic["sequences_per_client"]) * traffic["seq_len"] \
        == 262_144
    entry = man.cell(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("lfm2-8b-a1b-ep4", "silo2-doc4k", 1)
    cell = man.cell_file(CELL)
    assert cell["check_rounds"] == 2 and cell["trace_rounds"] == 1
    assert set(cell["limits"]) == {"loss_r1", "loss_r2", "first_update_gap",
                                   "change_gap"}


def test_the_new_cells_metrics(man):
    mine = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert {"conv.busy_ms", "moe_gmm_fwd_roofline",
            "moe_gmm_bwd_roofline", "flash_fwd_roofline",
            "mla_flash_bwd_roofline", "update.mfu_pct", "update.busy_ms",
            "device.idle_pct", "fold.wait_ms", "fold.d2h_ms",
            "fold.accumulate_ms", "fold.finalize_ms", "fold.apply_ms",
            "fold.host_ms", "feed.host_ms"} <= mine
    # the accepted backward pattern would read the grouped products and
    # the convolution's forward too
    assert "flash_bwd_roofline" not in mine
    # attn.busy_ms stays sdar's alone (its own arithmetic test says so),
    # and no share of a roofline is stated for the convolution's kernels:
    # no peak of peaks.py bounds their events (the family's docstring)
    assert "attn.busy_ms" not in mine
    assert not {n for n in mine if n.startswith("short_conv")}
    assert {m["name"] for m in man.metrics("end_to_end", CELL)} \
        == {"setup_s", "rounds_per_hour", "tokens_per_s"}
    # the one new metric is this cell's alone, and reads by name
    for w in man.data["workloads"]:
        names = {m["name"] for m in man.metrics("per_layer", w["name"])}
        assert ("conv.busy_ms" in names) == (w["name"] == CELL)
    for m in man.metrics("per_layer", CELL):
        if m["name"] == "conv.busy_ms":
            assert "short_conv_" in m["reader"]["pattern"]
            assert m["reader"]["kind"] == "trace_time"
            assert (m["layer"], m["moves"]) == ("kernels", "rounds_per_hour")
