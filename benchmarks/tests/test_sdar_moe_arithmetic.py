"""The ``sdar_moe_lm`` family's functions of shapes against hand counts at
the published widths of ``sdar-30b-a3b-ep8``, and the configuration's
file against what ISSUE 31 fixed."""

import math

import pytest

from benchmarks import peaks
from benchmarks.families import sdar_moe_lm as family
from benchmarks.manifest import ROOT, Manifest

CELL = "sdar-a3b-ep8-silo2-bd4"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def config(man):
    return man.config("sdar-30b-a3b-ep8")


def test_the_configuration_keeps_every_published_key(config):
    """The catalog's ``config`` of SDAR-30B-A3B-Chat, every key under its
    name; only ``vocab_size`` differs (reduced), and the two other cuts
    have keys of the file's own."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["n_layer", "n_routed_experts",
                                 "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [5, 16, 18992]
    assert config["published"] == {"n_layer": 48, "n_routed_experts": 128,
                                   "vocab_size": 151936}
    assert config["router_experts"] == 128
    assert config["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 == 151936
    assert config["block_length"] == 4
    assert "8 chips share each layer" in config["deployment"]
    assert {"block_length", "noise_schedule", "shift", "qk_norm",
            "mask_id", "initializer_range"} <= set(config["assumed"])
    assert len(config["departures"]) >= 3


def test_parameter_count_by_hand(man, config):
    d = 2048
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    assert attention == 18_874_368                      # 18.87 M
    experts = 16 * 3 * d * 768
    assert experts == 75_497_472                        # 75.50 M
    norms = 2 * d + 2 * 128
    layer = attention + d * 128 + experts + norms
    total = 5 * layer + 2 * 18992 * d + d
    shapes = man.reference(config).param_shapes(config)
    assert sum(math.prod(s) for s in shapes.values()) == total
    assert total == config["memory_reckoning"]["parameters"]
    assert 550.9e6 < total < 551.1e6                    # ISSUE 31: 551.0 M


def test_train_flops_hand_count(config):
    d, t = 2048, 2048
    assert family.pairs(config, t) == t * t + t * 4     # of the (2 t)^2
    assert family.pairs(config, t) / (2 * t) ** 2 == pytest.approx(
        0.25, abs=1e-3)
    assert family.held_rows_per_position(config) == 1.0  # 8 * 16 / 128
    # multiply-adds a POSITION, forward; a clean token is two positions
    position = d * 4096 + 2 * d * 512 + 4096 * d + d * 128 \
        + 1.0 * 3 * d * 768
    attention = 32 * (128 + 128) * (t + 4)      # pairs a clean token
    fwd = 5 * (2 * position + attention) + d * 18992
    assert family.train_flops_per_token(config, t) == 3.0 * 2.0 * fwd
    assert 2.16e9 < 6.0 * fwd < 2.18e9      # 2.17 GFLOP a clean token


def test_kernel_costs_hand_count(man, config):
    traffic = man.traffic("silo2-bd4")
    costs = family.kernel_costs(config, traffic)
    n, h, t, hd = 1, 32, 2048, 128
    pairs = t * t + t * 4
    assert costs["flash_fwd"]["flops"] == 2 * pairs * (hd + hd) * n * h
    assert costs["flash_bwd"]["flops"] \
        == 2 * pairs * (3 * hd + 2 * hd) * n * h
    wide = n * 2 * t * 32 * hd * 2          # q, o, dO, dq: bf16, 32 heads
    narrow = n * 2 * t * 4 * hd * 2         # k, v, dk, dv: 4 heads
    lse = n * h * 2 * t * 4
    assert costs["flash_fwd"]["bytes"] == 2 * wide + 2 * narrow + lse
    assert costs["flash_bwd"]["bytes"] == 4 * wide + 4 * narrow + lse
    rows = n * 2 * t * 8 * 16 / 128
    assert rows == 4096                     # 256 rows an expert a step
    assert costs["moe_gmm_fwd"]["flops"] == 3 * 2 * rows * 2048 * 768
    assert costs["moe_gmm_bwd"]["flops"] == 2 * costs["moe_gmm_fwd"]["flops"]
    weights = 16 * 2048 * 768 * 2
    moved = rows * (2048 + 768) * 2
    assert costs["moe_gmm_fwd"]["bytes"] == 3 * (weights + moved)
    assert costs["moe_gmm_bwd"]["bytes"] == 2 * costs["moe_gmm_fwd"]["bytes"]
    row = peaks.peaks_of("TPU v5 lite")
    for name, k in costs.items():
        by_flops = k["flops"] / row["flops"]
        by_bytes = k["bytes"] / row["hbm_bytes_per_s"]
        assert (by_flops > by_bytes) == (k["bound"] == "flops"), name


def test_work_of_a_round(man):
    traffic = man.traffic("silo2-bd4")
    assert traffic["sequences_per_client"] == [32, 48]
    assert (traffic["batch_size"], traffic["epochs"],
            traffic["client_chunk"]) == (1, 1, 1)
    assert sum(traffic["sequences_per_client"]) == 80           # steps
    assert sum(traffic["sequences_per_client"]) * traffic["seq_len"] \
        == 163_840


def test_the_new_cells_metrics(man):
    mine = {m["name"] for m in man.metrics("per_layer", CELL)}
    assert {"moe_gmm_fwd_roofline", "moe_gmm_bwd_roofline",
            "flash_fwd_roofline", "mla_flash_bwd_roofline", "attn.busy_ms",
            "update.mfu_pct", "update.busy_ms", "device.idle_pct",
            "fold.wait_ms", "fold.d2h_ms", "fold.accumulate_ms",
            "fold.finalize_ms", "fold.apply_ms", "fold.host_ms",
            "feed.host_ms"} <= mine
    # the accepted backward pattern would read the grouped products too
    assert "flash_bwd_roofline" not in mine
    assert {m["name"] for m in man.metrics("end_to_end", CELL)} \
        == {"setup_s", "rounds_per_hour", "tokens_per_s"}
    # attn.busy_ms is this cell's alone
    for w in man.data["workloads"]:
        names = {m["name"] for m in man.metrics("per_layer", w["name"])}
        assert ("attn.busy_ms" in names) == (w["name"] == CELL)
