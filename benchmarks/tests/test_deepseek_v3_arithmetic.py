"""The ``deepseek_v3_lm`` family's functions of shapes against hand
counts at the published widths of ``kanana-2-30b-a3b-ep8``, and the
configuration's file against what ISSUE 27 fixed."""

import math

import pytest

from benchmarks import peaks
from benchmarks.families import deepseek_v3_lm as family
from benchmarks.manifest import ROOT, Manifest


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def config(man):
    return man.config("kanana-2-30b-a3b-ep8")


def test_the_configuration_keeps_every_published_width(config):
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "kv_lora_rank": 512, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "router_experts": 128,
        "routed_scaling_factor": 2.448, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "first_k_dense_replace": 1}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["n_layer", "n_routed_experts",
                                 "vocab_size"]
    assert [config[k] for k in config["reduced"]] == [5, 16, 16032]
    assert config["published"] == {"n_layer": 48, "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert config["experts_held"] == [0, 16]
    assert config["vocab_size"] * 8 == 128256
    assert "8 chips share each layer" in config["deployment"]
    assert len(config["departures"]) >= 3


def test_parameter_count_by_hand(man, config):
    d = 2048
    attention = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 \
        + 32 * 128 * d
    assert attention == 26_345_472                      # 26.35 M
    norms = 2 * d + 512
    dense_layer = attention + 3 * d * 6144 + norms
    expert = 3 * d * 768
    assert expert == 4_718_592                          # 4.72 M
    beside = attention + 3 * d * 1536 + d * 128 + 128 + norms
    expert_layer = beside + 16 * expert
    total = dense_layer + 4 * expert_layer + 2 * 16032 * d + d
    shapes = man.reference(config).param_shapes(config)
    assert sum(math.prod(s) for s in shapes.values()) == total
    assert total == config["memory_reckoning"]["parameters"]
    assert 575e6 < total < 577e6                        # ISSUE 27: 576 M


def test_train_flops_hand_count(config):
    d, t = 2048, 2048
    # multiply-adds a token, forward
    projections = d * 6144 + d * 576 + 512 * 8192 + 4096 * d
    assert projections == 26_345_472
    scores = 32 * (t // 2) * (192 + 128)   # the causal half, 192 and 128
    assert scores == 10_485_760
    dense = 3 * d * 6144
    shared, router = 3 * d * 1536, d * 128
    routed = 6 * 16 / 128 * 3 * d * 768    # 0.75 of an expert a token
    assert family.held_rows_per_token(config) == 0.75
    head = d * 16032
    fwd = 5 * (projections + scores) + dense \
        + 4 * (shared + router + routed) + head
    assert family.train_flops_per_token(config, t) == 3.0 * 2.0 * fwd
    assert 1.84e9 < 6.0 * fwd < 1.86e9     # ISSUE 27: about 1.85 GFLOP


def test_kernel_costs_hand_count(man, config):
    traffic = man.traffic("silo2-long")
    costs = family.kernel_costs(config, traffic)
    b, h, t = 2, 32, 2048
    assert costs["flash_fwd"]["flops"] == b * h * t * t * (192 + 128)
    assert costs["flash_bwd"]["flops"] == b * h * t * t * (3 * 192 + 2 * 128)
    token_rows = b * t * h * 2             # bf16 bytes a column
    lse = b * h * t * 4
    assert costs["flash_fwd"]["bytes"] == token_rows * (2 * 192 + 2 * 128) \
        + lse
    assert costs["flash_bwd"]["bytes"] == token_rows * (4 * 192 + 4 * 128) \
        + lse
    rows = b * t * 6 * 16 / 128
    assert rows == 3072                    # 192 tokens an expert a step
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
    traffic = man.traffic("silo2-long")
    assert traffic["sequences_per_client"] == [48, 64]
    steps = sum(-(-n // traffic["batch_size"])
                for n in traffic["sequences_per_client"])
    assert steps == 56
    assert sum(traffic["sequences_per_client"]) * traffic["seq_len"] \
        == 229_376
    twice = man.traffic("silo4-long2x")
    once = man.traffic("silo4-long")
    assert twice["sequences_per_client"] \
        == [2 * n for n in once["sequences_per_client"]]
    assert {k: v for k, v in twice.items()
            if k in ("seq_len", "batch_size", "epochs", "client_chunk",
                     "bucket_edges", "optimizer", "lr", "wd")} \
        == {k: v for k, v in once.items()
            if k in ("seq_len", "batch_size", "epochs", "client_chunk",
                     "bucket_edges", "optimizer", "lr", "wd")}
    assert sum(twice["sequences_per_client"]) * 2048 == 245_760


def test_the_new_cells_metrics(man):
    mine = {m["name"] for m in man.metrics("per_layer",
                                           "kanana2-a3b-ep8-silo2-long")}
    assert {"moe_gmm_fwd_roofline", "moe_gmm_bwd_roofline",
            "flash_fwd_roofline", "mla_flash_bwd_roofline",
            "update.mfu_pct", "update.busy_ms", "device.idle_pct",
            "fold.wait_ms", "feed.host_ms"} <= mine
    # the accepted backward pattern would read the grouped products too
    assert "flash_bwd_roofline" not in mine
    twice = {m["name"] for m in man.metrics("per_layer",
                                            "cgpt1.3b-silo4-long2x")}
    once = {m["name"] for m in man.metrics("per_layer",
                                           "cgpt1.3b-silo4-long")}
    assert twice == once
    for cell in ("kanana2-a3b-ep8-silo2-long", "cgpt1.3b-silo4-long2x"):
        assert {m["name"] for m in man.metrics("end_to_end", cell)} \
            == {"setup_s", "rounds_per_hour", "tokens_per_s"}
