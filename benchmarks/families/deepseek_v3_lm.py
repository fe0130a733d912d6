"""Family ``deepseek_v3_lm``: decoders of the ``deepseek_v3`` family
(latent attention, routed experts of which the configuration's share is
held here, a shared expert) through the program's streamed federated
round, built the way ``gpt2_lm`` builds its own, with the functions of
shapes that its metrics need.

From the program: ``DeepseekV3LM`` with its ``DecoderConfig``,
``make_seq_classification_spec``, ``FedAvgAPI`` and the name of the
schedule generator it runs (``packing_backend()``). Data, weights and the
feed order come from the configuration's reference module and
``benchmarks/feed.py``.
"""

from __future__ import annotations

import dataclasses
import types

from benchmarks.families.common import Cell, nest, seed32
# the streamed feed rule is the trainer's, whatever the model
from benchmarks.families.gpt2_lm import _feed, feed_of  # noqa: F401


def _sizes(config):
    held = int(config["n_routed_experts"])
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "dqk": int(config["qk_nope_head_dim"])
        + int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "latent": int(config["kv_lora_rank"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "shared": int(config["n_shared_experts"]),
        "held": held,
        "router": int(config.get("router_experts", held)),
        "top_k": int(config["num_experts_per_tok"]),
        "lead": int(config["first_k_dense_replace"]),
        "layers": int(config.get("n_layer", config["num_hidden_layers"])),
        "vocab": int(config["vocab_size"]),
    }


def held_rows_per_token(config):
    """Assignments a token lands on the experts held here, in expectation
    under a uniform router: experts per token times the share held."""
    s = _sizes(config)
    return s["top_k"] * s["held"] / s["router"]


def train_flops_per_token(config, seq_len):
    """Useful training FLOPs of one token: three times the forward pass's
    multiply-adds, twice. Per layer: the four attention projections (q;
    the latent with its rotary key head; keys and values out of the
    latent; the output), causal attention at half of the full square with
    scores ``qk_nope + qk_rope`` wide and values ``v_head_dim`` wide; the
    gated MLP in the leading dense layers; in the others the shared
    expert, the router at its full width and, in expectation under a
    uniform router, ``held_rows_per_token`` of one expert. Then the head
    over the vocabulary's slice. Padding of the score width, the sort,
    recomputation and the optimizer are not counted."""
    s = _sizes(config)
    d, h = s["d"], s["heads"]
    attention = d * h * s["dqk"] + d * (s["latent"] + s["rope"]) \
        + s["latent"] * h * (s["nope"] + s["dv"]) + h * s["dv"] * d \
        + h * (seq_len // 2) * (s["dqk"] + s["dv"])
    dense = 3 * d * s["dense"]
    sparse = 3 * d * s["shared"] * s["expert"] + d * s["router"] \
        + held_rows_per_token(config) * 3 * d * s["expert"]
    lead = min(s["lead"], s["layers"])
    fwd = s["layers"] * attention + lead * dense \
        + (s["layers"] - lead) * sparse + d * s["vocab"]
    return 3.0 * 2.0 * fwd


def kernel_costs(config, traffic):
    """FLOPs and HBM bytes the algorithm needs for ONE call of each
    kernel (one layer, one local step).

    Flash attention over q, k ``[B, T, H, Dqk]`` and v ``[B, T, H, Dv]``
    bf16, causal half. Forward: QK^T and PV, ``B*H*T^2*(Dqk + Dv)``
    FLOPs; reads q, k, v, writes o (bf16) and the row log-sum-exp (f32).
    Backward: five products (S again, dV, dP, dQ, dK),
    ``B*H*T^2*(3*Dqk + 2*Dv)``; reads q, k, v, o, dO and the log-sum-exp,
    writes dq, dk, dv.

    The grouped product of one expert layer, ``rows`` rows in expectation
    under a uniform router (``B*T*held_rows_per_token``). Forward: the
    three projections (gate, up, down), each ``2*rows*d*width`` FLOPs;
    bytes: the held experts' bf16 matrices once a product plus the rows
    in and out. Backward: for each projection the gradient to its rows
    and to its matrices, twice the forward in FLOPs and in bytes (the
    matrices are read once and written once)."""
    s = _sizes(config)
    b = int(traffic["batch_size"]) * int(traffic["client_chunk"])
    t, h = int(traffic["seq_len"]), s["heads"]
    wide, narrow = b * t * h * s["dqk"] * 2, b * t * h * s["dv"] * 2
    lse = b * h * t * 4
    rows = b * t * held_rows_per_token(config)
    d, width = s["d"], s["expert"]
    gmm_flops = 3 * 2.0 * rows * d * width
    gmm_bytes = 3 * 2.0 * (s["held"] * d * width + rows * (d + width))
    return {
        "flash_fwd": {"flops": 1.0 * b * h * t * t * (s["dqk"] + s["dv"]),
                      "bytes": 2.0 * wide + 2.0 * narrow + lse,
                      "bound": "flops"},
        "flash_bwd": {"flops": 1.0 * b * h * t * t
                      * (3 * s["dqk"] + 2 * s["dv"]),
                      "bytes": 4.0 * wide + 4.0 * narrow + lse,
                      "bound": "flops"},
        "moe_gmm_fwd": {"flops": gmm_flops, "bytes": gmm_bytes,
                        "bound": "bytes"},
        "moe_gmm_bwd": {"flops": 2.0 * gmm_flops, "bytes": 2.0 * gmm_bytes,
                        "bound": "bytes"},
    }


def build(config, traffic, seed, reference):
    # first thing: a program without this model fails here, in no time
    from fedml_tpu.models.deepseek_v3 import DecoderConfig, DeepseekV3LM

    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.parallel.packing import packing_backend

    s32 = seed32(seed)
    clients = reference.make_clients(config, traffic, seed)
    ns = [len(c["y"]) for c in clients]
    t = int(traffic["seq_len"])
    model = DeepseekV3LM(
        DecoderConfig.from_dict(config),
        dtype=jnp.dtype(config["as_run"]["compute_dtype"]))
    spec = make_seq_classification_spec(
        model, jnp.zeros((1, t), jnp.int32), name="lm")
    # the benchmark's weights reach the program as the spec's initial state
    weights = reference.make_weights(config, seed)
    spec = dataclasses.replace(
        spec, init_fn=lambda rng: {"params": nest(weights)})
    nums = dict(enumerate(ns))
    dataset = [sum(ns), 0, None, None, nums, dict(enumerate(clients)), {},
               int(config["vocab_size"])]
    run_args = types.SimpleNamespace(
        client_num_in_total=len(ns), client_num_per_round=len(ns),
        comm_round=10 ** 9, epochs=int(traffic["epochs"]),
        batch_size=int(traffic["batch_size"]), lr=float(traffic["lr"]),
        wd=float(traffic.get("wd", 0.0)), client_optimizer="sgd",
        frequency_of_the_test=10 ** 9, seed=s32,
        client_chunk=int(traffic["client_chunk"]),
        bucket_edges=traffic.get("bucket_edges", "geometric"),
        device_resident="0")
    api = FedAvgAPI(dataset, spec, run_args)
    del weights
    tokens = sum(ns) * t * int(traffic["epochs"])
    return Cell(
        api=api, ns=ns, traffic=traffic, seed32=s32, state_key="params",
        feed_fn=_feed, feed_backend=packing_backend(),
        work_per_round={
            "tokens": tokens,
            "useful_flops": tokens * train_flops_per_token(config, t)},
        shapes={"kernels": kernel_costs(config, traffic)})
