"""Family ``sdar_moe_lm``: decoders of the ``sdar_moe`` family
(grouped-query attention with per-head q/k norms, softmax-routed experts
of which the configuration's share is held here, no shared expert)
trained by block diffusion through the program's streamed federated
round, built the way ``gpt2_lm`` builds its own, with the functions of
shapes that its metrics need.

From the program: ``DecoderLM`` with its ``DecoderConfig``,
``make_block_diffusion_lm_spec``, ``FedAvgAPI`` and the name of the
schedule generator it runs (``packing_backend()``). Data (ids AND their
corruption), weights and the feed order come from the configuration's
reference module and ``benchmarks/feed.py``.

A sequence of ``L`` clean ids runs through the model as ``2 L`` positions
(the clean copy and the noised one): ``tokens`` counts the clean ids,
``useful_flops`` the ``2 L`` positions the objective needs and
attention's ``L^2 + L B`` pairs.
"""

from __future__ import annotations

import dataclasses
import types

from benchmarks.families.common import Cell, nest, seed32
# the streamed feed rule is the trainer's, whatever the model
from benchmarks.families.gpt2_lm import _feed, feed_of  # noqa: F401


def _sizes(config):
    router = int(config.get("router_experts", config["num_experts"]))
    return {
        "d": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert": int(config["moe_intermediate_size"]),
        "held": int(config.get("n_routed_experts", router)),
        "router": router,
        "top_k": int(config["num_experts_per_tok"]),
        "layers": int(config.get("n_layer", config["num_hidden_layers"])),
        "vocab": int(config["vocab_size"]),
        "block": int(config["block_length"]),
    }


def held_rows_per_position(config):
    """Assignments a position lands on the experts held here, in
    expectation under a uniform router: experts per token times the share
    held."""
    s = _sizes(config)
    return s["top_k"] * s["held"] / s["router"]


def pairs(config, seq_len):
    """Query-key pairs the block-diffusion mask keeps, a sequence: the
    clean copy's block-causal ``(L^2 + L B) / 2``, the noised rows' clean
    keys of earlier blocks ``(L^2 - L B) / 2`` and their own blocks' ``L
    B``: ``L^2 + L B`` of the ``(2 L)^2``."""
    return seq_len * (seq_len + _sizes(config)["block"])


def train_flops_per_token(config, seq_len):
    """Useful training FLOPs of one CLEAN token: three times the forward
    pass's multiply-adds, twice. Per layer, for the two positions a clean
    token runs as: the four attention projections (q at ``heads`` heads,
    k and v at ``kv_heads``, the output), the router at its full width
    and, in expectation under a uniform router, ``held_rows_per_position``
    of one expert; attention over the pairs the mask keeps (``pairs``),
    scores and values ``head_dim`` wide. Then the head over the
    vocabulary's slice, on the noised copy alone. The per-head norms, the
    sort, recomputation and the optimizer are not counted."""
    s = _sizes(config)
    d, h, hd = s["d"], s["heads"], s["head_dim"]
    per_position = 2 * d * h * hd + 2 * d * s["kv_heads"] * hd \
        + d * s["router"] \
        + held_rows_per_position(config) * 3 * d * s["expert"]
    attention = h * 2 * hd * pairs(config, seq_len) / seq_len
    fwd = s["layers"] * (2 * per_position + attention) + d * s["vocab"]
    return 3.0 * 2.0 * fwd


def kernel_costs(config, traffic):
    """FLOPs and HBM bytes the algorithm needs for ONE call of each
    kernel (one layer, one local step).

    Flash attention over q ``[n, 2 L, heads, head_dim]`` bf16 under the
    block-diffusion mask, ``pairs`` pairs a sequence and head. Forward:
    QK^T and PV, ``2 * pairs * (head_dim + head_dim) * n * heads``
    FLOPs; reads q and the ``kv_heads`` key and value heads, writes o
    (bf16) and the row log-sum-exp (f32). Backward: five products (S
    again, dV, dP, dQ, dK), ``2 * pairs * (3 * head_dim + 2 * head_dim) *
    n * heads``; reads q, k, v, o, dO and the log-sum-exp, writes dq, dk,
    dv. The bytes are the algorithm's: keys and values at their 4 heads,
    although this program repeats them to the query heads first.

    The grouped product of one expert layer, ``rows`` rows in expectation
    under a uniform router (``n * 2 L * held_rows_per_position``), as
    ``deepseek_v3_lm`` counts it."""
    s = _sizes(config)
    n = int(traffic["batch_size"]) * int(traffic["client_chunk"])
    t, h, hd = int(traffic["seq_len"]), s["heads"], s["head_dim"]
    wide = n * 2 * t * h * hd * 2
    narrow = n * 2 * t * s["kv_heads"] * hd * 2
    lse = n * h * 2 * t * 4
    p = pairs(config, t)
    rows = n * 2 * t * held_rows_per_position(config)
    d, width = s["d"], s["expert"]
    gmm_flops = 3 * 2.0 * rows * d * width
    gmm_bytes = 3 * 2.0 * (s["held"] * d * width + rows * (d + width))
    return {
        "flash_fwd": {"flops": 2.0 * p * (hd + hd) * n * h,
                      "bytes": 2.0 * wide + 2.0 * narrow + lse,
                      "bound": "flops"},
        "flash_bwd": {"flops": 2.0 * p * (3 * hd + 2 * hd) * n * h,
                      "bytes": 4.0 * wide + 4.0 * narrow + lse,
                      "bound": "flops"},
        "moe_gmm_fwd": {"flops": gmm_flops, "bytes": gmm_bytes,
                        "bound": "bytes"},
        "moe_gmm_bwd": {"flops": 2.0 * gmm_flops, "bytes": 2.0 * gmm_bytes,
                        "bound": "bytes"},
    }


def build(config, traffic, seed, reference):
    # first thing: a program without this objective fails here, in no time
    from fedml_tpu.algorithms.specs import make_block_diffusion_lm_spec
    from fedml_tpu.models.deepseek_v3 import DecoderConfig, DecoderLM

    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.parallel.packing import packing_backend

    s32 = seed32(seed)
    clients = reference.make_clients(config, traffic, seed)
    ns = [len(c["y"]) for c in clients]
    t = int(traffic["seq_len"])
    model = DecoderLM(
        DecoderConfig.from_dict(config),
        dtype=jnp.dtype(config["as_run"]["compute_dtype"]))
    spec = make_block_diffusion_lm_spec(
        model, jnp.zeros((1, t), jnp.int32), int(config["block_length"]),
        reference.mask_id(config))
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
