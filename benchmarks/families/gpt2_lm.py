"""Family ``gpt2_lm``: GPT-2-shaped decoders through the program's
streamed federated round (``TransformerLM`` + ``FedAvgAPI`` with bucketed
streaming, built the way ``bench.run_lm_bench`` builds it), with the
functions of shapes that its metrics need.

From the program: ``TransformerLM``, ``make_seq_classification_spec``,
``FedAvgAPI`` and the name of the schedule generator it runs
(``packing_backend()``). Data, weights and the feed order come from the
configuration's reference module and ``benchmarks/feed.py``.
"""

from __future__ import annotations

import dataclasses
import types

from benchmarks import feed as feed_rule
from benchmarks.families.common import Cell, nest, seed32


def train_flops_per_token(config, seq_len):
    """Useful training FLOPs of one token: three times the forward pass's
    matmuls (qkv, proj, the two MLP products, the untied head) plus causal
    attention at half of the full square. Padding, recomputation and the
    optimizer are not counted. (``bench._lm_analytic_flops_per_token``
    with ``n_inner`` free.)"""
    d, inner = int(config["n_embd"]), int(config["n_inner"])
    per_layer = 2 * (3 * d * d + d * d + 2 * d * inner) + 2 * seq_len * d
    fwd = int(config["n_layer"]) * per_layer \
        + 2 * d * int(config["vocab_size"])
    return 3.0 * fwd


def kernel_costs(config, traffic):
    """FLOPs and HBM bytes the algorithm needs for ONE call of each flash
    kernel (one layer, one local step): q, k, v of ``[B, T, H, D]`` bf16.
    Forward: QK^T and PV over the causal half, 2*B*H*T^2*D FLOPs; reads
    q, k, v, writes o (bf16) and the row log-sum-exp (f32). Backward: five
    products over the causal half (S again, dV, dP, dQ, dK), 5*B*H*T^2*D;
    reads q, k, v, o, dO and the log-sum-exp, writes dq, dk, dv."""
    b = int(traffic["batch_size"]) * int(traffic["client_chunk"])
    t, h = int(traffic["seq_len"]), int(config["n_head"])
    d = int(config["n_embd"]) // h
    tensor = b * t * h * d * 2
    lse = b * h * t * 4
    return {
        "flash_fwd": {"flops": 2.0 * b * h * t * t * d,
                      "bytes": 4.0 * tensor + lse, "bound": "flops"},
        "flash_bwd": {"flops": 5.0 * b * h * t * t * d,
                      "bytes": 8.0 * tensor + lse, "bound": "flops"},
    }


def _feed(ns, traffic, seed, rounds, backend):
    return feed_rule.streamed(ns, int(traffic["batch_size"]),
                              int(traffic["epochs"]),
                              int(traffic["client_chunk"]), seed, rounds,
                              backend)


def feed_of(config, traffic, seed, rounds, reference, backend="native"):
    """The feed of the first ``rounds`` rounds without building the cell
    (``tools/control.py``: reference against reference, so either rule
    of ``feed.py`` serves)."""
    return _feed(reference.client_sizes(traffic, seed), traffic,
                 seed32(seed), rounds, backend)


def build(config, traffic, seed, reference):
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.parallel.packing import packing_backend

    s32 = seed32(seed)
    clients = reference.make_clients(config, traffic, seed)
    ns = [len(c["y"]) for c in clients]
    t = int(traffic["seq_len"])
    d = int(config["n_embd"])
    if int(config["n_inner"]) % d:
        raise ValueError("TransformerLM takes the MLP width as a whole "
                         "multiple of the hidden size")
    model = TransformerLM(
        vocab_size=int(config["vocab_size"]),
        n_layers=int(config["n_layer"]), n_heads=int(config["n_head"]),
        d_model=d, max_len=int(config["n_positions"]),
        mlp_ratio=int(config["n_inner"]) // d,
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
