"""Family ``lfm2_moe_lm``: decoders of the ``lfm2_moe`` family (a token
mixer a layer by ``layer_types``: gated short convolutions beside
grouped-query attention; a dense FFN in the leading layers, then
sigmoid-routed experts of which the configuration's share is held here)
through the program's streamed federated round, built the way
``deepseek_v3_lm`` builds its own, with the functions of shapes that its
metrics need.

From the program: ``gated_short_conv`` (imported first: a program without
it fails before any data is made), ``DecoderLM`` with its
``DecoderConfig``, ``make_seq_classification_spec``, ``FedAvgAPI`` and the
name of the schedule generator it runs (``packing_backend()``). Data,
weights and the feed order come from the configuration's reference module
and ``benchmarks/feed.py``.
"""

from __future__ import annotations

import dataclasses
import types

from benchmarks.families.common import Cell, nest, seed32
# the streamed feed rule is the trainer's, whatever the model
from benchmarks.families.gpt2_lm import _feed, feed_of  # noqa: F401


def _sizes(config):
    router = int(config.get("router_experts", config["num_experts"]))
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    layers = int(config.get("n_layer", config["num_hidden_layers"]))
    types_ = list(config.get("layer_types_as_run", config["layer_types"]))
    if len(types_) != layers:
        raise ValueError(f"{len(types_)} layer types for {layers} layers")
    return {
        "d": d, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // heads),
        "taps": int(config["conv_L_cache"]),
        "dense": int(config["intermediate_size"]),
        "expert": int(config["moe_intermediate_size"]),
        "held": int(config.get("n_routed_experts", router)),
        "router": router,
        "top_k": int(config["num_experts_per_tok"]),
        "lead": int(config["num_dense_layers"]),
        "types": types_,
        "vocab": int(config["vocab_size"]),
    }


def held_rows_per_token(config):
    """Assignments a token lands on the experts held here, in expectation
    under a uniform router: experts per token times the share held."""
    s = _sizes(config)
    return s["top_k"] * s["held"] / s["router"]


def pairs(seq_len):
    """Query-key pairs the causal mask keeps, a sequence and head."""
    return seq_len * (seq_len + 1) / 2


def train_flops_per_token(config, seq_len):
    """Useful training FLOPs of one token: three times the forward pass's
    multiply-adds, twice. Per layer BY ITS TYPE: a ``conv`` mixer's
    in-projection ``d x 3 d``, out-projection ``d x d`` and ``taps``
    multiply-adds a channel; a ``full_attention`` mixer's four
    projections (q at ``heads`` heads, k and v at ``kv_heads``, the
    output) and the causal ``T (T + 1) / 2`` pairs a head, scores and
    values ``head_dim`` wide; then the dense FFN in the leading layers
    or, in the others, the router at its full width and, in expectation
    under a uniform router, ``held_rows_per_token`` of one expert. Then
    the head over the vocabulary's slice. The gates, the norms, the sort,
    recomputation and the optimizer are not counted."""
    s = _sizes(config)
    d, h, hd = s["d"], s["heads"], s["head_dim"]
    mixer = {
        "conv": d * 3 * d + d * d + s["taps"] * d,
        "full_attention": 2 * d * h * hd + 2 * d * s["kv_heads"] * hd
        + h * 2 * hd * pairs(seq_len) / seq_len}
    dense = 3 * d * s["dense"]
    sparse = d * s["router"] \
        + held_rows_per_token(config) * 3 * d * s["expert"]
    fwd = sum(mixer[kind] + (dense if i < s["lead"] else sparse)
              for i, kind in enumerate(s["types"])) + d * s["vocab"]
    return 3.0 * 2.0 * fwd


def kernel_costs(config, traffic):
    """FLOPs and HBM bytes the algorithm needs for ONE call of each
    kernel (one layer, one local step); never what a padded
    implementation does.

    Flash attention over q ``[n, T, heads, head_dim]`` bf16, causal,
    ``pairs`` pairs a sequence and head. Forward: QK^T and PV, ``2 *
    pairs * (head_dim + head_dim) * n * heads`` FLOPs; reads q and the
    ``kv_heads`` key and value heads, writes o (bf16) and the row
    log-sum-exp (f32). Backward: five products (S again, dV, dP, dQ,
    dK), ``2 * pairs * (3 * head_dim + 2 * head_dim) * n * heads``; reads
    q, k, v, o, dO and the log-sum-exp, writes dq, dk, dv (keys and
    values at their own 8 heads).

    The grouped product of one expert layer, ``rows`` rows in expectation
    under a uniform router (``n * T * held_rows_per_token``), as
    ``deepseek_v3_lm`` counts it.

    The gated short convolution over ``[n, T, 3 d]`` bf16: forward ``B *
    u``, ``taps`` multiply-adds and the gate a channel and position:
    ``(2 taps + 1) n T d`` FLOPs (7 at 3 taps). Backward: v and z again
    (``2 taps``), the two gates' products (2), the filter run backwards
    (``2 taps - 1``), the gradients of B and u (2) and the filter's sums
    (``2 taps``): ``(6 taps + 3) n T d``. Their arrays are ``8 n T d``
    bytes forward (three thirds in, y out) and ``14 n T d + 4 d taps``
    backward (the thirds and dy in, three gradients out, the filter's
    float32 sums): byte-bound, 0.082 and 0.143 ms a call at the chip's
    pace. NO metric of the benchmark reads these two entries yet: in the
    cell XLA keeps some of the kernels' arrays in VMEM between them and
    the neighbouring programs (the forward's y, two of the backward's
    three gradients: ``S(1)`` on their layouts in the compiled program,
    PERF.md section 6), so an event is shorter than its arrays' HBM time
    (the first traced run read 117 % by these bytes) and a share of the
    HBM pace would not be a share of a bound; ``conv.busy_ms`` reads the
    events' time, and ``scripts/short_conv_probe.py`` the byte share on
    operands that lie in HBM. The counts stand here, checked by hand in
    ``benchmarks/tests``, for the roofline a ``benchmark`` PR can state
    once ``peaks.py`` has a peak these kernels can be held to."""
    s = _sizes(config)
    n = int(traffic["batch_size"]) * int(traffic["client_chunk"])
    t, h, hd = int(traffic["seq_len"]), s["heads"], s["head_dim"]
    wide = n * t * h * hd * 2
    narrow = n * t * s["kv_heads"] * hd * 2
    lse = n * h * t * 4
    p = pairs(t)
    rows = n * t * held_rows_per_token(config)
    d, width, taps = s["d"], s["expert"], s["taps"]
    gmm_flops = 3 * 2.0 * rows * d * width
    gmm_bytes = 3 * 2.0 * (s["held"] * d * width + rows * (d + width))
    ntd = float(n * t * d)
    return {
        "flash_fwd": {"flops": 2.0 * p * (hd + hd) * n * h,
                      "bytes": 2.0 * wide + 2.0 * narrow + lse,
                      "bound": "flops"},
        "flash_bwd": {"flops": 2.0 * p * (3 * hd + 2 * hd) * n * h,
                      "bytes": 4.0 * wide + 4.0 * narrow + lse,
                      "bound": "flops"},
        # 512 rows an expert of 2048 x 1792: FLOP-bound here (0.46 ms
        # against 0.33 ms of bytes forward), where kanana2's and sdar's
        # 192 and 256 rows of 768-wide experts are byte-bound; the reader
        # takes the larger of the two whatever this says
        "moe_gmm_fwd": {"flops": gmm_flops, "bytes": gmm_bytes,
                        "bound": "flops"},
        "moe_gmm_bwd": {"flops": 2.0 * gmm_flops, "bytes": 2.0 * gmm_bytes,
                        "bound": "flops"},
        "short_conv_fwd": {"flops": (2 * taps + 1) * ntd,
                           "bytes": 8.0 * ntd, "bound": "bytes"},
        "short_conv_bwd": {"flops": (6 * taps + 3) * ntd,
                           "bytes": 14.0 * ntd + 4.0 * d * taps,
                           "bound": "bytes"},
    }


def build(config, traffic, seed, reference):
    # first thing: a program without this operator fails here, in no time
    from fedml_tpu.ops.short_conv import gated_short_conv  # noqa: F401
    from fedml_tpu.models.deepseek_v3 import DecoderConfig, DecoderLM

    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.parallel.packing import packing_backend

    s32 = seed32(seed)
    clients = reference.make_clients(config, traffic, seed)
    ns = [len(c["y"]) for c in clients]
    t = int(traffic["seq_len"])
    model = DecoderLM(
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
