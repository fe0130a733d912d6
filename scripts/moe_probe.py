"""What a routed-expert cell's builder reads on the chip beside the
benchmark's line (PERF.md, PR 27). Two parts, each optional:

``--gmm``: the grouped product of one expert layer (gate, up, down with
the gated activation between, forward and both backward products) under
the client-update program's ``jax.vmap`` over one lane, at the cell's
shapes: the sorted buffer's rows (``B*T*top_k``), the rows that land on
held experts under a uniform router, the held experts' matrices. Timed:
``fedml_tpu.ops.grouped_matmul`` (the Pallas megablox kernels at the
module's own tiling), against ``jax.lax.ragged_dot`` with the lane
axis folded away by hand (its batching rule under ``vmap`` of a gradient
is not there in jax 0.9.0). Milliseconds a call, median of ``--reps``.

``--rounds N``: the cell's trainer as the benchmark's family builds it,
driven N rounds: each round's loss (ISSUE 27's rule for the learning
rate reads the first six), seconds, and the routing counters against the
expectation under a uniform router; the peak of device memory.

    python3 scripts/moe_probe.py --workload <cell> --seed <n> --gmm --rounds 6

Prints one JSON object and writes it to ``chiprun_out/moe_probe/<cell>.json``.
"""

import argparse
import importlib
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _timed(fn, args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        a = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - a) * 1e3)
    return statistics.median(out)


def gmm_part(config, traffic, reps, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import deepseek_v3_lm as family
    from fedml_tpu.ops.grouped_matmul import grouped_matmul

    d, width = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    held = int(config["n_routed_experts"])
    tokens = int(traffic["batch_size"]) * int(traffic["seq_len"])
    buffer_rows = tokens * int(config["num_experts_per_tok"])
    rows = int(tokens * family.held_rows_per_token(config))
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(rows, [1.0 / held] * held).astype(np.int32)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    bf = jnp.bfloat16
    x = jax.random.normal(key, (1, buffer_rows, d), bf)
    wg, wu = (0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                       (1, held, d, width), bf)
              for i in (1, 2))
    wd = 0.02 * jax.random.normal(jax.random.fold_in(key, 3),
                                  (1, held, width, d), bf)
    gs = jnp.asarray(sizes)[None]

    def block(product):
        def loss(x, wg, wu, wd, gs):
            h = jax.nn.silu(product(x, wg, gs)) * product(x, wu, gs)
            return jnp.sum(product(h, wd, gs).astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2, 3))

    out = {"buffer_rows": buffer_rows, "rows_held": rows,
           "group_sizes": sizes.tolist(),
           "megablox_ms": _timed(jax.jit(jax.vmap(block(grouped_matmul))),
                                 (x, wg, wu, wd, gs), reps)}
    ragged = block(lambda a, b, g: jax.lax.ragged_dot(
        a, b, g, preferred_element_type=a.dtype))
    fn = jax.jit(lambda *a: ragged(*(v[0] for v in a)))
    try:
        out["ragged_dot_ms"] = _timed(fn, (x, wg, wu, wd, gs), reps)
    except Exception as exc:
        out["ragged_dot_ms"] = repr(exc)[:200]
    return out


def rounds_part(man, workload, config, traffic, seed, rounds):
    import jax

    family = importlib.import_module("benchmarks.families."
                                     + config["family"])
    cell = family.build(config, traffic, seed, man.reference(config))
    # whatever the family: the rows of one layer-step are what its
    # grouped products' FLOPs were counted for (three products of
    # 2 * rows * d * width), a round has the clients' steps, and the
    # leading dense layers route nothing
    rows = cell.shapes["kernels"]["moe_gmm_fwd"]["flops"] / (
        6.0 * int(config["hidden_size"])
        * int(config["moe_intermediate_size"]))
    steps = sum(-(-n // int(traffic["batch_size"])) for n in cell.ns) \
        * int(traffic["epochs"])
    layers = int(config.get("n_layer", config["num_hidden_layers"])) \
        - int(config.get("first_k_dense_replace",
                         config.get("num_dense_layers", 0)))
    expected = rows * steps * layers
    out = {"expected_rows_held": expected, "rounds": []}
    for _ in range(rounds):
        a = time.perf_counter()
        m = cell.api.train_one_round()
        out["rounds"].append({
            "loss": float(m["Train/Loss"]),
            "seconds": time.perf_counter() - a,
            **{k: float(v) for k, v in m.items() if k.startswith("moe_")},
            "rows_held_over_expected":
                float(m.get("moe_rows_held", 0.0)) / expected})
    out["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="kanana2-a3b-ep8-silo2-long")
    ap.add_argument("--seed", type=int, default=2700000001)
    ap.add_argument("--gmm", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=0)
    ap.add_argument("--cpu_root", default=None,
                    help="a toy checkout's root (benchmarks/tests/"
                         "toyroot.py): a rehearsal of the script itself on "
                         "the CPU, not a reading")
    args = ap.parse_args(argv)
    from benchmarks import harness
    from benchmarks.manifest import Manifest

    import jax

    man = Manifest(args.cpu_root) if args.cpu_root else Manifest()
    entry = man.cell(args.workload)
    config = man.config(entry["config"])
    traffic = man.traffic(entry["traffic"])
    harness.cache_dir(ROOT)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_root:
        print(f"moe_probe: needs a TPU, JAX reports {dev.platform}",
              file=sys.stderr)
        return 3
    result = {"workload": args.workload, "seed": args.seed,
              "device": {"platform": dev.platform, "kind": dev.device_kind}}
    if args.gmm:
        result["gmm"] = gmm_part(config, traffic, args.reps, args.seed)
    if args.rounds:
        result["rounds"] = rounds_part(man, args.workload, config, traffic,
                                       args.seed, args.rounds)
    out_dir = os.path.join(ROOT, "chiprun_out", "moe_probe")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
