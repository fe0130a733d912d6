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

``--dispatch`` (PR 35): one expert layer (``RoutedExperts`` at the
cell's widths, a router of fresh weights, bf16) forward and backward
under a lane ``vmap`` that names its axis, as the client-update program
runs it: the whole ``tokens x top-k`` buffer (the parent's form) against
the compact buffer of ``buffer_capacity`` rows in each candidate form of
the sum back to the tokens (``_sum_rows``: a scatter-add of the buffer's
rows, a gather a slot summed over the slots as one array and slot by
slot, ``top-k`` scatters at unique indices); and the index operations
alone at the same shapes. Each timed program makes eight calls in a
row, each reading the one before, so that a dispatch's half millisecond
is an eighth of itself a call. Last, the fallback on this device: the
layer under a router that sends every token to held experts, against
the whole buffer's gradient for the same tokens.

``--rounds N``: the cell's trainer as the benchmark's family builds it,
driven N rounds: each round's loss (ISSUE 27's rule for the learning
rate reads the first six), seconds, and the routing counters against the
expectation under a uniform router; the peak of device memory.

    python3 scripts/moe_probe.py --workload <cell> --seed <n> --gmm --rounds 6
    python3 scripts/moe_probe.py --workload <cell> --dispatch

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


def _where(idx, rows, slots):
    """``[rows, slots]`` buffer rows that read each of ``rows`` rows (an
    entry of ``len(idx)``: none), from ``idx`` alone: what the module's
    ``inverse`` says, derived here because a form of ``_sum_rows`` is
    given ``(z, idx, rows)`` and nothing else (a sort of ``len(idx)``
    integers inside the timed region: tens of microseconds)."""
    import jax.numpy as jnp

    by_row = jnp.argsort(idx, stable=True)
    sorted_idx = idx[by_row]
    rank = jnp.arange(idx.shape[0]) - jnp.searchsorted(
        sorted_idx, sorted_idx, side="left")
    return jnp.full((rows, slots), idx.shape[0], jnp.int32).at[
        sorted_idx, rank].set(by_row.astype(jnp.int32), mode="drop")


def sum_forms(slots):
    """The candidate forms of ``deepseek_v3._sum_rows(z, idx, rows)``
    beside the shipped one (a scatter-add of the buffer's rows)."""
    import jax.numpy as jnp

    def gather_sum(z, idx, rows):
        """A gather a slot, summed over the slots."""
        picked = z.at[_where(idx, rows, slots)].get(mode="fill",
                                                    fill_value=0)
        return jnp.sum(picked, axis=1)

    def gathers_summed(z, idx, rows):
        """The same, slot by slot: ``slots`` gathers of ``rows`` rows."""
        where = _where(idx, rows, slots)
        return sum(z.at[where[:, j]].get(mode="fill", fill_value=0)
                   for j in range(slots))

    def unique_scatters(z, idx, rows):
        """One scatter-add a slot over the buffer's rows of that slot,
        at unique indices."""
        where = _where(idx, rows, slots)
        slot = jnp.zeros((z.shape[0] + 1,), jnp.int32).at[where].set(
            jnp.broadcast_to(jnp.arange(slots), where.shape))[:-1]
        out = jnp.zeros((rows,) + z.shape[1:], z.dtype)
        for j in range(slots):
            out = out.at[jnp.where(slot == j, idx, rows)].add(
                z, mode="drop", unique_indices=True)
        return out

    return {"gather_sum": gather_sum, "gathers_summed": gathers_summed,
            "unique_scatters": unique_scatters}


def dispatch_part(config, traffic, reps, seed):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import deepseek_v3 as dsv3
    from fedml_tpu.parallel.mesh import LANE_AXIS

    cfg = dsv3.DecoderConfig.from_dict(config)
    # positions of a step: block diffusion runs a noised copy beside the
    # clean one
    tokens = int(traffic["batch_size"]) * int(traffic["seq_len"]) \
        * (2 if cfg.block_length else 1)
    d, k = cfg.hidden_size, cfg.num_experts_per_tok
    module = dsv3.RoutedExperts(cfg, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    x = jax.random.normal(key, (1, tokens, d), jnp.bfloat16)
    params = jax.vmap(lambda x: module.init(jax.random.fold_in(key, 1),
                                            x)["params"])(x)
    chain = 8   # calls a timed program makes, each reading the last one's

    def loss(params, x):
        out, sown = module.apply({"params": params}, x, mutable=["metrics"])
        return jnp.sum(out.astype(jnp.float32)
                       * jnp.cos(jnp.arange(d, dtype=jnp.float32))), \
            sown["metrics"]

    grad = jax.vmap(jax.grad(loss, argnums=(0, 1), has_aux=True),
                    axis_name=LANE_AXIS)

    def layer_ms():   # traced anew under each form
        jax.clear_caches()
        step = lambda _, x: x + (1e-9 * grad(params, x)[0][1]).astype(x.dtype)
        fn = jax.jit(lambda x: jax.lax.fori_loop(0, chain, step, x))
        return _timed(fn, (x,), reps) / chain

    def op_ms(op, out_shape, *args):
        step = lambda _, acc: acc + op(acc[0, 0], *args).astype(jnp.float32)
        fn = jax.jit(lambda: jax.lax.fori_loop(
            0, chain, step, jnp.zeros(out_shape, jnp.float32)))
        return _timed(fn, (), reps) / chain

    cap = dsv3.buffer_capacity(tokens * k, cfg.held[1], cfg.router_width)
    sown = jax.jit(grad)(params, x)[1]
    out = {"tokens": tokens, "assignments": tokens * k, "capacity": cap,
           "rows_held": float(sown["moe_rows_held"][0]),
           "overflow": float(sown["moe_overflow"][0]), "layer_ms": {},
           "op_ms": {}}
    forms = {"scatter_add": dsv3._sum_rows, **sum_forms(k)}
    shipped, block = dsv3._sum_rows, dsv3._CAPACITY_BLOCK
    try:
        dsv3._CAPACITY_BLOCK = 1 << 30          # every assignment a row
        out["layer_ms"]["whole_buffer"] = layer_ms()
        dsv3._CAPACITY_BLOCK = block
        for name, form in forms.items():
            dsv3._sum_rows = form
            out["layer_ms"]["compact." + name] = layer_ms()
    finally:
        dsv3._sum_rows, dsv3._CAPACITY_BLOCK = shipped, block
        jax.clear_caches()
    # the fallback on this device: a router that sends every token to
    # held experts (one constant column of the tokens, read by the held
    # experts' router weights) overflows the buffer; what the layer then
    # gives against the whole buffer's result for the same tokens
    crowd = jax.tree.map(lambda a: a, params)
    crowd["router"]["kernel"] = params["router"]["kernel"].at[
        :, 0, cfg.held[0]:cfg.held[0] + cfg.held[1]].set(4.0)
    x_crowd = x.at[:, :, 0].set(4.0)
    (_, gx), sown = jax.jit(grad)(crowd, x_crowd)
    try:
        dsv3._CAPACITY_BLOCK = 1 << 30
        jax.clear_caches()
        (_, gx_whole), sown_whole = jax.jit(grad)(crowd, x_crowd)
    finally:
        dsv3._CAPACITY_BLOCK = block
        jax.clear_caches()
    gap = jnp.abs(gx.astype(jnp.float32) - gx_whole.astype(jnp.float32))
    out["fallback"] = {
        "overflow": float(sown["moe_overflow"][0]),
        "rows_held": float(sown["moe_rows_held"][0]),
        "dropped": float(sown["moe_dropped"][0]),
        "whole_buffer_rows_held": float(sown_whole["moe_rows_held"][0]),
        "dx_max_gap": float(gap.max()),
        "dx_max": float(jnp.abs(gx_whole.astype(jnp.float32)).max())}
    # the index operations alone, under an even router's relation: the
    # buffer's rows from the tokens (the whole buffer's gather beside
    # it), and each form of the sum back
    held = jax.random.uniform(key, (tokens * k,)) < cap / (2 * tokens * k)
    order = jnp.argsort(~held, stable=True)
    token = order[:cap] // k
    z = jax.random.normal(key, (cap, d), jnp.float32)
    out["op_ms"]["take_rows"] = op_ms(
        lambda s, x, t: (x + s.astype(x.dtype))[t], (cap, d), x[0], token)
    out["op_ms"]["whole_buffer_gather"] = op_ms(
        lambda s, x, o: jnp.repeat(x + s.astype(x.dtype), k, axis=0)[o],
        (tokens * k, d), x[0], order)
    for name, form in forms.items():
        out["op_ms"][name] = op_ms(
            lambda s, z, t, form=form: form(z + s, t, tokens), (tokens, d),
            z, token)
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
    ap.add_argument("--dispatch", action="store_true")
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
    if args.dispatch:
        result["dispatch"] = dispatch_part(config, traffic, args.reps,
                                           args.seed)
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
