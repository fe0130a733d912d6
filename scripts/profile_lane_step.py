"""Measured time breakdown of one flagship lane step (VERDICT r3 next #1).

Round 3 measured 2.76 ms per 64-sample step-batch (8 vmapped lanes = 512
samples per device step at ~22 ms) = 8.9% MFU, with no evidence of where
the other ~91% goes. This script produces that breakdown as targeted
ablation microbenchmarks at the bench's exact shapes, answering:

  A. conv ceiling      -- ONE model, batch 512, plain train step: the best
                          ResNet-56/CIFAR can do on this chip (shape-bound
                          MXU underfill included).
  B. lane penalty      -- 8 vmapped models (distinct params), batch 64
                          each: what per-lane weights cost (XLA lowers the
                          batched-weight conv as grouped/batched convs).
  C. + augment         -- B plus the recipe's crop/flip/Cutout.
  D. + optimizer/flush -- the full lane-body step: SGD update, carry
                          select, payload accumulate (engine fori_loop
                          body semantics inline).
  E. no-BN variant of A -- batch-norm's share of the ceiling.

Timing: value-fetch (jnp.sum -> float): every timed call materializes a
scalar on host, which waits for the device like ``block_until_ready``.

A single dispatch+fetch costs about 0.9 ms on the TPU v5e host
(``R_dispatch_floor`` re-measures it every run) -- comparable to one
device step -- so single-step calls are biased by it. ``--inner N``
chains N steps inside ONE jitted call via
``lax.fori_loop`` (the carry perturbs the params tree by acc*1e-30 so
XLA cannot hoist the loop-invariant body) and divides by N; the
``R_dispatch_floor`` row reports the raw per-call RPC cost so the
residual bias (floor/N per row) is visible.

Usage: python scripts/profile_lane_step.py [--repeats 20] [--inner 50]
       [--cpu --tiny]
Prints one json line per ablation + a derived breakdown table.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESNET56_TRAIN_FLOPS = 3 * 2 * 125.75e6  # per sample (bench.py derivation)


def timed_interleaved(cases, repeats, warmup=2):
    """Median seconds per call for every case, with the repeats of ALL
    cases interleaved round-robin: the derived breakdown is a chain of
    subtractions (B-A, C-B, D-C), so slow drift (thermal state, host
    load) must bias every ablation equally rather than whichever
    happened to run last -- back-to-back blocks made the subtraction
    occasionally NEGATIVE on noisy hosts. Each call is forced by a host
    scalar fetch."""
    for fn, args_ in cases.values():  # compile + warm everything first
        for _ in range(warmup):
            float(fn(*args_))
    ts = {name: [] for name in cases}
    for _ in range(repeats):
        for name, (fn, args_) in cases.items():
            t0 = time.perf_counter()
            float(fn(*args_))
            ts[name].append(time.perf_counter() - t0)
    out = {}
    for name, v in ts.items():
        v.sort()
        out[name] = v[len(v) // 2]
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--repeats", type=int, default=20)
    p.add_argument("--inner", type=int, default=1,
                   help="steps chained inside one jitted call (amortizes "
                        "the per-dispatch RPC floor; reported times are "
                        "divided by this)")
    p.add_argument("--lanes", type=int, default=8)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--cpu", action="store_true",
                   help="force the host platform (sanity runs)")
    p.add_argument("--tiny", action="store_true",
                   help="8x8 images, 2 lanes (CPU sanity shapes)")
    p.add_argument("--fp32", action="store_true")
    args = p.parse_args()
    if args.inner < 1:
        p.error("--inner must be >= 1")
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from fedml_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu import models
    from fedml_tpu.data.augment import make_cifar_augment

    if args.tiny:
        args.lanes, image = 2, 8
    else:
        image = 32
    L, B = args.lanes, args.batch
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    dev = jax.devices()[0]
    print(f"# device={dev} kind={getattr(dev, 'device_kind', '?')} "
          f"lanes={L} batch={B} image={image} dtype={dtype.__name__}",
          file=sys.stderr)

    model = models.resnet56(class_num=10, dtype=dtype)
    rng = jax.random.PRNGKey(0)
    vs = model.init(rng, jnp.zeros((1, image, image, 3)))
    params, batch_stats = vs["params"], vs.get("batch_stats", {})
    opt = optax.chain(optax.add_decayed_weights(1e-3), optax.sgd(1e-3))

    def loss_one(p, bs, x, y):
        out, mut = model.apply({"params": p, "batch_stats": bs}, x,
                               train=True, mutable=["batch_stats"])
        logits = out.astype(jnp.float32)
        l = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return l, mut["batch_stats"]

    kx = jax.random.split(rng, 4)
    x_big = jax.random.normal(kx[0], (L * B, image, image, 3), jnp.float32)
    y_big = jax.random.randint(kx[1], (L * B,), 0, 10)
    x_lane = x_big.reshape(L, B, image, image, 3)
    y_lane = y_big.reshape(L, B)
    lane_params = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), params)
    lane_stats = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), batch_stats)

    cases = {}
    flops_step = L * B * RESNET56_TRAIN_FLOPS * (image / 32) ** 2

    # --- A: one model, batch L*B (the conv ceiling) ---------------------
    def step_A(p, bs, x, y):
        (l, _), g = jax.value_and_grad(loss_one, has_aux=True)(p, bs, x, y)
        return l + 1e-30 * sum(jnp.sum(t.astype(jnp.float32))
                           for t in jax.tree.leaves(g))

    cases["A_one_model_bs512"] = (step_A, (params, batch_stats, x_big, y_big))

    # --- B: L vmapped models, per-lane weights (the lane penalty) -------
    def step_B(ps, bss, x, y):
        def one(p, bs, xx, yy):
            (l, _), g = jax.value_and_grad(loss_one, has_aux=True)(
                p, bs, xx, yy)
            return l + 1e-30 * sum(jnp.sum(t.astype(jnp.float32))
                           for t in jax.tree.leaves(g))
        return jnp.sum(jax.vmap(one)(ps, bss, x, y))

    cases["B_vmap_lanes"] = (step_B, (lane_params, lane_stats, x_lane, y_lane))

    # --- B2: MXU-packed lanes (lane axis folded into channels) ----------
    # the round-5 lowering fix (models/lane_packed.py): same computation
    # as B with per-group conv K raised to 128; B/B2 is the measured
    # value of the relayout
    from fedml_tpu.models.lane_packed import make_lane_packed_apply
    packed_apply = make_lane_packed_apply(model, L)

    def loss_packed(ps, bss, x, y):
        logits, new_bs = packed_apply({"params": ps, "batch_stats": bss},
                                      x, train=True)
        l = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32).reshape(L * B, -1),
            y.reshape(-1)).mean()
        return l, new_bs

    def step_B2(ps, bss, x, y):
        (l, _), g = jax.value_and_grad(loss_packed, has_aux=True)(
            ps, bss, x, y)
        return l + 1e-30 * sum(jnp.sum(t.astype(jnp.float32))
                               for t in jax.tree.leaves(g))

    cases["B2_packed_lanes"] = (step_B2,
                                (lane_params, lane_stats, x_lane, y_lane))

    # --- C: B + the recipe's augmentation -------------------------------
    augment = make_cifar_augment(pad=4 if image >= 32 else 2,
                                 cutout_length=16 if image >= 32 else 4)

    def step_C(ps, bss, x, y, key):
        def one(p, bs, xx, yy, k):
            xx = augment(xx, k)
            (l, _), g = jax.value_and_grad(loss_one, has_aux=True)(
                p, bs, xx, yy)
            return l + 1e-30 * sum(jnp.sum(t.astype(jnp.float32))
                           for t in jax.tree.leaves(g))
        return jnp.sum(jax.vmap(one)(ps, bss, x, y,
                                     jax.random.split(key, L)))

    cases["C_plus_augment"] = (
        step_C, (lane_params, lane_stats, x_lane, y_lane, kx[2]))

    # --- D: the full engine lane-body semantics -------------------------
    # optimizer update + valid-select over (params, stats, opt) + payload
    # accumulate + flush-select back to global -- inline replica of
    # parallel/engine.py make_lane_update's per-step work
    opt_state0 = jax.vmap(lambda p: opt.init(p))(lane_params)
    pay0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                        lane_params)

    def step_D(ps, bss, opt_states, pay, x, y, key):
        def one(p, bs, os_, pa, xx, yy, k):
            xx = augment(xx, k)
            (l, (nbs)), g = jax.value_and_grad(loss_one, has_aux=True)(
                p, bs, xx, yy)
            up, nos = opt.update(g, os_, p)
            np_ = optax.apply_updates(p, up)
            valid = jnp.sum(yy) >= 0
            sel = lambda a, b: jax.tree.map(
                lambda u, v: jnp.where(valid, u, v), a, b)
            np_, nbs, nos = sel((np_, nbs, nos), (p, bs, os_))
            f = (jnp.sum(yy) % 7 == 0).astype(jnp.float32)  # flush gate
            pa = jax.tree.map(lambda acc, w: acc + f * w.astype(jnp.float32),
                              pa, np_)
            return l, (np_, nbs, nos, pa)

        ls, state = jax.vmap(one)(ps, bss, opt_states, pay, x, y,
                                  jax.random.split(key, L))
        # fold every state output into the fetched scalar: discarded
        # outputs would let XLA dead-code-eliminate the optimizer/select/
        # flush work this ablation exists to measure
        keep = sum(jnp.sum(t.astype(jnp.float32))
                   for t in jax.tree.leaves(state))
        return jnp.sum(ls) + 1e-30 * keep

    cases["D_full_lane_body"] = (
        step_D, (lane_params, lane_stats, opt_state0, pay0, x_lane, y_lane,
                 kx[3]))

    # --- E: A with BN on running stats (no batch reductions) ------------
    # isolates the batch-statistics part of BatchNorm: convs identical,
    # normalization becomes a per-channel scale/shift from stored stats
    def loss_eval_bn(p, x, y):
        logits = model.apply({"params": p, "batch_stats": batch_stats}, x,
                             train=False).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def step_E(p, x, y):
        l, g = jax.value_and_grad(loss_eval_bn)(p, x, y)
        return l + 1e-30 * sum(jnp.sum(t.astype(jnp.float32))
                           for t in jax.tree.leaves(g))

    cases["E_one_model_frozen_bn"] = (step_E, (params, x_big, y_big))

    def finalize(fn):
        """jit the case; with --inner N, chain N steps in one call via
        fori_loop. The carry (accumulated loss scalar) perturbs the
        params tree by acc*1e-30 each iteration, making the body
        carry-dependent so XLA's LICM cannot hoist it out of the loop;
        the perturbation itself is numerically irrelevant and costs one
        elementwise add per leaf."""
        if args.inner == 1:
            return jax.jit(fn)

        def run(p0, *rest):
            def body(_, acc):
                p = jax.tree.map(
                    lambda t: t + jnp.asarray(acc, t.dtype) *
                    jnp.asarray(1e-30, t.dtype), p0)
                return acc + fn(p, *rest).astype(jnp.float32)
            return jax.lax.fori_loop(0, args.inner, body, jnp.float32(0.0))
        return jax.jit(run)

    cases = {name: (finalize(fn), args_)
             for name, (fn, args_) in cases.items()}

    # R: what one dispatch+fetch costs with ~zero device work -- the
    # floor every row above amortizes over --inner steps. Always a SINGLE
    # call (never looped); its raw per-call time is the bias bound
    # floor/N per row.
    r_x = jnp.ones((8,), jnp.float32)
    cases["R_dispatch_floor"] = (jax.jit(lambda v: jnp.sum(v)), (r_x,))

    results = timed_interleaved(cases, args.repeats)
    rtt = results.pop("R_dispatch_floor")
    results = {k: v / args.inner for k, v in results.items()}

    from bench import mfu_fields, peak_flops  # single source for peaks
    peak = None if args.cpu else peak_flops(dev)
    out = {}
    for name, sec in results.items():
        out[name] = {"s": round(sec, 5),
                     "tflops": round(flops_step / sec / 1e12, 2),
                     "mfu": mfu_fields(flops_step / sec, peak)["mfu"]}
        print(json.dumps({name: out[name]}), flush=True)
    print(json.dumps({"R_dispatch_floor": {
        "s_per_call": round(rtt, 5), "inner": args.inner,
        "per_row_bias_ms": round(rtt / args.inner * 1e3, 3)}}), flush=True)

    a, b = results["A_one_model_bs512"], results["B_vmap_lanes"]
    c, d = results["C_plus_augment"], results["D_full_lane_body"]
    b2 = results["B2_packed_lanes"]
    breakdown = {
        "conv_ceiling_ms": round(a * 1e3, 3),
        "lane_penalty_ms": round((b - a) * 1e3, 3),
        "augment_ms": round((c - b) * 1e3, 3),
        "opt_flush_ms": round((d - c) * 1e3, 3),
        "lane_penalty_x": round(b / a, 2),
        "packed_lanes_ms": round(b2 * 1e3, 3),
        "packed_speedup_x": round(b / b2, 2),
    }
    # a negative component means the ablation chain INVERTED (a later,
    # strictly-more-work step timed faster than its predecessor) -- that
    # is measurement noise, not a negative cost, and must not read as a
    # breakdown row. Flag it instead of printing nonsense silently.
    inversions = [k for k in ("lane_penalty_ms", "augment_ms",
                              "opt_flush_ms") if breakdown[k] < 0]
    if inversions:
        breakdown["inversions"] = inversions
        print(f"# WARNING: breakdown inversion on {inversions} -- medians "
              "within noise despite interleaved repeats; treat those "
              "components as ~0, or rerun with a larger --repeats",
              file=sys.stderr)
    print(json.dumps({"breakdown": breakdown}), flush=True)


if __name__ == "__main__":
    main()
