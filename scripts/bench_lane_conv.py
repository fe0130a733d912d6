"""Per-lane conv lowering shoot-out at flagship shapes (VERDICT r4 next #3).

The r5 A-E breakdown measured the lane penalty (per-client weights vs one
shared model) at 2.19x, with the block-diagonal MXU-packed lowering
(``models/lane_packed.py``) recovering 1.4x of it (B2 = 15.36 ms vs
B = 21.49 ms vs A = 9.83 ms per 8x64 samples). This script measures the
remaining candidates per stage, at the exact ResNet-56/CIFAR bench
shapes, fwd and fwd+bwd:

  vmap        jax.vmap over lane-stacked weights (XLA grouped-conv
              lowering) -- ablation B's per-layer form.
  packed      block-diagonal lane merge to K=128 tiles (current B2;
              g = 128//Ci lanes per group, g x FLOP redundancy).
  packed_all  merge ALL lanes into one dense conv (G=1, L x redundancy;
              tests whether killing the group loop beats the FLOPs).
  bgc         ``batch_group_count=L`` conv: lanes ride the batch-group
              axis, per-lane weights in feature groups -- ZERO FLOP
              redundancy, but the TPU emitter chooses the loop.
  im2col      manual patch extraction + lane-batched ``dot_general``
              ([L, B*H*W, k*k*Ci] x [L, k*k*Ci, Co]): forces the
              matmul form XLA uses for dW, N=Co underfilled.
  shared      ONE weight set over the merged batch (the per-layer slice
              of ablation A): the no-lane-penalty floor for the layer.

All stride-1 3x3 convs with Ci==Co (the 52 of 55 convs that carry the
flagship's FLOPs); a winning candidate gets strided/1x1 support inside
``lane_conv`` afterwards.

Timing: ``--inner N`` chains N applications inside one jitted
``lax.fori_loop`` (self-feeding carry; one dispatch+fetch costs about
0.9 ms on the TPU v5e host, above a single conv) and every timed call
fetches a scalar to host, which waits for the device like
``block_until_ready`` does.

Usage: python scripts/bench_lane_conv.py [--inner 20] [--repeats 8]
       [--cpu --tiny]   # CI smoke
Prints one JSON line per (stage, candidate, pass) + a summary table.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_candidates(L):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.lane_packed import lane_conv, lane_merge, lane_unmerge

    dn = ("NHWC", "HWIO", "NHWC")
    pad = ((1, 1), (1, 1))

    def vmap_conv(x, w):
        return jax.vmap(lambda xi, wi: jax.lax.conv_general_dilated(
            xi, wi, (1, 1), pad, dimension_numbers=dn))(x, w)

    def packed(x, w):
        y = lane_conv(lane_merge(x), w, L)
        return lane_unmerge(y, L)

    def packed_all(x, w):
        y = lane_conv(lane_merge(x), w, L, min_k=10 ** 9)  # g=L, dense
        return lane_unmerge(y, L)

    def bgc(x, w):
        _, B, H, W, ci = x.shape
        co = w.shape[-1]
        lhs = x.reshape(L * B, H, W, ci)
        rhs = jnp.transpose(w, (1, 2, 3, 0, 4)).reshape(3, 3, ci, L * co)
        y = jax.lax.conv_general_dilated(
            lhs, rhs, (1, 1), pad, dimension_numbers=dn,
            batch_group_count=L)
        return jnp.transpose(
            y.reshape(B, H, W, L, co), (3, 0, 1, 2, 4))

    def im2col(x, w):
        _, B, H, W, ci = x.shape
        co = w.shape[-1]
        xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1), (0, 0)))
        # patches [L, B, H, W, 3, 3, Ci] via static slices (XLA fuses)
        rows = [xp[:, :, dh:dh + H, dw_:dw_ + W, :]
                for dh in range(3) for dw_ in range(3)]
        patches = jnp.stack(rows, axis=-2)  # [L,B,H,W,9,Ci]
        pk = patches.reshape(L, B * H * W, 9 * ci)
        wk = jnp.transpose(w, (0, 1, 2, 3, 4)).reshape(L, 9 * ci, co)
        y = jax.lax.dot_general(
            pk, wk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=x.dtype)
        return y.reshape(L, B, H, W, co)

    def shared(x, w):
        _, B, H, W, ci = x.shape
        y = jax.lax.conv_general_dilated(
            x.reshape(L * B, H, W, ci), w[0], (1, 1), pad,
            dimension_numbers=dn)
        return y.reshape(x.shape[:-1] + (w.shape[-1],))

    return {"vmap": vmap_conv, "packed": packed, "packed_all": packed_all,
            "bgc": bgc, "im2col": im2col, "shared": shared}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--inner", type=int, default=20)
    p.add_argument("--repeats", type=int, default=8)
    p.add_argument("--lanes", type=int, default=8)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--tiny", action="store_true",
                   help="tiny shapes + inner=2: CI smoke, not comparable")
    args = p.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from fedml_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    L, B = args.lanes, args.batch
    if args.tiny:
        args.inner, args.repeats = 2, 2
        stages = [("s1", 8, 8)]
    else:
        stages = [("s1", 32, 16), ("s2", 16, 32), ("s3", 8, 64)]

    cands = make_candidates(L)
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "lanes": L, "batch": B,
                      "inner": args.inner}), flush=True)

    # Dispatch floor: a call whose loop body is one scalar multiply --
    # measures dispatch + fetch cost per call (at small --inner that bias
    # swamps sub-ms convs, so every derived per-iteration number below
    # subtracts floor/inner).
    def _floor(s0):
        return jax.lax.fori_loop(
            0, args.inner, lambda _, s: s * 0.999, s0)
    jfl = jax.jit(_floor)
    float(jfl(1.0)); float(jfl(1.0))
    fts = []
    for _ in range(max(args.repeats, 5)):
        t0 = time.perf_counter()
        float(jfl(1.0))
        fts.append(time.perf_counter() - t0)
    fts.sort()
    floor_call = fts[len(fts) // 2]
    print(json.dumps({"dispatch_floor_ms_per_call":
                      round(floor_call * 1e3, 2)}), flush=True)

    results = {}
    for sname, H, C in stages:
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x32 = jax.random.normal(kx, (L, B, H, H, C), jnp.float32)
        w32 = jax.random.normal(kw, (L, 3, 3, C, C), jnp.float32) * 0.1
        # numerics-gate reference: explicitly the vmap candidate (the
        # per-layer form of ablation B) -- not whichever candidate dict
        # iteration happens to yield first
        ref = jax.jit(cands["vmap"])(x32, w32)
        # useful (non-redundant) fwd FLOPs of the per-lane convs
        fwd_flops = 2 * L * B * H * H * 9 * C * C
        for cname, fn in cands.items():
            # -- numerics gate (fp32, vs vmap) --
            y = jax.jit(fn)(x32, w32)
            err = float(jnp.max(jnp.abs(y - ref)))
            denom = float(jnp.max(jnp.abs(ref)))
            if cname != "shared" and err > 1e-3 * max(denom, 1.0):
                print(json.dumps({"stage": sname, "cand": cname,
                                  "SKIP": f"numerics err {err:.3e}"}),
                      flush=True)
                continue

            x = x32.astype(jnp.bfloat16)
            w = w32.astype(jnp.bfloat16)

            def fwd_loop(x0, w0, fn=fn):
                def body(_, c):
                    y = fn(c, w0)
                    return y * jnp.bfloat16(0.999)  # self-feed (Ci==Co)
                return jnp.sum(jax.lax.fori_loop(
                    0, args.inner, body, x0).astype(jnp.float32))

            def fb_loop(x0, w0, fn=fn):
                def loss(xc, wc):
                    return jnp.sum(fn(xc, wc).astype(jnp.float32))

                def body(_, c):
                    xc, wc = c
                    _, (dx, dw) = jax.value_and_grad(
                        loss, argnums=(0, 1))(xc, wc)
                    return (xc + dx.astype(xc.dtype) * jnp.bfloat16(1e-3),
                            wc + dw.astype(wc.dtype) * jnp.bfloat16(1e-8))
                xf, wf = jax.lax.fori_loop(0, args.inner, body, (x0, w0))
                return (jnp.sum(xf.astype(jnp.float32))
                        + jnp.sum(wf.astype(jnp.float32)))

            for pname, jf in (("fwd", jax.jit(fwd_loop)),
                              ("fwd+bwd", jax.jit(fb_loop))):
                try:
                    float(jf(x, w))  # compile + warm
                    float(jf(x, w))
                except Exception as e:  # noqa: BLE001 -- report, keep going
                    print(json.dumps({"stage": sname, "cand": cname,
                                      "pass": pname,
                                      "ERROR": repr(e)[:200]}), flush=True)
                    continue
                ts = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    float(jf(x, w))
                    ts.append(time.perf_counter() - t0)
                ts.sort()
                call = ts[len(ts) // 2]
                per = max(call - floor_call, 1e-9) / args.inner
                flops = fwd_flops * (1 if pname == "fwd" else 3)
                rec = {"stage": sname, "cand": cname, "pass": pname,
                       "ms": round(per * 1e3, 4),
                       "ms_raw_call": round(call * 1e3, 2),
                       "useful_tflops": round(flops / per / 1e12, 2)}
                results[(sname, cname, pname)] = per
                print(json.dumps(rec), flush=True)

    # summary: per stage, fwd+bwd ranking vs the shared floor
    for sname, _, _ in stages:
        floor = results.get((sname, "shared", "fwd+bwd"))
        rows = sorted((v, c) for (s, c, p_), v in results.items()
                      if s == sname and p_ == "fwd+bwd")
        if floor and rows:
            tab = {c: round(v / floor, 2) for v, c in rows}
            print(json.dumps({"summary": sname,
                              "x_over_shared_floor": tab}), flush=True)


if __name__ == "__main__":
    main()
