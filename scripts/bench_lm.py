"""TransformerLM single-chip MFU bench (VERDICT r3 next #2).

Purpose: prove the 8.9% flagship MFU is ResNet-56 *shape*-bound (16/32/64-
channel convs under-fill the 128x128 MXU), not engine overhead -- an
MXU-friendly model through the same stack should reach tens of percent.

Model: dense TransformerLM, d_model 1024, heads of dim 128 (the fused
Pallas flash-attention path on hardware), bf16 compute, one jitted
AdamW train step. Analytic FLOPs (matmuls only, causal attention at
half the score/AV cost, train = 3x forward):

  fwd/token = L * (24 d^2 + 2 T d) + 2 d V

Timing is value-fetch (a host scalar read waits for the device).

Usage: python scripts/bench_lm.py [--cpu --tiny] [--repeats 10]
Prints ONE json line: tokens/s, achieved TFLOPS, mfu.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import mfu_fields, peak_flops  # single source for chip peaks


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--repeats", type=int, default=10)
    p.add_argument("--inner", type=int, default=10,
                   help="train steps chained inside one jitted call "
                        "(lax.fori_loop threading params+opt): amortizes "
                        "the per-dispatch floor, which would otherwise "
                        "be charged to the step time")
    p.add_argument("--d_model", type=int, default=1024)
    p.add_argument("--n_layers", type=int, default=8)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--tiny", action="store_true",
                   help="CPU-sized sanity shapes")
    args = p.parse_args()
    if args.inner < 1:
        p.error("--inner must be >= 1")
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.tiny:
        args.d_model, args.n_layers, args.seq = 256, 2, 128
        args.batch, args.vocab, args.repeats = 2, 512, 3

    from fedml_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.models.transformer import TransformerLM, lm_loss
    from fedml_tpu.observability.jaxmon import watch_compiles

    d, L, T, B, V = (args.d_model, args.n_layers, args.seq, args.batch,
                     args.vocab)
    n_heads = max(1, d // 128)  # head dim 128: the Pallas hardware path
    dev = jax.devices()[0]
    model = TransformerLM(vocab_size=V, n_layers=L, n_heads=n_heads,
                          d_model=d, max_len=T, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    idx = jax.random.randint(rng, (B, T), 0, V)
    tgt = jnp.roll(idx, -1, axis=1)
    params = model.init(rng, idx)["params"]
    tx = optax.adamw(3e-4)
    opt = tx.init(params)

    def loss_fn(p):
        return lm_loss(model.apply({"params": p}, idx), tgt)

    def one_step(p, o):
        l, g = jax.value_and_grad(loss_fn)(p)
        up, o = tx.update(g, o, p)
        p = optax.apply_updates(p, up)
        return p, o, l

    @jax.jit
    def step(p, o):
        # chain --inner real optimizer steps in ONE dispatch: params and
        # opt state thread through the fori_loop carry (each iteration's
        # weights differ, so nothing is loop-invariant), and only the
        # final loss scalar is fetched
        def body(_, carry):
            p, o, _ = carry
            return one_step(p, o)
        return jax.lax.fori_loop(0, args.inner, body,
                                 (p, o, jnp.float32(0.0)))

    # CompileWatcher measures the compile directly off jax.monitoring's
    # backend-compile events -- no wall-clock delta around an async
    # dispatch, so the old FL114 suppression is gone (the bench.py --lm
    # flagship path measures the same way)
    with watch_compiles() as compile_watch:
        params, opt, l = step(params, opt)
        float(l)  # value-fetch: the first call's execution tail completes
    compile_s = compile_watch.total_compile_seconds
    ts = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        params, opt, l = step(params, opt)
        float(l)  # value-fetch forces the whole chained call
        ts.append(time.perf_counter() - t0)
    ts.sort()
    sec = ts[len(ts) // 2] / args.inner

    fwd_per_token = L * (24 * d * d + 2 * T * d) + 2 * d * V
    flops_step = 3 * fwd_per_token * B * T
    achieved = flops_step / sec
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(json.dumps({
        "metric": f"TransformerLM train step (d{d} L{L} T{T} B{B} V{V}, "
                  f"bf16, flash-attn)",
        "tokens_per_s": round(B * T / sec),
        "ms_per_step": round(sec * 1e3, 2),
        "achieved_tflops": round(achieved / 1e12, 1),
        **mfu_fields(achieved, None if args.cpu else peak_flops(dev)),
        "n_params": n_params,
        "inner_steps_per_dispatch": args.inner,
        "compile_s": round(compile_s, 1),
        "compile_count": compile_watch.total_compiles,
        "compile_cache_hits": compile_watch.cache_hits,
        "device": str(dev),
    }))


if __name__ == "__main__":
    main()
