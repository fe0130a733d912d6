"""What a builder of the flash kernels reads on the chip before the
benchmark's line (PERF.md, PR 30): for a shape and a list of tile
choices, each of the three kernels alone.

For every shape (defaults: the two configurations' attention at the
cells' traffic, ``B 2, T 2048``: 16 heads of 128, and 32 heads with
scores of 192 -- 256 as the kernels see them -- over values of 128) and
every :class:`fedml_tpu.ops.pallas_attention.Tile` tried, ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` are compiled and timed on their
own ``[B, H, T, D]`` operands, causal: milliseconds a call (median of
``--reps`` samples, a sample being ``--chain`` calls dispatched back to
back and one ``block_until_ready``), grid steps a call, Mosaic compile
seconds, the largest distance from the first tile's result, and the
share of the FLOP roofline by the formula of ``benchmarks/families/*``
``kernel_costs`` (restated here, not imported: products over the causal
half, ``B*H*T^2*(Dqk + Dv)`` forward and ``B*H*T^2*(3*Dqk + 2*Dv)`` for
the two backward kernels TOGETHER, at the widths the model gives, the
zero columns not counted; 197 TFLOP/s). Last, the whole attention layer
(``flash_attention`` forward and its three gradients, with the
transposes, pads and the delta reduction XLA runs around the kernels)
under the schedule ``flash_schedule`` chooses and under 128 x 128 tiles.

    python3 scripts/flash_probe.py [--shape B,T,H,Dqk,Dv ...]
        [--fwd rows,major,minor ...] [--dq ...] [--dkv ...]
    python3 scripts/flash_probe.py --gqa 1,4096,32,8,64

``--gqa B,T,H,KV,D`` (PR 34) is the row of a grouped-query layer with
heads narrower than a tile (``lfm2-8b-a1b-ep4``: 32 query heads over 8
key/value heads of 64, T 4,096, causal) and times the FORMS such a layer
can take, the whole layer forward and backward: blocks of the array's own
64 columns with the kernels reading key/value head ``h // group``
themselves (what ships), the same with the heads repeated first
(``jnp.repeat``, what the parent did at 128), and both again with q, k
and v zero-padded to 128 columns (the baseline: the only form the
parent's kernels could run).

Prints one JSON object and writes it to ``chiprun_out/flash_probe/probe.json``.
``JAX_PLATFORMS=cpu`` rehearses it at a toy shape (interpret mode: the
times are then no device numbers and the object says so).
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PEAK_FLOPS = 197e12     # TPU v5e, bf16 (benchmarks/peaks.py)
CELL_SHAPES = [(2, 2048, 16, 128, 128), (2, 2048, 32, 192, 128)]
#: (rows, major, minor); 0 for ``major`` means the whole sequence
SWEEP = {
    "fwd": [(128, 128, 128), (256, 0, 256), (256, 0, 512), (512, 0, 256),
            (512, 0, 512), (512, 1024, 512), (512, 0, 1024), (1024, 0, 512),
            (1024, 0, 256), (512, 512, 512), (512, 0, 128)],
    "dq": [(128, 128, 128), (256, 0, 256), (256, 0, 512), (512, 0, 256),
           (512, 0, 512), (512, 1024, 512), (1024, 0, 256), (1024, 0, 512),
           (512, 512, 512), (512, 0, 128)],
    "dkv": [(128, 128, 128), (256, 0, 256), (256, 0, 512), (512, 0, 256),
            (512, 0, 512), (512, 1024, 512), (1024, 0, 256), (1024, 0, 512),
            (512, 512, 512), (512, 0, 128)],
}


def _timed(fn, args, reps, chain):
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        a = time.perf_counter()
        for _ in range(chain - 1):
            fn(*args)
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - a) * 1e3 / chain)
    return statistics.median(out)


def _compiled(fn, args):
    import jax

    a = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    return exe, time.perf_counter() - a


def _distance(got, ref):
    import jax
    import jax.numpy as jnp

    return max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - r.astype(jnp.float32))))
               for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)))


def probe_shape(shape, sweep, reps, chain, seed):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import pallas_attention as pa

    b, t, h, dqk, dv = shape
    interpret = pa._use_interpret()
    width = dqk + pa._score_pad(dqk, dv, interpret)
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(seed % (2 ** 31))
    rand = lambda i, d: jax.random.normal(jax.random.fold_in(key, i),
                                          (b, h, t, d), dtype)
    # the kernels' own operands: [B, H, T, D], scores padded as the
    # wrapper pads them
    q, k = (jnp.pad(rand(i, dqk), ((0, 0),) * 3 + ((0, width - dqk),))
            for i in (0, 1))
    v, do = rand(2, dv), rand(3, dv)
    scale = dqk ** -0.5
    kw = dict(scale=scale, causal=True, q_len=t, k_len=t, interpret=interpret)
    chosen, chosen_steps = pa.flash_schedule(t, t, width, dv, dtype)
    out = {"shape": dict(zip("B T H Dqk Dv".split(), shape)),
           "score_width_in_kernel": width,
           "flash_schedule": {n: list(x) for n, x in
                              zip(chosen._fields, chosen)},
           "flash_schedule_steps_a_head": list(chosen_steps)}
    flops = {"fwd": 1.0 * b * h * t * t * (dqk + dv),
             "bwd": 1.0 * b * h * t * t * (3 * dqk + 2 * dv)}

    def tile_of(rows, major, minor):
        return pa._clip(pa.Tile(rows, major or t, minor), t, t)

    lse = delta = None
    for name in ("fwd", "dq", "dkv"):
        one_head = {"fwd": pa._fwd_one_head, "dq": pa._dq_one_head,
                    "dkv": pa._dkv_one_head}[name]
        ref, table = None, []
        for choice in sweep[name]:
            tile = tile_of(*choice)
            fn = pa._per_head(functools.partial(one_head, tile=tile, **kw))
            if name == "fwd":
                args = (q, k, v)
            else:
                args = (q, k, v, do, lse, delta)
            row = {"tile": list(tile),
                   "grid_steps_a_call": b * h * (t // tile.rows)
                   * (t // tile.major)}
            try:
                exe, row["compile_s"] = _compiled(fn, args)
                got = exe(*args)
                row["ms"] = _timed(exe, args, reps, chain)
            except Exception as e:  # a tile Mosaic refuses is a finding
                row["error"] = str(e).splitlines()[0][:300]
                table.append(row)
                continue
            if ref is None:
                ref = got
            row["max_abs_from_first"] = _distance(got, ref)
            if name == "fwd":
                row["roofline_pct"] = 100 * flops["fwd"] / PEAK_FLOPS \
                    / (row["ms"] * 1e-3)
            table.append(row)
        out[name] = table
        if name == "fwd":
            o, lse = ref
            delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                            axis=-1)[:, :, None]
    best = {n: min((r for r in out[n] if "ms" in r), key=lambda r: r["ms"])
            for n in ("fwd", "dq", "dkv")}
    out["best"] = {n: {"tile": r["tile"], "ms": r["ms"]}
                   for n, r in best.items()}
    out["best"]["fwd_roofline_pct"] = best["fwd"]["roofline_pct"]
    out["best"]["bwd_roofline_pct"] = 100 * flops["bwd"] / PEAK_FLOPS / (
        (best["dq"]["ms"] + best["dkv"]["ms"]) * 1e-3)

    # the whole layer as a model calls it: [B, T, H, D] in and out
    sw = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    ql, kl, vl, dol = sw(q[..., :dqk]), sw(k[..., :dqk]), sw(v), sw(do)

    def layer(blocks):
        def loss(q, k, v):
            return jnp.sum(pa.flash_attention(q, k, v, True, scale, *blocks)
                           .astype(jnp.float32) * dol.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    out["layer_fwd_bwd"] = {}
    ref = None
    for label, blocks in (("tiles_128", (128, 128)),
                          ("flash_schedule", (None, None))):
        exe, secs = _compiled(layer(blocks), (ql, kl, vl))
        got = exe(ql, kl, vl)
        ref = got if ref is None else ref
        out["layer_fwd_bwd"][label] = {
            "ms": _timed(exe, (ql, kl, vl), reps, chain),
            "compile_s": secs, "max_abs_from_first": _distance(got, ref)}
    return out


# -- PR 34: the forms of a grouped-query layer with heads of 64 ---------------

def probe_gqa(shape, reps, chain, seed):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import pallas_attention as pa

    b, t, h, kvh, d = shape
    group = h // kvh
    interpret = pa._use_interpret()
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(seed % (2 ** 31))
    rand = lambda i, heads: jax.random.normal(
        jax.random.fold_in(key, i), (b, t, heads, d), dtype)
    q, k, v, do = rand(0, h), rand(1, kvh), rand(2, kvh), rand(3, h)
    scale = d ** -0.5
    pairs = t * (t + 1) / 2
    flops = {"fwd": 2.0 * pairs * (d + d) * b * h,
             "fwd_bwd": 2.0 * pairs * (7 * d) * b * h}
    out = {"shape": dict(zip("B T H KV D".split(), shape)),
           "flash_schedule": {n: list(x) for n, x in zip(
               pa.Schedule._fields, pa.flash_schedule(t, t, d, d, dtype)[0])}}
    wide = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, 128 - d),))
    rep = lambda x: jnp.repeat(x, group, axis=2)
    # on the CPU the forms at 128 are rehearsed too: interpret mode pads
    # nothing by itself
    forms = {
        "own64_group_read": lambda q, k, v: (q, k, v),
        "own64_repeat": lambda q, k, v: (q, rep(k), rep(v)),
        "pad128_group_read": lambda q, k, v: (wide(q), wide(k), wide(v)),
        "pad128_repeat": lambda q, k, v: (wide(q), rep(wide(k)),
                                           rep(wide(v))),
    }

    def layer(form):
        def loss(q, k, v):
            o = pa.flash_attention(*form(q, k, v), True, scale)[..., :d]
            return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    out["layer_fwd_bwd"], ref = {}, None
    for label, form in forms.items():
        row = {}
        try:
            exe, row["compile_s"] = _compiled(layer(form), (q, k, v))
            got = exe(q, k, v)
            row["ms"] = _timed(exe, (q, k, v), reps, chain)
            ref = got if ref is None else ref
            row["max_abs_from_first"] = _distance(got, ref)
            row["roofline_pct"] = 100 * flops["fwd_bwd"] / PEAK_FLOPS \
                / (row["ms"] * 1e-3)
        except Exception as e:
            row["error"] = str(e).splitlines()[0][:300]
        out["layer_fwd_bwd"][label] = row

    return out


def _tiles(values):
    return [tuple(int(x) for x in v.split(",")) for v in values]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=None,
                    help="B,T,H,Dqk,Dv (repeatable; default: the two cells')")
    for name in SWEEP:
        ap.add_argument(f"--{name}", action="append", default=None,
                        help="rows,major,minor (major 0: the whole "
                        "sequence); repeatable; default: the sweep")
    ap.add_argument("--gqa", action="append", default=None,
                    help="B,T,H,KV,D: the forms of a grouped-query layer "
                    "(repeatable); with it, the tile sweep runs only for "
                    "shapes given by --shape")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--seed", type=int, default=30)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    shapes = _tiles(args.shape) if args.shape \
        else ([] if args.gqa else CELL_SHAPES)
    sweep = {n: _tiles(getattr(args, n)) if getattr(args, n) else SWEEP[n]
             for n in SWEEP}
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "times_are_device_numbers": dev.platform == "tpu",
              "reps": args.reps, "chain": args.chain,
              "shapes": [probe_shape(s, sweep, args.reps, args.chain,
                                     args.seed) for s in shapes],
              "gqa": [probe_gqa(s, args.reps, args.chain, args.seed)
                      for s in _tiles(args.gqa or [])]}
    out_dir = os.path.join(ROOT, "chiprun_out", "flash_probe")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "probe.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
