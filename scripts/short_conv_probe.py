"""The gated short convolution on the chip: the Pallas kernels of
``fedml_tpu.ops.short_conv`` against the plain ``jax.numpy`` form of the
same equations, milliseconds a call in both directions (PERF.md, PR 34).

For ``bcu`` ``[n, T, 3 d]`` bf16 and ``w`` ``[d, L]`` float32 (default: the
cell's ``1, 4096, 2048, 3``) the forward alone and the forward with its
backward (``d bcu`` and ``dw`` of a weighted sum of the output) are
compiled and timed in both forms: median of ``--reps`` samples, a sample
being ``--chain`` calls dispatched back to back and one
``block_until_ready``. Beside each time the share of the byte roofline by
the formula of ``benchmarks/families/lfm2_moe_lm.py`` ``kernel_costs``
(restated here, not imported: ``8 n T d`` bytes forward, ``14 n T d + 4 d
L`` backward; 819 GB/s), and the largest distance between the two forms.

    python3 scripts/short_conv_probe.py [--shape n,T,d,L ...]

Prints one JSON object and writes it to
``chiprun_out/short_conv_probe/probe.json``. ``JAX_PLATFORMS=cpu``
rehearses it at a toy shape (interpret mode: the times are then no device
numbers and the object says so).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from scripts.flash_probe import _compiled, _distance, _timed  # noqa: E402

PEAK_BYTES = 819e9      # TPU v5e HBM (benchmarks/peaks.py)


def plain(bcu, w):
    """The equations of ``fedml_tpu/ops/short_conv.py`` in ``jax.numpy``,
    float32 arithmetic as the kernels', for XLA to fuse as it likes."""
    import jax.numpy as jnp

    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    v, (t, taps) = b * u, (bcu.shape[1], w.shape[1])
    z = sum(w[:, k] * jnp.pad(v, ((0, 0), (taps - 1 - k, 0), (0, 0)))[:, :t]
            for k in range(taps))
    return (c * z).astype(bcu.dtype)


def probe(shape, reps, chain, seed):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.short_conv import gated_short_conv

    n, t, d, taps = shape
    key = jax.random.PRNGKey(seed % (2 ** 31))
    bcu = jax.random.normal(key, (n, t, 3 * d), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, taps)) / taps ** 0.5
    g = jax.random.normal(jax.random.fold_in(key, 2), (n, t, d), jnp.bfloat16)
    least = {"fwd": 8.0 * n * t * d / PEAK_BYTES,
             "bwd": (14.0 * n * t * d + 4.0 * d * taps) / PEAK_BYTES}
    out = {"shape": dict(zip("n T d L".split(), shape)),
           "least_ms": {k: v * 1e3 for k, v in least.items()}}
    results = {}
    for label, op in (("pallas", gated_short_conv), ("plain_jnp", plain)):
        def loss(bcu, w, op=op):
            return jnp.sum(op(bcu, w).astype(jnp.float32)
                           * g.astype(jnp.float32))

        fwd, fwd_s = _compiled(op, (bcu, w))
        both, both_s = _compiled(jax.value_and_grad(loss, argnums=(0, 1)),
                                 (bcu, w))
        results[label] = (fwd(bcu, w), both(bcu, w)[1])
        ms_fwd = _timed(fwd, (bcu, w), reps, chain)
        ms_both = _timed(both, (bcu, w), reps, chain)
        out[label] = {
            "fwd_ms": ms_fwd, "fwd_and_bwd_ms": ms_both,
            "bwd_ms_by_difference": ms_both - ms_fwd,
            "fwd_roofline_pct": 100 * least["fwd"] / (ms_fwd * 1e-3),
            "fwd_and_bwd_roofline_pct":
                100 * (least["fwd"] + least["bwd"]) / (ms_both * 1e-3),
            "compile_s": fwd_s + both_s}
    (y, (dbcu, dw)), (y0, (dbcu0, dw0)) = results["pallas"], \
        results["plain_jnp"]
    out["max_abs_between_forms"] = {
        "y": _distance(y, y0), "dbcu": _distance(dbcu, dbcu0),
        "dw_relative": _distance(dw, dw0)
        / max(float(jnp.max(jnp.abs(dw0))), 1e-30)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", default=None,
                    help="n,T,d,L (repeatable; default: the cell's "
                    "1,4096,2048,3)")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--seed", type=int, default=34)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    shapes = [tuple(int(x) for x in s.split(","))
              for s in args.shape or ["1,4096,2048,3"]]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "times_are_device_numbers": dev.platform == "tpu",
              "reps": args.reps, "chain": args.chain,
              "shapes": [probe(s, args.reps, args.chain, args.seed)
                         for s in shapes]}
    out_dir = os.path.join(ROOT, "chiprun_out", "short_conv_probe")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "probe.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
