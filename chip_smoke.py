#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the federated round still
starts on the chip.

Run from the root of a checkout on a machine with a TPU:

    python3 chip_smoke.py

One process owns the chip and runs every leg in turn, all over one
compile-cache directory (``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``):

- Leg A: FedAvg on ResNet-56 at the flagship's full width (32 clients,
  LDA 0.5 over 50,000 synthetic samples, batch 64, bf16, MXU-packed
  lanes) through ``fedml_tpu.experiments.main_fedavg``. Only local epochs
  (1) and rounds (4) are cut; every shape is the flagship's.
- Leg B: the Pallas flash attention compiled (fwd+bwd against ``mha`` at
  T=512 and T=80) and two rounds of the federated LM (``TransformerLM``
  d512 / 4 heads of 128 / T=80 through ``FedAvgAPI`` +
  ``BucketedStreamRunner``).
- Leg C, when ``jax.device_count() >= 4``: Leg A's command with
  ``--mesh 4`` as given (``ShardedLaneRunner``) and with ``--wave_mode 1``
  (``make_sharded_round``); the cohort and the state must occupy all four
  devices and round 0's Train/Loss must agree with Leg A's.

Legs A and B always run; Leg C runs exactly when there are four devices.
There is no CPU leg and no "skipped because no chip": the script exits
non-zero, printing no result line, unless ``jax.devices()[0].platform``
is ``tpu``. On success the last line of stdout is one JSON object with
exactly these keys, ``{"ok": true, "device": {"platform", "kind",
"count"}}``; the line before it, ``legs passed: [...]``, names the legs
that ran. Seconds printed along the way are information for the reader
(``info_*``), not benchmark metrics.

``tests/test_chip_smoke.py`` imports the leg functions and runs them on
the CPU with a toy :class:`Sizes`; nothing in this file switches on the
platform after the preamble.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

#: |Leg C round-0 Train/Loss - Leg A round-0 Train/Loss| bound. Same seed,
#: same shards, same schedule; what differs is which lanes share a conv
#: group (sharded lanes) or the conv lowering itself (vmapped clients in
#: the wave_mode 1 round), i.e. bf16 rounding order over one local epoch.
#: Measured on four v5e chips: 1.9e-5 and 1.8e-3 at a loss of 2.378.
MESH_LOSS_TOL = 0.01


class SmokeError(Exception):
    """A leg's check failed."""


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size a leg uses. The defaults ARE the smoke (the flagship's
    width, depth, batch and cohort); the tier-1 dry run passes a toy
    instance so the commands are known to parse and run before chip time
    is spent on them."""
    # Legs A and C: FedAvg / ResNet-56 (the dry run swaps in the 4-layer
    # CNN, the other lane-packed family, to stay inside tier-1's budget)
    model: str = "resnet56"
    n_train: int = 50_000
    n_test: int = 1_000
    image_size: int = 32
    clients: int = 32
    batch_size: int = 64
    client_chunk: int = 8
    rounds: int = 4
    mesh: int = 4
    mesh_rounds: int = 3
    # Leg B: kernels
    attn_seq_lens: tuple = (512, 80)
    attn_batch: int = 2
    attn_heads: int = 4
    head_dim: int = 128
    # Leg B: federated LM (bench.py --lm's construction)
    lm_d_model: int = 512
    lm_layers: int = 4
    lm_seq: int = 80
    lm_clients: int = 32
    lm_batch: int = 4
    lm_chunk: int = 8
    lm_rounds: int = 2


FULL = Sizes()


def _finite(x):
    return isinstance(x, (int, float)) and math.isfinite(x)


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _memory_stat(devices, key):
    """``memory_stats()[key]`` per device, or None on a backend that keeps
    no memory stats (XLA:CPU; ``main`` refuses to start without them)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s[key]) for s in stats]


def _placed_bytes(tree, devices):
    """Bytes of ``tree``'s array shards on each of ``devices``, from the
    arrays' own sharding."""
    import jax

    placed = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            if shard.device in placed:
                placed[shard.device] += shard.data.nbytes
    return [placed[d] for d in devices]


def preamble():
    """Versions, device, packing backend, cache directory. Returns the
    device record of the final JSON line."""
    from importlib import metadata

    import jax
    import jaxlib

    from fedml_tpu.parallel.packing import packing_backend
    from fedml_tpu.utils.compile_cache import enable_compilation_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"python={sys.version.split()[0]} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"device_count={device['count']}")
    print(f"packing_backend={packing_backend()}")
    print(f"compile_cache_dir={enable_compilation_cache()}")
    return device


def fedavg_leg(sz: Sizes, run_dir, *, mesh=0, wave_mode=3, rounds=None):
    """One FedAvg run through ``main_fedavg`` (Leg A; Leg C with
    ``mesh``). Checks the spans, the losses, the trained sample count,
    where the state lives and the per-round compile counts; returns the
    evidence."""
    import jax
    import numpy as np

    from fedml_tpu.experiments import main_fedavg

    rounds = rounds or sz.rounds
    # a run dir left by an earlier run must not satisfy this run's checks
    shutil.rmtree(run_dir, ignore_errors=True)
    mesh_devices = jax.devices()[:mesh]
    # what earlier legs left unreachable must not count as this run's
    gc.collect()
    live_before = _memory_stat(mesh_devices, "bytes_in_use")
    argv = ["--dataset", "synthetic_images", "--n_train", str(sz.n_train),
            "--n_test", str(sz.n_test), "--image_size", str(sz.image_size),
            "--model", sz.model, "--model_dtype", "bf16",
            "--client_num_in_total", str(sz.clients),
            "--client_num_per_round", str(sz.clients),
            "--partition_method", "hetero", "--partition_alpha", "0.5",
            "--batch_size", str(sz.batch_size), "--lr", "0.001",
            "--wd", "0.001", "--wave_mode", str(wave_mode),
            "--client_chunk", str(sz.client_chunk),
            "--device_data_cap_gb", "4", "--frequency_of_the_test", "1",
            "--trace", "1", "--epochs", "1", "--comm_round", str(rounds),
            "--mesh", str(mesh), "--run_dir", run_dir]
    print("main_fedavg " + " ".join(argv), flush=True)
    t0 = time.time()
    api, _ = main_fedavg.main(argv)
    wall_s = time.time() - t0

    mode = ("mxu-lanes" if not mesh else
            "sharded-lanes" if wave_mode == 3 else "packed")
    spans = [s for s in _read_jsonl(os.path.join(run_dir, "spans.jsonl"))
             if s["name"] == "local-train"]
    modes = [s["attrs"].get("mode") for s in spans]
    check(modes == [mode] * rounds,
          f"local-train spans {modes}, expected {rounds} x {mode!r}")

    hist = api.history
    check(len(hist) == rounds, f"{len(hist)} round records, not {rounds}")
    for m in hist:
        check(_finite(m.get("Train/Loss")) and _finite(m.get("Test/Loss")),
              f"round {m.get('round')}: non-finite loss in {m}")

    trained = int(np.asarray(api._last_metrics["count"]).sum())
    shards = int(sum(api.train_data_local_num_dict.values()))
    check(trained == shards,
          f"trained {trained} samples, shards hold {shards}")

    want = set(jax.devices()[:mesh] if mesh else jax.devices()[:1])
    for leaf in jax.tree.leaves(api.global_state):
        check(isinstance(leaf, jax.Array) and leaf.devices() == want,
              f"global state leaf {type(leaf).__name__} lives on "
              f"{getattr(leaf, 'sharding', None)}, expected {want}")

    records = _read_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    watch = next(r for r in reversed(records)
                 if "compile/compiles_per_round" in r)
    per_round = watch["compile/compiles_per_round"]
    check(len(per_round) == rounds and not any(per_round[2:]),
          f"compiles per round {per_round}: expected zero from the third "
          "round on")

    out = {"mode": mode,
           "train_loss": [m["Train/Loss"] for m in hist],
           "test_loss": [m["Test/Loss"] for m in hist],
           "samples_trained": trained,
           "compiles_per_round": per_round,
           "cache_hits": watch["compile/cache_hits"],
           "cache_misses": watch["compile/cache_misses"],
           "info_compile_s_per_round": watch["compile/seconds_per_round"],
           "info_round_wall_s": [round(m["round_time_s"], 3) for m in hist],
           "info_leg_wall_s": round(wall_s, 1)}
    if mesh:
        # the cohort's data must be spread over the mesh, not parked on
        # device 0: the resident stack on the lane path, one round's
        # packed cohort on the wave_mode 1 path
        runner = api.runner
        cohort = (runner.device_data if runner.mode == "sharded-lanes"
                  else runner.pack(api._sample_cohort(0)))
        sh = cohort["x"].sharding
        check(len(sh.device_set) == mesh and not sh.is_fully_replicated,
              f"cohort x sharding {sh} does not span {mesh} devices")
        data = _placed_bytes(cohort, mesh_devices)
        state = _placed_bytes(api.global_state, mesh_devices)
        check(all(data) and all(state),
              f"a mesh device holds no cohort shard or no state: cohort "
              f"bytes {data}, state bytes {state}")
        out["cohort_bytes"], out["state_bytes"] = data, state
        # and the allocator must agree. The peak is a process-lifetime
        # value (device 0 carries Leg A's, the others the previous mesh
        # run's), so the check is on LIVE bytes: with this run's state
        # and cohort still referenced, every device holds at least their
        # shards more than it did before the run started.
        live = _memory_stat(mesh_devices, "bytes_in_use")
        if live is not None:
            grown = [b - a for a, b in zip(live_before, live)]
            check(all(g >= d + s for g, d, s in zip(grown, data, state)),
                  f"live bytes grew by {grown} over the run; the cohort "
                  f"and state shards alone are {data} + {state}")
            out["live_bytes_grown"] = grown
            peak = _memory_stat(mesh_devices, "peak_bytes_in_use")
            check(all(peak), f"a mesh device reports no peak memory: {peak}")
            out["peak_bytes_in_use"] = peak
    return out


def leg_a(sz: Sizes, out_dir):
    return fedavg_leg(sz, os.path.join(out_dir, "leg_a"))


def leg_c(sz: Sizes, out_dir, leg_a_loss0):
    """Leg A's command over ``sz.mesh`` devices, sharded lanes then the
    wave_mode 1 sharded round; round 0's Train/Loss against Leg A's."""
    out = {}
    for name, wave_mode in (("sharded_lanes", 3), ("sharded_round", 1)):
        ev = fedavg_leg(sz, os.path.join(out_dir, f"leg_c_{name}"),
                        mesh=sz.mesh, wave_mode=wave_mode,
                        rounds=sz.mesh_rounds)
        diff = abs(ev["train_loss"][0] - leg_a_loss0)
        ev["loss0_diff_vs_leg_a"] = diff
        check(diff <= MESH_LOSS_TOL,
              f"{name}: round-0 Train/Loss {ev['train_loss'][0]} vs Leg A "
              f"{leg_a_loss0}: |diff| {diff:.3g} > {MESH_LOSS_TOL}")
        out[name] = ev
    return out


def _max_abs_diff(a, b):
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def check_flash_attention(sz: Sizes):
    """Flash attention forward and backward against the materializing
    ``mha`` oracle, plain and causal, at every ``attn_seq_lens``; compiled,
    a head dim of 64 must raise the documented error."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.attention import mha
    from fedml_tpu.ops.pallas_attention import (_use_interpret,
                                                flash_attention)

    out = {}
    if not _use_interpret():
        # compiled kernels take head dims that fill 128 lanes, or 64
        # (since PR 34); any other width gets the clean error, not a
        # Mosaic layout failure
        small = jnp.zeros((1, 16, 1, 32), jnp.bfloat16)
        try:
            flash_attention(small, small, small)
        except ValueError as e:
            check("multiple of 128" in str(e), f"D=32 raised: {e}")
        else:
            raise SmokeError("flash_attention compiled at head_dim 32 "
                             "without the 'multiple of 128' error")
        out["D32_raises"] = True
    B, H, D = sz.attn_batch, sz.attn_heads, sz.head_dim
    for T in sz.attn_seq_lens:
        ks = jax.random.split(jax.random.PRNGKey(T), 3)
        q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                   for kk in ks)
        for causal in (False, True):
            def loss(fn, args):
                return jnp.sum(fn(*args, causal).astype(jnp.float32) ** 2)

            fwd = _max_abs_diff(flash_attention(q, k, v, causal),
                                mha(q, k, v, causal))
            g_flash = jax.grad(lambda a: loss(flash_attention, a))((q, k, v))
            g_ref = jax.grad(lambda a: loss(mha, a))((q, k, v))
            bwd = max(_max_abs_diff(a, b) for a, b in zip(g_flash, g_ref))
            print(f"flash T={T} causal={causal}: fwd_err={fwd:.2e} "
                  f"bwd_err={bwd:.2e}", flush=True)
            check(fwd < 2e-2, f"flash fwd T={T} causal={causal}: {fwd}")
            check(bwd < 0.3, f"flash bwd T={T} causal={causal}: {bwd}")
            out[f"T{T}_causal{int(causal)}"] = {"fwd_err": fwd,
                                                "bwd_err": bwd}
    return out


def check_federated_lm(sz: Sizes):
    """Rounds of the federated LM as ``bench.py --lm`` builds it:
    ``TransformerLM`` with heads of ``head_dim``, ragged synthetic
    Shakespeare clients, geometric buckets, through ``FedAvgAPI`` +
    ``BucketedStreamRunner``."""
    import types

    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.data.shakespeare import (VOCAB_SIZE,
                                            synthetic_shakespeare_clients)
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.observability.jaxmon import watch_compiles

    T, C = sz.lm_seq, sz.lm_clients
    dataset = synthetic_shakespeare_clients(C, T, VOCAB_SIZE)
    model = TransformerLM(vocab_size=VOCAB_SIZE, n_layers=sz.lm_layers,
                          n_heads=max(1, sz.lm_d_model // sz.head_dim),
                          d_model=sz.lm_d_model, max_len=T,
                          dtype=jnp.bfloat16)
    spec = make_seq_classification_spec(
        model, jnp.zeros((1, T), jnp.int32), name="lm")
    run_args = types.SimpleNamespace(
        client_num_in_total=C, client_num_per_round=C,
        comm_round=sz.lm_rounds, epochs=1, batch_size=sz.lm_batch,
        lr=3e-4, wd=0.0, client_optimizer="adam",
        frequency_of_the_test=10 ** 9, seed=0,
        client_chunk=sz.lm_chunk, bucket_edges="geometric",
        device_resident="0")
    api = FedAvgAPI(dataset, spec, run_args)
    losses, walls = [], []
    with watch_compiles() as watch:
        for _ in range(sz.lm_rounds):
            m = api.train_one_round()
            check(_finite(m["Train/Loss"]), f"LM round: {m}")
            shapes = api.runner.compiled_shapes()
            check(shapes == m["bucket/shapes"],
                  f"compiled_shapes() {shapes} != buckets_used "
                  f"{m['bucket/shapes']}")
            losses.append(m["Train/Loss"])
            walls.append(round(m["round_time_s"], 3))
    print(f"federated LM: losses={losses}", flush=True)
    return {"train_loss": losses, "bucket_shapes": shapes,
            "compiles_per_round": watch.compiles_per_round,
            "info_round_wall_s": walls,
            "info_compile_s": round(watch.total_compile_seconds, 1)}


def leg_b(sz: Sizes):
    return {"flash_attention": check_flash_attention(sz),
            "federated_lm": check_federated_lm(sz)}


def result_line(device):
    """The last line of stdout on success. The driver takes exactly these
    keys and no others; which legs ran is printed on the line before."""
    return json.dumps({"ok": True,
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out_dir",
                   default=os.path.join(REPO, "chiprun_out", "chip_smoke"))
    args = p.parse_args(argv)

    # every compile persists, so a second run over the same directory
    # must find them all (the zero-misses check of the cache placement)
    os.environ.setdefault("FEDML_TPU_COMPILE_MIN_S", "0")
    device = preamble()
    if device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not 'tpu' "
              "-- this smoke only proves anything on the chip",
              file=sys.stderr)
        return 2
    from fedml_tpu.ops.pallas_attention import _use_interpret
    if _use_interpret():
        print("chip_smoke: Pallas kernels would run interpreted",
              file=sys.stderr)
        return 2
    import jax
    if _memory_stat(jax.devices(), "bytes_in_use") is None:
        print("chip_smoke: the backend reports no memory stats (Leg C "
              "checks them)", file=sys.stderr)
        return 2

    os.makedirs(args.out_dir, exist_ok=True)
    evidence, failed = {}, []

    def run(name, fn):
        t0 = time.time()
        try:
            evidence[name] = fn()
            print(f"leg {name}: PASS  (info_wall_s={time.time() - t0:.1f})",
                  flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"leg {name}: FAIL  (info_wall_s={time.time() - t0:.1f})",
                  flush=True)

    run("A", lambda: leg_a(FULL, args.out_dir))
    run("B", lambda: leg_b(FULL))
    if device["count"] < FULL.mesh:
        print(f"leg C: not run, device_count={device['count']} < "
              f"{FULL.mesh}", flush=True)
    elif "A" not in evidence:
        failed.append("C")
        print("leg C: FAIL  (needs Leg A's round-0 loss)", flush=True)
    else:
        run("C", lambda: leg_c(FULL, args.out_dir,
                               evidence["A"]["train_loss"][0]))

    with open(os.path.join(args.out_dir, "evidence.json"), "w") as f:
        json.dump({"device": device, "failed": failed,
                   "evidence": evidence}, f, indent=1, sort_keys=True)
    print("evidence: " + json.dumps(evidence, sort_keys=True), flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs {failed}", file=sys.stderr)
        return 1
    print("legs passed: " + json.dumps(sorted(evidence)), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
