"""Benchmark: FedAvg rounds/hour, CIFAR-10-scale ResNet-56, 32 clients.

The north-star metric (BASELINE.json): CIFAR-10 + ResNet-56 cross-silo FedAvg
with 32 clients -- reference recipe LDA alpha=0.5, bs64, SGD, 20 local epochs
(``benchmark/README.md:105``, ``fedml_experiments/distributed/fedavg/
README.md:38-52``, published at 10 clients) -- measured as rounds/hour.

Baseline derivation (no wall-clock numbers are published in-repo, BASELINE.md):
the reference runs one torch process per client over 8 V100s with pickle-over-
MPI transport and 0.3 s receive polling. At 32 clients x (50000/32 samples x
20 epochs / bs64) ~= 490 ResNet-56 steps per client per round, ~15 ms/step on
V100, 4 waves over 8 GPUs => ~29 s compute + serialization of 32 full
state_dicts and CPU aggregation => ~60 s/round ~= 60 rounds/hour. We use
BASELINE_ROUNDS_PER_HOUR = 60 (an estimate favorable to the reference). So
the comparison can be re-derived, the output also carries per-step ms,
model FLOPs, achieved TFLOPS and MFU.

TPU design measured here: client shards live in HBM for the whole run
(uploaded once); each round the host builds only an index schedule; the
cohort is sorted by local step count and dispatched in jitted waves whose
``fori_loop`` trip count is the wave maximum (``parallel/engine.py``
WaveRunner) -- padded steps are never executed; weighted aggregation and the
server step stay on device; bf16 matmuls on the MXU.

Data is synthetic CIFAR-10-shaped (50000x32x32x3; zero-egress environment) --
identical compute/communication profile to real CIFAR-10.

Failure: a missing accelerator (without ``--platform cpu``), a device kind
with no entry in the peak table, or a round that raises ends the run with a
non-zero exit; the configuration asked for is the one measured, or nothing.

Usage: python bench.py [--smoke] [--rounds N] [--epochs E] [--flat]
Prints one JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Federated LM flagship (``--lm``, docs/PERFORMANCE.md round 8):
LEAF-Shakespeare-shaped TransformerLM fine-tuning (flash attention)
through FedAvgAPI + the bucketed streaming engine; one JSON record
with ``lm_rounds_per_hour`` + cost-model MFU (``flops_source:
xla-cost-model``), sharing the --check-regress ledger with the CIFAR
flagship. ``--warmup`` runs the fedwarm AOT round-program warmup
(fedml_tpu.compile) through the persistent compilation cache first --
over a warmed ``--compile_cache_dir`` a restarted bench/server starts
in cache-load time (the warm-restart gate in scripts/ci.sh).

MFU methodology (docs/PERFORMANCE.md round 7): per-sample train FLOPs
come from the XLA cost model of the actual compiled train step
(``fedml_tpu.observability.costmodel.train_step_cost``); the analytic
constant below remains as the cross-checked fallback (``flops_source``
in the record says which was used; a tier-1 test pins agreement within
the documented tolerance).

Perf-regression ledger: every perf run appends its record to
``--ledger`` (default ``bench_results/ledger.jsonl``; empty string
disables), and ``python bench.py --check-regress`` compares the newest
record against the median of its same-metric predecessors with a noise
band (``--regress_band``), exiting non-zero on regression -- gated both
ways in scripts/ci.sh.

Compression tools (CPU-only, no accelerator needed; see
docs/COMPRESSION.md):
  python bench.py --compression_sweep [--sweep_model resnet56|cnn]
      one JSON line per compressor spec: encoded bytes, ratio vs the raw
      binary codec AND vs the legacy JSON-list path, encode/decode
      latency.
  python bench.py --check
      size-regression gate: binary framing of an UNCOMPRESSED
      ResNet-sized pytree must stay >= 5x smaller than the JSON-list
      path (exit 1 on regression).
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

BASELINE_ROUNDS_PER_HOUR = 60.0
FLAGSHIP_EPOCHS = 20

# ResNet-56 (CIFAR) analytic cost: 125.75M MACs/sample forward
#   stem 3x3x3x16@32x32 (0.44M) + 3 stages x 9 BasicBlocks x 2 convs
#   (42.47M + 41.42M + 41.42M incl. strided first convs + 1x1 downsamples)
#   + fc 64x10. Forward FLOPs = 2 x MACs; training step ~= 3 x forward
#   (fwd + input-grad + weight-grad). Published derivable from
#   fedml_api/model/cv/resnet.py resnet56 topology.
# Since round 7 this constant is the FALLBACK (and cross-check anchor)
# only: the record's MFU uses the XLA cost model of the compiled train
# step when available, and tests/test_observability.py pins the two
# within FLOPS_XCHECK_TOL so this constant can never silently rot.
RESNET56_MACS_PER_SAMPLE = 125.75e6
TRAIN_FLOPS_PER_SAMPLE = 3 * 2 * RESNET56_MACS_PER_SAMPLE
#: documented tolerance between the analytic constant and the XLA
#: cost-model count (the analytic 3x-forward rule over conv/fc MACs vs
#: XLA's exact HLO op count incl. GroupNorm/activations; measured ratio
#: ~0.87 at smoke shapes -- docs/PERFORMANCE.md round 7)
FLOPS_XCHECK_TOL = 0.30

# bf16 peak by device kind (dense, per chip; Google Cloud TPU documentation)
_PEAK_TFLOPS = (("v5 lite", 197.0), ("v5e", 197.0), ("v5p", 459.0),
                ("v6", 918.0), ("v4", 275.0), ("v3", 123.0))


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device`` by its ``device_kind``. A device
    that is not in the table is an error, never a default: utilization
    against an assumed peak is not a measurement."""
    kind = getattr(device, "device_kind", "").lower()
    for key, tf in _PEAK_TFLOPS:
        if key in kind:
            return tf * 1e12
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {kind!r}; add it to "
        "bench._PEAK_TFLOPS with its source")


def mfu_fields(achieved_flops, peak, digits=4) -> dict:
    """The record's ``mfu`` / ``assumed_peak_tflops`` fields. ``peak`` is
    :func:`peak_flops` of the device, or None on an explicit CPU smoke
    (``--platform cpu``), which has no accelerator peak to stand against:
    both fields are None there, never a CPU number under a device
    metric's name."""
    if peak is None:
        return {"mfu": None, "assumed_peak_tflops": None}
    return {"mfu": round(achieved_flops / peak, digits),
            "assumed_peak_tflops": peak / 1e12}


#: rewritten by main() once --algo is known, so failure lines from a
#: FedOpt run are not attributed to the FedAvg bench
_FAILURE_METRIC = "FedAvg rounds/hour (CIFAR-10-scale ResNet-56)"


def emit_failure(error, **extra):
    """One JSON line naming the failure; the caller exits non-zero."""
    out = {"metric": _FAILURE_METRIC,
           "value": 0.0, "unit": "rounds/hour", "vs_baseline": 0.0,
           "error": error}
    out.update(extra)
    print(json.dumps(out), flush=True)


def arm_watchdog(budget_s, context):
    """Emit the JSON line and hard-exit if the bench wedges mid-run (a
    round blocked on a dead device cannot be unblocked from Python)."""

    def fire():
        emit_failure(f"watchdog: no result within {budget_s:.0f}s "
                     f"({context})")
        os._exit(1)

    t = threading.Timer(budget_s, fire)
    t.daemon = True
    t.start()
    return t


def build_api(args, epochs, client_chunk, wave_mode):
    import types

    import jax.numpy as jnp

    from fedml_tpu import models
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.data.synthetic import load_synthetic_images

    if args.smoke:
        n_train, image = 2 * args.clients * 8, 16
        epochs = 1  # smoke validates the path, not the workload
    else:
        n_train, image = 50_000, 32

    dataset = load_synthetic_images(
        client_num=args.clients, n_train=n_train, n_test=max(64, n_train // 50),
        image_size=image, partition="hetero", partition_alpha=0.5, seed=0)

    model = models.resnet56(class_num=10, dtype=jnp.bfloat16)
    augment_fn = None
    if not args.no_augment:
        # the reference recipe trains WITH crop/flip/Cutout
        # (data_loader.py:57-76) -- include it so the measured workload is
        # the recipe, not a lighter one (fused on device; ~1% of step cost)
        from fedml_tpu.data.augment import make_cifar_augment
        augment_fn = make_cifar_augment(
            pad=4 if image >= 32 else 2,
            cutout_length=16 if image >= 32 else 4)
    spec = make_classification_spec(model, jnp.zeros((1, image, image, 3)),
                                    augment_fn=augment_fn,
                                    lane_lowering=args.lane_lowering)
    run_args = types.SimpleNamespace(
        client_num_in_total=args.clients, client_num_per_round=args.clients,
        comm_round=10 ** 9, epochs=epochs, batch_size=args.batch_size,
        lr=0.001, wd=0.001, client_optimizer="sgd", frequency_of_the_test=10 ** 9,
        seed=0, client_chunk=client_chunk, wave_mode=wave_mode,
        device_resident="auto", device_data_cap_gb=4.0,
        device_dtype=args.device_dtype)
    if args.algo == "fedopt":
        # second bench line (non-FedAvg path): same engine/shapes, server
        # Adam on the pseudo-gradient (reference ``fedopt`` algorithm) --
        # shows the measured advantage is the engine's, not the recipe's
        from fedml_tpu.algorithms.fedopt import FedOptAPI
        run_args.server_optimizer = "adam"
        run_args.server_lr = 0.001
        api = FedOptAPI(dataset, spec, run_args)
    else:
        api = FedAvgAPI(dataset, spec, run_args)
    if api.runner.mode == "packed":
        raise RuntimeError("device-resident path required for the bench")
    return api


def train_step_flops_per_sample(api, image, batch_size):
    """Per-sample train FLOPs of the compiled train step (XLA cost
    model), or None when the backend exposes no cost analysis -- the
    caller then falls back to the analytic constant. Abstract shapes
    only: the probe compiles but never executes or allocates."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.observability.costmodel import train_step_cost

    batch = {"x": jax.ShapeDtypeStruct((batch_size, image, image, 3),
                                       jnp.float32),
             "y": jax.ShapeDtypeStruct((batch_size,), jnp.int32),
             "mask": jax.ShapeDtypeStruct((batch_size,), jnp.float32)}
    pc = train_step_cost(api.spec, api.cfg, batch)
    if pc is None:
        return None
    return pc.flops / batch_size


def measure(args, epochs, client_chunk, wave_mode):
    """Run warmup + measured rounds; a round that raises fails the run."""
    from fedml_tpu.observability.jaxmon import watch_compiles

    api = build_api(args, epochs, client_chunk, wave_mode)
    t0 = time.time()
    with watch_compiles() as compile_watch:
        if getattr(args, "warmup", 0):
            # fedwarm AOT warmup: every round program compiles through
            # the persistent cache before the first dispatch; counted in
            # the same warmup bucket (record_fields carries the
            # cache-hit/miss split -- the warmed-restart evidence)
            from fedml_tpu.compile import warmup_api
            warmup_api(api)
        api.train_one_round()  # compile + warmup
    compile_s = time.time() - t0

    rounds = 1 if args.smoke else args.rounds
    times, metrics, samples = [], None, []
    from fedml_tpu.observability.tracing import Tracer, set_tracer
    from fedml_tpu.utils.profiling import profile_trace
    # fedtrace spans over the MEASURED rounds only (warmup excluded):
    # per-phase attribution for the perf trajectory -- which of
    # cohort-select / broadcast / local-train (dispatch) / aggregate
    # (device wait) / report moves when a round gets faster
    tracer = Tracer()
    prev_tracer = set_tracer(tracer)
    try:
        with profile_trace(args.profile_dir,
                           enabled=args.profile_dir is not None):
            for _ in range(rounds):
                t0 = time.time()
                metrics = api.train_one_round()
                times.append(time.time() - t0)
                samples.append(float(np.asarray(
                    api._last_metrics["count"]).sum()))
    finally:
        set_tracer(prev_tracer)
    phase_s = {name: round(float(np.median(durs)), 4)
               for name, durs in sorted(tracer.durations_by_name().items())}
    # XLA cost-model probe AFTER the measured rounds; an unavailable cost
    # analysis makes main() use the analytic constant, and the record's
    # flops_source says so
    image = 16 if args.smoke else 32
    flops_xla = train_step_flops_per_sample(api, image, args.batch_size)
    return {
        "round_s": float(np.median(times)),
        "times": times,
        "compile_s": compile_s,
        **compile_watch.record_fields(),
        "flops_per_sample_xla": flops_xla,
        "samples_per_round": float(np.mean(samples)),
        "train_acc": float(metrics["Train/Acc"]),
        "phase_s": phase_s,
    }


def _ragged_lr_clients(clients, dim=16, classes=4, seed=0):
    """Ragged synthetic population: lognormal shard sizes (the LDA-skew
    shape at population scale), tiny LR task -- the workload is the
    *cohort axis*, not the model, so a CPU host can smoke 50k clients."""
    rng = np.random.default_rng(seed)
    ns = np.clip(rng.lognormal(mean=2.0, sigma=1.0, size=clients),
                 1, 400).astype(np.int64)
    # one draw for the whole population, then per-client views: 50k
    # per-client RNG round-trips would dominate the setup time
    total = int(ns.sum())
    x = rng.standard_normal((total, dim)).astype(np.float32)
    y = rng.integers(0, classes, total).astype(np.int32)
    local, local_num = {}, {}
    off = 0
    for c in range(clients):
        n = int(ns[c])
        local[c] = {"x": x[off:off + n], "y": y[off:off + n]}
        local_num[c] = n
        off += n
    test = {"x": x[:256], "y": y[:256]}
    # the 8-tuple dataset contract (SURVEY.md section 1 L2)
    return [total, len(test["y"]), {"x": x, "y": y}, test, local_num,
            local, {0: test}, classes]


def run_massive_cohort(args):
    """``--massive_cohort [N]``: one-chip bucketed-streaming rounds over N
    ragged simulated clients (default 50,000), with buffered-async
    aggregation when ``--massive_async`` is set. Emits one BENCH_*-style
    JSON line whose headline is clients/sec."""
    import types

    import jax

    from fedml_tpu import models
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_classification_spec
    from fedml_tpu.observability.jaxmon import watch_compiles

    C = int(args.massive_cohort)
    dim, classes = 16, 4
    dataset = _ragged_lr_clients(C, dim=dim, classes=classes)
    import jax.numpy as jnp
    spec = make_classification_spec(
        models.LogisticRegression(num_classes=classes, apply_sigmoid=False),
        jnp.zeros((1, dim)))
    run_args = types.SimpleNamespace(
        client_num_in_total=C, client_num_per_round=C,
        comm_round=10 ** 9, epochs=1, batch_size=8, lr=0.05, wd=0.0,
        client_optimizer="sgd", frequency_of_the_test=10 ** 9, seed=0,
        client_chunk=args.massive_chunk, bucket_edges="geometric",
        async_agg=int(args.massive_async), buffer_k=args.buffer_k,
        staleness_decay=args.staleness_decay, async_window=4,
        device_resident="0", compressor=args.compressor)
    from fedml_tpu.observability.costmodel import CostModel, set_cost_model

    api = FedAvgAPI(dataset, spec, run_args)
    # XLA cost model armed for the whole run: per-bucket-shape FLOPs and
    # FLOP-weighted padding waste in the record. The per-edge AOT probes
    # compile during the warmup round (counted by `watcher`, dedup'd by
    # the persistent compile cache) and never touch the jit dispatch
    # cache, so steady_compiles and bucket_shapes stay honest.
    cost_model = CostModel()
    prev_cm = set_cost_model(cost_model)
    try:
        t0 = time.time()
        with watch_compiles() as watcher:
            api.train_one_round()  # compile + warmup (one program/bucket)
        compile_s = time.time() - t0
        rounds = max(1, args.rounds)
        times = []
        with watch_compiles() as steady_watcher:
            for _ in range(rounds):
                t0 = time.time()
                metrics = api.train_one_round()
                times.append(time.time() - t0)
    finally:
        set_cost_model(prev_cm)
    round_s = float(np.median(times))
    comp_tag = (f", {args.compressor} streaming-EF"
                if api.compressor is not None else "")
    out = {
        "metric": f"massive-cohort clients/sec (bucketed streaming, "
                  f"{C} ragged LR clients"
                  + (", async buffered" if args.massive_async else "")
                  + comp_tag + ")",
        "value": round(C / round_s, 1),
        "unit": "clients/sec",
        "compressor": (args.compressor if api.compressor is not None
                       else None),
        "clients_per_round": C,
        "rounds_measured": rounds,
        "round_s": round(round_s, 3),
        "compile_s": round(compile_s, 2),
        # compile-cache satellite: warm-cache runs show compiles ~0 here
        "warmup_compiles": watcher.total_compiles,
        "warmup_compile_s": round(watcher.total_compile_seconds, 2),
        "steady_compiles": steady_watcher.total_compiles,
        "bucket_shapes": api.runner.compiled_shapes(),
        "bucket_waste_frac": metrics.get("bucket/waste_frac"),
        "executed_steps": metrics.get("bucket/executed_steps"),
        "true_steps": metrics.get("bucket/true_steps"),
        "train_loss": round(float(metrics["Train/Loss"]), 4),
        "device": str(jax.devices()[0]),
    }
    binfo = api._last_info["bucket"]
    # per-bucket-shape attribution: step counts always, FLOPs when the
    # backend exposes cost analysis (flops_source tells which)
    out["per_bucket"] = [b for b in binfo["per_bucket"] if not b["skipped"]]
    if "executed_flops" in binfo:
        out["executed_flops"] = binfo["executed_flops"]
        out["true_flops"] = binfo["true_flops"]
        out["flops_waste_frac"] = binfo["flops_waste_frac"]
        out["flops_source"] = binfo["flops_source"]
        out["achieved_gflops"] = round(
            binfo["executed_flops"] / round_s / 1e9, 3)
    else:
        out["flops_source"] = "unavailable"
    if args.massive_async:
        out["async"] = {k.split("/", 1)[1]: v for k, v in metrics.items()
                        if k.startswith("async/")}
    if api.compressor is not None:
        # uplink accounting from the streaming-EF round (static per-client
        # encoded bytes x cohort; the EF convergence gate is tier-1)
        out["bytes_on_wire"] = metrics["bytes_on_wire"]
        out["compression_ratio"] = metrics["compression_ratio"]
    print(json.dumps(out), flush=True)
    if args.ledger:
        from fedml_tpu.observability.perfmon import append_ledger
        append_ledger(out, args.ledger)
    return 0


def _lm_analytic_flops_per_token(d, n_layers, seq, vocab):
    """Matmul-only train FLOPs/token (3x forward; causal attention at
    half cost) -- the cross-check fallback when the backend exposes no
    cost analysis (same derivation as scripts/bench_lm.py)."""
    fwd = n_layers * (24 * d * d + 2 * seq * d) + 2 * d * vocab
    return 3.0 * fwd


def run_lm_bench(args):
    """``--lm``: the federated LM flagship bench. LEAF Shakespeare
    (real via ``--lm_data_dir``, synthetic-shaped otherwise),
    TransformerLM over the fused flash-attention path, streamed through
    ``FedAvgAPI`` + ``BucketedStreamRunner`` -- the workload where the
    engine's measured 41.9% single-step MFU actually shows (ResNet-56 is
    shape-capped at ~20%; docs/PERFORMANCE.md round 8). Emits ONE
    JSON record whose headline is ``lm rounds/hour`` with cost-model
    MFU (``flops_source: xla-cost-model``), feeding the same
    ``--check-regress`` ledger as the CIFAR flagship."""
    import types

    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.specs import make_seq_classification_spec
    from fedml_tpu.data.shakespeare import (SEQUENCE_LENGTH, VOCAB_SIZE,
                                            synthetic_shakespeare_clients)
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.observability.costmodel import (CostModel, set_cost_model,
                                                   train_step_cost)
    from fedml_tpu.observability.jaxmon import watch_compiles

    d, L_layers, T = args.lm_d_model, args.lm_layers, args.lm_seq
    C, bs = args.lm_clients, args.lm_batch
    if T is None:
        T = SEQUENCE_LENGTH
    if args.smoke:
        d, L_layers, T, C = min(d, 64), min(L_layers, 2), min(T, 32), min(C, 8)
    if args.lm_data_dir:
        from fedml_tpu.data.shakespeare import load_shakespeare
        dataset = load_shakespeare(args.lm_data_dir, client_num=C,
                                   leaf=bool(args.lm_leaf))
        V = dataset[7]
        T = dataset[2]["x"].shape[1]
    else:
        V = VOCAB_SIZE
        dataset = synthetic_shakespeare_clients(C, T, V)
    n_heads = max(1, d // 128)  # head dim 128: the Pallas hardware path
    model = TransformerLM(vocab_size=V, n_layers=L_layers, n_heads=n_heads,
                          d_model=d, max_len=T, dtype=jnp.bfloat16)
    spec = make_seq_classification_spec(
        model, jnp.zeros((1, T), jnp.int32), name="lm")
    run_args = types.SimpleNamespace(
        client_num_in_total=C, client_num_per_round=C,
        comm_round=10 ** 9, epochs=args.lm_epochs, batch_size=bs,
        lr=3e-4, wd=0.0, client_optimizer="adam",
        frequency_of_the_test=10 ** 9, seed=0,
        client_chunk=args.lm_chunk, bucket_edges="geometric",
        device_resident="0")
    dev = jax.devices()[0]
    # no accelerator / unknown device: fail NOW, before measuring
    peak = None if args.platform == "cpu" else peak_flops(dev)

    cost_model = CostModel()
    prev_cm = set_cost_model(cost_model)
    try:
        api = FedAvgAPI(dataset, spec, run_args)
        warm_report = None
        t0 = time.time()
        with watch_compiles() as warm_watch:
            if args.warmup:
                # AOT warmup through the persistent cache BEFORE the
                # first dispatch (fedml_tpu.compile); its compiles land
                # in the warmup bucket, and over a warmed cache dir they
                # are hits (the warm-restart gate)
                from fedml_tpu.compile import warmup_api
                warm_report = warmup_api(api)
            api.train_one_round()
        compile_s = time.time() - t0
        rounds = 1 if args.smoke else max(1, args.rounds)
        times = []
        with watch_compiles() as steady_watch:
            for _ in range(rounds):
                t0 = time.time()
                metrics = api.train_one_round()
                times.append(time.time() - t0)
    finally:
        set_cost_model(prev_cm)
    round_s = float(np.median(times))
    rph = 3600.0 / round_s
    binfo = api._last_info["bucket"]
    tokens_round = binfo["true_steps"] * bs * T
    analytic = _lm_analytic_flops_per_token(d, L_layers, T, V)
    # MFU from the XLA cost model of the compiled bucket programs
    # (executed FLOPs, incl. padded lanes -- the honest device load);
    # the analytic matmul count stays on the record as the cross-check
    if "executed_flops" in binfo:
        achieved = binfo["executed_flops"] / round_s
        flops_source = "xla-cost-model"
    else:
        achieved = analytic * tokens_round / round_s
        flops_source = "analytic"
    # per-token train FLOPs of ONE compiled local step (train_step_cost):
    # the per-program complement of the executed-FLOPs MFU above
    batch_abs = {
        "x": jax.ShapeDtypeStruct((bs, T), jnp.int32),
        "y": jax.ShapeDtypeStruct((bs, T), jnp.int32),
        "mask": jax.ShapeDtypeStruct((bs,), jnp.float32)}
    pc = train_step_cost(api.spec, api.cfg, batch_abs)
    smoke_tag = " [SMOKE -- not baseline-comparable]" if args.smoke else ""
    out = {
        "metric": (f"federated-LM rounds/hour (TransformerLM d{d} "
                   f"L{L_layers} T{T} V{V}, bf16 flash-attn, {C} clients, "
                   f"bs{bs}, {args.lm_epochs} local epochs)" + smoke_tag),
        "value": round(rph, 2),
        "unit": "rounds/hour",
        "lm_rounds_per_hour": round(rph, 2),
        "round_s": round(round_s, 3),
        "rounds_measured": rounds,
        "tokens_per_round": int(tokens_round),
        "tokens_per_s": round(tokens_round / round_s),
        "achieved_tflops": round(achieved / 1e12, 3),
        **mfu_fields(achieved, peak, digits=6),
        "flops_source": flops_source,
        "analytic_flops_per_token": analytic,
        "compile_s": round(compile_s, 2),
        "warmup_compiles": warm_watch.total_compiles,
        "warmup_compile_s": round(warm_watch.total_compile_seconds, 2),
        "warmup_cache_hits": warm_watch.cache_hits,
        "warmup_cache_misses": warm_watch.cache_misses,
        "steady_compiles": steady_watch.total_compiles,
        "bucket_shapes": api.runner.compiled_shapes(),
        "bucket_waste_frac": metrics.get("bucket/waste_frac"),
        "train_loss": round(float(metrics["Train/Loss"]), 4),
        "n_params": sum(int(np.prod(x.shape)) for x in
                        jax.tree.leaves(api.global_state["params"])),
        "device": str(dev),
    }
    if pc is not None:
        out["train_flops_per_token_step_cost"] = pc.flops / (bs * T)
        out["step_cost_vs_analytic"] = round(
            pc.flops / (bs * T) / analytic, 3)
    if warm_report is not None:
        out["warmup_programs"] = warm_report["warmup/programs"]
        out["warmup_seconds"] = warm_report["warmup/seconds"]
    print(json.dumps(out), flush=True)
    if args.ledger:
        from fedml_tpu.observability.perfmon import append_ledger
        append_ledger(out, args.ledger)
    return 0


def _quality_rel(final, ref):
    """Max relative leaf deviation between two param pytrees (the
    steering bench's convergence-within-tolerance metric)."""
    num = max(float(np.max(np.abs(np.asarray(final[k], np.float64)
                                  - np.asarray(ref[k], np.float64))))
              for k in ref)
    den = max(max(float(np.max(np.abs(np.asarray(v, np.float64))))
                  for v in ref.values()), 1e-9)
    return num / den


def run_steering_bench(args):
    """``--steering``: the fedpace headline bench. One seeded diurnal
    trace (day/outage/night-with-correlated-dropouts/flash,
    ``resilience.faults.DiurnalTrace``), a small sweep of FIXED
    (deadline, overselect) configs, and one ``--pace_steering`` run --
    all over the real distributed control plane (``run_tcp_fedavg`` on
    ``--steering_transport``) with the perf monitor armed so the
    controller reads live ``fed_report_latency_seconds`` windows. Emits
    ONE JSON record whose headline is the steered rounds/hour, with the
    best *surviving, quality-qualified* fixed config's rounds/hour and
    the speedup beside it; feeds the ``--check-regress`` ledger.

    Why steering wins here (docs/RESILIENCE.md "Pace steering"): a
    fixed deadline must be long enough to survive the outage phase
    (shorter configs abandon ``max_round_retries+1`` times and FAIL the
    run -- recorded and disqualified), and then pays that long deadline
    on every night round, where correlated dropouts make the target
    unreachable and the round always runs to its deadline. The steered
    run backs off through the outage (abandon-backoff) and tightens the
    deadline to the live night tail."""
    import tempfile

    from fedml_tpu.observability import enable
    from fedml_tpu.program import CohortPolicy
    from fedml_tpu.resilience import (run_tcp_fedavg,
                                      PaceBounds, PaceController)
    from fedml_tpu.resilience.faults import DiurnalTrace, TraceLoadGen

    from fedml_tpu.resilience.faults import LoadPhase

    scale = float(args.steering_scale)
    if args.steering_trace:
        trace = DiurnalTrace.from_file(args.steering_trace)
    else:
        # one-shot curve: day -> flash crowd -> outage -> night, the
        # night holding to the end of the run (repeat=False). Every
        # round past the outage is a night round for EVERY config, so
        # the comparison is dominated by the regime the knobs exist
        # for -- a repeating trace would hand fixed configs free fast
        # rounds each dawn and turn the gate into a phase-alignment
        # lottery
        trace = DiurnalTrace([
            LoadPhase(dur_s=0.15 * scale, delay_s=0.05, jitter=0.5,
                      name="day"),
            LoadPhase(dur_s=0.1 * scale, delay_s=0.02, jitter=0.5,
                      name="flash"),
            LoadPhase(dur_s=5.5 * scale, delay_s=1.5, jitter=0.2,
                      name="outage"),
            LoadPhase(dur_s=600.0, delay_s=0.3, jitter=0.5,
                      dropout_p=0.5, name="night"),
        ], repeat=False, seed=args.steering_seed)
    world = 9
    cohort_target = 5
    quorum = 0.5
    rounds = int(args.steering_rounds)
    transport = args.steering_transport
    w0 = {"w": np.zeros((8, 8), np.float32), "b": np.ones(8, np.float32)}
    population = list(range(1, world))
    join_timeout = max(240.0, 60.0 * scale * rounds)

    def one_run(policy, pace=None, shaped=True):
        gen = (TraceLoadGen(trace, seed=args.steering_seed,
                            population=population) if shaped else None)
        d = tempfile.mkdtemp(prefix="bench_steering_")
        t0 = time.time()
        with enable(perfmon=True, flightrec_dir=d, compile_events=False):
            if gen is not None:
                gen.reset_epoch()
            try:
                srv = run_tcp_fedavg(
                    world, rounds, policy, w0, fault_plan=gen,
                    cohort_target=cohort_target, transport=transport,
                    pace_controller=pace, join_timeout=join_timeout)
            except TimeoutError as e:
                return {"failed": f"hung: {e}",
                        "wall_s": round(time.time() - t0, 3)}
        wall = time.time() - t0
        out = {"wall_s": round(wall, 3),
               "rounds_completed": len(srv.history),
               "degraded": srv.counters["rounds_degraded"],
               "abandoned": srv.counters["rounds_abandoned"]}
        if srv.failed is not None or len(srv.history) < rounds:
            out["failed"] = srv.failed or "incomplete"
            return out
        out["rph"] = round(rounds / wall * 3600.0, 2)
        out["final"] = srv.history[-1]
        return out

    # unshaped full-participation reference: the convergence yardstick
    ref = one_run(CohortPolicy(deadline_s=30.0, quorum=quorum),
                  shaped=False)
    assert "rph" in ref, f"reference run failed: {ref}"

    sweep_cfgs = [(0.6, 0.6), (1.2, 0.0), (2.5, 0.6)]
    quality_tol = float(args.steering_quality_tol)
    fixed = []
    for d_s, eps in sweep_cfgs:
        r = one_run(CohortPolicy(deadline_s=d_s, overselect=eps,
                                  quorum=quorum))
        r["config"] = {"deadline_s": d_s, "overselect": eps}
        if "rph" in r:
            r["quality_rel"] = round(_quality_rel(r.pop("final"),
                                                  ref["final"]), 4)
        fixed.append(r)
        print(f"# fixed {r['config']}: "
              + (f"{r['rph']} rph, quality {r['quality_rel']}"
                 if "rph" in r else f"FAILED ({r['failed']})"),
              file=sys.stderr)

    pace = PaceController(
        PaceBounds(deadline_s=(0.25, 8.0), overselect=(0.0, 1.0)),
        seed=args.steering_seed, deadline_s=1.0, overselect=0.0)
    steered = one_run(CohortPolicy(deadline_s=1.0, quorum=quorum),
                      pace=pace)
    if "rph" not in steered:
        emit_failure(f"steered run failed: {steered.get('failed')}",
                     metric="fedpace steered rounds/hour")
        return 1
    steered["quality_rel"] = round(_quality_rel(steered.pop("final"),
                                                ref["final"]), 4)

    qualified = [r for r in fixed
                 if "rph" in r and r["quality_rel"] <= quality_tol]
    best_fixed = max(qualified, key=lambda r: r["rph"]) if qualified \
        else None
    speedup = (round(steered["rph"] / best_fixed["rph"], 3)
               if best_fixed else None)
    threshold = 1.10  # the acceptance gate: >= 10% more rounds/hour
    ok = (steered["quality_rel"] <= quality_tol and best_fixed is not None
          and speedup is not None and speedup >= threshold)
    out = {
        "metric": (f"fedpace steered rounds/hour (seeded diurnal trace "
                   f"x{scale}, {transport}, {world - 1} clients, "
                   f"target {cohort_target})"),
        "value": steered["rph"],
        "unit": "rounds/hour",
        "rounds": rounds,
        "steered": steered,
        "pace_decisions": len(pace.decisions),
        "pace_final": {"deadline_s": pace.deadline_s,
                       "overselect": pace.overselect},
        "fixed_sweep": fixed,
        "best_fixed_rph": best_fixed["rph"] if best_fixed else None,
        "best_fixed_config": best_fixed["config"] if best_fixed else None,
        "speedup_vs_best_fixed": speedup,
        "speedup_threshold": threshold,
        "quality_tol": quality_tol,
        "trace": trace.to_dict(),
        "transport": transport,
        "pass": ok,
    }
    print(json.dumps(out), flush=True)
    if args.ledger:
        from fedml_tpu.observability.perfmon import append_ledger
        append_ledger(out, args.ledger)
    return 0 if ok else 1


def _soak_report_frame_nbytes(init_params, compressor=None):
    """Exact on-wire bytes of one swarm report frame for this model --
    plain (full params) or compressed (EF delta schema). Static given
    the template: encoded sizes are shape-only for every wire
    compressor, so the plain/compressed byte ratio needs no second
    measurement run."""
    from fedml_tpu.compression.codec import message_to_wire
    from fedml_tpu.compression.wire import (ef_step, encode_rng,
                                            host_compressor)
    from fedml_tpu.core.message import Message

    params = {k: np.asarray(v, np.float32) for k, v in init_params.items()}
    out = Message("res_report", 1, 0)
    comp = host_compressor(compressor)
    if comp is None:
        out.add("params", params)
    else:
        enc, _dec, _res = ef_step(
            comp, {k: np.zeros_like(v) for k, v in params.items()},
            None, encode_rng((0, 0, 0)))
        out.add("cdelta", enc)
        out.add("compressor", comp.spec)
    out.add("num_samples", 1.0)
    out.add("round", 0)
    out.add("attempt", 0)
    return len(message_to_wire(out))


def run_soak_bench(args):
    """``--soak [N]``: the event-loop control-plane bench. One JSON
    record: reports/sec headline, connection count, bytes-per-report
    (with the wire-compression reduction when --compressor is set), and
    the ``fed_report_latency_seconds`` tail -- the ledger's evidence
    that the transport keeps its connections/sec and latency behavior."""
    import tempfile

    from fedml_tpu.net.soak import run_soak
    from fedml_tpu.observability import enable

    n = int(args.soak)
    soak_params = {"w": np.zeros(int(args.soak_params), np.float32)}
    d = tempfile.mkdtemp(prefix="bench_soak_")
    status_path = os.path.join(d, "status.json")
    trace_file = None
    if args.soak_trace:
        from fedml_tpu.resilience.faults import DiurnalTrace
        if args.soak_trace == "diurnal":
            # the canonical arrival curve, dropout-free (every swarm
            # client replies -- the soak gates on report counts)
            trace_file = DiurnalTrace.example(dropout=0.0).to_file(
                os.path.join(d, "soak_trace.json"))
        else:
            trace_file = args.soak_trace
    t0 = time.time()
    with enable(perfmon=True, status_path=status_path,
                compile_events=False) as obs:
        server, summary = run_soak(
            n, total_updates=int(args.soak_updates),
            jitter_s=float(args.soak_jitter), trace_path=trace_file,
            join_timeout=max(300.0, n / 10.0),
            decode_workers=int(args.soak_decode_workers),
            init_params=soak_params, compressor=args.compressor)
    wall_s = time.time() - t0
    if server.failed is not None:
        print(json.dumps({"metric": "eventloop-soak", "error":
                          server.failed}), flush=True)
        return 1
    with open(status_path) as f:
        status = json.load(f)
    assert status.get("final") is True, status
    reports = server.counters["reports"]
    q = obs.registry.histogram_quantile
    # ingest-stage accounting (ISSUE 14): frames decoded + decode wall
    # seconds on the server transport -- decode-seconds-per-report is
    # the quantity the batched/parallel ingest pipeline exists to move
    ingest = server.com_manager.ingest_stats()
    decode_s_per_report = (ingest["decode_s"] / ingest["frames"]
                           if ingest["frames"] else None)
    # bytes-on-wire accounting (fedsqueeze headline): measured uplink
    # bytes per report on the server transport vs the STATIC plain-frame
    # floor for the same model -- wire_reduction is what --compressor
    # buys (>= 8x gated in ci.sh for qsgd)
    raw_frame = _soak_report_frame_nbytes(soak_params)
    this_frame = _soak_report_frame_nbytes(soak_params, args.compressor)
    measured_per_report = (server.com_manager.bytes_received / reports
                           if reports else None)
    comp_tag = (f", {summary['compressor']} compressed"
                if summary.get("compressor") else "")
    jitter_model = "diurnal-trace" if trace_file else "uniform"
    # the metric string carries the regime (report size, arrival model,
    # compressor): ledger lineages must never judge a diurnal-trace row
    # against a jitter-free one or a compressed row against plain
    out = {
        "metric": f"eventloop-soak reports/sec ({n} connections, "
                  f"{int(args.soak_params)}-float reports, "
                  f"{jitter_model}, async buffered{comp_tag})",
        "value": round(reports / wall_s, 1),
        "unit": "reports/sec",
        "compressor": summary.get("compressor"),
        "soak_params": int(args.soak_params),
        "report_frame_bytes": this_frame,
        "raw_report_frame_bytes": raw_frame,
        "measured_bytes_per_report": (round(measured_per_report, 1)
                                      if measured_per_report else None),
        "wire_reduction": (round(raw_frame / measured_per_report, 2)
                           if measured_per_report else None),
        "connections": summary.get("connections"),
        "connections_per_sec": round(n / wall_s, 1),
        "updates": server.agg.version,
        "reports": reports,
        "wall_s": round(wall_s, 3),
        "report_latency_p50_s": q("fed_report_latency_seconds", 0.5),
        "report_latency_p90_s": q("fed_report_latency_seconds", 0.9),
        "report_latency_p99_s": q("fed_report_latency_seconds", 0.99),
        "sheds": getattr(server.com_manager, "sheds", 0),
        "status_outcome": status.get("outcome"),
        "transport": "eventloop",
        "jitter_model": jitter_model,
        "swarm_dropped": summary.get("dropped", 0),
        "decode_workers": ingest["workers"],
        "ingest_frames": ingest["frames"],
        "ingest_decode_s": ingest["decode_s"],
        "decode_s_per_report": (round(decode_s_per_report, 9)
                                if decode_s_per_report else None),
    }
    print(json.dumps(out), flush=True)
    if args.ledger:
        from fedml_tpu.observability.perfmon import append_ledger
        append_ledger(out, args.ledger)
        if ingest["frames"] and ingest["decode_s"] > 0:
            # second ledger row: decode THROUGHPUT (frames per decode
            # second -- higher is better, so --check-regress's one-sided
            # gate fires on a decode slowdown even when wall-clock
            # reports/sec is masked by reply jitter)
            # the decode lineage carries the arrival model too: diurnal
            # bursts batch more frames per drain than uniform jitter, so
            # frames/decode-sec amortizes differently (measured ~0.8x
            # swing) -- regimes must not judge each other
            decode_rec = {
                "metric": f"eventloop-soak decode frames/sec "
                          f"({n} connections, {int(args.soak_params)}"
                          f"-float reports, {jitter_model}{comp_tag})",
                "value": round(ingest["frames"] / ingest["decode_s"], 1),
                "unit": "frames/decode-sec",
                "decode_workers": ingest["workers"],
                "ingest_frames": ingest["frames"],
                "decode_s_per_report": out["decode_s_per_report"],
            }
            print(json.dumps(decode_rec), flush=True)
            append_ledger(decode_rec, args.ledger)
        if out["compressor"] and out["wire_reduction"]:
            # third ledger row, compressed runs only: the measured
            # bytes-on-wire reduction as its own one-sided metric, so a
            # RATIO regression (compressor silently shipping fatter
            # frames) fires --check-regress even when reports/sec is
            # masked by reply jitter
            ratio_rec = {
                "metric": f"eventloop-soak wire reduction "
                          f"({n} connections, {out['compressor']})",
                "value": out["wire_reduction"],
                "unit": "x-vs-plain-frames",
                "report_frame_bytes": out["report_frame_bytes"],
                "raw_report_frame_bytes": out["raw_report_frame_bytes"],
                "measured_bytes_per_report":
                    out["measured_bytes_per_report"],
            }
            print(json.dumps(ratio_rec), flush=True)
            append_ledger(ratio_rec, args.ledger)
    return 0


def run_tree_soak_bench(args):
    """``--tree_soak [N]``: the process-tree federation bench
    (fedml_tpu.topology). N leaves shard across a REAL tree of edge
    processes (``--tree_fanout``), each bottom edge driving its own
    soak swarm; the coordinator folds the edges' (compressed) upstream
    reports. One JSON record: leaf reports/sec through the whole tree,
    supervision counters (a clean run kills nothing and leaves no
    zombies), and the per-tier status.json audit -- every tier must
    parse and agree on the RoundProgram's invariant core
    (topology.tree.manifest_core), which is the CI gate's evidence
    that per-tier steering evolved knobs without forking the program.
    run_tree itself appends the headline tree-soak row plus one
    reports/sec row per edge tier member to --ledger."""
    import tempfile

    from fedml_tpu.topology import TreeSpec, manifest_core, run_tree

    fanout = tuple(int(f) for f in str(args.tree_fanout).split(","))
    n = int(args.tree_soak)
    n_bottom = 1
    for f in fanout:
        n_bottom *= f
    leaves_per_edge = max(1, n // n_bottom)
    d = tempfile.mkdtemp(prefix="bench_tree_")
    trace_file = None
    if args.soak_trace:
        from fedml_tpu.resilience.faults import DiurnalTrace
        if args.soak_trace == "diurnal":
            trace_file = DiurnalTrace.example(dropout=0.0).to_file(
                os.path.join(d, "tree_trace.json"))
        else:
            trace_file = args.soak_trace
    steering = bool(args.tree_steering)
    spec = TreeSpec(
        fanout=fanout, leaves_per_edge=leaves_per_edge,
        total_updates=int(args.soak_updates),
        transport=args.tree_transport, compressor=args.compressor,
        trace=trace_file, jitter_s=float(args.soak_jitter),
        steering=steering,
        # the knobs behind the committed steered-diurnal number: a real
        # edge deadline so outage-dark leaves cannot wedge a round (the
        # abandon-retry path re-runs it backed off), a flush deadline
        # shorter than the outage so the coordinator's DEGRADED path is
        # exercised, and a tier envelope the controllers steer inside
        edge_deadline_s=8.0, flush_deadline_s=10.0,
        tier_bounds={"deadline_s": [0.25, 120.0]} if steering else {})
    init_params = {"w": np.zeros(int(args.soak_params), np.float32)}
    t0 = time.time()
    try:
        res = run_tree(spec, d, init_params=init_params,
                       join_timeout=max(300.0, n / 5.0),
                       ledger_path=args.ledger or None)
    except TimeoutError as e:
        print(json.dumps({"metric": "tree-soak", "error": str(e)}),
              flush=True)
        return 1
    wall_s = time.time() - t0
    server = res["server"]
    if server.failed is not None:
        print(json.dumps({"metric": "tree-soak",
                          "error": server.failed}), flush=True)
        return 1
    # the per-tier audit: one status.json per process in the tree, all
    # final, all carrying the SAME program core (steered knobs aside)
    expected_statuses = 1 + sum(
        int(np.prod(fanout[:t + 1])) for t in range(len(fanout)))
    cores = []
    for name, st in sorted(res["statuses"].items()):
        assert st.get("final") is True, (name, st.get("final"))
        cores.append(manifest_core(st["program"]))
    assert len(cores) == expected_statuses, (len(cores),
                                             expected_statuses)
    assert all(c == cores[0] for c in cores), "program cores diverged"
    total_reports = sum(s.get("reports", 0)
                        for ss in res["swarm_summaries"].values()
                        for s in ss)
    jitter_model = "diurnal-trace" if trace_file else "uniform"
    comp_tag = f", {args.compressor} upstream" if args.compressor else ""
    out = {
        "metric": f"tree-soak leaf reports/sec through bench "
                  f"({spec.n_leaves} leaves, fanout "
                  f"{'x'.join(map(str, fanout))}, {spec.transport}, "
                  f"{jitter_model}, "
                  f"{'steered' if steering else 'fixed'}{comp_tag})",
        "value": round(total_reports / max(wall_s, 1e-9), 1),
        "unit": "reports/sec",
        "leaves": spec.n_leaves,
        "fanout": list(fanout),
        "transport": spec.transport,
        "compressor": args.compressor,
        "jitter_model": jitter_model,
        "steering": steering,
        "updates": server.agg.version,
        "reports": total_reports,
        "statuses": len(cores),
        "program_cores_match": True,
        "respawned": res["respawned"],
        "killed": res["killed"],
        "zombies": res["zombies"],
        "clients_dropped": server.counters["clients_dropped"],
        "clients_rejoined": server.counters["clients_rejoined"],
        "wall_s": round(wall_s, 3),
    }
    print(json.dumps(out), flush=True)
    return 0 if res["zombies"] == 0 else 1


def _sweep_params(model_name):
    """Model-shaped ``params`` pytree on CPU (shapes are what matter)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu import models
    from fedml_tpu.algorithms.specs import make_classification_spec

    if model_name == "cnn":
        model = models.CNNOriginalFedAvg(only_digits=True)
        example = jnp.zeros((1, 28, 28, 1))
    else:
        model = models.resnet56(class_num=10)
        example = jnp.zeros((1, 32, 32, 3))
    spec = make_classification_spec(model, example)
    state = spec.init_fn(jax.random.PRNGKey(0))
    return state["params"]


def _json_list_nbytes(params):
    """Byte cost of the legacy JSON nested-list codec for this pytree."""
    import jax
    from fedml_tpu.core.message import params_to_lists
    return len(json.dumps(params_to_lists(
        jax.tree.map(np.asarray, params))).encode())


def run_compression_tools(args):
    """``--compression_sweep`` / ``--check``: host-side codec measurements
    (one JSON line each; returns a process exit code)."""
    import jax

    from fedml_tpu.compression import (encode_tree, decode_tree,
                                       get_compressor, tree_wire_nbytes)

    params = _sweep_params(args.sweep_model)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(params))
    raw_binary = tree_wire_nbytes(jax.tree.map(np.asarray, params))
    json_bytes = _json_list_nbytes(params)

    if args.check:
        ratio = json_bytes / raw_binary
        ok = ratio >= 5.0
        print(json.dumps({
            "metric": "codec size regression (none codec vs JSON lists, "
                      f"{args.sweep_model}-sized pytree)",
            "n_params": n_params, "json_list_bytes": json_bytes,
            "binary_bytes": raw_binary, "ratio": round(ratio, 2),
            "threshold": 5.0, "pass": ok}))
        return 0 if ok else 1

    rng = jax.random.PRNGKey(0)
    for spec_str in args.compressors.split(","):
        spec_str = spec_str.strip()
        comp = get_compressor(spec_str)
        compress = jax.jit(lambda t, r, c=comp: c.compress(t, r))
        decompress = jax.jit(lambda e, c=comp: c.decompress(e, params))
        enc = jax.block_until_ready(compress(params, rng))  # compile
        jax.block_until_ready(decompress(enc))
        enc_t, dec_t = [], []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            enc = jax.block_until_ready(compress(params, rng))
            enc_t.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(decompress(enc))
            dec_t.append(time.perf_counter() - t0)
        wire = encode_tree(jax.tree.map(np.asarray, enc))
        decode_tree(wire)  # the host decode path stays exercised
        print(json.dumps({
            "compressor": spec_str, "model": args.sweep_model,
            "n_params": n_params, "encoded_bytes": len(wire),
            "raw_binary_bytes": raw_binary, "json_list_bytes": json_bytes,
            "ratio_vs_binary": round(raw_binary / len(wire), 2),
            "ratio_vs_json": round(json_bytes / len(wire), 2),
            "encode_ms": round(1e3 * float(np.median(enc_t)), 2),
            "decode_ms": round(1e3 * float(np.median(dec_t)), 2)}),
            flush=True)
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes to validate the bench path quickly "
                        "(result is NOT comparable to the baseline)")
    p.add_argument("--rounds", type=int, default=3,
                   help="measured rounds (after one warmup/compile round)")
    p.add_argument("--epochs", type=int, default=FLAGSHIP_EPOCHS)
    p.add_argument("--clients", type=int, default=32)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--client_chunk", type=int, default=8,
                   help="clients per concurrent wave (HBM activation knob)")
    p.add_argument("--mode", type=int, default=3, choices=(0, 1, 2, 3),
                   help="3 = MXU-packed lanes (lane axis folded into "
                        "channels, models/lane_packed.py; default), 2 = "
                        "vmap packed lanes, 1 = size-sorted waves, "
                        "0 = flat")
    p.add_argument("--flat", action="store_true",
                   help="shorthand for --mode 0")
    p.add_argument("--no_augment", action="store_true",
                   help="drop the recipe's crop/flip/Cutout augmentation")
    p.add_argument("--lane_lowering", default=None,
                   choices=("auto", "blockdiag", "bgc"),
                   help="mode-3 per-lane conv strategy "
                        "(models/lane_packed.py): blockdiag (default, "
                        "behind the committed 114.5 rph number); "
                        "bgc = zero-redundancy batch-group convs "
                        "everywhere; auto = bgc for Ci<=32 stages, "
                        "block-diagonal for Ci=64")
    p.add_argument("--device_dtype", type=str, default=None,
                   choices=("bf16", "bfloat16"),
                   help="halve the HBM residency of the data")
    p.add_argument("--profile_dir", type=str, default=None,
                   help="write a jax.profiler trace of the measured rounds")
    p.add_argument("--algo", choices=("fedavg", "fedopt"), default="fedavg",
                   help="fedopt = same engine/shapes with a server-Adam "
                        "step on the pseudo-gradient (second bench line; "
                        "vs_baseline stays tied to the FedAvg baseline)")
    p.add_argument("--lm", action="store_true",
                   help="federated LM flagship bench: LEAF-Shakespeare-"
                        "shaped TransformerLM fine-tuning (flash "
                        "attention) through FedAvgAPI + the bucketed "
                        "streaming engine; one JSON record with "
                        "lm rounds/hour + cost-model MFU "
                        "(flops_source: xla-cost-model), feeding the "
                        "--check-regress ledger beside the CIFAR "
                        "flagship (docs/PERFORMANCE.md round 8)")
    p.add_argument("--lm_clients", type=int, default=32)
    p.add_argument("--lm_batch", type=int, default=4,
                   help="LM bench: sequences per local step")
    p.add_argument("--lm_epochs", type=int, default=1,
                   help="LM bench: local epochs per round (LEAF recipe)")
    p.add_argument("--lm_d_model", type=int, default=512,
                   help="LM bench: model width (heads of dim 128 -- the "
                        "Pallas hardware flash path)")
    p.add_argument("--lm_layers", type=int, default=4)
    p.add_argument("--lm_seq", type=int, default=None,
                   help="LM bench: sequence length (default: the LEAF "
                        "Shakespeare 80-char window)")
    p.add_argument("--lm_chunk", type=int, default=8,
                   help="LM bench: clients per streamed dispatch")
    p.add_argument("--lm_data_dir", type=str, default=None,
                   help="LM bench: real Shakespeare data (TFF h5 layout; "
                        "--lm_leaf 1 for LEAF JSON). Default: synthetic "
                        "LEAF-shaped shards (zero-egress environment)")
    p.add_argument("--lm_leaf", type=int, default=0)
    p.add_argument("--warmup", type=int, default=0,
                   help="AOT round-program warmup (fedml_tpu.compile) "
                        "before the first dispatch: every jitted round "
                        "program compiles through the persistent cache "
                        "up front, so warmed re-runs/restarts start in "
                        "cache-load time (the fedwarm gate)")
    p.add_argument("--massive_cohort", nargs="?", const=50_000, type=int,
                   default=None, metavar="N",
                   help="bucketed-streaming massive-cohort bench: one chip "
                        "runs rounds of N (default 50,000) ragged "
                        "simulated LR clients; emits a JSON record with "
                        "clients/sec, bucket-shape count and padded-waste "
                        "fraction (docs/PERFORMANCE.md round 6)")
    p.add_argument("--soak", nargs="?", const=1000, type=int,
                   default=None, metavar="N",
                   help="event-loop soak bench (fedml_tpu.net.soak): one "
                        "host drives N (default 1,000) swarm connections "
                        "through a real buffered-async server over the "
                        "selector transport; emits a JSON record with "
                        "connections/sec + reports/sec and the "
                        "fed_report_latency_seconds tail (p50/p90/p99) "
                        "-- the --check-regress ledger's control-plane "
                        "metric (docs/NETWORKING.md)")
    p.add_argument("--soak_updates", type=int, default=3,
                   help="soak bench: async server updates (flush windows)")
    p.add_argument("--soak_jitter", type=float, default=0.5,
                   help="soak bench: max seeded per-report reply jitter "
                        "in seconds (the latency histogram's tail)")
    p.add_argument("--soak_trace", type=str, default=None,
                   help="soak bench: replay a DiurnalTrace JSON file as "
                        "the swarm's reply model instead of uniform "
                        "--soak_jitter ('diurnal' = the built-in "
                        "day/outage/night/flash curve, dropout-free)")
    p.add_argument("--compressor", type=str, default=None,
                   help="wire/update compression spec for --soak and "
                        "--massive_cohort (e.g. 'qsgd', 'topk:0.01', "
                        "'signsgd'). --soak: swarm clients ship "
                        "EF-compressed report deltas over the real "
                        "eventloop wire (compression.wire, "
                        "sub-byte-packed qsgd codes); --massive_cohort: "
                        "the bucketed chunk program runs streaming-EF "
                        "(engine.py). Records gain bytes-on-wire + "
                        "reduction fields; the compressed rows land on "
                        "the ledger as their own metric strings")
    p.add_argument("--soak_params", type=int, default=16384,
                   help="soak bench: model floats per report (the "
                        "report payload is ~4x this in bytes "
                        "uncompressed; sized so byte effects are "
                        "measurable over the frame headers)")
    p.add_argument("--soak_decode_workers", type=int, default=1,
                   help="soak bench: parallel frame-decode workers on "
                        "the server transport (net/ingest.py DecodeStage"
                        "; 1 = inline dispatcher decode). Trajectories "
                        "are identical at any setting -- only decode "
                        "throughput moves (decode_s_per_report on the "
                        "record)")
    p.add_argument("--tree_soak", nargs="?", const=1000, type=int,
                   default=None, metavar="N",
                   help="process-tree soak bench (fedml_tpu.topology): "
                        "N (default 1,000) leaves sharded across a "
                        "REAL tree of edge processes (--tree_fanout), "
                        "the coordinator folding the edges' upstream "
                        "reports in this process; emits a JSON record "
                        "with tree-wide leaf reports/sec + supervision "
                        "counters and audits every tier's status.json "
                        "(parseable, matching program core) -- the "
                        "fedtree headline gate (docs/NETWORKING.md). "
                        "Reuses --soak_updates/--soak_jitter/"
                        "--soak_trace/--soak_params/--compressor")
    p.add_argument("--tree_fanout", type=str, default="2",
                   help="tree soak: comma-separated edge fan-out per "
                        "tier, root-first ('2' = 2 edges; '2,2' = "
                        "edges-of-edges, 4 bottom edges)")
    p.add_argument("--tree_transport", default="eventloop",
                   choices=("tcp", "eventloop"),
                   help="tree soak: transport for every star in the "
                        "tree")
    p.add_argument("--tree_steering", action="store_true",
                   help="tree soak: arm one PaceController per tier "
                        "(coordinator + every edge), edge bounds "
                        "clamped inside the coordinator's envelope")
    p.add_argument("--steering", action="store_true",
                   help="fedpace headline bench (resilience/steering.py):"
                        " on one seeded diurnal trace, run a small sweep "
                        "of fixed (deadline, overselect) configs and one "
                        "--pace_steering run over the real distributed "
                        "control plane; emit a JSON record with steered "
                        "rounds/hour, best-surviving-fixed rounds/hour "
                        "and the speedup, gated >= 1.10x with final-model"
                        " quality within tolerance; feeds the "
                        "--check-regress ledger (docs/RESILIENCE.md)")
    p.add_argument("--steering_rounds", type=int, default=20,
                   help="steering bench: federated rounds per run")
    p.add_argument("--steering_scale", type=float, default=1.0,
                   help="steering bench: trace duration multiplier "
                        "(smaller = faster, noisier)")
    p.add_argument("--steering_seed", type=int, default=7,
                   help="steering bench: trace/load-generator seed")
    p.add_argument("--steering_trace", type=str, default=None,
                   help="steering bench: DiurnalTrace JSON file to "
                        "replay (default: the built-in curve)")
    p.add_argument("--steering_transport", default="tcp",
                   choices=("tcp", "eventloop"),
                   help="steering bench: control-plane transport")
    p.add_argument("--steering_quality_tol", type=float, default=0.5,
                   help="steering bench: max relative final-model "
                        "deviation vs the unshaped full-participation "
                        "reference for a run to qualify")
    p.add_argument("--massive_async", type=int, default=0,
                   help="massive-cohort bench: run the buffered-async "
                        "aggregation path (--buffer_k/--staleness_decay)")
    p.add_argument("--massive_chunk", type=int, default=128,
                   help="massive-cohort bench: clients per streamed "
                        "dispatch (smaller = tighter trip counts in the "
                        "heavy tail, more dispatches; measured sweet spot "
                        "128 -- see docs/PERFORMANCE.md round 6)")
    p.add_argument("--buffer_k", type=int, default=2048,
                   help="massive-cohort bench: async buffer K")
    p.add_argument("--staleness_decay", type=float, default=0.5,
                   help="massive-cohort bench: async staleness exponent")
    p.add_argument("--compile_cache_dir", type=str, default=None,
                   help="persistent XLA compilation cache directory "
                        "(default <checkout>/.jax_cache; "
                        "JAX_COMPILATION_CACHE_DIR in the environment "
                        "wins over both)")
    p.add_argument("--ledger", type=str,
                   default="bench_results/ledger.jsonl",
                   help="perf-regression ledger: every perf run appends "
                        "its JSON record here (JSONL, append-only; '' "
                        "disables). --check-regress reads it")
    p.add_argument("--check-regress", "--check_regress",
                   dest="check_regress", action="store_true",
                   help="perf-regression gate: compare the ledger's "
                        "newest record against the median of its "
                        "same-metric predecessors; exit 1 when the "
                        "headline value drops below median*(1-band). "
                        "A fresh ledger (no predecessor) passes. Never "
                        "touches the accelerator")
    p.add_argument("--regress_band", type=float, default=None,
                   help="noise band for --check-regress (default 0.15: "
                        "15%% below the baseline median fails)")
    p.add_argument("--compression_sweep", action="store_true",
                   help="measure each --compressors spec on a "
                        "--sweep_model pytree (encoded bytes + "
                        "encode/decode latency; CPU, no accelerator)")
    p.add_argument("--check", action="store_true",
                   help="size-regression gate: binary none-codec framing "
                        "must be >=5x smaller than the JSON-list path for "
                        "a ResNet-sized pytree (exit 1 on regression)")
    p.add_argument("--sweep_model", choices=("resnet56", "cnn"),
                   default="resnet56")
    p.add_argument("--compressors", type=str,
                   default="none,topk:0.01,topk:0.1,randk:0.1,qsgd:8,"
                           "signsgd",
                   help="comma-separated specs for --compression_sweep")
    p.add_argument("--repeats", type=int, default=5,
                   help="timing repeats per spec in --compression_sweep")
    p.add_argument("--platform", choices=("default", "cpu"),
                   default="default",
                   help="cpu forces the host platform so the bench PATH "
                        "can be smoked without an accelerator; its record "
                        "carries no MFU and is not baseline-comparable")
    args = p.parse_args()

    if args.check_regress:
        # ledger-only gate: no jax import
        from fedml_tpu.observability.perfmon import (DEFAULT_REGRESS_BAND,
                                                     check_regression)
        band = (args.regress_band if args.regress_band is not None
                else DEFAULT_REGRESS_BAND)
        ok, detail = check_regression(args.ledger, band=band)
        print(json.dumps(detail), flush=True)
        sys.exit(0 if ok else 1)

    if args.compression_sweep or args.check:
        # host-side codec measurements: never touch the accelerator
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.exit(run_compression_tools(args))

    if args.steering:
        # control-plane bench: sockets + numpy (jax only inside the
        # fp64 fold), held to the host platform
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.exit(run_steering_bench(args))

    if args.soak:
        # control-plane bench: sockets + numpy (jax only inside the
        # server's fp64 fold), held to the host platform
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.exit(run_soak_bench(args))

    if args.tree_soak:
        # process-tree bench: the coordinator fold is the only jax
        # touch; every other tier is its own subprocess on CPU
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.exit(run_tree_soak_bench(args))

    if args.massive_cohort:
        # the workload is the cohort axis, not the model: runs on any
        # platform (CI smokes it on CPU; numbers are per-device honest)
        if args.platform == "cpu":
            import jax
            jax.config.update("jax_platforms", "cpu")
        from fedml_tpu.utils.compile_cache import enable_compilation_cache
        enable_compilation_cache(args.compile_cache_dir)
        sys.exit(run_massive_cohort(args))

    if args.lm:
        # the federated LM flagship: CPU-smokeable (flash attention runs
        # interpret-mode off-TPU), per-device honest numbers
        if args.platform == "cpu":
            import jax
            jax.config.update("jax_platforms", "cpu")
        from fedml_tpu.utils.compile_cache import enable_compilation_cache
        enable_compilation_cache(args.compile_cache_dir)
        sys.exit(run_lm_bench(args))

    if args.algo == "fedopt":
        global _FAILURE_METRIC
        _FAILURE_METRIC = "FedOpt rounds/hour (CIFAR-10-scale ResNet-56)"
    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    # budget scales with the workload: compile (~5 min worst) + one warmup
    # + measured rounds at a generous 5 min/round ceiling
    budget_s = max(45 * 60, 5 * 60 + (args.rounds + 1) * 5 * 60)
    watchdog = arm_watchdog(budget_s, f"{args.rounds} rounds")

    import jax

    from fedml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(args.compile_cache_dir)
    device = jax.devices()[0]
    # no accelerator / unknown device: fail NOW, before measuring
    peak = None if args.platform == "cpu" else peak_flops(device)
    mode = 0 if args.flat else args.mode
    # the configuration asked for is the one measured: a failure raises
    meas = measure(args, args.epochs, args.client_chunk, mode)

    round_s = meas["round_s"]
    rph = 3600.0 / round_s
    # FLOPs for the workload ACTUALLY run: primary source is the XLA
    # cost model of the compiled train step (measure() probed it);
    # fallback is the analytic constant, spatially scaled for the smoke
    # (16x16 scales every conv's cost by (16/32)^2). The analytic number
    # always rides the record as the cross-check anchor.
    image = 16 if args.smoke else 32
    analytic_flops = TRAIN_FLOPS_PER_SAMPLE * (image / 32) ** 2
    if meas.get("flops_per_sample_xla"):
        flops_per_sample = meas["flops_per_sample_xla"]
        flops_source = "xla-cost-model"
    else:
        flops_per_sample = analytic_flops
        flops_source = "analytic"
    epochs_run = 1 if args.smoke else args.epochs
    flops_round = meas["samples_per_round"] * flops_per_sample
    achieved = flops_round / round_s
    flagship = (not args.smoke and args.platform == "default"
                and args.epochs == FLAGSHIP_EPOCHS
                and args.clients == 32 and args.batch_size == 64)
    # step-batches actually executed per round (for per-step ms): samples/bs
    steps_round = meas["samples_per_round"] / args.batch_size

    result = {
        "metric": (f"{'FedOpt' if args.algo == 'fedopt' else 'FedAvg'} "
                   "rounds/hour (CIFAR-10-scale ResNet-56, "
                   f"{args.clients} clients, bs{args.batch_size}, "
                   f"{epochs_run} local epochs)"
                   + (" [SMOKE -- not baseline-comparable]" if args.smoke
                      else "")),
        "value": round(rph, 2),
        "unit": "rounds/hour",
        "vs_baseline": (round(rph / BASELINE_ROUNDS_PER_HOUR, 2)
                        if flagship else 0.0),
        "round_time_s": round(round_s, 3),
        "compile_s": round(meas["compile_s"], 1),
        "compile_count": meas["compile_count"],
        "compile_seconds": meas["compile_seconds"],
        "samples_per_round": meas["samples_per_round"],
        "ms_per_step_batch": round(1e3 * round_s / max(steps_round, 1), 3),
        "model_train_flops_per_sample": flops_per_sample,
        "flops_source": flops_source,
        "analytic_flops_per_sample": analytic_flops,
        "achieved_tflops": round(achieved / 1e12, 2),
        **mfu_fields(achieved, peak),
        "device": str(device),
        # median seconds per span name over the measured rounds
        # (fedml_tpu.observability fedtrace); "aggregate" is the
        # end-of-round device wait -- the honest compute attribution,
        # since dispatch is async
        "phase_timings_s": meas["phase_s"],
    }
    result["exec_mode"] = {3: "mxu-lanes", 2: "lanes", 1: "waves",
                           0: "flat"}[mode]
    if flops_source == "xla-cost-model":
        result["flops_vs_analytic"] = round(
            flops_per_sample / analytic_flops, 3)
    watchdog.cancel()
    print(json.dumps(result))
    if args.ledger:
        from fedml_tpu.observability.perfmon import append_ledger
        append_ledger(result, args.ledger)
    print(f"# times={[round(t, 2) for t in meas['times']]} "
          f"train_acc={meas['train_acc']:.3f} "
          f"wave_mode={mode}", file=sys.stderr)


if __name__ == "__main__":
    main()
