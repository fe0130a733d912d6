"""Shared experiment plumbing: flags, setup, and the checkpointed run loop.

The canonical flag set mirrors the reference
(``fedml_experiments/distributed/fedavg/main_fedavg.py:46-105``); TPU-native
additions (``--mesh``, ``--run_dir``, ``--checkpoint_dir``, ``--resume``,
``--profile_dir``) replace the GPU-placement flags
(``--gpu_server_num/--gpu_num_per_server``), which are accepted but ignored
so reference scripts still launch.
"""

from __future__ import annotations

import argparse
import logging
import os
import random

import numpy as np


def add_base_args(parser: argparse.ArgumentParser):
    p = parser
    p.add_argument("--model", type=str, default="lr",
                   help="model name (models/factory.py)")
    p.add_argument("--dataset", type=str, default="synthetic",
                   help="dataset name (data/registry.py)")
    p.add_argument("--data_dir", type=str, default=None)
    p.add_argument("--partition_method", type=str, default="hetero",
                   help="homo | hetero (LDA) | hetero-fix")
    p.add_argument("--partition_alpha", type=float, default=0.5)
    p.add_argument("--client_num_in_total", type=int, default=10)
    p.add_argument("--client_num_per_round", type=int, default=10)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--client_optimizer", type=str, default="sgd")
    p.add_argument("--lr", type=float, default=0.03)
    p.add_argument("--wd", type=float, default=0.0)
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--epochs", type=int, default=1,
                   help="local epochs per round")
    p.add_argument("--comm_round", type=int, default=10)
    p.add_argument("--is_mobile", type=int, default=0,
                   help="accepted for parity; device bridge uses the MQTT "
                        "comm backend regardless")
    p.add_argument("--frequency_of_the_test", type=int, default=5)
    p.add_argument("--gpu_server_num", type=int, default=1,
                   help="ignored (no GPU placement on TPU)")
    p.add_argument("--gpu_num_per_server", type=int, default=1,
                   help="ignored (no GPU placement on TPU)")
    p.add_argument("--ci", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data_augmentation", type=int, default=1,
                   help="train-time crop/flip/Cutout for the CIFAR family "
                        "(on-device; reference data_loader.py:57-76). "
                        "Default on, matching the reference transforms; "
                        "0 disables (CI equivalence runs)")
    # TPU-native controls
    p.add_argument("--mesh", type=int, default=0,
                   help="shard clients over an N-device mesh (0 = vmapped "
                        "single-device simulation)")
    p.add_argument("--wave_mode", type=int, default=1, choices=(0, 1, 2, 3),
                   help="device-resident rounds: 3 = MXU-packed lanes "
                        "(lane axis folded into channels, "
                        "models/lane_packed.py; an error for model "
                        "families without a packed lowering), 2 = packed "
                        "lanes (one dispatch, LPT-balanced), 1 = "
                        "size-sorted waves with dynamic trip counts "
                        "(default), 0 = flat single-program round "
                        "(A/B / debugging). 2/3 are errors together with "
                        "--device_resident 0, --compressor or "
                        "--bucket_edges/--async_agg, which bypass "
                        "residency")
    p.add_argument("--client_chunk", type=int, default=8,
                   help="clients per concurrent wave on the device-"
                        "resident path (HBM activation knob)")
    p.add_argument("--device_resident", type=str, default="auto",
                   help="auto | 0: keep client shards resident in HBM "
                        "when they fit (single-chip path)")
    p.add_argument("--device_data_cap_gb", type=float, default=2.0,
                   help="largest stacked dataset kept resident in HBM; "
                        "over it --wave_mode 0/1 stream host-packed "
                        "cohorts per round and --wave_mode 2/3 (lanes) "
                        "stop with an error")
    p.add_argument("--device_dtype", type=str, default=None,
                   choices=("bf16", "bfloat16"),
                   help="keep device-resident floating image data in "
                        "bfloat16 (half the HBM footprint; default keeps "
                        "source dtype; integer data is never cast)")
    p.add_argument("--compressor", type=str, default=None,
                   help="client-update compression spec "
                        "(fedml_tpu.compression): none | topk:R | randk:R "
                        "| qsgd:BITS | signsgd. Runs the error-feedback "
                        "compressed round and logs bytes_on_wire / "
                        "compression_ratio per round; default off")
    p.add_argument("--moe_experts", type=int, default=8,
                   help="expert count for --model moe_transformer")
    p.add_argument("--model_config", type=str, default=None,
                   help="JSON file of --model deepseek_v3: the family's "
                        "config.json keys (models/deepseek_v3.py)")
    p.add_argument("--model_dtype", type=str, default=None,
                   choices=("bf16", "bfloat16"),
                   help="compute-dtype for the model zoo: bf16 runs convs/"
                        "matmuls as 1-pass MXU ops (~2x step throughput on "
                        "CIFAR ResNets) while master params and the "
                        "optimizer stay fp32; default fp32")
    p.add_argument("--platform", type=str, default=None,
                   help="force a jax platform (e.g. cpu) from the "
                        "command line; JAX_PLATFORMS in the environment "
                        "does the same")
    p.add_argument("--run_dir", type=str, default=None,
                   help="metrics/summary output dir (wandb-summary analog)")
    p.add_argument("--enable_wandb", type=int, default=0)
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--save_frequency", type=int, default=10,
                   help="checkpoint every N rounds")
    p.add_argument("--resume", type=int, default=0,
                   help="resume from latest checkpoint in --checkpoint_dir")
    p.add_argument("--profile_dir", type=str, default=None,
                   help="write a jax.profiler trace of the round loop here")
    p.add_argument("--audit", type=int, default=0,
                   help="runtime retrace/transfer audit "
                        "(fedml_tpu.analysis.runtime): count jit "
                        "(re)traces per round and arm jax.transfer_guard "
                        "around the end-of-round sync; the report "
                        "(audit/retraces_per_round, "
                        "audit/transfer_guard_violations, ...) goes to the "
                        "metrics sink at the end of the run")
    p.add_argument("--compile_cache_dir", type=str, default=None,
                   help="persistent XLA compilation cache directory "
                        "(default <checkout>/.jax_cache; "
                        "JAX_COMPILATION_CACHE_DIR in the environment "
                        "wins over both). Warm-cache restarts load "
                        "executables instead of compiling, counted by "
                        "the CompileWatcher's cache hits/misses")
    p.add_argument("--warmup", type=int, default=0,
                   help="AOT round-program warmup (fedml_tpu.compile): "
                        "enumerate every jitted round function this run "
                        "will dispatch and compile them up front through "
                        "the persistent compilation cache, so a restarted "
                        "server (--resume) reloads executables in "
                        "cache-load time instead of recompiling 155-193 s "
                        "per config; the warmup report (programs, "
                        "seconds, cache hits/misses) goes to the metrics "
                        "sink")
    # resilience knobs (fedml_tpu.resilience): over-selection, report
    # deadline, quorum, simulated stragglers; --resume above is the
    # recovery half
    from fedml_tpu.resilience.integration import add_resilience_args
    add_resilience_args(p)
    # buffered-async aggregation + bucketed ragged streaming
    # (fedml_tpu.resilience.async_agg / parallel.engine
    # BucketedStreamRunner): the massive-cohort knobs
    from fedml_tpu.resilience.async_agg import add_async_args
    add_async_args(p)
    # closed-loop pace steering (fedml_tpu.resilience.steering): the
    # controller that consumes the perfmon histograms -- adapts
    # buffer_k/flush_deadline/deadline/overselect within --pace_*_bounds
    from fedml_tpu.resilience.steering import add_steering_args
    add_steering_args(p)
    # observability knobs (fedml_tpu.observability): span tracing, trace
    # export dir, control-plane flight recorder
    from fedml_tpu.observability import add_observability_args
    add_observability_args(p)
    # synthetic-dataset size overrides (CI / bench knobs; ignored by
    # file-backed loaders)
    p.add_argument("--n_train", type=int, default=None)
    p.add_argument("--n_test", type=int, default=None)
    p.add_argument("--image_size", type=int, default=None)
    return p


def setup(args, run_name=None):
    """Logging + seeds + metrics sink (reference ``main_fedavg.py:281-313``:
    proctitle, logging format, wandb init on rank 0, fixed seeds). Also
    brings up ``jax.distributed`` when the multi-host env vars are set
    (``FEDML_TPU_COORDINATOR`` et al. -- the mpirun-hostfile analog,
    SURVEY.md section 2.8); metrics sink writes on process 0 only, as the
    reference inits wandb on rank 0."""
    from fedml_tpu.parallel.multihost import (
        is_primary, maybe_initialize_distributed)
    from fedml_tpu.utils import MetricsLogger, init_logging

    if getattr(args, "platform", None):
        import jax
        jax.config.update("jax_platforms", args.platform)
    from fedml_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache(getattr(args, "compile_cache_dir", None))
    proc, nproc = maybe_initialize_distributed()
    init_logging(proctitle=run_name)
    logging.info("args = %s (process %d/%d)", vars(args), proc, nproc)
    random.seed(args.seed)
    np.random.seed(args.seed)
    if not is_primary():
        return _LogOnlySink()  # rank>0: no files; same call/close surface
    logger = MetricsLogger(
        run_dir=args.run_dir, enable_wandb=bool(args.enable_wandb),
        run_name=run_name, config=args)
    return logger


class _LogOnlySink:
    """Non-primary metrics sink: MetricsLogger call surface, no files."""

    def __call__(self, d):
        logging.info("%s", d)

    def close(self, *a, **kw):
        return None


def audit_scope(args, logger, wired=True):
    """``--audit`` context for the experiment mains: arms the runtime
    retrace/transfer auditor (``fedml_tpu.analysis.runtime.audit``) with
    the run's metrics sink. Mains whose algorithm loop has no
    ``end_of_round_sync`` interception point yet pass ``wired=False``:
    the flag then warns loudly instead of being silently ignored or
    producing a misleading zero-round report."""
    from fedml_tpu.analysis.runtime import audit

    enabled = bool(getattr(args, "audit", 0))
    if enabled and not wired:
        logging.warning(
            "--audit is not wired for this entry point (its round loop "
            "has no end_of_round_sync interception point yet); ignoring "
            "the flag")
        enabled = False
    return audit(metrics_logger=logger, enabled=enabled)


def observability_scope(args, logger):
    """``--trace/--flightrec/--perfmon/--costmodel`` context for the
    experiment mains: arms the fedtrace switchboard
    (``fedml_tpu.observability.enable``) with the run's metrics sink.
    Exports ``trace.json``/``spans.jsonl`` to ``--trace_dir`` (default
    ``--run_dir``), flight-recorder dumps, ``metrics.prom`` and
    ``status.json`` to ``--run_dir`` (else the trace dir); a run with
    every flag off keeps the default tracer (the recorder level: spans
    timed into a ring, nothing exported, nothing on the wire) and no
    other observability code on the hot paths."""
    from fedml_tpu.observability import enable

    trace = bool(getattr(args, "trace", 0))
    flightrec = bool(getattr(args, "flightrec", 0))
    perfmon = bool(getattr(args, "perfmon", 0))
    run_dir = getattr(args, "run_dir", None)
    trace_dir = getattr(args, "trace_dir", None) or run_dir
    if trace and trace_dir is None:
        trace_dir = "."
        logging.warning("--trace without --trace_dir/--run_dir: exporting "
                        "trace.json/spans.jsonl to the working directory")
    return enable(trace=trace, trace_dir=trace_dir,
                  flightrec=flightrec, flightrec_dir=run_dir or trace_dir,
                  metrics_logger=logger,
                  perfmon=perfmon,
                  status_path=getattr(args, "status_path", None),
                  xprof_dir=getattr(args, "xprof_dir", None),
                  xprof_round=getattr(args, "xprof_round", None),
                  cost_model=bool(getattr(args, "costmodel", 0)))


def race_audit_scope(args, logger):
    """``--race_audit`` context: arms the concurrency race sanitizer
    (``fedml_tpu.analysis.runtime.race_audit``). Locks the control plane
    creates inside the context are instrumented; the simulation path
    creates few (the vmapped rounds are single-threaded), so a zero
    report there is honest -- the TCP/chaos drivers are where the
    sanitizer bites (see the ci.sh chaos smoke)."""
    from fedml_tpu.analysis.runtime import race_audit

    return race_audit(enabled=bool(getattr(args, "race_audit", 0)),
                      metrics_logger=logger)


def make_mesh(args):
    if not getattr(args, "mesh", 0):
        return None
    import jax
    from fedml_tpu.parallel.mesh import make_client_mesh
    return make_client_mesh(args.mesh, devices=jax.devices()[:args.mesh])


def load_dataset_and_model(args):
    """Dataset switch + model factory (reference ``main_fedavg.py:108-252``)."""
    from fedml_tpu.data.registry import load_dataset
    from fedml_tpu.models.factory import create_model

    dataset = load_dataset(args, args.dataset)
    model = create_model(args, args.model, output_dim=dataset[7])
    return dataset, model


def example_train_data(dataset):
    """Pooled train set, or any client shard for loaders that keep data
    client-resident (Landmarks, VOC) and carry ``train_global=None``."""
    global_train = dataset[2]
    if global_train is None or "x" not in global_train:
        global_train = next(d for d in dataset[5].values()
                            if d is not None and len(d["y"]))
    return global_train


def make_spec(args, model, dataset):
    """Task-spec selection by dataset, mirroring the reference's
    dataset-keyed ModelTrainer choice
    (``fedml_experiments/standalone/fedavg/main_fedavg.py:269-275``)."""
    import jax.numpy as jnp
    from fedml_tpu.algorithms import specs

    example_x = jnp.asarray(example_train_data(dataset)["x"][:1])
    name = args.dataset
    if name in ("stackoverflow_nwp", "shakespeare", "fed_shakespeare",
                "synthetic_sequences"):
        return specs.make_seq_classification_spec(model, example_x)
    if name == "stackoverflow_lr":
        return specs.make_multilabel_spec(model, example_x)
    augment_fn = None
    if (getattr(args, "data_augmentation", 0)
            and name in ("cifar10", "cifar100", "cinic10")):
        from fedml_tpu.data.augment import make_cifar_augment
        from fedml_tpu.data.cifar import normalized_black
        # crop/flip for all three; Cutout(16) as in the reference pipeline;
        # crop borders filled with the normalized black level since shards
        # are stored post-normalization
        augment_fn = make_cifar_augment(pad=4, cutout_length=16,
                                        pad_fill=normalized_black(name))
    return specs.make_classification_spec(model, example_x,
                                          augment_fn=augment_fn)


def run_fedavg_family(api, args, logger):
    """Checkpoint-wired wrapper around ``FedAvgAPI.train`` shared by every
    FedAvg-family main: optional resume (restores model, server state, both
    RNG streams, and round index in O(1)), per-N-rounds checkpoint saves,
    and an optional profiler trace around the whole loop."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.utils import Checkpointer, profile_trace

    from fedml_tpu.parallel.multihost import is_primary, sync

    # EVERY process restores (round_idx / RNG streams / states must agree
    # across ranks or the SPMD schedules diverge); only process 0 SAVES.
    ckpt = None
    if args.checkpoint_dir:
        ckpt = Checkpointer(args.checkpoint_dir)
        if is_primary():
            ckpt.save_config(args)
        if args.resume:
            sync("pre-restore")  # saves from a prior run are fully flushed
            saved = ckpt.restore(server_state_template=api.server_state)
            if saved is not None:
                api.global_state = api.place_state(
                    jax.tree.map(jnp.asarray, saved["global_state"]))
                api.server_state = api.place_state(saved["server_state"])
                if saved["rng"] is not None:
                    api.rng = jnp.asarray(saved["rng"], dtype=jnp.uint32)
                if saved["data_rng"] is not None:
                    api._data_rng = saved["data_rng"]
                api.round_idx = saved["round_idx"]
                logging.info("resumed from round %d", api.round_idx)
                # surfaces in metrics.jsonl/summary.json next to the
                # res/* counters (resilience observability contract)
                logger({"round": api.round_idx, "res/resumes": 1})

    def on_round(api_, metrics):
        last = api_.round_idx == args.comm_round
        if (ckpt is not None
                and (api_.round_idx % args.save_frequency == 0 or last)):
            # EVERY process calls save (orbax CheckpointManager.save is a
            # collective under jax.process_count()>1 -- its internal
            # barriers would deadlock a primary-only call); payloads are
            # identical host numpy on all ranks (replicated pytrees
            # convert locally), and orbax writes from process 0
            to_np = lambda t: jax.tree.map(np.asarray, t)
            ckpt.save(api_.round_idx, to_np(api_.global_state),
                      server_state=to_np(api_.server_state),
                      rng=np.asarray(api_.rng),
                      metric=metrics.get(
                          getattr(api_, "checkpoint_metric", "Test/Acc")),
                      data_rng=api_._data_rng)

    if getattr(args, "warmup", 0):
        # AFTER any restore (a resumed server is exactly the warm-restart
        # case), BEFORE the round loop: every jitted round program is
        # AOT-compiled through the persistent cache, so over a warmed
        # --compile_cache_dir the run starts in cache-load time
        from fedml_tpu.compile import warm_restart
        logger(warm_restart(api, getattr(args, "compile_cache_dir", None)))

    with observability_scope(args, logger):
        with profile_trace(args.profile_dir,
                           enabled=args.profile_dir is not None):
            with race_audit_scope(args, logger):
                with audit_scope(args, logger):
                    api.train(on_round=on_round)
    if ckpt is not None:
        ckpt.close()
    return api.global_state
