"""FedAvg: the north-star algorithm (reference ``fedml_api/distributed/fedavg``
+ ``fedml_api/standalone/fedavg``).

One API class serves both reference paradigms: ``mesh=None`` runs the
vmapped single-chip simulation (semantics of ``fedavg_api.py:40-115``);
passing a mesh runs the shard_map/psum round (semantics of
``FedAVGAggregator.py:58-87`` + managers, minus the pickle transport).
"""

from __future__ import annotations

import contextlib
import logging
import time

import jax
import numpy as np

from fedml_tpu.algorithms.specs import block_diffusion_counters
from fedml_tpu.core.trainer import TrainSpec
from fedml_tpu.observability.perfmon import get_perf_monitor
from fedml_tpu.observability.routing import (layer_mix_counters,
                                              note_routing, routing_counters)
from fedml_tpu.observability.jaxmon import feed_tracer
from fedml_tpu.observability.tracing import RoundLog, get_tracer
from fedml_tpu.utils.profiling import end_of_round_sync
from fedml_tpu.parallel.engine import ClientUpdateConfig, make_eval_fn
# pack_schedule: the benchmark's planted fault patches it by this name too
# (benchmarks/tests/test_dry_run.py)
from fedml_tpu.parallel.packing import (  # noqa: F401 (re-export)
    pack_eval, pack_schedule)
from fedml_tpu.parallel.runners import select_runner
# the cohort-seed fold and the reference's seeded sampling now live in
# the program's cohort leg (the ONE definition shared by the simulation
# path and the distributed FSM -- the cross-path A/B and resume
# contracts depend on them agreeing); re-exported under their historical
# home for the many algorithm/test callers that import them from here
from fedml_tpu.program import RoundProgram
from fedml_tpu.program.cohort import (  # noqa: F401 (re-export)
    attempt_seed, client_sampling)


class FedAvgAPI:
    """Round-loop orchestrator.

    Args:
      dataset: the 8-tuple contract (SURVEY.md section 1 L2):
        [train_data_num, test_data_num, train_data_global, test_data_global,
         train_data_local_num_dict, train_data_local_dict,
         test_data_local_dict, class_num] where local dicts map
        client_idx -> {"x": np.ndarray, "y": np.ndarray}.
      spec: TrainSpec for the model/task.
      args: hyperparameters (client_num_per_round, comm_round, epochs,
        batch_size, lr, client_optimizer, wd, frequency_of_the_test, ci).
      mesh: optional jax Mesh -- enables the sharded round path.
      payload_fn / server_fn / server_state: aggregator hooks for algorithm
        variants (FedOpt, FedNova, robust FedAvg) built on this same loop.
      compressor: client-update compression spec (``"topk:0.01"``,
        ``"qsgd:8"``, ``"signsgd"``, ... -- ``fedml_tpu.compression``) or a
        Compressor instance; defaults to ``args.compressor``. Runs the
        compressed round with per-client error-feedback residuals and logs
        ``bytes_on_wire`` / ``compression_ratio`` per round. Simulation
        path only: on a mesh, aggregation is ICI collectives where the
        wire bottleneck this models does not exist.
    """

    def __init__(self, dataset, spec: TrainSpec, args, mesh=None,
                 payload_fn=None, server_fn=None, server_state=None,
                 metrics_logger=None, compressor=None):
        # from here on compile events are spans of the tracer's record,
        # each under the span that paid it (start-up has no watcher)
        feed_tracer()
        self._round_log = RoundLog()
        with get_tracer().span("build", api=type(self).__name__):
            self._build(dataset, spec, args, mesh, payload_fn, server_fn,
                        server_state, metrics_logger, compressor)

    def _build(self, dataset, spec, args, mesh, payload_fn, server_fn,
               server_state, metrics_logger, compressor):
        (self.train_data_num, self.test_data_num, self.train_data_global,
         self.test_data_global, self.train_data_local_num_dict,
         self.train_data_local_dict, self.test_data_local_dict,
         self.class_num) = dataset
        self.spec = spec
        self.args = args
        self.mesh = mesh
        self.metrics_logger = metrics_logger or (lambda d: logging.info("%s", d))

        cfg = ClientUpdateConfig(
            optimizer=getattr(args, "client_optimizer", "sgd"),
            lr=args.lr,
            weight_decay=getattr(args, "wd", 0.0),
            momentum=getattr(args, "momentum", 0.0),
            grad_clip=getattr(args, "grad_clip", None))
        self.cfg = cfg
        from fedml_tpu.compression import get_compressor
        compressor = get_compressor(
            compressor if compressor is not None
            else getattr(args, "compressor", None))

        # over-selection + simulated deadline misses (--overselect /
        # --straggler_p): cohort restriction IS the renormalized partial
        # aggregate, since the round fns weight by per-client sample counts
        from fedml_tpu.resilience.integration import SimResilience
        self.resilience = SimResilience.from_args(args)
        self._last_res_record = None
        # closed-loop pace steering for the simulation rounds
        # (--pace_steering, resilience/steering.py): adapts the
        # over-selection eps from the previous round's observed loss
        # fraction -- the sim has no wall clock, so the deadline knobs
        # stay put and the decision stream is a pure function of
        # (seed, trace), bitwise-reproducible across runs. None (the
        # default) is exactly today's sampling path.
        from fedml_tpu.resilience.steering import PaceController
        self.pace = PaceController.from_args(args)
        if self.pace is not None and self.resilience is None:
            logging.warning(
                "--pace_steering without --overselect/--straggler_p: the "
                "simulation rounds have no sampling loop to steer; "
                "ignoring the flag")
            self.pace = None

        seed = getattr(args, "seed", 0)
        self.rng = jax.random.PRNGKey(seed)
        tracer = get_tracer()
        with tracer.span("init-state"):
            global_state = spec.init_fn(jax.random.fold_in(self.rng, 0))
        self.round_idx = 0
        self.history = []

        # the ONE RoundProgram this API executes: the arg surface's
        # cohort/aggregation/codec legs as pure data (the distributed
        # control plane drives the same program through its host view --
        # the conformance suite pins the two consumers equal), and the
        # ONE runner that lowers it (parallel/runners.py: the execution
        # path is chosen there, once; it owns its feed and the host
        # stream ``_data_rng`` its schedules draw from). ``compressor``
        # is what the runner runs: None when the stream resolved
        # --compressor none to the plain chunk program
        self.program = RoundProgram.from_args(
            args, codec=compressor if compressor is not None else "none",
            client_update=(spec, cfg))
        with tracer.span("select-runner") as sp:
            self.runner = select_runner(
                self.program, spec, cfg, args, mesh,
                self.train_data_local_dict, global_state["params"],
                payload_fn=payload_fn, server_fn=server_fn,
                compressor=compressor,
                data_rng=np.random.default_rng(seed))
            sp.set(mode=self.runner.mode)
        self.compressor = self.runner.compressor
        # the state's way to the device, once (the runner has refused a
        # bogus combination by now): left on the host it would ride every
        # chunk program's dispatch again until the first server step
        # returns it from the device
        with tracer.span("init-state"):
            self.global_state = self.place_state(global_state)
            self.server_state = self.place_state(
                server_state if server_state is not None else ())
        self.eval_fn = make_eval_fn(spec)

    @property
    def _data_rng(self):
        """The host stream the runner's schedules draw from (checkpointed
        with the run; a restore assigns it)."""
        return self.runner.data_rng

    @_data_rng.setter
    def _data_rng(self, rng):
        self.runner.data_rng = rng

    def place_state(self, tree):
        """Put a global/server state pytree where the round functions
        return it: replicated over the mesh on the sharded paths (a state
        left on device 0 gives round 1 a new input sharding, and the whole
        round program compiles a second time), on the default device
        otherwise (where the rounds leave it; device arrays pass through
        as they are)."""
        if self.mesh is None:
            return jax.device_put(tree)
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.multihost import global_put
        return global_put(self.mesh, tree, P())

    def _sample_cohort(self, round_idx):
        """Cohort for one round: plain seeded sampling, or -- with
        resilience enabled -- over-selection trimmed to the reporting
        subset (``fedml_tpu.resilience.SimResilience.sample``)."""
        if self.resilience is None:
            self._last_res_record = None
            with get_tracer().span("cohort-select", round=int(round_idx)):
                return client_sampling(round_idx,
                                       len(self.train_data_local_dict),
                                       self.args.client_num_per_round)
        if self.pace is not None and self._last_res_record is not None:
            # steer BEFORE sampling: the previous round's loss fraction
            # decides this round's over-selection (within bounds); the
            # decision rides this round's record as pace/* fields
            import dataclasses
            prev = self._last_res_record
            # loss is the shortfall vs the aggregation target C (surplus
            # over-selection trimmed by "first C win" must not read as
            # loss, or eps ratchets on its own success)
            target = min(self.args.client_num_per_round,
                         len(self.train_data_local_dict))
            dec = self.pace.decide(
                outcome=("degraded" if prev["res/degraded"]
                         else "complete"),
                selected=target,
                reporting=min(prev["res/reporting"], target))
            self.resilience.policy = dataclasses.replace(
                self.resilience.policy, overselect=dec.overselect)
            # the program IS the round definition: steering evolves its
            # cohort leg in step so program readers see the live eps
            self.program = self.program.replace(
                cohort=dataclasses.replace(self.program.cohort,
                                           overselect=dec.overselect))
        # SimResilience.sample opens its own cohort-select span (carrying
        # the per-attempt selected/reporting attrs)
        client_indexes, record = self.resilience.sample(
            round_idx, len(self.train_data_local_dict),
            self.args.client_num_per_round)
        if self.pace is not None:
            record.update(self.pace.record())
        self._last_res_record = record
        return client_indexes

    def train_one_round(self):
        # span model (docs/OBSERVABILITY.md): where one jitted round fn is
        # dispatched asynchronously, "local-train" measures dispatch and
        # the device time lands in "aggregate" -- the end-of-round sync is
        # where the host waits for the round's outputs (the FL114 lesson).
        # The bucketed stream is the other case: run_round fetches every
        # chunk's weight and returns when the new state is ready, so
        # "local-train" holds the device wait and the fold (its fold.*
        # children say which) and "aggregate" is about 0
        tracer = get_tracer()
        mon = get_perf_monitor()  # one global read when monitoring is off
        t0 = time.time()
        self._round_log.begin()
        with (mon.xprof(self.round_idx) if mon is not None
              else contextlib.nullcontext()):
            with tracer.span("round", round=int(self.round_idx)) as rnd:
                train_metrics = self._traced_round_body(tracer, t0)
        # the record's two reports: the start-up ends at the first round
        # that compiles nothing; a later round far over its neighbours'
        # median is warned about once, with what grew
        self._round_log.end(
            tracer, rnd,
            self._last_info.get("bucket", {}).get("executed_steps"))
        if mon is not None:
            # true steps are known host-side only on the bucketed path;
            # elsewhere the per-step histogram is skipped rather than
            # forcing a device read the disabled path would not do
            steps = self._last_info.get("bucket", {}).get("true_steps")
            mon.observe_round(train_metrics["round_time_s"], steps=steps)
        self.round_idx += 1
        return train_metrics

    def _traced_round_body(self, tracer, t0):
        self.rng, round_rng = jax.random.split(self.rng)
        client_indexes = self._sample_cohort(self.round_idx)
        logging.info("round %d over %d clients", self.round_idx,
                     len(client_indexes))
        logging.debug("client_indexes = %s", client_indexes)
        if all(len(self.train_data_local_dict[i]["y"]) == 0
               for i in client_indexes):
            raise ValueError(f"round {self.round_idx}: every sampled "
                             f"client has an empty shard")
        with tracer.span("local-train", mode=self.runner.mode,
                         clients=len(client_indexes)) as sp:
            self.global_state, self.server_state, info = \
                self.runner.run_round(self.global_state, self.server_state,
                                      client_indexes, round_rng)
            if "fold" in info:
                # the stream's metric sums are on the host already;
                # "fold" says where the payload sums were combined
                sp.set(fold=info["fold"], **info["embed"],
                       **routing_counters(info["metrics"]),
                       **layer_mix_counters(info["metrics"]),
                       **block_diffusion_counters(info["metrics"]))
        self._last_info = info
        with tracer.span("aggregate"):
            end_of_round_sync(self.global_state)
        dt = time.time() - t0
        with tracer.span("report"):
            from fedml_tpu.parallel.multihost import gather_metrics
            m = gather_metrics(info["metrics"])
        self._last_metrics = m  # full summed-metrics pytree for subclasses
        train_metrics = {
            "round": self.round_idx,
            "Train/Loss": float(m["loss_sum"].sum() / max(m["count"].sum(), 1)),
            "Train/Acc": float(m["correct"].sum() / max(m["count"].sum(), 1)),
            "round_time_s": dt,
        }
        train_metrics.update(note_routing(m))  # {} unless experts routed
        if self._last_res_record is not None:
            train_metrics.update(self._last_res_record)
        b = info.get("bucket")
        if b is not None:
            train_metrics.update({
                "bucket/clients": b["clients"],
                "bucket/shapes": b["buckets_used"],
                "bucket/chunks": b["chunks"],
                "bucket/executed_steps": b["executed_steps"],
                "bucket/true_steps": b["true_steps"],
                "bucket/waste_frac": b["waste_frac"],
            })
            if "executed_flops" in b:
                # XLA cost-model attribution (armed via set_cost_model /
                # --costmodel): padded waste in FLOPs from the programs
                # actually compiled, per round
                train_metrics.update({
                    "bucket/executed_flops": b["executed_flops"],
                    "bucket/true_flops": b["true_flops"],
                    "bucket/flops_waste_frac": b["flops_waste_frac"],
                })
        # buffer-depth/staleness series ride every round record on async
        # runs (metrics.jsonl observability contract) even when the
        # registry is off; the uplink bytes every compressed round. Set
        # directly on the record (callers read the returned dict;
        # count_wire is the transports' path and would double-report)
        train_metrics.update(info.get("async") or {})
        train_metrics.update(info.get("wire") or {})
        # round_idx advances in train_one_round (after the round span ends)
        return train_metrics

    def _packed_global_eval(self):
        """Global test set packed ONCE (shared by every evaluate_global,
        incl. subclasses). Small packs additionally stay device-resident
        PERMANENTLY -- gated to 25% of ``device_data_cap_gb`` so the
        steady-state HBM reservation is bounded; configs tuned to the full
        cap should lower it or raise the cap. Large packs cache host-side
        (skipping the re-pack, still re-uploading per eval)."""
        if not hasattr(self, "_eval_packed"):
            packed = pack_eval(self.test_data_global, self.args.batch_size)
            nbytes = sum(v.nbytes for v in packed.values())
            cap = 0.25 * float(
                getattr(self.args, "device_data_cap_gb", 2.0)) * 1e9
            if nbytes <= cap:
                import jax.numpy as jnp
                packed = {k: jnp.asarray(v) for k, v in packed.items()}
            self._eval_packed = packed
        return self._eval_packed

    def evaluate_global(self):
        m = jax.tree.map(np.asarray, self.eval_fn(
            self.global_state, self._packed_global_eval()))
        return {"Test/Loss": float(m["loss_sum"] / max(m["count"], 1)),
                "Test/Acc": float(m["correct"] / max(m["count"], 1))}

    def evaluate_local(self, max_clients=None):
        """Per-client eval on local test shards (reference
        ``_local_test_on_all_clients``, ``fedavg_api.py:117-180``; ``--ci``
        short-circuits to one client, ``fedavg_api.py:157-162``)."""
        if getattr(self.args, "ci", 0):
            max_clients = 1
        totals = None
        for i, d in self.test_data_local_dict.items():
            if max_clients is not None and i >= max_clients:
                break
            if d is None or len(d["y"]) == 0:
                continue
            packed = pack_eval(d, self.args.batch_size)
            m = jax.tree.map(np.asarray, self.eval_fn(self.global_state, packed))
            totals = m if totals is None else jax.tree.map(np.add, totals, m)
        if totals is None:
            return {}
        return {"Test/Loss": float(totals["loss_sum"] / max(totals["count"], 1)),
                "Test/Acc": float(totals["correct"] / max(totals["count"], 1))}

    def train(self, on_round=None):
        """Full training loop (reference ``fedavg_api.py:40-81``): per-round
        cohort sampling, local training, aggregation; eval every
        ``frequency_of_the_test`` rounds and on the final round. Starts at
        ``self.round_idx`` so a checkpoint-restored API resumes mid-run.

        ``on_round(api, metrics)`` is called after each round -- the
        checkpoint/extra-eval hook used by the experiment mains. Each round
        is annotated as a ``jax.profiler`` step so traces segment cleanly.
        """
        from fedml_tpu.utils.profiling import annotate_step, off_round_work

        freq = getattr(self.args, "frequency_of_the_test", 5)
        while self.round_idx < self.args.comm_round:
            with annotate_step(self.round_idx):
                metrics = self.train_one_round()
            last = self.round_idx == self.args.comm_round
            if self.round_idx % freq == 0 or last:
                # eval runs between round syncs: book its (first-time)
                # compile as off-round so the auditor never charges it to
                # the next round's retrace bucket. The span carries the
                # TRAINED round (round_idx already advanced) so it joins
                # the same round as the metrics record it lands in.
                with get_tracer().span(
                        "eval", round=int(metrics.get("round",
                                                      self.round_idx - 1))):
                    with off_round_work():
                        metrics.update(self.evaluate_global())
            self.metrics_logger(metrics)
            self.history.append(metrics)
            if on_round is not None:
                on_round(self, metrics)
        return self.global_state
