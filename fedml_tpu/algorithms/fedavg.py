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

from fedml_tpu.core.trainer import TrainSpec
from fedml_tpu.observability.perfmon import get_perf_monitor
from fedml_tpu.observability.routing import note_routing, routing_counters
from fedml_tpu.observability.tracing import get_tracer
from fedml_tpu.utils.profiling import end_of_round_sync
from fedml_tpu.parallel.engine import (
    ClientUpdateConfig, LaneRunner, ShardedLaneRunner, WaveRunner,
    make_indexed_sim_round, make_eval_fn)
from fedml_tpu.parallel.mesh import shard_cohort  # noqa: F401 (re-export)
from fedml_tpu.parallel.packing import (
    pack_cohort, pack_eval, pack_schedule, stack_clients)
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
        self.compressor = get_compressor(
            compressor if compressor is not None
            else getattr(args, "compressor", None))
        if self.compressor is not None and mesh is not None:
            raise ValueError(
                "compressor= applies to the single-chip simulation and the "
                "distributed control-plane paths; mesh rounds aggregate "
                "over ICI collectives, where the wire bottleneck being "
                "compressed does not exist")
        # Bucketed ragged streaming + optional buffered-async aggregation
        # (--bucket_edges / --async_agg): the massive-cohort path. Clients
        # are bucketed by local step count, streamed chunk-by-chunk
        # through one compiled program per bucket shape, and folded on
        # host in fp64 -- the cohort axis is unbounded (engine.py
        # BucketedStreamRunner; docs/PERFORMANCE.md round 6). Composes
        # with --compressor (streaming-EF: the chunk program compresses
        # each lane's update delta with per-client error feedback).
        # Validated BEFORE any round fn is built: a bogus mesh combo
        # must fail loudly here, not deep in shard_map.
        self.bucket_runner = None
        self.async_agg = None
        from fedml_tpu.program import AggregationPolicy
        async_policy = AggregationPolicy.from_args(args)
        use_buckets = (getattr(args, "bucket_edges", None) is not None
                       or async_policy is not None)
        if use_buckets:
            if mesh is not None:
                raise ValueError(
                    "--bucket_edges/--async_agg run the single-chip "
                    "bucketed streaming path; it does not compose with "
                    "--mesh (the sharded-lane path owns multi-chip)")
            if (self.compressor is not None
                    and self.compressor.name == "none"):
                # the identity compressor has no wire transform to
                # stream: keep the plain chunk program so --compressor
                # none stays bitwise-identical to no flag at all
                logging.info("bucketed streaming: --compressor none is "
                             "the identity -- running the plain chunk "
                             "program (bitwise)")
                self.compressor = None

        # the ONE RoundProgram this API executes: the arg surface's
        # cohort/aggregation/codec legs as pure data, jitted below via
        # compile_sim / compile_bucketed (the distributed control plane
        # drives the same program through its host view -- the
        # conformance suite pins the two consumers equal). Built AFTER
        # the --compressor none bucketed identity resolution so the
        # codec leg matches what actually runs.
        self.program = RoundProgram.from_args(
            args,
            codec=(self.compressor if self.compressor is not None
                   else "none"),
            client_update=(spec, cfg))
        self._host = self.program.host_view()

        self.compressed_round_fn = None
        if mesh is None:
            self.round_fn = self.program.compile_sim(
                spec, cfg, payload_fn, server_fn, compressed=False)
            if self.compressor is not None and not use_buckets:
                # the resolved instance is passed through: CodecSpec
                # coercion would re-derive it from the spec string and
                # drop instance-level configuration
                self.compressed_round_fn = self.program.compile_sim(
                    spec, cfg, payload_fn, server_fn, compressed=True,
                    compressor=self.compressor)
        else:
            self.round_fn = self.program.compile_sim(
                spec, cfg, payload_fn, server_fn, mesh=mesh)
        self.eval_fn = make_eval_fn(spec)

        if use_buckets:
            from fedml_tpu.parallel.packing import (_steps_for,
                                                    parse_bucket_edges)
            # edges are sized from the POPULATION max so bucket shapes --
            # and therefore compiled programs -- are stable across rounds
            # no matter which cohort is sampled
            pop_ns = [int(v)
                      for v in self.train_data_local_num_dict.values()]
            eff_bs = (args.batch_size
                      if args.batch_size not in (-1, 0)
                      else max(1, max(pop_ns)))
            s_max = max(_steps_for(max(n, 1), eff_bs, args.epochs)
                        for n in pop_ns)
            edges = parse_bucket_edges(
                getattr(args, "bucket_edges", None), s_max)
            # pass the RESOLVED batch size: -1 (full-batch) must pin to
            # the population max, not each cohort's, or re-sampled
            # cohorts change the compiled [C, S, B] shape
            self.bucket_runner = self.program.compile_bucketed(
                spec, cfg, payload_fn, server_fn,
                compressor=self.compressor,
                client_chunk=getattr(args, "client_chunk", 8) or 8,
                batch_size=eff_bs, epochs=args.epochs, edges=edges)
            if async_policy is not None:
                self.async_agg = self._host.make_aggregator()
                self._async_window = async_policy.async_window

        # Device-resident data path (single-chip): upload every client's
        # padded shard to HBM once; per-round host work shrinks to an index
        # schedule. Auto-enabled when the stacked arrays fit the cap.
        self.device_data = None
        self.sharded_lane_runner = None
        device_resident = getattr(args, "device_resident", "auto")
        if str(device_resident).lower() in ("0", "false", "none", ""):
            device_resident = False
        chunk = getattr(args, "client_chunk", 8) or 8
        wave_mode = int(getattr(args, "wave_mode", 1))
        # stacking copies the whole dataset host-side: only do it for the
        # paths that will consume it (single-chip residency, or mesh lanes);
        # compressed rounds thread EF residuals, which only the packed-
        # cohort round function does -- residency is bypassed there
        wants_residency = (device_resident and self.compressor is None
                           and self.bucket_runner is None
                           and (mesh is None or wave_mode in (2, 3)))
        if wave_mode in (2, 3):
            # lanes only exist over device-resident data: an option that
            # bypasses residency would run the host-packed, compressed or
            # bucketed round under the requested mode's name
            bypass = ("--device_resident 0" if not device_resident
                      else "--compressor" if self.compressor is not None
                      else "--bucket_edges/--async_agg"
                      if self.bucket_runner is not None else None)
            if bypass is not None:
                raise ValueError(
                    f"--wave_mode {wave_mode} runs lanes over device-"
                    f"resident data, which {bypass} bypasses; drop one of "
                    "the two (--wave_mode 1 is the default)")
            if wave_mode == 3 and spec.lane_loss_builder is None:
                raise ValueError(
                    f"--wave_mode 3 (MXU-packed lanes) needs a model "
                    f"family with a lane-packed lowering "
                    f"(models/lane_packed.py); spec '{spec.name}' has none "
                    "-- use --wave_mode 2 for the generic vmap lanes")
        stacked, nbytes = (self._stack_if_fits(args) if wants_residency
                           else (None, 0))
        if stacked is None and wave_mode in (2, 3):
            raise ValueError(
                f"--wave_mode {wave_mode} runs lanes over device-resident "
                f"data, but the stacked client shards need "
                f"{nbytes / 1e9:.2f} GB and --device_data_cap_gb is "
                f"{float(getattr(args, 'device_data_cap_gb', 2.0)):g}; "
                "raise the cap or use --wave_mode 1")
        self.packed_lane_runner = None
        if stacked is not None and mesh is None:
            import jax.numpy as jnp
            self.device_data = {"x": jnp.asarray(stacked["host"]["x"]),
                                "y": jnp.asarray(stacked["host"]["y"])}
            self._client_ns = stacked["n"]
            # execution modes for device-resident rounds (--wave_mode):
            # 3 = MXU-packed lanes (lane axis folded into channels,
            # models/lane_packed.py), 2 = packed lanes (one dispatch,
            # LPT-balanced), 1 = size-sorted waves (default), 0 = flat
            # single program (A/B / debugging)
            self.wave_runner = WaveRunner(
                spec, cfg, payload_fn, server_fn, client_chunk=chunk)
            self.lane_runner = LaneRunner(
                spec, cfg, payload_fn, server_fn, n_lanes=chunk)
            if wave_mode == 3:
                self.packed_lane_runner = LaneRunner(
                    spec, cfg, payload_fn, server_fn, n_lanes=chunk,
                    packed=True)
            self.indexed_round_fn = make_indexed_sim_round(
                spec, cfg, payload_fn, server_fn,
                client_chunk=getattr(args, "client_chunk", None))
        elif stacked is not None:
            # mesh + lanes: client rows live SHARDED over the mesh's
            # clients axis; each shard runs its residents as packed lanes
            # and aggregation is one psum (ShardedLaneRunner); wave_mode 3
            # additionally folds each shard's lane axis into channels
            # (MXU-shaped lowering)
            from fedml_tpu.parallel.multihost import global_cohort
            host = stacked["host"]
            placed = global_cohort(mesh, {"x": host["x"], "y": host["y"]})
            self.device_data = {"x": placed["x"], "y": placed["y"]}
            self._client_ns = stacked["n"]
            self.sharded_lane_runner = ShardedLaneRunner(
                spec, cfg, mesh, payload_fn, server_fn, n_lanes=chunk,
                packed=wave_mode == 3)
        self.server_state = self.place_state(
            server_state if server_state is not None else ())

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
        self.global_state = self.place_state(
            spec.init_fn(jax.random.fold_in(self.rng, 0)))
        self._data_rng = np.random.default_rng(seed)
        self.round_idx = 0
        self.history = []

        if self.compressor is not None:
            from fedml_tpu.compression import (ResidualStore,
                                               compressed_payload_nbytes,
                                               raw_payload_nbytes)
            # error-feedback residual per client IN TOTAL, carried across
            # rounds (clients keep their own accumulator between the rounds
            # they are sampled into -- DGC/EF-SignSGD semantics). Keyed by
            # STABLE client id, never cohort slot: re-sampled cohorts must
            # not cross-contaminate accumulators (regression-pinned in
            # tests/test_compression.py). Shared by the packed compressed
            # round and the bucketed streaming-EF path: dense device rows
            # when the population fits dense_cap_gb, lazy host spill
            # beyond (the unbounded-population contract)
            self._ef_store = ResidualStore(
                self.global_state["params"],
                num_clients=len(self.train_data_local_dict),
                dense_cap_gb=float(getattr(args, "device_data_cap_gb",
                                           2.0)))
            # on-wire cost per client update: static given the template, so
            # computed once from abstract shapes (nothing runs on device)
            self._payload_bytes = compressed_payload_nbytes(
                self.compressor, self.global_state["params"])
            self._raw_payload_bytes = raw_payload_nbytes(
                self.global_state["params"])

    def place_state(self, tree):
        """Put a global/server state pytree where the round functions
        return it: replicated over the mesh on the sharded paths (a state
        left on device 0 gives round 1 a new input sharding, and the whole
        round program compiles a second time), untouched otherwise."""
        if self.mesh is None:
            return tree
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.multihost import global_put
        return global_put(self.mesh, tree, P())

    def _stack_if_fits(self, args):
        """Stack every client's padded shard for HBM residency when the
        result fits ``device_data_cap_gb``. Applies the optional bf16 cast
        (floating x only -- token ids would be corrupted). Returns
        ``(stacked, nbytes)``: ``{"host": {"x","y"} numpy (cast applied),
        "n": [C]}`` or None when over the cap, and the stack's size."""
        import jax.numpy as jnp

        C = len(self.train_data_local_dict)
        n_max = max(1, max(len(d["y"])
                           for d in self.train_data_local_dict.values()))
        x0 = np.asarray(self.train_data_local_dict[0]["x"])
        y0 = np.asarray(self.train_data_local_dict[0]["y"])
        ddt = getattr(args, "device_dtype", None)
        cast_bf16 = (ddt in ("bf16", "bfloat16")
                     and np.issubdtype(x0.dtype, np.floating))
        x_itemsize = 2 if cast_bf16 else x0.dtype.itemsize
        row = (int(np.prod(x0.shape[1:], dtype=np.int64)) * x_itemsize
               + int(np.prod(y0.shape[1:], dtype=np.int64) or 1)
               * y0.dtype.itemsize)
        nbytes = C * n_max * row
        cap = float(getattr(args, "device_data_cap_gb", 2.0)) * 1e9
        if nbytes > cap:
            return None, nbytes
        stacked = stack_clients(
            [self.train_data_local_dict[i] for i in range(C)])
        xh = (np.asarray(stacked["x"], dtype=jnp.bfloat16) if cast_bf16
              else stacked["x"])
        return ({"host": {"x": xh, "y": stacked["y"]}, "n": stacked["n"]},
                nbytes)

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
            self._host = self.program.host_view()
        # SimResilience.sample opens its own cohort-select span (carrying
        # the per-attempt selected/reporting attrs)
        client_indexes, record = self.resilience.sample(
            round_idx, len(self.train_data_local_dict),
            self.args.client_num_per_round)
        if self.pace is not None:
            record.update(self.pace.record())
        self._last_res_record = record
        return client_indexes

    def _cohort(self, round_idx):
        client_indexes = self._sample_cohort(round_idx)
        logging.info("client_indexes = %s", client_indexes)
        datasets = [self.train_data_local_dict[i] for i in client_indexes]
        if all(len(d["y"]) == 0 for d in datasets):
            raise ValueError(
                f"round {round_idx}: every sampled client has an empty shard")
        # "broadcast" in the sim: packing + placing the cohort's data is
        # the host->device half of what a distributed round sends out
        with get_tracer().span("broadcast", clients=len(client_indexes)):
            packed = pack_cohort(datasets, self.args.batch_size,
                                 self.args.epochs, rng=self._data_rng)
            if self.mesh is not None:
                # multi-host: every process packed the identical cohort
                # (same seeded RNG stream); each contributes local shards
                from fedml_tpu.parallel.multihost import global_cohort
                packed = global_cohort(self.mesh, packed)
        return client_indexes, packed

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
        with (mon.xprof(self.round_idx) if mon is not None
              else contextlib.nullcontext()):
            with tracer.span("round", round=int(self.round_idx)):
                train_metrics = self._traced_round_body(tracer, t0)
        if mon is not None:
            # true steps are known host-side only on the bucketed path;
            # elsewhere the per-step histogram is skipped rather than
            # forcing a device read the disabled path would not do
            steps = (self._last_bucket_info["bucket"]["true_steps"]
                     if self.bucket_runner is not None else None)
            mon.observe_round(train_metrics["round_time_s"], steps=steps)
        self.round_idx += 1
        return train_metrics

    def _traced_round_body(self, tracer, t0):
        self.rng, round_rng = jax.random.split(self.rng)
        if self.bucket_runner is not None:
            client_indexes = self._sample_cohort(self.round_idx)
            logging.info("bucketed round over %d clients",
                         len(client_indexes))
            datasets = [self.train_data_local_dict[i]
                        for i in client_indexes]
            if all(len(d["y"]) == 0 for d in datasets):
                raise ValueError(f"round {self.round_idx}: every sampled "
                                 f"client has an empty shard")
            with tracer.span("local-train", mode="bucketed",
                             clients=len(client_indexes)) as sp:
                (self.global_state, self.server_state,
                 info) = self.bucket_runner.run_round(
                    self.global_state, self.server_state, datasets,
                    round_rng, data_rng=self._data_rng,
                    aggregator=self.async_agg,
                    async_window=getattr(self, "_async_window", 4),
                    client_ids=client_indexes,
                    residual_store=(self._ef_store
                                    if self.compressor is not None
                                    else None))
                # the stream's metric sums are on the host already;
                # "fold" says where the payload sums were combined
                sp.set(fold=info["fold"],
                       **routing_counters(info["metrics"]))
            self._last_bucket_info = info
            self._last_cohort_size = len(client_indexes)
        elif self.device_data is not None:
            import jax.numpy as jnp
            client_indexes = self._sample_cohort(self.round_idx)
            logging.info("client_indexes = %s", client_indexes)
            ns = [self._client_ns[i] for i in client_indexes]
            if sum(ns) == 0:
                raise ValueError(f"round {self.round_idx}: every sampled "
                                 f"client has an empty shard")
            with tracer.span("broadcast", clients=len(client_indexes)):
                sched = pack_schedule(ns, self.args.batch_size,
                                      self.args.epochs, rng=self._data_rng)
            mode = int(getattr(self.args, "wave_mode", 1))
            if self.sharded_lane_runner is not None:
                with tracer.span("local-train", mode="sharded-lanes"):
                    (self.global_state, self.server_state,
                     info) = self.sharded_lane_runner.run_round(
                        self.global_state, self.server_state,
                        self.device_data, client_indexes, sched, round_rng)
            elif mode in (2, 3):
                runner = (self.packed_lane_runner if mode == 3
                          else self.lane_runner)
                with tracer.span("local-train",
                                 mode="mxu-lanes" if mode == 3 else "lanes"):
                    (self.global_state, self.server_state,
                     info) = runner.run_round(
                        self.global_state, self.server_state,
                        self.device_data, client_indexes, sched, round_rng)
            elif mode == 1:
                with tracer.span("local-train", mode="waves"):
                    (self.global_state, self.server_state,
                     info) = self.wave_runner.run_round(
                        self.global_state, self.server_state,
                        self.device_data, client_indexes, sched, round_rng)
            else:
                with tracer.span("local-train", mode="flat"):
                    sel = jnp.asarray(np.asarray(client_indexes, np.int32))
                    dd = {"x": self.device_data["x"][sel],
                          "y": self.device_data["y"][sel]}
                    sched = {k: jnp.asarray(v) for k, v in sched.items()}
                    (self.global_state, self.server_state,
                     info) = self.indexed_round_fn(
                        self.global_state, self.server_state, dd, sched,
                        round_rng)
        elif self.compressed_round_fn is not None:
            client_indexes, packed = self._cohort(self.round_idx)
            with tracer.span("local-train", mode="compressed"):
                # gather/scatter by stable client id (ResidualStore): the
                # round fn sees cohort-ordered rows, the store owns the
                # id-keyed carry across re-sampled cohorts
                cohort_res = self._ef_store.gather(client_indexes)
                (self.global_state, self.server_state, new_res,
                 info) = self.compressed_round_fn(
                    self.global_state, self.server_state, packed, cohort_res,
                    round_rng)
                self._ef_store.scatter(client_indexes, new_res)
            self._last_cohort_size = len(client_indexes)
        else:
            _, packed = self._cohort(self.round_idx)
            with tracer.span("local-train", mode="packed"):
                self.global_state, self.server_state, info = self.round_fn(
                    self.global_state, self.server_state, packed, round_rng)
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
        if self.bucket_runner is not None:
            b = self._last_bucket_info["bucket"]
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
            # buffer-depth/staleness series ride every round record on
            # async runs (metrics.jsonl observability contract) even when
            # the registry is off
            train_metrics.update(self._last_bucket_info.get("async") or {})
        if self.compressor is not None:
            # client->server update traffic this round (uplink; the
            # downlink model broadcast is uncompressed and identical in
            # both regimes, so the ratio isolates what compression buys)
            # -- the packed compressed round and the bucketed
            # streaming-EF path account identically: per-client encoded
            # bytes are static given the template
            cohort = self._last_cohort_size
            wire = self._payload_bytes * cohort
            raw = self._raw_payload_bytes * cohort
            # set directly on the record (callers read the returned dict);
            # count_wire is the transports' path and would double-report
            train_metrics["bytes_on_wire"] = wire
            train_metrics["compression_ratio"] = round(raw / wire, 3)
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
