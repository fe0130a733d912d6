"""The seam for the execution path: one contract, one place that chooses.

A federated round runs down ONE of eight paths. Each is a *runner*:

- ``mode``: the path's name, the string the ``local-train`` span carries;
- ``run_round(global_state, server_state, client_indexes, rng) ->
  (global_state, server_state, info)``: one round over the cohort. The
  runner owns its feed -- it was built with the clients' data and draws
  its schedules from its own ``data_rng`` -- so the caller hands it the
  cohort's ids and the round key, nothing else;
- ``programs(global_state, server_state, client_indexes)``: every jitted
  function that round dispatches, as ``(name, function, abstract
  arguments)`` (what ``compile/warmup.py`` compiles ahead of time).

``bucketed`` is ``engine.BucketedStreamRunner`` (feed: the raw shards,
chunk by chunk). ``waves``, ``lanes``, ``mxu-lanes``, ``sharded-lanes``
and ``flat`` are a :class:`ResidentRunner` (feed: device-resident rows +
``pack_schedule``) around the engine's ``WaveRunner``, ``LaneRunner``,
``LaneRunner(packed=True)``, ``ShardedLaneRunner`` and
:class:`FlatRounds`. ``packed`` and ``compressed`` are a
:class:`PackedRunner` (feed: ``pack_cohort``) around ``make_sim_round``
(``make_sharded_round`` on a mesh) and ``make_compressed_sim_round``.

:func:`select_runner` is the one place that reads the arguments that
decide between them (``--bucket_edges`` / ``--async_agg``, ``--mesh``,
``--wave_mode``, ``--device_resident``, ``--compressor``), refuses the
combinations that would run one mode under another's name, and builds
the one runner that will run.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.observability.tracing import get_tracer
from fedml_tpu.parallel.engine import (
    LaneRunner, ShardedLaneRunner, WaveRunner, abstract, key_abstract,
    make_indexed_sim_round, residuals_abstract, wire_record)
from fedml_tpu.parallel.packing import (
    _steps_for, pack_cohort, pack_schedule, parse_bucket_edges,
    stack_clients)


class FlatRounds:
    """``make_indexed_sim_round`` as a resident dispatcher: the whole
    cohort in one program, every client padded to the cohort's longest
    schedule (A/B and debugging)."""

    mode = "flat"

    def __init__(self, spec, cfg, payload_fn=None, server_fn=None,
                 client_chunk=None):
        self._round_fn = make_indexed_sim_round(
            spec, cfg, payload_fn, server_fn, client_chunk=client_chunk)

    @staticmethod
    def _feed(device_data, ids, sched):
        sel = jnp.asarray(np.asarray(ids, np.int32))
        return ({"x": device_data["x"][sel], "y": device_data["y"][sel]},
                {k: jnp.asarray(v) for k, v in sched.items()})

    def run_schedule(self, global_state, server_state, device_data, ids,
                     sched, rng):
        return self._round_fn(global_state, server_state,
                              *self._feed(device_data, ids, sched), rng)

    def programs(self, global_state, server_state, device_data, ids, sched):
        feed = jax.eval_shape(lambda d, s: self._feed(d, ids, s),
                              device_data, sched)
        return [("indexed_round", self._round_fn,
                 (abstract(global_state), abstract(server_state), *feed,
                  key_abstract()))]


class ResidentRunner:
    """Rounds over device-resident data: every client's padded shard was
    uploaded to HBM once (``device_data``), so a round's host work is the
    index schedule (``pack_schedule``) and ``dispatch`` -- a
    ``WaveRunner``, ``LaneRunner``, ``ShardedLaneRunner`` or
    ``FlatRounds`` -- decides how the schedule runs."""

    compressor = None

    def __init__(self, dispatch, device_data, client_ns, batch_size, epochs,
                 data_rng):
        self.dispatch = dispatch
        self.mode = dispatch.mode
        self.device_data = device_data
        self.client_ns = client_ns
        self.batch_size, self.epochs = batch_size, epochs
        self.data_rng = data_rng

    def _schedule(self, client_indexes, rng):
        return pack_schedule([self.client_ns[i] for i in client_indexes],
                             self.batch_size, self.epochs, rng=rng)

    def run_round(self, global_state, server_state, client_indexes, rng):
        with get_tracer().span("broadcast", clients=len(client_indexes)):
            sched = self._schedule(client_indexes, self.data_rng)
        return self.dispatch.run_schedule(
            global_state, server_state, self.device_data, client_indexes,
            sched, rng)

    def programs(self, global_state, server_state, client_indexes):
        # shapes depend only on ns/bs/epochs: a throwaway rng keeps the
        # checkpointable host stream untouched
        sched = self._schedule(client_indexes, np.random.default_rng(0))
        return self.dispatch.programs(
            global_state, server_state, self.device_data, client_indexes,
            sched)


class PackedRunner:
    """The function-shaped rounds behind the contract: ``make_sim_round``,
    ``make_sharded_round`` (on a mesh) and, with a ``compressor``,
    ``make_compressed_sim_round`` threading the cohort's error-feedback
    residuals. The feed is ``pack_cohort`` of the cohort's raw shards,
    placed over the mesh when there is one."""

    def __init__(self, round_fn, shards, batch_size, epochs, data_rng,
                 mesh=None, compressor=None, residual_store=None,
                 wire_bytes=None):
        self._round_fn = round_fn
        self.mode = "packed" if compressor is None else "compressed"
        self.shards = shards
        self.batch_size, self.epochs = batch_size, epochs
        self.data_rng = data_rng
        self.mesh = mesh
        self.compressor = compressor
        self.residual_store = residual_store
        self.wire_bytes = wire_bytes

    def pack(self, client_indexes):
        """The cohort's batches, packed and placed: the host->device half
        of what a distributed round sends out."""
        with get_tracer().span("broadcast", clients=len(client_indexes)):
            packed = pack_cohort([self.shards[i] for i in client_indexes],
                                 self.batch_size, self.epochs,
                                 rng=self.data_rng)
            if self.mesh is not None:
                # multi-host: every process packed the identical cohort
                # (same seeded RNG stream); each contributes local shards
                from fedml_tpu.parallel.multihost import global_cohort
                packed = global_cohort(self.mesh, packed)
        return packed

    def run_round(self, global_state, server_state, client_indexes, rng):
        packed = self.pack(client_indexes)
        if self.compressor is None:
            return self._round_fn(global_state, server_state, packed, rng)
        # gather/scatter by stable client id (ResidualStore): the round fn
        # sees cohort-ordered rows, the store owns the id-keyed carry
        # across re-sampled cohorts
        residuals = self.residual_store.gather(client_indexes)
        global_state, server_state, residuals, info = self._round_fn(
            global_state, server_state, packed, residuals, rng)
        self.residual_store.scatter(client_indexes, residuals)
        info = dict(info, wire=wire_record(self.wire_bytes,
                                           len(client_indexes)))
        return global_state, server_state, info

    def programs(self, global_state, server_state, client_indexes):
        """The round function at ``pack_cohort``'s documented padding
        rule -- computed, not packed (materializing the cohort's batches
        for their shapes would copy the whole round's data)."""
        shard = next(d for d in self.shards.values() if len(d["y"]))
        ns = [len(self.shards[i]["y"]) for i in client_indexes]
        bs = self.batch_size
        if bs in (-1, 0):
            bs = max(1, max(ns))
        S = max(_steps_for(n, bs, self.epochs) for n in ns)
        S = -(-S // 8) * 8  # pack_cohort's step_bucket default
        C = len(client_indexes)
        sds = jax.ShapeDtypeStruct
        packed = {k: sds((C, S, bs) + shard[k].shape[1:], shard[k].dtype)
                  for k in ("x", "y")}
        packed["mask"] = sds((C, S, bs), jnp.float32)
        packed["n"] = sds((C,), jnp.float32)
        gs = abstract(global_state)
        args = (gs, abstract(server_state), packed)
        if self.compressor is not None:
            args += (residuals_abstract(gs["params"], C),)
        return [("sim_round", self._round_fn, args + (key_abstract(),))]


def stack_if_fits(shards, args):
    """Stack every client's padded shard for HBM residency when the
    result fits ``device_data_cap_gb``. Applies the optional bf16 cast
    (floating x only -- token ids would be corrupted). Returns
    ``(stacked, nbytes)``: ``stack_clients``' ``{"x", "y", "n"}`` (cast
    applied) or None when over the cap, and the stack's size."""
    C = len(shards)
    n_max = max(1, max(len(d["y"]) for d in shards.values()))
    x0, y0 = np.asarray(shards[0]["x"]), np.asarray(shards[0]["y"])
    ddt = getattr(args, "device_dtype", None)
    cast_bf16 = (ddt in ("bf16", "bfloat16")
                 and np.issubdtype(x0.dtype, np.floating))
    x_itemsize = 2 if cast_bf16 else x0.dtype.itemsize
    row = (int(np.prod(x0.shape[1:], dtype=np.int64)) * x_itemsize
           + int(np.prod(y0.shape[1:], dtype=np.int64) or 1)
           * y0.dtype.itemsize)
    nbytes = C * n_max * row
    if nbytes > float(getattr(args, "device_data_cap_gb", 2.0)) * 1e9:
        return None, nbytes
    stacked = stack_clients([shards[i] for i in range(C)])
    if cast_bf16:
        stacked["x"] = np.asarray(stacked["x"], dtype=jnp.bfloat16)
    return stacked, nbytes


def select_runner(program, spec, cfg, args, mesh, shards, params, *,
                  payload_fn=None, server_fn=None, compressor=None,
                  data_rng=None):
    """The ONE runner this arg surface runs.

    ``program`` is the ``RoundProgram`` being lowered, ``shards`` the
    population (client id -> raw ``{"x", "y"}``), ``params`` the model's
    parameters (a template: the error-feedback residuals and the wire
    accounting take their shapes from it), ``payload_fn`` / ``server_fn``
    the aggregator hooks, ``compressor`` the resolved client-update
    compressor or None, ``data_rng`` the host stream the runner's
    schedules draw from. Validated BEFORE anything is built: a bogus
    combination must fail loudly here, not deep in ``shard_map``, and
    never run one mode under another's name.
    """
    if compressor is not None and mesh is not None:
        raise ValueError(
            "compressor= applies to the single-chip simulation and the "
            "distributed control-plane paths; mesh rounds aggregate "
            "over ICI collectives, where the wire bottleneck being "
            "compressed does not exist")
    stream = (getattr(args, "bucket_edges", None) is not None
              or program.is_async)
    if stream:
        if mesh is not None:
            raise ValueError(
                "--bucket_edges/--async_agg run the single-chip "
                "bucketed streaming path; it does not compose with "
                "--mesh (the sharded-lane path owns multi-chip)")
        if compressor is not None and compressor.name == "none":
            # the identity compressor has no wire transform to stream:
            # keep the plain chunk program so --compressor none stays
            # bitwise-identical to no flag at all
            logging.info("bucketed streaming: --compressor none is "
                         "the identity -- running the plain chunk "
                         "program (bitwise)")
            compressor = None

    # --wave_mode picks how rounds over device-resident data execute:
    # 3 = MXU-packed lanes, 2 = lanes, 1 = size-sorted waves (default),
    # 0 = flat
    device_resident = getattr(args, "device_resident", "auto")
    if str(device_resident).lower() in ("0", "false", "none", ""):
        device_resident = False
    wave_mode = int(getattr(args, "wave_mode", 1))
    if wave_mode in (2, 3):
        # lanes only exist over device-resident data: an option that
        # bypasses residency would run the host-packed, compressed or
        # bucketed round under the requested mode's name
        bypass = ("--device_resident 0" if not device_resident
                  else "--compressor" if compressor is not None
                  else "--bucket_edges/--async_agg" if stream else None)
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
    # stacking copies the whole dataset host-side: only for the paths
    # that consume it (single-chip residency, or mesh lanes); compressed
    # rounds thread EF residuals, which only the packed-cohort round
    # function does -- residency is bypassed there
    wants_residency = (device_resident and compressor is None
                       and not stream
                       and (mesh is None or wave_mode in (2, 3)))
    # the data's way to where the rounds read it, by mode: the stack for
    # residency here and its upload below, or the population's step
    # counts that size the stream's bucket edges
    with get_tracer().span("index-data", clients=len(shards)):
        stacked, nbytes = (stack_if_fits(shards, args) if wants_residency
                           else (None, 0))
    if stacked is None and wave_mode in (2, 3):
        raise ValueError(
            f"--wave_mode {wave_mode} runs lanes over device-resident "
            f"data, but the stacked client shards need "
            f"{nbytes / 1e9:.2f} GB and --device_data_cap_gb is "
            f"{float(getattr(args, 'device_data_cap_gb', 2.0)):g}; "
            "raise the cap or use --wave_mode 1")

    chunk = getattr(args, "client_chunk", 8) or 8
    hooks = (spec, cfg, payload_fn, server_fn)
    ef = {}
    if compressor is not None:
        from fedml_tpu.compression import (ResidualStore,
                                           compressed_payload_nbytes,
                                           raw_payload_nbytes)
        # error-feedback residual per client IN TOTAL, carried across
        # rounds (clients keep their own accumulator between the rounds
        # they are sampled into -- DGC/EF-SignSGD semantics). Keyed by
        # STABLE client id, never cohort slot: re-sampled cohorts must
        # not cross-contaminate accumulators (regression-pinned in
        # tests/test_compression.py). Dense device rows when the
        # population fits dense_cap_gb, lazy host spill beyond (the
        # unbounded-population contract). The on-wire cost per client
        # update is static given the template: computed once from
        # abstract shapes (nothing runs on device)
        ef = dict(
            compressor=compressor,
            residual_store=ResidualStore(
                params, num_clients=len(shards),
                dense_cap_gb=float(getattr(args, "device_data_cap_gb",
                                           2.0))),
            wire_bytes=(compressed_payload_nbytes(compressor, params),
                        raw_payload_nbytes(params)))

    if stream:
        # edges are sized from the POPULATION max so bucket shapes -- and
        # therefore compiled programs -- are stable across rounds no
        # matter which cohort is sampled
        with get_tracer().span("index-data", clients=len(shards)):
            pop_ns = [len(d["y"]) for d in shards.values()]
            # the RESOLVED batch size: -1 (full-batch) must pin to the
            # population max, not each cohort's, or re-sampled cohorts
            # change the compiled [C, S, B] shape
            eff_bs = (args.batch_size if args.batch_size not in (-1, 0)
                      else max(1, max(pop_ns)))
            s_max = max(_steps_for(max(n, 1), eff_bs, args.epochs)
                        for n in pop_ns)
        return program.compile_bucketed(
            *hooks, client_chunk=chunk, batch_size=eff_bs,
            epochs=args.epochs,
            edges=parse_bucket_edges(getattr(args, "bucket_edges", None),
                                     s_max),
            shards=shards, data_rng=data_rng, **ef,
            **(dict(aggregator=program.host_view().make_aggregator(),
                    async_window=program.aggregation.async_window)
               if program.is_async else {}))
    if stacked is None:
        # the resolved compressor instance is passed through: CodecSpec
        # coercion would re-derive it from the spec string and drop
        # instance-level configuration
        round_fn = program.compile_sim(
            *hooks, mesh=mesh, compressed=compressor is not None,
            compressor=compressor)
        return PackedRunner(round_fn, shards, args.batch_size, args.epochs,
                            data_rng, mesh=mesh, **ef)
    host = {"x": stacked["x"], "y": stacked["y"]}
    if mesh is None:
        with get_tracer().span("index-data", bytes=int(nbytes)):
            device_data = jax.tree.map(jnp.asarray, host)
        dispatch = (
            LaneRunner(*hooks, n_lanes=chunk, packed=wave_mode == 3)
            if wave_mode in (2, 3)
            else WaveRunner(*hooks, client_chunk=chunk) if wave_mode == 1
            else FlatRounds(*hooks, client_chunk=getattr(
                args, "client_chunk", None)))
    else:
        # mesh + lanes: client rows live SHARDED over the mesh's clients
        # axis; each shard runs its residents as packed lanes and
        # aggregation is one psum; wave_mode 3 additionally folds each
        # shard's lane axis into channels (MXU-shaped lowering)
        from fedml_tpu.parallel.multihost import global_cohort
        with get_tracer().span("index-data", bytes=int(nbytes)):
            device_data = global_cohort(mesh, host)
        dispatch = ShardedLaneRunner(spec, cfg, mesh, payload_fn, server_fn,
                                     n_lanes=chunk, packed=wave_mode == 3)
    return ResidentRunner(dispatch, device_data, stacked["n"],
                          args.batch_size, args.epochs, data_rng)


__all__ = ["FlatRounds", "ResidentRunner", "PackedRunner", "stack_if_fits",
           "select_runner"]
