"""The federated round engine: one XLA program per round.

Reference behavior being replaced (SURVEY.md section 3.1): the server unicasts
pickled state_dicts to N client processes, each runs E epochs of local SGD,
sends weights back, and the server loops over state_dict keys on CPU.  Here
the entire round --

    per-client local-epochs ``lax.scan``  ->  weighted aggregation  ->  server step

-- is a single jitted function. Client parallelism is ``vmap`` on one chip
(standalone simulation, reference ``fedml_api/standalone/fedavg``) or
``shard_map`` over a ``clients`` mesh axis (distributed, reference
``fedml_api/distributed/fedavg``) with the weighted average as ``psum`` over
ICI. Both placements share the same ``client_update`` and the same
aggregator hooks, so every FL algorithm written against this engine runs in
both paradigms -- the reference needed two separate implementations per
algorithm (sections 2.2 vs 2.3).

Aggregator hooks (see ``fedml_tpu.algorithms``):
  payload_fn(local_state, global_state, aux) -> payload pytree
      per-client transform before averaging (identity for FedAvg, norm-clip
      for robust FedAvg, normalized delta for FedNova).
  server_fn(global_state, avg_payload, server_state, rng) -> (new_global, new_server_state)
      global update from the weighted-average payload (identity for FedAvg,
      optimizer step on the pseudo-gradient for FedOpt).

Consumers reach these round factories through
``RoundProgram.compile_sim`` / ``compile_bucketed``
(:mod:`fedml_tpu.program.sim`): the program object carries the
cohort/aggregation/codec policy and this module is its jit lowering --
the distributed control plane lowers the SAME program host-side via
``program.host_view()`` (docs/PROGRAM.md).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from fedml_tpu.core import pytree
from fedml_tpu.core.trainer import TrainSpec
from fedml_tpu.observability.costmodel import get_cost_model, program_cost
from fedml_tpu.observability.tracing import get_tracer
from fedml_tpu.ops import row_embed
from fedml_tpu.parallel.mesh import (CLIENT_AXIS, LANE_AXIS,
                                     zero_pad_leading)
from fedml_tpu.program.aggregation import (split_total, two_word_add,
                                           two_word_quotient)


@dataclasses.dataclass(frozen=True)
class ClientUpdateConfig:
    """Local-training hyperparameters (reference flags
    ``--client_optimizer --lr --wd``, ``main_fedavg.py:46-105``; optimizer
    construction parity with ``MyModelTrainer.py:25-31`` -- plain SGD or
    Adam(amsgrad) with weight decay, fresh optimizer state every round)."""
    optimizer: str = "sgd"
    lr: float = 0.03
    weight_decay: float = 0.0
    momentum: float = 0.0
    grad_clip: Optional[float] = None  # FedNAS clips local grads at 5.0


def make_optimizer(cfg: ClientUpdateConfig) -> optax.GradientTransformation:
    txs = []
    if cfg.grad_clip:
        txs.append(optax.clip_by_global_norm(cfg.grad_clip))
    if cfg.optimizer == "sgd":
        # torch.optim.SGD couples weight decay into the gradient
        if cfg.weight_decay:
            txs.append(optax.add_decayed_weights(cfg.weight_decay))
        txs.append(optax.sgd(cfg.lr, momentum=cfg.momentum or None))
    elif cfg.optimizer == "adam":
        # reference uses Adam(amsgrad=True, wd) -- MyModelTrainer.py:29-31;
        # torch couples wd into the gradient BEFORE the Adam statistics
        if cfg.weight_decay:
            txs.append(optax.add_decayed_weights(cfg.weight_decay))
        txs.append(optax.amsgrad(cfg.lr))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer}")
    return optax.chain(*txs)


def _split_state(state):
    params = state["params"]
    rest = {k: v for k, v in state.items() if k != "params"}
    return params, rest


def _tree_select(pred, new, old):
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)


def _augmented(spec: TrainSpec, batch, step_rng):
    if spec.augment_fn is None:
        return batch
    batch = dict(batch)
    batch["x"] = spec.augment_fn(batch["x"], jax.random.fold_in(step_rng, 13))
    return batch


def _make_grad_at(spec: TrainSpec):
    """``grad_at(params, rest, batch, step_rng)``: one step's
    augmentation, loss and gradient, as every client-update variant
    takes them. The row step (:func:`_make_trip_loop_core`) passes the
    tables it steps by rows apart from ``params`` and the lookups'
    ``deltas``, and takes the gradient by both: ``(params, deltas)``."""

    def grad_at(params, rest, batch, step_rng, tables=None, deltas=None):
        batch = _augmented(spec, batch, step_rng)

        def loss_wrapper(p, d=None):
            state = dict(rest)
            state["params"] = row_embed.join_tables(p, tables)
            if d is not None:
                state[row_embed.ROW_STEP] = d
            return spec.loss_fn(state, batch, step_rng, True)

        if deltas is None:
            return jax.value_and_grad(loss_wrapper, has_aux=True)(params)
        return jax.value_and_grad(loss_wrapper, argnums=(0, 1),
                                  has_aux=True)(params, deltas)

    return grad_at


def make_client_update(spec: TrainSpec, cfg: ClientUpdateConfig):
    """Build the jittable per-client local-training function.

    Returns ``fn(global_state, client_data, rng) -> (local_state, aux)`` where
    ``client_data`` is one client's slice of a packed cohort
    (``x [S,B,...], y [S,B,...], mask [S,B], n []``) and ``aux`` carries the
    true sample count ``n`` and executed step count ``steps`` (FedNova's tau).
    Fully-masked (padded) steps leave all carried state untouched.
    """
    optimizer = make_optimizer(cfg)
    grad_at = _make_grad_at(spec)

    def client_update(global_state, client_data, rng):
        params, rest = _split_state(global_state)
        opt_state = optimizer.init(params)
        S = client_data["mask"].shape[0]

        def step(carry, xs):
            params, rest, opt_state = carry
            batch, step_idx = xs
            (_, (new_state, metrics)), grads = grad_at(
                params, rest, batch, jax.random.fold_in(rng, step_idx))
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_rest = {k: new_state[k] for k in rest}
            valid = jnp.sum(batch["mask"]) > 0
            new_carry = _tree_select(valid, (new_params, new_rest, new_opt),
                                     (params, rest, opt_state))
            return new_carry, metrics

        batches = {k: client_data[k] for k in ("x", "y", "mask")}
        (params, rest, _), metrics = jax.lax.scan(
            step, (params, rest, opt_state), (batches, jnp.arange(S)))
        local_state = dict(rest)
        local_state["params"] = params
        steps_done = jnp.sum(jnp.any(client_data["mask"] > 0, axis=-1))
        aux = {"n": client_data["n"], "steps": steps_done}
        # metrics leaves are [S, ...] per-step sums; padded steps contributed 0
        metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics)
        return local_state, aux, metrics_sum

    return client_update


def make_indexed_client_update(spec: TrainSpec, cfg: ClientUpdateConfig):
    """Per-client local training over DEVICE-RESIDENT data.

    ``fn(global_state, data, sched, rng)`` where ``data`` is the client's
    full padded shard ``{"x": [n_max, ...], "y": [n_max, ...]}`` living in
    HBM and ``sched`` is a host-built index schedule ``{"idx": [S, B] int32,
    "mask": [S, B], "n": []}``. Each scan step *gathers* its batch on device
    (``jnp.take``), so the host stages bytes once per run instead of
    ``epochs x dataset`` copies per round -- the fix for SURVEY.md section 7
    hard part #2 (client-state swap without stalling).
    """
    optimizer = make_optimizer(cfg)
    grad_at = _make_grad_at(spec)

    def client_update(global_state, data, sched, rng):
        params, rest = _split_state(global_state)
        opt_state = optimizer.init(params)
        S = sched["mask"].shape[0]

        def step(carry, xs):
            params, rest, opt_state = carry
            idx_b, mask_b, step_idx = xs
            batch = {"x": jnp.take(data["x"], idx_b, axis=0),
                     "y": jnp.take(data["y"], idx_b, axis=0),
                     "mask": mask_b}
            (_, (new_state, metrics)), grads = grad_at(
                params, rest, batch, jax.random.fold_in(rng, step_idx))
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_rest = {k: new_state[k] for k in rest}
            valid = jnp.sum(mask_b) > 0
            new_carry = _tree_select(valid, (new_params, new_rest, new_opt),
                                     (params, rest, opt_state))
            return new_carry, metrics

        (params, rest, _), metrics = jax.lax.scan(
            step, (params, rest, opt_state),
            (sched["idx"], sched["mask"], jnp.arange(S)))
        local_state = dict(rest)
        local_state["params"] = params
        steps_done = jnp.sum(jnp.any(sched["mask"] > 0, axis=-1))
        aux = {"n": sched["n"], "steps": steps_done}
        metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics)
        return local_state, aux, metrics_sum

    return client_update


def _make_trip_loop_core(spec: TrainSpec, cfg: ClientUpdateConfig):
    """THE dynamic-trip training loop, shared by every variant that runs
    exactly ``trip`` (traced-scalar) steps: grad + optimizer step +
    masked valid-select + running metric sums. The variants
    (:func:`make_loop_client_update` over device-resident data + index
    schedules, :func:`make_streamed_client_update` over pre-gathered
    chunk batches) differ ONLY in their ``batch_at`` -- fixes to
    masking, augmentation RNG, or optimizer semantics land here once.

    **The row step.** Under plain SGD (no momentum, weight decay or
    clipping: a step is ``p - lr * g`` and nothing else) a table that the
    loss reads by one lookup of fewer positions than it has rows
    (``ops/row_embed.py`` ``RowEmbed``: the token embedding of the LM
    families) is carried apart from the other parameters and stepped by
    the rows it looked up: ``table.at[ids].add(-lr * rows_ct)``, in place,
    with the ids and the rows' cotangent from the lookup itself. No
    table-shaped cast, gradient or select is made. What the probe of the
    first step found is decided while tracing and kept in ``run.row_plan``
    (``{module path: positions a step stepped by rows, 0 for dense}``).
    Every other table, optimizer and model takes the dense step.

    Returns ``run(global_state, batch_at, trip, rng) ->
    (params, rest, metrics_sum)``.
    """
    optimizer = make_optimizer(cfg)
    grad_at = _make_grad_at(spec)
    plain_sgd = (cfg.optimizer == "sgd" and not cfg.momentum
                 and not cfg.weight_decay and not cfg.grad_clip)
    row_plan = {}

    def probe(params, rest, batch, rng):
        # one step's forward: its metric structure and, under plain SGD,
        # the lookups that could be stepped by rows
        state = dict(rest)
        state["params"] = params
        if plain_sgd:
            state[row_embed.ROW_STEP] = {}
        _, (new_state, metrics) = spec.loss_fn(
            state, _augmented(spec, batch, rng), rng, True)
        return metrics, row_embed.lookups(
            new_state.get(row_embed.ROW_STEP, {}))

    def run(global_state, batch_at, trip, rng):
        params, rest = _split_state(global_state)

        # abstract-eval one step: carry zeros of its metrics; decide the
        # tables stepped by rows from the shapes it looked up
        metrics0, found = jax.eval_shape(
            lambda: probe(params, rest, batch_at(0), rng))
        metrics0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                metrics0)
        rows = row_embed.plan_rows(params, found)
        row_plan.update({"/".join(p): (rows[p].size if p in rows else 0)
                         for p in found})
        tables, params = row_embed.split_tables(params, rows)
        opt_state = optimizer.init(params)

        def body(i, carry):
            params, tables, rest, opt_state, msum = carry
            batch = batch_at(i)
            step_rng = jax.random.fold_in(rng, i)
            deltas = row_embed.zero_deltas(tables, rows) if rows else None
            (_, (new_state, metrics)), grads = grad_at(
                params, rest, batch, step_rng, tables, deltas)
            if rows:
                grads, rows_ct = grads
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_rest = {k: new_state[k] for k in rest}
            valid = jnp.sum(batch["mask"]) > 0
            params, rest, opt_state = _tree_select(
                valid, (new_params, new_rest, new_opt),
                (params, rest, opt_state))
            if rows:
                tables = row_embed.step_rows(
                    tables, new_state[row_embed.ROW_STEP], rows_ct, valid,
                    cfg.lr)
            msum = jax.tree.map(jnp.add, msum, metrics)
            return (params, tables, rest, opt_state, msum)

        params, tables, rest, _, msum = jax.lax.fori_loop(
            0, trip, body, (params, tables, rest, opt_state, metrics0))
        return row_embed.join_tables(params, tables), rest, msum

    run.row_plan = row_plan
    return run


def make_loop_client_update(spec: TrainSpec, cfg: ClientUpdateConfig):
    """Per-client local training as a ``fori_loop`` with a DYNAMIC trip count.

    ``fn(global_state, data, sched, steps, rng) -> (local_state, aux,
    metrics_sum)``. Unlike :func:`make_indexed_client_update`'s fixed-length
    ``scan``, the step loop runs exactly ``steps`` iterations where ``steps``
    is a *traced scalar* -- so one compiled program serves every wave length,
    and steps past a wave's true maximum are never executed at all (instead
    of executing fully-masked fwd+bwd no-ops). Metrics accumulate as running
    sums in the carry; schedule rows are fetched with ``dynamic_index_in_dim``.
    """
    run = _make_trip_loop_core(spec, cfg)

    def client_update(global_state, data, sched, steps, rng):
        def batch_at(i):
            idx_b = jax.lax.dynamic_index_in_dim(
                sched["idx"], i, axis=0, keepdims=False)
            mask_b = jax.lax.dynamic_index_in_dim(
                sched["mask"], i, axis=0, keepdims=False)
            return {"x": jnp.take(data["x"], idx_b, axis=0),
                    "y": jnp.take(data["y"], idx_b, axis=0),
                    "mask": mask_b}

        params, rest, msum = run(global_state, batch_at, steps, rng)
        local_state = dict(rest)
        local_state["params"] = params
        steps_done = jnp.sum(jnp.any(sched["mask"] > 0, axis=-1))
        aux = {"n": sched["n"], "steps": steps_done}
        return local_state, aux, msum

    return client_update


def make_streamed_client_update(spec: TrainSpec, cfg: ClientUpdateConfig):
    """Per-client local training over PRE-GATHERED batch arrays with a
    dynamic trip count -- the bucketed-streaming unit.

    ``fn(global_state, batches, n, trip, rng) -> (local_state, aux,
    metrics_sum)`` where ``batches`` is ``{"x": [S, B, ...], "y":
    [S, B, ...], "mask": [S, B]}`` staged per chunk (no device-resident
    dataset -- the cohort axis is unbounded) and ``trip`` is a *traced*
    scalar: the loop executes exactly ``trip`` steps, so steps past a
    chunk's true maximum are never run even though the array shape is
    padded to the bucket edge. Fully-masked steps inside the trip are
    guarded no-ops (same valid-select as every other update variant --
    the training loop itself is :func:`_make_trip_loop_core`).
    """
    run = _make_trip_loop_core(spec, cfg)

    def client_update(global_state, batches, n, trip, rng):
        def batch_at(i):
            return {k: jax.lax.dynamic_index_in_dim(
                        batches[k], i, axis=0, keepdims=False)
                    for k in ("x", "y", "mask")}

        params, rest, msum = run(global_state, batch_at, trip, rng)
        local_state = dict(rest)
        local_state["params"] = params
        steps_done = jnp.sum(jnp.any(batches["mask"] > 0, axis=-1))
        aux = {"n": n, "steps": steps_done}
        return local_state, aux, msum

    client_update.row_plan = run.row_plan
    return client_update


class BucketedStreamRunner:
    """Bucketed ragged streaming: one chip, an UNBOUNDED cohort axis.

    The device-resident runners cap the cohort at what fits HBM and pad
    every client's schedule to the cohort max -- both walls at population
    scale (the paper's premise is O(10^4-10^6) non-IID clients with
    ragged sample counts per round). This runner removes both:

    - **Bucketing bounds padded compute.** The cohort is sorted ASCENDING
      by local step count and cut into fixed-size chunks; each chunk's
      schedule pads to the smallest GEOMETRIC edge covering it
      (``packing.parse_bucket_edges`` -- the compiled-shape anchor) while
      the dispatch's ``fori_loop`` trip count is the chunk's true maximum
      (a traced scalar), so steps past it never execute at all. Sorted
      neighbors make chunks near-homogeneous: executed-step waste is the
      sorted-adjacency slack (~0%, LPT-grade), and the edge only bounds
      the *allocated* shape. Fastest-first dispatch also mirrors a real
      async population's report order, so the staleness the async fold
      sees is honest.
    - **Streaming bounds memory.** Each dispatch stages one chunk's
      batches host->device (``packing.gather_batches``) and returns only
      the chunk's weighted payload SUM -- O(client_chunk) data and O(1)
      model state on device, regardless of cohort size.
    - **The synchronous fold lives on the device.** The chunks' payload
      sums are combined where they are made, in a two-word float32
      accumulator (``program.aggregation.two_word_add``: a sum and its
      running error, every add error-free; no ``x64``): chunk 0's sum
      becomes the high word, the first add makes the low one in the
      second payload's buffer, every later chunk is one small program
      dispatched right after its chunk's, in ordinal order -- the
      canonical fold's order. ``two_word_quotient`` divides by the total
      weight to two-word precision, rounds ONCE to float32, casts through
      the payload dtype template and hands the average to the jitted
      ``advance_fn``: no payload sum crosses to the host, no average
      crosses back, and the host fetches a chunk's weight and metric sums
      alone. **The contract:** every element of the new state is within
      one float32 ulp of ``fold_entries_fp64`` over the same entries,
      whatever the number of chunks, and equal to it on all but 1e-5 of
      the elements (none in a million differ on the CPU; on a TPU
      v5e, whose division is not correctly rounded, exact ties of the
      one final rounding go to the other neighbour: one to four in a
      million of a real round); a
      non-finite sum comes out non-finite (NaN where float64 gives an
      infinity). ``fold_entries_fp64`` stays the canonical fold of the
      buffered path below and of the server paths, and this fold's
      oracle (``tests/test_device_fold.py``). ``run_round`` returns when
      the new state is ready.
    - **One compiled program per bucket shape**, pinned: ``trip`` is
      traced and every chunk of a bucket shares the edge-padded shape, so
      steady-state retraces are zero and ``compiled_shapes()`` equals the
      number of non-empty buckets (asserted in CI).

    Async composition: build the runner with ``aggregator=`` (a
    ``resilience.async_agg.BufferedAggregator``) and the stream folds
    chunk partials through it instead -- up to
    ``async_window`` chunks stay in flight (the simulated client
    concurrency), every ``buffer_k`` folded clients flush a server update
    MID-ROUND, and chunks dispatched before a flush fold in staleness-
    discounted. That path is the host's, whole: each payload sum is
    copied out and folded through ``fold_entries_fp64`` (arrival order
    unknown, staleness scales, flushes at any chunk). With an unbounded
    buffer and decay 0 it IS ``fold_entries_fp64`` over the round's
    chunks, byte for byte, and the synchronous fold equals it within the
    one-ulp contract above (the CI oracle, both halves).

    Streaming-EF (``compressor=``): the chunk program additionally runs
    the client->server half of the wire per lane -- compress the local
    update delta plus the client's error-feedback residual, reconstruct
    the server's view, and aggregate the RECONSTRUCTED states -- so the
    payload partial sums are exactly what a real compressed transport
    would deliver. Residuals are gathered/scattered by STABLE client id
    through the ``compression.ResidualStore`` the runner was built with
    (dense device rows when the population fits, lazy host spill
    beyond), the residual arrays share the chunk's ONE compiled shape
    per bucket edge (``[client_chunk, ...]`` rows -- the compressor
    changes no shape), and the scatter-back happens at the fold point,
    so the dense path keeps the ``async_window`` pipeline fully
    asynchronous. Zero steady-state retraces and ``compiled_shapes() ==
    buckets_used`` hold exactly as in the plain path (CI-gated).
    """

    mode = "bucketed"

    def __init__(self, spec: TrainSpec, cfg: ClientUpdateConfig,
                 payload_fn=None, server_fn=None, client_chunk=256,
                 batch_size=32, epochs=1, edges=(8,), step_bucket=8,
                 compressor=None, shards=None, data_rng=None,
                 aggregator=None, async_window=4, residual_store=None,
                 wire_bytes=None):
        import numpy as np

        self.payload_fn = payload_fn or _default_payload
        self.server_fn = server_fn or _default_server
        self.client_chunk = max(1, int(client_chunk))
        self.batch_size = int(batch_size)
        self.epochs = int(epochs)
        self.edges = sorted(int(e) for e in edges)
        self.step_bucket = int(step_bucket)
        self.compressor = compressor
        # the feed (``shards``: stable client id -> raw ``{"x", "y"}``;
        # ``data_rng``: the host stream the schedules draw from) and the
        # fold's policy (class docstring); ``wire_bytes``: one client
        # update's static (encoded, raw) bytes
        self.shards = shards
        self.data_rng = data_rng or np.random.default_rng(0)
        self.aggregator = aggregator
        self.async_window = int(async_window)
        self.residual_store = residual_store
        self.wire_bytes = wire_bytes
        client_update = make_streamed_client_update(spec, cfg)
        # the row step's decisions, made when chunk_fn is first traced
        self._row_plan = client_update.row_plan
        payload_fn_ = self.payload_fn
        server_fn_ = self.server_fn

        def _aggregate(global_state, local_states, aux, metrics):
            payloads = jax.vmap(payload_fn_, in_axes=(0, None, 0))(
                local_states, global_state, aux)
            w = aux["n"].astype(jnp.float32)
            metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0),
                                       metrics)
            return (_weighted_payload_sum(payloads, w), jnp.sum(w),
                    metrics_sum)

        if compressor is None:
            @jax.jit
            def chunk_fn(global_state, batches, ns, trip, rngs):
                local_states, aux, metrics = jax.vmap(
                    client_update, in_axes=(None, 0, 0, None, 0),
                    axis_name=LANE_AXIS)(
                        global_state, batches, ns, trip, rngs)
                return _aggregate(global_state, local_states, aux, metrics)
        else:
            from fedml_tpu.compression.compressors import ErrorFeedback
            ef = ErrorFeedback(compressor)

            @jax.jit
            def chunk_fn(global_state, batches, ns, trip, rngs,
                         residuals, crngs):
                local_states, aux, metrics = jax.vmap(
                    client_update, in_axes=(None, 0, 0, None, 0),
                    axis_name=LANE_AXIS)(
                        global_state, batches, ns, trip, rngs)

                def compress_one(local_state, residual, crng):
                    # the client->server wire half, per lane: EF-compress
                    # the update delta, aggregate the server's RECON view
                    # (make_compressed_sim_round's exact semantics,
                    # streamed); only "params" is lossy -- batch_stats
                    # and other state average at full fidelity
                    delta = pytree.tree_sub(local_state["params"],
                                            global_state["params"])
                    _, dec, new_residual = ef.step(
                        delta, residual, global_state["params"], crng)
                    recon = dict(local_state)
                    recon["params"] = pytree.tree_add(
                        global_state["params"], dec)
                    return recon, new_residual

                with jax.named_scope("ef-compress"):
                    recon_states, new_residuals = jax.vmap(compress_one)(
                        local_states, residuals, crngs)
                pay_sum, w_sum, metrics_sum = _aggregate(
                    global_state, recon_states, aux, metrics)
                return pay_sum, w_sum, metrics_sum, new_residuals

        @partial(jax.jit, donate_argnums=(0, 1))
        def advance_fn(global_state, server_state, avg_payload, rng):
            return server_fn_(global_state, avg_payload, server_state, rng)

        # the synchronous fold's programs, apart from chunk_fn (whose
        # device time is read by its name as the client update's alone).
        # What a program can write its outputs into is donated: the low
        # word takes the second payload's buffer, the average the high
        # word's. A later payload, and the low word at the quotient, have
        # no output to become, so they are not donated (that would only
        # warn); run_round drops its one reference at the dispatch and
        # the runtime frees the buffer when the program has read it
        @partial(jax.jit, donate_argnums=(0, 1))
        def fold_first(acc_hi, payload):
            return two_word_add(acc_hi, None, payload)

        @partial(jax.jit, donate_argnums=(0, 1))
        def fold_next(acc_hi, acc_lo, payload):
            return two_word_add(acc_hi, acc_lo, payload)

        @partial(jax.jit, donate_argnums=(0,))
        def fold_quotient(acc_hi, acc_lo, total_hi, total_lo, dtypes):
            return two_word_quotient(acc_hi, acc_lo, total_hi, total_lo,
                                     dtypes)

        self._chunk_fn = chunk_fn
        self._advance_fn = advance_fn
        self._fold_first = fold_first
        self._fold_next = fold_next
        self._fold_quotient = fold_quotient
        self._dtypes = None
        # per-bucket-edge ProgramCost (or None for "probed, no cost
        # analysis"), populated lazily ONLY while a CostModel is armed;
        # the AOT probe compiles once per edge (warm-up round) and never
        # touches the jit dispatch cache, so compiled_shapes() and the
        # zero-steady-state-retrace gates stay honest
        self._edge_costs = {}

    def compiled_shapes(self) -> int:
        """Distinct compiled chunk programs (should equal the number of
        non-empty buckets ever dispatched -- the retrace-audit anchor)."""
        return int(self._chunk_fn._cache_size())

    def run_round(self, global_state, server_state, client_indexes, rng):
        """One federated round over the cohort ``client_indexes`` (stable
        client ids into ``self.shards``), streamed bucket by bucket.

        With ``self.aggregator`` set the fold is the host's
        buffered-async one; otherwise the partials fold synchronously, on
        the device (``info["fold"]`` says which: ``"device"`` or
        ``"host"``), and ``async_window`` (the buffered path's chunks in
        flight) is not read. With a ``compressor`` armed,
        ``self.residual_store`` carries each client's EF residual across
        the rounds it is sampled into, keyed by its id. Returns
        ``(new_global, new_server_state, info)`` with ``info["bucket"]``
        (waste accounting), ``info["async"]`` (buffer counters) and
        ``info["wire"]`` (uplink bytes, compressed runs) next to the
        usual ``aux``/``metrics``.
        """
        import numpy as np
        from collections import deque

        from fedml_tpu.parallel.packing import (
            _steps_for, bucket_edge_for, gather_batches, pack_schedule)

        # spans by the job done, one per step per chunk (never per leaf);
        # PERF.md section 3 says which metric reads each
        tracer = get_tracer()
        # the round's preamble, a leaf of its own: the cohort's shards and
        # step counts, the split of the round's keys and its fetch to the
        # host, the payload's dtypes (everything before the first pack)
        with tracer.span("prepare", clients=len(client_indexes)):
            data_rng, aggregator = self.data_rng, self.aggregator
            residual_store = self.residual_store
            client_ids = [int(i) for i in client_indexes]
            datasets = [self.shards[i] for i in client_ids]
            C = len(datasets)
            if C == 0:
                raise ValueError("bucketed round over an empty cohort")
            if self.compressor is not None and residual_store is None:
                raise ValueError(
                    "streaming-EF needs a residual_store: the error-feedback "
                    "accumulator is keyed by stable client id ACROSS rounds "
                    "(compression.ResidualStore; select_runner builds one)")
            ns = [len(d["y"]) for d in datasets]
            if sum(ns) == 0:
                raise ValueError("bucketed round: every client shard is empty")
            if self.batch_size in (-1, 0):
                # full-batch convention: resolve ONCE (first cohort seen) and
                # pin it -- a per-cohort B would change the [C, S, B] compiled
                # shape whenever a re-sampled cohort's largest shard differs,
                # breaking the zero-steady-state-retrace invariant. FedAvgAPI
                # resolves from the POPULATION max before construction.
                self.batch_size = max(1, max(ns))
            bs = self.batch_size
            steps_pc = np.asarray(
                [_steps_for(max(n, 1), bs, self.epochs) for n in ns], np.int64)
            bucket_edge_for(steps_pc.max(), self.edges)  # top-edge guard
            client_keys = np.asarray(
                jax.random.split(jax.random.fold_in(rng, 1), C))
            dtypes = _payload_dtypes(self, global_state)
            flush_rng = jax.random.fold_in(rng, 2)
            comp_keys = None
            if self.compressor is not None:
                # fold 3 is the compression stream -- the same derivation
                # rule as make_compressed_sim_round, per stable cohort slot
                comp_keys = np.asarray(
                    jax.random.split(jax.random.fold_in(rng, 3), C))

        gs, ss = global_state, server_state
        cm = get_cost_model()  # one global read when attribution is off
        flushes = 0
        metrics_acc = None
        # sync path: the two-word float32 sum of the chunks' payload sums,
        # on the device (None until chunk 0's payload sum becomes the high
        # word; the low word is made by the first add). Combined in
        # ordinal (= sorted-key) order, each chunk right after its
        # program, so the order is fold_entries_fp64's over the same
        # entries; O(1 model) device memory and no payload on the host
        on_device = aggregator is None
        acc_hi = acc_lo = None
        # chunks in flight before the oldest one's weight is fetched. The
        # device fold keeps ONE: the runtime allocates a chunk's output
        # at its dispatch and frees a folded payload only when its fold
        # program has run, so every chunk the host runs ahead is one more
        # payload sum alive (at 411 M parameters and six chunks: 11.58 GB
        # at 4 or 2 in flight, 9.94 GB at 1, the round equally long: the
        # next chunk is still fed while this one runs). The buffered
        # path's window is its policy's (the simulated concurrency)
        depth = 1 if on_device else max(1, self.async_window)
        sync_w = 0.0
        inflight = deque()
        exec_steps = 0
        per_bucket = []

        def note_bytes(sp, arrays):
            # a walk over every leaf: traced rounds only
            if tracer.enabled:
                leaves = jax.tree.leaves(arrays)
                sp.set(bytes=sum(int(a.nbytes) for a in leaves),
                       arrays=len(leaves))

        def apply_avg(avg, f):
            # avg: the fold's average. From the host fold an f32 numpy
            # pytree, cast through the payload dtype template on its way
            # to the device (accumulators run f32/f64, the model may
            # not); from the device fold it is there, and cast, already.
            # Then the donated server step
            nonlocal gs, ss
            with tracer.span("fold.apply") as sp:
                if on_device:
                    sp.set(bytes=0, arrays=0)  # nothing leaves the host
                else:
                    avg = jax.tree.map(
                        lambda a, d: jnp.asarray(np.asarray(a), d.dtype),
                        avg, dtypes)
                    note_bytes(sp, avg)
                gs, ss = self._advance_fn(gs, ss, avg,
                                          jax.random.fold_in(flush_rng, f))
                if on_device:
                    # the round's work ends inside the round: nothing
                    # the host did before waited for the last programs
                    # (a mid-round flush of the buffered path does not
                    # wait: chunks are still in flight behind it)
                    waiting = time.perf_counter()
                    jax.block_until_ready((gs, ss))
                    # the span's part in which the host waited for the
                    # device (the stalled-round report tells it from the
                    # dispatch before it)
                    sp.set(blocked_s=time.perf_counter() - waiting)

        def fold_oldest():
            nonlocal flushes, metrics_acc, sync_w
            ordinal, born, k_real, handles, scatter = inflight.popleft()
            if scatter is not None:
                # EF residual write-back, deferred to the fold point (the
                # documented sync point): the dense store's at[].set is
                # pure device work and keeps the pipeline asynchronous;
                # the sparse (host-spill) backing pays its np.asarray
                # sync here, where the chunk's outputs sync anyway
                ids, new_res = scatter
                residual_store.scatter(
                    ids, jax.tree.map(lambda x: x[:len(ids)], new_res))
            # FIRST host touch of this chunk's outputs: the device sync
            # point. Everything stays a device handle until here, so the
            # chunks in flight (``depth``) genuinely overlap host
            # packing/H2D staging with device compute. The device fold
            # fetches the weight and the metric sums alone (handles[0] is
            # None: the payload sum went into the accumulator at the
            # dispatch)
            if tracer.enabled:
                # the wait apart from the copy. Every output of a chunk
                # comes from one program, so waiting for the weight is
                # waiting for them all, wait + copy is what the copy
                # alone costs untraced, and the untraced path stays as is
                with tracer.span("fold.wait", ordinal=ordinal):
                    handles[1].block_until_ready()
            with tracer.span("fold.d2h") as sp:
                pay = jax.tree.map(np.asarray, handles[0])
                w = float(np.asarray(handles[1]))
                m_host = jax.tree.map(
                    lambda m: np.asarray(m, np.float64), handles[2])
                metrics_acc = m_host if metrics_acc is None else \
                    jax.tree.map(np.add, metrics_acc, m_host)
                note_bytes(sp, handles)
            if on_device:
                sync_w += w
                return
            staleness = aggregator.version - born
            with tracer.span("fold.add", on="host"):  # parent of buffer-fold
                aggregator.fold(ordinal, w, pay, staleness=staleness,
                                clients=k_real, preweighted=True)
            if aggregator.ready():
                res = aggregator.flush("buffer_k")
                apply_avg(res.params, flushes)
                flushes += 1

        # fastest-first streaming: the cohort is sorted ASCENDING by step
        # count and cut into chunks; each chunk's schedule is padded to
        # the smallest covering bucket edge (the compiled-shape anchor)
        # while its fori_loop trip is the chunk's true maximum. Sorted
        # neighbors make chunks near-homogeneous, so executed-step waste
        # is the sorted-adjacency slack (~0%, LPT-grade) -- and dispatch
        # order mirrors a real async population, whose fastest clients
        # report first (the staleness the async fold sees is honest).
        order = np.argsort(steps_pc, kind="stable")
        b_stats = {e: {"clients": 0, "chunks": 0, "executed_steps": 0,
                       "true_steps": 0} for e in self.edges}
        ordinal = 0
        for c0 in range(0, C, self.client_chunk):
            chunk = [int(i) for i in order[c0:c0 + self.client_chunk]]
            k = len(chunk)
            trip = int(steps_pc[chunk].max())
            edge = int(bucket_edge_for(trip, self.edges))
            with tracer.span("pack", clients=int(k),
                             rows=sum(ns[i] for i in chunk)):
                sched = pack_schedule([ns[i] for i in chunk], bs,
                                      self.epochs, rng=data_rng, s_max=edge,
                                      step_bucket=self.step_bucket)
                xb, yb = gather_batches(datasets, sched, chunk)
            maskb = sched["mask"]
            n_arr = sched["n"]
            rngs = client_keys[chunk]
            if k < self.client_chunk:  # ragged final chunk: pad to the
                # bucket's ONE compiled shape with inert clients
                pad = self.client_chunk - k
                xb, yb, maskb, n_arr = zero_pad_leading(
                    (xb, yb, maskb, n_arr), pad)
                rngs = np.concatenate([rngs, rngs[:1].repeat(pad, 0)])
            born = aggregator.version if aggregator else 0
            with tracer.span("h2d") as sp:
                batches_dev = {"x": jnp.asarray(xb), "y": jnp.asarray(yb),
                               "mask": jnp.asarray(maskb)}
                ns_dev, rngs_dev = jnp.asarray(n_arr), jnp.asarray(rngs)
                # the trip count is a transfer too (0.5 ms a chunk on the
                # chip's host): inside the span, not between two
                trip_dev = jnp.int32(trip)
                note_bytes(sp, (batches_dev, ns_dev, rngs_dev))
            args = (gs, batches_dev, ns_dev, trip_dev, rngs_dev)
            ids = None
            if self.compressor is not None:
                # EF residual rows for this chunk, gathered by STABLE
                # client id; padded lanes carry zero rows that share the
                # bucket's one compiled shape and are sliced off before
                # the scatter-back (their updates are discarded)
                ids = [client_ids[i] for i in chunk]
                res = residual_store.gather(ids)
                crngs = comp_keys[chunk]
                if k < self.client_chunk:
                    pad = self.client_chunk - k
                    res = jax.tree.map(
                        lambda x: jnp.concatenate(
                            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]),
                        res)
                    crngs = np.concatenate(
                        [crngs, crngs[:1].repeat(pad, 0)])
                args = args + (res, jnp.asarray(crngs))
            with tracer.span("bucket-chunk", edge=edge, clients=int(k),
                             trip=trip):
                out = self._chunk_fn(*args)
            if self.compressor is None:
                pay_sum, w_sum, msum = out
                scatter = None
            else:
                pay_sum, w_sum, msum, new_res = out
                scatter = (ids, new_res)
            del out
            if on_device:
                if acc_hi is None:
                    acc_hi = pay_sum  # chunk 0's sum IS the high word
                else:
                    with tracer.span("fold.add", on="device"):
                        acc_hi, acc_lo = (
                            self._fold_first(acc_hi, pay_sum)
                            if acc_lo is None else
                            self._fold_next(acc_hi, acc_lo, pay_sum))
                pay_sum = None  # the accumulator's, or the runtime's to free
            if cm is not None:
                if edge not in self._edge_costs:
                    # abstract AOT probe of this bucket shape's program
                    # (the dispatch above runs async meanwhile):
                    # ShapeDtypeStructs only, so the probe never holds
                    # or syncs device buffers
                    self._edge_costs[edge] = program_cost(
                        self._chunk_fn, *abstract(args))
                # note() every time (setdefault-idempotent): a CostModel
                # armed AFTER the runner warmed its edge cache must
                # still collect the catalog
                cm.note(f"bucket_chunk_s{edge}", self._edge_costs[edge])
            inflight.append((ordinal, born, k, (pay_sum, w_sum, msum),
                             scatter))
            ordinal += 1
            st = b_stats[edge]
            st["clients"] += k
            st["chunks"] += 1
            # padded lanes of the (single) ragged final chunk run too --
            # the waste accounting counts every executed vmap lane
            st["executed_steps"] += trip * self.client_chunk
            st["true_steps"] += int(steps_pc[chunk].sum())
            exec_steps += trip * self.client_chunk
            while len(inflight) > depth:
                fold_oldest()
        flops_exec, flops_true, have_cost = 0.0, 0.0, False
        for e in self.edges:
            st = b_stats[e]
            row = {"edge": int(e), "skipped": int(st["chunks"] == 0), **st}
            pc = self._edge_costs.get(e)
            if pc is not None and st["chunks"]:
                # XLA cost analysis charges a dynamic-trip loop body
                # ONCE: program flops ~= one step across all client_chunk
                # lanes (+ the per-dispatch aggregation epilogue, which
                # step-dominated chunks amortize -- docs/OBSERVABILITY.md)
                per_lane_step = pc.flops / self.client_chunk
                row["flops_per_step"] = per_lane_step
                row["executed_flops"] = per_lane_step * st["executed_steps"]
                row["true_flops"] = per_lane_step * st["true_steps"]
                row["bytes_accessed"] = pc.bytes_accessed
                flops_exec += row["executed_flops"]
                flops_true += row["true_flops"]
                have_cost = True
            per_bucket.append(row)

        while inflight:
            fold_oldest()
        if aggregator is not None:
            if aggregator.depth:
                # round-boundary drain: whatever is buffered flushes even
                # below K (the stream is over; holding updates across
                # rounds would starve the last window)
                res = aggregator.flush("drain")
                apply_avg(res.params, flushes)
                flushes += 1
            async_info = aggregator.record()
            async_info["async/flushes_this_round"] = flushes
        else:
            # before the quotient and the server step, which donate the
            # sum and the state
            if acc_hi is None or sync_w <= 0:
                raise ValueError("bucketed round folded zero weight "
                                 "(every cohort shard empty?)")
            with tracer.span("fold.finalize"):
                avg = self._fold_quotient(acc_hi, acc_lo,
                                          *split_total(sync_w), dtypes)
            acc_hi = acc_lo = None
            apply_avg(avg, 0)
            flushes = 1
            async_info = None

        true_steps = int(steps_pc.sum())
        row_positions = sum(self._row_plan.values())
        info = {
            "aux": {"n": np.asarray(ns, np.float32),
                    "steps": steps_pc.astype(np.int64)},
            "metrics": metrics_acc,
            "fold": "device" if on_device else "host",
            # how the lookup tables were stepped: by the rows looked up
            # (positions a step times the clients' steps) or densely
            "embed": {"embed.step": "rows" if row_positions else "dense",
                      "embed.rows": row_positions * true_steps},
            "bucket": {
                "edges": list(self.edges),
                "buckets_used": sum(1 for b in per_bucket
                                    if not b["skipped"]),
                "clients": C, "chunks": ordinal,
                "executed_steps": int(exec_steps),
                "true_steps": true_steps,
                "waste_frac": round(1.0 - true_steps / max(exec_steps, 1),
                                    4),
                "per_bucket": per_bucket,
            },
        }
        if have_cost and flops_exec > 0:
            # padded waste in FLOPs, from the programs actually compiled
            # (not step counts): buckets missing a cost probe are
            # excluded from both numerator and denominator
            info["bucket"]["executed_flops"] = flops_exec
            info["bucket"]["true_flops"] = flops_true
            info["bucket"]["flops_waste_frac"] = round(
                1.0 - flops_true / flops_exec, 4)
            info["bucket"]["flops_source"] = "xla"
        if async_info is not None:
            info["async"] = async_info
        if self.compressor is not None:
            info["wire"] = wire_record(self.wire_bytes, C)
        return gs, ss, info

    def programs(self, global_state, server_state, client_indexes):
        """Every program a round over ``client_indexes`` dispatches, as
        ``(name, jitted function, abstract arguments)``: one chunk
        program per bucket edge (whichever cohort comes, its chunks land
        on these), the donated server advance and, on the synchronous
        path, the device fold's programs as far as the cohort's chunk
        count reaches them."""
        shard = next(d for d in self.shards.values() if len(d["y"]))
        chunk, bs = self.client_chunk, self.batch_size
        gs, ss = abstract(global_state), abstract(server_state)
        key = key_abstract()
        keys = jax.ShapeDtypeStruct((chunk,) + key.shape, key.dtype)
        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
        out = []
        for edge in self.edges:
            batches = {k: jax.ShapeDtypeStruct(
                (chunk, edge, bs) + shard[k].shape[1:], shard[k].dtype)
                for k in ("x", "y")}
            batches["mask"] = f32(chunk, edge, bs)
            args = (gs, batches, f32(chunk),
                    jax.ShapeDtypeStruct((), jnp.int32), keys)
            if self.compressor is not None:
                args += (residuals_abstract(gs["params"], chunk), keys)
            out.append((f"bucket_chunk_s{edge}", self._chunk_fn, args))
        pay = jax.eval_shape(self._chunk_fn, *args)[0]
        dtypes = abstract(_payload_dtypes(self, global_state))
        if self.aggregator is None:
            n_chunks = -(-len(client_indexes) // chunk)
            if n_chunks > 1:
                out.append(("fold_first", self._fold_first, (pay, pay)))
            if n_chunks > 2:
                out.append(("fold_next", self._fold_next, (pay, pay, pay)))
            quotient = (pay, pay if n_chunks > 1 else None, f32(), f32(),
                        dtypes)
            out.append(("fold_quotient", self._fold_quotient, quotient))
            avg = jax.eval_shape(self._fold_quotient, *quotient)
        else:
            avg = jax.tree.map(
                lambda a, d: jax.ShapeDtypeStruct(a.shape, d.dtype),
                pay, dtypes)
        out.append(("advance", self._advance_fn, (gs, ss, avg, key)))
        return out


class WaveRunner:
    """Size-sorted wave execution of a federated round over device-resident
    data -- the throughput path for single-chip cohorts.

    The flat ``make_indexed_sim_round`` pads every client to the cohort-max
    step count, so under a skewed LDA partition most clients burn most steps
    on fully-masked fwd+bwd no-ops. Here the cohort is sorted by true step
    count and dispatched in waves of ``client_chunk`` clients; each wave runs
    one jitted program whose ``fori_loop`` trip count is the *wave* maximum
    (a traced scalar -- no recompilation across waves or rounds). Weighted
    payload sums accumulate on device across waves; a final jitted step
    normalizes and applies ``server_fn``. Total executed steps drop from
    ``C x S_max`` to ``sum_w k x S_w`` -- the padding-waste fix for the
    reference's straggler problem (its MPI path simply blocks on the slowest
    client process, ``FedAVGAggregator.py:58-87``).

    Consumes the SAME ``pack_schedule`` output (same host-RNG draw) as the
    flat path, so switching paths never perturbs the data stream, and
    checkpoints resume across either.
    """

    mode = "waves"

    def __init__(self, spec: TrainSpec, cfg: ClientUpdateConfig,
                 payload_fn=None, server_fn=None, client_chunk=8):
        self.payload_fn = payload_fn or _default_payload
        self.server_fn = server_fn or _default_server
        self.client_chunk = int(client_chunk or 8)
        client_update = make_loop_client_update(spec, cfg)
        payload_fn_ = self.payload_fn
        server_fn_ = self.server_fn

        @jax.jit
        def wave_fn(global_state, device_x, device_y, ids, sched, steps, rngs):
            data = {"x": jnp.take(device_x, ids, axis=0),
                    "y": jnp.take(device_y, ids, axis=0)}
            local_states, aux, metrics = jax.vmap(
                client_update, in_axes=(None, 0, 0, None, 0))(
                    global_state, data, sched, steps, rngs)
            payloads = jax.vmap(payload_fn_, in_axes=(0, None, 0))(
                local_states, global_state, aux)
            w = aux["n"].astype(jnp.float32)
            metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0), metrics)
            return (_weighted_payload_sum(payloads, w), jnp.sum(w),
                    metrics_sum, aux)

        @jax.jit
        def add_fn(a, b):
            return jax.tree.map(jnp.add, a, b)

        @jax.jit
        def finish_fn(global_state, server_state, pay_sum, w_sum, dtypes, rng):
            return server_fn_(global_state,
                              _average_through(pay_sum, w_sum, dtypes),
                              server_state, rng)

        self._wave_fn = wave_fn
        self._add_fn = add_fn
        self._finish_fn = finish_fn
        self._dtypes = None

    def programs(self, global_state, server_state, device_data, ids, sched):
        """The per-wave program, its cross-wave add and the finish step
        (operand shapes from the wave's outputs), at ``sched``'s shapes."""
        chunk = min(self.client_chunk, len(ids))
        gs, ss = abstract(global_state), abstract(server_state)
        key = key_abstract()
        sds = jax.ShapeDtypeStruct
        ws = {"idx": sds((chunk,) + sched["idx"].shape[1:], jnp.int32),
              "mask": sds((chunk,) + sched["mask"].shape[1:], jnp.float32),
              "n": sds((chunk,), jnp.float32)}
        wave_args = (gs, abstract(device_data["x"]),
                     abstract(device_data["y"]), sds((chunk,), jnp.int32),
                     ws, sds((), jnp.int32),
                     sds((chunk,) + key.shape, key.dtype))
        part = jax.eval_shape(self._wave_fn, *wave_args)[:3]
        return [
            ("wave", self._wave_fn, wave_args),
            ("wave_add", self._add_fn, (part, part)),
            ("wave_finish", self._finish_fn,
             (gs, ss, part[0], part[1],
              abstract(_payload_dtypes(self, global_state)), key)),
        ]

    def run_schedule(self, global_state, server_state, device_data, ids,
                     sched, rng):
        """One federated round.

        Args:
          device_data: ``{"x": [N_rows, ...], "y": [N_rows, ...]}`` full
            client shards resident in HBM (``stack_clients`` output).
          ids: cohort client rows into ``device_data`` (cohort order).
          sched: full packed schedule (``pack_schedule`` output, numpy,
            cohort order) -- ``{"idx" [C,S,B], "mask" [C,S,B], "n" [C]}``.
          rng: round PRNG key; per-client keys derive exactly as in the flat
            paths (``split(fold_in(rng, 1), C)`` indexed by cohort slot), so
            wave and flat trajectories agree to float reassociation.
        """
        import numpy as np

        mask = np.asarray(sched["mask"])
        C = mask.shape[0]
        steps_per_client = (mask.sum(axis=2) > 0).sum(axis=1).astype(np.int64)
        order = np.argsort(-steps_per_client, kind="stable")
        chunk = min(self.client_chunk, C)
        all_rngs = np.asarray(jax.random.split(jax.random.fold_in(rng, 1), C))
        ids = np.asarray(ids, np.int32)
        sched_idx = np.asarray(sched["idx"])
        sched_n = np.asarray(sched["n"], np.float32)

        acc = None
        wave_aux, wave_pos = [], []
        for w0 in range(0, C, chunk):
            pos = order[w0:w0 + chunk]
            k = len(pos)
            trip = int(steps_per_client[pos].max())
            w_idx, w_mask = sched_idx[pos], mask[pos]
            w_n, w_ids, w_rngs = sched_n[pos], ids[pos], all_rngs[pos]
            if k < chunk:  # pad the ragged last wave -> one stable jit shape
                pad = chunk - k
                w_idx, w_mask, w_n, w_ids = zero_pad_leading(
                    (w_idx, w_mask, w_n, w_ids), pad)
                w_rngs = np.concatenate([w_rngs, w_rngs[:1].repeat(pad, 0)])
            ws = {"idx": jnp.asarray(w_idx), "mask": jnp.asarray(w_mask),
                  "n": jnp.asarray(w_n)}
            # span measures dispatch (async): device time for the whole
            # round lands in the caller's end-of-round sync
            with get_tracer().span("wave", clients=int(k), trip=trip):
                pay_sum, w_sum, metrics_sum, aux = self._wave_fn(
                    global_state, device_data["x"], device_data["y"],
                    jnp.asarray(w_ids), ws, jnp.int32(trip),
                    jnp.asarray(w_rngs))
            part = (pay_sum, w_sum, metrics_sum)
            acc = part if acc is None else self._add_fn(acc, part)
            wave_aux.append(aux)
            wave_pos.append(pos)

        pay_sum, w_sum, metrics_sum = acc
        with get_tracer().span("server-update"):
            new_global, new_server_state = self._finish_fn(
                global_state, server_state, pay_sum, w_sum,
                _payload_dtypes(self, global_state),
                jax.random.fold_in(rng, 2))

        # gather per-client aux back into cohort order (host, post-dispatch)
        aux_out = {"n": np.zeros(C, np.float32),
                   "steps": np.zeros(C, np.int64)}
        for pos, aux in zip(wave_pos, wave_aux):
            k = len(pos)
            aux_out["n"][pos] = np.asarray(aux["n"])[:k]
            aux_out["steps"][pos] = np.asarray(aux["steps"])[:k]
        return new_global, new_server_state, {"aux": aux_out,
                                              "metrics": metrics_sum}


def make_lane_update(spec: TrainSpec, cfg: ClientUpdateConfig, payload_fn):
    """Build the per-lane sequential-clients update (shared by
    :class:`LaneRunner` and :class:`ShardedLaneRunner`).

    ``fn(global_state, data_x, data_y, n_max, rows, lane, step_keys, trip)
    -> (payload_weighted_sum_f32, weight_sum, metrics_sum)`` where
    ``data_x/data_y`` are device-resident stacks flattened on their first
    two axes (``[R * n_max, ...]``), ``rows`` maps schedule slot -> device
    row, ``lane`` is one lane's slice of the ``pack_lanes`` arrays and
    ``step_keys [L, 2]`` the pre-folded per-step PRNG keys. The lane
    trains its clients back-to-back: each client's final step flushes the
    weighted payload into the accumulator and resets carried state to the
    global model, so padded compute never executes.
    """
    optimizer = make_optimizer(cfg)
    grad_at = _make_grad_at(spec)

    def lane_update(global_state, data_x, data_y, n_max, rows, lane,
                    step_keys, trip):
        g_params, g_rest = _split_state(global_state)
        g_opt = optimizer.init(g_params)

        def batch_at(i):
            idx_b = jax.lax.dynamic_index_in_dim(
                lane["idx"], i, axis=0, keepdims=False)
            mask_b = jax.lax.dynamic_index_in_dim(
                lane["mask"], i, axis=0, keepdims=False)
            slot = jax.lax.dynamic_index_in_dim(
                lane["slot"], i, axis=0, keepdims=False)
            row = jnp.take(rows, slot)
            flat = row * n_max + idx_b
            return {"x": jnp.take(data_x, flat, axis=0),
                    "y": jnp.take(data_y, flat, axis=0),
                    "mask": mask_b}

        metrics0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: grad_at(
                g_params, g_rest, batch_at(0), step_keys[0]))[0][1][1])
        aux0 = {"n": jnp.float32(0), "steps": jnp.int32(0)}
        pay0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.float32),
            jax.eval_shape(payload_fn, global_state, global_state, aux0))

        def body(i, carry):
            params, rest, opt_state, pay, w, msum = carry
            batch = batch_at(i)
            step_rng = jax.lax.dynamic_index_in_dim(
                step_keys, i, axis=0, keepdims=False)
            (_, (new_state, metrics)), grads = grad_at(
                params, rest, batch, step_rng)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_rest = {k: new_state[k] for k in rest}
            valid = jnp.sum(batch["mask"]) > 0
            params, rest, opt_state = _tree_select(
                valid, (new_params, new_rest, new_opt),
                (params, rest, opt_state))
            msum = jax.tree.map(jnp.add, msum, metrics)

            # client boundary: flush weighted payload, reset to global
            f = jax.lax.dynamic_index_in_dim(
                lane["flush"], i, axis=0, keepdims=False)
            f_n = jax.lax.dynamic_index_in_dim(
                lane["flush_n"], i, axis=0, keepdims=False)
            f_steps = jax.lax.dynamic_index_in_dim(
                lane["flush_steps"], i, axis=0, keepdims=False)
            local_state = dict(rest)
            local_state["params"] = params
            payload = payload_fn(local_state, global_state,
                                 {"n": f_n,
                                  "steps": f_steps.astype(jnp.int32)})
            scale = f * f_n
            pay = jax.tree.map(
                lambda a, p: a + scale * p.astype(jnp.float32),
                pay, payload)
            w = w + scale
            params, rest, opt_state = _tree_select(
                f > 0, (g_params, g_rest, g_opt),
                (params, rest, opt_state))
            return (params, rest, opt_state, pay, w, msum)

        carry = (g_params, g_rest, g_opt, pay0, jnp.float32(0), metrics0)
        _, _, _, pay, w, msum = jax.lax.fori_loop(0, trip, body, carry)
        return pay, w, msum

    return lane_update


def make_packed_lane_update(spec: TrainSpec, cfg: ClientUpdateConfig,
                            payload_fn):
    """MXU-shaped variant of :func:`make_lane_update`: ALL lanes advance
    in one program per step, with the model's lane axis folded into
    channels by ``spec.lane_loss_builder`` (``models/lane_packed.py``)
    instead of ``jax.vmap`` over lane-stacked weights.

    Motivation (docs/PERFORMANCE.md): vmapped per-lane convs lower to
    ``feature_group_count=L`` grouped convs whose per-group K (the
    model's channel count, 16/32/64 for ResNet-56) underfills the MXU's
    128-wide systolic passes by 8x/4x/2x. The packed lowering merges
    lanes per group up to K=128. Everything outside the model forward --
    optimizer, payload, augmentation -- runs under a cheap elementwise
    ``jax.vmap`` over lanes, so per-lane semantics (valid-select, flush,
    divergent optimizer state) are bitwise those of the vmap path.

    Same signature/returns as the vmapped ``lane_update`` AFTER its
    round-level vmap: lanes arrays are ``[L, trip, ...]``, ``step_keys``
    ``[L, trip, 2]``, and the returns carry a leading lane axis.
    """
    optimizer = make_optimizer(cfg)
    if spec.lane_loss_builder is None:
        raise ValueError(
            f"spec '{spec.name}' has no lane_loss_builder: the packed "
            "lane path (wave_mode=3) supports model families with a "
            "lane-packed lowering (models/lane_packed.py); use "
            "wave_mode=2 for the generic vmap lane path")

    def packed_update(global_state, data_x, data_y, n_max, rows, lanes,
                      step_keys, trip):
        L = lanes["idx"].shape[0]  # static at trace time
        lane_loss_fn = spec.lane_loss_builder(L)

        def _select(pred, new, old):
            """Per-lane select: ``pred [L]`` against leading-L leaves."""
            return jax.tree.map(
                lambda nw, od: jnp.where(
                    pred.reshape((L,) + (1,) * (nw.ndim - 1)), nw, od),
                new, old)

        g_params, g_rest = _split_state(global_state)
        stack = lambda t: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), t)
        sg_params, sg_rest = stack(g_params), stack(g_rest)
        # per-lane init (NOT init-of-stacked): leaves like Adam's count
        # must carry a lane axis so divergent lanes can be selected
        sg_opt = jax.vmap(optimizer.init)(sg_params)

        def batch_at(i):
            idx_b = jax.lax.dynamic_index_in_dim(
                lanes["idx"], i, axis=1, keepdims=False)  # [L, B]
            mask_b = jax.lax.dynamic_index_in_dim(
                lanes["mask"], i, axis=1, keepdims=False)
            slot = jax.lax.dynamic_index_in_dim(
                lanes["slot"], i, axis=1, keepdims=False)  # [L]
            row = jnp.take(rows, slot)
            flat = row[:, None] * n_max + idx_b  # [L, B]
            x = jnp.take(data_x, flat.reshape(-1), axis=0).reshape(
                flat.shape + data_x.shape[1:])
            y = jnp.take(data_y, flat.reshape(-1), axis=0).reshape(
                flat.shape + data_y.shape[1:])
            return {"x": x, "y": y, "mask": mask_b}

        def grad_at(params, rest, batch, step_rngs):
            if spec.augment_fn is not None:
                batch = dict(batch)
                batch["x"] = jax.vmap(
                    lambda xx, k: spec.augment_fn(
                        xx, jax.random.fold_in(k, 13)))(
                    batch["x"], step_rngs)

            def loss_wrapper(p):
                state = dict(rest)
                state["params"] = p
                return lane_loss_fn(state, batch, step_rngs, True)

            return jax.value_and_grad(loss_wrapper, has_aux=True)(params)

        metrics0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: grad_at(
                sg_params, sg_rest, batch_at(0),
                step_keys[:, 0]))[0][1][1])
        aux0 = {"n": jnp.zeros((L,), jnp.float32),
                "steps": jnp.zeros((L,), jnp.int32)}
        vpayload = jax.vmap(payload_fn, in_axes=(0, None, 0))
        pay0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.float32),
            jax.eval_shape(vpayload, {**sg_rest, "params": sg_params},
                           global_state, aux0))

        def body(i, carry):
            params, rest, opt_state, pay, w, msum = carry
            batch = batch_at(i)
            step_rngs = jax.lax.dynamic_index_in_dim(
                step_keys, i, axis=1, keepdims=False)  # [L, 2]
            (_, (new_state, metrics)), grads = grad_at(
                params, rest, batch, step_rngs)
            updates, new_opt = jax.vmap(optimizer.update)(
                grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_rest = {k: new_state[k] for k in rest}
            valid = jnp.sum(batch["mask"], axis=1) > 0  # [L]
            params, rest, opt_state = _select(
                valid, (new_params, new_rest, new_opt),
                (params, rest, opt_state))
            msum = jax.tree.map(jnp.add, msum, metrics)

            f = jax.lax.dynamic_index_in_dim(
                lanes["flush"], i, axis=1, keepdims=False)  # [L]
            f_n = jax.lax.dynamic_index_in_dim(
                lanes["flush_n"], i, axis=1, keepdims=False)
            f_steps = jax.lax.dynamic_index_in_dim(
                lanes["flush_steps"], i, axis=1, keepdims=False)
            local_state = dict(rest)
            local_state["params"] = params
            payload = vpayload(local_state, global_state,
                               {"n": f_n, "steps": f_steps.astype(jnp.int32)})
            scale = f * f_n  # [L]
            pay = jax.tree.map(
                lambda a, p: a + scale.reshape(
                    (L,) + (1,) * (p.ndim - 1)) * p.astype(jnp.float32),
                pay, payload)
            w = w + scale
            params, rest, opt_state = _select(
                f > 0, (sg_params, sg_rest, sg_opt),
                (params, rest, opt_state))
            return (params, rest, opt_state, pay, w, msum)

        carry = (sg_params, sg_rest, sg_opt, pay0, jnp.zeros((L,),
                                                             jnp.float32),
                 metrics0)
        _, _, _, pay, w, msum = jax.lax.fori_loop(0, trip, body, carry)
        return pay, w, msum

    return packed_update


class LaneRunner:
    """Packed-lane execution: the WHOLE round as ONE jitted dispatch.

    ``pack_lanes`` lays the cohort's per-client step schedules end-to-end
    into K balanced lanes (LPT). Each lane's ``fori_loop`` trains clients
    back-to-back: at a client's final step the lane flushes the weighted
    payload into an on-device accumulator and resets its carried state to
    the global model, so no lane ever executes a padded fwd+bwd. Wall
    steps per round = max lane load ~= ceil(total_steps / K): strictly
    less straggle than size-sorted waves (``WaveRunner``), with a single
    program launch per round. RNG per client step is
    ``fold_in(client_key, local_step)`` with the same client keys as the
    flat paths, so lane, wave, and flat trajectories agree to float
    reassociation (tested in ``tests/test_engine.py``).

    Reference contrast: one torch process per client, rounds gated on the
    slowest process (``FedAVGAggregator.py:58-87``); here the scheduler
    is ~50 lines of host numpy and the chip never idles.
    """

    def __init__(self, spec: TrainSpec, cfg: ClientUpdateConfig,
                 payload_fn=None, server_fn=None, n_lanes=8, packed=False):
        self.payload_fn = payload_fn or _default_payload
        self.server_fn = server_fn or _default_server
        self.n_lanes = int(n_lanes or 8)
        self.packed = bool(packed)
        self.mode = "mxu-lanes" if self.packed else "lanes"
        if self.packed:
            # MXU-shaped lowering: lane axis folded into channels by the
            # spec's lane_loss_builder (raises if the model family has
            # none) instead of vmap over lane-stacked weights
            packed_update = make_packed_lane_update(spec, cfg, self.payload_fn)
        else:
            lane_update = make_lane_update(spec, cfg, self.payload_fn)
        server_fn_ = self.server_fn

        @partial(jax.jit, donate_argnums=(0, 1))
        def round_fn(global_state, server_state, device_x, device_y, rows,
                     lanes, step_keys, trip, dtypes, rng):
            R, n_max = device_x.shape[0], device_x.shape[1]
            dx = device_x.reshape((R * n_max,) + device_x.shape[2:])
            dy = device_y.reshape((R * n_max,) + device_y.shape[2:])
            if self.packed:
                pay, w, msum = packed_update(
                    global_state, dx, dy, n_max, rows, lanes, step_keys,
                    trip)
            else:
                pay, w, msum = jax.vmap(
                    lane_update, in_axes=(None, None, None, None, None, 0,
                                          0, None))(
                    global_state, dx, dy, n_max, rows, lanes, step_keys,
                    trip)
            pay_sum = jax.tree.map(lambda x: jnp.sum(x, axis=0), pay)
            w_sum = jnp.sum(w)
            metrics_sum = jax.tree.map(lambda m: jnp.sum(m, axis=0), msum)
            new_global, new_server = server_fn_(
                global_state, _average_through(pay_sum, w_sum, dtypes),
                server_state, rng)
            return new_global, new_server, metrics_sum

        self._round_fn = round_fn
        self._fold_keys = fold_step_keys
        self._dtypes = None

    def _lanes(self, sched):
        """``pack_lanes`` of a schedule as the round program takes it:
        ``(lane arrays, local_step, trip)``, on the host."""
        from fedml_tpu.parallel.packing import pack_lanes

        lanes = pack_lanes(sched, self.n_lanes)
        trip = max(lanes.pop("trip"), 1)
        local_step = lanes.pop("local_step")
        return ({k: lanes[k] for k in ("idx", "mask", "slot", "flush",
                                       "flush_n", "flush_steps")},
                local_step, trip)

    def programs(self, global_state, server_state, device_data, ids, sched):
        """The round's ONE donated program and the per-step PRNG
        derivation (its own jitted dispatch), at the lane shapes the same
        ``pack_lanes`` call gives ``run_schedule``."""
        lanes, local_step, _ = self._lanes(sched)
        key = key_abstract()
        sds = jax.ShapeDtypeStruct
        C, KL = len(ids), local_step.shape
        return [
            ("mxu_lane_round" if self.packed else "lane_round",
             self._round_fn,
             (abstract(global_state), abstract(server_state),
              abstract(device_data["x"]), abstract(device_data["y"]),
              sds((C,), jnp.int32), abstract(lanes),
              sds(KL + key.shape, key.dtype), sds((), jnp.int32),
              abstract(_payload_dtypes(self, global_state)), key)),
            ("fold_step_keys", self._fold_keys,
             (sds((C,) + key.shape, key.dtype), sds(KL, jnp.int32),
              sds(KL, jnp.int32))),
        ]

    def run_schedule(self, global_state, server_state, device_data, ids,
                     sched, rng):
        """Same contract as :meth:`WaveRunner.run_schedule` (cohort
        ``ids`` into ``device_data``, full ``pack_schedule`` output, round
        key); executes as one dispatch over ``n_lanes`` packed lanes."""
        import numpy as np

        C = len(np.asarray(sched["n"]))
        lanes, local_step, trip = self._lanes(sched)
        client_keys = jax.random.split(jax.random.fold_in(rng, 1), C)
        lane_arrays = {k: jnp.asarray(v) for k, v in lanes.items()}
        step_keys = self._fold_keys(client_keys, lane_arrays["slot"],
                                    jnp.asarray(local_step))
        rows = jnp.asarray(np.asarray(ids, np.int32))
        with get_tracer().span("lanes", clients=int(C),
                               n_lanes=int(self.n_lanes), trip=trip):
            new_global, new_server, metrics = self._round_fn(
                global_state, server_state, device_data["x"],
                device_data["y"], rows, lane_arrays, step_keys,
                jnp.int32(trip), _payload_dtypes(self, global_state),
                jax.random.fold_in(rng, 2))
        steps_pc = (np.asarray(sched["mask"]).sum(axis=2) > 0).sum(axis=1)
        aux = {"n": np.asarray(sched["n"], np.float32),
               "steps": steps_pc.astype(np.int64)}
        return new_global, new_server, {"aux": aux, "metrics": metrics}


class ShardedLaneRunner:
    """Packed lanes over a ``clients`` mesh: the multi-chip round as one
    SPMD dispatch with zero padded compute per shard.

    Client shards live in HBM sharded over the mesh's ``clients`` axis
    (each device owns a contiguous block of client rows); every mesh shard
    runs ITS resident cohort members as LPT-packed lanes (the
    :func:`make_lane_update` program), then the weighted payload sums meet
    in a ``psum`` over ICI and the server step runs replicated. This
    composes the single-chip lane design with the reference's multi-worker
    scaling story (SURVEY.md section 2.7/2.8): where the reference gates
    every round on its slowest client process and moves pickled
    state_dicts through MPI, here the only cross-chip traffic is one
    weighted-payload reduction.

    The fori_loop trip count is the max lane load across ALL shards
    (uniform SPMD control flow); shards with lighter loads run guarded
    no-op steps for the difference, so balance comes from placing clients
    on shards evenly (``FedAvgAPI`` places contiguous blocks; LDA skew
    within a block is absorbed by the in-shard LPT packing).
    """

    mode = "sharded-lanes"

    def __init__(self, spec: TrainSpec, cfg: ClientUpdateConfig, mesh,
                 payload_fn=None, server_fn=None, n_lanes=8, packed=False):
        self.payload_fn = payload_fn or _default_payload
        self.server_fn = server_fn or _default_server
        self.n_lanes = int(n_lanes or 8)
        self.mesh = mesh
        self.packed = bool(packed)
        if self.packed:
            # each shard runs ITS lanes through the MXU-shaped lowering
            # (models/lane_packed.py); the cross-chip psum is unchanged
            packed_update = make_packed_lane_update(spec, cfg, self.payload_fn)
        else:
            lane_update = make_lane_update(spec, cfg, self.payload_fn)
        server_fn_ = self.server_fn

        def shard_fn(global_state, server_state, dx, dy, rows, lanes,
                     step_keys, trip, dtypes, rng):
            # leading mesh axis arrives size-1 under shard_map: squeeze
            rows_l = rows[0]
            lanes_l = jax.tree.map(lambda a: a[0], lanes)
            keys_l = step_keys[0]
            R_local, n_max = dx.shape[0], dx.shape[1]
            dxf = dx.reshape((R_local * n_max,) + dx.shape[2:])
            dyf = dy.reshape((R_local * n_max,) + dy.shape[2:])
            if self.packed:
                pay, w, msum = packed_update(
                    global_state, dxf, dyf, n_max, rows_l, lanes_l,
                    keys_l, trip)
            else:
                pay, w, msum = jax.vmap(
                    lane_update,
                    in_axes=(None, None, None, None, None, 0, 0, None))(
                    global_state, dxf, dyf, n_max, rows_l, lanes_l, keys_l,
                    trip)
            pay_sum = jax.tree.map(
                lambda x: jax.lax.psum(jnp.sum(x, axis=0), CLIENT_AXIS),
                pay)
            w_sum = jax.lax.psum(jnp.sum(w), CLIENT_AXIS)
            metrics = jax.tree.map(
                lambda m: jax.lax.psum(jnp.sum(m, axis=0), CLIENT_AXIS),
                msum)
            new_global, new_server = server_fn_(
                global_state, _average_through(pay_sum, w_sum, dtypes),
                server_state, rng)
            return new_global, new_server, metrics

        sharded = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(), P(CLIENT_AXIS), P(CLIENT_AXIS),
                      P(CLIENT_AXIS), P(CLIENT_AXIS), P(CLIENT_AXIS),
                      P(), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False)
        self._round_fn = jax.jit(sharded, donate_argnums=(0, 1))
        self._fold_keys = fold_step_keys
        self._dtypes = None

    def programs(self, global_state, server_state, device_data, ids, sched):
        """Nothing yet: the SPMD shard shapes are not enumerated
        (ROADMAP), so a sharded-lane round compiles at its first
        dispatch."""
        logging.info("fedwarm: mesh-sharded lane rounds are not warmed "
                     "yet (SPMD shard shapes; follow-up)")
        return []

    def run_schedule(self, global_state, server_state, device_data, ids,
                     sched, rng):
        """Same contract as :meth:`LaneRunner.run_schedule`;
        ``device_data`` is SHARDED over the mesh's client axis (row
        blocks of size ``R / D``), and ``ids`` are global device rows."""
        import numpy as np

        from fedml_tpu.parallel.packing import pack_lanes

        mask = np.asarray(sched["mask"])
        C = mask.shape[0]
        D = self.mesh.shape[CLIENT_AXIS]
        R = int(device_data["x"].shape[0])
        assert R % D == 0, (R, D)
        block = R // D
        ids = np.asarray(ids, np.int64)
        K = self.n_lanes

        # split the cohort by owning shard; size lanes with the cheap
        # max-load query, pack arrays once per shard below
        from fedml_tpu.parallel.packing import lane_max_load

        steps_pc_all = (mask.sum(axis=2) > 0).sum(axis=1)
        per_shard = []
        l_needed = 1
        for d in range(D):
            members = np.nonzero((ids >= d * block)
                                 & (ids < (d + 1) * block))[0]
            sub = {k: np.asarray(sched[k])[members]
                   for k in ("idx", "mask", "n")}
            if len(members) == 0:
                sub = {"idx": np.zeros((1,) + mask.shape[1:], np.int32),
                       "mask": np.zeros((1,) + mask.shape[1:], np.float32),
                       "n": np.zeros((1,), np.float32)}
            else:
                l_needed = max(l_needed,
                               lane_max_load(steps_pc_all[members], K))
            per_shard.append((members, sub))

        # uniform allocation across shards (SPMD arrays must stack);
        # power-of-two bucket bounds recompiles across rounds
        L = 8
        while L < l_needed:
            L *= 2

        client_keys = jax.random.split(jax.random.fold_in(rng, 1), C)
        keys_np = np.asarray(client_keys)
        lane_stack, key_stack, row_stack, trips = [], [], [], []
        for d, (members, sub) in enumerate(per_shard):
            lanes = pack_lanes(sub, K, l_max=L)
            trips.append(lanes.pop("trip"))
            local_step = lanes.pop("local_step")
            k_sub = lanes["idx"].shape[0]
            if k_sub < K:  # pack_lanes clamps K to the member count;
                # pad with inert zero lanes so shards stack uniformly
                lanes = {k: np.concatenate(
                    [v, np.zeros((K - k_sub,) + v.shape[1:], v.dtype)])
                    for k, v in lanes.items()}
                local_step = np.concatenate(
                    [local_step,
                     np.zeros((K - k_sub,) + local_step.shape[1:],
                              local_step.dtype)])
            # slot -> LOCAL device row for this shard's member list
            rows_local = np.zeros((max(block, 1),), np.int32)
            if len(members):
                rows_local[:len(members)] = ids[members] - d * block
                member_keys = keys_np[members]
            else:
                member_keys = keys_np[:1]
            lane_stack.append(lanes)
            key_stack.append(self._fold_keys(
                jnp.asarray(member_keys), jnp.asarray(lanes["slot"]),
                jnp.asarray(local_step)))
            row_stack.append(rows_local)
        lanes_all = jax.tree.map(
            lambda *xs: jnp.asarray(np.stack(xs)), *lane_stack)
        keys_all = jnp.stack(key_stack)
        rows_all = jnp.asarray(np.stack(row_stack))
        trip = jnp.int32(max(max(trips), 1))

        with get_tracer().span("sharded-lanes", clients=int(C),
                               shards=int(D), trip=int(max(max(trips), 1))):
            new_global, new_server, metrics = self._round_fn(
                global_state, server_state, device_data["x"],
                device_data["y"], rows_all, lanes_all, keys_all, trip,
                _payload_dtypes(self, global_state),
                jax.random.fold_in(rng, 2))
        steps_pc = (mask.sum(axis=2) > 0).sum(axis=1)
        aux = {"n": np.asarray(sched["n"], np.float32),
               "steps": steps_pc.astype(np.int64)}
        return new_global, new_server, {"aux": aux, "metrics": metrics}


def make_indexed_sim_round(spec: TrainSpec, cfg: ClientUpdateConfig,
                           payload_fn=None, server_fn=None,
                           client_chunk=None):
    """Single-chip round over device-resident data + index schedules.

    ``fn(global_state, server_state, device_data, sched, rng)`` with
    ``device_data`` leading axis = cohort clients. ``client_chunk`` bounds
    peak activation memory: clients run in sequential waves of ``chunk``
    (``lax.map`` outer, ``vmap`` inner) instead of all at once -- the knob
    that lets 32-client ResNet cohorts fit one chip's HBM.
    """
    client_update = make_indexed_client_update(spec, cfg)
    payload_fn = payload_fn or _default_payload
    server_fn = server_fn or _default_server

    @partial(jax.jit, donate_argnums=(0, 1))
    def round_fn(global_state, server_state, device_data, sched, rng):
        C = sched["mask"].shape[0]
        rngs = jax.random.split(jax.random.fold_in(rng, 1), C)
        server_rng = jax.random.fold_in(rng, 2)

        def run(d, s, r):
            return jax.vmap(client_update, in_axes=(None, 0, 0, 0))(
                global_state, d, s, r)

        chunk = client_chunk
        if chunk is not None and chunk < C:
            # pad the cohort to a chunk multiple with fully-masked dummy
            # clients (the shared zero_pad_leading invariant) so the
            # memory knob works for any cohort size
            pad = (-C) % chunk
            if pad:
                device_data = zero_pad_leading(device_data, pad, jnp)
                sched_p = zero_pad_leading(sched, pad, jnp)
                rngs_p = jnp.concatenate([rngs, rngs[:1].repeat(pad, 0)])
            else:
                sched_p, rngs_p = sched, rngs
            Cp = C + pad
            waves = Cp // chunk
            reshard = lambda a: a.reshape((waves, chunk) + a.shape[1:])
            dd = jax.tree.map(reshard, device_data)
            ss = jax.tree.map(reshard, sched_p)
            rr = reshard(rngs_p)
            local_states, aux, metrics = jax.lax.map(
                lambda args: run(*args), (dd, ss, rr))
            unshard = lambda a: a.reshape((Cp,) + a.shape[2:])[:C]
            local_states, aux, metrics = jax.tree.map(
                unshard, (local_states, aux, metrics))
        else:
            local_states, aux, metrics = run(device_data, sched, rngs)

        payloads = jax.vmap(payload_fn, in_axes=(0, None, 0))(
            local_states, global_state, aux)
        avg_payload = pytree.tree_weighted_mean(payloads, aux["n"])
        new_global, new_server_state = server_fn(
            global_state, avg_payload, server_state, server_rng)
        return new_global, new_server_state, {"aux": aux, "metrics": metrics}

    return round_fn


def _default_payload(local_state, global_state, aux):
    return local_state


def _default_server(global_state, avg_payload, server_state, rng):
    return avg_payload, server_state


def payload_dtype_template(payload_fn, global_state):
    """Zero-scalar pytree carrying the payload's dtypes (the accumulators
    run in f32; the final average casts back through this template).
    Shared by every accumulate-then-normalize runner."""
    aux = {"n": jax.ShapeDtypeStruct((), jnp.float32),
           "steps": jax.ShapeDtypeStruct((), jnp.int32)}
    shapes = jax.eval_shape(payload_fn, global_state, global_state, aux)
    return jax.tree.map(lambda s: jnp.zeros((), s.dtype), shapes)


def _payload_dtypes(runner, global_state):
    """``runner``'s payload dtype template, made at its first round."""
    if runner._dtypes is None:
        runner._dtypes = payload_dtype_template(runner.payload_fn,
                                                global_state)
    return runner._dtypes


def _weighted_payload_sum(payloads, w):
    """``sum_c w[c] * payloads[c]`` over the leading client axis, f32."""
    return jax.tree.map(
        lambda x: jnp.tensordot(w, x.astype(jnp.float32), axes=(0, 0)),
        payloads)


def _average_through(pay_sum, w_sum, dtypes):
    """The weighted mean of accumulated f32 sums, cast back through the
    payload dtype template. No uniform fallback (unlike
    ``pytree.tree_weighted_mean``): an all-empty cohort (``w_sum == 0``)
    yields a zero payload, so callers fail fast on empty cohorts before
    dispatch (``FedAvgAPI`` raises)."""
    return jax.tree.map(
        lambda s, d: (s / jnp.maximum(w_sum, 1e-12)).astype(d.dtype),
        pay_sum, dtypes)


def key_abstract():
    """The abstract value of a PRNG key as the runners pass it."""
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def residuals_abstract(params, rows):
    """``rows`` error-feedback residuals, abstract: a residual has its
    parameter's shape and dtype."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((rows,) + a.shape, a.dtype), params)


def abstract(tree):
    """Pytree of arrays / ShapeDtypeStructs -> all-ShapeDtypeStructs: the
    arguments a runner's ``programs()`` lists."""
    return jax.eval_shape(lambda t: t, tree)


def wire_record(wire_bytes, cohort):
    """Client->server update traffic of one round (uplink; the downlink
    model broadcast is uncompressed and identical in both regimes, so
    the ratio isolates what compression buys). ``wire_bytes`` is one
    client's (encoded, raw) bytes: static given the template, so the
    packed compressed round and the streaming-EF path account alike."""
    wire, raw = wire_bytes[0] * cohort, wire_bytes[1] * cohort
    return {"bytes_on_wire": wire, "compression_ratio": round(raw / wire, 3)}


@jax.jit
def fold_step_keys(client_keys, slot, local_step):
    """Per-step PRNG keys for packed lanes:
    ``keys[k, i] = fold_in(client_keys[slot[k, i]], local_step[k, i])`` --
    the exact per-client-step derivation of the flat paths."""

    def one(s, t):
        return jax.random.fold_in(jnp.take(client_keys, s, axis=0), t)

    return jax.vmap(jax.vmap(one))(slot, local_step)


def make_sim_round(spec: TrainSpec, cfg: ClientUpdateConfig,
                   payload_fn=None, server_fn=None):
    """Single-chip round: clients vmapped over the cohort axis.

    ``fn(global_state, server_state, cohort_data, rng) ->
    (new_global, new_server_state, metrics)`` -- semantics of the reference
    standalone loop (``fedavg_api.py:40-115``) in one jitted call.
    """
    client_update = make_client_update(spec, cfg)
    payload_fn = payload_fn or _default_payload
    server_fn = server_fn or _default_server

    @partial(jax.jit, donate_argnums=(0, 1))
    def round_fn(global_state, server_state, cohort_data, rng):
        C = cohort_data["mask"].shape[0]
        # identical rng derivation as make_sharded_round so the two placements
        # produce bit-identical trajectories for stochastic models too
        rngs = jax.random.split(jax.random.fold_in(rng, 1), C)
        server_rng = jax.random.fold_in(rng, 2)
        local_states, aux, metrics = jax.vmap(
            client_update, in_axes=(None, 0, 0))(global_state, cohort_data, rngs)
        payloads = jax.vmap(payload_fn, in_axes=(0, None, 0))(
            local_states, global_state, aux)
        avg_payload = pytree.tree_weighted_mean(payloads, aux["n"])
        new_global, new_server_state = server_fn(
            global_state, avg_payload, server_state, server_rng)
        return new_global, new_server_state, {"aux": aux, "metrics": metrics}

    return round_fn


def make_sharded_round(spec: TrainSpec, cfg: ClientUpdateConfig, mesh,
                       payload_fn=None, server_fn=None):
    """Pod-scale round: cohort sharded over the ``clients`` mesh axis.

    Each shard trains ``C / n_shards`` clients (vmapped locally), then the
    weighted average runs as ``psum`` collectives over ICI -- the TPU-native
    replacement for MPISendThread + CPU aggregation (reference
    ``mpi/com_manager.py:36-79`` + ``FedAVGAggregator.py:58-87``).
    Works on any mesh size including 1x1, so the same code path serves
    single-chip runs and pod slices.
    """
    client_update = make_client_update(spec, cfg)
    payload_fn = payload_fn or _default_payload
    server_fn = server_fn or _default_server

    def shard_fn(global_state, server_state, cohort_data, rng):
        # leading axis of cohort_data here is the *local* client count C/D
        local_states, aux, metrics = jax.vmap(
            client_update, in_axes=(None, 0, 0))(
                global_state, cohort_data, cohort_data["rngs"])
        payloads = jax.vmap(payload_fn, in_axes=(0, None, 0))(
            local_states, global_state, aux)
        w = aux["n"].astype(jnp.float32)
        local_sum = _weighted_payload_sum(payloads, w)
        total = jnp.maximum(jax.lax.psum(jnp.sum(w), CLIENT_AXIS), 1e-12)
        avg_payload = jax.tree.map(
            lambda x, t: (jax.lax.psum(x, CLIENT_AXIS) / total).astype(t.dtype),
            local_sum, jax.tree.map(lambda x: x[0], payloads))
        new_global, new_server_state = server_fn(
            global_state, avg_payload, server_state, rng)
        return new_global, new_server_state, {"aux": aux, "metrics": metrics}

    sharded = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P(CLIENT_AXIS), P()),
        out_specs=(P(), P(), P(CLIENT_AXIS)),
        check_vma=False)

    @partial(jax.jit, donate_argnums=(0, 1))
    def round_fn(global_state, server_state, cohort_data, rng):
        C = cohort_data["mask"].shape[0]
        rngs = jax.random.split(jax.random.fold_in(rng, 1), C)
        data = dict(cohort_data)
        data["rngs"] = rngs
        return sharded(global_state, server_state, data,
                       jax.random.fold_in(rng, 2))

    return round_fn


def make_eval_fn(spec: TrainSpec):
    """Jitted evaluation over packed masked batches (``pack_eval`` output).
    Returns summed metric dict; divide by counts on host. Mirrors the
    reference eval protocol (``FedAVGAggregator.py:99-163``) with the model
    kept on device."""

    @jax.jit
    def eval_fn(state, data):
        def step(carry, batch):
            m = spec.metrics_fn(state, batch)
            return carry, m

        _, ms = jax.lax.scan(step, 0, {k: data[k] for k in ("x", "y", "mask")})
        return jax.tree.map(lambda x: jnp.sum(x, axis=0), ms)

    return eval_fn
