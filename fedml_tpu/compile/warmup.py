"""AOT round-program enumeration + warmup (see package docstring).

Design constraints that shaped this module:

- **AOT never touches the jit dispatch cache** (pinned by PR 10's cost
  model tests), so warming cannot perturb ``compiled_shapes()`` or the
  zero-steady-state-retrace gates -- the dispatch path's own compile
  becomes a persistent-cache HIT whose ``backend_compile`` event carries
  the cache-load time, not an XLA compile (see
  ``jaxmon.CACHE_HIT_EVENT``).
- **Shapes come from the same host code the round uses.** Where the
  round path builds host-side inputs (``pack_schedule``, ``pack_lanes``),
  the enumerator calls the same functions on the NEXT round's cohort
  (``api.round_idx`` -- round 0 fresh, round R on a resumed server) and
  abstracts the results -- shape rules are never re-derived by hand,
  so they cannot drift. Where the round path would materialize data
  (``pack_cohort``: the whole cohort's batches), shapes are computed
  from the documented padding rule instead.
- **Enumeration is conservative.** Paths whose shapes depend on runtime
  state this module cannot see (mesh-sharded lanes, the compressed round
  with EF residuals) are skipped with a log line, never guessed: a wrong
  warmup shape would silently waste a compile and then eat the real one
  anyway.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class RoundProgram:
    """One jitted callable + the abstract args a run will dispatch it
    with. ``fn.lower(*args).compile()`` is the warmup unit."""

    name: str
    fn: Any
    args: tuple


def _abs(tree):
    """Pytree of arrays / ShapeDtypeStructs -> all-ShapeDtypeStructs."""
    import jax

    return jax.tree.map(
        lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                  if not hasattr(a, "dtype") else a.dtype),
        tree)


def _key_abs():
    import jax

    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _next_cohort(api):
    """The NEXT round's nominal cohort ids -- plain seeded sampling at
    the configured target size, at ``api.round_idx`` (a resumed server
    warms the cohort it is about to dispatch, not round 0's: under
    partial participation over a ragged population the per-client
    sample counts -- and therefore the wave/lane schedule shapes --
    differ per cohort). With resilience enabled the live path may trim
    to a smaller reporting subset (those shapes compile on first use);
    warmup covers the full-reporting shape, which is also the steady
    state the zero-retrace gates pin."""
    from fedml_tpu.algorithms.fedavg import client_sampling

    if api.resilience is not None:
        logging.info("fedwarm: resilience active -- warming the nominal "
                     "full-reporting cohort shape; trimmed partial-round "
                     "shapes compile on first use")
    return client_sampling(int(getattr(api, "round_idx", 0)),
                           len(api.train_data_local_dict),
                           api.args.client_num_per_round)


def _nonempty_shard(api):
    return next(d for d in api.train_data_local_dict.values()
                if d is not None and len(d["y"]))


def _bucket_programs(api):
    """One chunk program per bucket edge + the donated server advance --
    the whole compiled surface of the bucketed streaming path."""
    import jax
    import jax.numpy as jnp

    r = api.bucket_runner
    shard = _nonempty_shard(api)
    x0, y0 = np.asarray(shard["x"]), np.asarray(shard["y"])
    chunk, bs = r.client_chunk, r.batch_size
    gs = _abs(api.global_state)
    key = _key_abs()
    out = []
    for edge in r.edges:
        batches = {
            "x": _sds((chunk, edge, bs) + x0.shape[1:], x0.dtype),
            "y": _sds((chunk, edge, bs) + y0.shape[1:], y0.dtype),
            "mask": _sds((chunk, edge, bs), jnp.float32),
        }
        out.append(RoundProgram(
            f"bucket_chunk_s{edge}", r._chunk_fn,
            (gs, batches, _sds((chunk,), jnp.float32),
             _sds((), jnp.int32),
             _sds((chunk,) + tuple(key.shape), key.dtype))))
    aux = {"n": _sds((), jnp.float32), "steps": _sds((), jnp.int32)}
    avg = jax.eval_shape(r.payload_fn, gs, gs, aux)
    out.append(RoundProgram(
        "advance", r._advance_fn,
        (gs, _abs(api.server_state), _abs(avg), key)))
    return out


def _wave_programs(api, cohort, sched):
    """The wave path: the per-wave program (+ its cross-wave add and the
    finish step, whose operand shapes come from the wave outputs)."""
    import jax
    import jax.numpy as jnp

    runner = api.wave_runner
    C = len(cohort)
    chunk = min(runner.client_chunk, C)
    gs = _abs(api.global_state)
    key = _key_abs()
    dx, dy = _abs(api.device_data["x"]), _abs(api.device_data["y"])
    ws = {"idx": _sds((chunk,) + sched["idx"].shape[1:], jnp.int32),
          "mask": _sds((chunk,) + sched["mask"].shape[1:], jnp.float32),
          "n": _sds((chunk,), jnp.float32)}
    wave_args = (gs, dx, dy, _sds((chunk,), jnp.int32), ws,
                 _sds((), jnp.int32),
                 _sds((chunk,) + tuple(key.shape), key.dtype))
    pay, w, msum, _ = jax.eval_shape(runner._wave_fn, *wave_args)
    part = (_abs(pay), _abs(w), _abs(msum))
    return [
        RoundProgram("wave", runner._wave_fn, wave_args),
        RoundProgram("wave_add", runner._add_fn, (part, part)),
        RoundProgram("wave_finish", runner._finish_fn,
                     (gs, _abs(api.server_state), _abs(pay), _abs(w),
                      _abs(runner._payload_dtypes(api.global_state)), key)),
    ]


def _lane_programs(api, runner, name, cohort, sched):
    """A (packed-)lane round: ONE donated program per round; lane-array
    shapes come from the same ``pack_lanes`` call ``run_round`` makes."""
    import jax.numpy as jnp

    from fedml_tpu.parallel.engine import fold_step_keys
    from fedml_tpu.parallel.packing import pack_lanes

    lanes = pack_lanes(sched, runner.n_lanes)
    lanes.pop("trip")
    local_step = lanes.pop("local_step")
    gs = _abs(api.global_state)
    key = _key_abs()
    K, L = local_step.shape
    lane_abs = {k: _abs(jnp.asarray(v)) for k, v in lanes.items()}
    step_keys = _sds((K, L) + tuple(key.shape), key.dtype)
    return [
        RoundProgram(
            name, runner._round_fn,
            (gs, _abs(api.server_state), _abs(api.device_data["x"]),
             _abs(api.device_data["y"]),
             _sds((len(cohort),), jnp.int32), lane_abs, step_keys,
             _sds((), jnp.int32),
             _abs(runner._payload_dtypes(api.global_state)), key)),
        # the per-step PRNG derivation is its own jitted dispatch
        RoundProgram(
            "fold_step_keys", fold_step_keys,
            (_sds((len(cohort),) + tuple(key.shape), key.dtype),
             _sds((K, L), jnp.int32), _sds((K, L), jnp.int32))),
    ]


def _flat_indexed_program(api, cohort, sched):
    import jax.numpy as jnp

    gs = _abs(api.global_state)
    C = len(cohort)
    dd = {"x": _sds((C,) + api.device_data["x"].shape[1:],
                    api.device_data["x"].dtype),
          "y": _sds((C,) + api.device_data["y"].shape[1:],
                    api.device_data["y"].dtype)}
    sched_abs = {"idx": _sds(sched["idx"].shape, jnp.int32),
                 "mask": _sds(sched["mask"].shape, jnp.float32),
                 "n": _sds(sched["n"].shape, jnp.float32)}
    return [RoundProgram("indexed_round", api.indexed_round_fn,
                         (gs, _abs(api.server_state), dd, sched_abs,
                          _key_abs()))]


def _packed_sim_program(api, cohort):
    """The packed sim round at pack_cohort's documented padding rule --
    computed analytically (materializing the cohort's batches just for
    shapes would copy the whole round's data)."""
    import math

    import jax.numpy as jnp

    from fedml_tpu.parallel.packing import _steps_for

    shard = _nonempty_shard(api)
    x0, y0 = np.asarray(shard["x"]), np.asarray(shard["y"])
    ns = [len(api.train_data_local_dict[i]["y"]) for i in cohort]
    bs = api.args.batch_size
    if bs in (-1, 0):
        bs = max(1, max(ns))
    S = max(_steps_for(n, bs, api.args.epochs) for n in ns)
    S = int(math.ceil(S / 8) * 8)  # pack_cohort step_bucket default
    C = len(cohort)
    packed = {"x": _sds((C, S, bs) + x0.shape[1:], x0.dtype),
              "y": _sds((C, S, bs) + y0.shape[1:], y0.dtype),
              "mask": _sds((C, S, bs), jnp.float32),
              "n": _sds((C,), jnp.float32)}
    return [RoundProgram("sim_round", api.round_fn,
                         (_abs(api.global_state), _abs(api.server_state),
                          packed, _key_abs()))]


def _eval_program(api):
    import math

    import jax.numpy as jnp

    data = api.test_data_global
    if data is None or "y" not in data or len(data["y"]) == 0:
        return []
    x0, y0 = np.asarray(data["x"]), np.asarray(data["y"])
    n = len(y0)
    bs = api.args.batch_size
    if bs in (-1, 0):
        bs = max(1, n)
    S = max(1, math.ceil(n / bs))
    packed = {"x": _sds((S, bs) + x0.shape[1:], x0.dtype),
              "y": _sds((S, bs) + y0.shape[1:], y0.dtype),
              "mask": _sds((S, bs), jnp.float32)}
    return [RoundProgram("eval", api.eval_fn,
                         (_abs(api.global_state), packed))]


def enumerate_round_programs(api) -> list[RoundProgram]:
    """Every jitted round function a ``FedAvgAPI`` run will dispatch, at
    the next round's arg shapes. See the module docstring for what is skipped
    (mesh lanes, compressed rounds) and why."""
    programs = []
    if api.bucket_runner is not None:
        programs += _bucket_programs(api)
    elif api.sharded_lane_runner is not None:
        logging.info("fedwarm: mesh-sharded lane rounds are not warmed "
                     "yet (SPMD shard shapes; follow-up)")
    elif api.device_data is not None:
        from fedml_tpu.parallel.packing import pack_schedule

        cohort = _next_cohort(api)
        ns = [api._client_ns[i] for i in cohort]
        # shapes depend only on ns/bs/epochs -- a throwaway rng keeps
        # the API's checkpointable host stream untouched
        sched = pack_schedule(ns, api.args.batch_size, api.args.epochs,
                              rng=np.random.default_rng(0))
        mode = int(getattr(api.args, "wave_mode", 1))
        if mode in (2, 3):
            runner = (api.packed_lane_runner
                      if mode == 3 and api.packed_lane_runner is not None
                      else api.lane_runner)
            name = ("mxu_lane_round"
                    if runner is api.packed_lane_runner else "lane_round")
            programs += _lane_programs(api, runner, name, cohort, sched)
        elif mode == 1:
            programs += _wave_programs(api, cohort, sched)
        else:
            programs += _flat_indexed_program(api, cohort, sched)
    elif api.compressed_round_fn is not None:
        logging.info("fedwarm: compressed rounds are not warmed yet "
                     "(EF residual shapes; compression follow-up)")
    else:
        programs += _packed_sim_program(api, _next_cohort(api))
    programs += _eval_program(api)
    return programs


def warmup_programs(programs) -> dict:
    """AOT-compile every program (through the persistent cache when one
    is enabled). Returns the warmup report: per-program seconds plus the
    CompileWatcher's compile/cache tallies for exactly this warmup."""
    from fedml_tpu.observability.jaxmon import watch_compiles

    per_program = {}
    t0 = time.time()
    with watch_compiles() as watcher:
        for p in programs:
            t1 = time.time()
            p.fn.lower(*p.args).compile()
            per_program[p.name] = round(time.time() - t1, 4)
    report = {
        "warmup/programs": len(programs),
        "warmup/seconds": round(time.time() - t0, 4),
        "warmup/per_program_s": per_program,
        "warmup/compile_count": watcher.total_compiles,
        "warmup/compile_seconds": round(watcher.total_compile_seconds, 4),
        "warmup/cache_hits": watcher.cache_hits,
        "warmup/cache_misses": watcher.cache_misses,
    }
    logging.info("fedwarm: %d programs in %.2fs (%d compiles %.2fs, "
                 "%d cache hits / %d misses)", len(programs),
                 report["warmup/seconds"], watcher.total_compiles,
                 watcher.total_compile_seconds, watcher.cache_hits,
                 watcher.cache_misses)
    return report


def warmup_api(api) -> dict:
    """Enumerate + warm every round program of a constructed API."""
    return warmup_programs(enumerate_round_programs(api))


def warm_restart(api, cache_dir: Optional[str] = None,
                 min_compile_time_secs: Optional[float] = None) -> dict:
    """The recovery-path hook: (re)enable the persistent cache over the
    run's ``--compile_cache_dir`` and warm every round program BEFORE the
    server re-enters the round loop. Over a warmed directory every AOT
    compile is a cache hit (deserialization), so a restarted server
    rejoins in cache-load time instead of the 155-193 s recompile the
    CompileWatcher measured -- the Bonawitz-style requirement that a
    recovered server must not stall the fleet (docs/RESILIENCE.md)."""
    from fedml_tpu.utils.compile_cache import enable_compilation_cache

    used = enable_compilation_cache(cache_dir, min_compile_time_secs)
    report = warmup_api(api)
    report["warmup/cache_dir"] = used
    return report


__all__ = ["RoundProgram", "enumerate_round_programs", "warmup_programs",
           "warmup_api", "warm_restart"]
