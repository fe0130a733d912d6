"""AOT round-program enumeration + warmup (see package docstring).

Design constraints that shaped this module:

- **AOT never touches the jit dispatch cache** (pinned by PR 10's cost
  model tests), so warming cannot perturb ``compiled_shapes()`` or the
  zero-steady-state-retrace gates -- the dispatch path's own compile
  becomes a persistent-cache HIT whose ``backend_compile`` event carries
  the cache-load time, not an XLA compile (see
  ``jaxmon.CACHE_HIT_EVENT``).
- **The runner lists its own programs.** ``api.runner.programs(...)``
  (``parallel/runners.py``: the contract every execution path keeps)
  names each jitted function a round dispatches with its abstract
  arguments; this module asks for the NEXT round's cohort
  (``api.round_idx`` -- round 0 fresh, round R on a resumed server) and
  adds eval. Where the round path builds host-side inputs
  (``pack_schedule``, ``pack_lanes``) a runner calls the same functions
  and abstracts the results -- shape rules are never re-derived by
  hand, so they cannot drift. Where the round path would materialize
  data (``pack_cohort``: the whole cohort's batches), shapes are
  computed from the documented padding rule instead.
- **Enumeration is conservative.** A path whose shapes its runner does
  not enumerate yet (mesh-sharded lanes) lists nothing and says so in a
  log line, never a guess: a wrong warmup shape would silently waste a
  compile and then eat the real one anyway.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Optional

import numpy as np

from fedml_tpu.parallel.engine import abstract


@dataclasses.dataclass(frozen=True)
class RoundProgram:
    """One jitted callable + the abstract args a run will dispatch it
    with. ``fn.lower(*args).compile()`` is the warmup unit."""

    name: str
    fn: Any
    args: tuple


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


def _eval_program(api):
    import math

    import jax

    data = api.test_data_global
    if data is None or "y" not in data or len(data["y"]) == 0:
        return []
    n = len(data["y"])
    bs = api.args.batch_size
    if bs in (-1, 0):
        bs = max(1, n)
    S = max(1, math.ceil(n / bs))
    packed = {k: jax.ShapeDtypeStruct((S, bs) + np.shape(data[k])[1:],
                                      np.asarray(data[k]).dtype)
              for k in ("x", "y")}
    packed["mask"] = jax.ShapeDtypeStruct((S, bs), np.float32)
    return [RoundProgram("eval", api.eval_fn,
                         (abstract(api.global_state), packed))]


def enumerate_round_programs(api) -> list[RoundProgram]:
    """Every jitted function a ``FedAvgAPI`` run will dispatch, at the
    next round's arg shapes: what the API's runner lists for that cohort
    (each runner knows its own programs and their shapes; see the module
    docstring for what a runner leaves out), plus eval."""
    return [RoundProgram(*p) for p in api.runner.programs(
        api.global_state, api.server_state, _next_cohort(api))
    ] + _eval_program(api)


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
