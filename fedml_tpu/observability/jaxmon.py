"""Compile-event watcher: the first honest measurement of compile latency.

ROADMAP names 155-193 s per-config compiles as a cost center, but until
now nothing *measured* them per round -- the runtime auditor counts trace
events for its retrace gate, while durations were eyeballed from logs.
This listener subscribes to ``jax.monitoring``'s duration events
(jaxpr trace + backend compile) and buckets **count and wall seconds per
federated round** at the same ``end_of_round_sync`` interception point
the auditor uses, feeding:

- the metrics registry (``jax_compiles_total``, ``jax_traces_total``
  counters; ``jax_compile_seconds`` histogram) when one is enabled;
- per-round lists in :meth:`CompileWatcher.report` (mirrored into the
  final metrics record by the ``enable()`` scope).

Unlike the auditor this is pure measurement -- no transfer guard, no
gates -- so it can stay on for every traced run.
"""

from __future__ import annotations

import contextlib
import threading
import time

#: jax.monitoring event names (same stable strings the runtime auditor
#: pins; see fedml_tpu.analysis.runtime).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the other seconds a first call pays before its program runs (names as
#: jax 0.9.0's dispatch.py and compiler.py record them): lowering the
#: jaxpr to an MLIR module, and reading an executable back from the
#: persistent cache (that read is also inside COMPILE_EVENT's duration)
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: persistent-compilation-cache outcomes. A cache HIT still fires
#: COMPILE_EVENT -- its duration is the cache-load time, not an XLA
#: compile -- so the warm-restart gate is "zero cache MISSES" (every
#: compile served from the warmed cache), not "zero compile events"
#: (docs/OBSERVABILITY.md, fedwarm). A MISS is recorded when an entry is
#: WRITTEN: a compile under the persistence thresholds is neither.
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_current = None


def _union_seconds(intervals):
    """Length of the union of ``[(start, end)]``."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def current_watcher():
    return _current


class CompileWatcher:
    """Counts jax trace/compile events and their durations, bucketed per
    round by :meth:`mark_round` (wired through ``end_of_round_sync``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active = False
        self._compiles = 0
        self._compile_s = 0.0
        self._traces = 0
        self.rounds = 0
        self.compiles_per_round = []
        self.compile_seconds_per_round = []
        self.traces_per_round = []
        self.total_compiles = 0
        self.total_compile_seconds = 0.0
        self.total_traces = 0
        # (start, end) of every tracing, lowering and cache-read event.
        # An event arrives as its block ends, so its interval is known; a
        # jit traced inside another's trace fires inside its parent's
        # interval, and the union counts those seconds once
        self._phases = {TRACE_EVENT: [], LOWER_EVENT: [],
                        CACHE_LOAD_EVENT: []}
        # persistent-compilation-cache outcomes (plain jax.monitoring
        # events): a warmed cache turns every compile into a HIT whose
        # COMPILE_EVENT duration is the deserialization time -- the
        # warm-restart gate asserts cache_misses == 0, since compile
        # COUNT stays nonzero even when nothing XLA-compiles
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_event(self, event, duration_secs, **kwargs):
        if not self._active:
            return
        from fedml_tpu.observability.registry import get_registry
        reg = get_registry()
        with self._lock:
            if event in self._phases:
                now = time.perf_counter()
                self._phases[event].append((now - float(duration_secs), now))
            if event == COMPILE_EVENT:
                self._compiles += 1
                self._compile_s += float(duration_secs)
                self.total_compiles += 1
                self.total_compile_seconds += float(duration_secs)
            elif event == TRACE_EVENT:
                self._traces += 1
                self.total_traces += 1
            else:
                return
        if reg is not None:
            if event == COMPILE_EVENT:
                reg.inc("jax_compiles_total",
                        help="XLA backend compiles observed")
                reg.observe("jax_compile_seconds", float(duration_secs),
                            help="XLA backend compile wall seconds")
            else:
                reg.inc("jax_traces_total",
                        help="jaxpr traces observed")

    def _on_plain_event(self, event, **kwargs):
        if not self._active:
            return
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1

    def mark_round(self):
        """Close the current round's bucket (round 0 holds warm-up)."""
        with self._lock:
            self.compiles_per_round.append(self._compiles)
            self.compile_seconds_per_round.append(round(self._compile_s, 4))
            self.traces_per_round.append(self._traces)
            self._compiles = 0
            self._compile_s = 0.0
            self._traces = 0
            self.rounds += 1

    def report(self):
        with self._lock:
            return {
                "compile/rounds": self.rounds,
                "compile/compiles_per_round": list(self.compiles_per_round),
                "compile/seconds_per_round":
                    list(self.compile_seconds_per_round),
                "compile/traces_per_round": list(self.traces_per_round),
                "compile/total_compiles": self.total_compiles,
                "compile/total_seconds":
                    round(self.total_compile_seconds, 4),
                "compile/total_traces": self.total_traces,
                "compile/trace_seconds": round(
                    _union_seconds(self._phases[TRACE_EVENT]), 4),
                "compile/lower_seconds": round(
                    _union_seconds(self._phases[LOWER_EVENT]), 4),
                "compile/cache_load_seconds": round(
                    _union_seconds(self._phases[CACHE_LOAD_EVENT]), 4),
                "compile/cache_hits": self.cache_hits,
                "compile/cache_misses": self.cache_misses,
            }

    def record_fields(self) -> dict:
        """Flat compile-cost fields for a bench record / ledger entry
        (count + wall seconds + persistent-cache outcomes; the per-round
        lists stay in :meth:`report`)."""
        with self._lock:
            return {"compile_count": self.total_compiles,
                    "compile_seconds":
                        round(self.total_compile_seconds, 4),
                    "compile_cache_hits": self.cache_hits,
                    "compile_cache_misses": self.cache_misses}

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        from jax import monitoring
        self._active = True
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_event_listener(self._on_plain_event)
        return self

    def stop(self):
        from jax import monitoring
        self._active = False
        monitoring.unregister_event_duration_listener(self._on_event)
        monitoring.unregister_event_listener(self._on_plain_event)


@contextlib.contextmanager
def watch_compiles():
    """Arm a :class:`CompileWatcher` for the block; yields it. The round
    loops' ``end_of_round_sync`` calls :meth:`CompileWatcher.mark_round`
    on the current watcher, so per-round buckets need no extra wiring."""
    global _current
    watcher = CompileWatcher().start()
    prev, _current = _current, watcher
    try:
        yield watcher
    finally:
        _current = prev
        watcher.stop()


__all__ = ["CompileWatcher", "watch_compiles", "current_watcher",
           "TRACE_EVENT", "COMPILE_EVENT", "LOWER_EVENT",
           "CACHE_LOAD_EVENT", "CACHE_HIT_EVENT", "CACHE_MISS_EVENT"]
