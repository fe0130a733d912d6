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

The same events, as they end, become finished spans of the current
tracer under the span that paid them (``jax.trace``, ``jax.lower``,
``jax.compile`` with ``cache`` hit / miss / none, ``jax.cache_load``;
``fun`` where jax names the function): ONE listener a process
(:func:`feed_tracer`, which the round loop calls when it is built) feeds
the tracer and whichever watchers are armed.
"""

from __future__ import annotations

import contextlib
import threading
import time

from fedml_tpu.observability.tracing import get_tracer, union_length

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

#: the tracer's span for each duration event
_SPAN_OF = {TRACE_EVENT: "jax.trace", LOWER_EVENT: "jax.lower",
            COMPILE_EVENT: "jax.compile", CACHE_LOAD_EVENT: "jax.cache_load"}
_CACHE_OUTCOME = {CACHE_HIT_EVENT: "hit", CACHE_MISS_EVENT: "miss"}

#: the process's one pair of jax.monitoring listeners: registered while a
#: watcher is armed or once the round loop has asked for the tracer's feed
_armed = []
_state = {"pinned": False, "registered": False}
_lock = threading.Lock()
#: a compile's cache outcome arrives as a plain event inside its
#: duration event, on the compiling thread
_outcome = threading.local()


def _on_duration(event, duration_secs, **kwargs):
    name = _SPAN_OF.get(event)
    if name is not None:
        attrs = {}
        if kwargs.get("fun_name"):
            attrs["fun"] = str(kwargs["fun_name"])
        if event == COMPILE_EVENT:
            attrs["cache"] = getattr(_outcome, "last", "none")
            _outcome.last = "none"
        # an event of the same kind, or a trace, that ended inside this
        # one was nested in it (a jit traced while its caller is traced
        # or lowered): the outer one stands for both, so a start-up's
        # thousands of nested traces are tens of spans and no second is
        # in two sums (a cache load stays beside its compile)
        get_tracer().record(name, duration_secs,
                            absorb=(name, "jax.trace"), **attrs)
    for watcher in tuple(_armed):
        watcher._on_event(event, duration_secs)


def _on_plain(event, **kwargs):
    if event in _CACHE_OUTCOME:
        _outcome.last = _CACHE_OUTCOME[event]
    for watcher in tuple(_armed):
        watcher._on_plain_event(event)


def _sync_listeners():
    """Register the pair when someone listens, take it out when no one
    does (jax.monitoring's listener lists are process-global)."""
    from jax import monitoring
    wanted = bool(_state["pinned"] or _armed)
    if wanted and not _state["registered"]:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_plain)
    elif not wanted and _state["registered"]:
        monitoring.unregister_event_duration_listener(_on_duration)
        monitoring.unregister_event_listener(_on_plain)
    _state["registered"] = wanted


def feed_tracer():
    """Keep the compile events flowing to the current tracer for the rest
    of the process, watcher or none (an operator's run without
    ``--compile_events`` has a start-up too). Idempotent."""
    with _lock:
        _state["pinned"] = True
        _sync_listeners()


def current_watcher():
    return _current


class CompileWatcher:
    """Counts jax trace/compile events and their durations, bucketed per
    round by :meth:`mark_round` (wired through ``end_of_round_sync``)."""

    def __init__(self):
        self._lock = threading.Lock()
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

    def _on_event(self, event, duration_secs):
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

    def _on_plain_event(self, event):
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
                    union_length(self._phases[TRACE_EVENT]), 4),
                "compile/lower_seconds": round(
                    union_length(self._phases[LOWER_EVENT]), 4),
                "compile/cache_load_seconds": round(
                    union_length(self._phases[CACHE_LOAD_EVENT]), 4),
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
        with _lock:
            _armed.append(self)
            _sync_listeners()
        return self

    def stop(self):
        with _lock:
            _armed.remove(self)
            _sync_listeners()


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
           "feed_tracer",
           "TRACE_EVENT", "COMPILE_EVENT", "LOWER_EVENT",
           "CACHE_LOAD_EVENT", "CACHE_HIT_EVENT", "CACHE_MISS_EVENT"]
