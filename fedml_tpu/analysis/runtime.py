"""Runtime retrace/transfer auditor + the concurrency race sanitizer.

What the linter cannot see statically -- an argument whose shape changes
every round, a cache key that silently includes a Python scalar -- shows up
at runtime as recompilation. JAX announces every trace/compile through
``jax.monitoring`` duration events; :func:`audit` counts them and buckets
the counts per federated round at the round loops' single end-of-round
sync point (``fedml_tpu.utils.profiling.end_of_round_sync``). A healthy
run compiles in round 0 and never again: ``retraces_per_round`` is
``[big, 0, 0, ...]``. Anything non-zero after round 0 is TPU time burned
re-lowering the same program.

The same sync point is armed with ``jax.transfer_guard``: the end-of-round
``block_until_ready`` must not require *any* host<->device transfer, so a
violation there means the aggregated state contains host-resident leaves
(an accidental ``np.*`` in the aggregation path). Violations are counted,
not raised -- the audit reports, the run continues. (On the CPU backend
device buffers are host-visible, so device->host violations largely cannot
trip there; the counter is exercised for real on TPU.)

The second half is the **race sanitizer** (:func:`race_audit`,
``--race_audit`` on the resilience-wired mains): the runtime analog of the
static concurrency rules FL124/FL125. Inside the context, the control
plane's cooperative lock factories (``fedml_tpu.analysis.locks``) return
*instrumented* locks that record, per thread, the order in which lock
creation sites are nested (lock-order cycles == FL124's runtime shape) and
whether any *state* lock is held when execution reaches a blocking
chokepoint (the TCP frame send/recv helpers are patched for the audit's
lifetime; ``io_lock`` families are exempt by declared purpose -- FL125's
runtime shape). The chaos smoke in ``scripts/ci.sh`` runs the TCP
fault-injection scenario under this audit and asserts both violation
lists stay empty.
"""

from __future__ import annotations

import contextlib
import logging
import threading

from fedml_tpu.core.locks import creation_site as _creation_site

#: jax.monitoring event names (the strings jax's dispatch path records;
#: hardcoded so the auditor never imports private modules).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_current = None


def current_auditor():
    """The auditor armed by the innermost active :func:`audit`, or None."""
    return _current


class RuntimeAuditor:
    """Counts jaxpr traces / backend compiles and transfer-guard
    violations, bucketed per round by :meth:`mark_round`."""

    def __init__(self, transfer_guard="device_to_host"):
        #: "device_to_host" (default: end-of-round sync must not pull
        #: state to host), "all" (also flags implicit host->device uploads
        #: -- noisy when rounds legitimately upload packed cohorts), or
        #: None to disable guarding.
        self.transfer_guard = transfer_guard
        self.retraces_per_round = []
        self.compiles_per_round = []
        self.transfer_guard_violations = 0
        self.rounds = 0
        self._traces = 0
        self._compiles = 0
        self._off_traces = 0
        self._off_compiles = 0
        self._off_depth = 0
        self._active = False

    # registered with jax.monitoring for the audit's lifetime; stays cheap
    # and inert once _active drops (listener dereg is best-effort)
    def _on_event(self, event, duration_secs, **kwargs):
        if not self._active:
            return
        if event == TRACE_EVENT:
            if self._off_depth:
                self._off_traces += 1
            else:
                self._traces += 1
        elif event == COMPILE_EVENT:
            if self._off_depth:
                self._off_compiles += 1
            else:
                self._compiles += 1

    @contextlib.contextmanager
    def off_round(self):
        """Book the enclosed work as off-round (trailing) instead of
        charging the *next* round's bucket. The round loops wrap their
        periodic eval in this: eval runs after the round's sync, so its
        first-time compile would otherwise surface as a phantom retrace
        in the following round -- the exact false positive the
        steady-state gate must not have."""
        self._off_depth += 1
        try:
            yield
        finally:
            self._off_depth -= 1

    def mark_round(self):
        """Close the current round's bucket. Round 0's bucket holds the
        initial compilation; later buckets should be zero."""
        self.retraces_per_round.append(self._traces)
        self.compiles_per_round.append(self._compiles)
        self._traces = 0
        self._compiles = 0
        self.rounds += 1

    @contextlib.contextmanager
    def guard(self, mode="disallow"):
        """Arm the configured transfer guard around a block; a guard trip
        is counted as a violation and logged, not propagated."""
        if self.transfer_guard is None:
            yield
            return
        import jax
        arm = (jax.transfer_guard if self.transfer_guard == "all"
               else jax.transfer_guard_device_to_host)
        try:
            with arm(mode):
                yield
        # guard trips surface as jaxlib.XlaRuntimeError, a RuntimeError
        # subclass ("Disallowed host-to-device transfer: ..."); catching
        # the concrete type keeps real failures propagating -- the same
        # FL107 standard the linter holds transport code to
        except RuntimeError as e:
            if "transfer" not in str(e).lower():
                raise
            self.transfer_guard_violations += 1
            logging.warning("audit: guarded transfer violation: %s", e)

    def sync_and_mark_round(self, state):
        """End-of-round hook: block on the round's outputs under the
        transfer guard, then close the round's trace bucket."""
        import jax
        try:
            with self.guard():
                jax.block_until_ready(state)
        finally:
            # a violation aborts block_until_ready mid-tree: redo the sync
            # unguarded so callers still get the barrier they asked for
            jax.block_until_ready(state)
        self.mark_round()
        return state

    def report(self):
        steady = sum(self.retraces_per_round[1:])
        return {
            "audit/rounds": self.rounds,
            "audit/retraces_per_round": list(self.retraces_per_round),
            "audit/compiles_per_round": list(self.compiles_per_round),
            # the headline number: traces after round 0 == recompilation
            # of programs that should have been cache-hits
            "audit/steady_state_retraces": steady,
            # activity outside any round bucket (periodic/final eval,
            # teardown): kept separate so it never masquerades as a round
            # retrace
            "audit/trailing_traces": self._off_traces + self._traces,
            "audit/trailing_compiles": self._off_compiles + self._compiles,
            "audit/transfer_guard_violations":
                self.transfer_guard_violations,
        }


@contextlib.contextmanager
def audit(metrics_logger=None, enabled=True, transfer_guard="device_to_host"):
    """Audit the enclosed run; yields the :class:`RuntimeAuditor` (or None
    when ``enabled`` is falsy, so ``--audit`` wires straight through).

    On exit the report is pushed to ``metrics_logger`` (any callable taking
    a dict -- a :class:`~fedml_tpu.utils.metrics.MetricsLogger` fits) and
    logged. Round bucketing needs the round loop to pass through
    ``end_of_round_sync``; activity that lands outside any round (the
    final eval, code that never syncs) is reported as trailing counts."""
    global _current
    if not enabled:
        yield None
        return
    from jax import monitoring
    auditor = RuntimeAuditor(transfer_guard=transfer_guard)
    auditor._active = True
    monitoring.register_event_duration_secs_listener(auditor._on_event)
    prev, _current = _current, auditor
    try:
        yield auditor
    finally:
        _current = prev
        auditor._active = False
        monitoring.unregister_event_duration_listener(auditor._on_event)
        report = auditor.report()
        logging.info("runtime audit: %s", report)
        if metrics_logger is not None:
            metrics_logger(report)
        _report_to_registry(report)


# -- race sanitizer -------------------------------------------------------

class _AuditedLock:
    """Instrumented lock handed out by the ``analysis.locks`` factories
    while a :func:`race_audit` is active. Semantics are exactly the
    wrapped ``threading`` primitive's; acquisition/release additionally
    maintain the auditor's per-thread held stack."""

    __slots__ = ("_inner", "_auditor", "kind", "site")

    def __init__(self, auditor, kind, reentrant, site):
        self._inner = threading.RLock() if reentrant else threading.Lock()
        self._auditor = auditor
        self.kind = kind
        self.site = site

    def acquire(self, blocking=True, timeout=-1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._auditor._acquired(self)
        return ok

    def release(self):
        self._auditor._released(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __getattr__(self, name):
        # exact surface parity with the wrapped primitive: e.g.
        # ``.locked()`` exists on Lock always, on RLock only from 3.12 --
        # delegating (instead of defining it here) keeps hasattr() and
        # AttributeError behavior identical inside and outside an audit
        if name == "_inner":  # not yet bound (unpickling-style paths)
            raise AttributeError(name)
        return getattr(self._inner, name)


class RaceAuditor:
    """Records lock-acquisition order and held-while-blocking events for
    every lock created through the cooperative factories while active."""

    def __init__(self):
        self._tls = threading.local()
        self._mu = threading.Lock()  # deliberately uninstrumented
        self._active = True
        self.locks_created = 0
        self.acquisitions = 0
        self.order_edges = {}         # (site_a, site_b) -> count
        self.held_while_blocking = []  # (label, (lock sites...), thread)

    # -- factory hook (fedml_tpu.analysis.locks) --------------------------
    def make_lock(self, kind, reentrant):
        with self._mu:
            self.locks_created += 1
        return _AuditedLock(self, kind, reentrant, _creation_site())

    def _held(self):
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def _acquired(self, lock):
        held = self._held()
        if self._active:
            with self._mu:
                self.acquisitions += 1
                for h in held:
                    if h.site != lock.site:
                        key = (h.site, lock.site)
                        self.order_edges[key] = \
                            self.order_edges.get(key, 0) + 1
        held.append(lock)

    def _released(self, lock):
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is lock:
                del held[i]
                return

    # -- chokepoints -------------------------------------------------------
    def blocking(self, label):
        """Called by the patched blocking chokepoints: any *state* lock
        held here is a held-while-blocking violation (io locks exist to
        be held across exactly this)."""
        if not self._active:
            return
        held = [l for l in self._held() if l.kind == "state"]
        if held:
            event = (label, tuple(sorted({l.site for l in held})),
                     threading.current_thread().name)
            with self._mu:
                self.held_while_blocking.append(event)
            from fedml_tpu.observability.flightrec import get_flight_recorder
            fr = get_flight_recorder()
            if fr is not None:  # lock-audit events belong in the black box
                fr.record("held_while_blocking", label=event[0],
                          locks=list(event[1]), thread_name=event[2])
            logging.warning("race audit: %s while holding state lock(s) "
                            "%s on %s", *event)

    # -- reporting ---------------------------------------------------------
    def lock_order_cycles(self):
        """Site-level cycles in the observed acquisition-order graph
        (same detector as the static FL124 pass)."""
        from fedml_tpu.analysis.concurrency import find_lock_cycles
        return [cycle + [cycle[0]]
                for cycle in find_lock_cycles(self.order_edges)]

    def report(self):
        return {
            "race/locks_created": self.locks_created,
            "race/acquisitions": self.acquisitions,
            "race/order_edges": sorted(
                f"{a} -> {b}" for (a, b) in self.order_edges),
            "race/lock_order_cycles": self.lock_order_cycles(),
            "race/held_while_blocking": list(self.held_while_blocking),
        }


@contextlib.contextmanager
def race_audit(enabled=True, metrics_logger=None):
    """Arm the race sanitizer: locks created through
    ``fedml_tpu.analysis.locks`` inside this context are instrumented,
    and the TCP frame helpers are patched to report blocking points.
    Yields the :class:`RaceAuditor` (or None when disabled, so
    ``--race_audit`` wires straight through); pushes the report to
    ``metrics_logger`` on exit."""
    if not enabled:
        yield None
        return
    from fedml_tpu.core import locks as _locks
    from fedml_tpu.core.comm import tcp as _tcp
    auditor = RaceAuditor()
    prev = _locks._auditor
    _locks._auditor = auditor
    orig_send, orig_recv = _tcp._send_frame, _tcp._recv_frame

    def _send(sock, payload):
        auditor.blocking("tcp._send_frame")
        return orig_send(sock, payload)

    def _recv(sock):
        auditor.blocking("tcp._recv_frame")
        return orig_recv(sock)

    _tcp._send_frame, _tcp._recv_frame = _send, _recv
    try:
        yield auditor
    finally:
        _locks._auditor = prev
        _tcp._send_frame, _tcp._recv_frame = orig_send, orig_recv
        auditor._active = False  # long-lived managers stop recording
        report = auditor.report()
        logging.info("race audit: %s", report)
        if metrics_logger is not None:
            metrics_logger(report)
        _report_to_registry(report)


def _report_to_registry(report):
    """Mirror an auditor report's scalar totals into the unified metrics
    registry (fedml_tpu.observability) when one is enabled, so audit
    results land in metrics.prom next to the wire/round counters."""
    from fedml_tpu.observability.registry import get_registry
    reg = get_registry()
    if reg is None:
        return
    for key, val in report.items():
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            if isinstance(val, list):
                reg.set_gauge("audit_events",
                              len(val), help="auditor event-list lengths",
                              event=key.split("/", 1)[-1])
            continue
        name = "audit_" + key.split("/", 1)[-1]
        reg.set_gauge(name, val, help="runtime auditor total")


__all__ = ["RuntimeAuditor", "audit", "current_auditor",
           "RaceAuditor", "race_audit",
           "TRACE_EVENT", "COMPILE_EVENT"]
