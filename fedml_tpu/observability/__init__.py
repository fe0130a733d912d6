"""fedtrace: round tracing, a unified metrics registry, a flight recorder.

The observability layer the scale-out arc reports against (see
docs/OBSERVABILITY.md). Three composable pieces, one switchboard:

- :mod:`~fedml_tpu.observability.tracing`: Dapper-style spans over the
  round lifecycle, at two levels of one ``Tracer``: the recorder (the
  process's default: a bounded ring on the host clock, from which
  :func:`startup_report` and the ``round_stall`` warning are made) and
  the exporting level (``--trace``: propagated across ranks in the
  message envelope's ``__trace__`` control field; Chrome-trace + JSONL
  export).
- :mod:`~fedml_tpu.observability.registry`: counters/gauges/histograms
  with labels; per-round snapshots into ``metrics.jsonl`` records and a
  Prometheus text dump at exit.
- :mod:`~fedml_tpu.observability.flightrec`: a bounded ring of
  control-plane events dumped to ``flightrec_<reason>.jsonl`` on
  PEER_LOST, abandoned rounds, and unhandled crashes.
- :mod:`~fedml_tpu.observability.jaxmon`: per-round compile count +
  duration via ``jax.monitoring``; the same events as spans under the
  span that paid them.

Everything but the recorder defaults OFF: ``get_tracer().enabled`` is
False, the registry and flight-recorder globals are None, and every
instrumentation point in the engine/transports/FSMs guards on that -- a
run without ``--trace`` / ``--flightrec`` times its context-managed spans
(a few microseconds each) and otherwise executes no observability code
beyond one global read per event, sends bit-identical frames and
produces bit-identical results. :func:`enable` flips the switchboard for
a scope and writes the artifacts on exit.
"""

from __future__ import annotations

import contextlib
import logging
import os
import sys
import threading

from fedml_tpu.observability.costmodel import (CostModel, get_cost_model,
                                               set_cost_model)
from fedml_tpu.observability.flightrec import (FlightRecorder,
                                               get_flight_recorder,
                                               set_flight_recorder)
from fedml_tpu.observability.perfmon import (PerfMonitor, StatusWriter,
                                             get_perf_monitor,
                                             set_perf_monitor)
from fedml_tpu.observability.registry import (MetricsRegistry, get_registry,
                                              set_registry)
from fedml_tpu.observability.tracing import (NOOP_TRACER, NoopTracer,
                                             RoundLog, Span, SpanContext,
                                             TRACE_KEY, Tracer, get_tracer,
                                             set_tracer, startup_report)


def add_observability_args(parser):
    """``--trace/--trace_dir/--flightrec`` for the experiment mains
    (wired through ``experiments/common.add_base_args``)."""
    parser.add_argument(
        "--trace", type=int, default=0,
        help="structured span tracing of the round lifecycle "
             "(fedml_tpu.observability): cohort-select/broadcast/"
             "local-train/report/aggregate/eval spans, stitched across "
             "ranks via trace ids in the message envelope; exports "
             "trace.json (Perfetto/chrome://tracing) + spans.jsonl to "
             "--trace_dir and arms the per-round compile-event watcher")
    parser.add_argument(
        "--trace_dir", type=str, default=None,
        help="span export directory (default: --run_dir, else '.')")
    parser.add_argument(
        "--flightrec", type=int, default=0,
        help="control-plane flight recorder: bounded ring of "
             "send/recv/decision/retry events, dumped to "
             "flightrec_<reason>.jsonl on PEER_LOST, abandoned rounds, "
             "and unhandled crashes")
    parser.add_argument(
        "--perfmon", type=int, default=0,
        help="runtime perf/health monitor (observability/perfmon.py): "
             "round/step/staleness/buffer-depth/report-latency "
             "histograms into the metrics registry, a rolling "
             "fed_rounds_per_hour gauge, and periodic status.json "
             "health snapshots (--status_path)")
    parser.add_argument(
        "--status_path", type=str, default=None,
        help="status.json path for --perfmon health snapshots "
             "(default: <run_dir>/status.json when --run_dir is set)")
    parser.add_argument(
        "--xprof_round", type=int, default=None,
        help="with --perfmon: capture a programmatic jax.profiler trace "
             "of exactly round N into --xprof_dir (no-op when the "
             "profiler is unavailable; fires at most once)")
    parser.add_argument(
        "--xprof_dir", type=str, default=None,
        help="jax.profiler output dir for --xprof_round "
             "(default: --run_dir, else '.')")
    parser.add_argument(
        "--costmodel", type=int, default=0,
        help="XLA cost-model performance attribution "
             "(observability/costmodel.py): per-compiled-program "
             "FLOPs/bytes from cost_analysis(); the bucketed streaming "
             "rounds additionally report per-bucket-shape FLOPs and "
             "FLOP-weighted padding waste")
    return parser


@contextlib.contextmanager
def enable(trace=False, trace_dir=None, flightrec=False, flightrec_dir=None,
           registry=True, compile_events=None, metrics_logger=None,
           flight_capacity=4096, perfmon=False, status_path=None,
           xprof_dir=None, xprof_round=None, cost_model=False):
    """Arm the observability switchboard for a scope.

    Yields an object with ``tracer`` / ``registry`` / ``recorder`` /
    ``compile_watcher`` / ``monitor`` / ``cost_model`` attributes (None
    for the pieces left off). On exit: exports ``trace.json`` +
    ``spans.jsonl`` into ``trace_dir``, dumps the registry to
    ``metrics.prom`` (in ``flightrec_dir`` or ``trace_dir`` when either
    is set), pushes the compile / perf-monitor / cost-model reports to
    ``metrics_logger``, forces a final ``status.json`` write, and
    pushes the process's ``startup`` record (:func:`startup_report`, as
    far as the start-up got) and restores the previous globals (scopes
    nest).

    ``compile_events`` defaults to ``trace`` -- the watcher needs jax, so
    a flight-recorder-only scope stays jax-free. ``perfmon`` arms the
    registry too (its histograms need a sink); ``status_path`` defaults
    to ``<flightrec_dir or trace_dir>/status.json`` when perfmon is on
    and either dir is set.
    """
    state = _Scope()
    prev_tracer = prev_reg = prev_fr = prev_mon = prev_cm = None
    hooks = None
    if compile_events is None:
        compile_events = bool(trace)
    # the compile watcher is the ONLY fallible setup step (it imports
    # jax and registers a monitoring listener): arm it FIRST, before any
    # global is installed, so a setup failure cannot leak a tracer/
    # registry/recorder (or chained excepthooks) past this function --
    # everything below is plain-Python construction that cannot raise
    # (PerfMonitor/CostModel only touch jax lazily, inside a round)
    if compile_events:
        from fedml_tpu.observability.jaxmon import watch_compiles
        state._watch_cm = watch_compiles()
        state.compile_watcher = state._watch_cm.__enter__()
    if trace:
        state.tracer = Tracer()
        prev_tracer = set_tracer(state.tracer)
    if registry and (trace or flightrec or perfmon):
        state.registry = MetricsRegistry()
        prev_reg = set_registry(state.registry)
    if flightrec:
        state.recorder = FlightRecorder(
            out_dir=flightrec_dir or trace_dir or ".",
            capacity=flight_capacity)
        prev_fr = set_flight_recorder(state.recorder)
        hooks = _install_crash_hooks(state.recorder)
    if perfmon:
        out_dir = flightrec_dir or trace_dir
        if status_path is None and out_dir is not None:
            status_path = os.path.join(out_dir, "status.json")
        state.monitor = PerfMonitor(status_path=status_path,
                                    xprof_dir=xprof_dir or out_dir,
                                    xprof_round=xprof_round)
        prev_mon = set_perf_monitor(state.monitor)
    if cost_model:
        state.cost_model = CostModel()
        prev_cm = set_cost_model(state.cost_model)
    try:
        yield state
    finally:
        if metrics_logger is not None:
            report = startup_report()
            if report is not None and report["rounds"]:
                metrics_logger({"startup": report})
        if state.compile_watcher is not None:
            state._watch_cm.__exit__(None, None, None)
            report = state.compile_watcher.report()
            logging.info("compile watch: %s", report)
            if metrics_logger is not None:
                metrics_logger(report)
        if state.cost_model is not None:
            set_cost_model(prev_cm)
            if metrics_logger is not None and state.cost_model.programs:
                metrics_logger(state.cost_model.record())
        if state.monitor is not None:
            set_perf_monitor(prev_mon)
            state.monitor.status_update(force=True, final=True)
            if state.monitor.status is not None:
                state.status_path = state.monitor.status.path
            if metrics_logger is not None and state.monitor.rounds:
                metrics_logger(state.monitor.record())
        if state.recorder is not None:
            _uninstall_crash_hooks(hooks)
            set_flight_recorder(prev_fr)
        if state.registry is not None:
            set_registry(prev_reg)
            out_dir = flightrec_dir or trace_dir
            if out_dir is not None:
                os.makedirs(out_dir, exist_ok=True)
                state.prom_path = state.registry.dump_prometheus(
                    os.path.join(out_dir, "metrics.prom"))
        if state.tracer is not None:
            set_tracer(prev_tracer)
            if trace_dir is not None:
                os.makedirs(trace_dir, exist_ok=True)
                state.chrome_path = state.tracer.export_chrome(
                    os.path.join(trace_dir, "trace.json"))
                state.spans_path = state.tracer.export_jsonl(
                    os.path.join(trace_dir, "spans.jsonl"))
                logging.info(
                    "fedtrace: %d spans -> %s (open in Perfetto / "
                    "chrome://tracing)", len(state.tracer.finished_spans()),
                    state.chrome_path)


class _Scope:
    """What :func:`enable` yields; also records artifact paths on exit."""

    def __init__(self):
        self.tracer = None
        self.registry = None
        self.recorder = None
        self.compile_watcher = None
        self.monitor = None
        self.cost_model = None
        self.chrome_path = None
        self.spans_path = None
        self.prom_path = None
        self.status_path = None
        self._watch_cm = None


def _install_crash_hooks(recorder):
    """Chain sys/threading excepthooks: an unhandled crash dumps the ring
    before the interpreter's default handling runs."""
    prev_sys = sys.excepthook
    prev_thr = threading.excepthook

    def on_crash(exc_type, exc, tb):
        try:
            recorder.record("crash", error=f"{exc_type.__name__}: {exc}")
            recorder.dump("crash", extra={"error": repr(exc)})
        except OSError:  # the disk is gone too: still run default handling
            pass
        prev_sys(exc_type, exc, tb)

    def on_thread_crash(args):
        try:
            recorder.record("crash", thread_name=getattr(
                args.thread, "name", "?"),
                error=f"{args.exc_type.__name__}: {args.exc_value}")
            recorder.dump("crash", extra={"error": repr(args.exc_value)})
        except OSError:
            pass
        prev_thr(args)

    sys.excepthook = on_crash
    threading.excepthook = on_thread_crash
    return (prev_sys, prev_thr, on_crash, on_thread_crash)


def _uninstall_crash_hooks(hooks):
    if hooks is None:
        return
    prev_sys, prev_thr, on_crash, on_thread_crash = hooks
    # only unwind our own frame: someone may have chained on top of us
    if sys.excepthook is on_crash:
        sys.excepthook = prev_sys
    if threading.excepthook is on_thread_crash:
        threading.excepthook = prev_thr


__all__ = ["Tracer", "NoopTracer", "NOOP_TRACER", "Span", "SpanContext",
           "TRACE_KEY", "get_tracer", "set_tracer", "startup_report",
           "RoundLog",
           "MetricsRegistry", "get_registry", "set_registry",
           "FlightRecorder", "get_flight_recorder", "set_flight_recorder",
           "PerfMonitor", "StatusWriter", "get_perf_monitor",
           "set_perf_monitor",
           "CostModel", "get_cost_model", "set_cost_model",
           "add_observability_args", "enable"]
