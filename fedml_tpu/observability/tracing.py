"""Structured span tracing for the federated round lifecycle ("fedtrace").

Dapper-style distributed tracing (Sigelman et al., 2010) scaled down to
this control plane: every span carries a ``trace_id`` shared by the whole
round tree and a ``parent_id`` naming the span it hangs under. Inside one
process, parentage flows through a thread-local context stack; across
ranks it rides the message envelope -- :meth:`Tracer.inject` writes a
``{"trace_id", "span_id"}`` dict under the reserved ``__trace__`` control
field (JSON header of the binary codec, so every transport carries it for
free) and the manager dispatch loop re-establishes it around handlers via
:meth:`Tracer.remote_context`. The result: a client rank's ``local-train``
span stitches under the server's ``round`` span into one tree, viewable in
Perfetto / ``chrome://tracing`` via :meth:`Tracer.export_chrome`.

Two levels of the one :class:`Tracer`. The process's default is the
RECORDER: context-managed spans timed on the host clock into a bounded
ring, nothing else -- ``enabled`` is False, ``start_span`` /
``remote_context`` / ``inject`` are the no-op's, so a run without
``--trace`` sends bit-identical frames and does none of the work the
instrumentation points gate on ``enabled`` (leaf walks, an explicit
``block_until_ready``). From that ring come :func:`startup_report` (what
a start-up cost, by the program span that paid each compile event) and
:class:`RoundLog`'s ``round_stall`` warning (a round that took much
longer than its neighbours, and whether the device or the host did).
``--trace`` / ``enable(trace=True)`` install the EXPORTING level: ids fit
to stitch across ranks, wire injection, detached spans, exports.
:data:`NOOP_TRACER` turns everything off.

One clock with the profiler: a context-managed span of an exporting
:class:`Tracer` also holds a ``jax.profiler.TraceAnnotation`` of its name
for its lifetime, so inside a profiler session (``--xprof_round``, the
benchmark's ``--trace 1``) the same spans stand on the host plane of the
xplane beside the device's events. With no session open the annotation is
a flag check; the recorder and the no-op tracer never make one.

Stdlib-only at import time (the transports must stay importable without
jax): ``jax.profiler`` is taken from ``sys.modules`` and only when ``jax``
is already there.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import logging
import os
import statistics
import sys
import threading
import time

from fedml_tpu.observability.registry import get_registry


#: Reserved message control field carrying the trace context on the wire.
TRACE_KEY = "__trace__"

def _new_id(nbytes=8):
    return os.urandom(nbytes).hex()


class SpanContext:
    """The propagatable half of a span: what children need to stitch."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id, span_id):
        self.trace_id = trace_id
        self.span_id = span_id

    def as_dict(self):
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_dict(cls, d):
        try:
            return cls(str(d["trace_id"]), str(d["span_id"]))
        except (TypeError, KeyError):
            return None


def _annotation(span):
    """The profiler's annotation for ``span`` (scalar attrs ride along as
    the event's stats), or None in a process that has not imported jax."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    return profiler.TraceAnnotation(
        span.name, **{k: v for k, v in span.attrs.items()
                      if isinstance(v, (int, float, str))})


class Span:
    """One timed phase. Created by :meth:`Tracer.start_span` (detached --
    for cross-thread begin/end like the server's per-attempt round span;
    a span that may end on another thread cannot be a profiler
    annotation, so these stay in the :class:`Tracer`'s record only) or
    :meth:`Tracer.span` (context manager, thread-local parentage, also on
    the profiler's timeline)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "t0", "t1", "thread", "_tracer")

    def __init__(self, tracer, name, trace_id, parent_id, attrs):
        self._tracer = tracer
        self.name = name
        self.span_id = tracer._new_id()
        # a root's trace is its own
        self.trace_id = trace_id if trace_id is not None else self.span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.t0 = tracer._now()
        self.t1 = None
        self.thread = threading.current_thread().name

    @property
    def context(self):
        return SpanContext(self.trace_id, self.span_id)

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def end(self):
        """Idempotent: a span double-ended by a racing path records once
        (the check-and-set runs under the tracer's lock -- two genuinely
        concurrent end() calls record exactly one span)."""
        self._tracer._finish(self, self._tracer._now())

    def as_dict(self):
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "ts": self.t0, "dur": (self.t1 or self.t0) - self.t0,
                "thread": self.thread, "attrs": self.attrs}


class _SpanScope:
    """Context manager pairing a span with the thread-local stack and,
    at the exporting level, with its ``jax.profiler.TraceAnnotation``."""

    __slots__ = ("_tracer", "span", "_annotation")

    def __init__(self, tracer, span):
        self._tracer = tracer
        self.span = span
        self._annotation = _annotation(span) if tracer.enabled else None

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._tracer._push(self.span.context)
        return self.span

    def __exit__(self, *exc):
        self._tracer._pop()
        self.span.end()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


class Tracer:
    """Collects spans in a bounded ring; thread-safe; at the exporting
    level exports Chrome trace-event JSON + JSONL.

    Args:
      max_spans: the ring's size -- the oldest spans are dropped beyond it
        (a multi-hour run must not grow host memory without bound). The
        drop count is reported in the Chrome export's metadata.
      exporting: the level. True (``--trace``): random ids that stitch
        across ranks, wire injection, detached spans, profiler
        annotations, ``enabled`` True. False (the process's default, the
        recorder): context-managed spans and :meth:`record` only, on the
        host clock; ``start_span`` / ``remote_context`` / ``inject`` do
        nothing and ``enabled`` stays False, so whatever an
        instrumentation point gates on it stays off.
    """

    def __init__(self, max_spans=200_000, exporting=True):
        self.enabled = bool(exporting)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._spans = collections.deque(maxlen=int(max_spans))
        self._dropped = 0
        # the recorder's ids only tell the spans of one process apart
        self._new_id = _new_id if exporting else itertools.count(1).__next__
        #: epoch anchor: span timestamps are epoch-based microseconds so
        #: traces from different processes of one job align in Perfetto,
        #: taken when the tracer is made (a reader that sets them beside
        #: ``time.time()``, as the benchmark's gap labels do, sees no
        #: drift between the two clocks since the process began)
        self._t0_epoch = time.time()
        self._t0_perf = time.perf_counter()

    def _now(self):
        # monotonic progression, epoch-anchored (us)
        return (self._t0_epoch
                + (time.perf_counter() - self._t0_perf)) * 1e6

    # -- thread-local context stack ---------------------------------------
    def _stack(self):
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, ctx):
        self._stack().append(ctx)

    def _pop(self):
        stack = self._stack()
        if stack:
            stack.pop()

    def current(self):
        """The innermost active context on this thread (or None)."""
        stack = self._stack()
        return stack[-1] if stack else None

    def remote_context(self, ctx):
        """Adopt a foreign :class:`SpanContext` (extracted from a message)
        as this thread's current parent for the ``with`` block -- the
        receive-side half of cross-rank stitching (exporting level)."""
        return _RemoteScope(self, ctx) if self.enabled else _NOOP_SCOPE

    # -- span creation -----------------------------------------------------
    def start_span(self, name, parent=None, root=False, **attrs):
        """Detached span: NOT pushed on the thread-local stack, so it can
        be ended from another thread (the FSM round span's lifecycle).
        ``parent`` is a :class:`SpanContext`; None falls back to the
        calling thread's current context; ``root=True`` forces a fresh
        trace even when a context is active (the server's per-attempt
        round spans are roots regardless of which handler thread opened
        them). The recorder keeps none (a span that may end on another
        thread, or never, is the exporting level's)."""
        if not self.enabled:
            return _NOOP_SCOPE
        return self._open(name, parent, root, attrs)

    def _open(self, name, parent, root, attrs):
        ctx = None if root else (
            parent if parent is not None else self.current())
        if ctx is not None:
            return Span(self, name, ctx.trace_id, ctx.span_id, attrs)
        return Span(self, name, None, None, attrs)

    def span(self, name, parent=None, root=False, **attrs):
        """Context-managed span parented on this thread's current context
        (or ``parent`` when given); children opened inside see it. At the
        exporting level it is a ``jax.profiler.TraceAnnotation`` as well,
        so a profiler session shows it under its own name."""
        return _SpanScope(self, self._open(name, parent, root, attrs))

    def record(self, name, seconds, absorb=(), **attrs):
        """A finished span of ``seconds`` that ended now, under this
        thread's innermost open span: how an event that is reported as it
        ends (``jax.monitoring``'s compile events) joins the tree of the
        span that paid for it. Recorded spans named in ``absorb`` that
        lie inside it (they ended just before, under the same span) are
        taken out and counted in its ``nested``."""
        span = self._open(name, None, False, attrs)
        t1, span.t0 = span.t0, span.t0 - float(seconds) * 1e6
        with self._lock:
            ring, nested = self._spans, 0
            while ring and ring[-1].name in absorb \
                    and ring[-1].t0 >= span.t0 \
                    and ring[-1].parent_id == span.parent_id \
                    and ring[-1].thread == span.thread:
                nested += 1 + ring.pop().attrs.get("nested", 0)
            if nested:
                span.attrs["nested"] = nested
            self._append(span, t1)
        return span

    def _finish(self, span, t1):
        with self._lock:
            if span.t1 is None:  # racing double-end: first one won
                self._append(span, t1)

    def _append(self, span, t1):
        # under the lock
        span.t1 = t1
        if len(self._spans) == self._spans.maxlen:
            self._dropped += 1  # the ring drops its oldest
        self._spans.append(span)

    # -- wire propagation --------------------------------------------------
    def inject(self, msg, ctx=None):
        """Attach ``ctx`` (default: this thread's current context) to a
        :class:`~fedml_tpu.core.message.Message` under ``__trace__``; the
        binary codec carries it as a JSON control field. The recorder
        leaves the message untouched."""
        if not self.enabled:
            return
        ctx = ctx if ctx is not None else self.current()
        if ctx is not None:
            msg.add(TRACE_KEY, ctx.as_dict())

    @staticmethod
    def extract(msg):
        """The receive-side inverse: a :class:`SpanContext` or None."""
        d = msg.get(TRACE_KEY)
        return SpanContext.from_dict(d) if isinstance(d, dict) else None

    # -- introspection / export --------------------------------------------
    def finished_spans(self):
        with self._lock:
            return list(self._spans)

    def spans_since(self, t0):
        """The finished spans that began at or after ``t0``, oldest
        first (the ring is in order of finishing, so a span's subtree is
        at its tail when it ends)."""
        out = []
        with self._lock:
            for s in reversed(self._spans):
                if s.t1 < t0:
                    break
                if s.t0 >= t0:
                    out.append(s)
        out.reverse()
        return out

    def durations_by_name(self):
        """``{span name: [durations in seconds]}`` -- the bench's
        per-phase attribution feed."""
        out = {}
        for s in self.finished_spans():
            out.setdefault(s.name, []).append(
                ((s.t1 or s.t0) - s.t0) / 1e6)
        return out

    def export_jsonl(self, path):
        """One JSON line per span (trace/span/parent ids, ts/dur in us)."""
        with open(path, "w") as f:
            for s in self.finished_spans():
                f.write(json.dumps(s.as_dict()) + "\n")
        return path

    def export_chrome(self, path):
        """Chrome trace-event JSON (load in Perfetto / chrome://tracing).

        Every finished span becomes a balanced B/E pair; pid groups by
        span thread name is not enough for cross-rank trees, so the trace
        and span ids ride in ``args`` and ``rank`` attrs (when present)
        name the track."""
        events = []
        threads = {}
        for s in self.finished_spans():
            tid = threads.setdefault(s.thread, len(threads))
            args = {"trace_id": s.trace_id, "span_id": s.span_id}
            if s.parent_id:
                args["parent_id"] = s.parent_id
            args.update({str(k): _jsonable(v) for k, v in s.attrs.items()})
            common = {"name": s.name, "cat": "fed", "pid": 0, "tid": tid}
            events.append({"ph": "B", "ts": s.t0, "args": args, **common})
            events.append({"ph": "E", "ts": s.t1 or s.t0, **common})
        meta = [{"ph": "M", "pid": 0, "tid": tid, "name": "thread_name",
                 "args": {"name": tname}}
                for tname, tid in sorted(threads.items(), key=lambda kv: kv[1])]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms",
               "otherData": {"dropped_spans": self._dropped}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


class _RemoteScope:
    __slots__ = ("_tracer", "_ctx")

    def __init__(self, tracer, ctx):
        self._tracer = tracer
        self._ctx = ctx

    def __enter__(self):
        self._tracer._push(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        self._tracer._pop()
        return False


def _jsonable(v):
    if isinstance(v, (int, float, str, bool, type(None))):
        return v
    return str(v)


# -- the no-op tracer ----------------------------------------------------

class _NoopScope:
    """Shared, reusable no-op context manager (also quacks like a Span)."""

    __slots__ = ()
    name = trace_id = span_id = parent_id = None
    context = None
    span = None  # _SpanScope surface parity

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def end(self):
        return None


_NOOP_SCOPE = _NoopScope()
_NoopScope.span = _NOOP_SCOPE  # `with t.span(..) as s:` yields the noop


class NoopTracer:
    """Zero-cost stand-in when tracing is off: every method returns a
    shared inert object; ``inject`` leaves the message untouched, so
    disabled runs put bit-identical frames on the wire."""

    enabled = False

    def span(self, name, parent=None, root=False, **attrs):
        return _NOOP_SCOPE

    def start_span(self, name, parent=None, root=False, **attrs):
        return _NOOP_SCOPE

    def remote_context(self, ctx):
        return _NOOP_SCOPE

    def current(self):
        return None

    def inject(self, msg, ctx=None):
        return None

    @staticmethod
    def extract(msg):
        return None

    def record(self, name, seconds, absorb=(), **attrs):
        return None

    def finished_spans(self):
        return []

    def spans_since(self, t0):
        return []

    def durations_by_name(self):
        return {}


NOOP_TRACER = NoopTracer()
#: the process's default: the recorder level, a ring of a few thousand
#: spans (a bucketed round opens 30-60, a start-up some hundreds)
_RECORDER = Tracer(max_spans=8192, exporting=False)
_tracer = _RECORDER


def get_tracer():
    """The process-wide tracer (default: the recorder level)."""
    return _tracer


def set_tracer(tracer):
    """Install ``tracer`` (None restores the default recorder;
    :data:`NOOP_TRACER` turns every span off); returns the previous one
    so scopes can nest."""
    global _tracer
    prev = _tracer
    _tracer = tracer if tracer is not None else _RECORDER
    return prev


# -- what the record is for: the start-up report and the stalled round ---

#: the compile events as spans (``jaxmon`` hands them to :meth:`record`),
#: and each one's key in the reports
_JAX_KEYS = {"jax.trace": "trace_s", "jax.lower": "lower_s",
             "jax.compile": "compile_s", "jax.cache_load": "cache_load_s"}
JAX_SPANS = tuple(_JAX_KEYS)


def union_length(intervals):
    """Length of the union of ``[(start, end)]``: what two intervals
    both cover counts once."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def _union_seconds(intervals):
    """The same for the record's microseconds, as seconds."""
    return union_length(intervals) / 1e6


def _blocked(span):
    """The interval in which ``span`` waited for the device, or None: a
    chunk's first fetch (``fold.wait`` exists at the exporting level
    only; without it the wait lands in ``fold.d2h``) and the end of
    ``fold.apply``."""
    if span.name in ("fold.wait", "fold.d2h"):
        return span.t0, span.t1
    if span.name == "fold.apply":
        return span.t1 - span.attrs.get("blocked_s", 0.0) * 1e6, span.t1
    return None


def _waits(spans):
    return [w for w in map(_blocked, spans) if w is not None]


class _Tree:
    """The spans of one stretch of the record, with who hangs under
    whom."""

    def __init__(self, spans):
        self.spans = spans
        self.by_id = {s.span_id: s for s in spans}
        self.kids = {}
        for s in spans:
            self.kids.setdefault(s.parent_id, []).append(s)

    def under(self, root):
        """``root``'s descendants (not itself)."""
        out, todo = [], [root]
        while todo:
            for k in self.kids.get(todo.pop().span_id, ()):
                out.append(k)
                todo.append(k)
        return out

    def self_seconds(self, span):
        kids = [(k.t0, k.t1) for k in self.kids.get(span.span_id, ())]
        return max((span.t1 - span.t0) / 1e6 - _union_seconds(kids), 0.0)

    def site(self, event):
        """Where a compile event was paid: the span it hangs under and,
        for a chunk's program, its bucket edge."""
        parent = self.by_id.get(event.parent_id)
        if parent is None:
            return "caller", None  # outside every span of the program
        return parent.name, parent.attrs.get("edge")


def _compile_parts(tree, events):
    """Seconds by kind over ``events``, and the same by site."""
    def by_kind(mine):
        return {key: _union_seconds(
            [(e.t0, e.t1) for e in mine if e.name == name])
            for name, key in _JAX_KEYS.items()}

    by_site = {}
    for e in events:
        by_site.setdefault(tree.site(e), []).append(e)
    sites = []
    for (site, edge), mine in sorted(
            by_site.items(), key=lambda kv: min(e.t0 for e in kv[1])):
        sites.append({"site": site, "edge": edge,
                      **{k: round(v, 4) for k, v in by_kind(mine).items()}})
    return by_kind(events), sites


def _covered(tree, root):
    """``root``'s seconds that a leaf span or a compile event under it
    covers; what is left is code between the spans."""
    under = tree.under(root)
    return _union_seconds(
        [(s.t0, s.t1) for s in under
         if s.name in JAX_SPANS or not any(
             k.name not in JAX_SPANS
             for k in tree.kids.get(s.span_id, ()))])


def _host_seconds(tree, rnd, under):
    """Host seconds by span name over a round's tree: each span's own
    (what no child covers), less what it waited for the device."""
    out = {}
    for s in under + [rnd]:
        own, wait = tree.self_seconds(s), _blocked(s)
        if wait is not None:
            own = max(own - (wait[1] - wait[0]) / 1e6, 0.0)
        out[s.name] = out.get(s.name, 0.0) + own
    return out


def round_table(tracer):
    """One row a ``round`` span of the record: its seconds, its waits for
    the device, and by span name how many there were, their seconds and
    their host seconds (their own, unblocked: ``local-train``'s is its
    self time)."""
    spans = tracer.finished_spans()
    tree = _Tree(spans)
    rows = []
    for rnd in (s for s in spans if s.name == "round"):
        under = tree.under(rnd)
        host = _host_seconds(tree, rnd, under)
        by = {}
        for s in under:
            row = by.setdefault(s.name, {"n": 0, "s": 0.0})
            row["n"] += 1
            row["s"] += (s.t1 - s.t0) / 1e6
        for name, row in by.items():
            row["s"] = round(row["s"], 6)
            row["host_s"] = round(host[name], 6)
        rows.append({
            "round": rnd.attrs.get("round"),
            "gc_s": rnd.attrs.get("gc_s"), "cpu_s": rnd.attrs.get("cpu_s"),
            "wall_s": round((rnd.t1 - rnd.t0) / 1e6, 6),
            "device_s": round(_union_seconds(_waits(under)), 6),
            "spans": by})
    return rows


class _Startup:
    """The process's start-up: from the first import of ``fedml_tpu`` to
    the start of the first round in which nothing compiles."""

    def __init__(self):
        self.t0 = self.t1 = None
        self.imports = []
        self.report = None

    @property
    def is_open(self):
        return self.t0 is not None and self.t1 is None


_startup = _Startup()


def begin_startup():
    """Open the process's ``startup`` (``fedml_tpu/__init__.py``, once)."""
    if _startup.t0 is None:
        _startup.t0 = _RECORDER._now()


def import_span(**attrs):
    """A span ``import`` of the default recorder; the start-up report
    keeps it whichever tracer is current when the report is made."""
    scope = _RECORDER.span("import", **attrs)
    if _startup.is_open:
        _startup.imports.append(scope.span)
    return scope


def _round_parts(tree, rnd):
    under = tree.under(rnd)
    events = [s for s in under if s.name in JAX_SPANS]
    parts, sites = _compile_parts(tree, events)
    compiles = [e for e in events if e.name == "jax.compile"]
    wall = (rnd.t1 - rnd.t0) / 1e6
    row = {"round": rnd.attrs.get("round"), "wall_s": round(wall, 4)}
    row.update({k: round(v, 4) for k, v in parts.items()})
    row.update(
        cache_hits=sum(e.attrs.get("cache") == "hit" for e in compiles),
        cache_misses=sum(e.attrs.get("cache") == "miss" for e in compiles),
        device_s=round(_union_seconds(_waits(under)), 4),
        pack_s=round(sum(s.t1 - s.t0 for s in under
                         if s.name == "pack") / 1e6, 4),
        h2d_s=round(sum(s.t1 - s.t0 for s in under
                        if s.name == "h2d") / 1e6, 4),
        unattributed_s=round(max(wall - _covered(tree, rnd), 0.0), 4),
        sites=sites)
    return row


def startup_report(tracer=None):
    """What the start-up cost and which span paid it, from the record.

    ``total_s`` runs from the first import of ``fedml_tpu`` to the start
    of the first round in which no compile event fired (to now, and
    ``closed`` False, while there has been none). ``import_s`` and
    ``build_s`` (``FedAvgAPI.__init__``; its parts ``init_state_s``,
    ``select_runner_s`` less ``index_data_s``) are spans' seconds;
    ``trace_s`` / ``lower_s`` / ``compile_s`` / ``cache_load_s`` are the
    compile events' (a second that a nested event covers too counts
    once; a cache load lies inside its compile event), whole and by
    ``sites`` (the span each hangs under and, for ``bucket-chunk``, its
    ``edge``); ``rounds`` has one row a start-up round with the same
    split, its waits for the device (``device_s``), its feed and what no
    leaf span covers. ``caller_s`` is the time between the program's
    spans (the caller's own: data and weights, snapshots);
    ``unattributed_s`` the time inside ``build`` and the rounds that no
    leaf span or event covers. None before ``fedml_tpu`` was imported or
    under :data:`NOOP_TRACER`."""
    st = _startup
    if st.report is not None:
        return st.report
    tracer = tracer if tracer is not None else _tracer
    if st.t0 is None or not isinstance(tracer, Tracer):
        return None
    t1 = st.t1 if st.t1 is not None else tracer._now()
    spans = [s for s in tracer.spans_since(st.t0) if s.t1 <= t1]
    spans += [s for s in st.imports if s.t1 is not None and s not in spans]
    tree = _Tree(spans)
    tops = [s for s in spans if s.name in ("import", "build", "round")]
    builds = [s for s in tops if s.name == "build"]
    rounds = [_round_parts(tree, s) for s in tops if s.name == "round"]
    parts, sites = _compile_parts(
        tree, [s for s in spans if s.name in JAX_SPANS])

    def seconds(name, within=spans):
        return sum(s.t1 - s.t0 for s in within if s.name == name) / 1e6

    index_s = seconds("index-data")
    report = {
        "closed": st.t1 is not None,
        "total_s": round((t1 - st.t0) / 1e6, 4),
        "import_s": round(_union_seconds(
            [(s.t0, s.t1) for s in spans if s.name == "import"]), 4),
        "build_s": round(seconds("build", builds), 4),
        "init_state_s": round(seconds("init-state"), 4),
        "select_runner_s": round(seconds("select-runner") - index_s, 4),
        "index_data_s": round(index_s, 4)}
    report.update({k: round(v, 4) for k, v in parts.items()})
    report.update(
        device_s=round(sum(r["device_s"] for r in rounds), 4),
        caller_s=round((t1 - st.t0) / 1e6 - _union_seconds(
            [(s.t0, s.t1) for s in tops]), 4),
        unattributed_s=round(
            sum(r["unattributed_s"] for r in rounds)
            + sum((b.t1 - b.t0) / 1e6 - _covered(tree, b)
                  for b in builds), 4),
        sites=sites, rounds=rounds,
        dropped_spans=tracer._dropped)
    return report


def _close_startup(tracer, t1):
    """The round that began at ``t1`` compiled nothing: the start-up
    ended there. The report is made once, while the ring holds it."""
    _startup.t1 = t1
    _startup.report = startup_report(tracer)
    logging.info("startup %s", json.dumps(_startup.report))


# seconds Python's collector ran, by generation, since the first RoundLog
_gc_seconds = [0.0, 0.0, 0.0]
_gc_began = [0.0]


def _on_gc(phase, info):
    if phase == "start":
        _gc_began[0] = time.perf_counter()
    else:
        _gc_seconds[info["generation"]] += \
            time.perf_counter() - _gc_began[0]


class RoundLog:
    """What a round loop keeps of its rounds, all from the tracer's
    record: it ends the process's start-up at the first round that
    compiles nothing, and holds each later round against the median of
    up to the last :attr:`HISTORY` (seconds per executed step): one that
    took over :attr:`RATIO` times as long and :attr:`FLOOR_S` seconds
    more is reported once, as ``logging.warning("round_stall %s", json)``
    and in the registry's ``fed_round_stalls_total``, with what grew."""

    HISTORY = 8
    RATIO = 1.25
    FLOOR_S = 0.25
    #: rounds before a median is trusted
    MIN_ROUNDS = 3

    def __init__(self):
        self._rounds = collections.deque(maxlen=self.HISTORY)
        self._mark = None
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def begin(self):
        """Call right before the round's span opens."""
        self._mark = (list(_gc_seconds), time.process_time())

    def end(self, tracer, rnd, steps=None):
        """Call after the round's span ``rnd`` closed; ``steps`` are the
        steps it executed where the runner counts them. Returns the
        stall's record, or None."""
        if getattr(rnd, "t1", None) is None or self._mark is None:
            return None  # the no-op tracer: nothing recorded, no report
        gc0, cpu0 = self._mark
        # on the round's span for whoever reads the record: the seconds
        # Python's collector ran in it, by generation, and its CPU seconds
        gc_s = [round(b - a, 4) for a, b in zip(gc0, _gc_seconds)]
        cpu_s = time.process_time() - cpu0
        rnd.set(gc_s=gc_s, cpu_s=round(cpu_s, 4))
        tree = _Tree([s for s in tracer.spans_since(rnd.t0)
                      if s.thread == rnd.thread])
        under = tree.under(rnd)
        events = [s for s in under if s.name in JAX_SPANS]
        if _startup.is_open:
            if events:
                return None  # a start-up round: the report's, not ours
            _close_startup(tracer, rnd.t0)
        wall = (rnd.t1 - rnd.t0) / 1e6
        n = max(int(steps or 0), 1)
        device = _union_seconds(_waits(under))
        selfs = _host_seconds(tree, rnd, under)
        past = list(self._rounds)
        self._rounds.append({"wall": wall / n, "device": device / n,
                             "selfs": {k: v / n for k, v in selfs.items()}})
        if len(past) < self.MIN_ROUNDS:
            return None

        def usual(get):
            return statistics.median(get(r) for r in past) * n

        median = usual(lambda r: r["wall"])
        excess = wall - median
        if wall <= self.RATIO * median or excess <= self.FLOOR_S:
            return None
        grew = {k: v - usual(lambda r: r["selfs"].get(k, 0.0))
                for k, v in selfs.items()}
        host = sum(v for v in grew.values() if v > 0)
        dev = device - usual(lambda r: r["device"])
        if dev >= max(host, excess / 2):
            verdict, where = "device", "fold.d2h"
        elif host >= excess / 2:
            verdict, where = "host", max(grew, key=grew.get)
        else:
            verdict, where = "unattributed", None
        chunks = sorted((s for s in under if s.name == "bucket-chunk"),
                        key=lambda s: s.t0)
        fetches = sorted((s for s in under if s.name == "fold.d2h"),
                         key=lambda s: s.t0)
        waited = {s.attrs.get("ordinal"): (s.t1 - s.t0) / 1e6
                  for s in under if s.name == "fold.wait"}
        record = {
            "round": rnd.attrs.get("round"), "verdict": verdict,
            "where": where, "wall_s": round(wall, 4),
            "median_s": round(median, 4), "excess_s": round(excess, 4),
            "steps": int(steps) if steps else None,
            # each pair: this round, the median of the rounds before
            "device_s": [round(device, 4), round(device - dev, 4)],
            "spans": {k: [round(v, 4), round(v - grew[k], 4)]
                      for k, v in sorted(selfs.items())},
            # a chunk: its dispatch after the round's start, and the
            # seconds its first fetch waited
            "chunks": [[round((c.t0 - rnd.t0) / 1e6, 4),
                        round((f.t1 - f.t0) / 1e6 + waited.get(i, 0.0), 4)]
                       for i, (c, f) in enumerate(zip(chunks, fetches))],
            "compile": [[e.name, *tree.site(e),
                         round((e.t1 - e.t0) / 1e6, 4)] for e in events],
            "gc_s": gc_s, "cpu_over_wall": round(cpu_s / wall, 4),
        }
        logging.warning("round_stall %s", json.dumps(record))
        reg = get_registry()
        if reg is not None:
            reg.inc("fed_round_stalls_total",
                    help="rounds that took over 1.25 times the median of "
                         "the last 8 and 0.25 s more")
        return record


__all__ = ["TRACE_KEY", "SpanContext", "Span", "Tracer", "NoopTracer",
           "NOOP_TRACER", "get_tracer", "set_tracer", "JAX_SPANS",
           "union_length", "begin_startup", "import_span", "startup_report",
           "round_table", "RoundLog"]
