"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device numbers.

What the trace of this stack looks like (looked at by hand, TPU v5e, jax
0.9.0): one plane per chip named ``/device:TPU:<n>`` with the lines ``XLA
Modules`` (one event per executed program, named ``jit_<fn>(<hash>)``),
``XLA Ops`` (one event per HLO operation, named by its HLO text, so a
Pallas kernel reads ``%<jax name>.<n> = ... custom-call(...)``) and ``Async
XLA Ops``; one plane ``/host:CPU`` with a line per thread, where
``jax.profiler.TraceAnnotation`` events appear under their own name and
every Python call of the main thread as ``$<file>.py:<line> <function>``
(the profiler's Python tracer, on by default). Host and device share the
time base to within about 2 ms.

The traced window is the span from the first to the last annotation named
``window_annotation``; device events are clipped to it. Busy time is the
union of the ``XLA Ops`` intervals, averaged over the device planes.
"""

from __future__ import annotations

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: operations whose event spans the events of their body, which the line
#: has too: left out of the list of the longest operations
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    """The newest ``*.xplane.pb`` under ``trace_dir`` or None."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals):
    """Total length and the merged list of ``[(start, end)]`` intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


class TraceSummary:
    """Events of one trace, in seconds from the trace's own origin."""

    def __init__(self, devices, annotations, window, host=()):
        #: per device plane: {"ops": [(name, start, end)], "modules": [...]}
        self.devices = devices
        #: the host plane's events [(name, start, end)], all threads
        self.host = host
        #: host annotations [(name, start, end)]
        self.annotations = annotations
        #: (start, end) of the traced window
        self.window = window

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def _clipped(self, events):
        w0, w1 = self.window
        return [(n, max(a, w0), min(b, w1)) for n, a, b in events
                if b > w0 and a < w1]

    @functools.cached_property
    def busy_s(self):
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(_union([(a, b) for _, a, b in self._clipped(d["ops"])])[0]
                   for d in self.devices) / len(self.devices)

    def time_of(self, pattern, line="ops"):
        """(seconds, events) of the events whose name matches ``pattern``,
        averaged over the chips."""
        rx = re.compile(pattern)
        total, count = 0.0, 0
        for d in self.devices:
            for name, a, b in self._clipped(d[line]):
                if rx.search(name):
                    total += b - a
                    count += 1
        n = max(len(self.devices), 1)
        return total / n, count / n

    def host_time_of(self, pattern):
        """(seconds, events) of the host plane's events whose name matches
        ``pattern``: the union of their intervals, so that a call inside a
        matching call is not counted twice."""
        rx = re.compile(pattern)
        hits = [(a, b) for n, a, b in self._clipped(self.host)
                if rx.search(n)]
        return _union(hits)[0], len(hits)

    def top_ops(self, limit=10):
        """``[[name, seconds]]`` of the operations that took most time
        (summed by name, averaged over the chips; loops and branches are
        left out, since their bodies' operations are on the line too)."""
        by = {}
        for d in self.devices:
            for name, a, b in self._clipped(d["ops"]):
                key = short_name(name)
                if key.rsplit(" ", 1)[-1] in CONTAINERS:
                    continue
                by[key] = by.get(key, 0.0) + (b - a)
        n = max(len(self.devices), 1)
        rows = sorted(by.items(), key=lambda kv: -kv[1])[:limit]
        return [[k, v / n] for k, v in rows]

    def idle_gaps(self, label, limit=10):
        """``[[name, seconds]]``: the idle time of the first chip inside
        the window, summed by what ``label(start, end)`` says the host was
        doing in each gap, longest first."""
        if not self.devices:
            return []
        _, merged = _union([(a, b) for _, a, b
                            in self._clipped(self.devices[0]["ops"])])
        edges = [self.window[0]] + [t for ab in merged for t in ab] \
            + [self.window[1]]
        by = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > 0:
                key = label(a, b)
                by[key] = by.get(key, 0.0) + (b - a)
        rows = sorted(by.items(), key=lambda kv: -kv[1])[:limit]
        return [[k, v] for k, v in rows]


def short_name(hlo_text):
    """``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8 fusion``: the
    operation's name and kind without its shapes."""
    m = re.match(r"%?(\S+) = .*?([a-z][a-z\-]*)\(", hlo_text)
    if not m:
        return hlo_text[:80]
    return f"{m.group(1)} {m.group(2)}"


def read(path, window_annotation="bench_round"):
    """Read one ``*.xplane.pb``. Raises ``ValueError`` when the trace
    holds no annotation named ``window_annotation``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations, host = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events)
            annotations = [e for e in host if e[0] == window_annotation]
    if not annotations:
        raise ValueError(f"no {window_annotation!r} annotation in {path}")
    annotations.sort(key=lambda a: a[1])
    window = (annotations[0][1], max(a[2] for a in annotations))
    return TraceSummary(devices, annotations, window, host)
