"""One reader per kind of metric file. A reader takes the metric's file
and the run's context and returns a number, or None when there is nothing
to read (the harness then leaves the metric out of the line).

The context (``ctx``) of a run:

- ``counters``: numbers the harness counted (``setup_s``, ``rounds``,
  ``window_s``, ``setup.compile_s``, ``window.compiles``, ``round_s.max``);
- ``work``: useful work done in the window, by name (``rounds``,
  ``tokens``, ``useful_flops``), from the family's functions of shapes;
- ``trace`` / ``traced_rounds``: a ``TraceSummary`` and how many rounds it
  holds, or None in an untraced run;
- ``shapes``: counts from shapes by the family (``kernels`` has the FLOPs
  and bytes the algorithm needs for one call of each kernel);
- ``peaks`` and ``chips``.
"""

from __future__ import annotations


def counter(spec, ctx):
    value = ctx["counters"].get(spec["counter"])
    return None if value is None else value * spec.get("scale", 1.0)


def rate(spec, ctx):
    """All of one kind of work of the window over all of its time."""
    work = ctx["work"].get(spec["work"])
    if work is None or ctx["counters"]["window_s"] <= 0:
        return None
    return work / ctx["counters"]["window_s"] * spec.get("scale", 1.0)


def share_of_peak(spec, ctx):
    """Useful FLOPs of the traced rounds over what the chips could have
    done in the device time of the programs matching ``pattern``: the
    whole step's share of the peak while it runs."""
    flops = ctx["work"].get("useful_flops")
    if ctx["trace"] is None or not flops or not ctx["counters"]["rounds"]:
        return None
    seconds, count = ctx["trace"].time_of(spec["pattern"],
                                          spec.get("line", "ops"))
    if not count or seconds <= 0:
        return None
    traced = flops / ctx["counters"]["rounds"] * ctx["traced_rounds"]
    return 100.0 * traced / (seconds * ctx["peaks"]["flops"] * ctx["chips"])


def trace_time(spec, ctx):
    """Device time of the events matching ``pattern``, per traced round."""
    if ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].time_of(spec["pattern"],
                                          spec.get("line", "ops"))
    if not count:
        return None
    return seconds / max(ctx["traced_rounds"], 1) * spec.get("scale", 1.0)


def host_time(spec, ctx):
    """Host time inside the events matching ``pattern`` (the program's
    Python calls, as the profiler's tracer names them), per traced
    round."""
    if ctx["trace"] is None:
        return None
    seconds, count = ctx["trace"].host_time_of(spec["pattern"])
    if not count:
        return None
    return seconds / max(ctx["traced_rounds"], 1) * spec.get("scale", 1.0)


def roofline(spec, ctx):
    """The least time the chip could take for the calls seen (the larger
    of FLOPs over peak FLOP/s and bytes over peak bytes/s) over the device
    time they took. ``events_per_call`` trace events make one call."""
    if ctx["trace"] is None:
        return None
    cost = ctx["shapes"].get("kernels", {}).get(spec["kernel"])
    seconds, count = ctx["trace"].time_of(spec["pattern"])
    if not cost or not count or seconds <= 0:
        return None
    calls = count / spec.get("events_per_call", 1)
    least = max(cost["flops"] / ctx["peaks"]["flops"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds


def trace_idle(spec, ctx):
    if ctx["trace"] is None or ctx["trace"].window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx["trace"].busy_s / ctx["trace"].window_s)


KINDS = {f.__name__: f for f in (counter, rate, share_of_peak,
                                 trace_time, host_time, roofline,
                                 trace_idle)}


def read(metric, ctx):
    """``metric`` is a manifest entry with its ``reader`` file."""
    spec = metric["reader"]
    try:
        fn = KINDS[spec["kind"]]
    except KeyError:
        raise ValueError(f"metric {metric['name']!r}: unknown reader kind "
                         f"{spec.get('kind')!r}; readers.py has "
                         f"{sorted(KINDS)}") from None
    return fn(spec, ctx)
