"""One run of one cell: set-up, window, check, result line.

The run is a closed loop: one trainer takes round after round. Set-up
builds ONE trainer (the program's ``FedAvgAPI``) from the seed, drives it
through its first ``check_rounds`` rounds by the same call the window
uses (``train_one_round``; these rounds compile, or load, every program
of the window) and keeps the global weights after each on the host. The
window then drives that same trainer until ``--seconds`` have passed and
the round in flight has ended in its device sync. Once the window has
closed, ``memory_peak_bytes`` is read, the trainer is freed, and the plain
reference follows the same rounds from the same seed; ``compare.py``
decides ``correct``.

With ``--trace 1`` the profiler runs over the first ``trace_rounds``
rounds of the window; stopping it falls between two rounds and its
seconds are taken out of the window's length. The cell's newest trace
stays at ``<checkout>/.bench_trace/<cell>`` for ``tools/dump_trace.py``.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import time

from benchmarks import compare, readers, trace_reader
from benchmarks.manifest import ROOT, Manifest
from benchmarks.peaks import peaks_of

ANNOTATION = "bench_round"
EXIT_NO_CHIP = 3


def cache_dir(root):
    """JAX's persistent compilation cache: where the environment says, or
    at the fixed ``<checkout>/.jax_cache`` (the path is part of the key)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(root, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _devices(chips, require_chip):
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"benchmark: needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return None
    return devices[:chips]


def _label_gap(spans, rounds, offset):
    """Name an idle gap of the trace by what the host was doing at its
    middle: the deepest program span that covers it, inside which round of
    the harness, or the boundary between two rounds."""
    def label(a, b):
        mid = (a + b) / 2.0
        inside = [r for r in rounds if r[1] <= mid <= r[2]]
        if not inside:
            return "between rounds (harness)"
        t_us = (mid + offset) * 1e6
        cover = [s for s in spans
                 if s["ts"] <= t_us <= s["ts"] + s["dur"]]
        if not cover:
            return "in round, outside the program's spans"
        deepest = min(cover, key=lambda s: s["dur"])
        return "in span " + deepest["name"]
    return label


def _norms_of_change(snapshots, init):
    """Per snapshot: leaf -> norm of (snapshot - init), computed on the
    device leaf by leaf (the host copy goes up, one float comes back)."""
    import jax
    import jax.numpy as jnp

    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))))
    return [{k: float(norm(jnp.asarray(snap[k]), init[k])) for k in init}
            for snap in snapshots]


def run(workload, seed, seconds, trace, *, root=ROOT, require_chip=True,
        cell_hook=None, t_start=None):
    """Run one cell; returns ``(exit code, result dict or None)`` and
    prints the result line. ``cell_hook(cell)`` lets a test break the
    timed path underneath; ``require_chip=False`` lets it run on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    man = Manifest(root)
    cell_entry = man.cell(workload)
    config = man.config(cell_entry["config"])
    traffic = man.traffic(cell_entry["traffic"])
    cell_file = man.cell_file(workload)
    chips = int(cell_entry["chips"])
    if root not in sys.path:
        sys.path.insert(0, root)
    import fedml_tpu  # noqa: F401  (no program, no run)
    import jax

    cache_dir(root)
    devices = _devices(chips, require_chip)
    if devices is None:
        return EXIT_NO_CHIP, None
    from fedml_tpu.observability.jaxmon import watch_compiles
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    reference = man.reference(config)
    check_rounds = int(cell_file["check_rounds"])

    # ---- set-up ----------------------------------------------------------
    with watch_compiles() as setup_watch:
        cell = family.build(config, traffic, seed, reference)
        if cell_hook is not None:
            cell_hook(cell)
        prog_losses, snapshots, check_round_s = [], [], []
        for _ in range(check_rounds):
            a = time.perf_counter()
            m = cell.api.train_one_round()
            check_round_s.append(time.perf_counter() - a)
            prog_losses.append(float(m["Train/Loss"]))
            snapshots.append(cell.snapshot())
    setup_s = time.perf_counter() - t_start

    # ---- window ----------------------------------------------------------
    tracer = Tracer() if trace else None
    prev_tracer = set_tracer(tracer) if trace else None
    trace_dir = os.path.join(root, ".bench_trace", workload)
    tracing, pause, traced_rounds = False, 0.0, 0
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        tracing = True
    rounds, failed, error = [], 0, None
    try:
        with watch_compiles() as window_watch:
            w0 = time.perf_counter()
            while True:
                a = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation(
                            ANNOTATION, round=len(rounds)):
                        cell.api.train_one_round()
                except Exception as exc:  # a failed round ends the window
                    failed, error = 1, repr(exc)
                    break
                b = time.perf_counter()
                rounds.append((a, b, time.time()))
                if tracing and len(rounds) >= int(cell_file["trace_rounds"]):
                    jax.profiler.stop_trace()
                    tracing, traced_rounds = False, len(rounds)
                    pause = time.perf_counter() - b
                if b - w0 - pause >= seconds:
                    break
    finally:
        if tracing:
            jax.profiler.stop_trace()
            traced_rounds = len(rounds)
        if trace:
            set_tracer(prev_tracer)
    n = len(rounds)
    window_s = (rounds[-1][1] - rounds[0][0] - pause) if n else 0.0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    # ---- check, against the reference, once the state is freed -----------
    feed, feed_backend = cell.feed(check_rounds), cell.feed_backend
    work = {"rounds": n,
            **{k: v * n for k, v in cell.work_per_round.items()}}
    shapes = cell.shapes
    cell.free()
    t_ref = time.perf_counter()
    ref = reference.run_rounds(config, traffic, seed, check_rounds, feed)
    prog_norms = _norms_of_change(snapshots, ref["init"])
    checks = compare.training_checks(prog_losses, prog_norms, ref["loss"],
                                     ref["change_norms"],
                                     cell_file["limits"])
    del ref, snapshots
    reference_s = time.perf_counter() - t_ref
    correct = bool(n >= 1 and not failed and all(c["ok"] for c in checks))

    # ---- metrics ---------------------------------------------------------
    summary = None
    if trace:
        path = trace_reader.find_xplane(trace_dir)
        if path is not None:
            summary = trace_reader.read(path, ANNOTATION)
    spans = [s.as_dict() for s in tracer.finished_spans()] if trace else []
    ctx = {
        "counters": {
            "setup_s": setup_s, "rounds": n, "window_s": window_s,
            "setup.compile_s": setup_watch.total_compile_seconds,
            "window.compiles": window_watch.total_compiles,
            "round_s.max": max((b - a for a, b, _ in rounds), default=None),
        },
        "work": work, "trace": summary,
        "traced_rounds": traced_rounds, "shapes": shapes,
        "peaks": peaks_of(devices[0].device_kind) if require_chip
        else {"flops": float("nan"), "hbm_bytes_per_s": float("nan")},
        "chips": chips,
    }
    metrics = {}
    for m in man.metrics("per_layer" if trace else "end_to_end", workload):
        value = readers.read(m, ctx)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": n + failed, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        # the trace's clock against the host's: the first annotation began
        # at rounds[0]'s start
        offset = (rounds[0][2] - (rounds[0][1] - rounds[0][0])) \
            - summary.annotations[0][1]
        label = _label_gap(spans, summary.annotations, offset)
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(label, 10)}
    result["info"] = {"rounds": n, "window_s": window_s,
                      "setup_s": setup_s, "reference_s": reference_s,
                      "round_s": [b - a for a, b, _ in rounds],
                      "check_round_s": check_round_s,
                      "program_loss": prog_losses, "error": error,
                      "feed_backend": feed_backend,
                      "setup_compiles": setup_watch.total_compiles,
                      "cache_hits": setup_watch.cache_hits,
                      "cache_misses": setup_watch.cache_misses}
    result["checks"] = [{k: c[k] for k in ("name", "value", "limit")}
                        for c in checks]
    for c in checks:
        print(f"check {c['name']} value {c['value']:.6g} limit "
              f"{c['limit']:.6g} {'ok' if c['ok'] else 'FAILED'}"
              + (f" (leaf {c['leaf']})" if c.get("leaf") else ""),
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0, result
