"""What the benchmark's harness does not export, for one cell of
``BENCHMARK.json`` on the machine it is started on (PERF.md, PR 25):

- set-up's first two rounds under a ``CompileWatcher`` each: the seconds
  of tracing, lowering, cache loads and backend compiles (the first round
  of a warm process against the second);
- the cost of tracing when on: ``--runs`` windows of ``--rounds`` rounds
  under the no-op tracer and as many under a real ``Tracer`` with no
  profiler session, alternating; then ``--extra`` more rounds under the
  real one. Every traced round's spans are summed by name, so a slow
  round shows which span carries its excess, and every round has the
  process's CPU seconds beside its wall seconds;
- one round under the profiler and a ``Tracer``, as ``harness.run`` makes
  it with ``--trace 1``: every span of the round against the host-plane
  event of its name (the offset reckoned as the harness reckons it for
  its gap labels), and ``local-train``'s self time;
- the fold's contract on this device (``--fold_contract 1``, last, since
  the host fold raises the process's peak memory): one real round of the
  cell from its current state folded both ways -- the synchronous
  stream's two-word float32 fold on the device against the canonical
  float64 host fold of the same payload sums (the buffered path with an
  unbounded buffer and decay 0) -- with the share of elements that
  differ, the largest distance in float32 ulps, and the device's peak
  memory before the host fold ran. A compiler that simplified the
  error-free sums away would show here, not in a CPU test.

    python3 scripts/span_probe.py --workload <cell> --seed <n>

Prints one JSON object and writes it to
``chiprun_out/span_probe/<cell>.json``. Needs a TPU unless ``--cpu_root``
names a toy checkout (``benchmarks/tests/toyroot.py``).
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _by_name(spans):
    """Spans summed by name: count, seconds, and bytes where given."""
    out = {}
    for s in spans:
        row = out.setdefault(s.name, {"n": 0, "s": 0.0})
        row["n"] += 1
        row["s"] += (s.t1 - s.t0) / 1e6
        if "bytes" in s.attrs:
            row["bytes"] = row.get("bytes", 0) + s.attrs["bytes"]
    return out


def _self_time(spans, name):
    """``name``'s seconds, its self seconds (what no child covers), and
    the self seconds by the child that ended before each uncovered
    stretch: what runs between two spans follows from the first."""
    parent = next(s for s in spans if s.name == name)
    kids = sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.t0)
    after, reach, prev = {}, parent.t0, "start"
    for s in kids + [None]:
        t0 = parent.t1 if s is None else s.t0
        if t0 > reach:
            after[prev] = after.get(prev, 0.0) + (t0 - reach) / 1e6
        if s is not None and s.t1 > reach:
            reach, prev = s.t1, s.name
    return {"s": (parent.t1 - parent.t0) / 1e6,
            "self_s": sum(after.values()), "self_after": after}


def _window(api, rounds, tracer):
    """``rounds`` rounds under ``tracer`` (None: the no-op one); per
    round its wall and CPU seconds and, traced, its spans by name."""
    from fedml_tpu.observability.tracing import set_tracer

    rows = []
    for _ in range(rounds):
        seen = len(tracer.finished_spans()) if tracer else 0
        prev = set_tracer(tracer)
        try:
            a, cpu = time.perf_counter(), time.process_time()
            api.train_one_round()
            b, cpu = time.perf_counter(), time.process_time() - cpu
        finally:
            set_tracer(prev)
        row = {"round_s": b - a, "cpu_s": cpu}
        if tracer:
            mine = tracer.finished_spans()[seen:]
            row["spans"] = _by_name(mine)
            row["local_train"] = _self_time(mine, "local-train")
        rows.append(row)
    return rows


def _profiled_round(api, trace_dir):
    """One round as ``harness.run`` traces it; the checks of the clock."""
    import jax

    from benchmarks import harness, trace_reader
    from fedml_tpu.observability.tracing import Tracer, set_tracer

    tracer = Tracer()
    prev = set_tracer(tracer)
    jax.profiler.start_trace(trace_dir)
    try:
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation(harness.ANNOTATION, round=0):
            api.train_one_round()
        b, wall = time.perf_counter(), time.time()
    finally:
        jax.profiler.stop_trace()
        set_tracer(prev)
    summary = trace_reader.read(trace_reader.find_xplane(trace_dir),
                                harness.ANNOTATION)
    offset = (wall - (b - a)) - summary.annotations[0][1]
    spans = tracer.finished_spans()
    worst, missing = {}, []
    for name in sorted({s.name for s in spans}):
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.t0)
        events = sorted((e for e in summary.host if e[0] == name),
                        key=lambda e: e[1])
        if len(events) != len(mine):
            missing.append([name, len(mine), len(events)])
            continue
        worst[name] = max(abs(e[1] + offset - s.t0 / 1e6)
                          for s, e in zip(mine, events))
    return {"round_s": b - a, "offset_s": offset,
            "start_disagreement_s": worst,
            "start_disagreement_max_s": max(worst.values(), default=None),
            "spans_without_event": missing,
            "local_train": _self_time(spans, "local-train"),
            "spans": _by_name(spans)}


def _peak_bytes():
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def _fold_both_ways(api, seed):
    """One round of ``api``'s cohort from a copy of its current state,
    through ``run_round`` twice: the device fold, then the host fold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.program import AggregationPolicy
    from fedml_tpu.program.aggregation import float32_ulps

    # the canonical host fold, built as the program builds it: an
    # unbounded buffer with decay 0 flushes once, through the float64 fold
    oracle = api.program.replace(aggregation=AggregationPolicy(
        buffer_k=10 ** 9, staleness_decay=0.0)).host_view()
    ids = api._sample_cohort(api.round_idx)
    runner = api.runner
    own = runner.aggregator, runner.data_rng
    out = {"peak_bytes_device_fold": _peak_bytes()}
    states = {}
    for way in ("device", "host"):
        runner.aggregator = (None if way == "device"
                             else oracle.make_aggregator())
        runner.data_rng = np.random.default_rng(seed)
        a = time.perf_counter()
        gs, _, info = runner.run_round(
            jax.tree.map(jnp.copy, api.global_state),
            jax.tree.map(jnp.copy, api.server_state), ids,
            jax.random.PRNGKey(seed % (2 ** 31)))
        jax.block_until_ready(gs)
        out[way + "_round_s"] = time.perf_counter() - a
        if info["fold"] != way:
            raise RuntimeError(f"asked for the {way} fold, the runner "
                               f"said {info['fold']}")
        states[way] = jax.device_get(jax.tree.leaves(gs))
        del gs
    runner.aggregator, runner.data_rng = own
    out["peak_bytes_after_host_fold"] = _peak_bytes()
    elements = differing = worst = 0
    for d, h in zip(states["device"], states["host"]):
        if d.dtype != np.float32 or not np.isfinite(h).all():
            raise RuntimeError("the probe compares finite float32 states")
        dist = float32_ulps(d, h)
        elements += dist.size
        differing += int((dist > 0).sum())
        worst = max(worst, int(dist.max(initial=0)))
    out.update(elements=elements, differing=differing,
               differing_share=differing / max(elements, 1),
               max_ulps=worst, chunks=info["bucket"]["chunks"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=1,
                    help="rounds to a window (a 45 s window's count)")
    ap.add_argument("--extra", type=int, default=0)
    ap.add_argument("--fold_contract", type=int, default=0)
    ap.add_argument("--cpu_root", default=None)
    args = ap.parse_args(argv)

    import jax

    from benchmarks import harness
    from benchmarks.manifest import Manifest
    from fedml_tpu.observability.jaxmon import watch_compiles
    from fedml_tpu.observability.tracing import Tracer

    root = args.cpu_root or ROOT
    man = Manifest(root)
    entry = man.cell(args.workload)
    config = man.config(entry["config"])
    harness.cache_dir(root)
    if args.cpu_root is None and jax.devices()[0].platform != "tpu":
        print("span_probe: needs a TPU, or --cpu_root", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    cell = family.build(config, man.traffic(entry["traffic"]), args.seed,
                        man.reference(config))
    out = {"workload": args.workload, "seed": args.seed, "setup": []}
    for _ in range(2):
        with watch_compiles() as watch:
            a = time.perf_counter()
            cell.api.train_one_round()
            out["setup"].append({"round_s": time.perf_counter() - a,
                                 **watch.report()})
    out["noop"], out["traced"] = [], []
    tracer = Tracer()
    for _ in range(args.runs):
        out["noop"].append(_window(cell.api, args.rounds, None))
        out["traced"].append(_window(cell.api, args.rounds, tracer))
    out["traced_extra"] = _window(cell.api, args.extra, tracer)
    with tempfile.TemporaryDirectory() as trace_dir:
        out["profiled"] = _profiled_round(cell.api, trace_dir)
    if args.fold_contract:
        out["fold_contract"] = _fold_both_ways(cell.api, args.seed)
    path = os.path.join(ROOT, "chiprun_out", "span_probe")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, args.workload + ".json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
