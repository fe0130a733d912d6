"""What the program's own record says of one cell of ``BENCHMARK.json``,
on the machine it is started on (PERF.md section 5, "Set-up's parts"):

- the start-up as ``benchmarks/harness.py`` makes it (the family's cell
  built from the seed, the cell's check rounds with a snapshot after
  each), then ``observability.startup_report()``: imports, the trainer's
  construction, and per start-up round the seconds of tracing, lowering,
  compiling and cache loads BY THE SPAN THAT PAID THEM (``bucket-chunk``
  with its ``edge``, ``fold.*``, ``prepare`` ...), the waits for the
  device, the feed, and what nothing covers;
- ``--rounds`` more rounds under the process's default tracer (the
  recorder level) and ``--traced`` under an exporting ``Tracer`` (what a
  ``--trace 1`` run installs: the explicit ``fold.wait``, the byte
  walks): every round's spans summed by name with their host seconds, so
  ``local-train``'s self time and a slow round's span are one look away;
- the probe's own steps (``caller.build``, ``caller.snapshot``) as spans
  of the same record, so that the report's ``caller_s`` has its parts
  (``sites`` names ``caller.build`` for what the family's data and
  weights compile: the probe asks for the events from its first line);
- the fold's contract on this device (``--fold_contract 1``, last, since
  the host fold raises the process's peak memory): one real round of the
  cell from its current state folded both ways -- the synchronous
  stream's two-word float32 fold on the device against the canonical
  float64 host fold of the same payload sums -- with the share of
  elements that differ, the largest distance in float32 ulps, and the
  device's peak memory before the host fold ran.

Every number comes from the ``Tracer``'s ring: the script reads no clock.

    python3 scripts/span_probe.py --workload <cell> --seed <n> [--cold]

``--cold`` runs against an empty compilation cache (a fresh directory
under ``/tmp``). Prints one JSON object and writes it to
``chiprun_out/span_probe/<cell>[.cold].json``. Needs a TPU unless
``--cpu_root`` names a toy checkout (``benchmarks/tests/toyroot.py``).
"""

import argparse
import importlib
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _rounds(api, n, tracer=None):
    """``n`` rounds under ``tracer`` (None: the process's default, the
    recorder); their rows of the record."""
    from fedml_tpu.observability.tracing import (get_tracer, round_table,
                                                 set_tracer)

    prev = set_tracer(tracer)
    try:
        for _ in range(n):
            api.train_one_round()
        return round_table(get_tracer())[-n:] if n else []
    finally:
        set_tracer(prev)


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
        gs, _, info = runner.run_round(
            jax.tree.map(jnp.copy, api.global_state),
            jax.tree.map(jnp.copy, api.server_state), ids,
            jax.random.PRNGKey(seed % (2 ** 31)))
        jax.block_until_ready(gs)
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
    ap.add_argument("--rounds", type=int, default=6,
                    help="steady rounds under the default tracer")
    ap.add_argument("--traced", type=int, default=2,
                    help="rounds under an exporting Tracer")
    ap.add_argument("--cold", action="store_true",
                    help="an empty compilation cache under /tmp")
    ap.add_argument("--fold_contract", type=int, default=0)
    ap.add_argument("--cpu_root", default=None)
    args = ap.parse_args(argv)

    import fedml_tpu  # noqa: F401  (the start-up begins here)
    import jax

    from benchmarks import harness
    from benchmarks.manifest import Manifest
    from fedml_tpu.observability import Tracer, get_tracer, startup_report
    from fedml_tpu.observability.jaxmon import feed_tracer
    from fedml_tpu.observability.tracing import round_table

    # the caller's own jitted work (the family's data and weights) is on
    # the record too: without this the events flow from FedAvgAPI on
    feed_tracer()

    root = args.cpu_root or ROOT
    man = Manifest(root)
    entry = man.cell(args.workload)
    config = man.config(entry["config"])
    harness.cache_dir(root)
    if args.cold:
        jax.config.update("jax_compilation_cache_dir",
                          tempfile.mkdtemp(prefix="span_probe_cold_",
                                           dir="/tmp"))
    if args.cpu_root is None and jax.devices()[0].platform != "tpu":
        print("span_probe: needs a TPU, or --cpu_root", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    family = importlib.import_module(
        "benchmarks.families." + config["family"])
    tracer = get_tracer()
    # set-up as harness.run makes it, the probe's own steps on the record
    with tracer.span("caller.build"):
        cell = family.build(config, man.traffic(entry["traffic"]),
                            args.seed, man.reference(config))
    for _ in range(int(man.cell_file(args.workload)["check_rounds"])):
        cell.api.train_one_round()
        with tracer.span("caller.snapshot"):
            cell.snapshot()
    out = {"workload": args.workload, "seed": args.seed,
           "cold": bool(args.cold), "startup": startup_report(),
           "check_rounds": round_table(tracer),
           "caller": [[s.name, round((s.t1 - s.t0) / 1e6, 4)]
                      for s in tracer.finished_spans()
                      if s.name.startswith("caller.")]}
    out["recorder"] = _rounds(cell.api, args.rounds)
    out["exporting"] = _rounds(cell.api, args.traced, Tracer())
    if args.fold_contract:
        out["fold_contract"] = _fold_both_ways(cell.api, args.seed)
    path = os.path.join(ROOT, "chiprun_out", "span_probe")
    os.makedirs(path, exist_ok=True)
    name = args.workload + (".cold" if args.cold else "") + ".json"
    with open(os.path.join(path, name), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
