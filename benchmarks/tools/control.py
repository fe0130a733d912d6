"""Read the control and the planted faults of a training cell on the chip.

    python3 benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--variants fp8,half_batch]

For each seed the plain reference follows the cell's check rounds once as
itself and once as each variant (the control: the nearest precision below
the configuration's; a fault: half of every batch left out), put in the
program's place, and the cell's own comparison (``compare.py``) reads the
numbers. No program, no window: training's readings need none. One JSON
line per seed and variant on standard output; ``PERF.md`` has the readings
the limits were set from.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=None)
    ap.add_argument("--root", default=None,
                    help="another checkout's root (the tests' toy cells)")
    args = ap.parse_args(argv)
    import importlib

    from benchmarks import compare, harness
    from benchmarks.manifest import ROOT, Manifest

    root = args.root or ROOT
    man = Manifest(root)
    entry = man.cell(args.workload)
    config = man.config(entry["config"])
    traffic = man.traffic(entry["traffic"])
    cell_file = man.cell_file(args.workload)
    harness.cache_dir(root)
    family = importlib.import_module("benchmarks.families."
                                     + config["family"])
    reference = man.reference(config)
    rounds = int(cell_file["check_rounds"])
    variants = (args.variants.split(",") if args.variants
                else [v for v in reference.VARIANTS if v != "f32"])
    for seed in (int(s) for s in args.seeds.split(",")):
        feed = family.feed_of(config, traffic, seed, rounds, reference)
        t0 = time.perf_counter()
        ref = reference.run_rounds(config, traffic, seed, rounds, feed)
        ref_s = time.perf_counter() - t0
        ref.pop("init", None)
        for variant in variants:
            t0 = time.perf_counter()
            alt = reference.run_rounds(config, traffic, seed, rounds, feed,
                                       variant=variant)
            alt.pop("init", None)
            checks = compare.training_checks(
                alt["loss"], alt["change_norms"], ref["loss"],
                ref["change_norms"], cell_file["limits"])
            print(json.dumps({
                "workload": args.workload, "seed": seed, "variant": variant,
                "correct": all(c["ok"] for c in checks),
                "checks": {c["name"]: [c["value"], c["limit"]]
                           for c in checks},
                "reference_s": ref_s,
                "variant_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
