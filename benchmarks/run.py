"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell on the machine it is started on: it loads, warms
up, measures, checks what the timed path produced against the plain
reference, prints one JSON object as the last line of its standard output,
and exits. It fails (no result line, exit code other than 0) when JAX
finds no TPU or fewer chips than the cell asks for, and where the program
(``fedml_tpu``) is not beside it. ``harness.py`` says what a run does.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks import harness

    code, _ = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=_T_START)
    return code


if __name__ == "__main__":
    sys.exit(main())
