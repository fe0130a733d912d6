"""Spreads of a cell's metrics over sets of runs, as the bounds are set
from them.

    python3 benchmarks/tools/spread.py <set1.jsonl> [<set2.jsonl> ...]

Each file holds one result line (the harness's last line of standard
output) per run. A spread is the distance between the first and the third
quartile, as ``statistics.quantiles(values, n=4)`` gives them, as a share
of the median. Prints, per metric, each set's median and spread, the
widest spread (a bound over eight times it is too loose) and the mean of
the sets' spreads with each set's run farthest from its median left out
(a bound under twice that is too tight); a run's first line of a set
(which compiles) is kept, so leave it out of the file where it should not
count.
"""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    """The spread without the run farthest from the median."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return spread(rest)


def main(paths):
    sets = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    for name in names:
        rows = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if len(values) >= 3:
                rows.append((statistics.median(values), spread(values),
                             len(values), trimmed(values)))
        if rows:
            print(name, " ".join(f"median {m:.6g} spread {100 * s:.3f}% "
                                 f"(n={n})" for m, s, n, _ in rows),
                  f"widest {100 * max(r[1] for r in rows):.3f}%",
                  f"trimmed mean "
                  f"{100 * statistics.mean(r[3] for r in rows):.3f}%")
    wrong = [r for runs in sets for r in runs if not r["correct"]]
    print("runs", sum(len(s) for s in sets), "not correct", len(wrong))
    for runs in sets:
        for r in runs:
            print(" ", {c["name"]: f"{c['value']:.3g}"
                        for c in r.get("checks", [])})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
