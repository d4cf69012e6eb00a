"""How widely a set of runs spreads, read the two ways a check reads it,
and the bound the live cell's rule gives (PERF.md section 2).

    python3 -m chipbench.spread <runs.jsonl> [...]

Each file is one set: a line per run, a JSON object whose ``result`` (or
the line itself) is a result line of ``chipbench.run``. Prints, per
end-to-end metric, the set's median, its spread as the check's tightness
test reads it (``trimmed_range``) and as its looseness test and the
contract's rule of five read it (``quartile_spread``), both also as a
share of the median.
"""

import json
import math
import statistics
import sys


def trimmed_range(values):
    """The range of a set's values, leaving out the one run farthest from
    the set's median where that narrows it: how the ledger's notes state a
    spread ("A spread leaves out the run farthest from its median")."""
    rest = list(values)
    if len(rest) >= 3:
        med = statistics.median(rest)
        rest.remove(max(rest, key=lambda v: abs(v - med)))
    return max(rest) - min(rest)


def quartile_spread(values):
    """Third quartile less first, as ``statistics.quantiles(values, n=4)``
    gives them."""
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def bound_from(relative_spreads, factor=3.0, step=0.005, cap=0.06):
    """The rule: ``factor`` times the widest relative spread, rounded up
    to the next ``step``, at most ``cap``."""
    want = factor * max(relative_spreads)
    return min(cap, math.ceil(round(want / step, 9)) * step)


def read_set(path):
    """{metric: [values]} of one file's runs, and the runs themselves."""
    runs = []
    with open(path) as f:
        for line in f:
            if line.strip():
                row = json.loads(line)
                runs.append(row.get("result", row))
    by_metric = {}
    for run in runs:
        for name, m in run["metrics"].items():
            by_metric.setdefault(name, []).append(m["value"])
    return by_metric, runs


def main(argv):
    for path in argv:
        by_metric, runs = read_set(path)
        bad = sum(1 for r in runs if not r["correct"] or r["failed"])
        print(f"{path}: {len(runs)} runs, {bad} not correct or with failed frames")
        for name, values in by_metric.items():
            med = statistics.median(values)
            tr, qs = trimmed_range(values), quartile_spread(values)
            share = (lambda x: f" ({x / med:.4%})") if med else (lambda x: "")  # a count may be 0
            print(f"  {name}: median {med:.6g}, trimmed range {tr:.6g}{share(tr)}, "
                  f"quartile spread {qs:.6g}{share(qs)}, values "
                  + " ".join(f"{v:.6g}" for v in values))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
