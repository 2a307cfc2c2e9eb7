#!/usr/bin/env python3
"""How widely a cell's runs spread: the arithmetic behind a bound.

    python3 benchmark/spread.py chiprun_out/sets <cell>

Reads the result lines of two sets of runs (``<cell>.A.<seed>.log`` and
``<cell>.B.<seed>.log``, as ``tests/sets.sh`` leaves them) and prints, per
end-to-end metric (and per statistic of ``tests/gap_stats.py``, where
``sets.sh`` left one beside a log), each set's median and its spread — the
distance between the first and third quartile (``statistics.quantiles(values,
n=4)``) as a share of the median — the wider of the two, and each again
without the set's run farthest from the median. A bound is about five times
the widest spread over the cells, and never under 1%; a metric is judged in a
cell only where both sets spread by at most half its bound.
"""

import glob
import json
import os
import statistics
import sys


def last_json(path: str):
    """A run's result line; its metrics joined by what ``tests/gap_stats.py``
    read from the same run's records (``<log>.gaps`` beside the log), so that
    a statistic nobody is judged on is tabulated beside those that are."""
    with open(path) as f:
        lines = [l for l in f.read().splitlines() if l.startswith("{")]
    if not lines:
        return None
    run = json.loads(lines[-1])
    side = path[: -len(".log")] + ".gaps"
    if os.path.exists(side):
        with open(side) as f:
            extra = json.loads(f.read().split("gaps:", 1)[1])
        for name, value in extra.items():
            run["metrics"].setdefault(name, {"value": value})
    return run


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values: list) -> list:
    """Without the run farthest from the median: what the driver's test of
    tightness looks at, so one far-off run in a set does no harm."""
    m = statistics.median(values)
    far = max(values, key=lambda v: abs(v - m))
    rest = list(values)
    rest.remove(far)
    return rest


def main(folder: str, cell: str) -> None:
    sets = {}
    for s in ("A", "B"):
        runs = [last_json(p) for p in
                sorted(glob.glob(os.path.join(folder, f"{cell}.{s}.*.log")))]
        sets[s] = [r for r in runs if r]
        bad = [r for r in sets[s] if not r["correct"] or r["failed"]]
        print(f"set {s}: {len(sets[s])} runs, {len(bad)} not correct or with failures")
    names = sets["A"][0]["metrics"].keys()
    for name in names:
        row = {}
        for s, runs in sets.items():
            vals = [r["metrics"][name]["value"] for r in runs]
            row[s] = (statistics.median(vals), spread(vals), vals)
        wider = max(row["A"][1], row["B"][1])
        shift = row["B"][0] / row["A"][0] - 1.0
        trim = [spread(trimmed(row[s][2])) for s in ("A", "B")]
        print(f"{name}: median A {row['A'][0]:.4f} B {row['B'][0]:.4f} "
              f"(B vs A {shift:+.2%}); spread A {row['A'][1]:.3%} "
              f"B {row['B'][1]:.3%}; 5 x wider = {5 * wider:.2%}; without "
              f"each set's farthest run A {trim[0]:.3%} B {trim[1]:.3%}")
        for s in sets:
            print(f"    {s}: " + " ".join(f"{v:.3f}" for v in row[s][2]))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
