#!/usr/bin/env python3
"""Which statistic of a run holds still: tokens per second and the 50th,
90th, 95th and 99th percentile gap of the NEWEST record of a cell.

    python3 benchmark/tests/gap_stats.py benchmark/out <cell>

Prints one line, ``gaps: {...}``; ``sets.sh`` appends it to each run's log and
``spread.py`` tabulates it beside the run's own metrics. It reads the run's
records and touches neither jax nor the chip.
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import samples  # noqa: E402

PERCENTILES = (50, 90, 95, 99)


def stats(rec: dict) -> dict:
    gaps = samples.gaps_s(rec)
    out = {"out_tok_s": samples.tokens_in_window(rec) / rec["seconds"],
           "gaps": len(gaps)}
    for q in PERCENTILES:
        out[f"gap_p{q}_ms"] = samples.percentile(gaps, q) * 1e3
    return out


if __name__ == "__main__":
    folder, cell = sys.argv[1], sys.argv[2]
    newest = max(glob.glob(os.path.join(folder, f"{cell}.seed*.json")),
                 key=os.path.getmtime)
    with open(newest) as f:
        print("gaps:", json.dumps(stats(json.load(f))))
