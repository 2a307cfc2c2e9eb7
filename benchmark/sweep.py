"""The knee sweep: several rates in one process after one set-up.

Not a cell's run and never judged: it is made once, when a cell is defined,
to find the highest rate at which the queue does not grow through a window
(the knee). The cell then runs at about four fifths of it, a number written
into ``benchmark/cells/<cell>.json`` with the sweep's table in ``PERF.md``.
Between two rates the server is left to drain, so each rate starts empty.
"""

from __future__ import annotations

import json
import os

from benchmark import harness, samples


def waiting(rec: dict, t: float) -> int:
    """Requests due by ``t`` whose first token was not yet visible at ``t``."""
    return sum(
        1 for r in rec["requests"]
        if r["due"] <= t and not (r["stamps"] and r["stamps"][0] <= t)
    )


def row(rate: float, rec: dict) -> dict:
    t0, t1 = rec["window"]
    ttft = samples.ttft_s(rec)
    gaps = samples.gaps_s(rec)
    return {
        "rate_rps": rate,
        "due_in_window": sum(1 for r in rec["requests"] if t0 <= r["due"] <= t1),
        "first_tokens": len(ttft) - len(samples.overdue(rec)),
        "waiting_at_start": waiting(rec, t0),
        "waiting_at_mid": waiting(rec, (t0 + t1) / 2),
        "waiting_at_end": waiting(rec, t1),
        "overdue": len(samples.overdue(rec)),
        "ttft_p50_ms": samples.percentile(ttft, 50) * 1e3 if ttft else None,
        "ttft_p95_ms": samples.percentile(ttft, 95) * 1e3 if ttft else None,
        "itl_p95_ms": samples.percentile(gaps, 95) * 1e3 if gaps else None,
        "out_tok_s": samples.tokens_in_window(rec) / rec["seconds"],
        "rows_per_step": (
            sum(s["rows"] for s in samples.steps_in_window(rec))
            / max(len(samples.steps_in_window(rec)), 1)
        ),
        "compiles_in_window": rec["compiles_in_window"],
    }


def run(*, rates, cfg_file, block, traffic, devices, seed, seconds, out_dir,
        cell, attn: str = "kernel") -> list:
    session = harness.Session(
        cfg_file=cfg_file, block=block, traffic=traffic, devices=devices,
        seed=seed, out_dir=out_dir, attn=attn,
    )
    print("set-up by phase (s):", json.dumps(session.marks), flush=True)
    rows = []
    for rate in rates:
        rec = session.measure({"rate_rps": rate}, seconds)
        rows.append(row(rate, rec))
        print("sweep:", json.dumps(rows[-1]), flush=True)
        session.drain()
    fin = session.finish()
    print("reference:", json.dumps(fin["reference"]), "paths:",
          json.dumps(fin["paths"]), flush=True)
    with open(os.path.join(out_dir, f"{cell}.sweep.json"), "w") as f:
        json.dump({"rows": rows, "finish": fin}, f, default=float)
    return rows
