"""From a run's records to samples: the arithmetic every metric reader shares.

A run's records (``harness.run_cell``) hold, per request, the due time, the
submit time, the server's own stamps and the time each token became visible;
per step, the program's step record stamped with the harness's clock; and
the window ``[t0, t1]``. All times are ``time.perf_counter()`` seconds of one
process. A sample is an EVENT INSIDE THE WINDOW: a first token that became
visible in it, a gap that ended in it, a step that ended in it.
"""

from __future__ import annotations

import math

ADMIT_BUCKET_MIN = 8  # the server's smallest admit bucket; the rest double


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation, on plain floats. One
    definition for every latency metric of the benchmark."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def decode_step_s(rec: dict) -> list:
    """Device time of each decode microstep in the trace: every
    ``serve_chunk`` execution ÷ its cycles (one per stage of the ring;
    ``chunk_cycles`` is 1), over executions and chips."""
    tr = rec.get("trace")
    if not tr or "serve_chunk" not in tr["modules"]:
        return []
    return [d / rec["chips"] for chip in tr["modules"]["serve_chunk"]
            for d in chip]


def in_window(rec: dict, t: float) -> bool:
    t0, t1 = rec["window"]
    return t is not None and t0 <= t <= t1


def overdue(rec: dict) -> list:
    """Requests a healthy system would have answered: due more than
    ``tail_s`` before the window's end and still without a first token (or
    failed). Each counts as failed and as the worst time to first token."""
    t0, t1 = rec["window"]
    late = []
    for r in rec["requests"]:
        if r["due"] < t1 - rec["tail_s"] and not r["stamps"]:
            late.append(t1 - r["due"])
    return late


def ttft_s(rec: dict) -> list:
    """First token visible − due time, for every first token that became
    visible in the window, plus the overdue requests at their worst."""
    out = [
        r["stamps"][0] - r["due"] for r in rec["requests"]
        if r["stamps"] and in_window(rec, r["stamps"][0])
    ]
    return out + overdue(rec)


def gaps_s(rec: dict) -> list:
    """Gaps between successive visible tokens of one request, pooled over
    requests, for every gap that ended in the window. Tokens that became
    visible in the same step are 0 apart, and are counted."""
    out = []
    for r in rec["requests"]:
        s = r["stamps"]
        out.extend(b - a for a, b in zip(s, s[1:]) if in_window(rec, b))
    return out


def tokens_in_window(rec: dict) -> int:
    return sum(
        1 for r in rec["requests"] for s in r["stamps"] if in_window(rec, s)
    )


def steps_in_window(rec: dict, lo=None, hi=None) -> list:
    t0, t1 = rec["window"]
    lo, hi = (t0 if lo is None else lo), (t1 if hi is None else hi)
    return [s for s in rec["steps"] if lo <= s["t"] <= hi]


def longest_pause_s(rec: dict) -> float:
    """The longest time in the window in which the pump was not inside
    ``server.step()``. It sleeps 0.5 ms after an idle step and nothing after
    a productive one, so more than some tenths of a second here means the
    whole process stood still (PERF.md section 6: the machine can freeze
    every process for seconds), and the run's numbers carry that."""
    marks = [m for m in rec["pump_marks"] if in_window(rec, m[1])]
    return max((b[0] - a[1] for a, b in zip(marks, marks[1:])), default=0.0)


def bucket(n: int) -> int:
    b = ADMIT_BUCKET_MIN
    while b < n:
        b *= 2
    return b


def admissions(rec: dict, lo=None, hi=None) -> list:
    """Requests grouped by the admission that started them (the server
    stamps the rows of one admission within microseconds of each other).
    Each group is a list of request records; groups started in [lo, hi]."""
    t0, t1 = rec["window"]
    lo, hi = (t0 if lo is None else lo), (t1 if hi is None else hi)
    started = sorted(
        (r for r in rec["requests"]
         if r["server_started_at"] is not None
         and lo <= r["server_started_at"] <= hi),
        key=lambda r: r["server_started_at"],
    )
    groups: list = []
    for r in started:
        if groups and (
            r["server_started_at"] - groups[-1][-1]["server_started_at"] < 1e-3
            and bucket(r["prompt_len"]) == bucket(groups[-1][-1]["prompt_len"])
        ):
            groups[-1].append(r)
        else:
            groups.append([r])
    return groups
