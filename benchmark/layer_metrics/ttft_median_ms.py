"""Scheduler: median time to first token — first token visible − DUE time
(open loop) — over the first tokens that became visible in the window, ms.
Overdue requests count at their worst (samples.overdue). Not judged: a
window at this system's knee holds a handful of requests."""
from benchmark import samples


def read(rec):
    s = samples.ttft_s(rec)
    return samples.percentile(s, 50) * 1e3 if s else None
