"""Scheduler: 95th percentile of the server's own queue wait
(Request.started_at − submitted_at) over requests admitted in the window, ms."""
from benchmark import samples


def read(rec):
    s = [r["server_started_at"] - r["server_submitted_at"]
         for r in rec["requests"]
         if samples.in_window(rec, r["server_started_at"])]
    return samples.percentile(s, 95) * 1e3 if s else None
