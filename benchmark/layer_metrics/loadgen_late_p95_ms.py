"""Load generator: 95th percentile of (submit time − due time) over requests
due in the window, ms. A starved generator must not read as a fast server."""
from benchmark import samples


def read(rec):
    s = [r["submitted"] - r["due"] for r in rec["requests"]
         if samples.in_window(rec, r["due"]) and r["submitted"] is not None]
    return samples.percentile(s, 95) * 1e3 if s else None
