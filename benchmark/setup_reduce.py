"""Set-up's account → numbers: the program's set-up ledger
(``llm_sharding_tpu/obs/setupline.py``), cut to this run's set-up.

The readers run in the run's own process (``run.py`` hands them to
``harness.run_cell``), so the ledger is taken from the program's module, not
from a file. Set-up runs from the process's start, ``window[0] - setup_s``, to
the window's start; a span counts when it began and ended in between. The
cut ledger is kept in ``rec["setup"]``: the run's file in ``benchmark/out/``
then holds the whole account. A program without the ledger (a parent commit),
or a ledger with nothing in the cut, reads as None in every metric.
"""

from typing import Optional

HOST = ("setup.engine.host_pull", "setup.engine.stack")
PUT = ("setup.engine.put", "setup.engine.quant")
SERVER = ("setup.server",)
COMPILE = "setup.compile"
FIRST_RUN = "setup.first_run"


def ledger(rec: dict) -> Optional[list]:
    if "setup" not in rec:
        rec["setup"] = _cut(rec)
    return rec["setup"]


def _cut(rec: dict) -> Optional[list]:
    try:
        from llm_sharding_tpu.obs.setupline import SETUP
    except ImportError:
        return None
    t1 = rec["window"][0]
    t0 = t1 - rec["setup_s"]
    spans = [s for s in SETUP.snapshot()
             if s["end"] is not None and t0 <= s["start"] and s["end"] <= t1]
    return spans or None


def _dur(span: dict) -> float:
    return span["end"] - span["start"]


def seconds(rec: dict, names: tuple, less: tuple = ()) -> Optional[float]:
    """Summed durations of the spans called one of ``names``, less those of
    their children called one of ``less`` (the head's host staging runs
    inside the put it overlaps: it is the host's seconds, not the put's)."""
    spans = ledger(rec)
    if spans is None:
        return None
    chosen = {s["id"] for s in spans if s["name"] in names}
    return (
        sum(_dur(s) for s in spans if s["id"] in chosen)
        - sum(_dur(s) for s in spans
              if s["parent"] in chosen and s["name"] in less)
    )


def compiles(rec: dict) -> Optional[list]:
    spans = ledger(rec)
    if spans is None:
        return None
    return [s for s in spans if s["name"] == COMPILE]


def built_seconds(rec: dict, from_cache: bool) -> Optional[float]:
    """Tracing + lowering + the cache's load (``from_cache``) or the
    backend's compile, over the programs that were / were not a hit."""
    spans = compiles(rec)
    if spans is None:
        return None
    last = "cache_load_s" if from_cache else "backend_s"
    return sum(s["trace_s"] + s["lower_s"] + s[last] for s in spans
               if (s["cache"] == "hit") == from_cache)


def first_run_seconds(rec: dict) -> Optional[float]:
    """First runs, less the compiles inside them."""
    return seconds(rec, (FIRST_RUN,), less=(COMPILE,))


def unaccounted_pct(rec: dict) -> Optional[float]:
    """Of ``setup_s``, what neither the harness's own phases (the weights it
    makes, the traffic's ramp) nor any top-level span of the program covers."""
    spans = ledger(rec)
    if spans is None:
        return None
    ids = {s["id"] for s in spans}
    top = sorted((s["start"], s["end"]) for s in spans
                 if s["parent"] not in ids)
    covered, reach = 0.0, float("-inf")
    for start, end in top:  # the union's length
        covered += max(0.0, end - max(start, reach))
        reach = max(reach, end)
    own = rec["marks"]["weights_s"] + float(rec["traffic"].get("ramp_s", 0.0))
    return 100.0 * (rec["setup_s"] - own - covered) / rec["setup_s"]
