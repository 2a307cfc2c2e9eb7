"""A token's path → numbers: the step records' stamps of each program's
enqueue and each log's landing (``llm_sharding_tpu/obs/stepline.py``), cut to
the window.

The harness keeps ``to_dict()`` of every productive step in ``rec["steps"]``,
traced or not. A step's ``t0`` is ``time.perf_counter()`` at its begin — the
harness's own clock — and every other stamp an offset from it. What counts
as a host-bound step and as a step that held work is defined once, in that
module's docstring; ``llm_sharding_tpu/obs/report.token_path`` (the
operator's ``step-report``, which reads a run's file in ``benchmark/out/``
as it is) reduces the same records by the same rules. A program whose
records lack the stamps (a parent commit), or a window with nothing to read,
reads as None in every metric.
"""

from typing import Optional

from benchmark import samples


def _steps(rec: dict) -> Optional[list]:
    steps = samples.steps_in_window(rec)
    if not steps or not all(
        "logs" in s and "dispatches" in s and "end" in s for s in steps
    ):
        return None
    return steps


def emit_lags_s(rec: dict) -> Optional[list]:
    """For every log that carried tokens: from its landing on the host to
    the end of the step that applied it — when the harness stamps them."""
    steps = _steps(rec)
    if steps is None:
        return None
    return [
        s["end"] - log["landed"] for s in steps for log in s["logs"]
        if log["tokens"] and log["landed"] is not None
    ] or None


def landing_gaps_s(rec: dict) -> Optional[list]:
    """Between the landings of two decode logs whose programs stood next to
    each other in the device's queue (no admission, prefill chunk or verify
    between them: the programs are numbered), both known to the moment (the
    host was waiting when they landed): the device's pace as the host sees
    it."""
    steps = _steps(rec)
    if steps is None:
        return None
    logs = [(s["t0"], log) for s in steps for log in s["logs"]]
    return [
        (tb + b["landed"]) - (ta + a["landed"])
        for (ta, a), (tb, b) in zip(logs, logs[1:])
        if a["kind"] == b["kind"] == "chunk" and b["n"] == a["n"] + 1
        and a["exact"] and b["exact"]
        and a["landed"] is not None and b["landed"] is not None
    ] or None


def host_bound_pct(rec: dict) -> Optional[float]:
    """Of the steps that applied a decode log, those that did not have to
    wait for it: the device had finished before the host came."""
    steps = _steps(rec)
    if steps is None:
        return None
    waited = [
        all(log["waited"] for log in s["logs"] if log["kind"] == "chunk")
        for s in steps if any(log["kind"] == "chunk" for log in s["logs"])
    ]
    return 100.0 * waited.count(False) / len(waited) if waited else None


def queue_empty_pct(rec: dict, bound: str) -> Optional[float]:
    """Of the time the server held work (the steps that dispatched, applied
    or ended with rows, queue or logs), the part in which the host had left
    the device's queue empty, on the host's clock: ``bound`` ``"lo"`` from
    the stamp that found the previous program landed, ``"hi"`` from the last
    poll that saw the device busy."""
    steps = _steps(rec)
    if steps is None:
        return None
    work = [
        s for s in steps
        if s["dispatches"] or s["logs"] or s["rows"] or s["queued"]
        or s["pending"]
    ]
    work_s = sum(s["wall_s"] for s in work)
    if not work_s:
        return None
    key = f"starved_{bound}_s"
    empty_s = sum(d[key] for s in work for d in s["dispatches"])
    return 100.0 * empty_s / work_s
