"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction every
PR's device metrics come from.

The JAX profiler writes one plane per chip (``/device:TPU:<n>``) with a line
of whole programs (``XLA Modules``: one event per execution of a jitted
function, named ``jit_<function>(<fingerprint>)``) and a line of single
operations (``XLA Ops``), and a host plane whose lines are threads and hold
the harness's own ``bench.step`` / ``bench.submit`` annotations. Times are
nanoseconds on one clock.

- the traced window is the harness's own: from its ``bench.traced`` stamp
  for as long as its clock says. The profiler starts recording before the
  stamp and stops after the harness's last look at the clock, so every
  device interval is cut to that window first (``clip``); a chip that never
  rests would otherwise read busier than the window is long.
- busy: the union of the intervals in which an operation ran on a chip;
  idle share = 1 − busy ÷ traced window. Per chip, and the mean.
- per module: executions and their durations, by function name.
- collectives: operations whose name says so, on the line of operations or
  on the line of asynchronous start→done spans; ``exposed`` is the part of
  their time during which no other operation ran on that chip.
- breakdown: the operations that took most time of their own (nested
  operations taken out, mean over chips), and the idle time of the first
  chip over the whole traced window, summed by whether a request was in the
  server at all and, if one was, by what the harness's pump was doing.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start→done spans of copies and collectives
COLLECTIVE = re.compile(
    r"(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|collective-broadcast|^send|^recv)"
)
ANNOTATIONS = ("bench.step", "bench.submit")
TRACED_MARK = "bench.traced"  # wraps the harness's stamp of the trace's start
TOP = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def module_name(event_name: str) -> str:
    """``jit_serve_chunk(1234…)`` → ``serve_chunk``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO line; ``%fusion.7 =
    bf16[…] fusion(…)`` → ``fusion.7``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list) -> dict:
    """Seconds per operation name with the time of nested operations taken
    out (a ``while`` holds the operations of its body as children)."""
    out: dict = collections.defaultdict(float)
    stack: list = []  # [name, end, self_ns]
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out[done[0]] += done[2] * 1e-9
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    for done in stack:
        out[done[0]] += done[2] * 1e-9
    return out


def union(intervals: list) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: list) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a: list, b: list) -> list:
    """The parts of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(planes: dict, window: tuple) -> dict:
    """``planes`` with every device operation and host annotation cut to
    ``window`` = (start, end) in ns on the trace's clock, and of the whole
    programs those that started inside it (an execution keeps its length)."""
    lo, hi = window
    cut = lambda events: [(n, max(a, lo), min(b, hi)) for n, a, b in events
                          if b > lo and a < hi]
    return {
        "devices": {
            chip_id: {
                "modules": [e for e in chip["modules"] if lo <= e[1] < hi],
                "ops": cut(chip["ops"]),
                "async": cut(chip.get("async", [])),
            } for chip_id, chip in planes["devices"].items()
        },
        "host": {
            name: [(max(a, lo), min(b, hi)) for a, b in spans
                   if b > lo and a < hi] if name in ANNOTATIONS else spans
            for name, spans in planes["host"].items()
        },
    }


def traced_window(planes: dict, window_s: float) -> Optional[tuple]:
    """The harness's traced window on the trace's clock: from its
    ``bench.traced`` stamp (without one, from the first device event)
    for ``window_s`` seconds. None for a trace that holds neither."""
    mark = planes["host"].get(TRACED_MARK)
    starts = [mark[0][0]] if mark else [
        a for chip in planes["devices"].values()
        for _, a, _ in chip["ops"] + chip["modules"]
    ]
    if not starts:
        return None
    return (min(starts), min(starts) + int(round(window_s * 1e9)))


def read_planes(path: str) -> dict:
    """``{"devices": {chip: {"modules": [...], "ops": [...]}}, "host":
    {annotation: [(start, end)]}}`` with times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host: dict = collections.defaultdict(list)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops",
                       ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                short = (lambda n: n) if key == "modules" else op_name
                chip[key] = [
                    (short(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
            devices[int(m.group(1))] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS or e.name == TRACED_MARK:
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns)
                        )
    return {"devices": devices, "host": dict(host)}


def reduce_planes(planes: dict, window_s: float,
                  in_flight: Optional[list] = None) -> dict:
    """The numbers, from what ``read_planes`` gave. ``window_s`` is the
    traced window's length by the harness's clock, and everything is cut to
    that window first; ``in_flight`` is for ``idle_gaps``."""
    window = traced_window(planes, window_s)
    if window is not None:
        planes = clip(planes, window)
    chips = []
    op_time: dict = collections.defaultdict(float)
    modules: dict = collections.defaultdict(list)
    for chip_id in sorted(planes["devices"]):
        chip = planes["devices"][chip_id]
        ops = chip["ops"]
        busy = union([(a, b) for _, a, b in ops])
        coll = union([(a, b) for n, a, b in ops + chip.get("async", [])
                      if COLLECTIVE.search(n)])
        other = union([(a, b) for n, a, b in ops if not COLLECTIVE.search(n)])
        for n, sec in self_times(ops).items():
            op_time[n] += sec
        per_module: dict = collections.defaultdict(list)
        for n, a, b in chip["modules"]:
            per_module[module_name(n)].append((b - a) * 1e-9)
        for name, durs in per_module.items():
            modules[name].append(durs)
        busy_s = total(busy) * 1e-9
        chips.append({
            "chip": chip_id,
            "busy_s": busy_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "collective_s": total(coll) * 1e-9,
            "collective_exposed_s": total(subtract(coll, other)) * 1e-9,
            "ops": len(ops),
        })
    n = max(len(chips), 1)
    mean = lambda key: sum(c[key] for c in chips) / n
    out = {
        "window_s": window_s,
        "busy_s": mean("busy_s") if chips else 0.0,
        "idle_pct": mean("idle_pct") if chips else None,
        "collective_s": mean("collective_s") if chips else 0.0,
        "collective_exposed_s": mean("collective_exposed_s") if chips else 0.0,
        "chips": chips,
        # per module: one list of execution times per chip that ran it
        "modules": dict(modules),
        "breakdown": {
            "device_ops": [
                [name, s / n] for name, s in
                sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
            ],
            "idle_gaps": idle_gaps(planes, in_flight, window),
        },
    }
    return out


def idle_gaps(planes: dict, in_flight: Optional[list] = None,
              window: Optional[tuple] = None) -> list:
    """The first chip's idle time over the whole traced window — the time
    before its first operation and after its last included — summed by what
    was going on: no request in the server (``in_flight``: merged intervals
    on the trace's clock in which one was; None = not known), else inside
    ``bench.step``, inside ``bench.submit``, or between steps. Seconds.
    ``window`` is the traced window on the trace's clock; without it, the
    span of what the trace holds."""
    if not planes["devices"]:
        return []
    first = planes["devices"][min(planes["devices"])]
    busy = union([(a, b) for _, a, b in first["ops"]])
    # the pump never rests, so its annotations span the traced window
    marks = [iv for name in ANNOTATIONS for iv in planes["host"].get(name, [])]
    edges = marks + busy
    if window is not None:
        window = [tuple(window)]
    elif edges:
        window = [(min(a for a, _ in edges), max(b for _, b in edges))]
    else:
        return []
    gaps = subtract(window, busy)
    if not gaps:
        return []
    out = []
    left = gaps
    if in_flight is not None:
        left = subtract(gaps, subtract(window, in_flight))
        out.append(["no request in flight", (total(gaps) - total(left)) * 1e-9])
    for name in ANNOTATIONS:
        spans = union(planes["host"].get(name, []))
        rest = subtract(left, spans)
        out.append([name, (total(left) - total(rest)) * 1e-9])
        left = rest
    out.append(["between steps", total(left) * 1e-9])
    longest = max(gaps, key=lambda g: g[1] - g[0])
    out.append(["longest single gap", (longest[1] - longest[0]) * 1e-9])
    return sorted(out, key=lambda kv: -kv[1])[:TOP]


def in_flight_on_trace_clock(planes: dict, records: dict) -> Optional[list]:
    """When some request was in the server (submitted, not yet finished), as
    merged intervals on the trace's clock. The harness stamps the trace's
    start on its own clock inside a ``bench.traced`` annotation, which ties
    the two clocks."""
    mark = planes["host"].get(TRACED_MARK)
    if not mark or not records.get("traced"):
        return None
    ta, tb = records["traced"]
    to_ns = lambda t: mark[0][0] + (t - ta) * 1e9
    return union([
        (to_ns(r["submitted"]),
         to_ns(r["finished"] if r["finished"] is not None else tb + 60.0))
        for r in records["requests"] if r["submitted"] is not None
    ])


def reduce_dir(trace_dir: str, records: dict) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler left no .xplane.pb under {trace_dir}")
    ta, tb = records["traced"]
    planes = read_planes(path)
    out = reduce_planes(
        planes, tb - ta, in_flight_on_trace_clock(planes, records)
    )
    out["xplane_bytes"] = os.path.getsize(path)
    return out
