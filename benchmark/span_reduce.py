"""From the same ``.xplane.pb`` to the program's own names: what the program
under test wrote into the profiler's trace, reduced once per run.

``trace_reduce.py`` says how long the chips were busy, under XLA's numbering
of the operations (``fusion.207``), with the idle time put down to the
harness's own annotations. Since PR 24 the program names its work itself,
and this reduction reads those names:

- **scopes.** Every device operation's *event metadata* carries ``tf_op``,
  the ``jax.named_scope`` path it was traced under
  (``jit(serve_chunk)/…/attn/kv_layout/transpose:``), ``program_id`` (which
  ties it to its ``XLA Modules`` event, ``jit_serve_chunk(<id>)``) and
  ``bytes_accessed``. ``jax.profiler.ProfileData`` does not surface
  metadata stats, so the file is read here by a plain walk over the
  protobuf wire format (``XSpace`` → ``XPlane`` → ``XLine`` → ``XEvent``;
  nothing imported but the standard library). An operation's scope is the
  innermost word of the program's vocabulary
  (``llm_sharding_tpu.obs.stepline.SCOPES``) on its path; a fusion carries
  the path XLA gave it (its root's); no word → ``unscoped``. Own time per
  (module, scope): nested operations taken out, everything cut to the
  harness's stamped window — by ``trace_reduce``'s ``clip``,
  ``self_times``, ``union``, ``subtract``, imported, not copied.
- **annotations.** The server's step profiler writes ``serve.step`` (stats
  ``step_num``, ``rows``, ``queued``, ``pending`` as the step began; only
  while it holds work, plus the one step after), ``serve.<phase>``,
  ``serve.blocked`` and ``serve.prefill`` (``rows``, ``prompt_tokens``,
  ``positions``) on the pump's thread.
- **idle, by work.** Chip 0's idle time in the window, split by whether the
  server held work (a run of ``serve.step``s that began with work) and,
  inside work, by the innermost ``serve.<phase>`` / ``serve.blocked`` the
  pump was in, in a step but in no phase, or between steps. (The profiler
  records an annotation when it ends: the step in progress at an edge of
  the trace is not in it, see ``work_intervals``.)

``spans(rec)`` is what the readers under ``layer_metrics/`` call: the first
call reduces the run's trace and stores the result under ``rec["spans"]``
(so the run's records file keeps the per-scope table and the idle split),
later calls return it. It is None — and every reader then reports nothing —
when the run was not traced or the program has no vocabulary (a commit
before PR 24): nothing here raises into a run.
"""

from __future__ import annotations

import collections
import functools
import os
import re
import struct
import sys
import time
from typing import Optional

from benchmark import trace_reduce
from benchmark.trace_reduce import clip, self_times, subtract, total, union

HERE = os.path.dirname(os.path.abspath(__file__))

DECODE_MODULE = "serve_chunk"
PREFILL_MODULES = ("serve_admit", "serve_prefill_chunk", "serve_admit_finish")
UNSCOPED = "unscoped"
STEP, BLOCKED, PREFILL = "serve.step", "serve.blocked", "serve.prefill"
BETWEEN_STEPS = "between steps"  # or in a step that the trace's edge cut
TOP_OPS = 12
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def program_scopes() -> Optional[tuple]:
    """The program's scope vocabulary, or None for a program without one."""
    from llm_sharding_tpu.obs import stepline

    return getattr(stepline, "SCOPES", None)


# ------------------------------------------------------------ the wire walk

def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """``(field number, value)`` of one message: an int for a varint, the
    ``(start, end)`` of a length-delimited value, raw bytes for fixed ones."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = buf[i:i + 8]
            i += 8
        elif wire == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, v


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span: tuple, stat_names: dict) -> tuple:
    """One ``XStat`` → ``(name, value)``; a ``ref_value`` is resolved."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(key, key), value


def _map_entry(buf: bytes, span: tuple) -> tuple:
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf: bytes, span: tuple) -> dict:
    """One ``XPlane``: its name, its lines (unparsed), its metadata."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(buf, entry)
        for f, v in _fields(buf, *value):
            if f == 2:
                stat_names[key] = _text(buf, v)
    events = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        meta = {"name": "", "stats": {}}
        for f, v in _fields(buf, *value):
            if f == 2:
                meta["name"] = _text(buf, v)
            elif f == 5:
                k, val = _stat(buf, v, stat_names)
                meta["stats"][k] = val
        events[key] = meta
    return {"name": name, "lines": lines, "event_meta": events,
            "stat_names": stat_names}


def _line(buf: bytes, span: tuple) -> tuple:
    """One ``XLine`` → ``(name, timestamp ns, [its events, unparsed])``."""
    name, t0, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    return name, t0, events


def _events(buf: bytes, events: list, t0: int, want=None,
            stat_names=None) -> list:
    """A line's ``XEvent``s → ``[(metadata id, start ns, end ns, stats)]``.
    ``want``: keep only events of these metadata ids and read their stats."""
    out = []
    for i, end in events:
        meta = offset = dur = 0
        stats = None
        while i < end:  # _fields, unrolled: this loop runs per operation
            tag = buf[i]
            i += 1
            if tag == 0x08:
                meta, i = _varint(buf, i)
                if want is not None and meta not in want:
                    break
            elif tag == 0x10:
                offset, i = _varint(buf, i)
            elif tag == 0x18:
                dur, i = _varint(buf, i)
            elif tag & 7 == 2:
                n, i = _varint(buf, i)
                if tag == 0x22 and want is not None:
                    k, val = _stat(buf, (i, i + n), stat_names)
                    stats = stats or {}
                    stats[k] = val
                i += n
            elif tag & 7 == 0:
                _, i = _varint(buf, i)
            else:
                raise ValueError(f"wire type {tag & 7} in an XEvent")
        else:
            if want is None or meta in want:
                start = t0 + offset * 1e-3
                out.append((meta, start, start + dur * 1e-3, stats))
    return out


@functools.lru_cache(maxsize=1)
def read_xspace(path: str) -> dict:
    """The trace as ``trace_reduce`` shapes it (``devices``, ``host``; an
    operation is named by its metadata id) plus ``op_meta`` (per chip, per
    metadata id: ``name``, ``tf_op``, ``bytes_accessed``, ``module``) and
    ``spans`` (``[(thread, name, start, end, stats)]``, the ``serve.*``
    annotations). Times in ns on the trace's clock."""
    with open(path, "rb") as f:
        buf = f.read()
    devices, op_meta, host, spans = {}, {}, collections.defaultdict(list), []
    for f_, span in _fields(buf, 0, len(buf)):
        if f_ != 1:
            continue
        plane = _plane(buf, span)
        m = trace_reduce.DEVICE_PLANE.match(plane["name"])
        if m:
            chip = {"modules": [], "ops": [], "async": []}
            for ln in plane["lines"]:
                name, t0, events = _line(buf, ln)
                if name == trace_reduce.MODULES_LINE:
                    chip["modules"] = [
                        (plane["event_meta"][e[0]]["name"], e[1], e[2])
                        for e in _events(buf, events, t0)
                    ]
                elif name == trace_reduce.OPS_LINE:
                    chip["ops"] = [e[:3] for e in _events(buf, events, t0)]
            programs = {}
            for mod_name, _, _ in chip["modules"]:
                pid = _PROGRAM_ID.search(mod_name)
                if pid:
                    programs[int(pid.group(1))] = trace_reduce.module_name(
                        mod_name)
            metas = {}
            for mid, meta in plane["event_meta"].items():
                st = meta["stats"]
                metas[mid] = {
                    "name": trace_reduce.op_name(meta["name"]),
                    "tf_op": st.get("tf_op") or "",
                    "bytes_accessed": st.get("bytes_accessed") or 0,
                    "module": programs.get(st.get("program_id")),
                }
            devices[int(m.group(1))] = chip
            op_meta[int(m.group(1))] = metas
        elif plane["name"].startswith("/host:"):
            want = {mid: meta["name"]
                    for mid, meta in plane["event_meta"].items()
                    if meta["name"].startswith("serve.")
                    or meta["name"] == trace_reduce.TRACED_MARK}
            if not want:
                continue
            for ln in plane["lines"]:
                thread, t0, events = _line(buf, ln)
                for mid, a, b, stats in _events(
                        buf, events, t0, want, plane["stat_names"]):
                    if want[mid] == trace_reduce.TRACED_MARK:
                        host[want[mid]].append((a, b))
                    else:
                        spans.append((thread, want[mid], a, b, stats or {}))
    return {"devices": devices, "host": dict(host), "op_meta": op_meta,
            "spans": sorted(spans, key=lambda s: (s[2], -s[3]))}


# ------------------------------------------------------------- the reduction

def scope_of(tf_op: str, vocab) -> str:
    """The innermost word of ``vocab`` on an operation's scope path. The
    path's last component is the primitive, not a scope; a component may be
    wrapped by a transform (``vmap(attn)``)."""
    for part in reversed(tf_op.split("/")[:-1]):
        for word in _WORD.findall(part):
            if word in vocab:
                return word
    return UNSCOPED


def intersect(a: list, b: list) -> list:
    """The parts of merged intervals ``a`` that merged ``b`` covers."""
    return subtract(a, subtract(a, b))


def own_intervals(spans: list) -> dict:
    """``{name: [(start, end)]}`` — where each of the properly nested
    ``(name, start, end)`` spans of one thread was the innermost one."""
    out: dict = collections.defaultdict(list)
    stack: list = []  # [name, end, own time counted up to]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out[name].append((cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(a)
        if stack:
            parent = stack[-1]
            if a > parent[2]:
                out[parent[0]].append((parent[2], a))
            parent[2] = a
            b = min(b, parent[1])
        stack.append([name, b, a])
    close(float("inf"))
    return dict(out)


def work_intervals(steps: list, window: Optional[tuple] = None) -> list:
    """When the server held work, from its ``serve.step``s ``(start, end,
    stats)`` in time order: each run of steps that began with rows, queued
    requests or un-applied logs, from the first one's start to the last
    one's end. The step after a run (all three 0) only marks its end.

    The profiler records an annotation when it ENDS, so the step in progress
    at either edge of the trace is missing from it (its finished phases are
    there). A run therefore reaches the ``window``'s end when no closing
    step followed it, and its start when the first step on record already
    held rows or logs."""
    out, cur = [], None
    for i, (a, b, stats) in enumerate(steps):
        if any(stats.get(k) for k in ("rows", "queued", "pending")):
            if cur is None:
                cut = window and i == 0 and (
                    stats.get("rows") or stats.get("pending"))
                cur = (window[0] if cut else a, b)
            else:
                cur = (cur[0], b)
        elif cur is not None:
            out.append(cur)
            cur = None
    if cur is not None:
        out.append((cur[0], window[1] if window else cur[1]))
    return out


def idle_split(busy: list, window: tuple, spans: list) -> dict:
    """One chip's idle time in ``window`` (``busy``: its merged busy
    intervals), by work and, inside work, by what the pump was in.
    ``spans``: the ``serve.*`` annotations ``(thread, name, start, end,
    stats)``, cut to the window. Seconds."""
    gaps = subtract([tuple(window)], busy)
    steps = [(a, b, st) for _, n, a, b, st in spans if n == STEP]
    work = union(work_intervals(steps, window))
    idle_work = intersect(gaps, work)
    by = []
    threads = collections.defaultdict(list)
    for thread, name, a, b, _ in spans:
        if name != PREFILL:  # lies inside serve.admit; not a phase
            threads[thread].append((name, a, b))
    left = idle_work
    for nested in threads.values():
        for name, own in sorted(own_intervals(nested).items()):
            rest = subtract(left, union(own))
            by.append([name if name != STEP else "serve.step, in no phase",
                       (total(left) - total(rest)) * 1e-9])
            left = rest
    by.append([BETWEEN_STEPS, total(left) * 1e-9])
    return {
        "work_s": total(work) * 1e-9,
        "idle_s": total(gaps) * 1e-9,
        "idle_with_work_s": total(idle_work) * 1e-9,
        "idle_no_work_s": (total(gaps) - total(idle_work)) * 1e-9,
        "by": sorted((kv for kv in by if kv[1] > 0), key=lambda kv: -kv[1]),
    }


def reduce_planes(planes: dict, window_s: float, vocab) -> dict:
    """The numbers, from what ``read_xspace`` gave (or a test built).
    Everything is cut to the harness's traced window first."""
    window = trace_reduce.traced_window(planes, window_s)
    if window is None:
        return {"window_s": window_s, "chips": 0, "scopes": {}, "bytes": {},
                "top_ops": {}, "annotations": {}, "prefill": {}, "idle": None}
    cut = clip(planes, window)
    lo, hi = window
    spans = [(t, n, max(a, lo), min(b, hi), st)
             for t, n, a, b, st in planes.get("spans", [])
             if b > lo and a < hi]
    vocab = frozenset(vocab)
    chips = sorted(cut["devices"])
    scopes: dict = collections.defaultdict(lambda: collections.defaultdict(float))
    nbytes: dict = collections.defaultdict(lambda: collections.defaultdict(float))
    per_op: dict = collections.defaultdict(lambda: [0.0, 0, 0])
    for chip_id in chips:
        metas = planes["op_meta"][chip_id]
        ops = cut["devices"][chip_id]["ops"]
        count = collections.Counter(mid for mid, _, _ in ops)
        for mid, sec in self_times(ops).items():
            meta = metas[mid]
            if "scope" not in meta:
                meta["scope"] = scope_of(meta["tf_op"], vocab)
            module, scope = meta["module"] or "?", meta["scope"]
            scopes[module][scope] += sec / len(chips)
            moved = meta["bytes_accessed"] * count[mid]
            nbytes[module][scope] += moved / len(chips)
            row = per_op[(module, meta["name"], scope)]
            row[0] += sec / len(chips)
            row[1] += count[mid] / len(chips)
            row[2] += moved / len(chips)
    top_ops: dict = collections.defaultdict(list)
    for (module, name, scope), (sec, n, moved) in sorted(
            per_op.items(), key=lambda kv: -kv[1][0]):
        if len(top_ops[module]) < TOP_OPS:
            top_ops[module].append([name, scope, sec, n, moved])
    annotations: dict = collections.defaultdict(lambda: [0, 0.0])
    for _, name, a, b, _ in spans:
        annotations[name][0] += 1
        annotations[name][1] += (b - a) * 1e-9
    prefills = [st for _, n, _, _, st in spans if n == PREFILL]
    busy = union([(a, b) for _, a, b in cut["devices"][chips[0]]["ops"]]
                 ) if chips else []
    return {
        "window_s": window_s,
        "chips": len(chips),
        # own device seconds and bytes accessed, per module and scope, mean
        # over chips
        "scopes": {m: dict(s) for m, s in scopes.items()},
        "bytes": {m: dict(s) for m, s in nbytes.items()},
        # [operation, scope, own s, executions, bytes accessed], by own time
        "top_ops": dict(top_ops),
        "annotations": {n: {"count": c, "seconds": s}
                        for n, (c, s) in sorted(annotations.items())},
        "prefill": {
            "dispatches": len(prefills),
            "prompt_tokens": sum(st.get("prompt_tokens", 0) for st in prefills),
            "positions": sum(st.get("positions", 0) for st in prefills),
        },
        "idle": dict(idle_split(busy, window, spans), chip=chips[0])
        if chips else None,
    }


def spans(rec: dict) -> Optional[dict]:
    """The run's reduction, made on the first call and kept under
    ``rec["spans"]``; None where there is nothing to read."""
    if "spans" not in rec:
        try:
            rec["spans"] = _reduce_run(rec)
        except Exception as e:  # a reader never takes the run down
            print(f"benchmark/span_reduce.py: no reduction: {e!r}",
                  file=sys.stderr)
            rec["spans"] = None
    return rec["spans"]


def _reduce_run(rec: dict) -> Optional[dict]:
    vocab = program_scopes()
    if vocab is None or not rec.get("traced") or not rec.get("trace"):
        return None
    path = trace_reduce.find_xplane(os.path.join(HERE, "out", "trace"))
    # the newest trace under out/ is this run's only if it is the file that
    # trace_reduce measured
    if path is None or os.path.getsize(path) != rec["trace"].get("xplane_bytes"):
        return None
    t = time.perf_counter()
    ta, tb = rec["traced"]
    out = reduce_planes(read_xspace(path), tb - ta, vocab)
    out["seconds"] = time.perf_counter() - t
    print(f"span_reduce: {out['seconds']:.1f} s for "
          f"{os.path.getsize(path)} bytes", flush=True)
    return out


def scope_share(rec: dict, modules, scopes) -> Optional[float]:
    """Share of the own device time of ``modules`` that lies under
    ``scopes``, % — what the per-scope readers return."""
    sp = spans(rec)
    if not sp:
        return None
    part = whole = 0.0
    for m in modules:
        for scope, sec in sp["scopes"].get(m, {}).items():
            whole += sec
            if scope in scopes:
                part += sec
    return 100.0 * part / whole if whole else None
