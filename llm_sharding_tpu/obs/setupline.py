"""Set-up's own account: a ledger of spans for what happens before serving.

``setup_s`` is judged in every cell of the benchmark and was the one phase
with no span in it: everything known of a restart was four stopwatch marks
the harness took from outside. This module is the inside view — one
process-wide, bounded, in-memory list of spans on ``time.perf_counter()``
(the clock the harness's marks, ``setup_s`` and the profiler's session are
on), each ``{id, name, start, end, parent, **fields}``:

- ``setup.engine`` / ``setup.repartition`` (``runtime/engine.py``) with the
  children ``.host_pull`` / ``.stack`` / ``.put`` / ``.quant``, and
  ``setup.server`` (``engine.serve()`` and ``PipelineServer.restore``: the
  server module's import, the constructor) with ``.arena`` / ``.host``: opened with :meth:`SetupLedger.span`, nested through a
  per-thread stack. A span's self time is its duration less its children's.
- ``setup.compile``: one per program built, assembled from jax's own
  monitoring events (trace, lowering, backend compile or cache load, cache
  hit / miss) by the two listeners :func:`install` registers — handed in by
  ``runtime/engine.py``, as ``annotate`` is handed to ``StepProfiler``; this
  module never imports jax. The events of a compile arrive on the thread
  that dispatches, in order, and the backend's ends it. Which program it
  was comes from a tag that ``record_shape_key`` sets on a MISS
  (:meth:`SetupLedger.miss`) and the span's close clears: a hit pays
  nothing new and reaches no line of this file. The tag is taken by the
  compile that jax names after the program (``fun``: ``jit(serve_chunk)``);
  any other (the arena's fill, an argument's conversion, the engine's small
  programs) is ``program="-"`` under jax's own name, so the sum is whole.
- ``setup.first_run``: from a miss to the landing of the next log its
  thread fetches — the first dispatch of every program met on the way, less
  the ``setup.compile`` spans inside it (its children): what warm-up pays
  once per program beyond building it. Who sees the landing is the
  server's to say (:attr:`SetupLedger.watch_landing`); a caller that waits
  for its own result ends it with :meth:`SetupLedger.landed`.

Spans also go out through ``emit_span`` with ``src="setup"`` (flight
recorder, ``/debugz``, and a server's ``trace_path=`` once one is
attached), the whole ledger is ``/statz``'s ``setup`` and
:meth:`SetupLedger.account` renders the table an operator reads after a slow
restart. Nothing here runs per step or per token: tens of spans a process,
each a list append.

Stdlib only, like ``stepline``: the lint and report tooling import ``obs``
without jax.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import logging
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional

from ..analysis.lockorder import named_lock
from .trace import TraceWriter, emit_span

#: jax.monitoring's names (jax 0.9.0: ``_src/dispatch.py``,
#: ``_src/compiler.py``, ``_src/compilation_cache.py``).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: sent when the compiled program is WRITTEN to the cache. With neither
#: event the cache took no part (``cache="off"``): none is configured, or
#: the program is under its thresholds (jax asks a cache for a key either
#: way, so ``compile_requests_use_cache`` tells nothing)
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

logger = logging.getLogger("llm_sharding_tpu.setup")

COMPILE_SPAN = "setup.compile"
FIRST_RUN_SPAN = "setup.first_run"
UNTAGGED = "-"

#: a trace that ended this long before a lowering began belongs to no
#: compile (``jax.eval_shape``, ``make_jaxpr``: a trace and nothing after)
_STALE_TRACE_S = 2.0


class _Building:
    """One compile under way on one thread: what jax has said of it so far."""

    __slots__ = ("traces", "lower_s", "backend_s", "cache_load_s", "cache",
                 "start")

    def __init__(self, start: float):
        self.start = start
        self.traces: List[tuple] = []  # disjoint (start, end), outermost kept
        self.lower_s = 0.0
        self.backend_s = 0.0
        self.cache_load_s = 0.0
        self.cache = "off"


class SetupLedger:
    """The process's set-up spans. Thread-safe; builder state (the span
    stack, the compile under way, the tag) is per thread."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 capacity: int = 4096):
        self._clock = clock
        self._lock = named_lock("obs.setup.ledger")
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._ids = 0
        self._local = threading.local()
        # per dispatching thread (by ident, not thread-local: a landing may
        # be reported by another thread — the prefetcher reads async logs —
        # and ``outstanding`` is asked by whichever builds a server). Single
        # dict operations only, which the interpreter lock makes whole
        self._tags: Dict[int, tuple] = {}  # the program a miss announced
        # its open ``setup.first_run``: [span, landings still waited for]
        self._first_run: Dict[int, list] = {}
        self._writer: Optional[TraceWriter] = None
        self._unwritten: List[dict] = []  # emitted before a writer attached
        #: called with each closed ``setup.compile`` span (``obs/metrics.py``
        #: feeds ``server_compile_seconds`` from it)
        self.on_compile: Optional[Callable[[dict], None]] = None
        #: ``watch_landing(landed) -> bool``: arrange for ``landed(t,
        #: **fields)`` to be called when the next log the calling thread
        #: fetches has reached the host; False if it could not
        #: (``runtime/server.py`` supplies it)
        self.watch_landing: Optional[Callable[[Callable], bool]] = None

    # ------------------------------------------------------------- spans

    def _open(self, name: str, start: float, parent: Optional[int],
              fields: dict) -> dict:
        with self._lock:
            self._ids += 1
            span = {"id": self._ids, "name": name, "start": start,
                    "end": None, "parent": parent, **fields}
            self._spans.append(span)
        return span

    def _close(self, span: dict, end: float) -> None:
        span["end"] = end
        fields = {k: v for k, v in span.items()
                  if k not in ("id", "name", "start", "end", "parent")}
        ev = emit_span(self._writer, span["name"], end - span["start"],
                       src="setup", **fields)
        if self._writer is None:
            with self._lock:
                if len(self._unwritten) < (self._spans.maxlen or 0):
                    self._unwritten.append(ev)

    def _parent(self) -> Optional[int]:
        stack = getattr(self._local, "stack", None)
        if stack:
            return stack[-1]["id"]
        run = self._first_run.get(threading.get_ident())
        return None if run is None else run[0]["id"]

    def begin(self, name: str, **fields) -> dict:
        """Open ``name`` now, a child of whatever span this thread has open;
        :meth:`end` closes it. The caller may add what it learns in between
        (``span["bytes"] = …``): the close sends it out."""
        span = self._open(name, self._clock(), self._parent(), fields)
        self._local.__dict__.setdefault("stack", []).append(span)
        return span

    def end(self, span: dict) -> None:
        """Close ``span`` now — and drop from this thread's stack whatever
        was opened inside it and never closed (a constructor that raised
        half-way): those stay in the ledger, open, and parent nothing
        more."""
        stack = self._local.stack
        for i, open_span in enumerate(stack):
            if open_span is span:
                del stack[i:]
                break
        self._close(span, self._clock())

    @contextlib.contextmanager
    def span(self, name: str, **fields) -> Iterator[dict]:
        """``begin`` / ``end`` around a block; yields the span."""
        span = self.begin(name, **fields)
        try:
            yield span
        finally:
            self.end(span)

    def wraps(self, name: str,
              then: Optional[Callable[[], None]] = None) -> Callable:
        """Decorator: every call of the function runs inside a ``name``
        span (a constructor's whole body, without re-indenting it);
        ``then()`` is called after the span of a call that returned has
        closed."""
        def decorate(fn):
            @functools.wraps(fn)
            def spanned(*args, **kwargs):
                with self.span(name):
                    result = fn(*args, **kwargs)
                if then is not None:
                    then()
                return result
            return spanned
        return decorate

    def snapshot(self) -> List[dict]:
        """Copies of every span held, oldest first; an open one has
        ``end`` None."""
        with self._lock:
            return [dict(s) for s in self._spans]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._unwritten.clear()
            self._first_run.clear()
            self._tags.clear()

    def attach_writer(self, writer: TraceWriter) -> None:
        """A server's ``trace_path=`` file takes the spans from now on, and
        first those that closed before it existed (the engine's)."""
        with self._lock:
            self._writer, late = writer, self._unwritten
            self._unwritten = []
        for ev in late:
            writer.write_event(ev)

    def detach_writer(self, writer: TraceWriter) -> None:
        with self._lock:
            if self._writer is writer:
                self._writer = None

    # ----------------------------------------------- programs and compiles

    def miss(self, program: str, key, in_flight: int = 0,
             queued: int = 0) -> None:
        """``record_shape_key``'s miss branch: the next compile this thread
        pays is ``program`` at ``key``, built while the server held
        ``in_flight`` rows and ``queued`` requests; and its first run has
        begun."""
        me = threading.get_ident()
        self._tags[me] = (program, repr(key), int(in_flight), int(queued))
        with self._lock:
            run = self._first_run.get(me)
            if run is not None:
                run[0]["programs"].append(program)
        if run is None:
            span = self._open(FIRST_RUN_SPAN, self._clock(), self._parent(),
                              {"programs": [program]})
            run = self._first_run[me] = [span, 0]
        # every program's own log is waited for: a watch that could be set
        # (none is pending on that server) adds one landing to wait for
        if self.watch_landing is not None and self.watch_landing(
            lambda t=None, **fields: self._landed(me, t, fields, watched=True)
        ):
            with self._lock:
                run[1] += 1

    def landed(self, t: Optional[float] = None, **fields) -> None:
        """The calling thread has its first result in hand (a caller that
        waits for its own, as ``generate_ids`` does): its open
        ``setup.first_run`` ends. Nothing open, nothing done."""
        self._landed(threading.get_ident(), t, fields)

    def _landed(self, thread: int, t: Optional[float], fields: dict,
                watched: bool = False) -> None:
        with self._lock:
            run = self._first_run.get(thread)
            if run is None:
                return
            run[1] -= int(watched)
            if watched and run[1] > 0:
                return  # a later program's log is still on its way
            del self._first_run[thread]
        # a tag that outlived its program's first run announced no compile
        # (the jit cache held the program already)
        self._tags.pop(thread, None)
        run[0].update(fields)
        self._close(run[0], self._clock() if t is None else t)

    def outstanding(self) -> bool:
        """Has a miss announced a program that is not built yet?"""
        return bool(self._tags)

    def on_duration(self, event: str, duration: float, **kw) -> None:
        """The ``jax.monitoring`` duration listener."""
        if event == BACKEND_EVENT:
            b = self._building(duration)
            b.backend_s += duration
            self._built(b, kw.get("fun_name"))
        elif event == TRACE_EVENT:
            b = self._building(duration)
            end = self._clock()
            start = end - duration
            # a jitted function called inside reported first, and is inside
            b.traces = [t for t in b.traces if t[0] < start]
            b.traces.append((start, end))
        elif event == LOWER_EVENT:
            b = self._building(duration)
            begun = self._clock() - duration
            b.traces = [t for t in b.traces
                        if t[0] < begun and begun - t[1] < _STALE_TRACE_S]
            b.start = min([begun] + [t[0] for t in b.traces])
            b.lower_s += duration
        elif event == CACHE_LOAD_EVENT:
            self._building(duration).cache_load_s += duration

    def on_event(self, event: str, **kw) -> None:
        """The ``jax.monitoring`` event listener."""
        if event == CACHE_HIT_EVENT:
            self._building(0.0).cache = "hit"
        elif event == CACHE_MISS_EVENT:
            self._building(0.0).cache = "miss"

    def _building(self, duration: float) -> _Building:
        b = getattr(self._local, "building", None)
        if b is None:
            b = self._local.building = _Building(self._clock() - duration)
        return b

    def _built(self, b: _Building, fun_name) -> None:
        # the tag goes to the compile jax names after the program (a
        # dispatch site names its key after the jitted function it calls,
        # which shardlint holds it to): a small program built on the way
        # there — an argument's conversion — does not take it
        me, fun = threading.get_ident(), str(fun_name or "")
        tag = self._tags.get(me)
        if tag is not None and tag[0] in fun:
            del self._tags[me]
        else:
            tag = (UNTAGGED, "", 0, 0)
        program, key, in_flight, queued = tag
        self._local.building = None
        span = self._open(COMPILE_SPAN, b.start, self._parent(), {
            "program": program, "key": key, "fun": fun,
            "trace_s": sum(e - s for s, e in b.traces),
            "lower_s": b.lower_s, "backend_s": b.backend_s,
            "cache": b.cache, "cache_load_s": b.cache_load_s,
            "in_flight": in_flight, "queued": queued,
        })
        self._close(span, self._clock())
        if self.on_compile is not None:
            self.on_compile(span)

    # ---------------------------------------------------------- the account

    def account(self) -> str:
        """The table for the log: per span name seconds, self seconds, bytes
        and count; then per program keys built, compiled / loaded, seconds.
        Closed spans only."""
        return render(self.snapshot())

    def log_account(self, when: str) -> None:
        logger.info("set-up's account at %s:\n%s", when, self.account())

    def server_built(self) -> None:
        """A server is SERVING from birth: what the restart cost up to here
        goes to the log — unless another thread is mid-way through building
        an announced program (a second server of the process); a server's
        ``close()`` logs the account again, with everything built since."""
        if not self.outstanding():
            self.log_account("SERVING")


def self_seconds(spans: List[dict]) -> Dict[int, float]:
    """``{span id: duration less its children's}`` over closed spans."""
    out = {s["id"]: s["end"] - s["start"] for s in spans
           if s["end"] is not None}
    for s in spans:
        if s["end"] is not None and s["parent"] in out:
            out[s["parent"]] -= s["end"] - s["start"]
    return out


def compile_seconds(span: dict) -> float:
    """What one ``setup.compile`` cost: tracing and lowering, then the
    cache's load on a hit or the backend's compile."""
    built = span["cache_load_s"] if span["cache"] == "hit" \
        else span["backend_s"]
    return span["trace_s"] + span["lower_s"] + built


def render(spans: List[dict]) -> str:
    own = self_seconds(spans)
    by_name: Dict[str, list] = {}
    programs: Dict[str, list] = {}
    for s in spans:
        if s["id"] not in own:
            continue
        row = by_name.setdefault(s["name"], [0.0, 0.0, 0, 0])
        row[0] += s["end"] - s["start"]
        row[1] += own[s["id"]]
        row[2] += int(s.get("bytes", 0))
        row[3] += 1
        if s["name"] == COMPILE_SPAN:
            p = programs.setdefault(s["program"], [0, 0, 0, 0.0])
            p[0] += 1
            p[1 if s["cache"] != "hit" else 2] += 1
            p[3] += compile_seconds(s)
    lines = [f"{'span':<28}{'seconds':>10}{'self':>10}{'bytes':>16}"
             f"{'count':>7}"]
    for name in sorted(by_name):
        total, self_s, nbytes, n = by_name[name]
        lines.append(f"{name:<28}{total:>10.3f}{self_s:>10.3f}{nbytes:>16d}"
                     f"{n:>7d}")
    lines.append(f"{'program':<28}{'keys':>10}{'compiled':>10}{'loaded':>16}"
                 f"{'seconds':>10}")
    for name in sorted(programs):
        keys, compiled, loaded, secs = programs[name]
        lines.append(f"{name:<28}{keys:>10d}{compiled:>10d}{loaded:>16d}"
                     f"{secs:>10.3f}")
    return "\n".join(lines)


#: The process's ledger: every engine and server records into it.
SETUP = SetupLedger()

_installed = False


def install(register_duration_listener: Callable,
            register_event_listener: Callable) -> bool:
    """Register ``SETUP``'s two listeners with ``jax.monitoring`` — once a
    process, however many engines are built (jax keeps a listener for the
    process's life). Returns whether this call registered them."""
    global _installed
    with SETUP._lock:
        if _installed:
            return False
        _installed = True
    register_duration_listener(SETUP.on_duration)
    register_event_listener(SETUP.on_event)
    return True
