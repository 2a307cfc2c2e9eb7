"""Continuous step profiler: the serving loop's host–device overlap ledger.

The measurement layer that says whether a host-side bubble exists and sizes
it per phase — the role the reference repo's fitted per-device latency
models play for placement:

- The step pump records one :class:`StepRecord` per serve-loop step into a
  bounded ring: per-phase host durations (``admit`` / ``radix_plan`` /
  ``table_push`` / ``dispatch`` / ``fetch`` / ``apply`` / ``gauge_sweep``
  — finer than the old three-bucket histogram), time *blocked on device*
  (the log-fetch materialization wait, measured separately from host
  compute), the device's starved time, rows in flight, tokens applied, and
  queue depths.
- The record also carries the token's path on the host's clock: every
  program the step handed the device (``dispatches``: when it joined the
  queue, the depth found, the starved time that enqueue ended) and every
  log it applied (``logs``: when its program was enqueued and by which
  step, when it landed, whether the host had to wait for it, when its
  tokens were on the requests), plus ``after_landing`` — the stretch from
  the last log's landing to the step's end, split by phase.
- Derived series feed continuously: ``server_host_occupancy``,
  ``server_device_idle_frac``, ``server_step_wall_seconds``,
  ``server_token_emit_lag_seconds``,
  ``server_device_starved_seconds_total``,
  ``server_steps_host_bound_total``.
- Lock-wait accounting rides the :func:`~..analysis.lockorder.named_lock`
  factory's opt-in timed mode (``STEPLINE_LOCK_TIMING=1``); this module
  installs the process-wide sink that observes
  ``server_lock_wait_seconds{lock}``.
- An on-demand deep capture (``/profilez?steps=N``, ``:profile N``) arms an
  N-step window that additionally keeps the full sub-phase segment
  timeline, per-step lock-wait deltas, and trace_id exemplars of applied
  rows, returned as one JSON-ready bundle.

Accounting invariant (asserted by tests and the occupancy bench in-band):
phases are measured as DISJOINT stack segments — a nested phase's elapsed
time is excluded from its parent — and blocked time is excluded from the
phase it interrupts, so ``sum(phases) + blocked_s + unattributed_s ==
wall_s`` exactly, with ``unattributed_s`` (inter-phase gaps: autosnapshot,
metric observes) expected under 5% of wall on the CPU smoke serve.

The builder API (``begin_step``/``push``/``pop``/``blocked``/
``dispatched``/``log_landed``/``log_applied``/``end_step``) is
single-threaded by construction — only the step pump calls it — so builder
state is unlocked; only the ring itself takes a lock
(``obs.stepline.ring``), and gauge/histogram feeds happen outside it.

Starved time (``dispatched``) is counted where a program joins the device's
queue: if nothing enqueued before it is still un-landed, the device had
nothing to run from the previous program's landing to this enqueue. A
landing the host waited for is known to the moment; one a poll FOUND lies
between the last poll that saw the device busy and the poll that found it
done, so every bubble is a bracket: ``starved_lo_s`` from the found stamp
(``idle_s``, the lower bound), ``starved_hi_s`` from the last busy one.
Only time in which the server held work counts: a step that ENDS with no
live rows and no queue closes the account (logs still out then belong to
rows that are done), and the next bubble starts no earlier than the begin of
the next step — the one that first sees work again — so a client's think
time between two requests is never starved time, whether or not its caller
goes on stepping an empty server.

One definition for every reader of these records (the series below,
``obs/report.token_path``, the benchmark's ``path_reduce``): a step is
HOST-BOUND if it applied a ``chunk`` log it did not have to wait for (a
``verify``'s step drains its own program and always waits); the starved
SHARE is the starved time over the wall of the steps that held work — those
that dispatched, applied, or ended with rows, queue or logs.

A step has two ends. ``wall_s`` is read first and closes the invariant
above; ``end`` is read last, after the record is built and the series are
fed, and is as near as the profiler gets to ``step()`` returning — when a
stream's reader sees the tokens. A log's emit lag is ``end - landed``;
``after_landing`` splits the last log's by phase, and what ``end_step``
itself costs falls into its ``unattributed``.

Profiler annotations: the same phase stack also writes into the JAX
profiler's trace, on the profiler's clock, through an injected ``annotate``
factory (the server passes ``jax.profiler``'s; this module never imports
jax). ``begin_step``/``end_step`` bracket ``serve.step`` (a step marker
with ``step_num`` and the ``rows``/``queued``/``pending`` the server held at
the step's START), ``push``/``pop`` enter and exit ``serve.<phase>``,
``blocking`` wraps a wait on the device in ``serve.blocked``, ``prefill``
wraps one prefill dispatch in ``serve.prefill``. Idle polls write nothing:
a step is annotated when the server holds work at its start, and the one
step after the last such is too (all three counts 0 — the end of work).
Outside a profiler session an annotation costs about a microsecond; the
session is the only switch. The device side of the same trace is named by
``SCOPES``: the closed vocabulary of ``jax.named_scope`` words in the step
programs (README "Step profiling" maps both).

Everything here is stdlib-only: ``step-report`` and the lint/obs tooling
must run without jax.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional

from ..analysis import lockorder
from .metrics import REGISTRY

#: Canonical phase names, in typical per-step order. ``push`` accepts only
#: these so the metric's label space stays closed (shardlint checks the
#: README row against this set).
PHASES = (
    "admit",       # shed + ingress drain + prefill admission (incl. flush)
    "radix_plan",  # radix-tree chunk planning / staged plan refresh
    "table_push",  # block-table host->device push
    "dispatch",    # host-side chunk/spec dispatch (device executes async)
    "fetch",       # drain bookkeeping around the log fetch (host part)
    "apply",       # applying fetched token logs to requests
    "gauge_sweep", # load/KV/attn gauge sweep (pace via gauge_sweep_every_s)
)

_PHASE_SET = frozenset(PHASES)

#: Profiler annotation names. One per phase, plus the step, the device wait
#: and the prefill dispatch; a reader of the trace matches on these.
STEP_ANNOTATION = "serve.step"
BLOCKED_ANNOTATION = "serve.blocked"
PREFILL_ANNOTATION = "serve.prefill"
_PHASE_ANNOTATION = {p: "serve." + p for p in PHASES}

#: The closed vocabulary of ``jax.named_scope`` words in the step programs
#: (``parallel/serve.py`` and everything they call). A device operation in a
#: profiler trace carries its scope path in ``tf_op``; the innermost of these
#: words names what the operation is for. Program, trace reader
#: (``benchmark/span_reduce.py``) and README agree through this tuple.
SCOPES = (
    "embed",      # token embedding lookup (vocab-parallel psum included)
    "norm",       # the block's two RMS/layer norms
    "qkv",        # q/k/v projections and their biases
    "rope",       # rotary tables and their application
    "kv_write",   # this step's fresh KV entries into their blocks/rows
    "kv_take",    # one layer's rows sliced out of the DENSE cache stack
                  # (scan_layers; the paged scan slices nothing)
    "kv_layout",  # layout changes of a WINDOW of K/V: the flash kernel's
                  # operands, a gathered paged window (XLA path, prefix
                  # handle), an admitted window cut into head-major blocks
                  # — never the paged pool, which is stored as it is read
    "kv_put",     # the step's positions written back into the dense stack
    "absorb",     # latent attention's two absorbed products: the query's
                  # nope part into the latent space before the kernel, the
                  # latent output out of it after (models/deepseek_v3.py)
    "attn",       # the attention kernel / XLA attention and its GQA fold
    "indexer",    # a token-selecting model's indexer: its three projections,
                  # the index key's LayerNorm and rotary, and the scores of a
                  # query against the row's live index keys
    "select",     # the top-k over those scores and what turns it into the
                  # attention's list (a decode step) or mask (a chunk)
    "o_proj",     # output projection, its psum, the residual add
    "mlp",        # gated MLP, its psum, the residual add
    "router",     # a sparse MLP's router: float32 logits, softmax, top-k
    "moe",        # a sparse MLP's expert product: the pairs an expert
                  # has, the expert kernel (``moe_experts``; a decode call
                  # sums the rows' weighted outputs inside it), its counters
    "zero_expert",  # the zero-compute experts' term (the sum of the chosen
                  # identity experts' weights a token, times the expert
                  # path's input) and the add that joins a shortcut layer's
                  # expert output to the residual stream at the layer's end
                  # (models/longcat_flash.py)
    "ssm_proj",   # a Mamba mixer's two projections, ``w_in`` and ``w_out``
                  # with its residual add (models/nemotron_h.py, jamba.py)
    "conv",       # the mixer's causal depthwise conv, its tail shift, silu
    "ssm",        # the state update (a decode step) or a prefill chunk's
                  # scan (Mamba-2's block form, Mamba-1's scan in time), its
                  # read-out, the gate (Mamba-2: the gated grouped norm)
    "ssm_x",      # a Mamba-1 mixer's path to ``dt``, ``B``, ``C``: ``w_x``,
                  # the three norms, ``w_dt`` and its softplus, ``A`` — what
                  # Mamba-2 has no counterpart of (models/jamba.py)
    "moe_latent", # a LatentMoE's two projections, into the experts' latent
                  # space and out of it
    "kda_proj",   # a KDA mixer's projections: ``wq``, ``wk``, ``wv``, the
                  # two low-rank pairs (decay, output gate), ``w_beta``, and
                  # ``wo`` with its residual add (models/solar_open2.py; its
                  # conv keeps the word ``conv``)
    "kda",        # the decay's softplus and exp, the L2 norms of q and k, the
                  # state update (a decode step) or the chunkwise WY form (a
                  # prefill chunk), the read-out, the gated per-head norm
    "pass_close", # a looped stack's close of a pass: the final norm whose
                  # result enters the next pass, the exit gate's product and
                  # sigmoid, the running exit choice (models/stack.py)
    "head",       # final norm + this stage's logit slice
    "sample",     # argmax assembly / per-row sampling over the logits
    "ring_hop",   # stage->stage ppermute and the last stage's broadcast
    "state",      # ServeState row slicing and bookkeeping (everything in a
                  # step program's body that no inner word names)
)

STEP_PHASE = REGISTRY.histogram(
    "server_step_phase_seconds",
    "Serving-loop host phase durations, disjoint per step: admit (shed + "
    "ingress drain + prefill admission), radix_plan (chunk planning), "
    "table_push (block-table push), dispatch (host-side chunk/spec "
    "dispatch), fetch (drain bookkeeping around the log fetch), apply "
    "(token-log application), gauge_sweep (load/KV/attn gauge sweep)",
    labels=("phase",),
)
STEP_WALL = REGISTRY.histogram(
    "server_step_wall_seconds",
    "Wall time of one serve-loop step (all phases + device-blocked wait)",
)
HOST_OCCUPANCY = REGISTRY.gauge(
    "server_host_occupancy",
    "Fraction of step wall spent on host-side work (vs blocked on device), "
    "from the most recent step of any live server (last-writer-wins across "
    "dp replicas; per-replica values ride ReplicatedServer.stats())",
)
DEVICE_IDLE_FRAC = REGISTRY.gauge(
    "server_device_idle_frac",
    "Device-starved share of the most recent step's wall (any live "
    "server): the lower bound of server_device_starved_seconds_total, "
    "per step",
)
EMIT_LAG = REGISTRY.histogram(
    "server_token_emit_lag_seconds",
    "From the landing on host of a log that carried tokens to the end of "
    "the step that applied it: what the host adds to a token's gap",
)
DEVICE_STARVED = REGISTRY.counter(
    "server_device_starved_seconds_total",
    "Time the device had nothing queued while the server held work, "
    "counted at each enqueue from the previous program's landing: lo from "
    "the stamp that found it landed, hi from the last poll that saw it busy",
    labels=("bound",),
)
STEPS_HOST_BOUND = REGISTRY.counter(
    "server_steps_host_bound_total",
    "Steps that applied a decode chunk's log the device had finished before "
    "the host came for it (no wait); beside server_step_wall_seconds' count",
)
LOCK_WAIT = REGISTRY.histogram(
    "server_lock_wait_seconds",
    "Time acquire() blocked on a named runtime lock — populated only in "
    "the opt-in STEPLINE_LOCK_TIMING=1 mode (zero-overhead plain "
    "primitives otherwise)",
    labels=("lock",),
)


# Per-phase histogram children resolved ONCE: the per-step feed is the
# profiler's hot path, and the label space is closed over PHASES — no
# reason to pay the family lock + label lookup on every step.
_PHASE_CHILD = {p: STEP_PHASE.labels(phase=p) for p in PHASES}
_STARVED_LO = DEVICE_STARVED.labels(bound="lo")
_STARVED_HI = DEVICE_STARVED.labels(bound="hi")
_EMIT_LAG = EMIT_LAG.labels()
_HOST_BOUND = STEPS_HOST_BOUND.labels()

_LOG_KEYS = ("n", "kind", "by", "enq", "landed", "exact", "waited",
             "applied", "tokens")
_DISPATCH_KEYS = ("n", "kind", "enq", "in_flight", "starved_lo_s",
                  "starved_hi_s")
_LANDED, _APPLIED, _TOKENS = (
    _LOG_KEYS.index(k) for k in ("landed", "applied", "tokens")
)


def _lock_wait_sink(name: str, dt: float) -> None:
    # The obs-internal locks are themselves timed in STEPLINE_LOCK_TIMING
    # mode, and observing LOCK_WAIT acquires one — recording THEIR waits
    # here would recurse into the very lock being recorded. They stay
    # visible through lockorder.wait_totals() (the deep capture's per-step
    # deltas); only the histogram skips them.
    if name.startswith("obs."):
        return
    LOCK_WAIT.labels(lock=name).observe(dt)


# The sink is a process-wide no-op until timed locks exist (the timed mode
# is construction-time opt-in), so installing it unconditionally is free.
lockorder.set_wait_sink(_lock_wait_sink)

#: Exemplar trace_ids kept per armed step (bounded; first writers win).
_EXEMPLARS_PER_STEP = 8

#: Live profilers, for the process-wide /debugz step-ring tail.
_LIVE: "weakref.WeakSet[StepProfiler]" = weakref.WeakSet()


class StepRecord:
    """One serve-loop step's accounting. Plain data; ``to_dict`` is the
    wire/JSON form used by the ring snapshot, /profilez, and /debugz."""

    __slots__ = (
        "ts", "wall_s", "phases", "blocked_s", "idle_s", "unattributed_s",
        "rows", "tokens", "queued", "pending", "segments", "lock_waits",
        "exemplars", "prompt_tokens", "prefill_positions",
        "expert_tokens", "experts_read", "expert_steps", "expert_rows",
        "decode_blocks_live", "decode_blocks_reserved",
        "prefill_cells_live", "prefill_cells_walked", "kv_kinds",
        "prefill_kv_blocks", "decode_kv_entries", "recurrent_rows",
        "scan_positions", "sparse_tokens", "exit_passes", "seq", "t0", "end",
        "starved_hi_s", "logs", "dispatches", "after_landing",
    )

    def __init__(self, ts, wall_s, phases, blocked_s, idle_s,
                 unattributed_s, rows, tokens, queued, pending,
                 segments=None, lock_waits=None, exemplars=None,
                 prompt_tokens=0, prefill_positions=0, expert_tokens=None,
                 experts_read=None, expert_steps=0, expert_rows=0,
                 decode_blocks_live=0, decode_blocks_reserved=0,
                 prefill_cells_live=0, prefill_cells_walked=0):
        self.ts = ts
        self.wall_s = wall_s
        self.phases = phases
        self.blocked_s = blocked_s
        self.idle_s = idle_s
        self.unattributed_s = unattributed_s
        self.rows = rows
        self.tokens = tokens
        self.queued = queued
        self.pending = pending
        self.segments = segments
        self.lock_waits = lock_waits
        self.exemplars = exemplars
        # prefill dispatched in this step: real prompt tokens, and the
        # rows x positions the programs computed for them (padding included)
        self.prompt_tokens = prompt_tokens
        self.prefill_positions = prefill_positions
        # a model with experts (None otherwise), from the counters applied
        # in this step: tokens each expert received from LIVE rows and
        # positions, decode and prefill, summed over layers ([E]); distinct
        # experts read per layer, summed over the DECODE microsteps applied
        # ([L]); how many microsteps those were and the live rows they held
        self.expert_tokens = expert_tokens
        self.experts_read = experts_read
        self.expert_steps = expert_steps
        self.expert_rows = expert_rows
        # paged decode dispatched in this step: table entries its rows'
        # written frontiers made the decode kernel walk, and the entries
        # the tables of the rows it was called for reserved (dead rows
        # walk 0 and reserve the table's width)
        self.decode_blocks_live = decode_blocks_live
        self.decode_blocks_reserved = decode_blocks_reserved
        # chunked prefills whose counters landed in this step: the cells
        # (grid steps) the prefill kernel walked over their layer calls,
        # and the cells of every row at the table's whole width
        self.prefill_cells_live = prefill_cells_live
        self.prefill_cells_walked = prefill_cells_walked
        # a model with a KV state per kind of layer (None otherwise), per
        # kind ("full", "swa"): blocks its pool holds for live rows at this
        # step's decode dispatch and the pool's size, the table entries the
        # step's decode walked and reserved, and (window layers) the blocks
        # handed back to the pool behind the window in this step
        self.kv_kinds = None
        # chunked prefills dispatched in this step: the arena blocks their
        # fresh K/V landed in, by the form of the write ("tile" / "rows");
        # None in a step that dispatched no chunk
        self.prefill_kv_blocks = None
        # decode / verify dispatches of this step: the fresh K/V entries
        # they land in the arena, by the form of the write ("kernel" /
        # "scatter"); None in a step that dispatched none over a paged arena
        self.decode_kv_entries = None
        # a model with recurrent layers (None otherwise): rows holding a
        # recurrent state at this step's decode dispatch, and the positions
        # its prefill chunks put through the mixers' scan, ``{"real":
        # prompt tokens, "pad": padding}`` per mixer layer (host arithmetic
        # at dispatch)
        self.recurrent_rows = None
        self.scan_positions = None
        # a token-selecting model (None otherwise), summed over the layers
        # and decode dispatches of this step: ``{"scored": index keys a
        # row's query was scored against, "read": tokens kept by the
        # selection, "live": live context tokens of the rows, "walked":
        # tokens whose K/V blocks the attention streamed}`` (host arithmetic
        # at dispatch, from the length mirrors)
        self.sparse_tokens = None
        # a looped model (None otherwise): tokens applied in this step by
        # the pass their logits were read from, ``[passes]``
        self.exit_passes = None
        # the token's path (module docstring). ``seq`` is the number the
        # step's ``serve.step`` annotation carries as ``step_num``; ``t0``
        # the clock at its begin; every other time an offset from ``t0``.
        # ``idle_s`` is the lower bound of the step's starved time,
        # ``starved_hi_s`` the upper. ``logs`` / ``dispatches`` hold one
        # sequence per applied log / enqueued program, in the order of
        # ``_LOG_KEYS`` / ``_DISPATCH_KEYS``
        self.seq = 0
        self.t0 = 0.0
        self.end = wall_s
        self.starved_hi_s = idle_s
        self.logs = ()
        self.dispatches = ()
        self.after_landing = None

    @property
    def host_s(self) -> float:
        return sum(self.phases.values())

    @property
    def occupancy(self) -> float:
        return self.host_s / self.wall_s if self.wall_s > 0 else 0.0

    def to_dict(self) -> dict:
        d = {
            "ts": self.ts,
            "wall_s": self.wall_s,
            "phases": dict(self.phases),
            "blocked_s": self.blocked_s,
            "idle_s": self.idle_s,
            "unattributed_s": self.unattributed_s,
            "host_s": self.host_s,
            "occupancy": self.occupancy,
            "rows": self.rows,
            "tokens": self.tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefill_positions": self.prefill_positions,
            "decode_blocks_live": self.decode_blocks_live,
            "decode_blocks_reserved": self.decode_blocks_reserved,
            "prefill_cells_live": self.prefill_cells_live,
            "prefill_cells_walked": self.prefill_cells_walked,
            "queued": self.queued,
            "pending": self.pending,
            "seq": self.seq,
            "t0": self.t0,
            "end": self.end,
            "starved_hi_s": self.starved_hi_s,
            "logs": [dict(zip(_LOG_KEYS, log)) for log in self.logs],
            "dispatches": [
                dict(zip(_DISPATCH_KEYS, d)) for d in self.dispatches
            ],
        }
        if self.after_landing is not None:
            d["after_landing"] = dict(self.after_landing)
        if self.kv_kinds is not None:
            d["kv_kinds"] = {k: dict(v) for k, v in self.kv_kinds.items()}
        if self.prefill_kv_blocks is not None:
            d["prefill_kv_blocks"] = dict(self.prefill_kv_blocks)
        if self.decode_kv_entries is not None:
            d["decode_kv_entries"] = dict(self.decode_kv_entries)
        if self.recurrent_rows is not None:
            d["recurrent_rows"] = self.recurrent_rows
        if self.scan_positions is not None:
            d["scan_positions"] = dict(self.scan_positions)
        if self.sparse_tokens is not None:
            d["sparse_tokens"] = dict(self.sparse_tokens)
        if self.exit_passes is not None:
            d["exit_passes"] = list(self.exit_passes)
        if self.expert_tokens is not None:
            d["expert_tokens"] = list(self.expert_tokens)
            d["experts_read"] = list(self.experts_read)
            d["expert_steps"] = self.expert_steps
            d["expert_rows"] = self.expert_rows
        if self.segments is not None:
            d["segments"] = [list(s) for s in self.segments]
        if self.lock_waits is not None:
            d["lock_waits"] = dict(self.lock_waits)
        if self.exemplars is not None:
            d["exemplars"] = list(self.exemplars)
        return d


class StepProfiler:
    """Bounded-ring step profiler with an armable deep-capture window.

    ``clock`` is injectable for tests (defaults to ``time.perf_counter``).
    ``set_enabled(False)`` turns every builder call into a boolean check —
    the overhead bench's "off" arm. ``annotate`` is the profiler-annotation
    factory, ``annotate(name, **stats)`` → context manager (the server
    passes ``jax.profiler``'s; None writes no annotations)."""

    def __init__(self, ring_size: int = 512,
                 clock: Callable[[], float] = time.perf_counter,
                 name: str = "server",
                 annotate: Optional[Callable] = None):
        if ring_size < 1:
            raise ValueError(f"ring_size must be >= 1, got {ring_size}")
        self.name = name
        self._clock = clock
        self._ring_size = int(ring_size)
        self._ring: List[StepRecord] = []
        self._ring_next = 0  # overwrite cursor once the ring is full
        self._ring_mu = lockorder.named_lock("obs.stepline.ring")
        self._enabled = True
        self.steps_total = 0
        #: number of the step now open (``serve.step``'s ``step_num``)
        self.seq = 0
        # builder state (step-pump thread only; unlocked by design)
        self._t0: Optional[float] = None
        self._step_armed = False
        self._stack: List[list] = []  # [name, start, excluded_s, span]
        self._annotate = annotate
        self._step_span = None  # the open serve.step; None = unannotated
        self._had_work = False  # the previous step began with work
        self._prompt_tokens = 0
        self._prefill_positions = 0
        self._experts: Optional[list] = None  # [tokens, read, steps, rows]
        self._decode_blocks = [0, 0]  # [live, reserved]
        self._prefill_cells = [0, 0]  # [live, walked]
        self._prefill_kv_blocks = None  # {"tile" | "rows": blocks}
        self._decode_kv_entries = None  # {a DECODE_KV_WRITES form: entries}
        self._recurrent_rows = None
        self._scan_positions = None  # {"real" | "pad": positions}
        self._sparse_tokens = None  # {"scored" | "read" | "live" | "walked": n}
        self._exit_passes = None  # [passes] tokens by exit pass
        self._kv_kinds = None
        self._phases: Dict[str, float] = {}
        self._blocked_s = 0.0
        self._idle_s = 0.0
        self._starved_hi_s = 0.0
        self._logs: List[list] = []
        self._dispatches: List[tuple] = []
        # (landed_at, the phase totals at that moment): the last landing an
        # enqueue's poll found in this step, and that of the last log applied
        self._found: tuple = (None, None)
        self._landing: Optional[tuple] = None
        self._host_bound = False
        # the clock at the begin of the first step after the last idle one:
        # no starved time is counted before it
        self._work_from: Optional[float] = None
        self._segments: Optional[List[tuple]] = None
        self._exemplars: Optional[List[str]] = None
        self._lock_base: Optional[Dict[str, tuple]] = None
        # deep-capture state (armed by any thread; consumed by the pump)
        self._armed_left = 0
        self._capture: List[StepRecord] = []
        self._capture_requested = 0
        self._capture_done = threading.Event()
        self._capture_done.set()
        _LIVE.add(self)

    # -- enable / arm -------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, on: bool) -> None:
        self._enabled = bool(on)

    def arm(self, steps: int) -> None:
        """Arm an N-step deep capture. The next N completed steps keep the
        full sub-phase segment timeline, lock-wait deltas, and applied-row
        trace_id exemplars; :meth:`wait_capture` unblocks when done."""
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"capture steps must be >= 1, got {steps}")
        self._capture = []
        self._capture_requested = steps
        self._capture_done.clear()
        self._armed_left = steps  # publish last: the pump checks this

    @property
    def armed(self) -> bool:
        return self._armed_left > 0

    def wait_capture(self, timeout: Optional[float] = None) -> bool:
        return self._capture_done.wait(timeout)

    def capture_bundle(self) -> dict:
        """The current (possibly still filling) deep capture as one
        JSON-ready bundle."""
        steps = [r.to_dict() for r in self._capture]
        return {
            "profiler": self.name,
            "steps_requested": self._capture_requested,
            "steps_captured": len(steps),
            "complete": self._capture_done.is_set()
            and bool(self._capture_requested),
            "lock_timing": lockorder.timing_enabled(),
            "steps": steps,
        }

    def capture(self, steps: int, wait_s: float = 5.0) -> dict:
        """Arm, wait up to ``wait_s`` for N steps to land, return the
        bundle (``complete: false`` if the loop went idle first)."""
        self.arm(steps)
        self.wait_capture(wait_s)
        return self.capture_bundle()

    # -- builder API (step-pump thread only) --------------------------------

    def _enter(self, name: str, **stats):
        """Open one profiler annotation — only inside an annotated step."""
        if self._step_span is None:
            return None
        span = self._annotate(name, **stats)
        span.__enter__()
        return span

    @staticmethod
    def _exit(span) -> None:
        if span is not None:
            span.__exit__(None, None, None)

    def begin_step(self, rows: int = 0, queued: int = 0,
                   pending: int = 0) -> None:
        """Open a step. ``rows``/``queued``/``pending`` are what the server
        holds NOW (active rows, queued requests, un-applied logs): a step
        that begins with any, and the one step after the last such, is
        written into the profiler's trace; idle polls are not."""
        if not self._enabled:
            return
        if self._step_span is not None:
            # the previous step raised before end_step: close what it left
            # open, innermost first, so the trace stays properly nested
            for entry in reversed(self._stack):
                self._exit(entry[3])
            self._exit(self._step_span)
            self._step_span = None
        self._t0 = self._clock()
        self.seq = self.steps_total
        if self._work_from is None:
            self._work_from = self._t0
        self._stack = []
        self._phases = {}
        self._blocked_s = 0.0
        self._idle_s = 0.0
        self._starved_hi_s = 0.0
        self._logs = []
        self._dispatches = []
        self._found = (None, None)
        self._landing = None
        self._host_bound = False
        self._prompt_tokens = 0
        self._prefill_positions = 0
        self._experts = None
        self._decode_blocks = [0, 0]
        self._prefill_cells = [0, 0]
        self._prefill_kv_blocks = None
        self._decode_kv_entries = None
        self._recurrent_rows = None
        self._scan_positions = None
        self._sparse_tokens = None
        self._exit_passes = None
        self._kv_kinds = None
        work = bool(rows or queued or pending)
        if self._annotate is not None and (work or self._had_work):
            self._step_span = self._annotate(
                STEP_ANNOTATION, step_num=self.seq, rows=int(rows),
                queued=int(queued), pending=int(pending),
            )
            self._step_span.__enter__()
        self._had_work = work
        # a step only joins the capture window if it was armed at BEGIN —
        # arming mid-step (the /profilez handler races the pump) must not
        # count the half-observed step, which has no segment timeline
        self._step_armed = self._armed_left > 0
        if self._step_armed:
            self._segments = []
            self._exemplars = []
            self._lock_base = (
                lockorder.wait_totals()
                if lockorder.timing_enabled() else None
            )
        else:
            self._segments = None
            self._exemplars = None
            self._lock_base = None

    def push(self, phase: str) -> None:
        if not self._enabled or self._t0 is None:
            return
        if phase not in _PHASE_SET:
            raise ValueError(f"unknown phase {phase!r}; one of {PHASES}")
        self._stack.append(
            [phase, self._clock(), 0.0, self._enter(_PHASE_ANNOTATION[phase])]
        )

    def pop(self) -> None:
        if not self._enabled or self._t0 is None or not self._stack:
            return
        name, start, excluded, span = self._stack.pop()
        now = self._clock()
        self._exit(span)
        elapsed = now - start
        self._phases[name] = self._phases.get(name, 0.0) + max(
            0.0, elapsed - excluded
        )
        if self._stack:  # nested: parent must not double-count this span
            self._stack[-1][2] += elapsed
        if self._segments is not None:
            self._segments.append(
                (name, start - self._t0, max(0.0, elapsed - excluded))
            )

    def blocked(self, dt: float) -> None:
        """Account ``dt`` seconds of the step as blocked-on-device; it is
        excluded from the phase it interrupted."""
        if not self._enabled or self._t0 is None or dt <= 0.0:
            return
        self._blocked_s += dt
        if self._stack:
            self._stack[-1][2] += dt

    @contextlib.contextmanager
    def blocking(self):
        """Around a wait on the device (the log has not landed on host):
        the wait is accounted as ``blocked_s`` — excluded from the phase it
        interrupts, like ``blocked`` — and written as ``serve.blocked``."""
        if not self._enabled or self._t0 is None:
            yield
            return
        span = self._enter(BLOCKED_ANNOTATION)
        t = self._clock()
        try:
            yield
        finally:
            self.blocked(self._clock() - t)
            self._exit(span)

    @contextlib.contextmanager
    def prefill(self, rows: int, prompt_tokens: int, positions: int):
        """Around ONE prefill dispatch: ``rows`` the program computes,
        ``prompt_tokens`` the real prompt tokens among them (a radix hit's
        matched prefix is not prefilled and not counted), ``positions`` =
        rows x positions computed (padding to the bucket or chunk
        included). Adds both to the step's record and writes
        ``serve.prefill`` with the three as stats."""
        if not self._enabled or self._t0 is None:
            yield
            return
        self._prompt_tokens += int(prompt_tokens)
        self._prefill_positions += int(positions)
        span = self._enter(
            PREFILL_ANNOTATION, rows=int(rows),
            prompt_tokens=int(prompt_tokens), positions=int(positions),
        )
        try:
            yield
        finally:
            self._exit(span)

    def decode_blocks(self, live: int, reserved: int) -> None:
        """Add one decode dispatch's walk to the step's record: the table
        entries its rows' frontiers cover, and the entries reserved."""
        if not self._enabled or self._t0 is None:
            return
        self._decode_blocks[0] += int(live)
        self._decode_blocks[1] += int(reserved)

    def kv_kinds(self, kinds: dict) -> None:
        """Add a decode dispatch's per-kind KV accounting to the step's
        record (a model with a KV state per kind of layer): ``{kind:
        {"blocks_in_use", "blocks_total", "decode_blocks_live",
        "decode_blocks_reserved", "blocks_freed"}}``; counts add up over the
        step's dispatches, the gauges keep the newest."""
        if not self._enabled or self._t0 is None:
            return
        if self._kv_kinds is None:
            self._kv_kinds = {k: dict(v) for k, v in kinds.items()}
            return
        for k, v in kinds.items():
            mine = self._kv_kinds.setdefault(k, {})
            for name, n in v.items():
                if name in ("blocks_in_use", "blocks_total"):  # gauges
                    mine[name] = n
                else:
                    mine[name] = mine.get(name, 0) + n

    def prefill_cells(self, live: int, walked: int) -> None:
        """Add landed chunked prefills' kernel walk to the step's record:
        the cells walked, and those of the slot's whole rectangle."""
        if not self._enabled or self._t0 is None:
            return
        self._prefill_cells[0] += int(live)
        self._prefill_cells[1] += int(walked)

    def prefill_kv_blocks(self, write: str, blocks: int) -> None:
        """Add one chunk dispatch's K/V write to the step's record: the
        arena blocks it landed in, under the form of the write (``tile``
        or ``rows``)."""
        if not self._enabled or self._t0 is None:
            return
        if self._prefill_kv_blocks is None:
            self._prefill_kv_blocks = {}
        acc = self._prefill_kv_blocks
        acc[write] = acc.get(write, 0) + int(blocks)

    def decode_kv_entries(self, write: str, entries: int) -> None:
        """Add one decode or verify dispatch's K/V write to the step's
        record: the entries it landed, under the form of the write
        (``attention``, ``kernel`` or ``scatter``:
        ``obs.metrics.DECODE_KV_WRITES``)."""
        if not self._enabled or self._t0 is None:
            return
        if self._decode_kv_entries is None:
            self._decode_kv_entries = {}
        acc = self._decode_kv_entries
        acc[write] = acc.get(write, 0) + int(entries)

    def recurrent_rows(self, rows: int) -> None:
        """Record the rows holding a recurrent state at a decode dispatch
        (a gauge: the step's newest)."""
        if not self._enabled or self._t0 is None:
            return
        self._recurrent_rows = int(rows)

    def scan_positions(self, real: int, pad: int) -> None:
        """Add one chunk dispatch's positions through the mixers' scan
        to the step's record: prompt tokens and padding."""
        if not self._enabled or self._t0 is None:
            return
        acc = self._scan_positions or {"real": 0, "pad": 0}
        acc["real"] += int(real)
        acc["pad"] += int(pad)
        self._scan_positions = acc

    def sparse_tokens(
        self, scored: int, read: int, live: int, walked: int
    ) -> None:
        """Add one decode dispatch's selection to the step's record (a
        token-selecting model): index keys scored, tokens kept by the
        selection, live context tokens, tokens whose K/V blocks the
        attention streamed — each summed over rows and layers."""
        if not self._enabled or self._t0 is None:
            return
        acc = self._sparse_tokens or {}
        for key, n in (("scored", scored), ("read", read), ("live", live),
                       ("walked", walked)):
            acc[key] = acc.get(key, 0) + int(n)
        self._sparse_tokens = acc

    def exit_passes(self, counts) -> None:
        """Add the tokens of a fetched log to the step's record by the pass
        their logits were read from (a looped model): ``counts`` [passes]."""
        if not self._enabled or self._t0 is None:
            return
        acc = self._exit_passes or [0] * len(counts)
        self._exit_passes = [a + int(b) for a, b in zip(acc, counts)]

    def experts(self, tokens, read=None, steps: int = 0, rows: int = 0) -> None:
        """Add a fetched set of expert counters to the step's record:
        ``tokens`` [E] per expert; for decode microsteps also ``read`` [L]
        distinct experts per layer, how many microsteps and their live
        rows. A prefill dispatch passes ``tokens`` alone."""
        if not self._enabled or self._t0 is None:
            return
        if self._experts is None:
            self._experts = [[0] * len(tokens), None, 0, 0]
        acc = self._experts
        acc[0] = [a + int(b) for a, b in zip(acc[0], tokens)]
        if read is not None:
            acc[1] = [a + int(b) for a, b in zip(acc[1] or [0] * len(read), read)]
            acc[2] += int(steps)
            acc[3] += int(rows)

    def dispatched(self, kind: str, n: int, enq_at: float, in_flight: int,
                   landed_at: Optional[float] = None,
                   busy_seen_at: Optional[float] = None) -> None:
        """Program number ``n`` joined the device's queue at ``enq_at``
        behind ``in_flight`` programs not known to have landed. With none,
        the device had nothing to run since the previous program landed:
        between ``busy_seen_at`` (the last poll that saw it busy) and
        ``landed_at`` (the stamp that found it done; the same moment where
        the host waited for it). ``landed_at=None``: nothing ran before.
        Host time, not excluded from phases."""
        if not self._enabled or self._t0 is None:
            return
        lo = hi = 0.0
        if in_flight == 0 and landed_at is not None:
            if busy_seen_at is None:
                busy_seen_at = landed_at
            lo = max(0.0, enq_at - max(landed_at, self._work_from))
            hi = max(lo, enq_at - max(busy_seen_at, self._work_from))
            self._idle_s += lo
            self._starved_hi_s += hi
            if landed_at > self._t0 and landed_at != self._found[0]:
                # the caller's poll has just found that landing: the phase
                # totals as they stand here are those ``after_landing``
                # starts from, should this step apply the log
                self._found = (landed_at, self._phase_totals(self._clock()))
        self._dispatches.append(
            (n, kind, enq_at - self._t0, int(in_flight), lo, hi)
        )

    def _phase_totals(self, now: float) -> Dict[str, float]:
        """The step's phase totals as they stand at ``now``, the open
        phases' time so far included (a parent's less its open child's)."""
        totals = dict(self._phases)
        inner = 0.0
        for name, start, excluded, _ in reversed(self._stack):
            elapsed = now - start
            totals[name] = totals.get(name, 0.0) + max(
                0.0, elapsed - excluded - inner
            )
            inner = elapsed
        return totals

    def log_landed(self, kind: str, n: int, by: int, enq_at: float,
                   landed_at: Optional[float], exact: bool,
                   waited: bool) -> None:
        """The step holds the log of program ``n`` (enqueued at ``enq_at``
        by step ``by``) and is about to apply it. ``landed_at`` is when it
        reached the host (None: the read failed); ``exact`` whether the
        host was waiting for it at that moment or a poll found it;
        ``waited`` whether this step had to block for it."""
        if not self._enabled or self._t0 is None:
            return
        if landed_at is not None:
            if landed_at <= self._t0:  # an earlier step's poll found it
                at_landing: Dict[str, float] = {}
            elif landed_at == self._found[0]:
                at_landing = self._found[1]
            else:  # this step's drain did, just now
                at_landing = self._phase_totals(self._clock())
            self._landing = (landed_at, at_landing)
        if not waited and kind == "chunk":
            self._host_bound = True
        t0 = self._t0
        self._logs.append([
            n, kind, by, enq_at - t0,
            None if landed_at is None else landed_at - t0,
            bool(exact), bool(waited), None, 0,
        ])

    def log_applied(self, tokens: int) -> None:
        """The newest ``log_landed`` log's tokens (``tokens`` of them) are
        on their requests: a stream's reader can see them from now."""
        if not self._enabled or self._t0 is None or not self._logs:
            return
        log = self._logs[-1]
        log[_APPLIED] = self._clock() - self._t0
        log[_TOKENS] = int(tokens)

    def note_exemplar(self, trace_id: str) -> None:
        """Record an applied row's trace_id — deep-capture steps only."""
        ex = self._exemplars
        if ex is not None and len(ex) < _EXEMPLARS_PER_STEP:
            ex.append(trace_id)

    def end_step(self, rows: int = 0, tokens: int = 0, queued: int = 0,
                 pending: int = 0) -> Optional[StepRecord]:
        if not self._enabled or self._t0 is None:
            return None
        while self._stack:  # unbalanced push (exception paths): close out
            self.pop()
        t0 = self._t0
        wall = max(self._clock() - t0, 0.0)
        self._t0 = None
        self._exit(self._step_span)
        self._step_span = None
        phases = self._phases
        host = sum(phases.values())
        unattributed = max(0.0, wall - host - self._blocked_s)
        lock_waits = None
        if self._lock_base is not None:
            lock_waits = {}
            for k, (n, s) in lockorder.wait_totals().items():
                bn, bs = self._lock_base.get(k, (0, 0.0))
                if n > bn:
                    lock_waits[k] = {"count": n - bn, "wait_s": s - bs}
        rec = StepRecord(
            ts=time.time(), wall_s=wall, phases=phases,
            blocked_s=self._blocked_s, idle_s=self._idle_s,
            unattributed_s=unattributed, rows=int(rows), tokens=int(tokens),
            queued=int(queued), pending=int(pending),
            segments=self._segments, lock_waits=lock_waits,
            exemplars=self._exemplars, prompt_tokens=self._prompt_tokens,
            prefill_positions=self._prefill_positions,
            decode_blocks_live=self._decode_blocks[0],
            decode_blocks_reserved=self._decode_blocks[1],
            prefill_cells_live=self._prefill_cells[0],
            prefill_cells_walked=self._prefill_cells[1],
        )
        rec.kv_kinds = self._kv_kinds
        rec.prefill_kv_blocks = self._prefill_kv_blocks
        rec.decode_kv_entries = self._decode_kv_entries
        rec.recurrent_rows = self._recurrent_rows
        rec.scan_positions = self._scan_positions
        rec.sparse_tokens = self._sparse_tokens
        rec.exit_passes = self._exit_passes
        if self._experts is not None:
            tokens, read, rec.expert_steps, rec.expert_rows = self._experts
            rec.expert_tokens, rec.experts_read = tokens, read or []
        rec.seq, rec.t0 = self.seq, t0
        rec.starved_hi_s = self._starved_hi_s
        rec.logs, rec.dispatches = self._logs, self._dispatches
        if not (rows or queued):
            self._work_from = None  # no work held: the device owes nothing
        # metric feeds OUTSIDE the ring lock (family locks rank below it,
        # but obs never needs to nest — keep the ring hold minimal), and
        # BEFORE the step's last look at the clock: what they cost lies
        # between a log's landing and its tokens' stamp, and is accounted
        for name, dur in phases.items():
            _PHASE_CHILD[name].observe(dur)
        STEP_WALL.observe(wall)
        if wall > 0:
            HOST_OCCUPANCY.set(min(1.0, host / wall))
            DEVICE_IDLE_FRAC.set(min(1.0, self._idle_s / wall))
        if self._starved_hi_s > 0.0:
            _STARVED_LO.inc(self._idle_s)
            _STARVED_HI.inc(self._starved_hi_s)
        if self._host_bound:
            _HOST_BOUND.inc()
        after = None
        if self._landing is not None:
            landed_at, at_landing = self._landing
            after = {
                name: dur - at_landing.get(name, 0.0)
                for name, dur in phases.items()
                if dur > at_landing.get(name, 0.0)
            }
            in_phases = sum(after.values())
        end_at = self._clock()
        rec.end = end_at - t0
        if after is not None:
            after["unattributed"] = (end_at - landed_at) - in_phases
            rec.after_landing = after
        with self._ring_mu:
            if len(self._ring) < self._ring_size:
                self._ring.append(rec)
            else:
                self._ring[self._ring_next] = rec
                self._ring_next = (self._ring_next + 1) % self._ring_size
            self.steps_total += 1
        for log in self._logs:
            if log[_TOKENS] and log[_LANDED] is not None:
                _EMIT_LAG.observe(rec.end - log[_LANDED])
        if self._step_armed and self._armed_left > 0:
            self._capture.append(rec)
            self._armed_left -= 1
            if self._armed_left == 0:
                self._capture_done.set()
        return rec

    # -- readers (any thread) -----------------------------------------------

    def snapshot(self, last_n: Optional[int] = None) -> List[dict]:
        """The ring's records oldest-first (the tail ``last_n`` if given)."""
        with self._ring_mu:
            ordered = (
                self._ring[self._ring_next:] + self._ring[:self._ring_next]
            )
        if last_n is not None:
            ordered = ordered[-int(last_n):]
        return [r.to_dict() for r in ordered]

    def stats(self, last_n: int = 64) -> dict:
        """Aggregates over the tail of the ring: duration-weighted host
        occupancy and device-idle fraction, p50 step wall."""
        with self._ring_mu:
            ordered = (
                self._ring[self._ring_next:] + self._ring[:self._ring_next]
            )
            total = self.steps_total
        tail = ordered[-int(last_n):]
        if not tail:
            return {
                "steps": total, "host_occupancy": 0.0,
                "device_idle_frac": 0.0, "step_wall_p50_ms": 0.0,
            }
        walls = sorted(r.wall_s for r in tail)
        wall_sum = sum(walls)
        host_sum = sum(r.host_s for r in tail)
        idle_sum = sum(r.idle_s for r in tail)
        p50 = walls[(len(walls) - 1) // 2]
        return {
            "steps": total,
            "host_occupancy": (
                min(1.0, host_sum / wall_sum) if wall_sum > 0 else 0.0
            ),
            "device_idle_frac": (
                min(1.0, idle_sum / wall_sum) if wall_sum > 0 else 0.0
            ),
            "step_wall_p50_ms": p50 * 1e3,
        }


def debug_snapshot(limit: int = 32) -> List[dict]:
    """Step-ring tails of every live profiler, for the /debugz flight
    recorder: what the loop was DOING, not just what spans it emitted."""
    out = []
    for p in sorted(_LIVE, key=lambda p: p.name):
        out.append({
            "profiler": p.name,
            "stats": p.stats(),
            "steps": p.snapshot(limit),
        })
    return out
