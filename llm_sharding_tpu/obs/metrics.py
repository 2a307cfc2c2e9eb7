"""Process-wide metrics registry: counters, gauges, fixed-bucket histograms.

The serving stack's structured replacement for the reference's tagged stdout
prints (``node_worker.py:115-125``) and for the bare ``Counters`` tally this
repo carried through round 5. One ``Registry`` holds every metric family;
families are labeled (Prometheus-style), children are created on first use,
and every mutation is lock-protected so concurrent request/pump threads sum
exactly. Two read-out formats:

- ``prometheus_text()`` — the text exposition format (scrapeable by any
  Prometheus-compatible collector; served by ``obs.http.MetricsServer``);
- ``json_snapshot()`` — a JSON-friendly dict with histogram quantiles
  (p50/p90/p99, linear interpolation within the fixed buckets) for
  ``/statz`` and the ``:stats`` daemon control command.

Pure stdlib — importable from the device-program modules (parallel/serve.py)
without dragging jax in, and safe to import before backend initialization.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from ..analysis.lockorder import named_lock
from .setupline import SETUP, compile_seconds

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# Latency buckets (seconds): sub-ms host work through minute-scale compiles.
DEFAULT_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
# Throughput buckets (tokens/sec): CPU-smoke single digits to chip thousands.
DEFAULT_RATE_BUCKETS = (
    1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1000.0, 2000.0, 5000.0, 10000.0,
)

#: How long a bucket's exemplar stays "fresh": within the TTL only a larger
#: observation replaces it (bucket-max semantics — the slowest recent
#: request wins); past it any new observation does (recency semantics — a
#: p99 spike from an hour ago must not shadow today's).
EXEMPLAR_TTL_S = 60.0


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render without the trailing .0.
    Non-finite values (an inf/NaN observation poisons a histogram sum
    forever) render as Prometheus spellings instead of crashing the scrape
    — isfinite must be checked BEFORE floor (floor raises on inf/NaN)."""
    f = float(v)
    if not math.isfinite(f):
        return "+Inf" if f > 0 else ("-Inf" if f < 0 else "NaN")
    if f == math.floor(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _exemplar_str(ex) -> str:
    """OpenMetrics exemplar suffix for one bucket line ('' when absent)."""
    if ex is None:
        return ""
    tid, v, ts = ex
    return (
        f' # {{trace_id="{_escape_label(tid)}"}} {_fmt(v)} {repr(float(ts))}'
    )


def _label_str(names: Tuple[str, ...], values: Tuple[str, ...]) -> str:
    if not names:
        return ""
    inner = ",".join(
        f'{n}="{_escape_label(v)}"' for n, v in zip(names, values)
    )
    return "{" + inner + "}"


class _CounterChild:
    __slots__ = ("_lock", "_value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock  # shardlint: lock obs.metrics.family
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError(f"counters only go up (inc by {n})")
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class _GaugeChild:
    __slots__ = ("_lock", "_value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock  # shardlint: lock obs.metrics.family
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class _HistogramChild:
    __slots__ = ("_lock", "bounds", "counts", "sum", "count", "exemplars")

    def __init__(self, lock: threading.Lock, bounds: Tuple[float, ...]):
        self._lock = lock  # shardlint: lock obs.metrics.family
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        # per-bucket slow-request exemplar: index -> (trace_id, value, ts).
        # Sparse (most buckets never see a traced observation); see
        # EXEMPLAR_TTL_S for the replacement policy.
        self.exemplars: Dict[int, Tuple[str, float, float]] = {}

    def observe(self, v: float, trace_id: Optional[str] = None) -> None:
        v = float(v)
        with self._lock:
            i = 0
            for i, b in enumerate(self.bounds):  # noqa: B007
                if v <= b:
                    break
            else:
                i = len(self.bounds)
            self.counts[i] += 1
            self.sum += v
            self.count += 1
            if trace_id is not None:
                cur = self.exemplars.get(i)
                now = time.time()
                if (
                    cur is None or v >= cur[1]
                    or now - cur[2] > EXEMPLAR_TTL_S
                ):
                    self.exemplars[i] = (str(trace_id), v, now)

    def snap(self):
        """Atomic (counts, sum, count) copy — exposition must read under the
        same lock observe() writes under, or a concurrent scrape can emit a
        count that disagrees with its own sum/buckets."""
        with self._lock:
            return list(self.counts), self.sum, self.count

    def snap_exemplars(self) -> Dict[int, Tuple[str, float, float]]:
        with self._lock:
            return dict(self.exemplars)

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (0 < q <= 1) by linear interpolation within
        the fixed buckets — the standard Prometheus ``histogram_quantile``
        estimate, computed host-side. ``None`` with no observations; samples
        landing in the +Inf bucket clamp to the largest finite bound."""
        counts, _, total = self.snap()
        return _quantile_from(self.bounds, counts, total, q)


def _quantile_from(bounds, counts, total, q: float) -> Optional[float]:
    if total == 0:
        return None
    rank = q * total
    cum = 0
    for i, c in enumerate(counts):
        prev = cum
        cum += c
        if cum >= rank and c > 0:
            if i >= len(bounds):
                return bounds[-1] if bounds else None
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            return lo + (hi - lo) * (rank - prev) / c
    return bounds[-1] if bounds else None


_CHILD_TYPES = {
    "counter": _CounterChild,
    "gauge": _GaugeChild,
    "histogram": _HistogramChild,
}


class _Family:
    """One named metric family; labeled children created on first use.
    Unlabeled families proxy ``inc/set/dec/observe/value`` straight to their
    single child so call sites stay terse."""

    def __init__(
        self,
        kind: str,
        name: str,
        help: str,
        label_names: Tuple[str, ...],
        buckets: Optional[Tuple[float, ...]] = None,
    ):
        self.kind = kind
        self.name = name
        self.help = help
        self.label_names = label_names
        self.buckets = buckets
        self._lock = named_lock("obs.metrics.family")
        self._children: Dict[Tuple[str, ...], object] = {}
        if not label_names:
            self._children[()] = self._make_child()

    def _make_child(self):
        if self.kind == "histogram":
            return _HistogramChild(self._lock, self.buckets)
        return _CHILD_TYPES[self.kind](self._lock)

    def labels(self, *values, **kw):
        if kw:
            if values:
                raise ValueError("pass label values positionally OR by name")
            values = tuple(str(kw[n]) for n in self.label_names)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name} takes labels {self.label_names}, got {values}"
            )
        with self._lock:
            child = self._children.get(values)
            if child is None:
                child = self._children[values] = self._make_child()
            return child

    # unlabeled convenience proxies ------------------------------------
    def _solo(self):
        if self.label_names:
            raise ValueError(f"{self.name} is labeled: use .labels(...)")
        return self._children[()]

    def inc(self, n: float = 1.0) -> None:
        self._solo().inc(n)

    def set(self, v: float) -> None:
        self._solo().set(v)

    def dec(self, n: float = 1.0) -> None:
        self._solo().dec(n)

    def observe(self, v: float, trace_id: Optional[str] = None) -> None:
        self._solo().observe(v, trace_id=trace_id)

    @property
    def value(self) -> float:
        return self._solo().value

    def series(self):
        with self._lock:
            return sorted(self._children.items())


class StateGauge:
    """A one-hot state machine over a labeled gauge family: exactly one
    ``state`` label holds 1.0 at any time (the Prometheus idiom for enum
    state — ``server_health_state{state="SERVING"} 1`` — scrapers alert on
    ``{state="DEGRADED"} == 1`` without string parsing). ``set_state``
    serializes writers under its own lock, so concurrent transitions can
    never interleave into two states at 1; a scrape can at worst observe
    the one-hot mid-flip, never a stale extra state left behind."""

    __slots__ = ("_family", "states", "_state", "_set_lock")

    def __init__(self, family: "_Family", states: Tuple[str, ...]):
        self._family = family
        self.states = states
        self._state: Optional[str] = None
        self._set_lock = named_lock("obs.metrics.stategauge")
        for s in states:  # materialize every label so scrapes see the 0s
            family.labels(state=s).set(0.0)

    def set_state(self, state: str) -> None:
        if state not in self.states:
            raise ValueError(
                f"unknown state {state!r}; expected one of {self.states}"
            )
        with self._set_lock:
            for s in self.states:
                self._family.labels(state=s).set(1.0 if s == state else 0.0)
            self._state = state

    @property
    def state(self) -> Optional[str]:
        return self._state


class Registry:
    """Thread-safe named collection of metric families. Registration is
    get-or-create: re-registering the same (name, kind, labels) returns the
    existing family (module reloads and multiple servers share one tally);
    a conflicting re-registration raises."""

    def __init__(self):
        self._lock = named_lock("obs.metrics.registry")
        self._families: Dict[str, _Family] = {}

    def _register(self, kind, name, help, labels, buckets=None) -> _Family:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        labels = tuple(labels)
        for ln in labels:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.label_names != labels or (
                    kind == "histogram" and fam.buckets != buckets
                ):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}{fam.label_names}"
                    )
                return fam
            fam = _Family(kind, name, help, labels, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "", labels: Sequence[str] = ()):
        return self._register("counter", name, help, labels)

    def gauge(self, name: str, help: str = "", labels: Sequence[str] = ()):
        return self._register("gauge", name, help, labels)

    def histogram(
        self,
        name: str,
        help: str = "",
        labels: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS,
    ):
        buckets = tuple(sorted(float(b) for b in buckets))
        if not buckets:
            raise ValueError("histogram needs at least one finite bucket")
        return self._register("histogram", name, help, labels, buckets)

    def state_gauge(
        self, name: str, help: str = "", states: Sequence[str] = ()
    ) -> StateGauge:
        """A one-hot enum gauge (see ``StateGauge``), labeled ``state``."""
        if not states:
            raise ValueError("state_gauge needs at least one state")
        return StateGauge(
            self._register("gauge", name, help, ("state",)), tuple(states)
        )

    def get(self, name: str) -> Optional[_Family]:
        with self._lock:
            return self._families.get(name)

    def _sorted_families(self):
        with self._lock:
            return sorted(self._families.items())

    # ------------------------------------------------------------- readout

    def prometheus_text(self, openmetrics: bool = False) -> str:
        """Text exposition. Default: pure Prometheus text format 0.0.4 —
        NO exemplars, because 0.0.4 allows only an optional timestamp after
        the sample value and a strict parser fails the whole scrape on
        anything more. ``openmetrics=True`` emits the OpenMetrics flavor
        instead (what a scraper negotiates via ``Accept:
        application/openmetrics-text`` — the standard channel for
        exemplars): slow-request exemplars ride the histogram bucket lines
        (``… # {trace_id="…"} v ts``), counter metadata drops the
        ``_total`` suffix as the spec requires, and the body terminates
        with ``# EOF``."""
        out = []
        for name, fam in self._sorted_families():
            meta_name = (
                name[: -len("_total")]
                if openmetrics and fam.kind == "counter"
                and name.endswith("_total") else name
            )
            if fam.help:
                out.append(f"# HELP {meta_name} {fam.help}")
            out.append(f"# TYPE {meta_name} {fam.kind}")
            for values, child in fam.series():
                ls = _label_str(fam.label_names, values)
                if fam.kind == "histogram":
                    counts, total_sum, _ = child.snap()
                    exem = (
                        child.snap_exemplars() if openmetrics else {}
                    )
                    cum = 0
                    for i, (b, c) in enumerate(zip(fam.buckets, counts)):
                        cum += c
                        le = _label_str(
                            fam.label_names + ("le",), values + (_fmt(b),)
                        )
                        out.append(
                            f"{name}_bucket{le} {cum}"
                            + _exemplar_str(exem.get(i))
                        )
                    cum += counts[-1]
                    le = _label_str(
                        fam.label_names + ("le",), values + ("+Inf",)
                    )
                    out.append(
                        f"{name}_bucket{le} {cum}"
                        + _exemplar_str(exem.get(len(fam.buckets)))
                    )
                    out.append(f"{name}_sum{ls} {_fmt(total_sum)}")
                    out.append(f"{name}_count{ls} {cum}")
                else:
                    out.append(f"{name}{ls} {_fmt(child.value)}")
        if openmetrics:
            out.append("# EOF")
        return "\n".join(out) + "\n"

    def json_snapshot(self) -> dict:
        """JSON-friendly view: histograms carry count/sum/p50/p90/p99 and the
        per-bucket cumulative counts; counters/gauges carry the value."""
        snap: dict = {}
        for name, fam in self._sorted_families():
            series = []
            for values, child in fam.series():
                entry: dict = {"labels": dict(zip(fam.label_names, values))}
                if fam.kind == "histogram":
                    # one atomic snap feeds buckets, count, sum AND the
                    # quantiles — the whole entry is self-consistent
                    counts, total_sum, total = child.snap()
                    cum, buckets = 0, {}
                    for b, c in zip(fam.buckets, counts):
                        cum += c
                        buckets[_fmt(b)] = cum
                    buckets["+Inf"] = cum + counts[-1]
                    entry.update(
                        count=total,
                        sum=total_sum,
                        p50=_quantile_from(fam.buckets, counts, total, 0.50),
                        p90=_quantile_from(fam.buckets, counts, total, 0.90),
                        p99=_quantile_from(fam.buckets, counts, total, 0.99),
                        buckets=buckets,
                    )
                    exem = child.snap_exemplars()
                    if exem:
                        # keyed by bucket upper bound; a p99 spike on /statz
                        # links straight to its trace_id
                        entry["exemplars"] = {
                            (
                                _fmt(fam.buckets[i])
                                if i < len(fam.buckets) else "+Inf"
                            ): {
                                "trace_id": tid,
                                "value": v,
                                "ts": ts,
                            }
                            for i, (tid, v, ts) in sorted(exem.items())
                        }
                else:
                    entry["value"] = child.value
                series.append(entry)
            snap[name] = {"type": fam.kind, "help": fam.help, "series": series}
        return snap

    def json_text(self) -> str:
        return json.dumps(self.json_snapshot(), sort_keys=True)


#: The process-wide default registry every subsystem records into. Tests
#: that need isolation construct their own ``Registry``.
REGISTRY = Registry()


# -- paged KV memory (runtime/blocks.py + runtime/server.py) ----------------
# Defined here (not in the server module) so the three gauges exist — and
# show 0 — on /statz and the :stats control line even before the first
# paged server is constructed; the server's load-gauge sweep keeps them
# current, summed over live paged servers like server_queue_depth.
KV_BLOCKS_TOTAL = REGISTRY.gauge(
    "server_kv_blocks_total",
    "Allocatable KV arena blocks across live paged servers (the reserved "
    "trash block excluded)",
)
KV_BLOCKS_IN_USE = REGISTRY.gauge(
    "server_kv_blocks_in_use",
    "KV arena blocks currently held by live requests or shared prefixes",
)
ARENA_BYTES = REGISTRY.gauge(
    "server_arena_bytes",
    "Device bytes of the pooled KV arena across live paged servers, by "
    "storage dtype (K + V codes plus, for quantized int8/fp8 arenas, the "
    "per-block-per-head f32 scale arenas — computed via "
    "runtime/blocks.BlockAllocator.bytes_per_block, so HBM savings from "
    "--kv-dtype are observable, not just asserted)",
    labels=("dtype",),
)
KV_ENTRY_BYTES = REGISTRY.gauge(
    "server_kv_entry_bytes",
    "Bytes ONE token of ONE layer holds in the paged KV arena of the newest "
    "paged server: 2 x kv heads x head dim x itemsize, or a latent cache's "
    "single padded entry (deepseek_v3: [c_kv | k_pe], 1,280 in bf16)",
)
# a model with a KV state per kind of layer (window and full attention in one
# stack, models/mimo_v2.py): one pool, one table and one entry size per kind.
# Named apart from the unlabeled gauges above, which stay the FULL layers'
# pool (a metric family has one set of labels).
KV_KIND_BLOCKS_TOTAL = REGISTRY.gauge(
    "server_kv_kind_blocks_total",
    "Allocatable KV arena blocks of each kind of attention layer's pool "
    "(kind = full | swa) across live servers of a windowed model; a "
    "token-selecting model: kind = kv | index, the two arenas of the ONE pool",
    labels=("kind",),
)
KV_KIND_BLOCKS_IN_USE = REGISTRY.gauge(
    "server_kv_kind_blocks_in_use",
    "KV arena blocks of each kind's pool currently held by live rows: a "
    "window layer's pool holds what the window can still reach",
    labels=("kind",),
)
KV_KIND_ENTRY_BYTES = REGISTRY.gauge(
    "server_kv_kind_entry_bytes",
    "Bytes ONE token of ONE layer of a kind holds in that kind's arena "
    "(kv heads x (padded key + value) x itemsize) of the newest windowed "
    "server",
    labels=("kind",),
)
# a token-selecting model (a learned sparse-attention indexer,
# ``cfg.sparse_attn``): per decode step, summed over layers, host-side from
# the length mirrors — read / live is the share of the context a step's
# attention KEEPS (100% for a program that attends every live token), walked
# / live the share whose K/V blocks it streams to do so
SPARSE_TOKENS_SCORED = REGISTRY.counter(
    "server_sparse_tokens_scored_total",
    "Index keys a decode step's queries were scored against (a row's live "
    "context once it is longer than topk; 0 while the selection is "
    "everything and nothing is scored), summed over rows and layers",
)
#: forms a decode step's search can take (``ops/paged_attention.select_path``)
SELECT_BACKENDS = ("kernel", "interpret", "xla")
SELECT_BACKEND = REGISTRY.gauge(
    "server_select_backend",
    "Live servers of a token-selecting model by the form a decode step's "
    "search takes (ops/paged_attention.select_path at the slot's rows and "
    "the window's columns: select_mask's own resolution): kernel = ONE "
    "Pallas call a layer (select_topk) that holds the slot's scores in VMEM "
    "and searches the live rows only, xla = the digit search as XLA "
    "operations over every row (the CPU path; on a TPU, a slot of more than "
    "16 rows or a window that is no whole number of lane tiles: a server "
    "whose search fell back says so here), interpret = the kernel emulated "
    "off-TPU. One-hot for a single-server process, all zero where no live "
    "server's model selects",
    labels=("backend",),
)
SPARSE_TOKENS_READ = REGISTRY.counter(
    "server_sparse_tokens_read_total",
    "Tokens kept by the selection, the ones a decode step's attention "
    "attends: min(context, topk) a row and layer, summed over rows and layers",
)
SPARSE_TOKENS_LIVE = REGISTRY.counter(
    "server_sparse_tokens_live_total",
    "Live context tokens of the rows in a decode step of a token-selecting "
    "model, summed over rows and layers",
)
SPARSE_TOKENS_WALKED = REGISTRY.counter(
    "server_sparse_tokens_walked_total",
    "Tokens whose K/V blocks a decode step's attention streamed: the rows' "
    "live context a layer (the selection is a mask over the decode kernel's "
    "walk, not a gather of the kept tokens), summed over rows and layers",
)
KV_WINDOW_BLOCKS_FREED = REGISTRY.counter(
    "server_kv_window_blocks_freed_total",
    "Window-layer KV blocks handed back to their pool because the window "
    "passed them (a row still decoding; blocks of finished rows not "
    "counted)",
)
DECODE_KIND_BLOCKS_LIVE = REGISTRY.counter(
    "server_decode_kind_blocks_live_total",
    "Table entries the paged decode kernel had to walk, per kind of "
    "attention layer (a window layer: from the window's first block to the "
    "frontier), per decode step, host-side from the length mirrors",
    labels=("kind",),
)
DECODE_KIND_BLOCKS_RESERVED = REGISTRY.counter(
    "server_decode_kind_blocks_reserved_total",
    "Table entries the tables of the rows a decode step served reserve, "
    "per kind of attention layer",
    labels=("kind",),
)
KV_WASTE_FRAC = REGISTRY.gauge(
    "server_kv_waste_frac",
    "1 - live tokens / allocated token slots over the in-use blocks: the "
    "internal fragmentation of the paged KV pool (dense serving's "
    "equivalent figure is 1 - live/capacity per row). COLD prefix-cache "
    "blocks (radix-tree-held, no row mapping them) are excluded from the "
    "slot denominator — they are reusable capacity, not waste. Shared "
    "prefix tokens count once per mapping row, so heavy sharing can "
    "drive this to 0",
)

# -- automatic prefix cache (runtime/radix.py) ------------------------------
PREFIX_HIT_TOKENS = REGISTRY.counter(
    "server_prefix_cache_hit_tokens_total",
    "Prompt tokens served from the radix prefix cache instead of being "
    "prefilled (summed over admissions on live servers), by the tier the "
    "tokens lived in when the match was taken: hbm = already arena-"
    "resident, host = streamed back from the pinned host pool, disk = "
    "promoted from the memory-mapped disk pool. The saved prefill FLOPs "
    "scale with the sum",
    labels=("tier",),
)
PREFIX_HIT_RATE = REGISTRY.gauge(
    "server_prefix_cache_hit_rate",
    "Cumulative prefix-cache hit rate over live servers: cache-served "
    "prompt tokens / cache-eligible prompt tokens (requests without an "
    "explicit PrefixHandle or embeddings entry). 0 with the cache off "
    "or no eligible traffic yet",
)
KV_HOST_TIER_BLOCKS = REGISTRY.gauge(
    "server_kv_host_tier_blocks",
    "Prefix-cache blocks currently demoted to the pinned host-RAM pool "
    "across live servers (streamed back to HBM on a later radix hit)",
)
KV_DISK_TIER_BLOCKS = REGISTRY.gauge(
    "server_kv_disk_tier_blocks",
    "Prefix-cache blocks currently spilled to the bounded on-disk pool "
    "across live servers (memory-mapped entry files; promoted "
    "disk→host→arena on a later radix hit, and the pool survives "
    "restarts)",
)
GLOBAL_INDEX_ENTRIES = REGISTRY.gauge(
    "server_global_index_entries",
    "Live {prefix-hash, replica} entries in the cluster-global radix "
    "index — the map replicas publish their tree contents into and the "
    "fleet router consults before placing a request (deepest match "
    "first, then warmest tier)",
)

#: Decode-attention implementations a live server can run
#: (``ops/paged_attention`` dispatch; "dense" = non-paged serving,
#: "interpret" = the Pallas kernel emulated off-TPU via
#: PAGED_FORCE_KERNEL).
ATTN_BACKENDS = ("kernel", "interpret", "xla", "dense")
ATTN_BACKEND = REGISTRY.gauge(
    "server_attn_backend",
    "Live servers by resolved decode-attention backend: kernel = the "
    "Pallas paged kernel streaming only each row's mapped arena blocks, "
    "xla = the exact gather fallback, interpret = the kernel emulated "
    "off-TPU, dense = non-paged serving. One-hot over the labels for a "
    "single-server process; a count per backend otherwise",
    labels=("backend",),
)
ATTN_BLOCKS_READ = REGISTRY.counter(
    "server_attn_blocks_read_total",
    "KV arena blocks attended by paged decode steps, summed over live "
    "rows and ring cycles (host-side estimate from the length mirrors: "
    "ceil(len / block_size) per row per decode/verify step). Multiply by "
    "block_size x Nkv x Dh x 2 x dtype bytes x layers for an "
    "attention-bytes-per-step estimate; the dense equivalent reads "
    "capacity slots per row regardless of length",
)

DECODE_BLOCKS_LIVE = REGISTRY.counter(
    "server_decode_blocks_live_total",
    "Table entries the paged decode kernel had to walk: per decode/verify "
    "step, each live row's blocks up to its written frontier — "
    "ceil(written columns / block_size), admission padding included "
    "(host-side, from the length mirrors). A dead row walks none",
)
DECODE_BLOCKS_RESERVED = REGISTRY.counter(
    "server_decode_blocks_reserved_total",
    "Table entries the same steps' block tables reserved: every row the "
    "kernel was called for, dead ones included, times the table width. "
    "live / reserved is the share of the table's width that is real work",
)

#: Chunked-prefill implementations a dispatch can take: ``kernel`` = the
#: Pallas flash-style chunked-prefill kernel over the arena (interpret
#: mode counts here — it is the same code path emulated off-TPU),
#: ``xla`` = the exact in-op gather fallback over the arena, ``gather``
#: = the dense full-window slice path (non-paged serving).
PREFILL_PATHS = ("kernel", "xla", "gather")
PREFILL_PATH = REGISTRY.gauge(
    "server_prefill_path",
    "Chunked-prefill attention path of the most recent chunk dispatch, "
    "one-hot over {kernel, xla, gather}: kernel = the Pallas "
    "chunked-prefill kernel streaming table-named arena blocks "
    "(interpret-emulated off-TPU counts as kernel), xla = the arena "
    "gather inside the op (exact fallback), gather = dense (non-paged) "
    "full-window prefill",
    labels=("path",),
)
PREFILL_BLOCKS_READ = REGISTRY.counter(
    "server_prefill_blocks_read_total",
    "KV arena blocks attended by chunked-prefill dispatches, summed over "
    "admitting rows per chunk (host-side: ceil((prefix_offset + "
    "chunk_end) / block_size) per row — the written frontier each "
    "chunk's queries attend). Multiply by block bytes x layers for a "
    "prefill-attention-HBM estimate; the retired gather path moved the "
    "row's WHOLE mapped window in AND out per chunk on top of this",
)
#: The two forms of a prefill chunk's K/V write
#: (``ops/paged_attention.write_chunk_kv``): whole-block tiles, or the
#: decode write's rows.
PREFILL_KV_WRITES = ("tile", "rows")
PREFILL_KV_BLOCKS_WRITTEN = REGISTRY.counter(
    "server_prefill_kv_blocks_written_total",
    "KV arena blocks a chunked-prefill dispatch's fresh keys and values "
    "land in, per layer, summed over admitting rows per chunk (host-side: "
    "ceil(chunk / block_size) per row), by the form the chunk program's "
    "statics chose: write=tile — whole (heads, block_size, head_dim) "
    "blocks through the table, a chunk of whole blocks over a plain arena; "
    "write=rows — the decode write's row-wise scatter (a chunk under a "
    "block, an int8/fp8 arena). tile / (tile + rows) is the share of "
    "prefill writes that took the block-sized form",
    labels=("write",),
)
#: The three forms of a decode step's K/V write
#: (``ops/paged_attention.paged_attention_write``): the attention call
#: stores the entry itself; the write kernel ``paged_kv_write`` (what is left
#: to it: a selecting model's index keys); ``write_block_kv``'s scatter.
DECODE_KV_WRITES = ("attention", "kernel", "scatter")
DECODE_KV_ENTRIES_WRITTEN = REGISTRY.counter(
    "server_decode_kv_entries_written_total",
    "Fresh key/value entries a decode or verify dispatch lands in the "
    "paged arena, per layer, summed over live rows per step (host-side: "
    "one entry a row a decode step, K + 1 a verify), by the form the step "
    "program's statics chose: write=attention — stored by the attention "
    "kernel itself from the frontier cell it holds, the arena left in "
    "place (one entry a row, a plain arena, the attention on its kernel); "
    "write=kernel — one sublane tile a row moved by the write kernel "
    "paged_kv_write (what is left to it: a selecting model's index keys, "
    "one a row beside its K/V entry); write=scatter — the row-wise scatter "
    "(a verify's K + 1 entries, an int8/fp8 arena, context parallel, the "
    "XLA attention path). attention / (attention + scatter) is the share "
    "of K/V entries that cost no call of their own",
    labels=("write",),
)
# a model with recurrent layers (models/nemotron_h.py, models/jamba.py): a
# recurrent state of fixed size a request, indexed by row beside the paged arena
RECURRENT_ROWS_IN_USE = REGISTRY.gauge(
    "server_recurrent_rows_in_use",
    "Rows holding a live request's recurrent state (a mixer's float32 state "
    "and conv tail in every mixer layer) across live servers of a model "
    "with recurrent layers; host-side, from the rows in flight",
)
RECURRENT_ROW_BYTES = REGISTRY.gauge(
    "server_recurrent_row_bytes",
    "Bytes ONE request's recurrent state holds over ALL of a stage's mixer "
    "layers (layers x ModelConfig.recurrent_row_bytes: the state and the "
    "conv's last kernel - 1 inputs, float32) of the newest server of a model "
    "with recurrent layers: fixed, whatever the context",
)
#: paths a decode step's state update can take (``ops/ssm.ssm_step_rows``)
RECURRENT_BACKENDS = ("kernel", "interpret", "xla")
RECURRENT_BACKEND = REGISTRY.gauge(
    "server_recurrent_backend",
    "Live servers of a model with recurrent layers by the path their decode "
    "step advances the live rows' state on (ops/ssm.rows_backend at the "
    "server's resolved attention backend and the mixer's shapes): kernel = "
    "ONE Pallas call a mixer layer that reads and writes each live row's "
    "state once where it lies, xla = a loop over the live rows (the CPU "
    "path; on a TPU, a shape the kernel cannot tile), interpret = the "
    "kernel emulated off-TPU. One-hot for a single-server process, all zero "
    "where no live server's model has recurrent layers",
    labels=("backend",),
)
#: paths a prefill chunk's scan can take (``ops/ssm.scan_path``)
RECURRENT_SCAN_PATHS = ("block", "kernel", "interpret", "xla")
RECURRENT_SCAN_PATH = REGISTRY.gauge(
    "server_recurrent_scan_path",
    "Live servers of a model with recurrent layers by the path a prefill "
    "chunk's scan takes (ops/ssm.scan_path): block = Mamba-2's block form, "
    "matrix products inside blocks of positions (XLA); kernel = Mamba-1's "
    "scan in time as ONE Pallas call a mixer layer, the state resident "
    "while the positions loop inside it; xla = that scan as lax.scan over "
    "positions (the CPU path; on a TPU, a shape the kernel cannot tile); "
    "interpret = the kernel emulated off-TPU. One-hot for a single-server "
    "process, all zero where no live server's model has recurrent layers",
    labels=("path",),
)
#: forms a Mamba-1 mixer's decode step can take (``ops/ssm.mixer_step_path``)
RECURRENT_MIXER_STEPS = ("fused", "split")
RECURRENT_MIXER_STEP = REGISTRY.gauge(
    "server_recurrent_mixer_step",
    "Live servers of a model with Mamba-1 mixers by the form a mixer "
    "layer's decode step takes between its two projections "
    "(ops/ssm.mixer_step_path at the server's resolved attention backend, "
    "the mixer's shapes and its leaves' types): fused = ONE Pallas call a "
    "layer — the conv step, w_x, the three norms, w_dt, softplus and the "
    "state update, the state AND the conv's tail advanced where they lie, "
    "the live rows only; split = the conv step and the path to dt as XLA "
    "operations around the state update (server_recurrent_backend says "
    "which that is): the CPU path, a shape the kernel cannot tile, "
    "quantised w_x / w_dt. One-hot for a single-server process, all zero "
    "where no live server's model has Mamba-1 mixers",
    labels=("path",),
)
PREFILL_SCAN_KINDS = ("real", "pad")
PREFILL_SCAN_POSITIONS = REGISTRY.counter(
    "server_prefill_scan_positions_total",
    "Positions a chunked-prefill dispatch puts through the state-space scan "
    "(server_recurrent_scan_path says which), per mixer layer (slot rows x chunk a dispatch; "
    "host-side): kind=real — prompt tokens, which advance the state; "
    "kind=pad — padding (a short row, an empty row of the slot), which "
    "has dt = 0 and leaves it as it was. pad / (real + pad) is the scan's "
    "wasted share",
    labels=("kind",),
)
PREFILL_CELLS_LIVE = REGISTRY.counter(
    "server_prefill_cells_live_total",
    "Cells the chunked-prefill kernel walked, summed over the layer calls "
    "of the chunks whose counters have landed: a cell is one grid step — "
    "a (row, key/value head, query tile) scoring one group of table "
    "entries — and only those a real query may attend are in the grid "
    "(device-side: the grid's own length, ops/paged_attention."
    "prefill_walk). 0 where the XLA path serves prefill",
)
PREFILL_CELLS_WALKED = REGISTRY.counter(
    "server_prefill_cells_walked_total",
    "Cells the same layer calls would walk over every row of the slot and "
    "the table's whole width: rows x key/value heads x query tiles x "
    "groups of table entries. live / walked is the share of that "
    "rectangle that is real work",
)
PREFILL_POSITIONS = REGISTRY.counter(
    "server_prefill_positions_total",
    "Token positions computed by prefill dispatches (serve_admit, "
    "serve_prefill_chunk), rows x positions each: kind=prompt are real "
    "prompt tokens (a radix hit's matched prefix is not prefilled and not "
    "counted), kind=pad the rest — empty rows of the slot and the padding "
    "up to the admit bucket or chunk. pad / (prompt + pad) is the share of "
    "prefill compute that no prompt needed",
    labels=("kind",),
)


MOE_EXPERT_TOKENS = REGISTRY.counter(
    "server_moe_expert_tokens_total",
    "A model with sparse experts: (token, expert) pairs each expert "
    "received from live rows and prompt positions, summed over layers "
    "(dead rows of a slot and pad positions route nowhere and are not "
    "counted). Uneven counts are uneven load",
    labels=("expert",),
)
MOE_PAIRS_ROUTED = REGISTRY.counter(
    "server_moe_pairs_routed_total",
    "A model with sparse experts: (token, expert) pairs the router chose "
    "for live rows and prompt positions, over all of the layer's experts "
    "and summed over layers",
)
MOE_PAIRS_HELD = REGISTRY.counter(
    "server_moe_pairs_held_total",
    "Of server_moe_pairs_routed_total, the pairs that fell on experts this "
    "chip holds (a chip's share of the experts; all of them where it holds "
    "every expert): the others form no tile and are not read for",
)
MOE_ZERO_PAIRS = REGISTRY.counter(
    "server_moe_zero_pairs_total",
    "Of server_moe_pairs_routed_total, the pairs that fell on zero-compute "
    "experts (longcat_flash: ids past the real experts, which return their "
    "input): they form no tile, read nothing and cost a scaled add; 0 for "
    "a model without such experts",
)
# a looped model (``cfg.passes`` > 1: the same layers run several times a
# token, an exit gate over the passes' closed states chooses the one the head
# reads): nothing is counted — no label is made — for a model whose layers
# run once
EXIT_PASS = REGISTRY.counter(
    "server_exit_pass_total",
    "A looped model: tokens whose logits were read from pass `pass` (the "
    "first at which the exit gate's running probability reached the "
    "model's threshold, else the last; \"0\" .. str(passes - 1)), from the "
    "step programs' logs; every pass runs for every token whatever the gate "
    "says",
    labels=("pass",),
)
MOE_EXPERTS_READ = REGISTRY.gauge(
    "server_moe_experts_read",
    "A model with sparse experts: mean distinct experts read per layer per "
    "decode microstep over the newest applied chunk log (k at one live "
    "row, at most k x the live rows, never above the layer's experts)",
)


def set_prefill_path(path: str) -> None:
    """One-hot flip of ``server_prefill_path`` (the chunk-dispatch-site
    analogue of the ``server_attn_backend`` sweep)."""
    if path not in PREFILL_PATHS:
        raise ValueError(
            f"unknown prefill path {path!r}; expected one of "
            f"{PREFILL_PATHS}"
        )
    for p in PREFILL_PATHS:
        PREFILL_PATH.labels(path=p).set(1.0 if p == path else 0.0)


# -- replica supervision (runtime/replicated.py) ----------------------------
# Defined here like the KV gauges: the failover/migration counters and the
# per-replica state gauge exist — and show 0 / no series — before the first
# ReplicatedServer is constructed, so /statz and :stats always carry them.
REPLICA_FAILOVERS = REGISTRY.counter(
    "server_replica_failovers_total",
    "Replicas the router classified as FAILED (step raised, or containment "
    "events crossed the failure threshold inside the window) and failed "
    "over: quarantined, live requests migrated to survivors, then closed",
)
REPLICA_DRAINS = REGISTRY.counter(
    "server_replica_drains_total",
    "Elective replica drains (stop admitting, migrate every live request "
    "out, close): the scale-down half of dp elasticity",
)
REPLICA_SPAWNS = REGISTRY.counter(
    "server_replica_spawns_total",
    "Replicas spawned onto a freed device group (weights re-staged from "
    "the shared host arrays): the scale-up half of dp elasticity",
)
REQUESTS_MIGRATED = REGISTRY.counter(
    "server_requests_migrated_total",
    "Live requests moved between replicas during failover/drain, by "
    "outcome (ok = re-admitted on a survivor with its stream intact, "
    "failed = no survivor could adopt it — the request fails typed)",
    labels=("outcome",),
)

#: Router-level per-replica states: the three server health states, plus
#: QUARANTINED (classified failed; migration in progress) and OFFLINE (no
#: live replica on the device group — drained/failed-over, spawnable).
REPLICA_STATES = (
    "SERVING", "DEGRADED", "DRAINING", "QUARANTINED", "OFFLINE",
)
REPLICA_STATE = REGISTRY.gauge(
    "server_replica_state",
    "Per-replica supervision state, one-hot per replica label (the replica "
    "label is the device-group index, stable across drain/spawn cycles): "
    "exactly one state is 1 for each replica",
    labels=("replica", "state"),
)


def set_replica_state(replica, state: str) -> None:
    """One-hot flip of ``server_replica_state`` for one replica label (the
    per-replica analogue of ``StateGauge.set_state`` — a labeled StateGauge
    per replica would need dynamic registration; this keeps one family)."""
    if state not in REPLICA_STATES:
        raise ValueError(
            f"unknown replica state {state!r}; expected one of "
            f"{REPLICA_STATES}"
        )
    r = str(replica)
    for s in REPLICA_STATES:
        REPLICA_STATE.labels(replica=r, state=s).set(1.0 if s == state else 0.0)


# -- disaggregated prefill/decode serving (runtime/disagg.py) ---------------
# Defined here like the replica metrics: the families exist — and show 0 —
# before the first DisaggServer is constructed.
DISAGG_HANDOFFS = REGISTRY.counter(
    "server_disagg_handoffs_total",
    "Prefill→decode request hand-offs, by outcome (ok = KV blocks streamed "
    "and the decode replica resumed through the arena-gathered prefix — "
    "zero re-prefill FLOPs; cold = adopted without streamable KV (the "
    "decode side re-prefills, token-identically); retried = a transient "
    "kv_handoff fault deferred the hand-off one sweep; fallback = a "
    "permanent fault or refused adopt left the request decoding where the "
    "supervision layer could place it; no_target = no decode-capable "
    "replica live, the request keeps decoding on its prefill replica; "
    "failed = no replica could adopt the extracted request — it fails "
    "typed)",
    labels=("outcome",),
)
CP_STREAM_SHARDS = REGISTRY.counter(
    "server_cp_stream_shards_total",
    "Per-shard block-stream passes through a context-parallel paged arena "
    "(reads that gather blocks from their owner shard and writes that land "
    "blocks on the adopter's owner shard), by outcome (ok = the shard's "
    "slice moved; error = the pass raised — injected cp_shard_stream "
    "faults and real transfer failures both land here). Incremented only "
    "at cp>1; each snapshot, hand-off, host-tier demote/restore, or "
    "migration touches every owner shard of the rows it moves",
    labels=("outcome",),
)
HANDOFF_BYTES = REGISTRY.counter(
    "server_handoff_bytes_total",
    "Host bytes of KV block data streamed between replicas (prefill→decode "
    "hand-offs and cross-replica radix fills; quantized arenas stream "
    "codes + scales, so the figure reflects the wire cost, not the "
    "logical bf16 size)",
)
#: Replica roles in a disaggregated deployment: ``prefill`` replicas admit
#: fresh requests and hand their KV off after the first token, ``decode``
#: replicas resume them, ``unified`` replicas do both (the classic mode).
REPLICA_ROLES = ("prefill", "decode", "unified")
REPLICA_ROLE = REGISTRY.gauge(
    "server_replica_role",
    "Per-replica serving role, one-hot per replica label (the replica "
    "label is the device-group index): exactly one role is 1 for each "
    "replica of a disaggregated router; role assignment survives "
    "drain/spawn cycles on the group",
    labels=("replica", "role"),
)


def set_replica_role(replica, role: str) -> None:
    """One-hot flip of ``server_replica_role`` for one replica label (the
    role analogue of ``set_replica_state``)."""
    if role not in REPLICA_ROLES:
        raise ValueError(
            f"unknown replica role {role!r}; expected one of {REPLICA_ROLES}"
        )
    r = str(replica)
    for x in REPLICA_ROLES:
        REPLICA_ROLE.labels(replica=r, role=x).set(1.0 if x == role else 0.0)


DISAGG_TTFT_ERROR = REGISTRY.gauge(
    "server_disagg_ttft_error",
    "Relative |predicted − observed| / observed TTFT of the most recent "
    "planner-routed request: how well the profiler's fitted latency "
    "models track the live system (persistently high error means the "
    "profile.json was fitted on different hardware or load)",
)


# -- production ingress (runtime/ingress.py + runtime/fairness.py) ---------
# Defined here like the replica metrics: the families exist — and show 0 —
# on /statz before the first IngressServer is constructed.
INGRESS_REQUESTS = REGISTRY.counter(
    "server_ingress_requests_total",
    "HTTP requests through the ingress, by tenant and outcome (ok = "
    "completed, rejected_rate / rejected_tenant_queue = per-tenant "
    "early shed with 429, rejected_overload / rejected_draining = global "
    "shed with 503, deadline = budget expired (shed in queue or "
    "mid-decode), disconnect = client went away mid-stream (row "
    "cancelled, KV freed), failed = backend containment or a shutdown "
    "that interrupted the stream (finish_reason \"cancelled\"), "
    "bad_request, unauthorized = no tenant matched the credentials "
    "(tenant label \"unknown\"), fault = injected http_request fault)",
    labels=("tenant", "outcome"),
)
INGRESS_ACTIVE = REGISTRY.gauge(
    "server_ingress_active_streams",
    "HTTP requests currently dispatched to the backend with a live "
    "client attached (queued-in-ingress requests are not active yet)",
)
INGRESS_QUEUED = REGISTRY.gauge(
    "server_ingress_queued",
    "Requests waiting in the ingress fair queue for backend dispatch, "
    "summed over tenants",
)
INGRESS_TTFT = REGISTRY.histogram(
    "server_ingress_ttft_seconds",
    "HTTP arrival to first committed token, by tenant (includes the "
    "fair-queue wait — the figure the flood-isolation chaos test bounds "
    "for the well-behaved tenant)",
    labels=("tenant",),
)
TENANT_QUEUED = REGISTRY.gauge(
    "server_tenant_queued",
    "Requests waiting in the ingress fair queue, per tenant",
    labels=("tenant",),
)
TENANT_SERVICE = REGISTRY.counter(
    "server_tenant_service_tokens_total",
    "Accumulated service per tenant in tokens, by kind (prefill = prompt "
    "tokens charged at backend dispatch, decode = committed tokens "
    "charged as they stream): the quantity the weighted fair queue "
    "schedules on",
    labels=("tenant", "kind"),
)
TENANT_THROTTLED = REGISTRY.counter(
    "server_tenant_throttled_total",
    "Per-tenant early sheds at the ingress door, by reason (rate = "
    "token-bucket limit, queue = per-tenant queued-work cap) — each one "
    "a 429 with Retry-After, never a queue-timeout death",
    labels=("tenant", "reason"),
)

# -- load-driven autoscaling (runtime/autoscale.py) -------------------------
AUTOSCALE_SPAWNS = REGISTRY.counter(
    "server_autoscale_spawns_total",
    "Replica spawns initiated by the autoscaler (a subset of "
    "server_replica_spawns_total, which also counts :spawn and API calls)",
)
AUTOSCALE_DRAINS = REGISTRY.counter(
    "server_autoscale_drains_total",
    "Replica drains initiated by the autoscaler (a subset of "
    "server_replica_drains_total)",
)
AUTOSCALE_REPLICAS = REGISTRY.gauge(
    "server_autoscale_replicas",
    "Live replica count as of the autoscaler's last tick",
)
AUTOSCALE_LOAD = REGISTRY.gauge(
    "server_autoscale_load",
    "The load signal the autoscaler last evaluated: (backend queued + "
    "in-flight + ingress fair-queue depth) / live slot capacity — >1 "
    "means work is waiting that no live slot can take",
)


# -- compile/shape-key visibility -----------------------------------------

_SHAPE_KEYS_SEEN: set = set()
_SHAPE_KEYS_LOCK = named_lock("obs.metrics.shape_keys")
_SHAPE_KEYS = REGISTRY.counter(
    "engine_jit_shape_keys_total",
    "Host-side mirror of the jit program cache: first sight of a "
    "(program, static-shape key) is a miss (a compile), repeats are hits",
    labels=("program", "result"),
)
# what each miss then cost, from the set-up ledger's ``setup.compile`` span
# (obs/setupline.py): "which program recompiled, and did the cache have it"
_COMPILE_SECONDS = REGISTRY.histogram(
    "server_compile_seconds",
    "Wall time of building one program: tracing + lowering + the backend's "
    "compile (cache=miss|off) or the persistent cache's load (cache=hit); "
    "program is the shape-key name, '-' for a compile no dispatch site "
    "announced",
    labels=("program", "cache"),
    buckets=(0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0),
)
SETUP.on_compile = lambda span: _COMPILE_SECONDS.labels(
    program=span["program"], cache=span["cache"]
).observe(compile_seconds(span))


def _load_gauge(name: str) -> int:
    """A load gauge as the server's last sweep left it (0 with no server)."""
    fam = REGISTRY.get(name)
    return 0 if fam is None else int(fam.value)


def record_shape_key(program: str, key) -> bool:
    """Record one dispatch of a jitted serving program under its host-visible
    shape key (the static args + array shapes that key the jit cache).
    Returns True on a hit (the key was seen before — the compiled program is
    reused), False on a miss (this dispatch compiles). Recompile costs stop
    being silent: a serve daemon whose bucket ladder or placement churn keeps
    compiling shows up as a growing ``result="miss"`` count."""
    k = (program, key)
    with _SHAPE_KEYS_LOCK:
        hit = k in _SHAPE_KEYS_SEEN
        if not hit:
            _SHAPE_KEYS_SEEN.add(k)
    _SHAPE_KEYS.labels(program=program, result="hit" if hit else "miss").inc()
    if not hit:
        # the compile this dispatch pays is named in the set-up ledger, with
        # the load it stalls: rows and queue as of the last gauge sweep
        SETUP.miss(
            program, key, in_flight=_load_gauge("server_slots_active"),
            queued=_load_gauge("server_queue_depth"),
        )
    return hit
