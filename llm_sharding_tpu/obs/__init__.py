"""Serving telemetry: metrics registry, latency spans, HTTP exposition.

The modules, all stdlib-only (importable before jax backend init):

- ``metrics`` — thread-safe labeled counters/gauges/histograms with quantile
  readout, Prometheus text + JSON snapshot, and the process-wide
  ``REGISTRY`` every subsystem records into;
- ``trace``   — request-centric tracing: ``TraceContext`` propagation,
  the rotating JSONL span writer behind the server's ``trace_path=`` knob,
  and the in-memory ``FLIGHT_RECORDER`` span ring;
- ``stepline`` — the continuous step profiler: one ``StepRecord`` per
  serve-loop step (disjoint host-phase durations, device-blocked wait,
  idle-bubble estimate) in a bounded ring, the derived
  ``server_host_occupancy`` / ``server_device_idle_frac`` gauges, the
  lock-wait metric sink, and the armable ``/profilez`` deep capture;
- ``setupline`` — set-up's own account: the process-wide ``SETUP`` ledger of
  ``setup.*`` spans (engine staging, server construction, every compile or
  cache load by program and shape key, each program's first run), served
  as ``/statz``'s ``setup`` and rendered as the restart table in the log;
- ``http``    — ``MetricsServer``: a background stdlib-``http.server``
  thread serving ``/metrics`` (Prometheus, with slow-request exemplars),
  ``/statz`` (JSON), ``/debugz`` (the flight-recorder postmortem bundle),
  ``/profilez`` (the step profiler's deep-capture window) and
  ``/healthz``, wired into the CLI via ``--metrics-port``;
- ``report``  — the ``trace-report`` / ``step-report`` CLIs' span-tree
  reconstruction and per-phase latency/step attribution over merged
  per-replica JSONL files and capture bundles.

Metric names and the span schema are documented in README.md
(§ Observability, § Tracing & postmortems, § Step profiling).
"""

from .metrics import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS,
    DEFAULT_RATE_BUCKETS,
    REGISTRY,
    Registry,
    StateGauge,
    record_shape_key,
)
from .trace import (  # noqa: F401
    FLIGHT_RECORDER,
    SpanRing,
    TraceContext,
    TraceWriter,
    emit_span,
)
from .stepline import (  # noqa: F401
    PHASES,
    StepProfiler,
    StepRecord,
    debug_snapshot,
)
from .setupline import SETUP, SetupLedger  # noqa: F401
from .http import MetricsServer  # noqa: F401
