"""Request-centric distributed tracing (ISSUE 13): TraceContext
propagation across snapshot/restore, dp failover migration and the disagg
hand-off; slow-request exemplars; the flight recorder + ``/debugz``; the
rotating ``TraceWriter``; and the ``trace-report`` CLI.

The contract under test: ONE trace_id follows a request through every
process and replica it crosses — merging the per-replica JSONL files
rebuilds a single span tree with intact parentage (no orphan spans) — and
the exemplar machinery links a latency histogram's slow buckets straight to
trace ids.

``REPLICA_TEST_DP`` (default 2) sets the replica count for the dp/disagg
tests; tier-1 CI reruns this module at REPLICA_TEST_DP=2 with
``PAGED_FORCE_KERNEL=interpret`` so the hand-off trace paths also run
through the Pallas kernel code path.
"""

import json
import os
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs.http import MetricsServer
from llm_sharding_tpu.obs.metrics import REGISTRY, Registry
from llm_sharding_tpu.obs.report import (
    build_traces, load_events, render_report, report_json,
)
from llm_sharding_tpu.obs.trace import (
    SpanRing, TraceContext, TraceWriter, emit_span,
    valid_trace_id,
)
from llm_sharding_tpu.runtime.disagg import DisaggServer
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.faults import FaultPlan
from llm_sharding_tpu.runtime.replicated import ReplicatedServer
from llm_sharding_tpu.runtime.server import PipelineServer

CFG = tiny_llama(num_hidden_layers=8)
DP = int(os.environ.get("REPLICA_TEST_DP", "2"))
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "8"))
CAP = 64


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)


def prompt(seed, n=5):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read()


# ------------------------------------------------------------ context units


def test_trace_context_ids_and_json_roundtrip():
    ctx = TraceContext.new()
    assert valid_trace_id(ctx.trace_id) and valid_trace_id(ctx.span_id)
    assert ctx.parent_id is None
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_id == ctx.span_id
    assert child.span_id != ctx.span_id
    back = TraceContext.from_json(child.to_json())
    assert (back.trace_id, back.span_id, back.parent_id) == (
        child.trace_id, child.span_id, child.parent_id
    )
    assert TraceContext.from_json(None) is None
    # a caller-supplied id is honored only when sane
    assert TraceContext.new(trace_id="my-trace_01").trace_id == "my-trace_01"
    evil = TraceContext.new(trace_id='bad"id\nwith spaces')
    assert valid_trace_id(evil.trace_id)
    assert "\n" not in evil.trace_id


def test_trace_writer_rotation_and_close(tmp_path):
    path = str(tmp_path / "t.jsonl")
    w = TraceWriter(path, max_bytes=2000)
    for i in range(200):
        w.emit("spam", i=i, pad="x" * 40)
    assert os.path.exists(path)
    assert os.path.exists(path + ".1"), "rollover file missing"
    assert os.path.getsize(path) <= 2000
    assert os.path.getsize(path + ".1") <= 2000
    # both files hold ONLY complete JSON lines (rotation never tears one)
    for p in (path, path + ".1"):
        with open(p) as f:
            for line in f:
                assert json.loads(line)["span"] == "spam"
    w.close()
    size = os.path.getsize(path)
    w.emit("after_close")  # must be a no-op, not a crash
    w.close()  # idempotent
    assert os.path.getsize(path) == size


def test_span_ring_bounded_and_disable():
    ring = SpanRing(capacity=4)
    for i in range(10):
        ring.append({"span": "s", "i": i})
    snap = ring.snapshot()
    assert len(snap) == 4 and snap[0]["i"] == 6 and snap[-1]["i"] == 9
    ring.set_enabled(False)
    ring.append({"span": "s", "i": 99})
    assert len(ring.snapshot()) == 4
    ring.set_enabled(True)
    ring.clear()
    assert ring.snapshot() == []


# ------------------------------------------------------ exemplars + /debugz


def test_exemplars_in_prometheus_text_and_statz():
    r = Registry()
    h = r.histogram("t_lat_seconds", "test", buckets=(0.1, 1.0))
    h.observe(0.05)  # no trace_id -> no exemplar for this bucket
    h.observe(0.5, trace_id="trace-slow")
    h.observe(5.0, trace_id="trace-slowest")
    h.observe(0.4, trace_id="trace-smaller")  # smaller within TTL: kept out
    # the DEFAULT exposition stays pure text format 0.0.4 — exemplar
    # syntax there would fail a strict scraper's whole scrape
    plain = r.prometheus_text()
    assert "trace-slow" not in plain and "# EOF" not in plain
    text = r.prometheus_text(openmetrics=True)
    assert '# {trace_id="trace-slow"} 0.5' in text
    assert '# {trace_id="trace-slowest"} 5' in text
    assert "trace-smaller" not in text
    assert text.endswith("# EOF\n")
    # bucket lines without an exemplar stay plain samples
    assert 'le="0.1"} 1\n' in text
    snap = r.json_snapshot()["t_lat_seconds"]["series"][0]
    assert snap["exemplars"]["1"]["trace_id"] == "trace-slow"
    assert snap["exemplars"]["+Inf"]["trace_id"] == "trace-slowest"
    assert snap["exemplars"]["1"]["value"] == 0.5
    assert "0.1" not in snap["exemplars"]
    # OpenMetrics counter metadata drops the _total suffix; samples keep it
    r.counter("t_hits_total", "test").inc()
    om = r.prometheus_text(openmetrics=True)
    assert "# TYPE t_hits counter" in om and "t_hits_total 1" in om
    assert "# TYPE t_hits_total counter" in r.prometheus_text()


def test_exemplar_content_negotiation_on_metrics():
    r = Registry()
    r.histogram("t_neg_seconds", "test", buckets=(1.0,)).observe(
        0.5, trace_id="neg-trace"
    )
    ms = MetricsServer(port=0, registry=r)
    port = ms.start()
    try:
        plain = _get(port, "/metrics").decode()
        assert "neg-trace" not in plain
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/metrics",
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert "openmetrics-text" in resp.headers["Content-Type"]
            om = resp.read().decode()
        assert 'trace_id="neg-trace"' in om
        assert om.endswith("# EOF\n")
    finally:
        ms.stop()


def test_debugz_bundle_schema():
    r = Registry()
    r.counter("t_debugz_total", "test").inc(3)
    emit_span(None, "debugz_probe", dur_s=0.01, src="test", detail=1)
    ms = MetricsServer(
        port=0, registry=r,
        statz_extra={"counters": lambda: {"k": 1}},
        health_provider=lambda: "SERVING",
    )
    port = ms.start()
    try:
        bundle = json.loads(_get(port, "/debugz"))
        assert bundle["health"] == "SERVING"
        assert isinstance(bundle["generated_at"], float)
        assert bundle["counters"] == {"k": 1}
        assert bundle["metrics"]["t_debugz_total"]["series"][0]["value"] == 3
        probes = [
            e for e in bundle["recent_spans"] if e["span"] == "debugz_probe"
        ]
        assert probes and probes[-1]["detail"] == 1
        # /debugz exists alongside the original endpoints
        assert b"t_debugz_total" in _get(port, "/metrics")
    finally:
        ms.stop()


# ----------------------------------------------- trace-report CLI (no jax)


def _write_fake_traces(tmp_path):
    ing = str(tmp_path / "t.ingress")
    srv = str(tmp_path / "t.r0")
    root = TraceContext.new(trace_id="traceA")
    reqctx = root.child()
    wi = TraceWriter(ing)
    wi.emit(
        "ingress", dur_s=2.0, trace_id=root.trace_id, span_id=root.span_id,
        tenant="alice", rid=0, outcome="ok", src="ingress",
    )
    wi.emit(
        "queue", dur_s=0.5, trace_id=root.trace_id, parent=root.span_id,
        tenant="alice", src="ingress",
    )
    wi.close()
    ws = TraceWriter(srv)
    ws.emit(
        "request", dur_s=1.4, trace_id=reqctx.trace_id,
        span_id=reqctx.span_id, parent=reqctx.parent_id, id=5, tokens=8,
        ttft_s=0.6, tenant="alice", src="s0",
    )
    ws.emit(
        "prefill", dur_s=0.5, trace_id=reqctx.trace_id,
        parent=reqctx.span_id, id=5, bucket=8, src="s0",
    )
    ws.emit(
        "decode", dur_s=0.8, trace_id=reqctx.trace_id,
        parent=reqctx.span_id, id=5, tokens=8, src="s0",
    )
    ws.close()
    return ing, srv


def test_trace_report_builds_tree_and_stats(tmp_path):
    ing, srv = _write_fake_traces(tmp_path)
    events = load_events([ing, srv])
    traces = build_traces(events)
    assert list(traces) == ["traceA"]
    tr = traces["traceA"]
    assert tr.root["span"] == "ingress"
    assert tr.orphans() == []
    assert tr.tenant == "alice"
    assert tr.e2e_s == 2.0
    text = render_report(events)
    assert "per-phase latency" in text
    assert "traceA" in text
    assert "alice" in text
    tree = render_report(events, trace_id="traceA")
    assert tree.splitlines()[0] == "trace traceA"
    assert "ingress" in tree and "decode" in tree
    js = report_json(events)
    assert js["traces"] == 1
    assert js["slowest"][0]["trace_id"] == "traceA"
    assert js["slowest"][0]["orphans"] == 0
    phases = {p["phase"] for p in js["phases"]}
    assert {"ingress", "queue", "request", "prefill", "decode"} <= phases
    assert js["latency"]["ttft"]["count"] == 1


def test_trace_report_cli_runs_without_backend(tmp_path, capsys):
    from llm_sharding_tpu import cli

    ing, srv = _write_fake_traces(tmp_path)
    assert cli.main(["trace-report", ing, srv]) == 0
    out = capsys.readouterr().out
    assert "per-phase latency" in out and "traceA" in out
    assert cli.main(["trace-report", "--json", ing, srv]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["traces"] == 1
    assert cli.main(
        ["trace-report", "--trace", "traceA", str(tmp_path / "t.*")]
    ) == 0
    assert "trace traceA" in capsys.readouterr().out
    # --json + --trace honors the filter (single-trace JSON, not summary)
    assert cli.main(
        ["trace-report", "--json", "--trace", "traceA", ing, srv]
    ) == 0
    one = json.loads(capsys.readouterr().out)
    assert one["found"] and one["trace_id"] == "traceA"
    assert one["root_span"] == "ingress" and one["orphans"] == 0
    assert len(one["spans"]) == 5
    assert cli.main(
        ["trace-report", "--json", "--trace", "nope", ing, srv]
    ) == 1
    capsys.readouterr()
    assert cli.main(["trace-report", str(tmp_path / "missing.jsonl")]) == 2


# ------------------------------------------------- serve-path propagation


def test_trace_context_snapshot_restore_roundtrip(params, tmp_path):
    """The trace identity survives a process boundary: requests snapshotted
    mid-flight restore with the SAME trace_id/span ids, and the revived
    daemon's request spans land under them."""
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=CAP)
    ra = srv.submit(prompt(1), 8)
    rb = srv.submit(prompt(2), 6, temperature=0.9, seed=3)
    for _ in range(3):
        srv.step()  # ra mid-decode
    snap = srv.snapshot()
    before = {ra.id: ra.trace, rb.id: rb.trace}
    srv2 = PipelineServer.restore(eng, snap)
    srv2._trace = TraceWriter(str(tmp_path / "restored.jsonl"))
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    for rid, ctx in before.items():
        got = restored[rid].trace
        assert got.trace_id == ctx.trace_id
        assert got.span_id == ctx.span_id
        assert got.parent_id == ctx.parent_id
    srv2.run_until_idle()
    srv2.close()
    events = load_events([str(tmp_path / "restored.jsonl")])
    done = {
        e["id"]: e for e in events if e["span"] == "request"
    }
    assert done[ra.id]["trace_id"] == before[ra.id].trace_id
    assert done[ra.id]["span_id"] == before[ra.id].span_id
    assert done[rb.id]["trace_id"] == before[rb.id].trace_id
    srv.close()


def test_failover_migration_single_trace_no_orphans(params, tmp_path):
    """dp failover: a request that prefills on the doomed replica and
    finishes on a survivor leaves ONE trace — extract span from the dead
    side, migrate span from the router, adopt + request spans from the
    survivor — with parentage intact."""
    tp = str(tmp_path / "dp.jsonl")
    plan = FaultPlan.permanent("replica_step", key=0, start=4)
    srv = ReplicatedServer(
        CFG, params, data_parallel=DP, num_stages=2,
        devices=jax.devices()[: 2 * DP], cache_dtype=jnp.float32,
        capacity=CAP, fault_plan=plan, trace_path=tp,
    )
    reqs = [srv.submit(prompt(10 + i), 10) for i in range(2 * DP)]
    srv.run_until_idle()
    srv.close()
    files = [tp + f".r{d}" for d in range(DP)] + [tp + ".router"]
    assert all(os.path.exists(f) for f in files)
    events = load_events(files)
    traces = build_traces(events)
    # router decision spans: the failover event itself was recorded
    assert any(e["span"] == "failover" for e in events)
    migrated = [
        r for r in reqs
        if traces[r.trace.trace_id].first("migrate") is not None
    ]
    assert migrated, "the failover migrated no traced request"
    for r in reqs:
        assert r.error is None
        tr = traces[r.trace.trace_id]
        assert tr.orphans() == [], f"orphan spans in trace of req {r.id}"
        assert len([e for e in tr.spans if e["span"] == "request"]) == 1
        assert {e["trace_id"] for e in tr.spans} == {r.trace.trace_id}
    for r in migrated:
        tr = traces[r.trace.trace_id]
        assert tr.first("adopt") is not None
        # spans came from BOTH sides of the migration
        srcs = {e.get("src") for e in tr.spans}
        assert len(srcs & {f"r{d}" for d in range(DP)}) >= 2, srcs


def test_disagg_handoff_single_tree(params, tmp_path):
    """ACCEPTANCE (backend half): a disagg request yields one span tree —
    radix/prefill on the prefill replica, handoff (bytes + outcome) from
    the router, adopt + decode + request on the decode replica — under one
    trace_id with no orphan spans."""
    tp = str(tmp_path / "disagg.jsonl")
    srv = DisaggServer(
        CFG, params, data_parallel=DP, num_stages=2,
        devices=jax.devices()[: 2 * DP], cache_dtype=jnp.float32,
        capacity=CAP, kv_block_size=BS, kv_blocks=6 * CAP // BS + 1,
        prefix_cache="hbm",
        roles=["prefill"] + ["decode"] * (DP - 1),
        trace_path=tp,
    )
    p = prompt(77, n=2 * BS + 1)
    req = srv.submit(p, 24)
    srv.run_until_idle()
    srv.close()
    events = load_events(
        [tp + f".r{d}" for d in range(DP)] + [tp + ".router"]
    )
    tr = build_traces(events)[req.trace.trace_id]
    assert tr.orphans() == []
    names = {e["span"] for e in tr.spans}
    assert {
        "request", "prefill", "extract", "handoff", "adopt", "decode",
    } <= names, names
    hand = tr.first("handoff")
    assert hand["outcome"] in ("ok", "cold")
    if hand["outcome"] == "ok":
        assert hand["bytes"] > 0 and hand["streamed"] > 0
    # prefill on the prefill side, decode spans on a decode replica
    assert tr.first("prefill")["src"] == "r0"
    decode_srcs = {
        e["src"] for e in tr.spans if e["span"] == "decode"
    }
    assert decode_srcs & {f"r{d}" for d in range(1, DP)}
    # the request span is the tree node everything parents to
    root = tr.root
    assert root["span"] == "request"
    assert all(
        e.get("parent") == root["span_id"]
        for e in tr.spans if e is not root
    )


def test_ingress_x_trace_id_and_exemplar(params, tmp_path, monkeypatch):
    """ACCEPTANCE (front half): X-Trace-Id is honored end to end — the
    response echoes it, the ingress root + fair-queue spans and the
    backend's request tree all carry it, and it lands as the exemplar on
    the ingress TTFT histogram (and in the /debugz bundle).

    The test OWNS the two pieces of process-wide state it asserts on. A
    bucket's exemplar is bucket-max for ``EXEMPLAR_TTL_S``: any earlier test
    of the worker (``-n 6 --dist loadfile`` puts several files in one
    process) whose slower first token fell into the same bucket of
    ``server_ingress_ttft_seconds{tenant="default"}`` within the last minute
    keeps its own trace id there, and this one — run alone it passes — is
    refused. So the request goes in under a tenant no other test names, and
    the spans go to a flight recorder of this test's own, which nothing
    else can wrap."""
    import http.client

    from llm_sharding_tpu.obs import trace as trace_mod
    from llm_sharding_tpu.runtime.ingress import IngressServer, TenantConfig

    ring = SpanRing()
    monkeypatch.setattr(trace_mod, "FLIGHT_RECORDER", ring)
    tenant = "trace-exemplar-test"
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    tp = str(tmp_path / "ingress_t.jsonl")
    backend = eng.serve(capacity=CAP, trace_path=tp)
    ing = IngressServer(
        backend, poll_interval_s=0.0005, trace_path=tp,
        tenants=[TenantConfig(tenant, key="sk-trace-exemplar")],
    )
    ing.start()
    tid = "pinned-trace-0042"
    try:
        conn = http.client.HTTPConnection("127.0.0.1", ing.port, timeout=120)
        conn.request(
            "POST", "/v1/completions",
            json.dumps({
                "prompt": [int(t) for t in prompt(55)], "max_tokens": 6,
            }),
            {"Content-Type": "application/json", "X-Trace-Id": tid,
             "Authorization": "Bearer sk-trace-exemplar"},
        )
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert resp.getheader("X-Trace-Id") == tid
        conn.close()
    finally:
        ing.stop()
        backend.close()
    events = load_events([tp, tp + ".ingress"])
    tr = build_traces(events)[tid]
    assert tr.orphans() == []
    assert tr.root["span"] == "ingress"
    assert tr.root["outcome"] == "ok"
    names = {e["span"] for e in tr.spans}
    assert {"ingress", "queue", "request", "prefill", "decode"} <= names
    # the request span parents to the ingress root; stage spans to it
    req_span = tr.first("request")
    assert req_span["parent"] == tr.root["span_id"]
    assert tr.first("decode")["parent"] == req_span["span_id"]
    # exemplar: the TTFT histogram's slow bucket names this trace
    fam = REGISTRY.get("server_ingress_ttft_seconds")
    exem = fam.labels(tenant=tenant).snap_exemplars()
    assert {e[0] for e in exem.values()} == {tid}
    # and the flight recorder carried the spans for /debugz
    ring_spans = [e for e in ring.snapshot() if e.get("trace_id") == tid]
    assert {e["span"] for e in ring_spans} >= {"ingress", "request"}


# ------------------------------------------------------ autoscaler pacing


def test_autoscaler_paced_rebalance():
    from llm_sharding_tpu.runtime.autoscale import Autoscaler

    class FakeDisagg:
        def __init__(self):
            self.servers = [object()]
            self._groups = [0]
            self.planner = object()
            self.calls = 0

        def rebalance(self):
            self.calls += 1
            return ("prefill", 0)

        def spawn_replica(self):
            raise AssertionError("load is mid-band; no spawn expected")

        def drain(self, d):
            raise AssertionError("load is mid-band; no drain expected")

    now = [0.0]
    target = FakeDisagg()
    sc = Autoscaler(
        target, min_replicas=1, max_replicas=1,
        load_fn=lambda: 0.5, clock=lambda: now[0],
        rebalance_every_s=10.0,
    )
    for t in (1.0, 5.0, 9.9):
        now[0] = t
        sc.tick(now=t)
    assert target.calls == 0
    sc.tick(now=10.5)
    assert target.calls == 1 and sc.rebalances == 1
    sc.tick(now=12.0)
    assert target.calls == 1  # paced: once per interval, not per tick
    sc.tick(now=21.0)
    assert target.calls == 2
    # a planner-less target is silently skipped
    target.planner = None
    sc.tick(now=32.0)
    assert target.calls == 2


def test_autoscaler_rebalance_defaults_off():
    from llm_sharding_tpu.runtime.autoscale import Autoscaler

    class Boom:
        def __init__(self):
            self.servers = [object()]
            self._groups = [0]
            self.planner = object()

        def rebalance(self):
            raise AssertionError("rebalance_every_s=0 must never call this")

    sc = Autoscaler(
        Boom(), min_replicas=1, max_replicas=1, load_fn=lambda: 0.5,
        clock=lambda: 1e9,
    )
    sc.tick(now=2e9)
