"""Serving telemetry (obs/): registry math, exposition, and live-serve spans.

Covers the ISSUE-1 acceptance surface: histogram bucket/quantile math,
registry thread-safety (concurrent increments sum exactly), Prometheus text
golden output, and a CPU-mesh serve run asserting TTFT/queue-wait spans are
recorded, ``/metrics`` scrapes, ``/statz`` matches ``Counters.snapshot()``,
and the JSONL trace carries admit/chunk/apply spans.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.obs.http import MetricsServer
from llm_sharding_tpu.obs.metrics import (
    REGISTRY, Registry, record_shape_key,
)
from llm_sharding_tpu.runtime.server import Counters

# ---------------------------------------------------------------- registry


def test_histogram_buckets_and_quantiles():
    r = Registry()
    h = r.histogram("h_seconds", "t", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 0.5, 0.5, 0.5, 5.0, 5.0, 5.0, 50.0):
        h.observe(v)
    child = h.labels()
    assert child.counts == [2, 4, 3, 1]  # per-bucket, last is +Inf
    assert child.count == 10
    assert child.sum == pytest.approx(67.1)
    # p50: rank 5 lands in bucket (0.1, 1.0] with cum-before 2, count 4:
    # 0.1 + 0.9 * (5-2)/4 = 0.775
    assert child.quantile(0.5) == pytest.approx(0.775)
    # p90: rank 9 lands in bucket (1.0, 10.0]: 1.0 + 9.0 * (9-6)/3 = 10.0
    assert child.quantile(0.9) == pytest.approx(10.0)
    # p99 lands in +Inf → clamps to the largest finite bound
    assert child.quantile(0.99) == pytest.approx(10.0)
    # empty histogram has no quantiles
    assert r.histogram("h2_seconds", buckets=(1.0,)).labels().quantile(0.5) is None


def test_registry_thread_safety_exact_sums():
    r = Registry()
    c = r.counter("c_total", labels=("who",))
    h = r.histogram("h_seconds", buckets=(0.5,))
    n_threads, n_iters = 8, 5000

    def work(i):
        child = c.labels(who=str(i % 2))
        for _ in range(n_iters):
            child.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(child.value for _, child in c.series())
    assert total == n_threads * n_iters
    assert h.labels().count == n_threads * n_iters
    assert h.labels().counts[0] == n_threads * n_iters


def test_registry_conflicting_reregistration():
    r = Registry()
    r.counter("x_total", labels=("a",))
    # same signature → same family (get-or-create)
    assert r.counter("x_total", labels=("a",)) is r.get("x_total")
    with pytest.raises(ValueError):
        r.gauge("x_total")
    with pytest.raises(ValueError):
        r.counter("x_total", labels=("b",))
    with pytest.raises(ValueError):
        r.counter("bad name")


def test_prometheus_text_golden():
    r = Registry()
    c = r.counter("req_total", "requests", labels=("kind",))
    c.labels(kind="a").inc(3)
    g = r.gauge("depth", "queue depth")
    g.set(7)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert r.prometheus_text() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 7\n"
        "# HELP lat_seconds latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{kind="a"} 3\n'
    )


def test_json_snapshot_shape():
    r = Registry()
    h = r.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    snap = r.json_snapshot()["lat_seconds"]["series"][0]
    assert snap["count"] == 1
    assert snap["buckets"] == {"0.1": 1, "1": 1, "+Inf": 1}
    assert snap["p50"] == pytest.approx(0.05)
    # round-trips through json
    json.loads(r.json_text())


def test_record_shape_key_hit_miss():
    key = ("unique-test-key", 12345)
    assert record_shape_key("test_prog", key) is False  # first sight: miss
    assert record_shape_key("test_prog", key) is True  # repeat: hit
    fam = REGISTRY.get("engine_jit_shape_keys_total")
    assert fam.labels(program="test_prog", result="miss").value >= 1
    assert fam.labels(program="test_prog", result="hit").value >= 1


# ---------------------------------------------------------------- counters


def test_counters_snapshot_roundtrip_across_builds():
    c = Counters(requests_submitted=2, tokens_generated=9)
    snap = c.snapshot()
    assert Counters.from_snapshot(snap) == c
    # unknown keys (a NEWER build's snapshot) are ignored
    snap["some_future_counter"] = 42
    assert Counters.from_snapshot(snap) == c
    # missing keys (an OLDER build's snapshot) default to 0
    assert Counters.from_snapshot({"chunks": 3}) == Counters(chunks=3)


def test_counters_inc_mirrors_registry():
    before = REGISTRY.get("server_chunks_total").value
    c = Counters()
    c.inc("chunks", 2)
    assert c.chunks == 2
    assert REGISTRY.get("server_chunks_total").value == before + 2
    # direct field writes (aggregation, restore) do NOT mirror
    c.chunks += 5
    assert REGISTRY.get("server_chunks_total").value == before + 2


# ----------------------------------------------------------- http endpoint


def test_metrics_server_endpoints():
    r = Registry()
    r.counter("x_total", "x").inc(4)
    ms = MetricsServer(port=0, registry=r, statz_extra={"extra": lambda: {"k": 1}})
    port = ms.start()
    try:
        text = _get(port, "/metrics").decode()
        assert "# TYPE x_total counter\nx_total 4" in text
        statz = json.loads(_get(port, "/statz"))
        assert statz["metrics"]["x_total"]["series"][0]["value"] == 4
        assert statz["extra"] == {"k": 1}
        assert _get(port, "/healthz") == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            _get(port, "/nope")
    finally:
        ms.stop()


def test_healthz_reflects_health_provider():
    """/healthz answers from the attached health state machine: 200 only on
    SERVING, 503 with the state name on DEGRADED/DRAINING, 503 when the
    provider itself dies — so a load balancer can act on it."""
    state = {"v": "SERVING"}
    ms = MetricsServer(
        port=0, registry=Registry(), health_provider=lambda: state["v"]
    )
    port = ms.start()
    try:
        assert _get(port, "/healthz") == b"ok\n"
        for bad in ("DEGRADED", "DRAINING"):
            state["v"] = bad
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(port, "/healthz")
            assert ei.value.code == 503
            assert ei.value.read() == f"{bad}\n".encode()
        state["v"] = "SERVING"
        assert _get(port, "/healthz") == b"ok\n"

        def boom():
            raise RuntimeError("provider died")

        ms.set_health_provider(boom)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/healthz")
        assert ei.value.code == 503 and b"unhealthy" in ei.value.read()
        ms.set_health_provider(None)  # detached: back to bare liveness
        assert _get(port, "/healthz") == b"ok\n"
    finally:
        ms.stop()


def test_state_gauge_one_hot():
    r = Registry()
    sg = r.state_gauge("h_state", "health", states=("A", "B", "C"))
    fam = r.get("h_state")
    assert {v[0]: c.value for v, c in fam.series()} == {
        "A": 0.0, "B": 0.0, "C": 0.0,
    }
    sg.set_state("B")
    assert sg.state == "B"
    assert {v[0]: c.value for v, c in fam.series()} == {
        "A": 0.0, "B": 1.0, "C": 0.0,
    }
    sg.set_state("C")
    assert {v[0]: c.value for v, c in fam.series()} == {
        "A": 0.0, "B": 0.0, "C": 1.0,
    }
    with pytest.raises(ValueError):
        sg.set_state("D")


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read()


# ------------------------------------------------------- prefetch failures


def test_prefetch_error_names_its_chunk():
    from llm_sharding_tpu.runtime.server import _Prefetched

    class Exploding:
        def __array__(self, *a, **k):
            raise RuntimeError("transfer died")

    before = REGISTRY.get("server_fetch_failures_total").value
    p = _Prefetched(Exploding(), tag="chunk m0=17")
    with pytest.raises(RuntimeError, match=r"chunk m0=17"):
        p.get()
    assert REGISTRY.get("server_fetch_failures_total").value == before + 1


class _Slow:
    """A device array in miniature: ready when told, copied when read."""

    def __init__(self, value, fail=0):
        self.value, self.fail = value, fail
        self.ready = self.began = False
        self.reads = 0

    def copy_to_host_async(self):
        self.began = True

    def is_ready(self):
        return self.ready

    def __array__(self, *a, **k):
        self.reads += 1
        if self.reads <= self.fail:
            raise OSError("connection dropped")
        return np.asarray(self.value)


def test_a_direct_read_begins_at_dispatch_and_never_waits_to_say_landed():
    """The step's way: the copy begins when the read is made, a look
    at it reads nothing while the device works, and the first look after
    the device is done finishes it and stamps when."""
    from llm_sharding_tpu.runtime.server import _Prefetched

    h = _Slow([3, 1, 4])
    p = _Prefetched(h, tag="chunk m0=4")
    assert h.began
    assert not p.landed() and h.reads == 0 and p.done_at is None
    h.ready = True
    assert p.landed() and h.reads == 1 and p.done_at is not None
    assert list(p.get()) == [3, 1, 4] and p.handle is None
    p.wait()  # landed: nothing more to read
    assert h.reads == 1


def test_a_direct_read_waits_on_its_own_thread():
    from llm_sharding_tpu.runtime.server import _Prefetched

    h = _Slow([7])
    p = _Prefetched(h, tag="admit slot=0")
    assert list(p.get_retryable()) == [7] and h.reads == 1  # not ready: it waits
    assert p.landed() and p.done_at is not None


def test_a_failed_direct_read_keeps_its_handle_and_names_its_chunk():
    from llm_sharding_tpu.runtime.server import _Prefetched

    before = REGISTRY.get("server_fetch_failures_total").value
    h = _Slow([1, 2], fail=2)
    p = _Prefetched(h, tag="chunk m0=17")
    h.ready = True
    assert p.landed() and p.error is not None and p.handle is h
    assert REGISTRY.get("server_fetch_failures_total").value == before + 1
    with pytest.raises(RuntimeError, match=r"chunk m0=17"):
        p.get()
    with pytest.raises(RuntimeError, match=r"retry failed for chunk m0=17"):
        p.get_retryable()  # the second failure, re-issued from the handle
    assert list(p.get_retryable()) == [1, 2] and p.error is None


# ------------------------------------------------------ live serve telemetry


CFG = None


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny CPU-mesh serve run with tracing on; shared by the telemetry
    assertions below."""
    global CFG
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama
    from llm_sharding_tpu.runtime.engine import PipelineEngine

    CFG = tiny_llama(num_hidden_layers=8)
    params = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    trace_path = str(tmp_path_factory.mktemp("obs") / "trace.jsonl")

    ttft_before = REGISTRY.get("server_ttft_seconds").labels().count
    qwait_before = REGISTRY.get("server_queue_wait_seconds").labels().count

    srv = eng.serve(capacity=64, trace_path=trace_path)
    rng = np.random.default_rng(0)
    reqs = [
        srv.submit(
            rng.integers(1, CFG.vocab_size, 5).astype(np.int32),
            max_new_tokens=6,
        )
        for _ in range(3)
    ]
    srv.run_until_idle()
    srv.close()
    return srv, reqs, trace_path, ttft_before, qwait_before


def test_serve_records_latency_spans(served):
    srv, reqs, _, ttft_before, qwait_before = served
    # one TTFT and one queue-wait observation per admitted request
    assert REGISTRY.get("server_ttft_seconds").labels().count == ttft_before + 3
    assert (
        REGISTRY.get("server_queue_wait_seconds").labels().count
        == qwait_before + 3
    )
    for r in reqs:
        assert r.first_token_at is not None
        assert r.first_token_at >= r.submitted_at
        assert r.last_token_at >= r.first_token_at
    # step phases landed
    phases = REGISTRY.get("server_step_phase_seconds")
    for phase in ("admit", "dispatch", "apply"):
        assert phases.labels(phase=phase).count > 0, phase
    # the admit-bucket ladder rung used by the 5-token prompts
    assert REGISTRY.get("server_admit_bucket_total").labels(bucket="8").value >= 3


def test_serve_statz_matches_counters_and_metrics_scrape(served):
    srv, _, _, _, _ = served
    ms = MetricsServer(port=0, statz_extra={"counters": srv.counters.snapshot})
    port = ms.start()
    try:
        text = _get(port, "/metrics").decode()
        # valid Prometheus text incl. request counters and a TTFT histogram
        assert "# TYPE server_requests_completed_total counter" in text
        assert "# TYPE server_ttft_seconds histogram" in text
        assert 'server_ttft_seconds_bucket{le="+Inf"}' in text
        for line in text.strip().splitlines():
            assert line.startswith("#") or " " in line
        statz = json.loads(_get(port, "/statz"))
        assert statz["counters"] == srv.counters.snapshot()
        for name in (
            "server_ttft_seconds",
            "server_queue_wait_seconds",
            "server_intertoken_seconds",
        ):
            series = statz["metrics"][name]["series"][0]
            assert series["count"] > 0, name
            assert series["p50"] is not None and series["p99"] is not None
    finally:
        ms.stop()


def test_serve_trace_jsonl_spans(served):
    srv, reqs, trace_path, _, _ = served
    with open(trace_path) as f:
        events = [json.loads(line) for line in f]
    spans = {e["span"] for e in events}
    assert {"admit", "request"} <= spans
    # the per-step loop spans left the span stream (ISSUE 55): what they
    # said is in the step ring, asserted below
    assert not {"chunk", "apply"} & spans
    for e in events:
        assert isinstance(e["ts"], float)
    completions = {e["id"]: e for e in events if e["span"] == "request"}
    assert set(completions) == {r.id for r in reqs}
    for e in completions.values():
        assert e["tokens"] == 6
        assert e["ttft_s"] > 0
        assert e["dur_s"] >= e["ttft_s"]
    # every chunk dispatch is in the step ring, in the queue's order, and
    # its log was applied in that order by the step that dispatched it or a
    # later one
    steps = srv.stepline_snapshot()
    chunks = [d["n"] for s in steps for d in s["dispatches"]
              if d["kind"] == "chunk"]
    assert chunks and chunks == sorted(chunks)
    applied = [(s["seq"], log) for s in steps for log in s["logs"]
               if log["kind"] == "chunk"]
    assert [log["n"] for _, log in applied] == chunks[:len(applied)]
    assert all(log["by"] <= seq for seq, log in applied)
    assert sum(log["tokens"] for s in steps for log in s["logs"]) == 18


def test_complete_line_reports_zero_rate_not_inf(served, caplog):
    """The ``tok/s=inf`` fix: a zero/unset duration reports 0.0."""
    srv, _, _, _, _ = served
    import logging

    from llm_sharding_tpu.runtime.server import Request

    req = Request(999, np.asarray([1, 2], np.int32), 4)
    req.started_at = None  # never admitted → no window
    srv._rows.append(req)  # temporary row slot for _apply_token
    row = len(srv._rows) - 1
    srv._mirror_len = np.append(srv._mirror_len, 0)
    srv._mirror_budget = np.append(srv._mirror_budget, 1)
    with caplog.at_level(logging.INFO, "llm_sharding_tpu.server"):
        # budget 1 → this token finishes the request regardless of its value
        srv._apply_token(row, req, 5)
    del srv._rows[row]
    line = next(m for m in caplog.messages if "id=999" in m)
    assert "tok/s=0.0" in line
    assert "inf" not in line
    assert "queue_wait=" in line


def test_cli_serve_metrics_port_and_stats(tmp_path, capsys, monkeypatch):
    """The daemon wiring end to end: ``serve --metrics-port --trace-path``
    serves Prometheus text + /statz JSON from the live process, ``:stats``
    prints the telemetry snapshot in-band, and the trace file lands."""
    import io
    import socket

    from llm_sharding_tpu import cli
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama
    from llm_sharding_tpu.runtime import engine as engine_mod
    from llm_sharding_tpu.utils import shard_store

    cfg = tiny_llama(num_hidden_layers=8, vocab_size=64)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    shards = str(tmp_path / "tiny_f32")
    shard_store.save_shards(cfg, params, shards)

    class IdTokenizer:
        def __call__(self, text):
            return {"input_ids": [ord(c) % 60 + 1 for c in text]}

        def decode(self, ids, skip_special_tokens=True):
            return "".join(chr(int(i) % 26 + 97) for i in ids)

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    probed = {}

    class ProbingStdin(io.StringIO):
        """Feeds one prompt, scrapes the live daemon's endpoints once that
        prompt has fully streamed, then issues ``:stats`` and EOF."""

        def __iter__(self):
            yield "hello\n"
            probed["metrics"] = _get(port, "/metrics").decode()
            probed["statz"] = json.loads(_get(port, "/statz"))
            probed["debugz"] = json.loads(_get(port, "/debugz"))
            yield ":stats\n"

    monkeypatch.setattr("sys.stdin", ProbingStdin())
    trace = str(tmp_path / "trace.jsonl")
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
            "--metrics-port", str(port), "--trace-path", trace,
        ]
    )
    assert rc == 0
    assert "# TYPE server_ttft_seconds histogram" in probed["metrics"]
    assert "server_requests_completed_total" in probed["metrics"]
    # /statz carries THIS daemon's exact counter tally (1 request so far)
    assert probed["statz"]["counters"]["requests_completed"] == 1
    assert probed["statz"]["metrics"]["server_ttft_seconds"]["series"][0][
        "count"
    ] > 0
    captured = capsys.readouterr()
    assert "metrics: http://127.0.0.1:" in captured.err
    # :stats printed the JSON snapshot to stderr
    stats_line = next(
        l for l in captured.err.splitlines()
        if l.startswith("{") and '"metrics"' in l
    )
    parsed = json.loads(stats_line)
    assert parsed["counters"]["requests_completed"] == 1
    assert "server_queue_wait_seconds" in parsed["metrics"]
    # the trace file got the admission's and the request's spans; what the
    # per-step chunk/apply spans said is in the step ring /debugz carries
    with open(trace) as f:
        spans = {json.loads(line)["span"] for line in f}
    assert {"admit", "request"} <= spans and not {"chunk", "apply"} & spans
    ring = max(  # the daemon's own: the newest of the process's live rings
        (p for p in probed["debugz"]["recent_steps"] if p["steps"]),
        key=lambda p: p["steps"][-1]["ts"],
    )
    logs = [(s["seq"], log) for s in ring["steps"] for log in s["logs"]]
    assert sum(log["tokens"] for _, log in logs) == 4
    assert all(log["by"] <= seq for seq, log in logs)
    assert [log["n"] for _, log in logs] == sorted(log["n"] for _, log in logs)


def test_engine_placement_swap_metrics():
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama
    from llm_sharding_tpu.parallel.placement import PlacementSpec
    from llm_sharding_tpu.runtime.engine import PipelineEngine

    cfg = tiny_llama(num_hidden_layers=8)
    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    swaps = REGISTRY.get("engine_placement_swaps_total")
    before = swaps.value
    eng = PipelineEngine(cfg, params, num_stages=4, cache_dtype=jnp.float32)
    assert swaps.value == before + 1  # constructor applies the placement
    assert REGISTRY.get("engine_pipeline_stages").value == 4
    eng.apply_placement(PlacementSpec.balanced(8, 2))
    assert swaps.value == before + 2
    assert REGISTRY.get("engine_pipeline_stages").value == 2
    assert REGISTRY.get("engine_placement_swap_seconds").labels().count >= 2


def test_exposition_survives_client_disconnect(capfd):
    """ISSUE 9 satellite: a scraper that closes its socket early must not
    splatter a handler-thread traceback — the write guard swallows the
    broken pipe and the server keeps answering the next request."""
    import socket
    import time as _time
    import urllib.request as _url

    from llm_sharding_tpu.obs.http import write_ignoring_disconnect

    # unit: the guard reports the disconnect instead of raising
    class _Gone:
        def write(self, data):
            raise BrokenPipeError("client went away")

    class _Reset:
        def write(self, data):
            raise ConnectionResetError("RST")

    class _Fine:
        wrote = b""

        def write(self, data):
            self.wrote += data

    assert write_ignoring_disconnect(_Gone(), b"x") is False
    assert write_ignoring_disconnect(_Reset(), b"x") is False
    f = _Fine()
    assert write_ignoring_disconnect(f, b"body") is True
    assert f.wrote == b"body"

    # integration: a socket that closes right after the request line —
    # the handler thread must survive and the endpoint must keep serving
    r = Registry()
    r.counter("c_total", "t").inc(3)
    ms = MetricsServer(port=0, registry=r)
    port = ms.start()
    try:
        for _ in range(3):
            s = socket.create_connection(("127.0.0.1", port), timeout=5)
            s.sendall(
                b"GET /statz HTTP/1.1\r\nHost: x\r\n"
                b"Connection: close\r\n\r\n"
            )
            # vanish without reading the response
            s.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER,
                b"\x01\x00\x00\x00\x00\x00\x00\x00",  # RST on close
            )
            s.close()
        _time.sleep(0.2)  # let the handler threads hit the dead sockets
        with _url.urlopen(f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
            assert b"c_total 3" in resp.read()
    finally:
        ms.stop()
    err = capfd.readouterr().err
    assert "Traceback" not in err, err
