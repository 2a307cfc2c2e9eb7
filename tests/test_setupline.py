"""Set-up's own account (ISSUE 41): the ``obs/setupline`` ledger, the spans the
engine and the server leave in it, the compile spans assembled from jax's
monitoring events by program and shape key, and the benchmark's readers.

A CPU run says which spans exist, how they nest and what they name — never
what set-up costs on the chip.
"""

import importlib.util
import json
import logging
import os
import threading
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs import metrics, setupline
from llm_sharding_tpu.obs.http import MetricsServer
from llm_sharding_tpu.obs.setupline import (
    BACKEND_EVENT, CACHE_HIT_EVENT, CACHE_LOAD_EVENT, CACHE_MISS_EVENT,
    LOWER_EVENT, SETUP, TRACE_EVENT, SetupLedger, compile_seconds, render,
    self_seconds,
)
from llm_sharding_tpu.runtime.engine import PipelineEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tiny_llama(num_hidden_layers=4)
SERVE = dict(capacity=64, kv_block_size=8, kv_blocks=33, prefill_chunk=16)


class Clock:
    """An injected clock: time moves only when a test says so."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


def ledger():
    clock = Clock()
    return SetupLedger(clock=clock), clock


# ------------------------------------------------------- (a) the ledger alone

def test_spans_nest_and_self_times_sum_to_the_roots_duration():
    led, clock = ledger()
    with led.span("setup.engine") as root:
        clock.tick(1.0)
        with led.span("setup.engine.stack", what="layers") as stack:
            clock.tick(2.0)
            stack["bytes"] = 7
        with led.span("setup.engine.put") as put:
            clock.tick(3.0)
            with led.span("setup.engine.stack", what="head"):
                clock.tick(0.5)
            clock.tick(1.5)
        clock.tick(0.25)
    spans = led.snapshot()
    by_id = {s["id"]: s for s in spans}
    assert [s["name"] for s in spans] == [
        "setup.engine", "setup.engine.stack", "setup.engine.put",
        "setup.engine.stack",
    ]
    assert [s["parent"] for s in spans] == [
        None, root["id"], root["id"], put["id"]]
    assert by_id[stack["id"]]["bytes"] == 7 and spans[3]["what"] == "head"
    own = self_seconds(spans)
    assert own[root["id"]] == pytest.approx(1.25)
    assert own[put["id"]] == pytest.approx(4.5)
    assert sum(own.values()) == pytest.approx(root["end"] - root["start"])
    assert root["end"] - root["start"] == pytest.approx(8.25)


def test_a_span_left_open_by_an_error_parents_nothing_more():
    led, clock = ledger()
    with pytest.raises(RuntimeError):
        with led.span("setup.server"):
            led.begin("setup.server.host")  # never ended: the body raised
            clock.tick(1.0)
            raise RuntimeError("half-way")
    with led.span("setup.engine"):
        clock.tick(1.0)
    server, host, engine = led.snapshot()
    assert host["end"] is None and host["parent"] == server["id"]
    assert server["end"] is not None and engine["parent"] is None
    assert host["id"] not in self_seconds(led.snapshot())


def test_the_ledger_is_bounded():
    led = SetupLedger(clock=Clock(), capacity=3)
    for i in range(5):
        with led.span("setup.engine", i=i):
            pass
    assert [s["i"] for s in led.snapshot()] == [2, 3, 4]


def build(led, clock, fun, *, cache=None, inner_trace=True):
    """The events of one jit call's compile, as jax sends them."""
    if inner_trace:  # a jitted function called inside reports first
        clock.tick(0.2)
        led.on_duration(TRACE_EVENT, 0.2, fun_name="inner")
    clock.tick(0.3)
    led.on_duration(TRACE_EVENT, 0.5 if inner_trace else 0.3, fun_name=fun)
    clock.tick(1.0)
    led.on_duration(LOWER_EVENT, 1.0, fun_name=fun)
    # jax asks a cache for a key whether or not one is configured
    led.on_event("/jax/compilation_cache/compile_requests_use_cache")
    if cache == "hit":
        clock.tick(2.0)
        led.on_event(CACHE_HIT_EVENT)
        led.on_duration(CACHE_LOAD_EVENT, 2.0)
        led.on_duration(BACKEND_EVENT, 2.1, fun_name=fun)
    else:
        clock.tick(4.0)
        if cache == "miss":
            led.on_event(CACHE_MISS_EVENT)
        led.on_duration(BACKEND_EVENT, 4.0, fun_name=fun)


@pytest.mark.parametrize("cache,seconds", [
    ("hit", 0.5 + 1.0 + 2.0), ("miss", 0.5 + 1.0 + 4.0),
    (None, 0.5 + 1.0 + 4.0),
])
def test_a_compile_is_one_span_by_program_key_and_cache(cache, seconds):
    led, clock = ledger()
    seen = []
    led.on_compile = seen.append
    led.miss("serve_chunk", (2, 1, "xla"), in_flight=3, queued=4)
    assert led.outstanding()
    t0 = clock.t
    build(led, clock, "jit(serve_chunk)", cache=cache)
    first, span = led.snapshot()
    assert first["name"] == "setup.first_run" and first["end"] is None
    assert span["name"] == "setup.compile" and span["parent"] == first["id"]
    assert (span["program"], span["key"]) == ("serve_chunk", repr((2, 1, "xla")))
    assert span["cache"] == (cache or "off") and span["fun"] == "jit(serve_chunk)"
    # the inner function's trace lies inside the outer's: counted once
    assert span["trace_s"] == pytest.approx(0.5)
    assert span["lower_s"] == pytest.approx(1.0)
    assert span["cache_load_s"] == pytest.approx(2.0 if cache == "hit" else 0.0)
    assert (span["in_flight"], span["queued"]) == (3, 4)
    assert span["start"] == pytest.approx(t0) and span["end"] == clock.t
    assert compile_seconds(span) == pytest.approx(seconds)
    assert seen == [span] and not led.outstanding()


def test_a_small_program_on_the_way_does_not_take_the_tag():
    led, clock = ledger()
    led.miss("serve_prefill_chunk", (16,))
    build(led, clock, "jit(convert_element_type)", inner_trace=False)
    assert led.outstanding()
    build(led, clock, "jit(serve_prefill_chunk)")
    build(led, clock, "jit(<lambda>)", inner_trace=False)  # no tag set
    programs = [(s["program"], s["key"]) for s in led.snapshot()
                if s["name"] == "setup.compile"]
    assert programs == [("-", ""), ("serve_prefill_chunk", "(16,)"), ("-", "")]


def test_a_trace_with_no_compile_after_it_joins_none():
    led, clock = ledger()
    clock.tick(1.0)
    led.on_duration(TRACE_EVENT, 1.0, fun_name="shape_only")  # eval_shape
    clock.tick(30.0)
    build(led, clock, "jit(f)", inner_trace=False)
    (span,) = led.snapshot()
    assert span["trace_s"] == pytest.approx(0.3)
    assert span["end"] - span["start"] == pytest.approx(5.3)


def test_a_first_run_ends_when_every_watched_log_has_landed():
    led, clock = ledger()
    watches = []

    def watch(landed):
        if len(watches) == 1 and not watches[0][1]:
            return False  # one still set on that server
        watches.append([landed, False])
        return True

    led.watch_landing = watch
    led.miss("serve_admit", (8,))
    build(led, clock, "jit(serve_admit)")
    watches[0][1] = True  # its log was fetched: the shadow is off again
    led.miss("serve_chunk", (2,))
    build(led, clock, "jit(serve_chunk)")
    assert len(watches) == 2
    watches[0][0](clock.tick(0.1), log="admit")
    (first,) = [s for s in led.snapshot() if s["name"] == "setup.first_run"]
    assert first["end"] is None  # the chunk's own log is still on its way
    landed_at = clock.tick(0.2)
    clock.tick(5.0)
    watches[1][0](landed_at, log="chunk m0=0")
    (first,) = [s for s in led.snapshot() if s["name"] == "setup.first_run"]
    assert first["end"] == landed_at and first["log"] == "chunk m0=0"
    assert first["programs"] == ["serve_admit", "serve_chunk"]
    own = self_seconds(led.snapshot())
    assert own[first["id"]] == pytest.approx(0.3)  # less the two compiles
    # a miss the jit cache answered leaves a tag: the landing clears it
    led.miss("serve_chunk", (3,))
    assert led.outstanding()
    led.landed()
    assert not led.outstanding()


def test_the_account_lists_spans_then_programs():
    led, clock = ledger()
    with led.span("setup.engine"):
        with led.span("setup.engine.put") as put:
            clock.tick(2.0)
            put["bytes"] = 1024
    led.miss("serve_chunk", (1,))
    build(led, clock, "jit(serve_chunk)", cache="hit")
    led.miss("serve_chunk", (2,))
    build(led, clock, "jit(serve_chunk)", cache="miss")
    led.begin("setup.server")  # open: not in the table
    lines = led.account().splitlines()
    assert lines == render(led.snapshot()).splitlines()
    assert lines[0].split() == ["span", "seconds", "self", "bytes", "count"]
    rows = {l.split()[0]: l.split()[1:] for l in lines}
    assert rows["setup.engine"] == ["2.000", "0.000", "0", "1"]
    assert rows["setup.engine.put"] == ["2.000", "2.000", "1024", "1"]
    assert rows["setup.compile"][3] == "2" and "setup.server" not in rows
    # program: keys, compiled, loaded, seconds
    assert rows["serve_chunk"] == ["2", "1", "1", "9.000"]


def test_emitted_spans_reach_a_writer_attached_later(tmp_path):
    from llm_sharding_tpu.obs.trace import FLIGHT_RECORDER, TraceWriter

    led, clock = ledger()
    with led.span("setup.engine", stages=2):
        clock.tick(1.5)
    ring = [e for e in FLIGHT_RECORDER.snapshot() if e.get("src") == "setup"]
    assert ring[-1]["span"] == "setup.engine" and ring[-1]["dur_s"] == 1.5
    writer = TraceWriter(str(tmp_path / "t.jsonl"))
    led.attach_writer(writer)
    with led.span("setup.server"):
        clock.tick(0.5)
    led.detach_writer(writer)
    with led.span("setup.repartition"):
        pass
    writer.close()
    lines = [json.loads(l) for l in open(tmp_path / "t.jsonl")]
    assert [(l["span"], l["src"]) for l in lines] == [
        ("setup.engine", "setup"), ("setup.server", "setup")]
    assert lines[0]["stages"] == 2


# ------------------------------------------ (b)-(e), (g) a tiny engine on CPU

@pytest.fixture
def fresh():
    """Nothing built, nothing seen: every dispatch of the test is a miss
    that compiles (the suite's other modules share the process)."""
    jax.clear_caches()
    metrics._SHAPE_KEYS_SEEN.clear()
    SETUP.clear()
    yield
    SETUP.clear()


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def engine(params, host_staging=True, stages=2):
    return PipelineEngine(
        CFG, params, num_stages=stages, devices=jax.devices()[:stages],
        cache_dtype=jnp.float32, host_staging=host_staging,
    )


def named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("host_staging,stages", [(True, 2), (False, 1)])
def test_engine_and_server_leave_their_spans(fresh, params, host_staging,
                                             stages):
    eng = engine(params, host_staging, stages)
    srv = eng.serve(**SERVE)
    spans = SETUP.snapshot()
    (root,) = named(spans, "setup.engine")
    children = {s["name"] for s in spans if s["parent"] == root["id"]}
    if host_staging:
        assert children >= {"setup.engine.host_pull", "setup.engine.stack",
                            "setup.engine.put"}
        (pull,) = named(spans, "setup.engine.host_pull")
        assert pull["leaves"] > 0 and pull["bytes"] > 0  # device → host
        what = {s["what"]: s for s in named(spans, "setup.engine.stack")}
        (put,) = named(spans, "setup.engine.put")
        # the head is staged while the layers' copies are in flight
        assert what["layers"]["parent"] == root["id"]
        assert what["head"]["parent"] == put["id"]
    else:
        assert "setup.engine.host_pull" not in {s["name"] for s in spans}
        assert not named(spans, "setup.engine.stack")
        (put,) = named(spans, "setup.engine.put")
    assert put["stages"] == stages and put["bytes"] > 0
    (server,) = named(spans, "setup.server")
    (arena,) = named(spans, "setup.server.arena")
    (host,) = named(spans, "setup.server.host")
    assert arena["parent"] == host["parent"] == server["id"]
    assert arena["blocks"] == {"full": SERVE["kv_blocks"]}
    assert arena["bytes"] >= srv.arena_bytes_device > 0
    assert arena["end"] <= host["start"] and server["parent"] is None
    # the arena's fills are programs no dispatch site announced
    fills = [s for s in named(spans, "setup.compile")
             if s["parent"] == arena["id"]]
    assert fills and {s["program"] for s in fills} == {"-"}
    assert all(s["end"] is not None for s in spans)
    own = self_seconds(spans)
    for top in (root, server):
        inside = [own[s["id"]] for s in spans
                  if s["id"] == top["id"] or _under(spans, s, top["id"])]
        assert sum(inside) == pytest.approx(top["end"] - top["start"])
    srv.close()


def _under(spans, span, root_id) -> bool:
    by_id = {s["id"]: s for s in spans}
    while span["parent"] is not None:
        if span["parent"] == root_id:
            return True
        span = by_id[span["parent"]]
    return False


def test_every_program_dispatched_has_its_compile_span(fresh, params, caplog):
    srv = engine(params).serve(**SERVE)
    before = set(metrics._SHAPE_KEYS_SEEN)
    a = srv.submit(prompt(1, 5), 4)   # one-shot admit, then decode chunks
    srv.run_until_idle()
    b = srv.submit(prompt(2, 29), 4)  # chunked: prefill chunks + the finish
    srv.run_until_idle()
    assert a.done and b.done
    seen = set(metrics._SHAPE_KEYS_SEEN) - before
    assert {p for p, _ in seen} == {
        "serve_admit", "serve_chunk", "serve_prefill_chunk",
        "serve_admit_finish"}
    spans = SETUP.snapshot()
    tagged = [s for s in named(spans, "setup.compile") if s["program"] != "-"]
    assert sorted((s["program"], s["key"]) for s in tagged) == sorted(
        (p, repr(k)) for p, k in seen)
    for s in tagged:
        assert s["program"] in s["fun"] and s["cache"] == "off"
        assert s["backend_s"] > 0 and s["trace_s"] > 0 and s["lower_s"] > 0
        assert s["in_flight"] == 0  # nothing was decoding beside them
    # each is inside the first run of its program, which has ended
    runs = {s["id"]: s for s in named(spans, "setup.first_run")}
    assert all(s["parent"] in runs for s in tagged)
    assert all(r["end"] is not None for r in runs.values())
    assert sorted(p for r in runs.values() for p in r["programs"]) == sorted(
        s["program"] for s in tagged)
    assert not SETUP.outstanding() and "_fetch" not in srv.__dict__
    # (c) the same keys again: no span, no tag, no watch — the hit path
    n = len(spans)
    c = srv.submit(prompt(3, 5), 4)
    d = srv.submit(prompt(4, 29), 4)
    srv.run_until_idle()
    assert c.done and d.done and len(SETUP.snapshot()) == n
    assert not SETUP.outstanding() and "_fetch" not in srv.__dict__
    # the account: when a server is built (SERVING from birth), and at close
    with caplog.at_level(logging.INFO, logger="llm_sharding_tpu.setup"):
        srv2 = srv.engine.serve(**SERVE)
        assert srv2.health == "SERVING"
        srv2.close()
    said = [r.getMessage() for r in caplog.records
            if "set-up's account" in r.getMessage()]
    assert [m.splitlines()[0] for m in said] == [
        "set-up's account at SERVING:", "set-up's account at close:"]
    assert all("serve_prefill_chunk" in m and "setup.server.arena" in m
               for m in said)
    srv.close()


def test_a_compile_with_rows_in_flight_says_so(fresh, params):
    srv = engine(params).serve(**SERVE)
    a = srv.submit(prompt(1, 5), 40)
    while len(a.tokens) < 3:
        srv.step()
    b = srv.submit(prompt(2, 12), 4)  # another admit bucket: a new program
    srv.run_until_idle()
    assert a.done and b.done
    admits = [s for s in named(SETUP.snapshot(), "setup.compile")
              if s["program"] == "serve_admit"]
    assert [s["in_flight"] for s in admits] == [0, 1]
    assert admits[1]["key"] != admits[0]["key"]
    srv.close()


def test_a_restore_is_a_server_built_too(fresh, params):
    from llm_sharding_tpu.runtime.server import PipelineServer

    eng = engine(params)
    srv = eng.serve(capacity=64, kv_block_size=8, kv_blocks=33)
    r = srv.submit(prompt(1, 5), 8)
    srv.step()
    snap = srv.snapshot()
    srv.close()
    srv2 = PipelineServer.restore(eng, snap)
    built = named(SETUP.snapshot(), "setup.server")
    assert len(built) == 2 and all(s["parent"] is None for s in built)
    arenas = named(SETUP.snapshot(), "setup.server.arena")
    assert [a["parent"] for a in arenas] == [s["id"] for s in built]
    srv2.run_until_idle()
    srv2.close()


def test_statz_holds_the_ledger_and_metrics_the_histogram(fresh, params):
    srv = engine(params).serve(**SERVE)
    r = srv.submit(prompt(1, 5), 2)
    srv.run_until_idle()
    assert r.done
    ms = MetricsServer(port=0)
    port = ms.start()
    try:
        get = lambda path: urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10).read()
        setup = json.loads(get("/statz"))["setup"]
        text = get("/metrics").decode()
    finally:
        ms.stop()
        srv.close()
    assert {"setup.engine", "setup.server", "setup.compile",
            "setup.first_run"} <= {s["name"] for s in setup}
    assert "# TYPE server_compile_seconds histogram" in text
    assert ('server_compile_seconds_count{program="serve_chunk",cache="off"}'
            in text)


def test_the_listeners_are_registered_once_however_many_engines(params):
    from jax._src import monitoring  # the public module only registers

    engine(params)
    engine(params, stages=1)
    assert [l == SETUP.on_duration
            for l in monitoring.get_event_duration_listeners()].count(
                True) == 1
    assert [l == SETUP.on_event
            for l in monitoring.get_event_listeners()].count(True) == 1
    assert setupline.install(None, None) is False  # and asks jax nothing


def test_a_repartition_has_its_own_root_and_generate_ends_its_first_run(
        fresh, params):
    from llm_sharding_tpu.parallel.placement import PlacementSpec

    eng = engine(params)
    eng.apply_placement(PlacementSpec.from_ranges([(0, 1), (1, 4)], 4))
    spans = SETUP.snapshot()
    (again,) = named(spans, "setup.repartition")
    assert again["parent"] is None and again["stages"] == 2
    assert {s["name"] for s in spans if s["parent"] == again["id"]} == {
        "setup.engine.stack", "setup.engine.put"}
    eng.generate_ids(prompt(1, 6), 3)
    (run,) = named(SETUP.snapshot(), "setup.first_run")
    assert run["programs"] == ["pipeline_generate"] and run["end"] is not None
    (built,) = [s for s in named(SETUP.snapshot(), "setup.compile")
                if s["program"] == "pipeline_generate"]
    assert built["parent"] == run["id"]


def test_dispatch_from_another_thread_keeps_its_own_tag(fresh):
    got = []

    def other():
        metrics.record_shape_key("serve_verify", (9,))
        got.append(SETUP.outstanding())
        SETUP.on_duration(BACKEND_EVENT, 0.1, fun_name="jit(serve_verify)")

    metrics.record_shape_key("serve_chunk", (1,))
    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and got == [True]
    assert SETUP.outstanding()  # this thread's program is still to build
    SETUP.on_duration(BACKEND_EVENT, 0.1, fun_name="jit(serve_chunk)")
    assert not SETUP.outstanding()
    assert sorted(s["program"] for s in named(SETUP.snapshot(),
                                              "setup.compile")) == [
        "serve_chunk", "serve_verify"]
    SETUP.landed()


# ---------------------------------------------- (f) the benchmark's readers

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name,
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def synthetic(monkeypatch):
    """A run's set-up: process start at 100, window start at 200."""
    led, clock = ledger()
    monkeypatch.setattr(setupline, "SETUP", led)
    clock.tick(20.0)  # imports, backend start, the harness's weights
    with led.span("setup.engine"):
        with led.span("setup.engine.host_pull"):
            clock.tick(3.0)
        with led.span("setup.engine.stack"):
            clock.tick(5.0)
        with led.span("setup.engine.put"):
            clock.tick(6.0)
            with led.span("setup.engine.stack"):
                clock.tick(2.0)
            clock.tick(4.0)
    with led.span("setup.server"):
        with led.span("setup.server.arena"):
            build(led, clock, "jit(<lambda>)", cache="hit")  # 3.5 s
        clock.tick(0.5)
    led.miss("serve_admit", (8,))
    build(led, clock, "jit(serve_admit)", cache="miss")  # 5.5 s
    clock.tick(1.0)
    led.landed()
    clock.tick(34.5)  # the warm-up requests' own steps
    clock.tick(15.0)  # the ramp
    assert clock.t == 200.0
    led.miss("serve_chunk", (2,))  # inside the window: not set-up's
    build(led, clock, "jit(serve_chunk)", cache="miss")
    led.landed()
    return {"window": [200.0, 250.0], "setup_s": 100.0,
            "marks": {"weights_s": 12.0}, "traffic": {"ramp_s": 15.0}}


READINGS = {
    "setup_engine_host_s": 3.0 + 5.0 + 2.0,
    "setup_engine_put_s": 6.0 + 4.0,
    "setup_server_s": 4.0,
    "setup_compile_s": 0.5 + 1.0 + 4.0,
    "setup_cache_load_s": 0.5 + 1.0 + 2.0,
    "setup_first_run_s": 1.0,
    "setup_programs_built": 2,
    # 100 - weights 12 - ramp 15 - engine 20 - server 4 - first run 6.5
    "setup_unaccounted_pct": 42.5,
}


@pytest.mark.parametrize("name", list(READINGS))
def test_a_reader_on_a_synthetic_ledger_and_on_none(name, monkeypatch):
    read = reader(name)
    rec = synthetic(monkeypatch)
    assert read(rec) == pytest.approx(READINGS[name])
    # the cut ledger rides in the run's records, to be written with them
    assert {s["name"] for s in rec["setup"]} >= {
        "setup.engine", "setup.compile"}
    assert all(s["end"] <= 200.0 for s in rec["setup"])
    json.dumps(rec["setup"])
    # nothing before the window (or nothing at all): no reading
    monkeypatch.setattr(setupline, "SETUP", SetupLedger())
    empty = dict(rec)
    del empty["setup"]
    assert read(empty) is None
    # a program without the ledger (the parent commit): no reading, no raise
    monkeypatch.delattr(setupline, "SETUP")
    absent = dict(rec)
    del absent["setup"]
    assert read(absent) is None


def test_the_benchmark_names_the_eight_with_the_six_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    moving = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in moving] == list(READINGS)
    first = bench["per_layer"].index(moving[0])  # appended together, in order
    assert bench["per_layer"][first:first + len(moving)] == moving
    layers = {m["layer"] for m in bench["per_layer"] if m not in moving}
    for m in moving:
        assert m["workloads"] == cells[:6] and m["better"] == "lower"
        assert m["source"] == "program_span" and m["layer"] in layers
