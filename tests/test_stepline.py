"""Host–device overlap profiler (ISSUE 16): the continuous step timeline
(``obs/stepline``), lock-wait accounting riding the ``named_lock`` factory's
opt-in timed mode, the ``/profilez`` deep capture, the ``:profile`` control
line, and the jax-free ``step-report`` CLI.

The contract under test: every serve-loop step leaves ONE StepRecord whose
disjoint phase durations plus device-blocked wait plus the explicit
unattributed remainder sum to the step wall EXACTLY (the accounting
invariant — enforced with a fake clock, and re-checked in-band on a real
CPU smoke serve where the unattributed slice must stay under 5%).

``REPLICA_TEST_DP`` (default 2) sets the replica count for the dp tests;
tier-1 CI reruns this module at REPLICA_TEST_DP=2 with
``PAGED_FORCE_KERNEL=interpret`` so the per-replica stats also run through
the Pallas kernel code path.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu import cli
from llm_sharding_tpu.analysis import lockorder
from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs import stepline
from llm_sharding_tpu.obs.http import MetricsServer
from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.obs.report import (
    extract_steps, load_steps, render_step_report, step_report_json,
)
from llm_sharding_tpu.obs.stepline import PHASES, StepProfiler
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.replicated import ReplicatedServer

CFG = tiny_llama(num_hidden_layers=8)
DP = int(os.environ.get("REPLICA_TEST_DP", "2"))
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
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return r.read()


class FakeClock:
    """A settable clock: the accounting tests control time exactly."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t


def _check_invariant(rec):
    """wall == phases + blocked + unattributed, exactly by construction."""
    host = sum(rec["phases"].values())
    assert rec["host_s"] == pytest.approx(host, abs=1e-12)
    assert rec["wall_s"] == pytest.approx(
        host + rec["blocked_s"] + rec["unattributed_s"], abs=1e-9
    )


# ------------------------------------------------------------ builder units


def test_ring_bounds_and_overwrite():
    clk = FakeClock()
    p = StepProfiler(ring_size=4, clock=clk.now, name="t-ring")
    for i in range(7):
        clk.t = float(i)
        p.begin_step()
        clk.t = float(i) + 0.5
        p.end_step(tokens=i)
    assert p.steps_total == 7
    snap = p.snapshot()
    assert len(snap) == 4, "ring must stay bounded"
    # oldest-first, holding the LAST four steps (3..6)
    assert [r["tokens"] for r in snap] == [3, 4, 5, 6]
    assert p.snapshot(last_n=2)[-1]["tokens"] == 6
    with pytest.raises(ValueError):
        StepProfiler(ring_size=0)


def test_phase_accounting_sums_to_wall_exactly():
    clk = FakeClock()
    p = StepProfiler(ring_size=8, clock=clk.now, name="t-acct")
    p.begin_step()
    clk.t = 1.0
    p.push("admit")
    clk.t = 2.0
    p.pop()  # admit = 1.0
    clk.t = 2.5
    p.push("dispatch")
    p.blocked(0.25)  # interrupts dispatch: excluded from the phase
    clk.t = 4.0
    p.pop()  # dispatch = 1.5 - 0.25 = 1.25
    clk.t = 5.0
    rec = p.end_step(rows=3, tokens=7, queued=2, pending=1)
    assert rec.wall_s == 5.0
    assert rec.phases == {"admit": 1.0, "dispatch": 1.25}
    assert rec.blocked_s == 0.25
    # the inter-phase gaps land in the explicit remainder, never silently
    assert rec.unattributed_s == pytest.approx(2.5)
    assert rec.host_s == pytest.approx(2.25)
    assert rec.occupancy == pytest.approx(2.25 / 5.0)
    assert (rec.rows, rec.tokens, rec.queued, rec.pending) == (3, 7, 2, 1)
    _check_invariant(rec.to_dict())


def test_nested_phases_stay_disjoint():
    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-nest")
    p.begin_step()
    clk.t = 1.0
    p.push("fetch")
    clk.t = 2.0
    p.push("apply")  # nested inside fetch
    clk.t = 3.0
    p.pop()  # apply = 1.0; fetch must EXCLUDE it
    clk.t = 4.0
    p.pop()  # fetch = 3.0 elapsed - 1.0 nested = 2.0
    rec = p.end_step()
    assert rec.phases == {"apply": 1.0, "fetch": 2.0}
    assert rec.unattributed_s == pytest.approx(1.0)  # the 0->1 gap
    _check_invariant(rec.to_dict())


def test_builder_guards():
    p = StepProfiler(name="t-guard")
    p.begin_step()
    with pytest.raises(ValueError):
        p.push("not_a_phase")  # the label space stays closed
    assert p.end_step() is not None
    # disabled: every builder call is a no-op, nothing records
    p.set_enabled(False)
    p.begin_step()
    p.push("admit")
    p.pop()
    assert p.end_step() is None
    assert p.steps_total == 1
    p.set_enabled(True)
    # unbalanced push (exception path) is closed out by end_step
    clk = FakeClock()
    q = StepProfiler(clock=clk.now, name="t-unbal")
    q.begin_step()
    clk.t = 1.0
    q.push("dispatch")
    clk.t = 3.0
    rec = q.end_step()
    assert rec.phases == {"dispatch": 2.0}
    _check_invariant(rec.to_dict())


def test_arm_capture_keeps_segments_and_exemplars():
    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-cap")
    with pytest.raises(ValueError):
        p.arm(0)
    p.arm(2)
    assert p.armed and not p.wait_capture(0)
    for i in range(3):  # one more step than armed
        p.begin_step()
        clk.t += 1.0
        p.push("apply")
        for j in range(12):  # exemplars stay bounded per step
            p.note_exemplar(f"trace-{i}-{j}")
        clk.t += 0.5
        p.pop()
        p.end_step(tokens=i)
    assert not p.armed and p.wait_capture(0)
    bundle = p.capture_bundle()
    assert bundle["profiler"] == "t-cap"
    assert bundle["steps_requested"] == 2
    assert bundle["steps_captured"] == 2 and bundle["complete"]
    assert bundle["lock_timing"] == lockorder.timing_enabled()
    assert [s["tokens"] for s in bundle["steps"]] == [0, 1]
    for s in bundle["steps"]:
        (seg,) = s["segments"]
        assert list(seg) == ["apply", pytest.approx(1.0), pytest.approx(0.5)]
        assert len(s["exemplars"]) == 8
        _check_invariant(s)
    # steps outside the armed window carry no capture extras
    tail = p.snapshot()[-1]
    assert "segments" not in tail and "exemplars" not in tail
    # the whole bundle is JSON-serializable as-is (the /profilez wire form)
    json.dumps(bundle)


def test_stats_occupancy_math():
    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-stats")
    for wall, work in ((1.0, 0.25), (3.0, 1.5)):
        p.begin_step()
        p.push("dispatch")
        clk.t += work
        p.pop()
        # the device's queue was empty for the 0.1 before this enqueue
        p.dispatched("chunk", 0, clk.t, 0, clk.t - 0.1, clk.t - 0.1)
        clk.t += wall - work
        p.end_step()
    st = p.stats()
    assert st["steps"] == 2
    # duration-weighted, not a mean of per-step ratios
    assert st["host_occupancy"] == pytest.approx(1.75 / 4.0)
    assert st["device_idle_frac"] == pytest.approx(0.2 / 4.0)
    assert st["step_wall_p50_ms"] == pytest.approx(1000.0)
    empty = StepProfiler(name="t-empty").stats()
    assert empty == {
        "steps": 0, "host_occupancy": 0.0, "device_idle_frac": 0.0,
        "step_wall_p50_ms": 0.0,
    }


# ------------------------------------------------- timed locks + wait sink


def test_timed_lock_mode_off_by_default_and_on_demand():
    assert not lockorder.timing_enabled()
    base = lockorder.named_lock("server.mutex")
    assert not isinstance(base, lockorder._TimedBase)
    lockorder.enable_timing(True)
    try:
        lockorder.reset_wait_totals()
        mu = lockorder.named_lock("server.mutex")
        assert isinstance(mu, lockorder.TimedLock)
        with mu:
            pass
        with mu:
            pass
        n, wait_s = lockorder.wait_totals()["server.mutex"]
        assert n == 2 and wait_s >= 0.0
        # a contended acquire records a real wait
        mu.acquire()
        t = threading.Thread(target=lambda: (mu.acquire(), mu.release()))
        t.start()
        import time as _time

        _time.sleep(0.05)
        mu.release()
        t.join()
        n2, wait2 = lockorder.wait_totals()["server.mutex"]
        assert n2 == n + 2 and wait2 >= 0.04
        # rlock/condition variants wrap too
        assert isinstance(
            lockorder.named_lock("replica.router", "rlock"),
            lockorder.TimedRLock,
        )
        cv = lockorder.named_lock("disagg.handoff", "condition")
        assert isinstance(cv, lockorder.TimedCondition)
        with cv:
            cv.notify_all()
    finally:
        lockorder.enable_timing(False)
        lockorder.reset_wait_totals()
    assert lockorder.wait_totals() == {}


def test_lock_wait_sink_feeds_metric_but_skips_obs_locks():
    def count(lock):
        fam = REGISTRY.json_snapshot()["server_lock_wait_seconds"]
        for s in fam["series"]:
            if s["labels"].get("lock") == lock:
                return s["count"]
        return 0

    before = count("server.mutex")
    stepline._lock_wait_sink("server.mutex", 0.002)
    assert count("server.mutex") == before + 1
    # obs-internal locks must NOT feed the histogram: observing it takes an
    # obs lock, so recording those waits would recurse into itself
    obs_before = count("obs.metrics.family")
    stepline._lock_wait_sink("obs.metrics.family", 0.002)
    assert count("obs.metrics.family") == obs_before


# -------------------------------------------------- live serve (CPU smoke)


def test_smoke_serve_accounting_invariant_in_band(params):
    """ACCEPTANCE: on a real CPU serve, every step's phases + blocked +
    unattributed sum to wall, and the unattributed slice stays under 5%."""
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=CAP)
    for i in range(3):
        srv.submit(prompt(30 + i), 10)
    srv.run_until_idle()
    recs = srv.stepline_snapshot()
    assert recs, "the serve loop recorded no steps"
    for r in recs:
        _check_invariant(r)
        assert set(r["phases"]) <= set(PHASES)
    wall = sum(r["wall_s"] for r in recs)
    unatt = sum(r["unattributed_s"] for r in recs)
    assert wall > 0
    assert unatt / wall < 0.05, (
        f"unattributed {unatt / wall:.1%} of wall — phase coverage regressed"
    )
    # the loop did real work in the instrumented phases
    phases_seen = set()
    for r in recs:
        phases_seen |= set(r["phases"])
    assert {"admit", "dispatch", "fetch", "apply"} <= phases_seen
    assert sum(r["tokens"] for r in recs) == 30
    st = srv.stepline_stats()
    assert st["steps"] == len(recs) == srv.stepline.steps_total
    assert 0.0 < st["host_occupancy"] <= 1.0
    assert st["step_wall_p50_ms"] > 0.0
    # continuous gauges fed without any arming
    snap = REGISTRY.json_snapshot()
    occ = snap["server_host_occupancy"]["series"][0]["value"]
    assert 0.0 < occ <= 1.0
    assert snap["server_step_wall_seconds"]["series"][0]["count"] >= len(recs)
    srv.close()


def test_gauge_sweep_pacing(params):
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    with pytest.raises(ValueError):
        eng.serve(capacity=CAP, gauge_sweep_every_s=-1.0)

    def sweeps(srv):
        srv.submit(prompt(41), 12)
        srv.run_until_idle()
        return sum(
            1 for r in srv.stepline_snapshot() if "gauge_sweep" in r["phases"]
        )

    unpaced = eng.serve(capacity=CAP)  # default 0.0: sweep every step
    n_unpaced = sweeps(unpaced)
    unpaced.close()
    paced = eng.serve(capacity=CAP, gauge_sweep_every_s=3600.0)
    n_paced = sweeps(paced)
    paced.close()
    assert n_unpaced >= 3
    assert n_paced <= 1, "a 1h pace must sweep at most once in a short serve"


# --------------------------------------------------- /profilez + /debugz


def test_profilez_http_arm_capture_roundtrip(params):
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=CAP)
    ms = MetricsServer(port=0)
    ms.set_profilez_provider(
        lambda steps, wait_s: (
            srv.stepline_capture(steps, wait_s)
            if steps is not None
            else {"stepline": srv.stepline_stats(),
                  "steps": srv.stepline_snapshot(64)}
        )
    )
    port = ms.start()
    stop = threading.Event()

    def pump():  # the step pump an idle daemon would be running
        while not stop.is_set():
            srv.step()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        srv.submit(prompt(50), 8)
        bundle = json.loads(_get(port, "/profilez?steps=3&wait_s=30"))
        assert bundle["profiler"] == "server"
        assert bundle["steps_captured"] == 3 and bundle["complete"]
        for s in bundle["steps"]:
            _check_invariant(s)
            assert isinstance(s["segments"], list)
            # armed steps name their sub-phase timeline offsets
            for name, off, dur in s["segments"]:
                assert name in PHASES and off >= 0.0 and dur >= 0.0
        # bare GET: the non-arming ring view through the same provider
        view = json.loads(_get(port, "/profilez"))
        assert view["stepline"]["steps"] >= 3
        assert view["steps"] and "wall_s" in view["steps"][-1]
        # /debugz rides the process-wide ring tails (satellite: postmortems
        # show what the loop was DOING, not just what spans it emitted)
        dbg = json.loads(_get(port, "/debugz"))
        mine = [
            p for p in dbg["recent_steps"] if p["profiler"] == "server"
        ]
        assert mine and mine[-1]["steps"], "debugz lost the step-ring tail"
        # bad query → 400, with a JSON error body
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/profilez?steps=zero")
        assert ei.value.code == 400
        assert "steps" in json.loads(ei.value.read())["error"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/profilez?steps=2&wait_s=soon")
        assert ei.value.code == 400
    finally:
        stop.set()
        t.join(timeout=10)
        ms.stop()
        srv.close()


def test_profilez_without_provider():
    ms = MetricsServer(port=0)
    port = ms.start()
    try:
        view = json.loads(_get(port, "/profilez"))
        assert isinstance(view["profilers"], list)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/profilez?steps=2")
        assert ei.value.code == 503
    finally:
        ms.stop()


# ------------------------------------------------------ :profile / :stats


def test_profile_control_line(params, capsys):
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=CAP)
    # arg errors never kill the daemon
    assert cli._serve_control(eng, srv, ":profile", None) is srv
    assert cli._serve_control(eng, srv, ":profile zero", None) is srv
    assert cli._serve_control(eng, srv, ":profile 0", None) is srv
    err = capsys.readouterr().err
    assert "usage: :profile" in err
    assert err.count("profile failed") == 2
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            srv.step()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        srv.submit(prompt(60), 8)
        assert cli._serve_control(eng, srv, ":profile 2", None) is srv
    finally:
        stop.set()
        t.join(timeout=10)
    bundle = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert bundle["steps_requested"] == 2 and bundle["complete"]
    # :stats carries the aggregates (satellite 3)
    cli._serve_control(eng, srv, ":stats", None)
    stats = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert stats["stepline"]["steps"] == srv.stepline.steps_total
    assert "host_occupancy" in stats["stepline"]
    srv.close()


# ------------------------------------------------------------ dp fan-out


def test_dp_stats_and_stepline_fanout(params):
    srv = ReplicatedServer(
        CFG, params, data_parallel=DP, num_stages=2,
        devices=jax.devices()[: 2 * DP], cache_dtype=jnp.float32,
        capacity=CAP,
    )
    for i in range(2 * DP):
        srv.submit(prompt(70 + i), 6)
    srv.run_until_idle()
    st = srv.stats()
    assert len(st["replicas"]) == DP
    for entry in st["replicas"]:
        assert 0.0 <= entry["host_occupancy"] <= 1.0
        assert entry["step_wall_p50_ms"] > 0.0
    fan = srv.stepline_stats()
    assert set(fan) == {f"r{d}" for d in range(DP)}
    assert all(v["steps"] > 0 for v in fan.values())
    snaps = srv.stepline_snapshot(8)
    for d in range(DP):
        assert snaps[f"r{d}"], f"replica {d} recorded no steps"
        for r in snaps[f"r{d}"]:
            _check_invariant(r)
    srv.close()


# ------------------------------------------- step-report CLI (jax-free)


def _fake_step(ts, wall, phases, blocked=0.0, idle=0.0, rows=1, tokens=2):
    host = sum(phases.values())
    return {
        "ts": ts, "wall_s": wall, "phases": phases, "blocked_s": blocked,
        "idle_s": idle, "unattributed_s": wall - host - blocked,
        "host_s": host, "occupancy": host / wall, "rows": rows,
        "tokens": tokens, "queued": 0, "pending": 0,
    }


def _fake_bundle():
    return {
        "profiler": "server", "steps_requested": 2, "steps_captured": 2,
        "complete": True, "lock_timing": False,
        "steps": [
            _fake_step(1.0, 0.1, {"admit": 0.02, "dispatch": 0.05},
                       blocked=0.01, idle=0.004),
            _fake_step(2.0, 0.2, {"dispatch": 0.10, "apply": 0.06},
                       blocked=0.02),
        ],
    }


def test_extract_steps_accepts_every_bundle_shape():
    bundle = _fake_bundle()
    raw = extract_steps(bundle["steps"], src="x")
    assert len(raw) == 2 and raw[0]["src"] == "x"
    assert [s["src"] for s in extract_steps(bundle)] == ["server"] * 2
    debugz = {"recent_steps": [{"profiler": "r1", "stats": {},
                                "steps": bundle["steps"]}]}
    assert [s["src"] for s in extract_steps(debugz)] == ["r1"] * 2
    fanout = {"r0": _fake_bundle(), "r1": dict(_fake_bundle(), profiler="")}
    got = extract_steps(fanout)
    assert len(got) == 4
    assert extract_steps({"unrelated": 1}) == []
    assert extract_steps("junk") == []


def test_step_report_cli_golden(tmp_path, capsys):
    cap = tmp_path / "cap.json"
    cap.write_text(json.dumps(_fake_bundle()))
    (tmp_path / "junk.json").write_text("{not json")  # skipped, not fatal
    assert cli.main(
        ["step-report", str(cap), str(tmp_path / "junk.json")]
    ) == 0
    out = capsys.readouterr().out
    assert "2 step(s), 0.300s wall, 4 token(s)" in out
    assert "per-phase host attribution:" in out
    for row in ("dispatch", "admit", "apply", "blocked", "unattributed"):
        assert row in out
    assert "device-idle bubble" in out
    # machine-readable form round-trips the same numbers
    assert cli.main(["step-report", "--json", str(cap)]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["summary"]["steps"] == 2
    assert js["summary"]["tokens"] == 4
    assert js["summary"]["host_occupancy"] == pytest.approx(0.23 / 0.3)
    assert js["summary"]["max_accounting_residual_s"] == pytest.approx(0.0)
    assert js["phases"][0]["phase"] == "dispatch"  # biggest total first
    assert js["phases"][0]["total_s"] == pytest.approx(0.15)
    assert js["worst_bubbles"][0]["idle_s"] == pytest.approx(0.004)
    # glob expansion + the jax-free load path share trace-report's policy
    assert cli.main(["step-report", str(tmp_path / "cap.*")]) == 0
    capsys.readouterr()
    assert cli.main(["step-report", str(tmp_path / "missing.json")]) == 2
    assert cli.main(["step-report", str(tmp_path / "junk.json")]) == 1


def test_step_report_merges_and_sorts_files(tmp_path):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps([_fake_step(5.0, 0.1, {"apply": 0.05})]))
    b.write_text(json.dumps([_fake_step(1.0, 0.1, {"admit": 0.05})]))
    steps = load_steps([str(a), str(b)])
    assert [s["ts"] for s in steps] == [1.0, 5.0]
    text = render_step_report(steps)
    assert "2 step(s)" in text
    assert render_step_report([]) == "no step records in the input"
    assert step_report_json([])["summary"]["steps"] == 0


# ------------------------------------------- profiler annotations (ISSUE 24)


class FakeAnnotations:
    """The injected ``annotate`` factory: records every enter and exit in
    order, with the stats each annotation was made with."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **stats):
        log = self.log

        class Span:
            def __enter__(self):
                log.append(("enter", name, stats))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return Span()

    def trail(self):
        return [(e[0], e[1]) for e in self.log]


def test_phase_stack_enters_and_exits_the_injected_annotation_in_order():
    clk, ann = FakeClock(), FakeAnnotations()
    p = StepProfiler(clock=clk.now, name="t-ann", annotate=ann)
    p.begin_step(rows=2, queued=1, pending=1)
    p.push("admit")
    p.push("fetch")  # nested: _drain(0) inside admission
    with p.blocking():  # the device wait, inside the phase it interrupts
        clk.t += 0.25
    p.pop()
    p.pop()
    p.push("dispatch")
    clk.t += 1.0
    p.pop()
    rec = p.end_step(rows=2, tokens=3)
    assert ann.trail() == [
        ("enter", "serve.step"), ("enter", "serve.admit"),
        ("enter", "serve.fetch"), ("enter", "serve.blocked"),
        ("exit", "serve.blocked"), ("exit", "serve.fetch"),
        ("exit", "serve.admit"), ("enter", "serve.dispatch"),
        ("exit", "serve.dispatch"), ("exit", "serve.step"),
    ]
    # the step carries what the server held at its START, and its number
    assert ann.log[0][2] == {
        "step_num": 0, "rows": 2, "queued": 1, "pending": 1,
    }
    # blocking() is blocked(): accounted, and excluded from the phase
    assert rec.blocked_s == pytest.approx(0.25)
    assert rec.phases["fetch"] == pytest.approx(0.0)
    assert rec.phases["dispatch"] == pytest.approx(1.0)
    _check_invariant(rec.to_dict())
    # every name is one the trace reader knows
    names = {e[1] for e in ann.log}
    assert names <= {stepline.STEP_ANNOTATION, stepline.BLOCKED_ANNOTATION,
                     stepline.PREFILL_ANNOTATION} | {
                         "serve." + ph for ph in PHASES}


def test_idle_polls_write_nothing_and_the_one_closing_step_is_written():
    ann = FakeAnnotations()
    p = StepProfiler(name="t-idle", annotate=ann)

    def step(rows=0, queued=0, pending=0):
        p.begin_step(rows, queued, pending)
        p.push("admit")
        p.pop()
        p.end_step()

    for _ in range(50):  # an empty server being polled
        step()
    assert ann.log == [], "idle polls must not reach the trace"
    assert p.steps_total == 50, "the ring still records every step"
    step(queued=1)
    step(rows=1, pending=1)
    step()  # the closing step: the one after the last that held work
    for _ in range(50):
        step()
    steps = [e[2] for e in ann.log if e[:2] == ("enter", "serve.step")]
    assert [(s["rows"], s["queued"], s["pending"]) for s in steps] == [
        (0, 1, 0), (1, 0, 1), (0, 0, 0),
    ]
    assert [s["step_num"] for s in steps] == [50, 51, 52]
    # phases of an unannotated step are not written either
    assert ann.trail().count(("enter", "serve.admit")) == 3
    # no factory, no annotations, same records
    q = StepProfiler(name="t-none")
    q.begin_step(rows=1)
    q.push("admit")
    with q.blocking():
        pass
    with q.prefill(1, 1, 1):
        pass
    q.pop()
    assert q.end_step().prompt_tokens == 1


def test_prefill_counts_reach_the_record_and_the_annotation():
    ann = FakeAnnotations()
    p = StepProfiler(name="t-prefill", annotate=ann)
    p.begin_step(queued=2)
    p.push("admit")
    with p.prefill(rows=4, prompt_tokens=53, positions=256):
        pass
    with p.prefill(rows=4, prompt_tokens=300, positions=1024):
        pass
    with p.prefill(rows=4, prompt_tokens=0, positions=0):  # admit_finish
        pass
    p.pop()
    rec = p.end_step(tokens=1).to_dict()
    assert (rec["prompt_tokens"], rec["prefill_positions"]) == (353, 1280)
    stats = [e[2] for e in ann.log if e[:2] == ("enter", "serve.prefill")]
    assert stats == [
        {"rows": 4, "prompt_tokens": 53, "positions": 256},
        {"rows": 4, "prompt_tokens": 300, "positions": 1024},
        {"rows": 4, "prompt_tokens": 0, "positions": 0},
    ]
    # the next step starts from nothing
    p.begin_step()
    assert p.end_step().prefill_positions == 0
    # outside a step it accounts nothing and does not raise
    with p.prefill(1, 1, 1), p.blocking():
        pass


def test_a_step_that_raised_leaves_a_properly_nested_trace():
    ann = FakeAnnotations()
    p = StepProfiler(name="t-raise", annotate=ann)
    p.begin_step(rows=1)
    p.push("admit")
    p.push("fetch")  # ... and step() raised here: no pop, no end_step
    p.begin_step(rows=1)
    p.end_step()
    assert ann.trail() == [
        ("enter", "serve.step"), ("enter", "serve.admit"),
        ("enter", "serve.fetch"), ("exit", "serve.fetch"),
        ("exit", "serve.admit"), ("exit", "serve.step"),
        ("enter", "serve.step"), ("exit", "serve.step"),
    ]


def test_obs_imports_without_jax_and_names_the_vocabulary():
    """``obs/`` stays stdlib-only (step-report and lint run without jax),
    and the scope vocabulary is one closed tuple beside PHASES."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from llm_sharding_tpu.obs import stepline, metrics, trace, report\n"
        "assert 'jax' not in sys.modules, 'obs/ pulled jax in'\n"
        "print(','.join(stepline.SCOPES))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    words = out.stdout.strip().split(",")
    assert words == list(stepline.SCOPES) and len(set(words)) == len(words)
    for must in ("kv_take", "kv_layout", "kv_put", "attn", "mlp", "state"):
        assert must in words


def _host_annotations(trace_dir):
    """``[(name, stats)]`` of the serve.* events in a trace's host plane."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, dict(e.stats), e.start_ns, e.end_ns))
    return sorted(out, key=lambda e: e[2])


def test_a_traced_server_leaves_its_spans_in_the_host_plane(params, tmp_path):
    """A profiler session is the only switch: a tiny server stepped under
    ``jax.profiler`` on the CPU backend leaves ``serve.step`` (with its
    stats), ``serve.dispatch`` and ``serve.prefill`` in the host plane, and
    the step record and the counter carry the same prefill counts."""
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=CAP, batch_per_slot=2)
    srv.submit(prompt(70), 3)  # compile outside the traced steps
    srv.run_until_idle()
    fam = REGISTRY.get("server_prefill_positions_total")
    before = {k: fam.labels(kind=k).value for k in ("prompt", "pad")}
    n0 = srv.stepline.steps_total
    for _ in range(20):
        srv.step()  # idle polls before the trace: the closing step passes
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(10):
            srv.step()  # idle polls inside the trace
        srv.submit(prompt(71, n=5), 4)
        srv.run_until_idle()
        for _ in range(10):
            srv.step()
    finally:
        jax.profiler.stop_trace()
    recs = srv.stepline_snapshot()[n0 - srv.stepline.steps_total:]
    srv.close()
    spans = _host_annotations(str(tmp_path))
    steps = [s for s in spans if s[0] == "serve.step"]
    working = [s for s in steps
               if s[1]["rows"] or s[1]["queued"] or s[1]["pending"]]
    # 40 idle polls wrote nothing; the steps that held work and the one
    # closing step did
    assert len(steps) == len(working) + 1
    assert steps[-1][1]["rows"] == steps[-1][1]["queued"] == 0
    assert steps[0][1]["queued"] == 1 and steps[0][1]["rows"] == 0
    nums = [s[1]["step_num"] for s in steps]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    names = {s[0] for s in spans}
    assert {"serve.step", "serve.admit", "serve.dispatch", "serve.fetch",
            "serve.apply", "serve.prefill"} <= names
    # every phase and prefill span lies inside a step span
    for name, _, a, b in spans:
        if name != "serve.step":
            assert any(s[2] <= a and b <= s[3] for s in steps), name
    # one admission: 5 real tokens, the slot's 2 rows at the bucket of 8
    pre = [s[1] for s in spans if s[0] == "serve.prefill"]
    assert pre == [{"rows": 2, "prompt_tokens": 5, "positions": 16}]
    assert sum(r["prompt_tokens"] for r in recs) == 5
    assert sum(r["prefill_positions"] for r in recs) == 16
    after = {k: fam.labels(kind=k).value for k in ("prompt", "pad")}
    assert after["prompt"] - before["prompt"] == 5
    assert after["pad"] - before["pad"] == 11


def test_decode_blocks_reach_the_record():
    p = StepProfiler(name="t-decode-blocks")
    p.begin_step(rows=1)
    p.decode_blocks(live=33, reserved=512)
    p.decode_blocks(live=34, reserved=512)
    rec = p.end_step(rows=1, tokens=2)
    assert (rec.decode_blocks_live, rec.decode_blocks_reserved) == (67, 1024)
    d = rec.to_dict()
    assert (d["decode_blocks_live"], d["decode_blocks_reserved"]) == (67, 1024)
    # the next step starts from nothing; outside a step nothing is counted
    p.begin_step()
    d = p.end_step().to_dict()
    assert (d["decode_blocks_live"], d["decode_blocks_reserved"]) == (0, 0)
    p.decode_blocks(live=1, reserved=1)
    p.begin_step()
    assert p.end_step().decode_blocks_reserved == 0


def test_a_paged_server_counts_the_walk_and_the_reservation(params):
    """The decode kernel's walk, counted on the host from the length
    mirrors: every decode chunk reserves rows x table width entries and
    walks each LIVE row's blocks up to its written column (the admission
    bucket's padding included) — a dead row, and a dead slot, walk 0. The
    step records and ``/metrics`` carry the same two sums."""
    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    bs, cap = 8, 64
    srv = eng.serve(
        capacity=cap, batch_per_slot=2, kv_block_size=bs, kv_blocks=65
    )
    live_c = REGISTRY.get("server_decode_blocks_live_total")
    resv_c = REGISTRY.get("server_decode_blocks_reserved_total")
    before = (live_c.value, resv_c.value, srv.counters.snapshot()["chunks"])
    n0 = srv.stepline.steps_total
    new = 12
    srv.submit(prompt(90, n=5), new)  # one row of one slot; bucket 8
    srv.run_until_idle()
    for _ in range(3):
        srv.step()
    recs = srv.stepline_snapshot()[n0 - srv.stepline.steps_total:]
    srv.close()
    live = sum(r["decode_blocks_live"] for r in recs)
    reserved = sum(r["decode_blocks_reserved"] for r in recs)
    chunks = srv.counters.snapshot()["chunks"] - before[2]
    assert chunks >= new - 1
    # 2 slots x 2 rows, a table of capacity / block_size entries, per chunk
    assert reserved == chunks * 4 * (cap // bs)
    # one live row, written columns 8 (the bucket) .. 8 + new: 2 or 3
    # blocks a step (the mirrors trail the device by the token in flight,
    # so the first step still reads the bucket's 1); the other three rows
    # walk nothing
    busy = [r["decode_blocks_live"] for r in recs if r["decode_blocks_live"]]
    assert len(busy) >= new - 1
    assert busy == sorted(busy) and set(busy[1:]) == {2, 3}
    assert 0 < live <= 3 * chunks and live < reserved / 8
    # steps after the reply ended dispatched nothing
    assert recs[-1]["decode_blocks_reserved"] == 0
    assert live_c.value - before[0] == live
    assert resv_c.value - before[1] == reserved
    text = REGISTRY.prometheus_text()
    assert "\nserver_decode_blocks_live_total " in text
    assert "\nserver_decode_blocks_reserved_total " in text


def test_chunked_admission_counts_every_chunk_and_agrees_with_the_buckets(
        params):
    """The program's own count of prefill positions equals what the
    admission rule says from outside — ``batch_per_slot`` rows x the
    power-of-two bucket per admission (``benchmark``'s prompt_pad_pct) —
    one-shot and chunked, while admission goes by slot."""
    from llm_sharding_tpu.runtime.server import ADMIT_BUCKETS

    eng = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(
        capacity=128, batch_per_slot=2, kv_block_size=8, kv_blocks=65,
        prefill_chunk=16,
    )
    n0 = srv.stepline.steps_total
    lens = [5, 20, 33]  # one-shot; a 32 bucket in two chunks; 64 in four
    for i, n in enumerate(lens):
        srv.submit(prompt(80 + i, n=n), 2)
    srv.run_until_idle()
    recs = srv.stepline_snapshot()[n0 - srv.stepline.steps_total:]
    srv.close()
    bucket = lambda n: next(b for b in ADMIT_BUCKETS if b >= n)
    assert sum(r["prompt_tokens"] for r in recs) == sum(lens)
    assert sum(r["prefill_positions"] for r in recs) == sum(
        2 * bucket(n) for n in lens
    )


def test_prefill_cells_reach_the_record():
    p = StepProfiler(name="t-prefill-cells")
    p.begin_step(rows=1)
    p.prefill_cells(live=84, walked=3584)
    p.prefill_cells(live=28, walked=1792)
    rec = p.end_step(rows=1)
    assert (rec.prefill_cells_live, rec.prefill_cells_walked) == (112, 5376)
    d = rec.to_dict()
    assert (d["prefill_cells_live"], d["prefill_cells_walked"]) == (112, 5376)
    # a step without prefill reads zero; outside a step nothing is counted
    p.begin_step()
    d = p.end_step().to_dict()
    assert (d["prefill_cells_live"], d["prefill_cells_walked"]) == (0, 0)
    p.prefill_cells(live=1, walked=1)
    p.begin_step()
    assert p.end_step().prefill_cells_walked == 0


def test_prefill_kv_blocks_reach_the_record_by_the_writes_form():
    p = StepProfiler(name="t-prefill-kv-blocks")
    p.begin_step(rows=1)
    p.prefill_kv_blocks("tile", 32)
    p.prefill_kv_blocks("tile", 8)
    p.prefill_kv_blocks("rows", 2)
    rec = p.end_step(rows=1)
    assert rec.prefill_kv_blocks == {"tile": 40, "rows": 2}
    assert rec.to_dict()["prefill_kv_blocks"] == {"tile": 40, "rows": 2}
    # a step that dispatched no chunk carries no such key; outside a step
    # nothing is counted
    p.begin_step()
    assert "prefill_kv_blocks" not in p.end_step().to_dict()
    p.prefill_kv_blocks("tile", 1)
    p.begin_step()
    assert p.end_step().prefill_kv_blocks is None


def test_decode_kv_entries_reach_the_record_by_the_writes_form():
    p = StepProfiler(name="t-decode-kv-entries")
    p.begin_step(rows=3)
    p.decode_kv_entries("kernel", 3)
    p.decode_kv_entries("kernel", 3)
    p.decode_kv_entries("scatter", 9)
    rec = p.end_step(rows=3)
    assert rec.decode_kv_entries == {"kernel": 6, "scatter": 9}
    assert rec.to_dict()["decode_kv_entries"] == {"kernel": 6, "scatter": 9}
    # a step that dispatched no decode over a paged arena carries no such
    # key; outside a step nothing is counted
    p.begin_step()
    assert "decode_kv_entries" not in p.end_step().to_dict()
    p.decode_kv_entries("kernel", 1)
    p.begin_step()
    assert p.end_step().decode_kv_entries is None


@pytest.mark.parametrize("stages", [1, 2])
def test_a_one_request_slot_of_four_walks_a_fraction_of_its_rectangle(
        params, stages, monkeypatch):
    """The chunked-prefill kernel's walk, counted by the program itself
    (the grid's length, ``serve_prefill_chunk``'s second result, read once
    a later fetch shows it landed — no wait of its own): ONE request in a
    slot of four rows walks its own row's written cells, summed over the
    chunks' layer calls, where the rectangle is every row at the table's
    whole width. Step records and ``/metrics`` carry the same two sums."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    eng = PipelineEngine(
        CFG, params, num_stages=stages, devices=jax.devices()[:stages],
        cache_dtype=jnp.float32,
    )
    bs, cap, chunk, rows = 8, 128, 16, 4
    srv = eng.serve(
        capacity=cap, batch_per_slot=rows, kv_block_size=bs, kv_blocks=129,
        prefill_chunk=chunk,
    )
    live_c = REGISTRY.get("server_prefill_cells_live_total")
    walk_c = REGISTRY.get("server_prefill_cells_walked_total")
    before = (live_c.value, walk_c.value)
    n0 = srv.stepline.steps_total
    srv.submit(prompt(70, n=40), 6)  # a bucket of 64: four chunks of 16
    srv.run_until_idle()
    for _ in range(3):
        srv.step()
    recs = srv.stepline_snapshot()[n0 - srv.stepline.steps_total:]
    srv.close()
    live = sum(r["prefill_cells_live"] for r in recs)
    walked = sum(r["prefill_cells_walked"] for r in recs)
    T = cap // bs  # 16 table entries, 8 a cell: two cells a run
    kv, tiles = CFG.num_key_value_heads, 1
    assert walked == 4 * rows * kv * tiles * (T // 8) * CFG.num_hidden_layers
    # chunks 1-3 hold real queries and see 2, 4 and 5 entries of one row
    # (one cell each); the fourth is past the prompt and walks nothing
    assert live == 3 * kv * tiles * 1 * CFG.num_hidden_layers
    assert 0 < live / walked < 0.30
    assert live_c.value - before[0] == live
    assert walk_c.value - before[1] == walked
    # the decode steps after the admission landed no prefill counters
    assert (recs[-1]["prefill_cells_live"], recs[-1]["prefill_cells_walked"]
            ) == (0, 0)
    text = REGISTRY.prometheus_text()
    assert "\nserver_prefill_cells_live_total " in text
    assert "\nserver_prefill_cells_walked_total " in text


def test_the_gather_path_walks_no_prefill_cell(params):
    """``paged_attn='xla'`` serves prefill by the gather: no kernel, no
    walk, both counters stay where they were."""
    eng = PipelineEngine(
        CFG, params, num_stages=1, devices=jax.devices()[:1],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(
        capacity=64, batch_per_slot=2, kv_block_size=8, kv_blocks=33,
        prefill_chunk=16, paged_attn="xla",
    )
    n0 = srv.stepline.steps_total
    srv.submit(prompt(71, n=20), 3)
    srv.run_until_idle()
    recs = srv.stepline_snapshot()[n0 - srv.stepline.steps_total:]
    srv.close()
    assert sum(r["prefill_positions"] for r in recs) == 2 * 32
    assert sum(r["prefill_cells_walked"] for r in recs) == 0
    assert sum(r["prefill_cells_live"] for r in recs) == 0
