"""Fault-tolerant serving: deterministic chaos against the resilience layer.

The serving daemon must shed load (bounded queue, deadlines), absorb
transient faults with NO effect on output (greedy token-exactness vs the
fault-free run), contain persistent faults to exactly the affected requests
(co-resident slots finish, the daemon keeps admitting), and recover from a
crash via atomic auto-snapshots — all observable through the obs registry.
Faults are injected with ``runtime/faults.FaultPlan`` at the named sites the
server actually crosses, so every scenario here is reproducible bit-for-bit.
"""

import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.faults import (
    FaultPlan, FaultSpec, PermanentFault, TransientFault, backoff_delays,
    is_transient,
)
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.server import (
    DeadlineExceeded, PipelineServer, QueueFull, RequestFailed, ServerClosed,
    load_snapshot, save_snapshot,
)

# an end-of-text id the 256-token vocabulary cannot emit: random weights then
# never end a request before a test extracts or snapshots it
CFG = tiny_llama(num_hidden_layers=8, eos_token_id=256)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle_tokens(params, prompt, max_new):
    res = generate(CFG, params, prompt, max_new, cache_dtype=jnp.float32)
    L = int(res.lengths[0])
    return list(res.tokens[0, len(prompt) : L])


def counter_value(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    if labels:
        return fam.labels(**labels).value
    return fam.value


def prompt(seed, n=5):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


# ---------------------------------------------------------------- FaultPlan


def test_fault_plan_deterministic_and_typed():
    """Same specs + seed → identical fire sequence; kinds map to the right
    exception types; per-key specs only fire for their key."""

    def fire_seq(plan, n=20):
        seq = []
        for _ in range(n):
            try:
                plan.check("chunk_dispatch")
            except TransientFault:
                seq.append("t")
            except PermanentFault:
                seq.append("p")
            else:
                seq.append(".")
        return "".join(seq)

    mk = lambda: FaultPlan(  # noqa: E731
        [FaultSpec("chunk_dispatch", "transient", at=(1,), rate=0.3)], seed=5
    )
    a, b = fire_seq(mk()), fire_seq(mk())
    assert a == b and "t" in a

    plan = FaultPlan.permanent("request_apply", key=7)
    plan.check("request_apply", key=3)  # other key: no fire
    with pytest.raises(PermanentFault):
        plan.check("request_apply", key=7)
    with pytest.raises(PermanentFault):
        plan.check("request_apply", key=7)  # permanent never clears

    burst = FaultPlan([FaultSpec(
        "log_fetch", "transient", from_call=0, max_fires=2
    )])
    for _ in range(2):
        with pytest.raises(TransientFault):
            burst.check("log_fetch")
    burst.check("log_fetch")  # cleared after max_fires

    with pytest.raises(ValueError):
        FaultSpec("no_such_site")
    assert backoff_delays(3, 0.01, max_s=0.02) == (0.01, 0.02, 0.02)


def test_prefetched_retry_reissues_the_device_read():
    """A REAL transient fetch failure is absorbable: the failed read keeps
    the device handle and ``get_retryable`` re-issues the read,
    while ``is_transient`` sees through the tagged RuntimeError wrapper to
    the registered exception type underneath."""
    from llm_sharding_tpu.runtime.server import _Prefetched

    class FlakyHandle:
        calls = 0

        def __array__(self, *a, **k):
            type(self).calls += 1
            if type(self).calls < 3:
                raise OSError("connection dropped")
            return np.arange(4)

    p = _Prefetched(FlakyHandle(), tag="chunk m0=0")
    p.read()  # the first read fails: the error is kept WITH the handle
    assert isinstance(p.error, OSError) and p.handle is not None

    with pytest.raises(RuntimeError) as ei:  # retry 1: fails again, wrapped
        p.get_retryable()
    assert is_transient(ei.value, (OSError,))  # unwraps __cause__
    assert not is_transient(ei.value)  # unregistered: permanent
    out = p.get_retryable()  # retry 2: the re-issued read succeeds
    assert list(out) == [0, 1, 2, 3]
    assert p.error is None and p.handle is None
    assert list(p.get()) == [0, 1, 2, 3]


# ------------------------------------------------- chaos: transient faults


def test_transient_faults_every_site_token_exact(setup, tmp_path):
    """(a) A transient-fault plan armed at EVERY site retries to completion
    with greedy output token-identical to the no-fault run — and the
    retries are observable."""
    params, eng = setup
    pa, pb = prompt(21), prompt(22, n=3)

    clean = eng.serve(capacity=64)
    ra, rb = clean.submit(pa, 10), clean.submit(pb, 8)
    clean.run_until_idle()
    want_a, want_b = list(ra.tokens), list(rb.tokens)
    assert want_a == oracle_tokens(params, pa, 10)

    plan = FaultPlan([
        FaultSpec("admit_dispatch", "transient", at=(0,)),
        FaultSpec("chunk_dispatch", "transient", at=(0, 2, 5)),
        FaultSpec("log_fetch", "transient", at=(1, 4)),
        FaultSpec("snapshot_write", "transient", at=(0,)),
        FaultSpec("request_apply", "transient", at=(2,), key=0),
    ])
    retries0 = sum(
        c.value for _, c in REGISTRY.get("server_retries_total").series()
    )
    srv = eng.serve(
        capacity=64, fault_plan=plan, fault_backoff_s=0.0,
        snapshot_every_s=1e9, snapshot_path=str(tmp_path / "snap"),
    )
    srv._last_snapshot_at = -1e12  # force one snapshot_write crossing
    fa, fb = srv.submit(pa, 10), srv.submit(pb, 8)
    srv.run_until_idle()
    assert list(fa.tokens) == want_a and list(fb.tokens) == want_b
    assert fa.error is None and fb.error is None
    assert srv.health == "SERVING"
    assert plan.stats()["total_fires"] >= 7
    retries1 = sum(
        c.value for _, c in REGISTRY.get("server_retries_total").series()
    )
    assert retries1 - retries0 >= 7


# ------------------------------------------------ chaos: permanent faults


def test_permanent_request_fault_contained(setup):
    """(b) A permanent per-request fault fails ONLY that request: the
    co-resident slot row finishes token-exactly, the daemon stays alive,
    and subsequently admits and completes new requests."""
    params, eng = setup
    srv = eng.serve(
        capacity=64, batch_per_slot=2,
        fault_plan=FaultPlan.permanent("request_apply", key=0),
        fault_backoff_s=0.0,
    )
    pa, pb = prompt(31), prompt(32)
    victim = srv.submit(pa, 8)   # id 0 → poisoned
    neighbor = srv.submit(pb, 8)  # co-admitted into the same slot batch
    srv.run_until_idle()

    assert victim.done and isinstance(victim.error, PermanentFault)
    assert neighbor.error is None
    assert neighbor.tokens == oracle_tokens(params, pb, 8)
    with pytest.raises(RequestFailed) as ei:
        srv.result(victim)
    assert isinstance(ei.value.__cause__, PermanentFault)

    # the daemon keeps serving: a fresh request admits into the freed row
    # and completes, and health recovers to SERVING
    pc = prompt(33, n=4)
    rc = srv.submit(pc, 6)
    assert srv.result(rc) == oracle_tokens(params, pc, 6)
    assert srv.health == "SERVING"
    assert srv.counters.requests_failed == 1
    assert srv.counters.requests_completed == 2


def test_dispatch_fault_past_retries_degrades_then_recovers(setup):
    """A decode dispatch failing PAST the retry budget (two consecutive
    transient fires vs fault_retries=1) fails the rows it was driving
    (DEGRADED), but the daemon survives: the next submission admits,
    completes token-exactly, and health returns to SERVING."""
    params, eng = setup
    # dispatch call 1 fires, its retry (call 2) fires again → retries
    # exhausted → containment; call 3+ is clean
    srv = eng.serve(
        capacity=64,
        fault_plan=FaultPlan([
            FaultSpec("chunk_dispatch", "transient", at=(1, 2)),
        ]),
        fault_retries=1, fault_backoff_s=0.0,
    )
    pa = prompt(41)
    ra = srv.submit(pa, 8)
    srv.run_until_idle()
    assert ra.done and isinstance(ra.error, TransientFault)
    assert srv.health == "DEGRADED"
    with pytest.raises(RequestFailed):
        srv.result(ra)

    pb = prompt(42, n=4)
    rb = srv.submit(pb, 6)
    assert srv.result(rb) == oracle_tokens(params, pb, 6)
    assert srv.health == "SERVING"


def test_lost_log_fetch_contained(setup):
    """A log read lost past retries (permanent log_fetch fault) fails the
    in-flight requests but never wedges the drain loop; the daemon then
    serves new requests cleanly."""
    params, eng = setup
    srv = eng.serve(
        capacity=64,
        fault_plan=FaultPlan([
            FaultSpec("log_fetch", "permanent", at=(1,)),
        ]),
        fault_retries=0, fault_backoff_s=0.0,
    )
    ra = srv.submit(prompt(51), 8)
    srv.run_until_idle()
    assert ra.done and isinstance(ra.error, PermanentFault)
    pb = prompt(52, n=4)
    rb = srv.submit(pb, 6)
    assert srv.result(rb) == oracle_tokens(params, pb, 6)
    assert srv.health == "SERVING"


# --------------------------------------- shed paths: queue, deadline, close


def test_queue_full_and_deadline_counters(setup):
    """(c) Queue-full rejection, queued-deadline shed and in-flight
    deadline cancel all bump their counters and fail typed."""
    _, eng = setup
    srv = eng.serve(capacity=64, max_queue=2)

    qf0 = counter_value("server_rejected_total", reason="queue_full")
    dq0 = counter_value("server_deadline_expired_total", where="queued")
    di0 = counter_value("server_deadline_expired_total", where="in_flight")

    # queue-full: 2 queued (no pumping yet) → third submit rejected
    r1 = srv.submit(prompt(61), 4)
    r2 = srv.submit(prompt(62), 4, deadline_s=1e-4)
    with pytest.raises(QueueFull):
        srv.submit(prompt(63), 4)
    assert counter_value("server_rejected_total", reason="queue_full") == qf0 + 1

    # r2's deadline expires while queued → shed at admit time
    time.sleep(0.005)
    srv.run_until_idle()
    assert r1.error is None and r1.done and r1.tokens
    assert isinstance(r2.error, DeadlineExceeded)
    assert counter_value(
        "server_deadline_expired_total", where="queued"
    ) == dq0 + 1

    # in-flight expiry: admit, decode a little, sleep past the deadline,
    # and the next chunk boundary's sweep cancels the row
    r3 = srv.submit(prompt(64), 48, deadline_s=0.05)
    srv.step()  # admit + first chunk
    time.sleep(0.06)
    srv.step()  # sweep catches the expired row
    assert r3.done and isinstance(r3.error, DeadlineExceeded)
    assert counter_value(
        "server_deadline_expired_total", where="in_flight"
    ) == di0 + 1
    with pytest.raises(ValueError):
        srv.submit(prompt(65), 4, deadline_s=0.0)


def test_close_is_a_real_shutdown(setup):
    """close(): idempotent; queued requests fail with ServerClosed (their
    stream() unblocks with RequestFailed), submits are rejected, step()
    no-ops, snapshot() refuses."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    queued = srv.submit(prompt(71), 4)  # never pumped → still queued
    closed0 = counter_value("server_rejected_total", reason="closed")
    srv.close()
    srv.close()  # idempotent
    assert srv.health == "DRAINING"
    assert queued.done and isinstance(queued.error, ServerClosed)
    with pytest.raises(RequestFailed) as ei:
        list(srv.stream(queued))
    assert isinstance(ei.value.__cause__, ServerClosed)
    with pytest.raises(ServerClosed):
        srv.submit(prompt(72), 4)
    assert counter_value("server_rejected_total", reason="closed") == closed0 + 1
    assert srv.step() is False
    srv.run_until_idle()  # returns immediately
    with pytest.raises(ServerClosed):
        srv.snapshot()


def test_close_unblocks_in_flight_stream(setup):
    """An in-flight request's consumer also unblocks on close — after its
    partial tokens."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    r = srv.submit(prompt(73), 12)
    for _ in range(4):
        srv.step()
    got_before_close = len(r.tokens)
    srv.close()
    out = []
    with pytest.raises(RequestFailed):
        for t in srv.stream(r):
            out.append(t)
    # the stream replays exactly the final partials (no loss, no
    # duplication)
    assert out == list(r.tokens)
    assert len(out) >= got_before_close > 0


# ------------------------------------------------- crash recovery + health


def test_autosnapshot_crash_restore_no_loss_no_dup(setup, tmp_path):
    """(d) Auto-snapshot → kill → restore: every in-flight request resumes
    with already-streamed tokens intact, completing token-identically to
    the uninterrupted oracle (no loss, no duplication)."""
    params, eng = setup
    snap_dir = str(tmp_path / "auto")
    snaps0 = counter_value("server_snapshots_total")
    srv = eng.serve(
        capacity=64, snapshot_every_s=0.0, snapshot_path=snap_dir,
    )
    pa, pb = prompt(81), prompt(82, n=3)
    ra = srv.submit(pa, 12)
    rb = srv.submit(pb, 10)
    for _ in range(5):
        srv.step()  # both mid-decode; a snapshot lands after every step
    assert counter_value("server_snapshots_total") > snaps0
    streamed = {0: list(ra.tokens), 1: list(rb.tokens)}
    assert any(streamed.values())
    del srv  # the "crash": the daemon dies between steps

    srv2 = PipelineServer.restore(eng, load_snapshot(snap_dir))
    revived = {
        r.id: r for r in list(srv2._rows) + list(srv2._queue)
        if r is not None
    }
    # already-streamed tokens are replayed into the revived requests
    for rid, toks in streamed.items():
        assert revived[rid].tokens[: len(toks)] == toks
    srv2.run_until_idle()
    assert revived[0].tokens == oracle_tokens(params, pa, 12)
    assert revived[1].tokens == oracle_tokens(params, pb, 10)
    # no tmp/old turds from the atomic writes
    leftovers = [
        d for d in os.listdir(tmp_path)
        if d.startswith("auto") and d != "auto"
    ]
    assert leftovers == []


def test_save_snapshot_atomic_overwrite(setup, tmp_path):
    """Repeated saves to one path atomically replace the previous snapshot
    (tmp+rename), and a snapshot taken later wins."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    path = str(tmp_path / "snap")
    r = srv.submit(prompt(91), 6)
    srv.step()
    save_snapshot(srv.snapshot(), path)
    mid = load_snapshot(path)
    assert mid["counters"]["requests_completed"] == 0
    srv.run_until_idle()
    save_snapshot(srv.snapshot(), path)  # overwrite in place
    snap = load_snapshot(path)
    assert snap["counters"]["requests_completed"] == 1
    assert len(r.tokens) == 6
    assert sorted(os.listdir(tmp_path)) == ["snap"]


def test_load_snapshot_recovers_parked_previous(setup, tmp_path):
    """A crash INSIDE save_snapshot's rename window leaves ``path`` absent
    and the previous snapshot parked at ``path.old.<pid>`` —
    ``load_snapshot`` must fall back to it instead of failing recovery."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    path = str(tmp_path / "snap")
    r = srv.submit(prompt(93), 6)
    srv.run_until_idle()
    save_snapshot(srv.snapshot(), path)
    os.rename(path, path + ".old.12345")  # simulate the mid-swap crash
    snap = load_snapshot(path)  # falls back to the parked sibling
    assert snap["counters"]["requests_completed"] == 1
    assert r.tokens  # the pre-crash run really decoded
    srv2 = PipelineServer.restore(eng, snap)
    assert srv2.counters.requests_completed == 1


def test_failed_autosnapshot_keeps_serving(setup, tmp_path):
    """A persistently failing snapshot writer is counted, never fatal."""
    params, eng = setup
    fails0 = counter_value("server_snapshot_failures_total")
    srv = eng.serve(
        capacity=64, snapshot_every_s=0.0,
        snapshot_path=str(tmp_path / "s"),
        fault_plan=FaultPlan.permanent("snapshot_write"),
        fault_retries=0, fault_backoff_s=0.0,
    )
    pa = prompt(95, n=4)
    ra = srv.submit(pa, 6)
    srv.run_until_idle()
    assert ra.tokens == oracle_tokens(params, pa, 6)
    assert counter_value("server_snapshot_failures_total") > fails0
    assert not os.path.isdir(str(tmp_path / "s"))


def test_deadline_survives_snapshot_as_remaining_budget(setup):
    """Deadlines serialize as time-remaining and re-arm on restore — a
    revived request keeps (roughly) the budget it had left, not a stale
    absolute timestamp from the dead process."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    r = srv.submit(prompt(96), 8, deadline_s=120.0)
    srv.step()
    snap = srv.snapshot()
    d = next(x for x in snap["rows"] + snap["queue"] if x is not None)
    assert 0.0 < d["deadline_left"] <= 120.0
    srv2 = PipelineServer.restore(eng, snap)
    revived = next(
        x for x in list(srv2._rows) + list(srv2._queue) if x is not None
    )
    assert revived.deadline_at is not None
    assert revived.deadline_at - time.perf_counter() <= 120.0
    srv2.run_until_idle()
    assert revived.error is None
    assert revived.tokens == oracle_tokens(params, prompt(96), 8)


def test_health_state_machine_and_gauge(setup):
    """SERVING → DEGRADED (containment) → SERVING (clean step) → DRAINING
    (close), with the one-hot gauge tracking the worst live state."""
    _, eng = setup
    srv = eng.serve(
        capacity=64,
        fault_plan=FaultPlan.permanent("request_apply", key=0),
        fault_backoff_s=0.0,
    )
    assert srv.health == "SERVING"
    victim = srv.submit(prompt(97), 6)
    while not victim.done:
        srv.step()
    assert srv.health == "DEGRADED"
    gauge = REGISTRY.get("server_health_state")
    assert gauge.labels(state="DEGRADED").value == 1.0
    ok = srv.submit(prompt(98, n=4), 4)
    srv.run_until_idle()
    assert ok.error is None and srv.health == "SERVING"
    srv.close()
    assert srv.health == "DRAINING"


def test_replica_step_site_keyed_per_group():
    """The replica-level crash site (``replica_step``, keyed by the dp
    router with the replica's device-group index): a plan armed for one
    group must count and fire per key — the other replicas' checks advance
    their own counters and never trip it."""
    plan = FaultPlan.permanent("replica_step", key=1, start=2)
    for _ in range(5):
        plan.check("replica_step", key=0)  # another replica: never fires
    plan.check("replica_step", key=1)  # pass 0
    plan.check("replica_step", key=1)  # pass 1
    with pytest.raises(PermanentFault):
        plan.check("replica_step", key=1)  # pass 2 = start -> fires
    assert plan.stats()["total_fires"] == 1
    # unknown sites still refuse at construction (typo'd chaos plans fail
    # loudly, not vacuously)
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("replica_crash")


# -------------------------------------------------- ingress chaos (ISSUE 9)


def _ingress_over(eng, fault_plan=None, tenants=None, **serve_kw):
    """A paged server + HTTP front door for the ingress chaos scenarios
    (paged so the KV-block hygiene assertions have an allocator to audit)."""
    from llm_sharding_tpu.runtime.ingress import IngressServer

    srv = eng.serve(
        capacity=64, kv_block_size=4, kv_blocks=80, **serve_kw
    )
    ing = IngressServer(
        srv, fault_plan=fault_plan, tenants=tenants,
        poll_interval_s=0.0005,
    )
    ing.start()
    return srv, ing


def _post(port, body, headers=None, timeout=120.0):
    import http.client as _hc
    import json as _json

    conn = _hc.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/completions", _json.dumps(body),
            {"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        data = resp.read()
        return resp.status, dict(resp.getheaders()), (
            _json.loads(data) if data else None
        )
    finally:
        conn.close()


def test_http_request_fault_site_sheds_typed(setup):
    """An injected ``http_request`` fault (infrastructure trouble at the
    front door, keyed by tenant) answers 503 + Retry-After — no handler
    traceback, no crashed daemon — and the very next request serves."""
    params, eng = setup
    plan = FaultPlan.transient_at("http_request", 0, key="default")
    srv, ing = _ingress_over(eng, fault_plan=plan)
    try:
        f0 = counter_value(
            "server_ingress_requests_total",
            tenant="default", outcome="fault",
        )
        body = {"prompt": [int(t) for t in prompt(81)], "max_tokens": 4}
        status, headers, payload = _post(ing.port, body)
        assert status == 503
        assert payload["error"]["type"] == "ingress_fault"
        assert int(headers["Retry-After"]) >= 1
        assert counter_value(
            "server_ingress_requests_total",
            tenant="default", outcome="fault",
        ) == f0 + 1
        # the shed was EARLY: the backend never saw the request
        assert srv.counters.requests_submitted == 0
        status, _, payload = _post(ing.port, body)
        assert status == 200
        assert len(payload["choices"][0]["token_ids"]) == 4
        assert plan.stats()["total_fires"] == 1
    finally:
        ing.stop()
        srv.close()


def test_slow_client_fault_frees_row_and_kv_blocks(setup):
    """A ``slow_client`` fault mid-SSE (the client stalled/vanished,
    deterministically injected at the second event write) takes the real
    disconnect path: the backend row is cancelled and every KV block
    returns to the pool — the allocator audits clean."""
    from llm_sharding_tpu.runtime.faults import FaultPlan as FP

    params, eng = setup
    plan = FP([FaultSpec("slow_client", "transient", at=(1,),
                         key="default")])
    srv, ing = _ingress_over(eng, fault_plan=plan)
    try:
        c0 = srv.counters.requests_cancelled
        d0 = counter_value(
            "server_ingress_requests_total",
            tenant="default", outcome="disconnect",
        )
        import http.client as _hc
        import json as _json

        conn = _hc.HTTPConnection("127.0.0.1", ing.port, timeout=120)
        conn.request(
            "POST", "/v1/completions",
            _json.dumps({
                "prompt": [int(t) for t in prompt(82)],
                "max_tokens": 48, "stream": True,
            }),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        first = resp.readline()  # event 0 made it out before the stall
        assert first.startswith(b"data: ")
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if (
                srv.counters.requests_cancelled == c0 + 1
                and srv._alloc.in_use == 0
            ):
                break
            time.sleep(0.02)
        conn.close()
        assert srv.counters.requests_cancelled == c0 + 1
        srv._alloc.check()
        assert srv._alloc.in_use == 0, (
            f"disconnect leaked {srv._alloc.in_use} KV block(s)"
        )
        assert counter_value(
            "server_ingress_requests_total",
            tenant="default", outcome="disconnect",
        ) == d0 + 1
        assert plan.stats()["total_fires"] == 1
    finally:
        ing.stop()
        srv.close()


def test_flood_tenant_leaves_other_tenant_ttft_bounded(setup):
    """Tenant A floods; tenant B's p99 TTFT stays a small fraction of the
    flood's wall time — starvation (strict FIFO) would push B's first
    token to roughly the END of the flood."""
    import http.client as _hc
    import json as _json
    import threading as _th

    from llm_sharding_tpu.runtime.fairness import TenantConfig

    params, eng = setup
    srv, ing = _ingress_over(
        eng, tenants=[TenantConfig("a"), TenantConfig("b")],
    )
    try:
        t0 = time.monotonic()
        a_done = []
        lock = _th.Lock()

        def one_flood(i):
            _post(ing.port, {
                "prompt": [int(t) for t in prompt(90 + i)],
                "max_tokens": 32,
            }, {"X-Tenant": "a"}, timeout=300)
            with lock:
                a_done.append(time.monotonic())

        flood = [_th.Thread(target=one_flood, args=(i,)) for i in range(8)]
        for t in flood:
            t.start()
        time.sleep(0.05)
        # B: three streaming requests THROUGH the flood, TTFT measured
        # client-side at the first SSE event
        ttfts = []
        for i in range(3):
            conn = _hc.HTTPConnection("127.0.0.1", ing.port, timeout=300)
            sent = time.monotonic()
            conn.request(
                "POST", "/v1/completions",
                _json.dumps({
                    "prompt": [int(t) for t in prompt(95 + i)],
                    "max_tokens": 4, "stream": True,
                }),
                {"Content-Type": "application/json", "X-Tenant": "b"},
            )
            resp = conn.getresponse()
            assert resp.status == 200
            line = resp.readline()
            assert line.startswith(b"data: ")
            ttfts.append(time.monotonic() - sent)
            while resp.readline():  # drain to [DONE]/EOF
                pass
            conn.close()
        for t in flood:
            t.join(timeout=300)
        flood_span = max(a_done) - t0
        p99 = sorted(ttfts)[-1]  # 3 samples: p99 = worst
        assert p99 < max(0.5 * flood_span, 0.5), (
            f"tenant B's worst TTFT {p99:.3f}s looks starved "
            f"(flood wall time {flood_span:.3f}s)"
        )
        srv._alloc.check()
        assert srv._alloc.in_use == 0
    finally:
        ing.stop()
        srv.close()
