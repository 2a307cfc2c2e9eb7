"""Async executor (ISSUE 17): scheduler/executor split with multi-step
in-flight dispatch (``runtime/async_exec.py``).

The acceptance bar is TOKEN IDENTITY: with ``inflight_steps=N>1`` the
executor keeps up to N state-donating decode dispatches enqueued on device
while an off-thread scheduler plans admissions/evictions and a completion
sidecar applies landed logs — and greedy output must equal the serial
(``inflight_steps=1``) run byte-for-byte on every workload shape the server
supports: plain decode, chunked prefill, radix prefix hits, speculative
decode. On top of that: a mid-flight snapshot restores token-identically
(settled-boundary contract), the chaos scenarios (deadline shed via the
scheduler delta, contained permanent fault, dp failover) stay green at
depth 2 with the paged allocator and radix invariants intact, and the
stepline's exact accounting survives the new plan/publish/drain phases.

CI's chaos lane reruns ``test_resilience.py`` + this module under
``SHARDLINT_LOCK_ORDER=1 SERVE_TEST_INFLIGHT=2`` so every lock the
scheduler/sidecar threads take is order-checked while overlapped
dispatches are actually in flight.
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
from llm_sharding_tpu.runtime.faults import FaultPlan, PermanentFault
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.replicated import ReplicatedServer
from llm_sharding_tpu.runtime.server import (
    DeadlineExceeded, PipelineServer,
)

CFG = tiny_llama(num_hidden_layers=8)
BS = 8  # paged block size for the radix workloads


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(3), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return list(res.tokens[0, len(p): int(res.lengths[0])])


def prompts(seed, n, lo=3, hi=9):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, CFG.vocab_size, int(l)).astype(np.int32)
        for l in rng.integers(lo, hi, n)
    ]


def gauge(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value if labels else fam.value


# ------------------------------------------------------------- construction


def test_inflight_steps_validated(setup):
    _, eng = setup
    with pytest.raises(ValueError, match="inflight_steps"):
        eng.serve(capacity=64, inflight_steps=0)


def test_depth1_is_the_serial_path(setup):
    """Rollback contract: inflight_steps=1 (the default) spawns NO helper
    threads — the serial step loop is byte-identical to before."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    assert srv.inflight_steps == 1
    assert srv._scheduler is None and srv._sidecar is None
    srv.close()


def test_helper_threads_start_and_stop(setup):
    _, eng = setup
    srv = eng.serve(capacity=64, inflight_steps=2)
    assert srv._scheduler.is_alive() and srv._sidecar.is_alive()
    assert gauge("server_inflight_steps") == 2.0
    srv.close()
    srv._scheduler.join(timeout=5.0)
    srv._sidecar.join(timeout=5.0)
    assert not srv._scheduler.is_alive() and not srv._sidecar.is_alive()


# ------------------------------------------------ THE token-identity matrix

# every workload shape the server supports must be token-identical to its
# serial run at every depth: the device executes ONE deterministic donated
# state chain regardless of how many dispatches the host keeps enqueued
WORKLOADS = {
    "plain": {},
    "chunked": dict(prefill_chunk=8),
    "radix": dict(kv_block_size=BS, kv_blocks=160, prefix_cache="hbm"),
    "spec": dict(speculate=2),
}


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_token_identity_vs_serial(setup, depth, workload):
    params, eng = setup
    kw = WORKLOADS[workload]
    lo, hi = (9, 14) if workload == "chunked" else (3, 9)
    ps = prompts(100 * depth + len(workload), 5, lo=lo, hi=hi)
    if workload == "radix":
        # shared head so the second wave actually HITS the radix tree
        head = prompts(7, 1, lo=2 * BS, hi=2 * BS + 1)[0]
        ps = [np.concatenate([head, p]) for p in ps]

    def run(d):
        srv = eng.serve(capacity=64, inflight_steps=d, **kw)
        reqs = [srv.submit(p, 10) for p in ps]
        srv.run_until_idle()
        if workload == "radix":
            # second wave: same prefixes, now cached — hit path under depth
            reqs += [srv.submit(p, 10) for p in ps]
            srv.run_until_idle()
            assert srv.prefix_cache_stats()["hit_tokens"] > 0
        toks = [list(r.tokens) for r in reqs]
        assert all(r.error is None for r in reqs)
        srv.close()
        return toks

    assert run(depth) == run(1)


def test_tokens_match_oracle_under_depth(setup):
    """Not just self-consistent: the async run equals the single-prompt
    oracle (the generate() reference) per request."""
    params, eng = setup
    ps = prompts(23, 4)
    srv = eng.serve(capacity=64, inflight_steps=3)
    reqs = [srv.submit(p, 12) for p in ps]
    srv.run_until_idle()
    for r, p in zip(reqs, ps):
        assert list(r.tokens) == oracle(params, p, 12)
    srv.close()


# --------------------------------------------------- settled-boundary paths


def test_mid_flight_snapshot_restore_token_exact(setup):
    """snapshot() mid-decode with overlapped dispatches in flight settles
    to a step boundary first; the restored server (which inherits
    inflight_steps via format-5 serve_kwargs) finishes every request
    token-identically to the uninterrupted oracle."""
    params, eng = setup
    srv = eng.serve(capacity=64, inflight_steps=2)
    ps = prompts(31, 3)
    reqs = [srv.submit(p, 12) for p in ps]
    for _ in range(4):
        srv.step()  # several dispatches enqueued beyond the applied logs
    snap = srv.snapshot()
    assert snap["format"] == 8
    assert snap["serve_kwargs"]["inflight_steps"] == 2
    ids = [r.id for r in reqs]
    srv.close()

    srv2 = PipelineServer.restore(eng, snap)
    assert srv2.inflight_steps == 2
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    srv2.run_until_idle()
    for rid, p in zip(ids, ps):
        assert restored[rid].tokens == oracle(params, p, 12)
    srv2.close()


def test_extract_settles_in_flight_dispatches(setup):
    """extract() on a healthy async server auto-settles (drains the
    overlapped window) so the extracted state is a step boundary — the
    resumed request must not lose the tokens that were still in flight."""
    params, eng = setup
    src = eng.serve(capacity=64, inflight_steps=2)
    dst = eng.serve(capacity=64)
    p = prompts(37, 1)[0]
    r = src.submit(p, 14)
    for _ in range(3):
        src.step()
    st = src.extract(r)  # settle=None → auto-settle (SERVING, depth>1)
    dst.adopt(st, r)
    dst.run_until_idle()
    assert r.tokens == oracle(params, p, 14)
    src.close()
    dst.close()


# ------------------------------------------------------------ chaos @ depth


def test_deadline_shed_through_scheduler_delta(setup):
    """Deadline expiry at depth 2: the off-thread scheduler plans the
    expirations and the executor applies them from the published delta
    (the executor re-validates each candidate at the boundary)."""
    params, eng = setup
    srv = eng.serve(capacity=64, inflight_steps=2)
    dq0 = gauge("server_deadline_expired_total", where="queued")
    di0 = gauge("server_deadline_expired_total", where="in_flight")

    # queued shed: expires before any pumping; the scheduler's delta (or
    # the executor's no-delta fallback on the very first step) sheds it
    rq = srv.submit(prompts(41, 1)[0], 4, deadline_s=1e-4)
    time.sleep(0.005)
    srv._scheduler.kick()
    time.sleep(0.08)  # let the scheduler publish a delta with the expiry
    srv.step()
    assert rq.done and isinstance(rq.error, DeadlineExceeded)
    assert gauge(
        "server_deadline_expired_total", where="queued"
    ) == dq0 + 1

    # in-flight cancel: admitted, decoding, deadline passes mid-window
    ri = srv.submit(prompts(42, 1)[0], 48, deadline_s=0.05)
    srv.step()  # admit + dispatch
    time.sleep(0.06)
    srv._scheduler.kick()
    time.sleep(0.08)
    srv.step()  # delta carries the expired row → cancelled at the boundary
    assert ri.done and isinstance(ri.error, DeadlineExceeded)
    assert gauge(
        "server_deadline_expired_total", where="in_flight"
    ) == di0 + 1

    # the daemon is still healthy and exact afterwards
    p = prompts(43, 1)[0]
    rc = srv.submit(p, 6)
    assert srv.result(rc) == oracle(params, p, 6)
    srv.close()


def test_permanent_fault_contained_at_depth2(setup):
    """A poisoned request at depth 2 fails alone: the co-resident row
    finishes token-exactly, new requests admit, and the paged allocator +
    radix tree invariants hold after the containment (no leaked blocks
    from the overlapped dispatches the containment unwound)."""
    params, eng = setup
    srv = eng.serve(
        capacity=64, batch_per_slot=2, inflight_steps=2,
        kv_block_size=BS, kv_blocks=160, prefix_cache="hbm",
        fault_plan=FaultPlan.permanent("request_apply", key=0),
        fault_backoff_s=0.0,
    )
    pa, pb = prompts(51, 2)
    victim = srv.submit(pa, 8)    # id 0 → poisoned
    neighbor = srv.submit(pb, 8)  # co-admitted into the same slot batch
    srv.run_until_idle()
    assert victim.done and isinstance(victim.error, PermanentFault)
    assert neighbor.error is None
    assert neighbor.tokens == oracle(params, pb, 8)

    pc = prompts(52, 1, lo=4, hi=5)[0]
    rc = srv.submit(pc, 6)
    assert srv.result(rc) == oracle(params, pc, 6)
    assert srv.health == "SERVING"
    srv._alloc.check()
    srv._radix.check()
    srv.close()


def test_dp2_failover_at_depth2(setup):
    """Replica failover with the async executor on BOTH replicas: the
    failing replica's requests replay (extract(settle=False) — no settle
    on a dead replica) and finish token-identically on the survivor."""
    params, _ = setup
    srv = ReplicatedServer(
        CFG, params, data_parallel=2, num_stages=2,
        devices=jax.devices()[:4], cache_dtype=jnp.float32,
        capacity=64, inflight_steps=2,
        fault_plan=FaultPlan.permanent("replica_step", key=0, start=4),
    )
    assert all(s.inflight_steps == 2 for s in srv.servers)
    ps = prompts(61, 4)
    reqs = [srv.submit(p, 12) for p in ps]
    srv.run_until_idle()
    assert len(srv.servers) == 1
    for r, p in zip(reqs, ps):
        assert r.error is None, (r.id, r.error)
        assert r.tokens == oracle(params, p, 12), (
            f"req {r.id} diverged after failover at depth 2"
        )
    srv.close()


# ----------------------------------------------------------- observability


def test_metrics_and_scheduler_lag_populated(setup):
    _, eng = setup
    srv = eng.serve(capacity=64, inflight_steps=2)
    fam0 = REGISTRY.get("server_scheduler_lag_seconds")
    lag0 = fam0.labels().count if fam0 is not None else 0
    for p in prompts(71, 4):
        srv.submit(p, 10)
    srv.run_until_idle()
    assert gauge("server_inflight_steps") == 2.0
    # deterministic: force one planned delta through the executor (the
    # tight run_until_idle loop may outpace the scheduler thread)
    srv._scheduler.kick()
    time.sleep(0.1)
    srv.step()
    fam = REGISTRY.get("server_scheduler_lag_seconds")
    assert fam is not None and fam.labels().count > lag0, (
        "no scheduler delta was ever consumed — the executor ran serial"
    )
    srv.close()


def test_stepline_async_phases_and_exact_accounting(setup):
    """The new plan/publish/drain phases slot into the stepline WITHOUT
    breaking its exact-accounting invariant: every step's phases + blocked
    + unattributed still sum to wall, unattributed stays under 5%, and the
    publish/drain phases actually appear. The scheduler's off-thread plan
    time feeds the phase histogram only (observe_offthread) — it must NOT
    appear in step records, which would double-count overlapped time."""
    _, eng = setup
    srv = eng.serve(capacity=64, inflight_steps=2)
    for p in prompts(81, 4):
        srv.submit(p, 10)
    srv.run_until_idle()
    recs = srv.stepline_snapshot()
    assert recs, "the async executor recorded no steps"
    phases_seen = set()
    for r in recs:
        host = sum(r["phases"].values())
        assert r["host_s"] == pytest.approx(host, abs=1e-12)
        assert r["wall_s"] == pytest.approx(
            host + r["blocked_s"] + r["unattributed_s"], abs=1e-9
        )
        assert "plan" not in r["phases"], (
            "off-thread plan time leaked into a step record — it overlaps "
            "the step and would break wall-clock accounting"
        )
        phases_seen |= set(r["phases"])
    assert {"publish", "drain", "dispatch", "apply"} <= phases_seen
    wall = sum(r["wall_s"] for r in recs)
    unatt = sum(r["unattributed_s"] for r in recs)
    # lock-order instrumentation (the chaos lane's SHARDLINT_LOCK_ORDER=1)
    # adds bookkeeping to every named-lock acquisition — measurement
    # overhead that lands in the unattributed slice, not a coverage
    # regression; the 5% acceptance bar applies to uninstrumented runs
    cap = 0.12 if os.environ.get("SHARDLINT_LOCK_ORDER") == "1" else 0.05
    assert wall > 0 and unatt / wall < cap
    # the scheduler's plan time landed in the phase histogram out-of-band
    srv._scheduler.kick()
    time.sleep(0.1)  # deterministic: one more plan cycle completes
    snap = REGISTRY.json_snapshot()
    series = snap["server_step_phase_seconds"]["series"]
    plan = [s for s in series if s["labels"].get("phase") == "plan"]
    assert plan and plan[0]["count"] > 0
    srv.close()
