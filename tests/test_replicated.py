"""Data-parallel continuous batching (VERDICT r3 next-#5): D replica
servers over disjoint device groups behind a least-loaded router, every
request token-exact vs the solo oracle and the load actually spread — plus
the replica SUPERVISION chaos suite (ISSUE 6): a replica killed mid-decode
fails over with every affected stream finishing token-identically on a
survivor, drain/spawn elasticity drops zero streams, queued requests on a
quarantined replica re-route, and prefix-bound rows re-resolve their local
handle.

``REPLICA_TEST_DP`` (default 2) sets the replica count — tier-1 CI reruns
this module at dp3 so failover fans one replica's requests across TWO
survivors (odd-replica routing/migration math a single survivor never
exercises). All chaos plans use fixed seeds/indices: deterministic gate.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs.metrics import (
    REGISTRY, REPLICA_FAILOVERS, REQUESTS_MIGRATED,
)
from llm_sharding_tpu.runtime.faults import FaultPlan
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.replicated import ReplicatedServer
from llm_sharding_tpu.runtime.server import DEGRADED, DRAINING, SERVING

CFG = tiny_llama(num_hidden_layers=8)
DP = int(os.environ.get("REPLICA_TEST_DP", "2"))


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(5), dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup(params):
    srv = ReplicatedServer(
        CFG, params, data_parallel=DP, num_stages=2,
        devices=jax.devices()[: 2 * DP], cache_dtype=jnp.float32,
        capacity=64,
    )
    return params, srv


def make_rsrv(params, **kw):
    """A fresh supervised dp server for the chaos tests (they mutate the
    replica set — the shared module fixture must stay intact)."""
    return ReplicatedServer(
        CFG, params, data_parallel=DP, num_stages=2,
        devices=jax.devices()[: 2 * DP], cache_dtype=jnp.float32,
        capacity=64, **kw,
    )


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p[None], n, cache_dtype=jnp.float32, **kw)
    return [int(x) for x in res.tokens[0, len(p): int(res.lengths[0])]]


def test_dp_serve_token_exact_and_spread(setup):
    """dp × pp2: 6 requests (mixed greedy/sampled/filtered) served across
    all replicas, each token-exact vs its solo oracle."""
    params, srv = setup
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, CFG.vocab_size, int(n)).astype(np.int32)
        for n in rng.integers(3, 7, 6)
    ]
    kws = [
        {}, dict(temperature=0.9, seed=3), dict(temperature=1.1, seed=7, top_k=5),
        {}, dict(temperature=0.7, seed=1, top_p=0.8), {},
    ]
    reqs = [srv.submit(p, 8, **kw) for p, kw in zip(prompts, kws)]
    srv.run_until_idle()
    for r, p, kw in zip(reqs, prompts, kws):
        assert r.tokens == oracle(params, p, 8, **kw), f"req {r.id} mismatch"
    # the router spread work over EVERY replica
    per_replica = [s.counters.requests_completed for s in srv.servers]
    assert all(n > 0 for n in per_replica), per_replica
    assert srv.counters.requests_completed == 6


def test_dp_serve_stream_and_cancel(setup):
    params, srv = setup
    rng = np.random.default_rng(1)
    pa = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    ra = srv.submit(pa, 10)
    rb = srv.submit(pb, 30)
    streamed = list(srv.stream(ra))
    assert streamed == oracle(params, pa, 10)
    assert srv.cancel(rb)
    srv.run_until_idle()
    assert rb.done


def test_dp_serve_privacy_entry(setup):
    params, srv = setup
    rng = np.random.default_rng(2)
    p = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    r = srv.submit_embedding(srv.embed_prompt(p)[0], 8)
    srv.run_until_idle()
    assert r.tokens == oracle(params, p, 8)


def test_dp_prefix_prefill_and_release(setup):
    """prefill_prefix fans out per replica; release_prefix releases every
    per-replica handle (the paged never-fits ceiling depends on it) and
    rejects a non-replicated handle typed."""
    params, srv = setup
    rng = np.random.default_rng(3)
    pfx = rng.integers(1, CFG.vocab_size, 8).astype(np.int32)
    sfx = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
    h = srv.prefill_prefix(pfx)
    r = srv.submit(sfx, 6, prefix=h)
    srv.run_until_idle()
    assert r.tokens == oracle(params, np.concatenate([pfx, sfx]), 6)
    srv.release_prefix(h)
    assert all(lh.blocks is None for lh in h.per_server.values())
    with pytest.raises(ValueError, match="ReplicatedPrefixHandle"):
        srv.release_prefix(h.per_server[srv.servers[0]])


def test_dp_devices_not_divisible_rejected():
    params = llama.init_params(CFG, jax.random.key(5), dtype=jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        ReplicatedServer(
            CFG, params, data_parallel=3, devices=jax.devices()[:4],
        )


def test_cancel_routed_to_owner_replica(setup):
    """cancel() must reach the OWNING replica and must not disturb another
    replica's request occupying the same row number (the row-ownership
    guard in PipelineServer.cancel)."""
    params, srv = setup
    rng = np.random.default_rng(3)
    pa = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    ra = srv.submit(pa, 20)  # replica A, row 0
    rb = srv.submit(pb, 20)  # replica B, row 0 (least-loaded router)
    sa, sb = srv._owner[ra], srv._owner[rb]
    assert sa is not sb, "router did not spread the two requests"
    srv.step()
    assert srv.cancel(rb)
    assert rb.done and not ra.done
    # a stray cancel on the WRONG server is refused by the ownership guard
    # (ra is live on sa; sb holds a different/no request in that row)
    assert not sb.cancel(ra)
    assert not ra.done
    # the other replica's same-numbered row kept decoding; A still exact
    srv.run_until_idle()
    assert ra.tokens == oracle(params, pa, 20)


# --------------------------------------------------------------- satellites


def test_pick_skips_non_serving_replicas(setup):
    """Health-aware routing: a DEGRADED replica must not receive new
    traffic while any SERVING replica exists (it used to win least-loaded
    ties); with none SERVING the router falls back in severity order."""
    params, srv = setup
    s0 = srv.servers[0]
    rest = srv.servers[1:]
    try:
        s0._health = DEGRADED
        for _ in range(2 * DP):
            assert srv._pick() is not s0
        for s in rest:
            s._health = DEGRADED
        assert srv._pick() in srv.servers  # severity fallback still routes
        s0._health = DRAINING
        for _ in range(2 * DP):
            assert srv._pick() is not s0  # DEGRADED beats DRAINING
    finally:
        for s in srv.servers:
            s._health = SERVING


def test_close_aggregates_replica_errors(params):
    """close() must close EVERY replica even when one raises, then re-raise
    one aggregated error — a wedged replica can't block daemon shutdown."""
    srv = make_rsrv(params)
    boom = RuntimeError("wedged device")

    def bad_close():
        raise boom

    srv.servers[0].close = bad_close
    with pytest.raises(RuntimeError, match=rf"1 of {DP} replica"):
        srv.close()
    # every OTHER replica really closed despite the wedged one
    assert all(s._closed for s in srv.servers[1:])


def test_stats_carries_per_replica_health_and_kv(params):
    """/statz per-replica entries name WHICH replica is degraded (health)
    and, on paged replicas, its KV-block occupancy."""
    srv = make_rsrv(params)
    try:
        st = srv.stats()
        assert [e["replica"] for e in st["replicas"]] == list(range(DP))
        assert all(e["health"] == SERVING for e in st["replicas"])
        assert st["offline_groups"] == []
        assert "kv_blocks_in_use" not in st["replicas"][0]  # dense
    finally:
        srv.close()
    paged = ReplicatedServer(
        CFG, params, data_parallel=2, num_stages=2,
        devices=jax.devices()[:4], cache_dtype=jnp.float32, capacity=64,
        kv_block_size=8, kv_blocks=24,
    )
    try:
        e = paged.stats()["replicas"][0]
        assert e["kv_blocks_total"] == 23  # block 0 is the trash sink
        assert e["kv_blocks_in_use"] == 0
    finally:
        paged.close()


# -------------------------------------------------------------- chaos suite


def test_replica_failover_mid_decode_token_exact(params):
    """THE failover exactness gate: a seeded permanent ``replica_step``
    fault kills replica 0 mid-decode; every in-flight request it owned —
    greedy AND seeded-sampled (the carried-rng guarantee) — finishes
    token-identically to the unfaulted oracle on a survivor, with zero
    drops and zero duplicates."""
    plan = FaultPlan.permanent("replica_step", key=0, start=4)
    srv = make_rsrv(params, fault_plan=plan)
    rng = np.random.default_rng(4)
    n = 2 * DP
    prompts = [
        rng.integers(1, CFG.vocab_size, int(l)).astype(np.int32)
        for l in rng.integers(3, 7, n)
    ]
    # request 0 lands on replica 0 (round-robin from _rr=0) and is SAMPLED:
    # its migration must resume the carried rng chain, not restart the seed
    kws = [dict(temperature=1.1, seed=7, top_k=5)] + [{}] * (n - 1)
    reqs = [srv.submit(p, 12, **kw) for p, kw in zip(prompts, kws)]
    owners = {srv._owner[r] for r in reqs}
    assert len(owners) == DP, "router did not spread over all replicas"
    before = REPLICA_FAILOVERS.value
    srv.run_until_idle()
    assert REPLICA_FAILOVERS.value == before + 1
    assert len(srv.servers) == DP - 1
    for r, p, kw in zip(reqs, prompts, kws):
        assert r.error is None, (r.id, r.error)
        want = oracle(params, p, 12, **kw)
        assert r.tokens == want, f"req {r.id} diverged after failover"
    # the per-replica one-hot gauge parked the dead replica's group OFFLINE
    fam = REGISTRY.get("server_replica_state")
    assert fam.labels(replica="0", state="OFFLINE").value == 1.0
    srv.close()


def test_drain_and_spawn_under_load_zero_drops(params):
    """Elasticity round-trip under load: drain() migrates every live
    stream (greedy + sampled, token-exact), spawn_replica() restores the
    replica count on the freed group and serves new traffic."""
    srv = make_rsrv(params)
    rng = np.random.default_rng(5)
    n = 3 * DP
    prompts = [
        rng.integers(1, CFG.vocab_size, int(l)).astype(np.int32)
        for l in rng.integers(3, 7, n)
    ]
    kws = [
        dict(temperature=0.9, seed=i) if i % 3 == 0 else {}
        for i in range(n)
    ]
    reqs = [srv.submit(p, 16, **kw) for p, kw in zip(prompts, kws)]
    for _ in range(4):
        srv.step()  # everyone mid-decode or queued
    victim = srv._by_group[0]
    live_on_victim = sum(
        1 for r in reqs if srv._owner[r] is victim and not r.done
    )
    ok_before = REQUESTS_MIGRATED.labels(outcome="ok").value
    moved = srv.drain(0)
    assert moved == live_on_victim > 0
    assert REQUESTS_MIGRATED.labels(outcome="ok").value == ok_before + moved
    assert len(srv.servers) == DP - 1 and victim._closed
    spawned = srv.spawn_replica()
    assert len(srv.servers) == DP and srv._by_group[0] is spawned
    extra = [srv.submit(prompts[0], 6), srv.submit(prompts[1], 6)]
    srv.run_until_idle()
    for r, p, kw in zip(reqs, prompts, kws):
        assert r.error is None, (r.id, r.error)
        assert r.tokens == oracle(params, p, 16, **kw), f"req {r.id} dropped tokens"
    assert extra[0].tokens == oracle(params, prompts[0], 6)
    assert extra[1].tokens == oracle(params, prompts[1], 6)
    # zero drops, zero duplicates: every request completed exactly once
    # (the drained victim's pre-drain completions plus the survivors')
    assert (
        srv.counters.requests_completed
        + victim.counters.requests_completed
    ) == n + 2
    srv.close()


def test_quarantine_reroutes_queued_requests(params):
    """A replica whose dispatches fail persistently trips the containment
    threshold: its in-flight rows were already failed typed (PR 3
    containment), but its QUEUED requests must migrate and complete on the
    survivors instead of starving behind a dead replica."""
    srv = make_rsrv(params, failure_threshold=1)
    rng = np.random.default_rng(6)
    n = 4 * DP  # 2 slots per replica -> half the work queues
    prompts = [
        rng.integers(1, CFG.vocab_size, 4).astype(np.int32) for _ in range(n)
    ]
    reqs = [srv.submit(p, 6) for p in prompts]
    srv.step()  # admit the first wave everywhere
    victim = srv.servers[0]
    in_flight = [
        r for r in reqs if srv._owner[r] is victim and r.row is not None
    ]
    queued = [r for r in reqs if srv._owner[r] is victim and r.row is None]
    assert in_flight and queued
    # poison exactly this replica's decode dispatch (a per-replica plan:
    # the shared-plan sites would fault every replica at once)
    victim.fault_plan = FaultPlan.permanent("chunk_dispatch")
    srv.run_until_idle()
    assert len(srv.servers) == DP - 1
    for r in in_flight:
        # contained on the poisoned replica: done + typed cause, so a
        # stream()/result() consumer raises RequestFailed, never spins
        assert r.done and r.error is not None
    for r, p in zip(reqs, prompts):
        if r in in_flight:
            continue
        assert r.error is None, (r.id, r.error)
        assert r.tokens == oracle(params, p, 6), f"req {r.id} mismatch"
    srv.close()


def test_prefix_bound_migration_re_resolves_local_handle(params):
    """A migrated prefix-bound request must re-resolve the TARGET replica's
    local handle through the ReplicatedPrefixHandle.per_server map — the
    source handle's device KV died with its replica."""
    srv = make_rsrv(params)
    rng = np.random.default_rng(7)
    pfx = rng.integers(1, CFG.vocab_size, 8).astype(np.int32)
    sfx_a = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
    sfx_b = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    h = srv.prefill_prefix(pfx)
    ra = srv.submit(sfx_a, 10, prefix=h)
    rb = srv.submit(sfx_b, 10, prefix=h)
    srv.step()
    src = srv._owner[ra]
    d = srv._group_of[src]
    moved = srv.drain(d)
    assert moved >= 1
    assert srv._owner[ra] is not src
    # the adopted request now holds the TARGET's local handle
    assert ra.prefix is h.per_server[srv._owner[ra]]
    srv.run_until_idle()
    assert ra.error is None and rb.error is None
    assert ra.tokens == oracle(params, np.concatenate([pfx, sfx_a]), 10)
    assert rb.tokens == oracle(params, np.concatenate([pfx, sfx_b]), 10)
    srv.close()


def test_drain_respects_min_replicas_and_spawn_bounds(params):
    """The elasticity floor: drain refuses to go below min_replicas; spawn
    refuses without a freed group. Both typed ValueErrors."""
    srv = make_rsrv(params, min_replicas=1)
    for d in range(DP - 1, 0, -1):
        srv.drain(d)
    assert len(srv.servers) == 1
    with pytest.raises(ValueError, match="min_replicas"):
        srv.drain(0)
    with pytest.raises(ValueError, match="no live replica"):
        srv.drain(DP - 1)  # already drained
    srv.spawn_replica()
    assert len(srv.servers) == 2
    if DP == 2:
        with pytest.raises(ValueError, match="no freed device group"):
            srv.spawn_replica()
    srv.close()


def test_supervision_kwargs_validated(params):
    with pytest.raises(ValueError, match="failure_threshold"):
        make_rsrv(params, failure_threshold=0)
    with pytest.raises(ValueError, match="failure_window_s"):
        make_rsrv(params, failure_window_s=0.0)
    with pytest.raises(ValueError, match="min_replicas"):
        make_rsrv(params, min_replicas=DP + 1)
