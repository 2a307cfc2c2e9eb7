"""Serve-state checkpoint/resume: a LIVE serving daemon snapshotted
mid-decode and restored into a fresh server continues every in-flight and
queued request token-exactly. Extends the weights-only checkpoint story
(``utils/shard_store``) to the serving runtime — the reference's daemon
holds per-request DynamicCaches in process memory and cannot recover them
(``/root/reference/utils/node_worker.py:184``)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.server import (
    PipelineServer, load_snapshot, save_snapshot,
)

# an end-of-text id the 256-token vocabulary cannot emit: random weights then
# never end a request before a test extracts or snapshots it
CFG = tiny_llama(num_hidden_layers=8, eos_token_id=256)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(17), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return list(res.tokens[0, len(p): int(res.lengths[0])])


def test_snapshot_restore_mid_decode_token_exact(setup):
    """Two in-flight requests (one greedy, one seeded sampled) + one queued:
    snapshot mid-decode, restore into a FRESH server, run to completion —
    every token sequence equals the uninterrupted oracle."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(51)
    pa = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
    pc = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    ra = srv.submit(pa, max_new_tokens=14)
    rb = srv.submit(pb, max_new_tokens=12, temperature=0.9, seed=8)
    for _ in range(4):
        srv.step()  # a and b are mid-decode
    rc = srv.submit(pc, max_new_tokens=6)  # still queued (no free slot pump)
    snap = srv.snapshot()
    assert any(d is not None for d in snap["rows"])
    assert len(snap["queue"]) >= 0

    # the ORIGINAL server is abandoned (simulated failure); a fresh daemon
    # resumes from the snapshot over the same engine
    srv2 = PipelineServer.restore(eng, snap)
    # request objects in the new server are reconstructions; grab them by id
    # BEFORE draining (completed rows are nulled out of the slot table)
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    srv2.run_until_idle()
    assert restored[ra.id].tokens == oracle(params, pa, 14)
    assert restored[rb.id].tokens == oracle(
        params, pb, 12, temperature=0.9, seed=8
    )
    assert restored[rc.id].tokens == oracle(params, pc, 6)
    assert all(restored[i].done for i in (ra.id, rb.id, rc.id))


def test_snapshot_disk_round_trip(setup):
    """snapshot → save_snapshot → load_snapshot → restore, token-exact (no
    pickling: arrays in npz, bookkeeping in json)."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(53)
    p = rng.integers(1, CFG.vocab_size, 6).astype(np.int32)
    r = srv.submit(p, max_new_tokens=12)
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    import tempfile

    d = tempfile.mkdtemp()
    save_snapshot(snap, d)
    srv2 = PipelineServer.restore(eng, load_snapshot(d))
    got = next(
        x for x in srv2._rows + list(srv2._queue)
        if x is not None and x.id == r.id
    )
    srv2.run_until_idle()
    assert got.done and got.tokens == oracle(params, p, 12)


def test_restore_rejects_mismatched_placement(setup):
    params, eng = setup
    srv = eng.serve(capacity=64)
    snap = srv.snapshot()
    eng2 = PipelineEngine(params=dict(
        llama.init_params(CFG, jax.random.key(17), dtype=jnp.float32)
    ), cfg=CFG, num_stages=2, cache_dtype=jnp.float32)
    with pytest.raises(ValueError, match="shape"):
        PipelineServer.restore(eng2, snap)


def test_replicated_snapshot_restore(setup):
    """dp2 daemon: per-replica snapshots restored into a fresh router,
    in-flight requests on BOTH replicas continue token-exactly."""
    from llm_sharding_tpu.runtime.replicated import ReplicatedServer

    params, _ = setup
    kw = dict(data_parallel=2, num_stages=2, cache_dtype=jnp.float32,
              capacity=64)
    rsrv = ReplicatedServer(CFG, params, devices=jax.devices()[:4], **kw)
    rng = np.random.default_rng(57)
    prompts = [rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
               for _ in range(4)]
    reqs = [rsrv.submit(p, 10) for p in prompts]
    for _ in range(3):
        rsrv.step()
    snaps = rsrv.snapshot()
    assert len(snaps) == 2

    fresh = ReplicatedServer(CFG, params, devices=jax.devices()[:4], **kw)
    rsrv2 = ReplicatedServer.restore_into(fresh, snaps)
    # request ids are PER-REPLICA counters — match revived requests by
    # prompt content (distinct random prompts), not by id
    restored = [
        r
        for s in rsrv2.servers
        for r in list(s._rows) + list(s._queue)
        if r is not None
    ]
    assert len(restored) == 4
    rsrv2.run_until_idle()
    for p in prompts:
        got = next(r for r in restored if np.array_equal(r.prompt, p))
        assert got.tokens == oracle(params, p, 10)


def test_stream_and_cancel_after_restore(setup):
    """A restored server is fully live: its requests stream (pumping the
    server) and cancel like freshly submitted ones."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(59)
    pa = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    ra = srv.submit(pa, max_new_tokens=10)
    rb = srv.submit(pb, max_new_tokens=30)
    for _ in range(3):
        srv.step()
    srv2 = PipelineServer.restore(eng, srv.snapshot())
    got_a = next(r for r in srv2._rows if r is not None and r.id == ra.id)
    got_b = next(r for r in srv2._rows if r is not None and r.id == rb.id)
    # stream() replays from the first token — pre-restore tokens included
    assert list(srv2.stream(got_a)) == oracle(params, pa, 10)
    assert srv2.cancel(got_b)  # mid-decode cancel on the restored server
    srv2.run_until_idle()
    assert got_b.done and len(got_b.tokens) < 30
    assert rb is not got_b  # the original object belongs to the dead server


def test_snapshot_refuses_queued_prefix(setup):
    params, eng = setup
    srv = eng.serve(capacity=128)
    rng = np.random.default_rng(55)
    h = srv.prefill_prefix(rng.integers(1, CFG.vocab_size, 8).astype(np.int32))
    # occupy all slots so the prefix request stays queued
    blockers = [
        srv.submit(rng.integers(1, CFG.vocab_size, 4).astype(np.int32), 20)
        for _ in range(4)
    ]
    srv.step()
    srv.submit(rng.integers(1, CFG.vocab_size, 3).astype(np.int32), 4, prefix=h)
    assert blockers  # silence lint
    with pytest.raises(ValueError, match="prefix"):
        srv.snapshot()


def test_snapshot_is_read_only_on_request_ids(setup):
    """snapshot() must not consume a request id (ADVICE r5: the old
    itertools.count-based tracking burned one per snapshot on the live
    daemon) — a request submitted after N snapshots still gets the next
    consecutive id."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    r0 = srv.submit(np.array([1, 2, 3], np.int32), 2)
    srv.run_until_idle()
    for _ in range(3):
        snap = srv.snapshot()
    assert snap["next_id"] == r0.id + 1
    r1 = srv.submit(np.array([4, 5], np.int32), 2)
    assert r1.id == r0.id + 1
    srv.run_until_idle()


def test_paged_snapshot_restore_mid_decode_token_exact(setup, tmp_path):
    """Paged-mode daemon snapshotted mid-decode, saved to disk, restored:
    in-flight requests finish token-exactly AND the block allocator is
    rebuilt from the snapshot's per-row ownership lists (invariant holds,
    every block comes home on drain)."""
    params, eng = setup
    srv = eng.serve(capacity=64, kv_block_size=16, kv_blocks=24)
    rng = np.random.default_rng(71)
    pa = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
    ra = srv.submit(pa, max_new_tokens=14)
    rb = srv.submit(pb, max_new_tokens=10)
    for _ in range(4):
        srv.step()
    snap = srv.snapshot()
    assert snap["format"] == 8 and snap["paged"] is not None
    import tempfile

    d = tempfile.mkdtemp(dir=tmp_path)
    save_snapshot(snap, d)
    srv2 = PipelineServer.restore(eng, load_snapshot(d))
    assert srv2.paged and srv2.kv_block_size == 16
    srv2._alloc.check()
    assert srv2._alloc.in_use == srv._alloc.in_use > 0
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    srv2.run_until_idle()
    assert restored[ra.id].tokens == oracle(params, pa, 14)
    assert restored[rb.id].tokens == oracle(params, pb, 10)
    srv2._alloc.check()
    assert srv2._alloc.in_use == 0


def _paged_snapshot_mid_decode(eng, **kw):
    srv = eng.serve(capacity=64, **kw)
    rng = np.random.default_rng(77)
    p = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    srv.submit(p, max_new_tokens=10)
    for _ in range(3):
        srv.step()
    return srv, srv.snapshot()


@pytest.mark.parametrize("old_format", [2, 4, 7])
@pytest.mark.parametrize(
    "kv_block_size", [16, CFG.num_key_value_heads],
    ids=["block16", "block_equals_kv_heads"],
)
def test_paged_snapshot_of_an_older_format_is_refused_by_name(
    setup, tmp_path, kv_block_size, old_format
):
    """A paged snapshot of format <= 7 holds the arena as ``[.., BS, Nkv,
    Dh]``; this build stores it head-major. ``restore`` refuses it with the
    curated message — from the dict and after a save/load round trip —
    BEFORE building anything, and in particular where ``kv_block_size ==
    num_key_value_heads``: there the two layouts have the SAME shape, the
    leaf loop's shape check would pass and the bytes would be read
    wrongly."""
    _, eng = setup
    srv, snap = _paged_snapshot_mid_decode(
        eng, kv_block_size=kv_block_size, kv_blocks=64 * 4 // kv_block_size,
    )
    assert snap["format"] == 8 and snap["paged"] is not None
    if kv_block_size == CFG.num_key_value_heads:
        k = snap["state"]["k"].shape  # [S, Lp, NB, Nkv, BS, Dh]
        assert k[3] == k[4]  # shapes alone cannot tell the layouts apart
    PipelineServer.restore(eng, snap).close()  # this build's own: fine
    snap["format"] = old_format
    with pytest.raises(ValueError, match=r"retired \[.., block_size, Nkv, Dh\] layout"):
        PipelineServer.restore(eng, snap)
    d = str(tmp_path / "snap")
    save_snapshot(snap, d)
    with pytest.raises(ValueError, match=f"paged snapshot of format {old_format}"):
        PipelineServer.restore(eng, load_snapshot(d))
    srv.close()


def test_replicated_restore_refuses_an_older_paged_snapshot(setup):
    """The router's ``restore_into`` adopts per-replica snapshots through
    the same gate: one older paged snapshot among them refuses the lot,
    and the fresh router is left serving."""
    from llm_sharding_tpu.runtime.replicated import ReplicatedServer

    params, _ = setup
    kw = dict(data_parallel=2, num_stages=2, cache_dtype=jnp.float32,
              capacity=64, kv_block_size=16, kv_blocks=17)
    rsrv = ReplicatedServer(CFG, params, devices=jax.devices()[:4], **kw)
    rng = np.random.default_rng(78)
    p = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    rsrv.submit(p, 8)
    for _ in range(2):
        rsrv.step()
    snaps = rsrv.snapshot()
    snaps[1]["format"] = 7
    fresh = ReplicatedServer(CFG, params, devices=jax.devices()[:4], **kw)
    with pytest.raises(ValueError, match="paged snapshot of format 7"):
        ReplicatedServer.restore_into(fresh, snaps)
    r = fresh.submit(p, 8)
    fresh.run_until_idle()
    assert r.tokens == oracle(params, p, 8)


@pytest.mark.parametrize("old_format", [2, 5, 7])
def test_dense_snapshot_of_an_older_format_still_restores(setup, old_format):
    """The gate is about the PAGED arena's bytes: a dense snapshot of any
    earlier format restores as before and finishes token-exactly."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(79)
    p = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    r = srv.submit(p, max_new_tokens=10)
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    assert snap["paged"] is None
    snap["format"] = old_format
    srv2 = PipelineServer.restore(eng, snap)
    got = next(
        x for x in srv2._rows + list(srv2._queue)
        if x is not None and x.id == r.id
    )
    srv2.run_until_idle()
    assert got.done and got.tokens == oracle(params, p, 10)


def test_dense_snapshot_refuses_paged_server(setup):
    """Mode mismatch is a curated refusal, not a shape error: a dense
    snapshot carries no block ownership, so a paged restore target must
    reject it up front."""
    _, eng = setup
    srv = eng.serve(capacity=64)
    snap = srv.snapshot()
    assert snap["paged"] is None
    snap["serve_kwargs"]["kv_block_size"] = 16
    snap["serve_kwargs"]["kv_blocks"] = 24
    with pytest.raises(ValueError, match="dense-mode snapshot"):
        PipelineServer.restore(eng, snap)


def test_paged_snapshot_refuses_dense_server(setup):
    _, eng = setup
    srv = eng.serve(capacity=64, kv_block_size=16, kv_blocks=24)
    snap = srv.snapshot()
    snap["serve_kwargs"]["kv_block_size"] = None
    snap["serve_kwargs"]["kv_blocks"] = None
    with pytest.raises(ValueError, match="paged-mode snapshot"):
        PipelineServer.restore(eng, snap)


def test_legacy_format1_snapshot_still_restores(setup):
    """A pre-paged (format 1) snapshot — no block_tables leaf, no paged
    section, no kv serve kwargs — restores into a dense server and its
    requests complete token-exactly."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(73)
    p = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    r = srv.submit(p, max_new_tokens=10)
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    # rewrite as a format-1 era snapshot
    snap["format"] = 1
    snap["paged"] = None
    snap["state"] = {
        k: v for k, v in snap["state"].items() if k != "block_tables"
    }
    for k in ("kv_block_size", "kv_blocks"):
        snap["serve_kwargs"].pop(k, None)
    srv2 = PipelineServer.restore(eng, snap)
    got = next(
        x for x in srv2._rows + list(srv2._queue)
        if x is not None and x.id == r.id
    )
    srv2.run_until_idle()
    assert got.done and got.tokens == oracle(params, p, 10)


def test_restore_runs_engine_serve_validation(setup):
    """restore() applies the same engine guards serve() does (ADVICE r5):
    an in-program-dp engine gets the curated NotImplementedError pointing
    at ReplicatedServer, not an obscure mesh/sharding failure later."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    snap = srv.snapshot()
    eng_dp = PipelineEngine(
        CFG, llama.init_params(CFG, jax.random.key(17), dtype=jnp.float32),
        data_parallel=2, num_stages=2, cache_dtype=jnp.float32,
    )
    with pytest.raises(NotImplementedError, match="ReplicatedServer"):
        PipelineServer.restore(eng_dp, snap)


# ------------------------------------------------ portable request state
# (PipelineServer.extract / adopt — the migration primitive the dp
# supervision layer in runtime/replicated.py builds failover and drain on;
# exercised here server-to-server without a router)


@pytest.fixture(scope="module")
def two_servers(setup):
    """Two INDEPENDENT single-engine servers over disjoint device groups —
    the minimal migration topology."""
    params, _ = setup
    ea = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    )
    eb = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[2:4],
        cache_dtype=jnp.float32,
    )
    return params, ea.serve(capacity=64), eb.serve(capacity=64)


def test_extract_adopt_mid_decode_token_exact(two_servers):
    """Greedy AND seeded-sampled requests extracted mid-decode from server
    A and adopted on server B finish token-identically to the
    uninterrupted oracle, through the SAME Request objects (the consumer's
    token list keeps growing in place)."""
    params, sa, sb = two_servers
    rng = np.random.default_rng(71)
    pa = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    pb = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    ra = sa.submit(pa, 14)
    rb = sa.submit(pb, 14, temperature=0.9, seed=21)
    for _ in range(5):
        sa.step()
    assert ra.tokens and rb.tokens, "requests must be mid-decode"
    toks_a, toks_b = ra.tokens, rb.tokens  # the live consumer views
    for r in (ra, rb):
        st = sa.extract(r)
        assert st.remaining == 14 - len(r.tokens)
        sb.adopt(st, r)
    # rng carry: greedy rows carry none, sampled rows carry the chain at
    # exactly len(tokens) splits
    assert ra.carried_rng is None and rb.carried_rng is not None
    assert sb.result(ra) == oracle(params, pa, 14)
    assert sb.result(rb) == oracle(params, pb, 14, temperature=0.9, seed=21)
    assert ra.tokens is toks_a and rb.tokens is toks_b  # object identity
    # server A is empty and untouched otherwise
    assert not sa._queue and not sa._any_active()


def test_extract_adopt_queued_and_embeds(two_servers):
    """A never-admitted queued request migrates (no rng to carry), and the
    embeddings privacy entry migrates by embedding its generated tail on
    the target — both token-exact."""
    params, sa, sb = two_servers
    rng = np.random.default_rng(72)
    p1 = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    p2 = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    rq = sa.submit(p1, 8, temperature=0.7, seed=3)  # stays queued: no step
    re = sa.submit_embedding(sa.engine.embed_prompt(p2)[0], 10)
    for _ in range(4):
        sa.step()  # admits + decodes re; rq admits too
    st_e = sa.extract(re)
    assert st_e.embeds is not None and st_e.tail.size == len(re.tokens)
    sb.adopt(st_e, re)
    assert sb.result(re) == oracle(params, p2, 10)
    # rq may have admitted by now; extract regardless and finish on B
    st_q = sa.extract(rq)
    sb.adopt(st_q, rq)
    assert sb.result(rq) == oracle(params, p1, 8, temperature=0.7, seed=3)


def test_extract_rejects_foreign_and_finished(two_servers):
    params, sa, sb = two_servers
    rng = np.random.default_rng(73)
    p = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    r = sa.submit(p, 4)
    with pytest.raises(ValueError, match="not held"):
        sb.extract(r)
    sa.run_until_idle()
    assert r.done
    with pytest.raises(ValueError, match="finished"):
        sa.extract(r)


def test_adopt_refuses_oversized_resume(two_servers):
    """A resumed prompt (original + generated) that cannot fit the target's
    capacity is refused with a typed ValueError BEFORE any mutation — the
    router treats it as 'try another survivor'."""
    params, sa, sb = two_servers
    rng = np.random.default_rng(74)
    p = rng.integers(1, CFG.vocab_size, 5).astype(np.int32)
    r = sa.submit(p, 12)
    for _ in range(3):
        sa.step()
    st = sa.extract(r)
    tiny = PipelineEngine(
        CFG, params, num_stages=2, devices=jax.devices()[:2],
        cache_dtype=jnp.float32,
    ).serve(capacity=16)
    with pytest.raises(ValueError, match="capacity"):
        tiny.adopt(st, r)
    assert not r.done and r.error is None  # still adoptable elsewhere
    sb.adopt(st, r)
    assert sb.result(r) == oracle(params, p, 12)


def test_mid_flight_snapshot_restore_token_exact(setup):
    """snapshot() with a dispatched chunk's log still in flight settles to a
    step boundary first; the restored server finishes every request
    token-identically to the uninterrupted oracle."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    rng = np.random.default_rng(31)
    ps = [rng.integers(1, CFG.vocab_size, 5).astype(np.int32) for _ in range(3)]
    reqs = [srv.submit(p, 12) for p in ps]
    for _ in range(4):
        srv.step()
    assert srv._pending  # a chunk dispatched, its tokens not yet applied
    seen = [len(r.tokens) for r in reqs]
    snap = srv.snapshot()
    assert snap["format"] == 8 and not srv._pending
    # the settle landed them
    assert all(len(r.tokens) > n for r, n in zip(reqs, seen))
    srv.close()
    srv2 = PipelineServer.restore(eng, snap)
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    srv2.run_until_idle()
    for r, p in zip(reqs, ps):
        assert restored[r.id].tokens == oracle(params, p, 12)
    srv2.close()


@pytest.mark.parametrize("settle", [True, False])
def test_extract_settles_in_flight_dispatches(two_servers, settle):
    """extract(settle=True) — an elective migration's — lands the chunk in
    flight first, so the migrated state carries its tokens; without it
    (failover's) they replay on the adopter. Token-identical either way."""
    params, src, dst = two_servers
    p = np.random.default_rng(37).integers(1, CFG.vocab_size, 5).astype(np.int32)
    r = src.submit(p, 14)
    for _ in range(3):
        src.step()
    assert src._pending
    seen = len(r.tokens)
    st = src.extract(r, settle=settle)
    assert (len(r.tokens) > seen) == settle and not (settle and src._pending)
    dst.adopt(st, r)
    assert dst.result(r) == oracle(params, p, 14)
    src.run_until_idle()  # (the log left in flight applies to no one)


def test_migrated_request_snapshot_roundtrip(two_servers, tmp_path):
    """A request snapshotted AFTER a migration restores token-exactly: the
    snapshot carries the migration bookkeeping (``baked`` — tokens folded
    into the resumed prompt) so the restored mirrors line up."""
    params, sa, sb = two_servers
    rng = np.random.default_rng(75)
    p = rng.integers(1, CFG.vocab_size, 4).astype(np.int32)
    r = sa.submit(p, 12, temperature=1.1, seed=9)
    for _ in range(4):
        sa.step()
    pre = len(r.tokens)
    assert pre > 0
    sb.adopt(sa.extract(r), r)
    for _ in range(3):
        sb.step()  # re-admitted and decoding on B (baked > 0 now)
    assert r.baked == pre
    path = str(tmp_path / "migrated_snap")
    save_snapshot(sb.snapshot(), path)
    srv2 = PipelineServer.restore(sb.engine, load_snapshot(path))
    got = next(
        x for x in list(srv2._rows) + list(srv2._queue)
        if x is not None and np.array_equal(
            x.prompt[: len(p)], p
        )
    )
    assert got.baked == pre
    srv2.run_until_idle()
    assert got.tokens == oracle(params, p, 12, temperature=1.1, seed=9)
    srv2.close()


def test_extract_adopt_chunked_admission_rng_carry(two_servers):
    """A migrated SAMPLED request whose resumed prompt crosses the target's
    ``prefill_chunk`` re-admits through the CHUNKED path: the carried chain
    is stored unsplit by ``serve_admit_finish`` (the first decode commit
    performs the next split) — still token-identical to the uninterrupted
    sampled oracle."""
    params, sa, sb = two_servers
    src = sa.engine.serve(capacity=64, prefill_chunk=8)
    dst = sb.engine.serve(capacity=64, prefill_chunk=8)
    rng = np.random.default_rng(76)
    p = rng.integers(1, CFG.vocab_size, 12).astype(np.int32)  # bucket 16 > 8
    r = src.submit(p, 12, temperature=0.9, seed=4)
    for _ in range(5):
        src.step()
    assert r.tokens, "must be mid-decode"
    st = src.extract(r)
    assert st.rng is not None
    dst.adopt(st, r)
    assert dst.result(r) == oracle(params, p, 12, temperature=0.9, seed=4)
    src.close()
    dst.close()


def test_snapshot_carries_paged_attn_pin(setup):
    """An operator's explicit attention-backend pin survives restore like
    every other serve kwarg (snapshot-wins): a paged_attn='xla' daemon
    restores as 'xla', not back to 'auto' — which on a TPU host would
    silently re-enable the kernel the operator pinned away from. Pre-PR-6
    snapshots lack the key and restore as 'auto' via the default."""
    _, eng = setup
    srv = eng.serve(capacity=64, kv_block_size=16, kv_blocks=24,
                    paged_attn="xla")
    snap = srv.snapshot()
    assert snap["serve_kwargs"]["paged_attn"] == "xla"
    srv2 = PipelineServer.restore(eng, snap)
    assert srv2.paged_attn == "xla" and srv2.attn_impl == "xla"
    # legacy snapshot without the key: constructor default applies
    del snap["serve_kwargs"]["paged_attn"]
    srv3 = PipelineServer.restore(eng, snap)
    assert srv3.paged_attn == "auto"
