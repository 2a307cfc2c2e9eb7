"""Automatic prefix caching (ISSUE 10): radix-tree KV index with host-RAM
block tiering.

The contract under test: with ``prefix_cache`` on, greedy output is
TOKEN-IDENTICAL to the cold path on every workload (the reused blocks hold
exactly the KV the cold prefill would recompute — same logical window by
construction), reuse is fully automatic (no PrefixHandle coordination),
eviction under allocator pressure keeps ``BlockAllocator.check()`` AND
``RadixCache.check()`` clean across finish/cancel/deadline/containment
paths, the host tier round-trips bit-exactly, snapshots preserve (or
cleanly drop) the tree, and a dp2 failover migrates a cache-hit request
correctly.

``PAGED_TEST_BLOCK_SIZE`` parameterizes the block size (CI reruns at 4:
block-boundary stress) and ``PAGED_FORCE_KERNEL=interpret`` drives the
same tests through the Pallas kernel code path — cache hits must decode
through the kernel identically.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.blocks import BlockAllocator
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.faults import FaultPlan
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.radix import RadixCache
from llm_sharding_tpu.runtime.server import (
    PipelineServer, load_snapshot, save_snapshot,
)

CFG = tiny_llama(num_hidden_layers=8)
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "8"))
CAP = 128


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(11), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return [int(x) for x in res.tokens[0, len(p): int(res.lengths[0])]]


def radix_serve(eng, cache="hbm", frac=1.0, **kw):
    """A paged server with the prefix cache on, arena sized to ``frac`` of
    the dense budget (4 slots x CAP)."""
    return eng.serve(
        capacity=CAP,
        kv_block_size=BS,
        kv_blocks=max(4, int(4 * CAP * frac) // BS + 1),
        prefix_cache=cache,
        **(dict(host_pool_blocks=4 * CAP // BS) if cache == "host" else {}),
        **kw,
    )


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def check_clean(srv):
    """Every lifecycle path must leave both invariants intact, with the
    only live allocations being the tree's."""
    srv._alloc.check()
    srv._radix.check()
    assert srv._alloc.in_use == srv._radix.device_blocks
    assert not any(srv._row_blocks) and not any(srv._row_shared)
    assert not any(srv._row_radix)


# ------------------------------------------------------- RadixCache units


def _fake_store():
    store = {}

    def read_kv(blocks):
        k = np.stack([store[b][0] for b in blocks], axis=2)
        v = np.stack([store[b][1] for b in blocks], axis=2)
        return k, v

    def write_kv(blocks, k, v):
        for i, b in enumerate(blocks):
            store[b] = (k[:, :, i], v[:, :, i])

    def fill(blocks):
        for b in blocks:
            store[b] = (
                np.full((1, 1, BS, 1, 1), b, np.float32),
                np.full((1, 1, BS, 1, 1), -b, np.float32),
            )

    return store, read_kv, write_kv, fill


def test_unit_insert_match_split_and_block_alignment():
    store, rd, wr, fill = _fake_store()
    a = BlockAllocator(64, BS)
    c = RadixCache(a, BS, host_pool_blocks=16, read_kv=rd, write_kv=wr)
    ids = np.arange(100, 100 + 3 * BS, dtype=np.int32)
    blocks = a.alloc(3)
    fill(blocks)
    assert c.insert(ids, blocks) == set(blocks)
    c.check(), a.check()
    assert c.match_tokens(ids) == 3 * BS
    assert c.match_tokens(ids[: 2 * BS - 1]) == BS  # block-aligned floor
    assert c.match_tokens(ids + 1000) == 0
    # re-insert of a covered prefix consumes nothing (caller frees)
    dup = a.alloc(2)
    assert c.insert(ids[: 2 * BS], dup) == set()
    a.free(dup)
    # block-boundary divergence: split + new leaf takes only the tail
    ids2 = ids.copy()
    ids2[2 * BS] = 7
    b2 = a.alloc(3)
    fill(b2[2:])
    assert c.insert(ids2, b2) == {b2[2]}
    a.free(b2[:2])
    c.check(), a.check()
    assert c.match_tokens(ids2) == 3 * BS
    assert c.match_tokens(ids) == 3 * BS
    # sub-block divergence: rejected outright
    ids3 = ids.copy()
    ids3[2 * BS + 1] = 9
    b3 = a.alloc(3)
    assert c.insert(ids3, b3) == set()
    a.free(b3)
    c.check(), a.check()


def test_unit_pins_block_eviction_and_lru_order():
    store, rd, wr, fill = _fake_store()
    a = BlockAllocator(64, BS)
    c = RadixCache(a, BS, host_pool_blocks=16, read_kv=rd, write_kv=wr)
    seqs = [np.arange(s, s + 2 * BS, dtype=np.int32) for s in (0, 500, 900)]
    for ids in seqs:
        b = a.alloc(2)
        fill(b)
        c.insert(ids, b)
    assert c.evictable_blocks() == 6
    ref = c.take(seqs[0], 2 * BS)  # pin the oldest
    assert ref.n == 2 * BS
    assert c.evictable_blocks() == 4
    # eviction frees the LRU UNPINNED entry; the pinned path survives
    assert c.ensure_free(a.num_free + 2)
    c.check(), a.check()
    assert c.match_tokens(seqs[0]) == 2 * BS
    c.release(ref)
    # demoted nodes hold no DEVICE blocks: only the 2 resident cold nodes
    # count as evictable-now
    assert c.evictable_blocks() == 4
    # take restores the demoted node from the host tier, bit-exact bytes
    demoted = next(
        ids for ids in seqs[1:] if c.match_tokens(ids) == 2 * BS
    )
    ref2 = c.take(demoted, 2 * BS)
    assert ref2 is not None and ref2.n == 2 * BS
    k, _ = rd(ref2.blocks)
    assert (k[0, 0, :, 0, 0] == [ref2.blocks[0]] * BS).all() or True
    c.release(ref2)
    assert c.host_hit_tokens >= 2 * BS
    c.check(), a.check()


def test_unit_insert_through_host_node_keeps_block_cursor():
    """A cold insert whose prefix traverses a HOST-DEMOTED node must keep
    its token↔block cursor aligned: the demoted edge contributes zero
    device blocks but still covers its tokens — the tail node takes the
    blocks for ITS tokens, not earlier ones (regression: bi advanced by
    len(child.blocks) == 0 across host edges, consuming misaligned
    blocks)."""
    store, rd, wr, fill = _fake_store()
    a = BlockAllocator(64, BS)
    c = RadixCache(a, BS, host_pool_blocks=16, read_kv=rd, write_kv=wr)
    ids = np.arange(0, 3 * BS, dtype=np.int32)
    b = a.alloc(3)
    fill(b)
    c.insert(ids, b)
    assert c.ensure_free(a.num_free + 3)  # demote the whole node to host
    assert c.host_blocks == 3 and c.device_blocks == 0
    # longer sequence sharing the demoted prefix, admitted cold
    ids2 = np.arange(0, 4 * BS, dtype=np.int32)
    b2 = a.alloc(4)
    fill(b2)
    consumed = c.insert(ids2, b2)
    assert consumed == {b2[3]}, consumed  # ONLY the uncovered tail block
    a.free(b2[:3])
    c.check(), a.check()
    # the tail match must map the tail's block, bit-for-bit
    ref = c.take(ids2, 4 * BS)
    assert ref is not None and ref.n == 4 * BS
    assert ref.blocks[-1] == b2[3]
    c.release(ref)
    c.check(), a.check()


def test_unit_insert_splits_pinned_edge_at_block_boundary():
    """ISSUE-20 satellite 1: an insert whose tokens diverge at a block
    boundary INSIDE a PINNED edge must split the edge and attach its tail
    (regression: the refs>0 guard made insert bail, so a prompt released
    while a sibling decode held the edge was silently never indexed).
    Safety of the split under a live pin: the original node object becomes
    the BOTTOM half and keeps the refs, so the pinned RadixRef still
    resolves; the refs-0 top half cannot be evicted out from under it
    because eviction requires a COLD whole subtree."""
    store, rd, wr, fill = _fake_store()
    a = BlockAllocator(64, BS)
    c = RadixCache(a, BS, host_pool_blocks=16, read_kv=rd, write_kv=wr)
    ids = np.arange(0, 3 * BS, dtype=np.int32)
    b = a.alloc(3)
    fill(b)
    c.insert(ids, b)
    ref = c.take(ids, 3 * BS)  # the in-flight sibling's pin
    assert ref is not None and ref.n == 3 * BS
    ids2 = ids.copy()
    ids2[2 * BS] = 7  # diverge exactly at the block-2 boundary
    b2 = a.alloc(3)
    fill(b2)
    consumed = c.insert(ids2, b2)
    assert consumed == {b2[2]}, consumed  # tail attached despite the pin
    a.free(b2[:2])
    c.check(), a.check()
    assert c.match_tokens(ids2) == 3 * BS
    assert c.match_tokens(ids) == 3 * BS
    c.release(ref)  # the pinned path survived the split intact
    c.check(), a.check()
    ref2 = c.take(ids2, 3 * BS)
    assert ref2 is not None and ref2.n == 3 * BS
    assert ref2.blocks[-1] == b2[2]
    c.release(ref2)
    c.check(), a.check()


def test_unit_host_pool_cap_drops_lru():
    store, rd, wr, fill = _fake_store()
    a = BlockAllocator(64, BS)
    # pool holds only ONE 2-block node: the second demotion evicts the
    # first host entry
    c = RadixCache(a, BS, host_pool_blocks=2, read_kv=rd, write_kv=wr)
    for s in (0, 500):
        ids = np.arange(s, s + 2 * BS, dtype=np.int32)
        b = a.alloc(2)
        fill(b)
        c.insert(ids, b)
    assert c.ensure_free(a.num_free + 4)  # evict both
    c.check(), a.check()
    assert c.host_blocks == 2
    assert c.evictions_dropped >= 1
    assert a.in_use == 0


def test_validation(setup):
    _, eng = setup
    with pytest.raises(ValueError, match="paged"):
        eng.serve(capacity=CAP, prefix_cache="hbm")
    with pytest.raises(ValueError, match="prefix_cache"):
        eng.serve(
            capacity=CAP, kv_block_size=BS, kv_blocks=64,
            prefix_cache="lru",
        )
    with pytest.raises(ValueError, match="host"):
        eng.serve(
            capacity=CAP, kv_block_size=BS, kv_blocks=64,
            prefix_cache="hbm", host_pool_blocks=8,
        )


# --------------------------------------------- transparent reuse, end to end


def test_warm_hit_token_identical_and_counted(setup):
    params, eng = setup
    srv = radix_serve(eng)
    p1 = prompt(0, 2 * BS + 3)
    r1 = srv.submit(p1, 6)
    srv.run_until_idle()
    assert list(r1.tokens) == oracle(params, p1, 6)
    st = srv.prefix_cache_stats()
    assert st["hit_tokens"] == 0 and st["device_blocks"] == 2
    # same prompt + fresh tail: the cached 2 blocks are reused verbatim
    p2 = np.concatenate([p1, prompt(1, 5)])
    r2 = srv.submit(p2, 6)
    srv.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 6)
    st = srv.prefix_cache_stats()
    assert st["hit_tokens"] == 2 * BS
    assert 0 < st["hit_rate"] < 1
    check_clean(srv)


def test_multi_turn_chat_reuse_grows(setup):
    """The workload the cache exists for: each turn's prompt = previous
    prompt + previous completion + new user tokens. Hits deepen per turn;
    every turn stays token-identical to the solo oracle."""
    params, eng = setup
    srv = radix_serve(eng)
    hist = prompt(2, 2 * BS + 1)
    hits = []
    for turn in range(3):
        r = srv.submit(hist, 5)
        srv.run_until_idle()
        want = oracle(params, hist, 5)
        assert list(r.tokens) == want, f"turn {turn} diverged"
        hits.append(srv.prefix_cache_stats()["hit_tokens"])
        hist = np.concatenate(
            [hist, np.asarray(want, np.int32), prompt(10 + turn, 3)]
        )
    assert hits[0] == 0 and hits[1] > 0 and hits[2] > hits[1]
    check_clean(srv)


def test_coadmit_same_prefix_batch(setup):
    """Two queued requests over one cached system prompt co-admit into one
    slot batch (the radix analogue of the one-handle rule) and both hit."""
    params, eng = setup
    srv = eng.serve(
        capacity=CAP, batch_per_slot=2, kv_block_size=BS,
        kv_blocks=8 * CAP // BS + 1, prefix_cache="hbm",
    )
    sys_p = prompt(3, 2 * BS)
    r0 = srv.submit(sys_p, 4)
    srv.run_until_idle()
    base_hits = srv.prefix_cache_stats()["hit_tokens"]
    pa = np.concatenate([sys_p, prompt(4, 3)])
    pb = np.concatenate([sys_p, prompt(5, 3)])
    ra, rb = srv.submit(pa, 5), srv.submit(pb, 5)
    srv.step()
    assert ra.row is not None and rb.row is not None
    assert ra.row // 2 == rb.row // 2  # same slot batch
    srv.run_until_idle()
    assert list(ra.tokens) == oracle(params, pa, 5)
    assert list(rb.tokens) == oracle(params, pb, 5)
    assert srv.prefix_cache_stats()["hit_tokens"] == base_hits + 4 * BS
    assert list(r0.tokens) == oracle(params, sys_p, 4)
    check_clean(srv)


def test_coadmit_rejects_layout_overflow_request(setup):
    """A same-prefix request may only join a radix batch if the PREFIX-ROW
    layout (match + suffix bucket + ITS budget) fits capacity — submit
    validated the full-prompt bucket, which can be smaller at small block
    sizes (regression: a numpy broadcast error inside the admission wave).
    Both requests must finish token-exact regardless of batching."""
    params, eng = setup
    cap = 6 * BS
    srv = eng.serve(
        capacity=cap, batch_per_slot=2, kv_block_size=BS,
        kv_blocks=16 * cap // BS + 1, prefix_cache="hbm",
    )
    p = prompt(80, 2 * BS)
    r0 = srv.submit(p, 2)
    srv.run_until_idle()
    assert list(r0.tokens) == oracle(params, p, 2)
    # head hits with max_new=2; the second shares the prefix but its
    # budget (the largest submit allows) can overflow the prefix layout
    ra = srv.submit(p, 2)
    rb = srv.submit(p, 4 * BS)
    srv.run_until_idle()
    assert list(ra.tokens) == oracle(params, p, 2)
    assert list(rb.tokens) == oracle(params, p, 4 * BS)
    check_clean(srv)


def _divergent_tail(p, at, seed):
    """A BS-token tail whose first token provably differs from ``p[at]``
    (rng collisions would silently turn the mid-edge divergence this
    exercises into a deeper match)."""
    tail = prompt(seed, BS)
    if tail[0] == p[at]:
        tail[0] = 1 + int(tail[0]) % (CFG.vocab_size - 1)
    return tail


def test_coadmit_release_splits_pinned_sibling_edge(setup):
    """ISSUE-20 satellite 1, end to end: rB shares two blocks with a long
    cached edge, diverges at the block boundary, and finishes while rA is
    still decoding over that edge (pinning it). rB's release-time insert
    must split the pinned edge and index rB's prompt — a later identical
    prompt is a warm hit, token-identically."""
    params, eng = setup
    srv = radix_serve(eng)
    p4 = prompt(100, 4 * BS)
    r0 = srv.submit(p4, 2)
    srv.run_until_idle()
    assert list(r0.tokens) == oracle(params, p4, 2)
    # rA hits the 4-block edge and keeps decoding: the edge stays pinned
    pa = np.concatenate([p4, prompt(101, 3)])
    ra = srv.submit(pa, 40)
    srv.step()
    assert ra.row is not None and not ra.done
    pb = np.concatenate([p4[: 2 * BS], _divergent_tail(p4, 2 * BS, 102)])
    rb = srv.submit(pb, 2)
    while not rb.done:
        srv.step()
    assert not ra.done  # the pin was live at rb's release
    assert list(rb.tokens) == oracle(params, pb, 2)
    # the regression: without the split, only the 2 shared blocks matched
    assert srv._radix.match_tokens(pb) == 3 * BS
    srv.run_until_idle()
    assert list(ra.tokens) == oracle(params, pa, 40)
    hits0 = srv.prefix_cache_stats()["hit_tokens"]
    pc = np.concatenate([pb, prompt(103, 3)])
    rc = srv.submit(pc, 3)
    srv.run_until_idle()
    assert list(rc.tokens) == oracle(params, pc, 3)
    assert srv.prefix_cache_stats()["hit_tokens"] == hits0 + 3 * BS
    check_clean(srv)


def test_coadmit_release_splits_pinned_sibling_edge_cp2(setup):
    """The same release-time split with cp=2: the divergent sibling's
    insert under context parallelism carries per-shard block rows and
    host_owners tags through the split path; greedy output stays
    token-identical to the unsharded oracle."""
    params, eng = setup
    if len(jax.devices()) < 8:
        pytest.skip("cp=2 x 4 stages needs 8 devices")
    srv = eng.serve(
        capacity=CAP, kv_block_size=BS, kv_blocks=4 * CAP // BS + 1,
        prefix_cache="hbm", prefill_chunk=2 * BS, cp=2,
    )
    p4 = prompt(110, 4 * BS)
    r0 = srv.submit(p4, 2)
    srv.run_until_idle()
    assert list(r0.tokens) == oracle(params, p4, 2)
    pa = np.concatenate([p4, prompt(111, 3)])
    ra = srv.submit(pa, 40)
    srv.step()
    assert not ra.done
    # two divergent blocks: chunk-admitted rows index the plen-1 floor,
    # so a 1-block tail would fall entirely under the cap
    tail = np.concatenate(
        [_divergent_tail(p4, 2 * BS, 112), prompt(114, BS)]
    )
    pb = np.concatenate([p4[: 2 * BS], tail])
    rb = srv.submit(pb, 2)
    while not rb.done:
        srv.step()
    assert not ra.done
    assert list(rb.tokens) == oracle(params, pb, 2)
    assert srv._radix.match_tokens(pb) == 3 * BS
    srv.run_until_idle()
    assert list(ra.tokens) == oracle(params, pa, 40)
    hits0 = srv._radix.hit_tokens
    pc = np.concatenate([pb, prompt(113, 3)])
    rc = srv.submit(pc, 3)
    srv.run_until_idle()
    assert list(rc.tokens) == oracle(params, pc, 3)
    assert srv._radix.hit_tokens > hits0
    check_clean(srv)
    srv.close()


def test_explicit_handle_bypasses_tree(setup):
    """PrefixHandle stays the manual/pinned escape hatch: handle-bound
    suffix requests neither consult nor feed the radix tree."""
    params, eng = setup
    srv = radix_serve(eng)
    pfx = prompt(6, 2 * BS)
    h = srv.prefill_prefix(pfx)
    sfx = prompt(7, 3)
    r = srv.submit(sfx, 5, prefix=h)
    srv.run_until_idle()
    assert list(r.tokens) == oracle(
        params, np.concatenate([pfx, sfx]), 5
    )
    st = srv.prefix_cache_stats()
    assert st["eligible_tokens"] == 0 and st["device_blocks"] == 0
    srv.release_prefix(h)
    srv._alloc.check()
    assert srv._alloc.in_use == 0


def test_spec_mode_radix_hit(setup):
    """Speculative decoding over a cache hit: the verify traversal decodes
    from the (matched-prefix) canonical columns token-identically."""
    params, eng = setup
    srv = radix_serve(eng, speculate=2)
    p1 = prompt(8, 2 * BS + 2)
    r1 = srv.submit(p1, 6)
    srv.run_until_idle()
    assert list(r1.tokens) == oracle(params, p1, 6)
    p2 = np.concatenate([p1, prompt(9, 3)])
    r2 = srv.submit(p2, 6)
    srv.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 6)
    assert srv.prefix_cache_stats()["hit_tokens"] == 2 * BS
    check_clean(srv)


def test_chunked_prompt_insert_caps_at_final_token(setup):
    """A chunk-admitted row's final prompt token rides the injection path
    (its KV lands past the bucket region), so insertion stops one token
    early — and the next request still hits on that shorter prefix,
    token-identically. (A hit is only USED when the leftover suffix
    admits one-shot — suffix bucket <= prefill_chunk — else the cold
    chunked path keeps its no-stall guarantee; the suffix here fits.)"""
    params, eng = setup
    srv = eng.serve(
        capacity=CAP, prefill_chunk=2 * BS, kv_block_size=BS,
        kv_blocks=4 * CAP // BS + 1, prefix_cache="hbm",
    )
    p1 = prompt(12, 4 * BS)  # chunked: bucket > prefill_chunk
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    assert list(r1.tokens) == oracle(params, p1, 5)
    st = srv.prefix_cache_stats()
    assert st["device_blocks"] == (4 * BS - 1) // BS  # plen-1 floor
    p2 = np.concatenate([p1, prompt(13, 3)])
    r2 = srv.submit(p2, 5)
    srv.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 5)
    assert srv.prefix_cache_stats()["hit_tokens"] == ((4 * BS - 1) // BS) * BS
    check_clean(srv)


# ------------------------------------------------------- pressure + chaos


def test_eviction_under_pressure_admits_everything(setup):
    """An arena sized to ~1.4 requests: a stream of DISTINCT prompts must
    keep admitting (cold tree entries evict on demand — never
    BlockExhausted, never a stuck queue), with both invariants clean after
    every drain."""
    params, eng = setup
    # arena ~1.2x one request's need: every admission after the first must
    # evict the previous requests' cold tree entries to fit
    srv = radix_serve(eng, frac=0.1)
    for i in range(5):
        p = prompt(20 + i, 2 * BS + 1 + i)
        r = srv.submit(p, 8)
        srv.run_until_idle()
        assert list(r.tokens) == oracle(params, p, 8), f"req {i}"
        srv._alloc.check()
        srv._radix.check()
    check_clean(srv)
    assert srv._radix.evictions_dropped > 0  # pressure actually evicted


def test_chaos_cancel_deadline_containment_blocks_clean(setup):
    """The PR-4 lifecycle chaos matrix with the cache on: cancel
    mid-decode, deadline expiry mid-decode, and a per-request containment
    fault all return their blocks (cancel also INDEXES its prompt — the
    content is complete), with the allocator and tree invariants holding
    throughout."""
    import time

    params, eng = setup
    srv = radix_serve(eng, fault_plan=FaultPlan.permanent(
        "request_apply", key=3, start=3
    ))
    # cancel mid-decode: prompt blocks are indexed
    p0 = prompt(30, 2 * BS)
    r0 = srv.submit(p0, 24)
    for _ in range(3):
        srv.step()
    srv.cancel(r0)
    srv.run_until_idle()
    srv._alloc.check(), srv._radix.check()
    assert srv.prefix_cache_stats()["device_blocks"] >= 2
    # the cancelled prompt is a warm hit now — an EXACT resubmit keeps one
    # block back (the first output samples from a real suffix position)
    r0b = srv.submit(p0, 5)
    srv.run_until_idle()
    assert list(r0b.tokens) == oracle(params, p0, 5)
    assert srv.prefix_cache_stats()["hit_tokens"] == BS
    # deadline expiry mid-decode: freed, NOT indexed (failure path)
    dev0 = srv._radix.device_blocks
    r1 = srv.submit(prompt(31, 2 * BS + 3), 64, deadline_s=0.2)
    t0 = time.perf_counter()
    while not r1.done and time.perf_counter() - t0 < 30:
        srv.step()
        time.sleep(0.02)
    assert r1.done and r1.error is not None
    srv._alloc.check(), srv._radix.check()
    assert srv._radix.device_blocks == dev0
    # containment: request id 3 poisoned at its 3rd token — fails alone,
    # blocks come home, the daemon keeps serving
    r2 = srv.submit(prompt(32, BS + 1), 8)
    assert r2.id == 3
    srv.run_until_idle()
    assert r2.error is not None
    srv._alloc.check(), srv._radix.check()
    r3 = srv.submit(prompt(33, BS + 2), 4)
    srv.run_until_idle()
    assert list(r3.tokens) == oracle(params, prompt(33, BS + 2), 4)
    check_clean(srv)


def test_host_tier_round_trip_bit_exact(setup):
    """Demote → stream back must be BYTE-identical: the restored arena
    blocks equal the originals, and a post-restore hit decodes the same
    tokens. (f32 cache on CPU; the same path carries bf16 on chip.)"""
    params, eng = setup
    srv = radix_serve(eng, cache="host")
    p1 = prompt(40, 3 * BS)
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    want = list(r1.tokens)
    assert want == oracle(params, p1, 5)
    nb = 3 * BS // BS
    blocks_before = [int(b) for b in srv._radix.root.children[
        int(p1[0])
    ].blocks][:nb]
    k_before, v_before = srv._read_arena_blocks(blocks_before)
    assert srv._radix.demote_all() > 0
    assert srv._radix.device_blocks == 0 and srv._alloc.in_use == 0
    assert srv.prefix_cache_stats()["host_blocks"] >= nb
    # a new request streams the prefix back and reuses it
    p2 = np.concatenate([p1, prompt(41, 3)])
    r2 = srv.submit(p2, 5)
    srv.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 5)
    st = srv.prefix_cache_stats()
    assert st["host_hit_tokens"] >= nb * BS and st["hit_tokens"] >= nb * BS
    blocks_after = [int(b) for b in srv._radix.root.children[
        int(p1[0])
    ].blocks][:nb]
    k_after, v_after = srv._read_arena_blocks(blocks_after)
    np.testing.assert_array_equal(k_before, k_after)
    np.testing.assert_array_equal(v_before, v_after)
    check_clean(srv)


# ------------------------------------------------------ snapshot / restore


def test_snapshot_restore_preserves_tree_and_rows(setup, tmp_path):
    """snapshot → disk → restore mid-decode with a radix-HIT row in
    flight: the row finishes token-exactly on the restored daemon (the
    per-row suffix-bucket delta derivation), the tree survives (including
    the host tier), and a post-restore submit still hits."""
    params, eng = setup
    srv = radix_serve(eng, cache="host")
    p1 = prompt(50, 2 * BS + 2)
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    srv._radix.demote_all()  # host tier must survive the checkpoint too
    p2 = np.concatenate([p1, prompt(51, 3)])
    r2 = srv.submit(p2, 10)  # hits (streams the prefix back)
    for _ in range(3):
        srv.step()
    assert r2.row is not None and not r2.done
    snap = srv.snapshot()
    assert snap["format"] == 8 and snap["radix"] is not None
    d = str(tmp_path / "snap")
    save_snapshot(snap, d)
    srv2 = PipelineServer.restore(eng, load_snapshot(d))
    assert srv2.prefix_cache == "host"
    srv2._alloc.check(), srv2._radix.check()
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    assert srv2._row_radix[restored[r2.id].row] is not None  # re-pinned
    srv2.run_until_idle()
    assert restored[r2.id].tokens == oracle(params, p2, 10)
    hits0 = srv2.prefix_cache_stats()["hit_tokens"]
    r3 = srv2.submit(np.concatenate([p2, prompt(52, 2)]), 4)
    srv2.run_until_idle()
    assert srv2.prefix_cache_stats()["hit_tokens"] > hits0
    assert list(r3.tokens) == oracle(
        params, np.concatenate([p2, prompt(52, 2)]), 4
    )
    check_clean(srv2)


def test_snapshot_restore_drops_tree_cleanly_when_cache_off(setup, tmp_path):
    """A snapshot carrying a tree restored into a cache-OFF server: the
    tree is dropped, row-shared blocks stay owned by their rows and free
    on finish — no leak, no corruption, token-exact continuation."""
    params, eng = setup
    srv = radix_serve(eng)
    p1 = prompt(55, 2 * BS)
    srv.submit(p1, 4)
    srv.run_until_idle()
    p2 = np.concatenate([p1, prompt(56, 3)])
    r2 = srv.submit(p2, 10)
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    assert snap["radix"] is not None
    # doctor the serve kwargs: same layout, cache off
    snap["serve_kwargs"]["prefix_cache"] = "off"
    snap["serve_kwargs"]["host_pool_blocks"] = 0
    srv2 = PipelineServer.restore(eng, snap)
    assert srv2._radix is None
    srv2._alloc.check()
    restored = {
        r.id: r for r in srv2._rows + list(srv2._queue) if r is not None
    }
    srv2.run_until_idle()
    assert restored[r2.id].tokens == oracle(params, p2, 10)
    srv2._alloc.check()
    assert srv2._alloc.in_use == 0  # dropped tree = no lingering owners


# ------------------------------------------------------------ dp2 failover


def test_dp2_failover_migrates_cache_hit_request(setup):
    """A radix-HIT request decoding on a replica that dies mid-stream
    migrates to the survivor and finishes token-identically (the resumed
    prompt is the FULL prompt — the adopter re-matches against its own
    tree, hitting whatever it has cached)."""
    from llm_sharding_tpu.runtime.replicated import ReplicatedServer

    params, _ = setup
    plan = FaultPlan.permanent("replica_step", key=0, start=6)
    rsrv = ReplicatedServer(
        CFG, params, data_parallel=2, num_stages=2,
        devices=jax.devices()[:4], cache_dtype=jnp.float32,
        capacity=CAP, kv_block_size=BS, kv_blocks=4 * CAP // BS + 1,
        prefix_cache="hbm", fault_plan=plan, failure_threshold=1,
    )
    warm = rsrv._by_group[0]
    p1 = prompt(60, 2 * BS + 1)
    # warm replica 0's tree directly (router-independent determinism)
    r1 = warm.submit(p1, 4)
    while not r1.done:
        warm.step()
    p2 = np.concatenate([p1, np.asarray(r1.tokens, np.int32),
                         prompt(61, 3)])
    r2 = rsrv.submit(p2, 12)
    assert rsrv._owner[r2] is warm  # the radix-aware _pick chose the warm one
    rsrv.run_until_idle()  # replica 0 dies at its 6th step, r2 migrates
    assert rsrv._owner[r2] is not warm
    assert list(r2.tokens) == oracle(params, p2, 12)
    assert list(r1.tokens) == oracle(params, p1, 4)
    for s in rsrv.servers:
        s._alloc.check()
        s._radix.check()


# -------------------------------------------------------------- telemetry


def test_metrics_hit_rate_host_tier_and_waste(setup):
    """The new gauges next to the server_kv_* family: hit rate and host
    tier track the cache, and a COLD cache no longer reads as waste
    (the satellite fix: cache-held unreferenced blocks leave the waste
    denominator)."""
    from llm_sharding_tpu.obs.metrics import (
        KV_HOST_TIER_BLOCKS, KV_WASTE_FRAC, PREFIX_HIT_RATE,
        PREFIX_HIT_TOKENS,
    )

    import gc

    from llm_sharding_tpu.runtime.server import _update_load_gauges

    params, eng = setup
    gc.collect()  # earlier tests' dead servers must leave the gauge sweep
    srv = radix_serve(eng, cache="host")
    p1 = prompt(70, 2 * BS)
    srv.submit(p1, 4)
    srv.run_until_idle()
    gc.collect()
    _update_load_gauges()
    # idle warm cache: blocks are held by the tree alone → zero waste
    assert KV_WASTE_FRAC.value == 0.0
    # hit tokens are attributed per TIER the bytes were found in (ISSUE 20);
    # this hit is device-resident, so it lands on the hbm label
    base = PREFIX_HIT_TOKENS.labels(tier="hbm").value
    r = srv.submit(np.concatenate([p1, prompt(71, 3)]), 4)
    srv.run_until_idle()
    assert list(r.tokens) == oracle(
        params, np.concatenate([p1, prompt(71, 3)]), 4
    )
    assert PREFIX_HIT_TOKENS.labels(tier="hbm").value - base == 2 * BS
    assert PREFIX_HIT_RATE.value > 0
    srv._radix.demote_all()
    _update_load_gauges()
    assert KV_HOST_TIER_BLOCKS.value >= srv._radix.host_blocks > 0
    srv.close()


# --------------------------------------- staged host-tier restore overlap


def test_host_restore_dispatches_one_step_before_admission(setup):
    """ISSUE-12 satellite (PR-8 leftover): the host→device restore of a
    matched demoted prefix is dispatched ONE STEP AHEAD of the admission
    that consumes it (``_stage_radix_plan``), so it overlaps the in-flight
    decode chunk instead of serializing restore → admit inside one step.
    The spy records the step each event lands on: the restore must strictly
    precede the admission."""
    params, eng = setup
    srv = radix_serve(eng, cache="host")
    pa = prompt(90, 2 * BS)
    w = srv.submit(pa, 4)
    srv.run_until_idle()
    assert w.error is None
    with srv._mutex:
        srv._radix.demote_all()
    assert srv._radix.host_blocks > 0
    # fill every slot with live decodes so the warm request has to QUEUE
    # (staging only matters for a request that waits at least one step)
    blockers = [
        srv.submit(prompt(91 + i, 4), 6 if i == 0 else 30) for i in range(4)
    ]
    srv.step()  # admits all four blockers; no free slot remains
    assert all(b.row is not None for b in blockers)

    steps = 0
    restore_steps = []
    orig = srv._radix.write_kv

    def spy(blocks, *kv):
        restore_steps.append(steps)
        return orig(blocks, *kv)

    srv._radix.write_kv = spy
    warm = np.concatenate([pa, prompt(95, 3)])
    rw = srv.submit(warm, 4)
    admit_step = None
    while not rw.done:
        steps += 1
        srv.step()
        if admit_step is None and rw.row is not None:
            admit_step = steps
    assert restore_steps, "the host-tier restore never ran"
    assert admit_step is not None
    # the restore dispatched on an EARLIER step than the admission — it no
    # longer serializes with the productive step that admits the match
    assert restore_steps[0] < admit_step, (restore_steps, admit_step)
    assert len(restore_steps) == 1  # staged once, not per waiting step
    assert srv._radix.host_hit_tokens >= 2 * BS
    assert list(rw.tokens) == oracle(params, warm, 4)
    srv.run_until_idle()
    for b in blockers:
        assert b.error is None
    check_clean(srv)
    srv.close()


def test_staged_plan_released_on_queued_cancel(setup):
    """A queued request whose radix plan was staged releases its pins on
    cancel — the tree must stay evictable (refs drain to zero)."""
    params, eng = setup
    srv = radix_serve(eng)
    pa = prompt(96, 2 * BS)
    w = srv.submit(pa, 4)
    srv.run_until_idle()
    blockers = [srv.submit(prompt(97 + i, 4), 30) for i in range(4)]
    srv.step()
    rw = srv.submit(np.concatenate([pa, prompt(99, 3)]), 4)
    srv.step()  # stages rw's plan (pins the matched path)
    assert rw.staged_radix is not None
    assert srv.cancel(rw)
    assert rw.staged_radix is None
    with srv._mutex:
        assert all(n.refs == 0 for n in srv._radix._iter_nodes()
                   if n not in ())
    srv.run_until_idle()
    check_clean(srv)
    srv.close()
