"""Memory-mapped disk KV tier (ISSUE 20 tentpole a).

The contract under test: with ``prefix_cache="disk"`` the demotion ladder
extends one rung below the pinned host pool — cold host-parked nodes
spill to per-entry ``.npy`` files under a bounded on-disk pool, promote
disk → host → arena on a later hit BYTE-exactly (including quantized
codes+scales and cp ``host_owners`` shard tags), and the pool is the
PERSISTENT artifact: a restarted server ``adopt_pool``s its entries cold
and a snapshot (format 7+) references them instead of inlining the KV.
Failure is contained — a crash mid-spill leaves only ignorable orphan
files, and a corrupt/missing entry drops the node so the request
re-prefills token-identically, never erroring upward.

``PAGED_TEST_BLOCK_SIZE`` parameterizes the block size (CI reruns at 4
under ``PAGED_FORCE_KERNEL=interpret``) and ``SHARDLINT_LOCK_ORDER=1``
drives the chaos lane with lock-order assertions armed.
"""

import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.blocks import PAGED_KV_LAYOUT, BlockAllocator
from llm_sharding_tpu.runtime.engine import PipelineEngine
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


def disk_serve(eng, pool, **kw):
    return eng.serve(
        capacity=CAP,
        kv_block_size=BS,
        kv_blocks=4 * CAP // BS + 1,
        prefix_cache="disk",
        host_pool_blocks=4 * CAP // BS,
        disk_pool_dir=str(pool),
        disk_pool_blocks=kw.pop("disk_pool_blocks", 4 * CAP // BS),
        **kw,
    )


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def check_clean(srv):
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


def _cache(tmp_path, a=None, host=16, disk=16, **kw):
    store, rd, wr, fill = _fake_store()
    a = a or BlockAllocator(64, BS)
    c = RadixCache(
        a, BS, host_pool_blocks=host, read_kv=rd, write_kv=wr,
        disk_pool_dir=str(tmp_path), disk_pool_blocks=disk, **kw,
    )
    return store, a, c, fill


def test_unit_ladder_demote_promote_byte_exact(tmp_path):
    """hbm → host → disk, then one take() promotes disk → host staging →
    arena: the arena bytes equal the pre-demotion bytes and the counters
    ride every rung."""
    store, a, c, fill = _cache(tmp_path)
    ids = np.arange(0, 3 * BS, dtype=np.int32)
    b = a.alloc(3)
    fill(b)
    before = {i: store[blk] for i, blk in enumerate(b)}
    c.insert(ids, b)
    # one node, two rungs: device→host then host→disk
    assert c.demote_all(to_disk=True) == 2
    c.check(), a.check()
    assert (c.device_blocks, c.host_blocks, c.disk_blocks) == (0, 0, 3)
    assert c.evictions_to_disk == 1 and a.in_use == 0
    # one entry on disk: kv components + the meta validity marker
    names = sorted(os.listdir(tmp_path))
    assert names == ["e0.json", "e0.kv0.npy", "e0.kv1.npy"]
    meta = json.load(open(tmp_path / "e0.json"))
    assert meta["prefix"] == [int(t) for t in ids] and meta["edge"] == 3 * BS
    ref = c.take(ids, 3 * BS)
    assert ref is not None and ref.n == 3 * BS
    assert ref.tier_tokens == {"hbm": 0, "host": 0, "disk": 3 * BS}
    for i, blk in enumerate(ref.blocks):
        np.testing.assert_array_equal(store[blk][0], before[i][0])
        np.testing.assert_array_equal(store[blk][1], before[i][1])
    assert c.disk_hit_tokens == 3 * BS and c.disk_blocks == 0
    # promoted: the entry files are gone (a later demotion re-spills)
    assert not [f for f in os.listdir(tmp_path) if f.startswith("e0.")]
    c.release(ref)
    c.check(), a.check()


def test_unit_disk_entry_preserves_extension_dtype(tmp_path):
    """A bfloat16 arena round-trips the disk tier byte-exactly WITH its
    dtype: np.save would reload extension dtypes as raw void ('|V2') and
    poison the arena write, so entries store a uint8 byte view plus the
    dtype name in the meta and the read side views the bytes back."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    store = {}

    def read_kv(blocks):
        k = np.stack([store[b][0] for b in blocks], axis=2)
        v = np.stack([store[b][1] for b in blocks], axis=2)
        return k, v

    def write_kv(blocks, k, v):
        assert k.dtype == bf16 and v.dtype == bf16  # dtype survived disk
        for i, b in enumerate(blocks):
            store[b] = (k[:, :, i], v[:, :, i])

    a = BlockAllocator(64, BS)
    c = RadixCache(
        a, BS, host_pool_blocks=16, read_kv=read_kv, write_kv=write_kv,
        disk_pool_dir=str(tmp_path), disk_pool_blocks=16,
    )
    ids = np.arange(0, 2 * BS, dtype=np.int32)
    b = a.alloc(2)
    rng = np.random.default_rng(97)
    for blk in b:
        store[blk] = (
            rng.standard_normal((1, 1, BS, 1, 1)).astype(bf16),
            rng.standard_normal((1, 1, BS, 1, 1)).astype(bf16),
        )
    before = {i: store[blk] for i, blk in enumerate(b)}
    c.insert(ids, b)
    assert c.demote_all(to_disk=True) == 2
    meta = json.load(open(tmp_path / "e0.json"))
    assert meta["dtypes"] == ["bfloat16", "bfloat16"]
    ref = c.take(ids, 2 * BS)
    assert ref is not None and ref.n == 2 * BS
    for i, blk in enumerate(ref.blocks):
        assert store[blk][0].dtype == bf16
        assert store[blk][0].tobytes() == before[i][0].tobytes()
        assert store[blk][1].tobytes() == before[i][1].tobytes()
    c.release(ref)
    c.check(), a.check()


def test_unit_disk_pool_cap_drops_lru(tmp_path):
    """A full disk pool makes room by dropping its coldest childless
    leaves; a node bigger than the whole pool is dropped, not spilled."""
    store, a, c, fill = _cache(tmp_path, disk=2)
    for s in (0, 500):
        ids = np.arange(s, s + 2 * BS, dtype=np.int32)
        b = a.alloc(2)
        fill(b)
        c.insert(ids, b)
    assert c.demote_all(to_disk=True) >= 2
    c.check(), a.check()
    assert c.disk_blocks == 2  # exactly ONE of the two entries fits
    assert c.evictions_dropped >= 1
    m0 = c.match_tokens(np.arange(0, 2 * BS, dtype=np.int32))
    m5 = c.match_tokens(np.arange(500, 500 + 2 * BS, dtype=np.int32))
    assert sorted([m0, m5]) == [0, 2 * BS]
    # a 3-block node can never fit the 2-block pool: it PARKS on the host
    # rung instead of spilling (and only host-pool pressure drops it)
    ids = np.arange(900, 900 + 3 * BS, dtype=np.int32)
    b = a.alloc(3)
    fill(b)
    c.insert(ids, b)
    c.demote_all(to_disk=True)
    assert c.match_tokens(ids) == 3 * BS
    assert c.host_blocks == 3 and c.disk_blocks == 2
    c.check(), a.check()


def test_unit_crash_mid_spill_is_invisible(tmp_path):
    """The meta JSON is the validity marker: kv files without one (a
    crash between component writes and the meta rename) are swept at
    adoption and never surface as an entry."""
    store, a, c, fill = _cache(tmp_path)
    ids = np.arange(0, 2 * BS, dtype=np.int32)
    b = a.alloc(2)
    fill(b)
    c.insert(ids, b)
    c.demote_all(to_disk=True)
    # simulate the crash: the NEXT entry's kv landed, its meta did not
    open(tmp_path / "e1.kv0.npy", "wb").write(b"\x93NUMPY partial")
    open(tmp_path / "e1.kv1.npy.tmp", "wb").write(b"torn tmp")
    store2, a2, c2, _ = _cache(tmp_path)
    assert c2.adopt_pool() == 1
    c2.check(), a2.check()
    assert c2.disk_blocks == 2
    assert not [f for f in os.listdir(tmp_path) if f.startswith("e1.")]
    # the adopted entry still promotes byte-exact through the new cache
    ref = c2.take(ids, 2 * BS)
    assert ref is not None and ref.n == 2 * BS
    np.testing.assert_array_equal(store2[ref.blocks[0]][0], store[b[0]][0])
    np.testing.assert_array_equal(store2[ref.blocks[1]][1], store[b[1]][1])
    c2.release(ref)
    c2.check(), a2.check()
    # entry ids never recycle across restarts — a third cache spills e2+
    assert c2._entry_seq >= 2


def test_unit_corrupt_entry_drops_node_and_truncates_match(tmp_path):
    """Corruption containment: a CRC-failing component drops the node
    (files unlinked, counter bumped) and take() truncates the match —
    the caller re-prefills, nothing raises."""
    store, a, c, fill = _cache(tmp_path)
    ids = np.arange(0, 2 * BS, dtype=np.int32)
    b = a.alloc(2)
    fill(b)
    c.insert(ids, b)
    c.demote_all(to_disk=True)
    path = tmp_path / "e0.kv0.npy"
    raw = bytearray(path.read_bytes())
    raw[-1] ^= 0xFF  # flip a payload byte: np.load fine, CRC not
    path.write_bytes(bytes(raw))
    assert c.take(ids, 2 * BS) is None
    assert c.disk_corrupt_dropped == 1 and c.disk_blocks == 0
    assert c.match_tokens(ids) == 0
    assert not [f for f in os.listdir(tmp_path) if f.startswith("e0.")]
    c.check(), a.check()
    # a MISSING component behaves identically
    ids2 = np.arange(700, 700 + 2 * BS, dtype=np.int32)
    b2 = a.alloc(2)
    fill(b2)
    c.insert(ids2, b2)
    c.demote_all(to_disk=True)
    os.unlink([
        tmp_path / f for f in os.listdir(tmp_path) if f.endswith(".kv1.npy")
    ][0])
    assert c.take(ids2, 2 * BS) is None
    assert c.disk_corrupt_dropped == 2
    c.check(), a.check()


def test_unit_adopt_pool_chains_and_owner_tags(tmp_path):
    """Adoption rebuilds parent→child entry chains (shorter prefixes
    first) and preserves ``host_owners`` shard tags through the meta; an
    entry whose parent chain is gone is unlinked, not mis-attached."""
    store, a, c, fill = _cache(
        tmp_path, block_owner=lambda b: b % 2,
    )
    ids = np.arange(0, 3 * BS, dtype=np.int32)
    b = a.alloc(3)
    fill(b)
    c.insert(ids, b)
    # split the edge so TWO chained nodes spill as separate entries
    ids2 = ids.copy()
    ids2[2 * BS] = 7
    b2 = a.alloc(3)
    fill(b2)
    c.insert(ids2, b2)
    owners = {}
    c.demote_all(to_disk=True)
    for fn in os.listdir(tmp_path):
        if fn.endswith(".json"):
            m = json.load(open(tmp_path / fn))
            owners[tuple(m["prefix"])] = m["owners"]
    assert len(owners) == 3 and all(o is not None for o in owners.values())
    store2, a2, c2, _ = _cache(tmp_path, block_owner=lambda b: b % 2)
    assert c2.adopt_pool() == 3
    c2.check(), a2.check()
    assert c2.disk_blocks == 4  # 2 shared + 2 divergent tails
    assert c2.match_tokens(ids) == 3 * BS
    assert c2.match_tokens(ids2) == 3 * BS
    for n in c2._iter_nodes():
        assert n.host_owners is not None
    # break the chain: drop the ROOT entry's meta; a fresh adoption must
    # unlink the now-orphaned child entries rather than mis-attach them
    root_prefix = min(owners, key=len)
    for fn in list(os.listdir(tmp_path)):
        if fn.endswith(".json"):
            if tuple(json.load(open(tmp_path / fn))["prefix"]) \
                    == root_prefix:
                os.unlink(tmp_path / fn)
    store3, a3, c3, _ = _cache(tmp_path)
    assert c3.adopt_pool() == 0
    assert c3.disk_blocks == 0 and not os.listdir(tmp_path)
    c3.check(), a3.check()


def _strip_layout(pool, layout=None):
    """Rewrite every entry meta of ``pool`` as a pool that outlived a
    layout change would hold it: no ``layout`` key (written before the
    arena went head-major), or another layout's name."""
    n = 0
    for fn in os.listdir(pool):
        if fn.endswith(".json"):
            m = json.load(open(os.path.join(pool, fn)))
            assert m.pop("layout") == PAGED_KV_LAYOUT
            if layout is not None:
                m["layout"] = layout
            json.dump(m, open(os.path.join(pool, fn), "w"))
            n += 1
    return n


@pytest.mark.parametrize("layout", [None, "S,L,NB,BS,Nkv,D"])
def test_unit_entry_of_another_layout_is_dropped_like_a_corrupt_one(
    tmp_path, layout
):
    """The pool outlives the process, so it can outlive a change of the
    arena's layout: an entry whose meta names no layout (or another one)
    is never read — ``adopt_pool`` unlinks it exactly as it unlinks a
    corrupt one, and a live cache that finds such a meta under a node
    drops the node and truncates the match. The caller re-prefills."""
    store, a, c, fill = _cache(tmp_path)
    ids = np.arange(0, 2 * BS, dtype=np.int32)
    b = a.alloc(2)
    fill(b)
    c.insert(ids, b)
    c.demote_all(to_disk=True)
    assert json.load(open(tmp_path / "e0.json"))["layout"] == PAGED_KV_LAYOUT
    assert _strip_layout(tmp_path, layout) == 1
    # the live cache: the read path refuses the bytes, drops the node
    assert c.take(ids, 2 * BS) is None
    assert c.disk_corrupt_dropped == 1 and c.disk_blocks == 0
    assert c.match_tokens(ids) == 0
    c.check(), a.check()
    # a fresh start over such a pool adopts nothing and sweeps it
    fill(b2 := a.alloc(2))
    c.insert(ids, b2)
    c.demote_all(to_disk=True)
    assert _strip_layout(tmp_path, layout) == 1
    store2, a2, c2, _ = _cache(tmp_path)
    assert c2.adopt_pool() == 0
    assert c2.disk_blocks == 0 and not os.listdir(tmp_path)
    assert c2.match_tokens(ids) == 0
    c2.check(), a2.check()


# --------------------------------------------------- end-to-end, one server


def test_disk_round_trip_token_identical_and_metrics(setup, tmp_path):
    """Warm → spill everything to disk → a warm resubmit promotes
    disk→host→arena and decodes token-identically; the hit lands on the
    disk tier label and the gauges see the spilled blocks."""
    from llm_sharding_tpu.obs.metrics import (
        KV_DISK_TIER_BLOCKS, PREFIX_HIT_TOKENS,
    )
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    import gc

    params, eng = setup
    srv = disk_serve(eng, tmp_path / "pool")
    p1 = prompt(40, 3 * BS)
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    assert list(r1.tokens) == oracle(params, p1, 5)
    blocks_before = [int(b) for b in srv._radix.root.children[
        int(p1[0])
    ].blocks][:3]
    k_before, v_before = srv._read_arena_blocks(blocks_before)
    with srv._mutex:
        assert srv._radix.demote_all(to_disk=True) >= 1
    st = srv.prefix_cache_stats()
    assert st["disk_blocks"] >= 3 and st["host_blocks"] == 0
    gc.collect()
    _update_load_gauges()
    assert KV_DISK_TIER_BLOCKS.value >= 3
    base = PREFIX_HIT_TOKENS.labels(tier="disk").value
    p2 = np.concatenate([p1, prompt(41, 3)])
    r2 = srv.submit(p2, 5)
    srv.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 5)
    assert PREFIX_HIT_TOKENS.labels(tier="disk").value - base == 3 * BS
    assert srv.prefix_cache_stats()["disk_hit_tokens"] == 3 * BS
    node = srv._radix.root.children[int(p1[0])]
    k_after, v_after = srv._read_arena_blocks(
        [int(b) for b in node.blocks][:3]
    )
    np.testing.assert_array_equal(k_before, k_after)
    np.testing.assert_array_equal(v_before, v_after)
    check_clean(srv)
    srv.close()


def test_restart_adopts_pool_byte_exact_quantized(setup, tmp_path):
    """The pool survives the process, QUANTIZED: an int8-arena server
    serves a warm hit (the never-demoted baseline), spills, dies, and a
    FRESH server over the same dir adopts the entries — the promoted
    arena blocks (codes AND scales) are byte-equal to the pre-crash ones,
    so the same warm request decodes the identical tokens."""
    params, eng = setup
    pool = tmp_path / "pool"
    srv = disk_serve(eng, pool, kv_dtype="int8")
    p1 = prompt(50, 3 * BS)
    srv.submit(p1, 5)
    srv.run_until_idle()
    # never-demoted warm baseline: the hbm-hit decode of p1 + a tail
    p2 = np.concatenate([p1, prompt(51, 3)])
    r_warm = srv.submit(p2, 5)
    srv.run_until_idle()
    want_warm = list(r_warm.tokens)
    assert srv._radix.hit_tokens >= 3 * BS
    node = srv._radix.root.children[int(p1[0])]
    before = srv._read_arena_blocks([int(b) for b in node.blocks][:3])
    assert len(before) == 4  # k, v codes + k, v scales
    with srv._mutex:
        srv._radix.demote_all(to_disk=True)
    assert srv._radix.disk_blocks >= 3
    srv.close()  # the process "dies"; only the pool dir remains

    srv2 = disk_serve(eng, pool, kv_dtype="int8")
    assert srv2._radix.disk_blocks >= 3  # adopt_pool re-indexed the entries
    assert srv2._radix.match_tokens(p1) == 3 * BS
    r2 = srv2.submit(p2, 5)
    srv2.run_until_idle()
    # byte-identical promoted KV + the same warm admission shape →
    # the never-demoted run's exact tokens
    assert list(r2.tokens) == want_warm
    assert srv2._radix.disk_hit_tokens >= 3 * BS
    node2 = srv2._radix.root.children[int(p1[0])]
    after = srv2._read_arena_blocks([int(b) for b in node2.blocks][:3])
    for a, b in zip(before, after):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    srv2._alloc.check(), srv2._radix.check()
    srv2.close()


def test_corrupt_entry_reprefills_token_identical(setup, tmp_path):
    """A corrupt pool entry is a cache MISS, not an error: the request
    re-prefills cold and decodes the same tokens."""
    params, eng = setup
    pool = tmp_path / "pool"
    srv = disk_serve(eng, pool)
    p1 = prompt(60, 3 * BS)
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    with srv._mutex:
        srv._radix.demote_all(to_disk=True)
    victim = [f for f in os.listdir(pool) if f.endswith(".kv0.npy")][0]
    raw = bytearray((pool / victim).read_bytes())
    raw[-1] ^= 0xFF
    (pool / victim).write_bytes(bytes(raw))
    p2 = np.concatenate([p1, prompt(61, 3)])
    r2 = srv.submit(p2, 5)
    srv.run_until_idle()
    assert r2.error is None
    assert list(r2.tokens) == oracle(params, p2, 5)
    assert srv._radix.disk_corrupt_dropped >= 1
    assert srv.prefix_cache_stats()["disk_hit_tokens"] == 0
    check_clean(srv)
    srv.close()


def test_restart_over_a_pool_without_layout_reprefills(setup, tmp_path):
    """A server restarted over a disk pool written before the arena went
    head-major (entry metas name no layout): every entry is dropped at
    adoption, the warm request re-prefills cold and decodes the oracle's
    tokens — never an error upward, never old-layout bytes in the arena."""
    params, eng = setup
    pool = tmp_path / "pool"
    srv = disk_serve(eng, pool)
    p1 = prompt(80, 3 * BS)
    srv.submit(p1, 5)
    srv.run_until_idle()
    with srv._mutex:
        srv._radix.demote_all(to_disk=True)
    assert srv._radix.disk_blocks >= 3
    srv.close()
    assert _strip_layout(pool) >= 1

    srv2 = disk_serve(eng, pool)
    assert srv2._radix.disk_blocks == 0 and not os.listdir(pool)
    assert srv2._radix.match_tokens(p1) == 0
    p2 = np.concatenate([p1, prompt(81, 3)])
    r2 = srv2.submit(p2, 5)
    srv2.run_until_idle()
    assert r2.error is None
    assert list(r2.tokens) == oracle(params, p2, 5)
    assert srv2.prefix_cache_stats()["disk_hit_tokens"] == 0
    check_clean(srv2)
    srv2.close()


def test_snapshot_format7_references_pool_not_inlines(setup, tmp_path):
    """Format 7 and later: a spilled node rides the snapshot as an entry REFERENCE
    — no KV arrays inlined — and the restored server promotes it from
    the same pool files, token-identically."""
    params, eng = setup
    pool = tmp_path / "pool"
    srv = disk_serve(eng, pool)
    p1 = prompt(70, 3 * BS)
    srv.submit(p1, 4)
    srv.run_until_idle()
    with srv._mutex:
        srv._radix.demote_all(to_disk=True)
    snap = srv.snapshot()
    assert snap["format"] == 8
    disk_nodes = [
        m for m in snap["radix"]["nodes"] if m["tier"] == "disk"
    ]
    assert disk_nodes and all("entry" in m for m in disk_nodes)
    assert not any(
        k.endswith(".kv0") for k in snap["radix"]["arrays"]
    )
    d = str(tmp_path / "snap")
    save_snapshot(snap, d)
    srv.close()
    srv2 = PipelineServer.restore(eng, load_snapshot(d))
    assert srv2.prefix_cache == "disk"
    assert srv2._radix.disk_blocks >= 3
    srv2._alloc.check(), srv2._radix.check()
    r = srv2.submit(np.concatenate([p1, prompt(71, 3)]), 4)
    srv2.run_until_idle()
    assert list(r.tokens) == oracle(
        params, np.concatenate([p1, prompt(71, 3)]), 4
    )
    assert srv2._radix.disk_hit_tokens >= 3 * BS
    check_clean(srv2)
    srv2.close()


def test_validation(setup, tmp_path):
    _, eng = setup
    with pytest.raises(ValueError, match="disk_pool_dir"):
        eng.serve(
            capacity=CAP, kv_block_size=BS, kv_blocks=64,
            prefix_cache="disk",
        )
    with pytest.raises(ValueError, match="disk"):
        eng.serve(
            capacity=CAP, kv_block_size=BS, kv_blocks=64,
            prefix_cache="host", disk_pool_dir=str(tmp_path),
        )


def test_cp2_disk_tier_round_trip_with_owner_tags(setup, tmp_path):
    """The ladder under context parallelism: a cp=2 server spills with
    per-block shard tags, a fresh cp=2 server adopts the pool, and the
    promotion decodes token-identically to the unsharded oracle."""
    params, eng = setup
    if len(jax.devices()) < 8:
        pytest.skip("cp=2 x 4 stages needs 8 devices")
    pool = tmp_path / "pool"

    def cp_serve():
        return eng.serve(
            capacity=CAP, kv_block_size=BS, kv_blocks=4 * CAP // BS + 1,
            prefix_cache="disk", host_pool_blocks=4 * CAP // BS,
            disk_pool_dir=str(pool), disk_pool_blocks=4 * CAP // BS,
            prefill_chunk=2 * BS, cp=2,
        )

    srv = cp_serve()
    p1 = prompt(80, 4 * BS)
    r1 = srv.submit(p1, 4)
    srv.run_until_idle()
    assert list(r1.tokens) == oracle(params, p1, 4)
    with srv._mutex:
        srv._radix.demote_all(to_disk=True)
    metas = [
        json.load(open(pool / f)) for f in os.listdir(pool)
        if f.endswith(".json")
    ]
    assert metas and all(m["owners"] is not None for m in metas)
    srv.close()

    srv2 = cp_serve()
    # chunk-admitted rows index plen-1 floor: 3 of the 4 prompt blocks
    assert srv2._radix.match_tokens(p1) == 3 * BS
    for n in srv2._radix._iter_nodes():
        assert n.host_owners is not None  # provenance survived the restart
    p2 = np.concatenate([p1, prompt(81, 3)])
    r2 = srv2.submit(p2, 4)
    srv2.run_until_idle()
    assert list(r2.tokens) == oracle(params, p2, 4)
    assert srv2._radix.disk_hit_tokens >= 3 * BS
    srv2._alloc.check(), srv2._radix.check()
    srv2.close()
