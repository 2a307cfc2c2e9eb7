"""Paged KV cache serving (ISSUE 5): block-table allocator, ragged paged
attention, block-level prefix sharing.

The contract under test: paged greedy serving is TOKEN-IDENTICAL to dense
serving on the same workload (the serve programs see the same logical
[Bs, W] window either way — dense slices it, paged gathers it through the
rows' block tables), exhaustion is a queue wait rather than a crash, and
every lifecycle path (finish/cancel/deadline/failure) provably returns its
blocks to the pool (``BlockAllocator.check`` is the invariant).

``PAGED_TEST_BLOCK_SIZE`` parameterizes the block size so CI can re-run
this module at a tiny size (block-boundary + table-growth stress) without a
second test body.
"""

import collections
import functools
import json
import os
import re
from unittest import mock

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.blocks import (
    TRASH_BLOCK, BlockAllocator, BlockExhausted,
)
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.faults import FaultPlan, PermanentFault
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.server import PipelineServer

from paged_arena import (
    LAYER_CASES, LAYERS, int8_stack, make_stack, others_untouched, window,
)

CFG = tiny_llama(num_hidden_layers=8)
# CI runs this module twice: default 16, then PAGED_TEST_BLOCK_SIZE=4 to
# stress block-boundary and multi-entry-table paths (capacity 64 → T=16)
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "16"))


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(11), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def prompt(seed, n=5):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def oracle_tokens(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return list(res.tokens[0, len(p): int(res.lengths[0])])


def paged_kw(capacity=64, rows=4, frac=1.0):
    """kv kwargs sized so ``frac`` of the dense KV budget (rows × capacity
    slots) is available as whole blocks, + the reserved trash block."""
    return dict(
        kv_block_size=BS,
        kv_blocks=max(2, int(rows * capacity * frac) // BS + 1),
    )


# ------------------------------------------------------------ BlockAllocator


def test_allocator_alloc_free_refcount():
    a = BlockAllocator(num_blocks=8, block_size=BS)
    assert a.capacity_blocks == 7 and a.num_free == 7 and a.in_use == 0
    x = a.alloc(3)
    assert len(x) == 3 and TRASH_BLOCK not in x and a.in_use == 3
    a.check()
    a.free(x)
    assert a.num_free == 7 and a.in_use == 0
    a.check()


def test_allocator_exhaustion_is_typed_and_not_partial():
    a = BlockAllocator(num_blocks=4, block_size=BS)
    a.alloc(2)
    free_before = a.num_free
    with pytest.raises(BlockExhausted):
        a.alloc(2)  # only 1 free: must not take it and then fail
    assert a.num_free == free_before
    a.check()


def test_allocator_fragmentation_reuse():
    """Freed blocks — including non-contiguous interior ones — are reused;
    the pool never leaks to fragmentation (blocks are position-free: any
    free block serves any table entry)."""
    a = BlockAllocator(num_blocks=10, block_size=BS)
    x = a.alloc(9)  # pool exhausted
    a.free([x[1], x[4], x[7]])  # interior holes
    y = a.alloc(3)  # fits exactly in the holes
    assert sorted(y) == sorted([x[1], x[4], x[7]])
    with pytest.raises(BlockExhausted):
        a.alloc(1)
    a.free([b for b in x if b not in y])
    a.free(y)
    assert a.num_free == 9
    a.check()


def test_allocator_share_refcounts():
    a = BlockAllocator(num_blocks=6, block_size=BS)
    shared = a.alloc(2)
    a.share(shared)  # row 1 maps them
    a.share(shared)  # row 2 maps them
    a.free(shared)   # row 1 done
    a.free(shared)   # row 2 done — still held by the original owner
    assert a.in_use == 2
    a.free(shared)   # owner releases: last reference drops
    assert a.in_use == 0
    a.check()


def test_allocator_misuse_is_loud():
    a = BlockAllocator(num_blocks=4, block_size=BS)
    x = a.alloc(1)
    with pytest.raises(ValueError, match="trash"):
        a.free([TRASH_BLOCK])
    with pytest.raises(ValueError):
        a.share([TRASH_BLOCK])
    free_block = [b for b in range(1, 4) if b not in x][0]
    with pytest.raises(ValueError):
        a.share([free_block])  # share of an unallocated block
    a.free(x)
    with pytest.raises(ValueError, match="double free"):
        a.free(x)
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=1, block_size=BS)  # only the trash block


def test_allocator_restore_rebuilds_ownership():
    a = BlockAllocator(num_blocks=8, block_size=BS)
    # rows 0,1 own private blocks; both map shared blocks [5, 6]
    a.restore(private_rows=[[1, 2], [3]], shared_rows=[[5, 6], [5, 6]])
    a.check()
    assert a.in_use == 5
    a.free([5, 6])  # row 0's references
    assert a.in_use == 5  # row 1 still maps them
    a.free([5, 6])
    assert a.in_use == 3
    with pytest.raises(ValueError):
        BlockAllocator(8, BS).restore([[1], [1]], [])  # double-owned


# ------------------------------------------------- ServeState ↔ state_specs


def test_state_specs_field_parity(setup):
    """Every ServeState leaf has a sharding spec and the two stay in sync:
    a field added to the NamedTuple without a spec makes state_specs'
    explicit-kwargs construction raise, and the structures must match leaf
    for leaf (this is what keeps snapshots and shard_map specs honest when
    paged fields land)."""
    from llm_sharding_tpu.parallel import serve as serve_ops

    _, eng = setup
    for kv in (dict(), dict(kv_blocks=8, kv_block_size=BS)):
        state = serve_ops.make_state(
            CFG, eng.mesh, eng.placement.max_layers_per_stage, capacity=32,
            batch_per_slot=1, cache_dtype=jnp.float32, **kv,
        )
        specs = serve_ops.state_specs(state)
        assert state._fields == specs._fields
        # a windowed model's second KV state (k_swa / v_swa / tables_swa), a
        # recurrent-state model's ``recurrent`` tree and a token-selecting
        # model's index arena ``idx`` are None — an empty pytree, no operand
        # of any program — for every other model, in the state and in its
        # specs alike
        live = {k: v for k, v in specs._asdict().items() if v is not None}
        assert set(specs._fields) - set(live) == {
            "k_swa", "v_swa", "tables_swa", "recurrent", "idx"}
        assert all(getattr(state, k) is None for k in set(specs._fields) - set(live))
        for name, spec in live.items():
            assert isinstance(spec, jax.sharding.PartitionSpec), name
        # one spec leaf per state leaf (the shard_map in/out contract)
        assert len(jax.tree.leaves(state)) == len(live)
        # block table leaf exists in BOTH modes (dense: [M,1] placeholder)
        # so the pytree shape — and with it snapshots — is mode-independent
        assert state.block_tables.ndim == 2


# -------------------------------------------- paged ↔ dense token identity


def run_workload(srv, specs):
    reqs = [srv.submit(p, n, **kw) for p, n, kw in specs]
    srv.run_until_idle()
    return [list(r.tokens) for r in reqs]


def check_drained(srv):
    """Post-drain allocator invariant: every block came home."""
    srv._alloc.check()
    assert srv._alloc.in_use == 0
    assert not any(srv._row_blocks) and not any(srv._row_shared)
    assert (srv._tables == TRASH_BLOCK).all()


def test_paged_token_identical_plain(setup):
    """Staggered mixed-length requests through fewer slots than requests:
    paged == dense == solo oracle, and the pool fully drains."""
    params, eng = setup
    specs = [
        (prompt(s, n), b, {})
        for s, n, b in [(1, 5, 12), (2, 3, 8), (3, 6, 4), (4, 2, 15),
                        (5, 4, 6), (6, 5, 9)]
    ]
    dense = run_workload(eng.serve(capacity=64), specs)
    srv = eng.serve(capacity=64, **paged_kw())
    paged = run_workload(srv, specs)
    assert paged == dense
    for (p, b, _), toks in zip(specs, paged):
        assert toks == oracle_tokens(params, p, b)
    check_drained(srv)


def test_paged_token_identical_batched_slots(setup):
    params, eng = setup
    specs = [(prompt(10 + i, 3 + i % 3), 7, {}) for i in range(5)]
    dense = run_workload(eng.serve(capacity=64, batch_per_slot=2), specs)
    srv = eng.serve(capacity=64, batch_per_slot=2, **paged_kw(rows=8))
    assert run_workload(srv, specs) == dense
    check_drained(srv)


def test_paged_token_identical_sampled(setup):
    """Seeded sampling: the rng path is row-indexed, not cache-layout
    indexed, so sampled output is identical too."""
    params, eng = setup
    specs = [
        (prompt(21), 10, dict(temperature=0.9, seed=5)),
        (prompt(22, 3), 8, dict(temperature=1.1, top_k=8, seed=9)),
    ]
    dense = run_workload(eng.serve(capacity=64), specs)
    srv = eng.serve(capacity=64, **paged_kw())
    assert run_workload(srv, specs) == dense
    check_drained(srv)


def test_paged_token_identical_chunked_prefill(setup):
    """Chunked admission scatters each prefill chunk through the tables;
    the final injected token rides the +1 block margin."""
    params, eng = setup
    p_long = prompt(31, 24)
    specs = [(p_long, 8, {}), (prompt(32, 3), 6, {})]
    dense = run_workload(
        eng.serve(capacity=64, prefill_chunk=8), specs
    )
    srv = eng.serve(capacity=64, prefill_chunk=8, **paged_kw())
    assert run_workload(srv, specs) == dense
    assert dense[0] == oracle_tokens(params, p_long, 8)
    check_drained(srv)


def test_paged_token_identical_spec_verify(setup):
    """Speculative verify in paged mode: the K+1 scratch columns live in
    trash-mapped table entries (never persisted), so acceptance/compaction
    matches dense exactly."""
    params, eng = setup
    specs = [(prompt(41, 4), 12, {}), (prompt(42, 6), 10, {})]
    dense = run_workload(eng.serve(capacity=64, speculate=2), specs)
    srv = eng.serve(capacity=64, speculate=2, **paged_kw())
    assert run_workload(srv, specs) == dense
    for (p, b, _), toks in zip(specs, dense):
        assert toks == oracle_tokens(params, p, b)
    check_drained(srv)


# ------------------------------------------------------- prefix sharing


def test_paged_prefix_sharing_token_identical_and_shared(setup):
    """Block-level prefix sharing: N rows decode against ONE stored copy of
    the prefix (refcount == mapping rows + the handle), output equals the
    dense prefix path AND the full-prompt oracle; releasing the handle
    returns the blocks once the last row finishes."""
    params, eng = setup
    pfx = prompt(51, 2 * max(BS, 8))
    sfx = [prompt(52 + i, 3) for i in range(3)]

    srv_d = eng.serve(capacity=128)
    hd = srv_d.prefill_prefix(pfx)
    dense = run_workload(srv_d, [(s, 6, dict(prefix=hd)) for s in sfx])

    srv = eng.serve(capacity=128, **paged_kw(capacity=128))
    h = srv.prefill_prefix(pfx)
    assert h.blocks and len(h.blocks) == srv._bucket(len(pfx)) // BS
    ref = srv._alloc._ref  # noqa: SLF001 — asserting the sharing invariant
    reqs = [srv.submit(s, 6, prefix=h) for s in sfx]
    for _ in range(8):  # pump until every row is admitted (mapped)
        srv.step()
        if all(r.row is not None for r in reqs):
            break
    assert all(ref[b] == 1 + len(sfx) for b in h.blocks)
    # stored once: in-use blocks < 3 × (prefix + suffix) private need
    assert srv._alloc.in_use < 3 * (len(h.blocks) + 2) + len(h.blocks)
    srv.run_until_idle()
    paged = [list(r.tokens) for r in reqs]
    assert paged == dense
    for s, toks in zip(sfx, paged):
        assert toks == oracle_tokens(params, np.concatenate([pfx, s]), 6)
    # rows done: only the handle's own references remain
    assert all(ref[b] == 1 for b in h.blocks)
    assert srv._alloc.in_use == len(h.blocks)
    srv.release_prefix(h)
    assert h.blocks is None
    check_drained(srv)
    srv.release_prefix(h)  # double release: no-op


# ---------------------------------------------- exhaustion + release paths


def test_block_exhaustion_queues_then_admits(setup):
    """A pool too small for all requests at once: admission waits in FIFO
    order (no crash, no partial admit) and the queued requests complete
    token-exactly as blocks free up."""
    params, eng = setup
    # room for exactly 2 rows' blocks (bucket 8 + budget 10 per row): the
    # other 2 submissions must wave through as blocks free
    per_row = -(-(8 + 10) // BS)
    srv = eng.serve(capacity=64, kv_block_size=BS,
                    kv_blocks=2 * per_row + 1)
    specs = [(prompt(61 + i, 4), 10, {}) for i in range(4)]
    reqs = [srv.submit(p, n, **kw) for p, n, kw in specs]
    srv.step()
    assert len(srv._queue) >= 1  # someone had to wait for blocks
    srv.run_until_idle()
    for (p, b, _), r in zip(specs, reqs):
        assert r.error is None and list(r.tokens) == oracle_tokens(params, p, b)
    assert srv.counters.requests_completed == 4
    check_drained(srv)


def test_oversized_request_typed_rejection(setup):
    """A request that could never fit even an EMPTY pool is a typed submit
    error, not a forever-queued ghost."""
    _, eng = setup
    srv = eng.serve(capacity=64, kv_block_size=BS, kv_blocks=2)
    with pytest.raises(ValueError, match="KV blocks"):
        srv.submit(prompt(70, 4), 40)
    assert len(srv._queue) == 0
    check_drained(srv)


def test_never_fits_prompt_typed_rejection_at_submit(setup):
    """A prompt that can NEVER admit — longer than the largest admit bucket
    the server's capacity allows, or whose positions would run past
    max_position_embeddings — is a typed ValueError at ``submit()``, not a
    forever-queued ghost (the long-context analogue of the block-ceiling
    check above; under cp the admissible length grows, the refusal contract
    does not change)."""
    _, eng = setup
    srv = eng.serve(capacity=64, **paged_kw())
    # no admit bucket >= 200 fits capacity 64
    with pytest.raises(ValueError, match="admit buckets"):
        srv.submit(prompt(71, 200), 4)
    assert len(srv._queue) == 0
    check_drained(srv)
    # position ceiling: capacity 256 > max_position_embeddings 128, so a
    # request can fit the cache yet run past the rope table — bucket(50)=64
    # plus 80 new tokens needs 144 positions
    srv = eng.serve(capacity=256, **paged_kw(capacity=256))
    with pytest.raises(ValueError, match="max_position_embeddings"):
        srv.submit(prompt(72, 50), 80)
    assert len(srv._queue) == 0
    check_drained(srv)


def test_embedding_oversized_with_pins_typed_rejection(setup):
    """``submit_embedding`` honors the same never-fits ceiling as
    ``submit()``: blocks pinned by a live prefix handle can only come back
    via release_prefix, so a need that fits the raw pool but not
    pool-minus-pins must reject at submit, not park at the FIFO head."""
    _, eng = setup
    srv = eng.serve(capacity=64, kv_block_size=BS, kv_blocks=64 // BS + 1)
    h = srv.prefill_prefix(prompt(80, max(BS, 8)))
    assert len(h.blocks) >= 1
    emb = eng.embed_prompt(prompt(81, 4))[0]
    # need == the whole pool: fits capacity_blocks, not capacity - pins
    max_new = srv._alloc.capacity_blocks * BS - srv._bucket(4)
    with pytest.raises(ValueError, match="pinned"):
        srv.submit_embedding(emb, max_new)
    assert len(srv._queue) == 0
    srv.release_prefix(h)
    check_drained(srv)


def test_prefix_handle_wrong_server_typed_error(setup):
    """A paged prefix handle is pool-LOCAL: its block ids index the
    allocating server's arena, so mapping (submit) or freeing
    (release_prefix) them on another server must be a typed error — not
    silent corruption of that server's live rows."""
    _, eng = setup
    a = eng.serve(capacity=64, **paged_kw())
    b = eng.serve(capacity=64, **paged_kw())
    h = a.prefill_prefix(prompt(90, max(BS, 8)))
    with pytest.raises(ValueError, match="different server"):
        b.submit(prompt(91, 3), 4, prefix=h)
    with pytest.raises(ValueError, match="different server"):
        b.release_prefix(h)
    assert h.blocks  # the foreign attempts touched nothing
    a.release_prefix(h)
    check_drained(a)
    check_drained(b)


def test_paged_server_kwarg_validation(setup):
    _, eng = setup
    with pytest.raises(ValueError, match="go together"):
        eng.serve(capacity=64, kv_block_size=BS)
    with pytest.raises(ValueError, match="power of two"):
        eng.serve(capacity=64, kv_block_size=BS + 1 if BS > 2 else 3,
                  kv_blocks=8)
    with pytest.raises(ValueError, match=">= 2"):
        eng.serve(capacity=64, kv_block_size=BS, kv_blocks=1)


def test_blocks_freed_on_cancel_and_deadline(setup):
    """Cancel and deadline-expiry both remap the row to trash and return
    its blocks — the freed blocks immediately serve a new admission."""
    params, eng = setup
    srv = eng.serve(capacity=64, **paged_kw(rows=2))
    r_cancel = srv.submit(prompt(81), 30)
    r_dead = srv.submit(prompt(82), 30, deadline_s=0.05)
    srv.step()
    held = srv._alloc.in_use
    assert held > 0
    assert srv.cancel(r_cancel)
    import time as _t

    _t.sleep(0.06)  # r_dead expires mid-flight
    srv.step()  # cancel batch + deadline sweep at the chunk boundary
    srv.run_until_idle()
    assert r_dead.done
    check_drained(srv)
    # the pool is whole again: a full-size request admits and completes
    r_new = srv.submit(prompt(83, 4), 6)
    assert srv.result(r_new) == oracle_tokens(params, prompt(83, 4), 6)
    check_drained(srv)


def test_blocks_freed_on_contained_failure(setup):
    """Chaos: a permanent per-request fault fails ONLY that request and
    frees its blocks; the co-resident row finishes token-exactly and the
    allocator invariant holds throughout."""
    params, eng = setup
    srv = eng.serve(
        capacity=64, batch_per_slot=2,
        fault_plan=FaultPlan.permanent("request_apply", key=0),
        fault_backoff_s=0.0, **paged_kw(rows=8),
    )
    pa, pb = prompt(91), prompt(92)
    victim = srv.submit(pa, 8)  # id 0 → poisoned
    neighbor = srv.submit(pb, 8)
    srv.run_until_idle()
    assert victim.done and isinstance(victim.error, PermanentFault)
    assert neighbor.error is None
    assert list(neighbor.tokens) == oracle_tokens(params, pb, 8)
    check_drained(srv)
    # freed row + blocks re-admit
    pc = prompt(93, 3)
    assert srv.result(srv.submit(pc, 6)) == oracle_tokens(params, pc, 6)
    check_drained(srv)


def test_kv_gauges_track_pool(setup):
    from llm_sharding_tpu.obs.metrics import (
        KV_BLOCKS_IN_USE, KV_BLOCKS_TOTAL, KV_WASTE_FRAC,
    )

    from llm_sharding_tpu.runtime.server import _update_load_gauges

    _, eng = setup
    srv = eng.serve(capacity=64, **paged_kw())
    r = srv.submit(prompt(95), 20)
    srv.step()
    _update_load_gauges()  # deterministic read-back point
    assert KV_BLOCKS_TOTAL.value >= srv._alloc.capacity_blocks
    assert KV_BLOCKS_IN_USE.value >= srv._alloc.in_use > 0
    assert 0.0 <= KV_WASTE_FRAC.value < 1.0
    srv.run_until_idle()
    assert r.done
    check_drained(srv)


# ------------------------------------------------------------- ragged op


@pytest.mark.parametrize("layer", LAYER_CASES)
def test_paged_attention_xla_matches_dense(layer):
    """The gather path over a scattered arena == dense cached_attention
    over the contiguous equivalent, sentinels and all — at each layer of a
    stack whose layers all differ (the window is read back by plain numpy
    indexing, so a gather that ignored ``layer`` fails here)."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.attention import cached_attention
    from llm_sharding_tpu.ops.paged_attention import paged_attention_xla

    rng = np.random.default_rng(0)
    B, T, bs, Nkv, G, D = 3, 4, 8, 2, 2, 16
    W, Nh = T * bs, Nkv * G
    NB = B * T + 1
    k_arena, v_arena = make_stack(rng, NB, Nkv, bs, D)
    # shuffled non-contiguous tables (block 0 = trash for the tails)
    perm = rng.permutation(np.arange(1, NB))
    tbl = np.zeros((B, T), np.int32)
    lengths = [W, W - bs - 3, 5]  # full / partial tail block / tiny
    for b in range(B):
        nblk = -(-lengths[b] // bs)
        tbl[b, :nblk] = perm[b * T: b * T + nblk]
    kvpos = np.full((B, W), POS_SENTINEL, np.int32)
    for b in range(B):
        kvpos[b, : lengths[b]] = np.arange(lengths[b])
    q = jnp.asarray(rng.normal(size=(B, 1, Nh, D)), jnp.float32)
    qpos = jnp.asarray([[lengths[b]] for b in range(B)], jnp.int32)

    got = paged_attention_xla(
        q, k_arena, v_arena, layer, jnp.asarray(tbl), qpos,
        jnp.asarray(kvpos),
    )
    want = cached_attention(
        q, jnp.asarray(window(k_arena, layer, tbl)),
        jnp.asarray(window(v_arena, layer, tbl)), qpos, jnp.asarray(kvpos),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("layer", LAYER_CASES)
def test_write_block_kv_scatters_into_owning_blocks(layer):
    """The decode-path write primitive: entries land at ``(layer, block,
    :, slot)`` of the stack — the block the table names, the in-block
    slot — trash-mapped columns hit the sink, untouched slots are
    untouched, EVERY OTHER LAYER keeps its bytes, and the ``valid`` gate
    (ring-inactive microsteps, masked layers) leaves an invalid entry's
    owning block alone: the entry goes to the trash block of its layer."""
    from llm_sharding_tpu.ops.paged_attention import write_block_kv

    rng = np.random.default_rng(3)
    NB, bs, Nkv, D = 6, 4, 2, 8
    B = 3
    k, v = make_stack(rng, NB, Nkv, bs, D)
    tbl = jnp.asarray([[2, 3, 0], [4, 0, 0], [5, 1, 0]], jnp.int32)
    cols = jnp.asarray([[5], [2], [9]], jnp.int32)  # row 2 → trash (entry 0)
    kn = jnp.asarray(rng.normal(size=(B, 1, Nkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, 1, Nkv, D)), jnp.float32)
    k2, v2 = write_block_kv(k, v, layer, tbl, cols, kn, vn)
    kl, k2l, v2l = (np.asarray(a)[layer] for a in (k, k2, v2))
    np.testing.assert_array_equal(k2l[3, :, 1], np.asarray(kn)[0, 0])
    np.testing.assert_array_equal(v2l[4, :, 2], np.asarray(vn)[1, 0])
    np.testing.assert_array_equal(k2l[0, :, 1], np.asarray(kn)[2, 0])
    np.testing.assert_array_equal(k2l[5], kl[5])
    np.testing.assert_array_equal(k2l[3, :, 0], kl[3, :, 0])
    others_untouched(k, k2, layer)
    others_untouched(v, v2, layer)
    # per-entry valid gating: only row 1 writes
    mask = jnp.asarray([[False], [True], [False]])
    k3, _ = write_block_kv(k, v, layer, tbl, cols, kn, vn, valid=mask)
    k3l = np.asarray(k3)[layer]
    np.testing.assert_array_equal(k3l[3, :, 1], kl[3, :, 1])
    np.testing.assert_array_equal(k3l[4, :, 2], np.asarray(kn)[1, 0])
    others_untouched(k, k3, layer)
    # scalar False (an inactive ring microstep) touches no block but the
    # layer's trash: rows 0 and 2 collide on its slot 1 (last wins, either
    # may), row 1 has slot 2 to itself
    k4, v4 = write_block_kv(
        k, v, layer, tbl, cols, kn, vn, valid=jnp.asarray(False)
    )
    for before, after, new in ((k, k4, kn), (v, v4, vn)):
        np.testing.assert_array_equal(
            np.asarray(after)[:, 1:], np.asarray(before)[:, 1:]
        )
        others_untouched(before, after, layer)
        trash, new = np.asarray(after)[layer, 0], np.asarray(new)
        np.testing.assert_array_equal(trash[:, 2], new[1, 0])
        assert any(np.array_equal(trash[:, 1], new[b, 0]) for b in (0, 2))
        np.testing.assert_array_equal(
            trash[:, [0, 3]], np.asarray(before)[layer, 0][:, [0, 3]]
        )


def _write_with_read_back(k_arena, v_arena, layer, tbl, cols, kn, vn, valid):
    """The write as it stood before the gate moved to the address: an
    invalid entry gathers the old rows of its owning block and writes them
    back. Kept here as the oracle of what the attended blocks must hold."""
    Nkv, bs = k_arena.shape[2], k_arena.shape[3]
    blk = jnp.take_along_axis(tbl, cols // bs, axis=1)
    entry = (layer, blk[:, :, None], jnp.arange(Nkv)[None, None, :],
             (cols % bs)[:, :, None])
    keep = jnp.asarray(valid)
    if keep.ndim:
        keep = keep[..., None, None]
    return (
        k_arena.at[entry].set(jnp.where(keep, kn, k_arena[entry])),
        v_arena.at[entry].set(jnp.where(keep, vn, v_arena[entry])),
    )


#: the gate as its callers hand it over: a scalar (a ring microstep, a
#: masked layer: ``write_valid & valid``) or one flag per entry (verify's
#: ``[B, S]``; a parked row of a decode step)
_VALID_CASES = {
    "scalar_true": lambda B, S: jnp.asarray(True),
    "scalar_false": lambda B, S: jnp.asarray(False),
    "per_entry": lambda B, S: jnp.asarray(
        (np.arange(B)[:, None] + np.arange(S)[None]) % 3 != 1
    ),
    "per_row": lambda B, S: jnp.broadcast_to(
        jnp.asarray([True, False, True])[:B, None], (B, S)
    ),
}


@pytest.mark.parametrize("S", (1, 3))
@pytest.mark.parametrize("valid_case", sorted(_VALID_CASES))
def test_an_invalid_entry_lands_in_the_trash_of_its_own_layer(valid_case, S):
    """The gate by address against the gate by value: every block a table
    can name (1 ...) holds, bit for bit, what the read-back formulation
    left there — valid entries written, invalid ones' owning slots as they
    were — every other layer is untouched, an invalid entry is found in
    block 0 of ITS layer at its slot, and attention over the rows' tables
    reads the same from both arenas."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_xla, write_block_kv,
    )

    rng = np.random.default_rng(31)
    NB, bs, Nkv, G, D, B, T = 9, 4, 2, 2, 8, 3, 3
    layer = 2
    k, v = make_stack(rng, NB, Nkv, bs, D)
    tbl = jnp.asarray([[2, 3, 0], [4, 6, 0], [5, 1, 7]], jnp.int32)
    lengths = np.asarray([4, 2, 7])
    cols = jnp.asarray(lengths[:, None] + np.arange(S)[None], jnp.int32)
    kn = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    valid = _VALID_CASES[valid_case](B, S)

    got = write_block_kv(k, v, layer, tbl, cols, kn, vn, valid=valid)
    want = _write_with_read_back(k, v, layer, tbl, cols, kn, vn, valid)
    flags = np.broadcast_to(np.asarray(valid), (B, S))
    for before, a, w, new in zip((k, v), got, want, (kn, vn)):
        a, w, new = np.asarray(a), np.asarray(w), np.asarray(new)
        np.testing.assert_array_equal(a[:, 1:], w[:, 1:])
        others_untouched(before, a, layer)
        trash = a[layer, 0]  # [Nkv, bs, D]
        slots = np.asarray(cols) % bs
        for b, s in zip(*np.nonzero(~flags)):
            same_slot = [
                new[b2, s2] for b2, s2 in zip(*np.nonzero(~flags))
                if slots[b2, s2] == slots[b, s]
            ]
            assert any(
                np.array_equal(trash[:, slots[b, s]], e) for e in same_slot
            )
            # ... and its owning slot holds what it held
            blk = int(np.asarray(tbl)[b, int(cols[b, s]) // bs])
            np.testing.assert_array_equal(
                a[layer, blk, :, slots[b, s]],
                np.asarray(before)[layer, blk, :, slots[b, s]],
            )
        if flags.all():
            np.testing.assert_array_equal(trash, np.asarray(before)[layer, 0])

    # what a decode step attends: the rows' windows after the write, the
    # valid entries visible, read through both arenas
    W = T * bs
    kvpos = np.full((B, W), POS_SENTINEL, np.int32)
    for b in range(B):
        n = lengths[b] + S
        kvpos[b, :n] = np.arange(n)
    q = jnp.asarray(rng.normal(size=(B, S, Nkv * G, D)), jnp.float32)
    out = [
        np.asarray(paged_attention_xla(
            q, ka, va, layer, tbl, cols, jnp.asarray(kvpos)
        ))
        for ka, va in (got, want)
    ]
    np.testing.assert_array_equal(out[0], out[1])


#: the arenas a chunk writes: key and value widths alike (llama, gpt2,
#: OLMoE), a latent arena that holds no values (deepseek_v3), keys wider
#: than values (mimo_v2: 192 stored beside 128)
_CHUNK_ARENAS = {"alike": (8, 8), "latent": (8, 0), "unlike": (12, 8)}
#: the tables a chunk meets: every row with blocks of its own; block 0 in
#: the chunk's range (a padded row of the slot, a short row's pad blocks, a
#: window layer's freed block)
_CHUNK_TABLES = {
    "owned": [[2, 3, 4, 5, 6], [7, 8, 9, 10, 11], [12, 13, 14, 15, 16]],
    "trash_in_range": [[2, 3, 0, 5, 0], [0, 0, 0, 0, 0], [12, 0, 14, 15, 16]],
}


def _chunk_case(arena, table, NB=17, bs=4, Nkv=2, B=3, Sc=8, seed=5):
    rng = np.random.default_rng(seed)
    D, Dv = _CHUNK_ARENAS[arena]
    k, _ = make_stack(rng, NB, Nkv, bs, D)
    v, _ = make_stack(rng, NB, Nkv, bs, Dv)
    kn = jnp.asarray(rng.normal(size=(B, Sc, Nkv, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, Sc, Nkv, Dv)), jnp.float32)
    return k, v, jnp.asarray(_CHUNK_TABLES[table], jnp.int32), kn, vn


def _chunk_cols(col0, B, Sc):
    return jnp.broadcast_to(
        col0 + jnp.arange(Sc, dtype=jnp.int32)[None, :], (B, Sc)
    )


@pytest.mark.parametrize("valid", (None, True, False))
@pytest.mark.parametrize("table", sorted(_CHUNK_TABLES))
@pytest.mark.parametrize("arena", sorted(_CHUNK_ARENAS))
@pytest.mark.parametrize("col0", (0, 12))
@pytest.mark.parametrize("layer", (0, LAYERS - 1))
def test_a_chunk_written_as_tiles_leaves_what_the_rows_leave(
    layer, col0, arena, table, valid
):
    """``write_chunk_kv`` against ``write_block_kv`` on the same chunk:
    both arenas equal bit for bit in every block a table can own (1 ...),
    every other layer untouched, and under ``valid=False`` no owned block
    changed at all — at the chunk's first column 0 and at a later block,
    with block 0 inside the chunk's range, for a latent arena and for keys
    wider than values."""
    from llm_sharding_tpu.ops.paged_attention import (
        chunk_writes_tiles, write_block_kv, write_chunk_kv,
    )

    k, v, tbl, kn, vn = _chunk_case(arena, table)
    B, Sc = kn.shape[:2]
    assert chunk_writes_tiles(Sc, k.shape[3], False)
    gate = None if valid is None else jnp.asarray(valid)
    got = jax.jit(write_chunk_kv)(
        k, v, layer, tbl, jnp.asarray(col0, jnp.int32), kn, vn, gate
    )
    want = write_block_kv(
        k, v, layer, tbl, _chunk_cols(col0, B, Sc), kn, vn, valid=gate
    )
    for before, a, w in zip((k, v), got, want):
        a, w = np.asarray(a), np.asarray(w)
        assert a.shape == w.shape == before.shape
        np.testing.assert_array_equal(a[:, 1:], w[:, 1:])
        others_untouched(before, a, layer)
        if valid is False:
            np.testing.assert_array_equal(
                a[:, 1:], np.asarray(before)[:, 1:]
            )
    if valid is not False and arena != "latent" and table == "owned":
        # the tiles are the chunk's own entries, block by block
        bs, j0 = k.shape[3], col0 // k.shape[3]
        np.testing.assert_array_equal(
            window(got[1], layer, tbl)[:, j0 * bs: j0 * bs + Sc],
            np.asarray(vn),
        )


@pytest.mark.parametrize("case", ("under_a_block", "int8_arena"))
def test_a_chunk_that_cannot_be_tiles_takes_the_row_wise_write(case):
    """What the chunk program can see statically decides the form: a chunk
    that is not whole blocks, and a quantized arena (its running per-block
    scales), are written by ``write_block_kv`` itself — the tile scatter
    is not in their program."""
    from llm_sharding_tpu.ops import paged_attention as pa

    k, v, tbl, kn, vn = _chunk_case("alike", "owned")
    col0, scales = jnp.asarray(4, jnp.int32), {}
    if case == "under_a_block":
        kn, vn = kn[:, :2], vn[:, :2]
        assert not pa.chunk_writes_tiles(2, k.shape[3], False)
    else:
        k, v, scales = int8_stack(np.random.default_rng(6), k, v)
        assert not pa.chunk_writes_tiles(kn.shape[1], k.shape[3], True)
    B, Sc = kn.shape[:2]
    with mock.patch.object(
        pa, "write_block_kv", wraps=pa.write_block_kv
    ) as rows:
        got = pa.write_chunk_kv(k, v, 1, tbl, col0, kn, vn, **scales)
    assert rows.call_count == 1
    want = pa.write_block_kv(
        k, v, 1, tbl, _chunk_cols(col0, B, Sc), kn, vn, **scales
    )
    assert len(got) == len(want) == (4 if scales else 2)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


# ---------------- a decode step's write and its attention as one op

#: the arenas a decode step writes (key/value heads, query heads a head, key
#: and value lanes, storage): the 7B's fold, OLMoE's sixteen heads in the
#: chip's dtype, keys wider than values with a window, a sink and a freed
#: block behind it (mimo_v2's window layers), a latent arena
_FUSED_ARENAS = {
    "gqa_4x7": dict(Nkv=4, G=7, D=8, Dv=8),
    "mha_16x1_bf16": dict(Nkv=16, G=1, D=8, Dv=8, dtype=jnp.bfloat16),
    "window_sink_k_wider": dict(Nkv=2, G=2, D=12, Dv=8, window=6, sink=True),
    "latent": dict(Nkv=1, G=4, D=8, Dv=0, latent_v=6),
    # a table of six entries walks in cells of two: a row's second cell
    # copied while its first is scored, the next row's first from its last
    "latent_cells_of_2": dict(Nkv=1, G=4, D=8, Dv=0, latent_v=6, T=6),
    "window_sink_k_wider_cells_of_2": dict(
        Nkv=2, G=2, D=12, Dv=8, window=6, sink=True, T=6),
}


def _fused_case(arena, bs=4, T=5, NB=24, seed=9):
    """Four rows of a slot at a decode step: row 0's entry at slot 0 of a
    block never written, row 1's at the last slot of its block, row 2 dead
    (table all trash, no real query), row 3 parked on a trash-mapped column
    (its entry goes to the sink, its query attends what it holds). Under a
    window the blocks behind it are freed (table entry 0)."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    a = dict(_FUSED_ARENAS[arena])
    T = a.pop("T", T)
    rng = np.random.default_rng(seed)
    dt = a.pop("dtype", jnp.float32)
    Nkv, G, D, Dv = a.pop("Nkv"), a.pop("G"), a.pop("D"), a.pop("Dv")
    k, _ = make_stack(rng, NB, Nkv, bs, D, dt)
    v, _ = make_stack(rng, NB, Nkv, bs, Dv, dt)
    cols = np.asarray([3 * bs, 2 * bs - 1, 0, 4 * bs + 1], np.int32)
    table = np.zeros((4, T), np.int32)
    table[0, :4] = [2, 3, 4, 5]
    table[1, :2] = [6, 7]
    table[3, :4] = [8, 9, 10, 11]  # column 4·bs + 1 is trash-mapped
    if a.get("window"):
        table[0, :1] = 0  # behind the window: handed back to the pool
    kvpos = np.full((4, T * bs), POS_SENTINEL, np.int32)
    for b in (0, 1):
        kvpos[b, : cols[b] + 1] = np.arange(cols[b] + 1)
    kvpos[3, : 4 * bs] = np.arange(4 * bs)
    qpos = np.asarray([cols[0], cols[1], POS_SENTINEL, 4 * bs + 1], np.int32)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)
    kw = {k_: a[k_] for k_ in ("window", "latent_v") if k_ in a}
    if a.get("sink"):
        kw["sink"] = jnp.asarray(rng.normal(size=(Nkv * G,)), jnp.float32)
    return dict(
        q=normal(4, 1, Nkv * G, D), k_new=normal(4, 1, Nkv, D),
        v_new=normal(4, 1, Nkv, Dv) if Dv else None, k=k, v=v,
        table=jnp.asarray(table), cols=jnp.asarray(cols[:, None]),
        qpos=jnp.asarray(qpos[:, None]), kvpos=jnp.asarray(kvpos), kw=kw,
    )


def _scatter_then_attend(c, layer, valid, **more):
    """What a decode layer called before the fused op: ``write_block_kv``
    then the exact XLA attention."""
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_xla, write_block_kv,
    )

    k, v = write_block_kv(
        c["k"], c["v"], layer, c["table"], c["cols"], c["k_new"], c["v_new"],
        valid=valid,
    )
    return paged_attention_xla(
        c["q"], k, v, layer, c["table"], c["qpos"], c["kvpos"], **c["kw"],
        **more,
    ), k, v


def _close(got, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("valid", (None, True, False, "rows"))
@pytest.mark.parametrize("arena", sorted(_FUSED_ARENAS))
@pytest.mark.parametrize("layer", (0, LAYERS - 1))
def test_the_fused_decode_write_leaves_what_the_scatter_leaves(
    layer, arena, valid
):
    """``paged_attention_write`` on the kernel path (interpreted) against
    ``write_block_kv`` then ``paged_attention_xla``: both arenas bit for bit
    in every block a table can own, every other layer untouched, the
    output within the kernel's tolerance; under ``valid=False`` no owned
    block changes at all, and a gate a row steers only that row's entry."""
    from llm_sharding_tpu.ops import paged_attention as pa

    c = _fused_case(arena)
    gate = {
        None: None, True: jnp.asarray(True), False: jnp.asarray(False),
        "rows": jnp.asarray([[True], [False], [True], [True]]),
    }[valid]
    assert pa.decode_writes_in_kernel(1, False, False, "interpret")
    with mock.patch.object(
        pa, "write_block_kv", wraps=pa.write_block_kv
    ) as scatter:
        out, k, v, ks, vs = jax.jit(
            lambda k, v: pa.paged_attention_write(
                c["q"], c["k_new"], c["v_new"], k, v, layer, c["table"],
                c["cols"], c["qpos"], c["kvpos"], valid=gate,
                backend="interpret", **c["kw"],
            )
        )(c["k"], c["v"])
    assert scatter.call_count == 0 and ks is None and vs is None
    want, k_w, v_w = _scatter_then_attend(c, layer, gate)
    _close(out, want, c["k"].dtype)
    for before, a, w in zip((c["k"], c["v"]), (k, v), (k_w, v_w)):
        a, w = np.asarray(a), np.asarray(w)
        assert a.shape == w.shape == before.shape and a.dtype == w.dtype
        np.testing.assert_array_equal(a[:, 1:], w[:, 1:])
        others_untouched(before, a, layer)
        if valid is False:
            np.testing.assert_array_equal(
                a[:, 1:], np.asarray(before)[:, 1:]
            )
    if valid in (None, True):
        # the entries are where the table says: row 0's at slot 0 of its
        # fourth block, row 1's at the last slot of its second
        bs = c["k"].shape[3]
        for b, (blk, slot) in enumerate(((5, 0), (7, bs - 1))):
            np.testing.assert_array_equal(
                np.asarray(k)[layer, blk, :, slot],
                np.asarray(c["k_new"].astype(k.dtype))[b, 0],
            )


#: where a row's fresh slot lies in its block of 16 float32 tokens (two
#: sublane tiles of 8): the block's first and last column, and either side
#: of the tiles' edge
_FRESH_SLOTS = {"block_first": 0, "tile_last": 7, "tile_first": 8,
                "block_last": 15}


def _store_case(slot, live, gate, seed=61):
    """Four rows of a slot over blocks of 16 tokens and a table of 8 entries
    (ONE cell of eight blocks a row: what follows a row's frontier block in
    its cell names the trash block): the first ``live`` rows hold two full
    blocks and write at ``slot`` of their third, the others are dead (table
    all trash, no real query, column 0). ``gate``: ``write_block_kv``'s
    ``valid``."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    bs, T, NB, Nkv, G, D = 16, 8, 20, 2, 3, 8
    rng = np.random.default_rng(seed)
    k, v = make_stack(rng, NB, Nkv, bs, D)
    col = 2 * bs + slot
    table = np.zeros((4, T), np.int32)
    kvpos = np.full((4, T * bs), POS_SENTINEL, np.int32)
    for b in range(live):
        table[b, :3] = 1 + 3 * b + np.arange(3)
        kvpos[b, : col + 1] = np.arange(col + 1)
    alive = np.arange(4) < live
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        q=normal(4, 1, Nkv * G, D), k_new=normal(4, 1, Nkv, D),
        v_new=normal(4, 1, Nkv, D), k=k, v=v, table=jnp.asarray(table),
        cols=jnp.asarray(np.where(alive, col, 0)[:, None], jnp.int32),
        qpos=jnp.asarray(
            np.where(alive, col, POS_SENTINEL)[:, None], jnp.int32),
        kvpos=jnp.asarray(kvpos), kw={},
    ), {
        "open": None, "shut": jnp.asarray(False),
        "row_0_shut": jnp.asarray([[False], [True], [True], [True]]),
    }[gate]


@pytest.mark.parametrize("gate", ("open", "shut", "row_0_shut"))
@pytest.mark.parametrize("live", (1, 4))
@pytest.mark.parametrize("slot", sorted(_FRESH_SLOTS))
def test_the_decode_kernel_stores_what_it_attends(slot, live, gate):
    """The interpreted decode kernel with the write INSIDE
    (``paged_attention_write`` where ``decode_writes_in_kernel`` holds: ONE
    Pallas call) against the parent's form — ``write_block_kv``, then the
    attention: the output bit for bit the same kernel's over the scattered
    arena and within tolerance of the XLA path's, both arenas bit for bit
    over every block a table can own — with the fresh slot at a block's
    first and last column and on either side of a sublane tile's edge, one
    live row of four and all four, the gate open, shut (a ring stage's
    bubble microstep: no owned block changes) and shut for one row. The
    frontier block is followed in its cell by entries that name the trash
    block, and a dead row is one the walk skips: neither stores anything."""
    from llm_sharding_tpu.ops import paged_attention as pa

    c, valid = _store_case(_FRESH_SLOTS[slot], live, gate)
    layer = 2
    assert pa.decode_blocks_per_cell(8, 16, 2, 16, 4) == 8  # one cell a row
    fused = lambda k, v: pa.paged_attention_write(
        c["q"], c["k_new"], c["v_new"], k, v, layer, c["table"], c["cols"],
        c["qpos"], c["kvpos"], valid=valid, backend="interpret",
    )
    assert [e.params["name"] for e in _pallas_calls(
        jax.make_jaxpr(fused)(c["k"], c["v"]).jaxpr)] == ["paged_decode"]
    out, k, v, _, _ = jax.jit(fused)(c["k"], c["v"])
    want, k_w, v_w = _scatter_then_attend(c, layer, valid)
    _close(out, want, jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(
        pa.paged_attention(
            c["q"], k_w, v_w, layer, c["table"], c["qpos"], c["kvpos"],
            backend="interpret",
        )
    ))
    col = 2 * 16 + _FRESH_SLOTS[slot]
    for before, a, w, new in zip(
        (c["k"], c["v"]), (k, v), (k_w, v_w), (c["k_new"], c["v_new"])
    ):
        a, w, before = np.asarray(a), np.asarray(w), np.asarray(before)
        np.testing.assert_array_equal(a[:, 1:], w[:, 1:])
        others_untouched(before, a, layer)
        # nothing lands in the sink: the kernel stores owned entries only
        np.testing.assert_array_equal(a[:, 0], before[:, 0])
        for b in range(live):
            shut = gate == "shut" or (gate == "row_0_shut" and b == 0)
            blk = int(c["table"][b, 2])
            np.testing.assert_array_equal(
                a[layer, blk, :, col % 16],
                before[layer, blk, :, col % 16] if shut
                else np.asarray(new)[b, 0],
            )


def test_a_rows_fresh_column_lies_in_its_frontier_block():
    """What lets the decode kernel store the entry from the cell it ends a
    row's walk in: the step's ``kv_positions`` already hold the fresh
    column at the query's position, so ``_live_blocks``' frontier — the
    last owned entry holding a key position at or under the row's query
    position — IS the entry of the fresh column, ``cols // BS``, for every
    live row of a decode step as ``serve_chunk`` makes one (a slot's rows
    share their column; rows of unlike prompt lengths hold the sentinel
    between their prompt's end and it). Where a selection has masked the
    fresh key itself out, the kernel stretches the walk to that entry
    (``tests/test_keye_vl2.py``)."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.paged_attention import _live_blocks

    bs, T = 8, 6
    for col in (0, 7, 8, 23, 40, 47):
        # three rows: a prompt as long as the slot's column, a shorter one
        # (sentinels between its end and the column), a dead row
        kvpos = np.full((3, T * bs), POS_SENTINEL, np.int32)
        kvpos[0, :col] = np.arange(col)
        kvpos[1, : col // 2] = np.arange(col // 2)
        qpos = np.asarray([col, col // 2, POS_SENTINEL], np.int32)
        kvpos[np.arange(2), col] = qpos[:2]  # serve_chunk: the fresh column
        table = np.zeros((3, T), np.int32)
        table[:2, : col // bs + 1] = 1 + np.arange(2 * (col // bs + 1)).reshape(
            2, -1)
        nlive = np.asarray(_live_blocks(
            jnp.asarray(table), jnp.asarray(qpos[:, None]),
            jnp.asarray(kvpos)))
        np.testing.assert_array_equal(nlive, [col // bs + 1, col // bs + 1, 0])


def test_the_fused_decode_write_carries_the_arena_through_a_layer_scan():
    """Inside ``lax.scan`` over two layers with the arenas donated (the
    kernel's output aliased over its operand, as the step programs carry
    them): what two scatter-then-attend calls leave and return."""
    from llm_sharding_tpu.ops import paged_attention as pa

    c = _fused_case("gqa_4x7")
    layers = jnp.asarray([1, 2], jnp.int32)
    want, k_w, v_w = [], c["k"], c["v"]
    for l in (1, 2):
        o, k_w, v_w = _scatter_then_attend(dict(c, k=k_w, v=v_w), l, None)
        want.append(o)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(k, v):
        def one(carry, l):
            o, k, v, _, _ = pa.paged_attention_write(
                c["q"], c["k_new"], c["v_new"], *carry, l, c["table"],
                c["cols"], c["qpos"], c["kvpos"], backend="interpret",
            )
            return (k, v), o
        return jax.lax.scan(one, (k, v), layers)

    (k, v), out = run(c["k"] + 0, c["v"] + 0)
    _close(out, jnp.stack(want), jnp.float32)
    np.testing.assert_array_equal(np.asarray(k)[:, 1:], np.asarray(k_w)[:, 1:])
    np.testing.assert_array_equal(np.asarray(v)[:, 1:], np.asarray(v_w)[:, 1:])


@pytest.mark.parametrize(
    "case", ("two_entries", "int8_arena", "stats", "xla_backend")
)
def test_a_decode_write_the_kernel_cannot_take_is_the_scatter(case):
    """What the call can see decides the form: a verify's two entries a
    row, an int8 arena (its running scales), partial statistics (context
    parallel) and the XLA attention path write through ``write_block_kv``
    itself — the write kernel is not in their program — and return what
    the pair of calls returned before."""
    from llm_sharding_tpu.ops import paged_attention as pa

    c = _fused_case("gqa_4x7")
    more, scales, backend = {}, {}, "interpret"
    if case == "two_entries":
        rng = np.random.default_rng(3)
        wide = lambda x: jnp.concatenate(
            [x, jnp.asarray(rng.normal(size=x.shape), x.dtype)], axis=1)
        c.update(q=wide(c["q"]), k_new=wide(c["k_new"]),
                 v_new=wide(c["v_new"]),
                 cols=jnp.concatenate([c["cols"], c["cols"] + 1], axis=1),
                 qpos=jnp.concatenate([c["qpos"], c["qpos"]], axis=1))
        assert not pa.decode_writes_in_kernel(2, False, False, backend)
    elif case == "int8_arena":
        k8, v8, scales = int8_stack(np.random.default_rng(6), c["k"], c["v"])
        c.update(k=k8, v=v8)
        assert not pa.decode_writes_in_kernel(1, True, False, backend)
    elif case == "stats":
        more = {"stats": True}
        assert not pa.decode_writes_in_kernel(1, False, True, backend)
    else:
        backend = "xla"
        assert not pa.decode_writes_in_kernel(1, False, False, "xla")
    args = (c["table"], c["cols"], c["qpos"], c["kvpos"])
    with mock.patch.object(pa, "write_rows_tpu") as kernel, mock.patch.object(
        pa, "write_block_kv", wraps=pa.write_block_kv
    ) as scatter:
        out, k, v, ks, vs = pa.paged_attention_write(
            c["q"], c["k_new"], c["v_new"], c["k"], c["v"], 1, *args,
            backend=backend, **scales, **more,
        )
    assert kernel.call_count == 0 and scatter.call_count == 1
    wrote = pa.write_block_kv(
        c["k"], c["v"], 1, c["table"], c["cols"], c["k_new"], c["v_new"],
        **scales,
    )
    k_w, v_w, ks_w, vs_w = wrote if scales else (*wrote, None, None)
    want = pa.paged_attention(
        c["q"], k_w, v_w, 1, *args[:1], *args[2:], backend=backend,
        k_scale=ks_w, v_scale=vs_w, **more,
    )
    for a, w in zip(jax.tree.leaves((out, k, v, ks, vs)),
                    jax.tree.leaves((want, k_w, v_w, ks_w, vs_w))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_a_masked_layer_writes_to_its_own_trash_block():
    """A padding layer of a stage (``layer_mask`` False) runs the block
    and discards it: its entries must land in block 0 of ITS layer index —
    not layer 0's, not a block the table owns — and the hidden state must
    pass through as if the layer were not there."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    cfg = tiny_llama(num_hidden_layers=3)
    params = llama.init_params(cfg, jax.random.key(5), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    B, T, bs, NB = 2, 2, 4, 6
    Nkv, D = cfg.num_key_value_heads, cfg.head_dim_
    k, v = make_stack(rng, NB, Nkv, bs, D, L=3)
    tbl = jnp.asarray([[2, 3], [4, 0]], jnp.int32)
    cols = jnp.asarray([[5], [1]], jnp.int32)
    window_cols = np.arange(T * bs)[None]
    kvpos = jnp.asarray(
        np.where(window_cols <= np.asarray(cols), window_cols, POS_SENTINEL),
        jnp.int32,
    )
    h = jnp.asarray(rng.normal(size=(B, 1, cfg.hidden_size)), jnp.float32)

    def run(mask):
        return llama.forward_layers_paged(
            cfg, params["layers"], h, k, v, tbl, cols, kvpos, cols,
            layer_mask=jnp.asarray(mask), backend="xla",
        )

    h_all, k_all, v_all, *_ = run([True, True, True])
    h_m, k_m, v_m, *_ = run([True, False, True])
    for before, full, masked in ((k, k_all, k_m), (v, v_all, v_m)):
        before, full, masked = map(np.asarray, (before, full, masked))
        # the masked layer: owned blocks as they were, the trash written
        np.testing.assert_array_equal(masked[1, 1:], before[1, 1:])
        assert not np.array_equal(masked[1, 0], before[1, 0])
        assert not np.array_equal(full[1, 1:], before[1, 1:])
        # no other layer's trash was touched, and layer 0 wrote as ever
        np.testing.assert_array_equal(masked[[0, 2], 0], before[[0, 2], 0])
        np.testing.assert_array_equal(masked[0], full[0])
    # the hidden state skips the masked layer: layers 0 and 2 alone
    two = {
        n: jnp.stack([a[0], a[2]]) for n, a in params["layers"].items()
    }
    h_two, *_ = llama.forward_layers_paged(
        cfg, two, h, k[jnp.asarray([0, 2])], v[jnp.asarray([0, 2])], tbl,
        cols, kvpos, cols, backend="xla",
    )
    np.testing.assert_allclose(
        np.asarray(h_m), np.asarray(h_two), rtol=1e-6, atol=1e-6
    )
    assert np.abs(np.asarray(h_m) - np.asarray(h_all)).max() > 1e-3


def test_paged_attention_pallas_interpret_matches_xla():
    """The Pallas TPU kernel (interpret mode on CPU) == the XLA gather
    path: same online-softmax result over trash-padded ragged windows."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_tpu, paged_attention_xla,
    )

    rng = np.random.default_rng(7)
    B, T, bs, Nkv, G, D = 2, 3, 16, 2, 2, 32
    W, Nh = T * bs, Nkv * G
    NB = 8
    k_arena, v_arena = make_stack(rng, NB, Nkv, bs, D)
    tbl = np.array([[3, 5, 0], [7, 0, 0]], np.int32)
    lengths = [bs + 9, 4]
    kvpos = np.full((B, W), POS_SENTINEL, np.int32)
    for b in range(B):
        kvpos[b, : lengths[b]] = np.arange(lengths[b])
    q = jnp.asarray(rng.normal(size=(B, 1, Nh, D)), jnp.float32)
    qpos = jnp.asarray([[lengths[b]] for b in range(B)], jnp.int32)

    args = (q, k_arena, v_arena, 2, jnp.asarray(tbl), qpos,
            jnp.asarray(kvpos))
    want = paged_attention_xla(*args)
    got = paged_attention_tpu(*args, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-6
    )


def test_paged_attention_pallas_interpret_multiquery_matches_xla():
    """S > 1 queries per row — the serve_verify shape (K+1 draft
    positions): the kernel's GQA fold tiles the positions across the
    grouped query rows and the causal mask stays per-position."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_tpu, paged_attention_xla,
    )

    rng = np.random.default_rng(17)
    B, S, T, bs, Nkv, G, D = 2, 3, 3, 8, 2, 2, 16
    W, Nh = T * bs, Nkv * G
    NB = 8
    k_arena, v_arena = make_stack(rng, NB, Nkv, bs, D)
    tbl = np.array([[3, 5, 0], [7, 2, 0]], np.int32)
    lengths = [bs + 5, 11]  # committed prefix per row
    kvpos = np.full((B, W), POS_SENTINEL, np.int32)
    for b in range(B):
        # prefix + the S in-flight verify positions
        kvpos[b, : lengths[b] + S] = np.arange(lengths[b] + S)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), jnp.float32)
    qpos = jnp.asarray(
        [[lengths[b] + i for i in range(S)] for b in range(B)], jnp.int32
    )

    args = (q, k_arena, v_arena, 1, jnp.asarray(tbl), qpos,
            jnp.asarray(kvpos))
    want = paged_attention_xla(*args)
    got = paged_attention_tpu(*args, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-6
    )


def _frontier_case(seed, S, Nkv, kv_dtype, T=8, bs=4, rows="0123"):
    """Four rows in ONE call, over a stack of ``LAYERS`` different layers,
    each at the frontier its digit of ``rows`` names: 0 dead, 1 one block,
    2 a frontier inside a group of four blocks with a TRASH entry below it,
    3 the full table — every row's blocks drawn from one shuffle of the
    pool, so no two table entries are neighbours in the arena. A dead row is
    a finished row as each decode program leaves it: S = 1
    (``serve_chunk``) a real query position over a table the host remapped
    to trash; S > 1 (``serve_verify``) sentinel queries over a table still
    mapped. Returns the ops' positional arguments, the scale keywords, and
    the expected live blocks per row."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    rng = np.random.default_rng([seed, S, Nkv, kv_dtype == "int8"])
    G, D, B = 2, 16, 4
    Nh, W, NB = Nkv * G, T * bs, 4 * T + 1
    k_arena, v_arena = make_stack(rng, NB, Nkv, bs, D)
    scales = {}
    if kv_dtype != "bf16":
        k_arena, v_arena, scales = int8_stack(
            rng, k_arena, v_arena,
            jnp.int8 if kv_dtype == "int8" else jnp.float8_e4m3fn,
        )
    kinds = np.array([int(c) for c in rows])
    nlive = np.array([0, 1, 6, T])[kinds]
    # tokens in the window, the S in flight included (their KV is written
    # before the kernel runs)
    ctx = np.array([9, max(S, 2), 6 * bs - 1, T * bs])[kinds]
    ids = rng.permutation(np.arange(1, NB))
    tbl = np.zeros((B, T), np.int32)
    for b in range(B):
        # + a budget block
        mapped = T if kinds[b] == 3 else min(nlive[b] + 1, T)
        tbl[b, :mapped] = ids[b * T: b * T + mapped]
    tbl[kinds == 2, 2] = 0  # trash below the frontier
    cols = np.arange(W)[None]
    kvpos = np.where(cols < ctx[:, None], cols, int(POS_SENTINEL))
    qpos = (ctx - S)[:, None] + np.arange(S)[None]
    if S == 1:
        # finished, remapped to trash; its position stays real
        tbl[kinds == 0] = 0
    else:
        tbl[kinds == 0, :3] = ids[-3:]
        qpos[kinds == 0] = int(POS_SENTINEL)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), jnp.float32)
    args = (q, k_arena, v_arena, 1, jnp.asarray(tbl),
            jnp.asarray(qpos, jnp.int32), jnp.asarray(kvpos, jnp.int32))
    return args, scales, nlive


#: ``(S, Nkv, kv_dtype, rows)``: the four frontiers in the order the walk
#: was written for, at every fold and store; fp8 codes; then 0 / 1 / 2 / 4
#: live rows of the four in a shuffled order (the body finds the next live
#: row itself and starts ITS first cell's copies from the row before)
_WALK_CASES = [
    *[(S, Nkv, kv, "0123") for kv in ("bf16", "int8") for Nkv in (1, 4, 16)
      for S in (1, 3)],
    (1, 4, "fp8", "0123"), (3, 1, "fp8", "3120"),
    (1, 4, "bf16", "0000"), (3, 4, "bf16", "0000"), (1, 4, "bf16", "0020"),
    (1, 4, "bf16", "3002"), (3, 1, "int8", "2003"), (1, 16, "bf16", "2313"),
    (3, 4, "int8", "1232"), (1, 1, "bf16", "3210"),
]


@pytest.mark.parametrize(
    "S, Nkv, kv_dtype, rows", _WALK_CASES,
    ids=["-".join(map(str, c)) for c in _WALK_CASES],
)
def test_decode_walk_ends_at_each_rows_frontier(S, Nkv, kv_dtype, rows):
    """The decode kernel (interpret) walks each row to its written
    frontier and no further, all key/value heads of a block in one tile,
    every block fetched by the body's own copy out of a shuffled pool:
    rows at four frontiers in one call — dead, one block, inside a
    ``bps`` group with a trash entry below it, the full table — at S = 1
    and verify-shaped S = 3, ``Nkv`` 1 / 4 / 16, float, int8 and fp8
    arenas, and 0 / 1 / 2 / 4 of the four rows live in any order.
    ``_live_blocks`` reads the frontiers off the operands; live rows equal
    the XLA gather and the single-block walk; the dead row comes back
    zeros; and the cells the walk skips contribute NOTHING: a row's output
    is bit for bit that of the same call on a table cut off at the row's
    frontier cell."""
    from llm_sharding_tpu.ops import paged_attention as pa

    from llm_sharding_tpu.ops.quant import fp8_kv_supported

    if kv_dtype == "fp8" and not fp8_kv_supported():
        pytest.skip("no fp8 on this backend")
    args, scales, nlive = _frontier_case(5, S, Nkv, kv_dtype, rows=rows)
    q, ka, va, layer, tbl, qpos, kvpos = args
    bs = ka.shape[3]
    np.testing.assert_array_equal(
        np.asarray(pa._live_blocks(tbl, qpos, kvpos)), nlive
    )
    want = np.asarray(pa.paged_attention_xla(*args, **scales))
    single = np.asarray(pa.paged_attention_tpu(
        *args, interpret=True, blocks_per_step=1, **scales
    ))
    live = nlive > 0
    for bps in (4, 8):
        got = np.asarray(pa.paged_attention_tpu(
            *args, interpret=True, blocks_per_step=bps, **scales
        ))
        assert not got[~live].any()
        np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            got[live], single[live], rtol=2e-6, atol=2e-6
        )
        for b in np.flatnonzero(live) if bps == 4 else ():
            width = -(-nlive[b] // bps) * bps  # the frontier cell's end
            cut = np.asarray(pa.paged_attention_tpu(
                q, ka, va, layer, tbl[:, :width], qpos,
                kvpos[:, : width * bs], interpret=True,
                blocks_per_step=bps, **scales,
            ))
            np.testing.assert_array_equal(got[b], cut[b])
    # S = 1: the dead row attends zeros on the XLA path too
    if S == 1:
        assert not want[~live].any()


@pytest.mark.parametrize("S, Nkv, kv_dtype, rows", [
    (1, 4, "bf16", "0123"), (3, 1, "int8", "3231"), (1, 16, "bf16", "2013"),
])
def test_a_wider_cell_folds_its_tiles_eight_at_a_time(S, Nkv, kv_dtype, rows):
    """A cell of 16 or 32 blocks gives bit for bit what cells of 8 give:
    its score tiles fold into the running softmax ``FOLD_TILES`` at a time
    (what the vector registers hold), so a cell's width — what the shapes
    allow, 8 blocks when a block was an operand — changes who copies a
    block and when, never a bit of a row's output or a token a model
    serves."""
    from llm_sharding_tpu.ops import paged_attention as pa

    assert pa.FOLD_TILES == 8
    args, scales, nlive = _frontier_case(
        11, S, Nkv, kv_dtype, T=32, rows=rows)
    eight = np.asarray(pa.paged_attention_tpu(
        *args, interpret=True, blocks_per_step=8, **scales))
    assert np.abs(eight[nlive > 0]).min() > 0
    for bps in (16, 32):
        wide = np.asarray(pa.paged_attention_tpu(
            *args, interpret=True, blocks_per_step=bps, **scales))
        np.testing.assert_array_equal(wide, eight)


@pytest.mark.parametrize("layer", LAYER_CASES)
@pytest.mark.parametrize("kernel", ("decode", "prefill"))
@pytest.mark.parametrize("kv_dtype", ("bf16", "int8"))
def test_kernels_read_the_layer_they_are_given(kv_dtype, kernel, layer):
    """Both Pallas kernels (interpret) against the XLA gather on a stack of
    ``LAYERS`` layers with DIFFERENT contents in each, at the first, a
    middle and the last layer, over a bf16 and an int8 arena: the layer
    index rides as a scalar-prefetch operand read by every arena and scale
    index map, and a kernel that always read layer 0 passes every
    single-layer case. The XLA side is held to plain numpy indexing by
    ``test_paged_attention_xla_matches_dense``. Then a WRITE at that layer
    — the scatter into the stack — must leave every other layer's bytes
    (codes and scales) untouched, and the kernel must see the entry."""
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops import paged_attention as pa

    rng = np.random.default_rng([23, layer, kernel == "prefill"])
    B, T, bs, Nkv, G, D = 2, 4, 8, 2, 2, 16
    S = 1 if kernel == "decode" else 6
    W, Nh, NB = T * bs, Nkv * G, 9
    dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32
    k_arena, v_arena = make_stack(rng, NB, Nkv, bs, D, dt)
    scales = {}
    if kv_dtype == "int8":
        k_arena, v_arena, scales = int8_stack(rng, k_arena, v_arena)
    tbl = jnp.asarray([[3, 5, 8, 0], [7, 2, 0, 0]], jnp.int32)
    lengths = np.array([2 * bs + 3, bs + 1])  # context behind the queries
    cols = np.arange(W)[None]
    kvpos = jnp.asarray(np.where(
        cols < (lengths + S)[:, None], cols, int(POS_SENTINEL)
    ), jnp.int32)
    qpos = jnp.asarray(lengths[:, None] + np.arange(S)[None], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), dt)

    def both(k_a, v_a, sc):
        args = (q, k_a, v_a, layer, tbl, qpos, kvpos)
        if kernel == "decode":
            got = pa.paged_attention(*args, backend="interpret", **sc)
        else:
            got = pa.paged_prefill(
                *args, backend="interpret", **sc,
                nlive=jnp.asarray(-(-(lengths + S) // bs), jnp.int32),
            )
        return (np.asarray(got, np.float32),
                np.asarray(pa.paged_attention_xla(*args, **sc), np.float32))

    tol = 2e-2 if kv_dtype == "bf16" else 2e-5
    got, want = both(k_arena, v_arena, scales)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    # the read depends on the layer: the same call one layer over differs
    other = (layer + 1) % LAYERS
    far = np.asarray(pa.paged_attention_xla(
        q, k_arena, v_arena, other, tbl, qpos, kvpos, **scales
    ), np.float32)
    assert np.abs(far - want).max() > 0.05

    # the write at ``layer``: the queries' own entries, large enough to
    # move the output
    wcols = jnp.asarray(lengths[:, None] + np.arange(S)[None], jnp.int32)
    kn = jnp.asarray(3.0 * rng.normal(size=(B, S, Nkv, D)), dt)
    vn = jnp.asarray(3.0 * rng.normal(size=(B, S, Nkv, D)), dt)
    out = pa.write_block_kv(
        k_arena, v_arena, layer, tbl, wcols, kn, vn, **scales
    )
    for before, after in zip(
        (k_arena, v_arena, *scales.values()), out
    ):
        others_untouched(before, after, layer)
        assert not np.array_equal(
            np.asarray(after)[layer], np.asarray(before)[layer]
        )
    sc2 = dict(zip(scales, out[2:]))
    got2, want2 = both(out[0], out[1], sc2)
    np.testing.assert_allclose(got2, want2, atol=tol, rtol=tol)
    assert np.abs(want2 - want).max() > 0.05


# ------------------------------------------------- kernel serve-path wiring


def test_paged_attn_kwarg_validation(setup):
    _, eng = setup
    with pytest.raises(ValueError, match="auto, kernel or xla"):
        eng.serve(capacity=64, paged_attn="pallas", **paged_kw())
    with pytest.raises(ValueError, match="only meaningful"):
        eng.serve(capacity=64, paged_attn="xla")  # dense server
    # explicit kernel on the CPU mesh: curated, at construction
    with pytest.raises(ValueError, match="TPU backend"):
        eng.serve(capacity=64, paged_attn="kernel", **paged_kw())


def test_kernel_rules_learned_from_the_v5e_compiler(monkeypatch):
    """What Mosaic refused during bring-up stays refused — or repaired.

    Shape rule: the scalar-prefetched block table, and the decode kernel's
    two entries a row beside it, must fit scalar memory: ``[128, 2048]``
    and ``[124, 2048]`` int32 "exceeded smem capacity" (by 1.6K), ``[120,
    2048]`` compiles since the walk is a loop in the body and no longer an
    entry a cell (with it, PR 28 to PR 53, it exceeded by 62.1K); ``[2000,
    33]`` (an odd width: rows pad to 128 entries) is held ineligible with
    16 KiB to spare, ``[1500, 33]`` and ``[1900, 33]`` compile, and the
    number of key/value heads no longer counts — ``[100, 2048]`` compiles
    at 32 (AOT compiles of ``paged_attention_tpu`` for a described v5e;
    PERF.md, PR 28 and PR 54). Repairs: a
    ``kv_positions`` tile that is neither 128 lanes wide nor the whole
    window (odd table width at block 16) and the int8/fp8 scale operand
    (a ``(1, 1)`` block of ``[NB, Nkv]``) now lower for the TPU platform —
    the block-shape check runs at lowering, so the CPU can hold the line.
    The operands are the layer-stacked head-major pool and the layer index
    (``test_kernels_compile_for_a_described_v5e`` runs Mosaic itself)."""
    from llm_sharding_tpu.ops.paged_attention import (
        kernel_eligible, paged_attention_tpu, paged_prefill_tpu,
    )

    ok = dict(head_dim=128, block_size=16, cache_dtype=jnp.bfloat16,
              kv_heads=4)
    assert not kernel_eligible(**ok, rows=128, table_width=2048)
    assert not kernel_eligible(**ok, rows=124, table_width=2048)
    assert kernel_eligible(**ok, rows=120, table_width=2048)
    assert kernel_eligible(**ok, rows=104, table_width=2048)
    assert not kernel_eligible(**ok, rows=2000, table_width=33)
    assert kernel_eligible(**ok, rows=1900, table_width=33)
    assert kernel_eligible(**ok, rows=1500, table_width=33)
    assert not kernel_eligible(**ok, rows=4000, table_width=33)
    # the walk is the body's: neither the heads a block nor the store count
    assert kernel_eligible(**{**ok, "kv_heads": 32}, rows=100,
                           table_width=2048)
    assert kernel_eligible(**{**ok, "block_size": 32,
                              "cache_dtype": jnp.int8},
                           rows=104, table_width=2048)

    S = jax.ShapeDtypeStruct
    B, Nh, Nkv, D, NB, Lp = 4, 28, 4, 128, 64, 3  # G = 7: Qwen2.5-7B's fold
    for fn, Sq in ((paged_attention_tpu, 1), (paged_prefill_tpu, 128)):
        for store, block, T in ((jnp.bfloat16, 16, 33), (jnp.int8, 32, 32)):
            quant = store == jnp.int8
            arena = S((Lp, NB, Nkv, block, D), store)
            scale = S((Lp, NB, Nkv), jnp.float32) if quant else None
            jax.jit(
                lambda q, k, v, l, t, qp, kp, ks, vs, fn=fn: fn(
                    q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
                )
            ).trace(
                S((B, Sq, Nh, D), jnp.bfloat16), arena, arena,
                S((), jnp.int32), S((B, T), jnp.int32),
                S((B, Sq), jnp.int32), S((B, T * block), jnp.int32),
                scale, scale,
            ).lower(lowering_platforms=("tpu",))

    # --paged-attn kernel fails at construction, by name, never mid-serve
    cfg = tiny_llama(num_hidden_layers=2, head_dim=128)
    eng = PipelineEngine(
        cfg, llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32),
        num_stages=1, cache_dtype=jnp.float32,
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"block table \[128, 2048\].*scalar"):
        eng.serve(
            capacity=32768, batch_per_slot=128, kv_block_size=16,
            kv_blocks=4097, paged_attn="kernel",
        )


#: Both benchmark cells' kernel shapes (``benchmark/configs/*.json``): 4
#: rows x 128 table entries of 32-token blocks, head 128; 28 q / 4 kv heads
#: (Qwen2.5-7B, one chip) and 40 / 8 (Qwen2.5-14B, a stage of the ring);
#: decode (S = 1) and a 256-token prefill chunk. The stack is cut to 3
#: layers x 260 blocks: the kernels' tiles do not depend on either.
#: OLMoE-1B-7B is plain MHA: 16 key/value heads and a query tile of G = 1.
_CELL_SHAPES = {"qwen25_7b": (28, 4), "qwen25_14b_pp4": (40, 8),
                "olmoe_1b_7b": (16, 16)}


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a DESCRIBED v5e host: the TPU's compiler is
    installed, no chip is attached. Described here, inside a fixture of
    this one file (never at import: only one process may load the TPU's
    library)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    """One chip of that host, as the sharding of a single-chip program."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


@pytest.mark.parametrize("store", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["paged_decode", "paged_prefill"])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_kernels_compile_for_a_described_v5e(v5e_chip, cell, kernel, store):
    """The TPU's own compiler (Mosaic included) accepts both kernels with
    the 5-D stacked operands — a squeezed layer dim, the ``(BS, D)`` tile at
    ``(layer, table[b, t], head)`` — and the layer index as one more
    scalar-prefetch operand, at both benchmark cells' shapes, over bf16 and
    int8 arenas. No chip: the topology is described (``v5e:2x2``), the
    compile is real, nothing runs."""
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_tpu, paged_prefill_tpu,
    )

    Nh, Nkv = _CELL_SHAPES[cell]
    B, T, BS, D, Lp, NB = 4, 128, 32, 128, 3, 260
    Sq, fn = {
        "paged_decode": (1, paged_attention_tpu),
        "paged_prefill": (256, paged_prefill_tpu),
    }[kernel]
    dt = jnp.bfloat16 if store == "bf16" else jnp.int8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    arena = S((Lp, NB, Nkv, BS, D), dt)
    scale = S((Lp, NB, Nkv), jnp.float32) if store == "int8" else None
    # conftest asks every matmul for "highest" precision (CPU oracles);
    # the chip runs the default, and Mosaic refuses an fp32 contraction of
    # bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda q, k, v, l, t, qp, kp, ks, vs: fn(
                q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
            )
        ).lower(
            S((B, Sq, Nh, D), jnp.bfloat16), arena, arena, S((), jnp.int32),
            S((B, T), jnp.int32), S((B, Sq), jnp.int32),
            S((B, T * BS), jnp.int32), scale, scale,
        ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and kernel in text
    # the pool goes to the kernel as it lies: no copy or transpose of an
    # arena-sized operand beside the custom call
    arena_elems = Lp * NB * Nkv * BS * D
    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < arena_elems


#: a chunk's write at the cells' arena entries: key/value heads, the lanes
#: of a stored key and of a value (0: the latent arena holds none; MiMo's
#: window layers store a key of 192 in 256 lanes beside a value of 128)
_CHUNK_WRITE_SHAPES = {
    "olmoe_1b_7b": (16, 128, 128), "qwen25_7b": (4, 128, 128),
    "gigachat31_702b_a36b": (1, 640, 0), "mimo_v25_swa": (8, 256, 128),
}


@pytest.mark.parametrize("cell", sorted(_CHUNK_WRITE_SHAPES))
def test_a_chunks_tile_write_leaves_the_carried_stack_where_it_lies(
        v5e_chip, cell):
    """``write_chunk_kv`` inside a scan that carries both arenas, as the
    layer scan does, compiled for the described v5e: the only operations
    whose result is as large as an arena are the scatters themselves — no
    copy, transpose or select of the stack (what a scatter with a
    non-contiguous window costs: ``write_block_kv``'s note) — and the
    program's temporaries stay far under one arena."""
    from llm_sharding_tpu.ops.paged_attention import write_chunk_kv

    Nkv, Dk, Dv = _CHUNK_WRITE_SHAPES[cell]
    B, Sc, BS, T, Lp, NB = 4, 256, 32, 128, 3, 260
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )

    def run(k_arena, v_arena, table, col0, k_new, v_new, valid):
        def one(carry, layer):
            return write_chunk_kv(
                *carry, layer, table, col0, k_new, v_new, valid=valid
            ), None
        return jax.lax.scan(
            one, (k_arena, v_arena), jnp.arange(Lp, dtype=jnp.int32)
        )[0]

    compiled = jax.jit(run, donate_argnums=(0, 1)).lower(
        S((Lp, NB, Nkv, BS, Dk), jnp.bfloat16),
        S((Lp, NB, Nkv, BS, Dv), jnp.bfloat16), S((B, T), jnp.int32),
        S((), jnp.int32), S((B, Sc, Nkv, Dk), jnp.bfloat16),
        S((B, Sc, Nkv, Dv), jnp.bfloat16), S((), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    arena_elems = Lp * NB * Nkv * BS * min(d for d in (Dk, Dv) if d)
    big = [
        # an arena-sized fusion is the scatter's own (in place), no loop
        "scatter" if m.group(2) == "fusion" and "kind=kCustom" in m.group(0)
        else m.group(2)
        for m in re.finditer(
            r"= \w+\[([\d,]+)\][^ ]* ([\w-]+)\(.*", text)
        if np.prod([int(x) for x in m.group(1).split(",")]) >= arena_elems
    ]
    assert "scatter" in big
    assert set(big) <= {
        "scatter", "parameter", "get-tuple-element", "bitcast", "while",
        "tuple",
    }, sorted(set(big))
    assert compiled.memory_analysis().temp_size_in_bytes < arena_elems // 4


@pytest.mark.parametrize("walk", ["built_in_the_op", "handed_in"])
@pytest.mark.parametrize(
    "cell", sorted(_CELL_SHAPES) + ["gigachat31_702b_a36b"]
)
def test_the_prefill_kernel_has_one_grid_axis_of_traced_length(
        v5e_chip, cell, walk):
    """All four configurations' chunk shapes (the latent one: 64 heads over
    one latent head of 640 lanes, values its first 512) compile for the
    described v5e with ONE grid axis whose bound is a traced scalar — the
    walk's length, no shape of the program — whether the op builds the
    walk or ``serve_prefill_chunk`` hands it in."""
    from llm_sharding_tpu.ops.paged_attention import (
        paged_prefill_tpu, prefill_walk,
    )

    Nh, Nkv, D, lv = {**{k: (*v, 128, 0) for k, v in _CELL_SHAPES.items()},
                      "gigachat31_702b_a36b": (64, 1, 640, 512)}[cell]
    B, T, BS, Lp, NB, Sq = 4, 128, 32, 3, 260, 256
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )

    def fn(q, k, v, l, t, qp, kp):
        w = prefill_walk(t, qp, kp, q_heads=Nh, kv_heads=Nkv)
        return paged_prefill_tpu(
            q, k, v, l, t, qp, kp, latent_v=lv,
            walk=w if walk == "handed_in" else None,
        )

    with jax.default_matmul_precision("default"):
        lowered = jax.jit(fn).lower(
            S((B, Sq, Nh, D), jnp.bfloat16),
            S((Lp, NB, Nkv, BS, D), jnp.bfloat16),
            S((Lp, NB, Nkv, BS, 0 if lv else D), jnp.bfloat16),
            S((), jnp.int32), S((B, T), jnp.int32), S((B, Sq), jnp.int32),
            S((B, T * BS), jnp.int32),
        )
        text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_prefill" in text
    # one grid axis, its bound no constant of the kernel (Mosaic writes a
    # dynamic bound as the least int64): handed to it at run time
    import base64
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    body = re.search(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', lowered.as_text()
    ).group(1)
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        kernel = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False
        )
    bounds = re.findall(r"iteration_bounds = array<i64: ([^>]*)>", kernel)
    assert bounds == [str(-2**63)]


def test_the_prefill_walk_counts_against_scalar_memory():
    """``kernel_eligible`` holds the table AND the prefill kernel's walk
    (an entry per cell of every row, head and query tile) to the scalar
    memory the v5e has: the benchmark's geometries fit with room, a slot of
    64 rows of Qwen2.5-7B at a 32k capacity does not."""
    from llm_sharding_tpu.ops.paged_attention import (
        kernel_eligible, prefill_query_tiles,
    )

    assert prefill_query_tiles(7, 256) == 7  # Qwen2.5-7B: a tile a group
    assert prefill_query_tiles(1, 256) == 1  # MHA
    assert prefill_query_tiles(64, 256) == 64  # absorbed latent attention
    assert prefill_query_tiles(2, 16) == 1  # a chunk under the tile
    ok = dict(head_dim=128, block_size=32, cache_dtype=jnp.bfloat16)
    for kv, tiles in ((4, 7), (8, 5), (16, 1), (1, 64)):
        assert kernel_eligible(**ok, rows=4, table_width=128, kv_heads=kv,
                               prefill_tiles=tiles)
    big = dict(rows=64, table_width=1024, kv_heads=4)
    assert kernel_eligible(**ok, **big)  # the decode walk alone fits
    assert not kernel_eligible(**ok, **big, prefill_tiles=7)


_HLO_BYTES = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4}


def _weight_stack_relayouts(text, floor=16 << 20):
    """The ``copy`` instructions of a compiled program that re-lay a weight
    out: the operand a ``stage_layers`` / ``head_params`` parameter (by the
    name jax gave it), the result above ``floor`` bytes, the result's
    minor-to-major order another than its operand's. A prefetch into
    another memory (the same order, an ``S(1)`` suffix) is a move and is
    not returned."""
    order = dict(re.findall(
        r"(%[\w.\-]+) = \w+\[[\d,]*\]\{([\d,]*)", text
    ))
    found = []
    for m in re.finditer(
        r"(%[\w.\-]+) = (\w+)\[([\d,]*)\]\{([\d,]*)[^ ]* "
        r"copy\((%[\w.\-]+)\)[^\n]*"
        r"op_name=\"(?:stage_layers|head_params)[^\n]*", text,
    ):
        name, dtype, shape, minor_to_major, operand = m.groups()
        size = _HLO_BYTES.get(dtype, 4) * int(
            np.prod([int(x) for x in shape.split(",")])
        )
        if size > floor and order.get(operand) != minor_to_major:
            found.append(m.group(0)[:160])
    return found


def _arena_ops(text, floor=4 << 20):
    """The instructions of a compiled program, kernels aside, whose result is
    a whole K/V arena: bf16, ``[..., NB, Nkv, 32, D]`` with the layer (and
    the stage) in front, ``floor`` elements or more — a scatter into the
    carried stack, a copy of it, a move of it into another memory (a
    ``copy-start``'s result is a tuple that begins with the copy). Returns
    ``[(operation, dims)]``."""
    found = []
    for m in re.finditer(
        r"%[\w.\-]+ = \(?bf16\[([\d,]+)\][^\n]*? "
        r"(copy|copy-start|scatter|dynamic-update-slice|fusion|select)\(",
        text,
    ):
        dims = [int(x) for x in m.group(1).split(",")]
        if len(dims) in (5, 6) and dims[-2] == 32 and np.prod(dims) >= floor:
            found.append((m.group(2), tuple(dims)))
    return found


def _windowed_projections(text):
    """The ``qkv`` dots of a compiled program, and those of them the
    compiler wrote as a convolution over a window wider than 1 (the head
    axis as a spatial dim: the form that wants its weights input-minor)."""
    dots = [
        l for l in text.splitlines()
        if " convolution(" in l and "/qkv/dot_general" in l
    ]
    windowed = [
        l.strip()[:200] for l in dots
        if any(
            int(n) > 1 for w in re.findall(r"window=\{size=([\dx]+)", l)
            for n in w.split("x")
        )
    ]
    return dots, windowed


@pytest.fixture(scope="module")
def compiled_serve_chunk(v5e_host):
    """``text(cell)``: the compiled text of a benchmark configuration's
    ``serve_chunk`` at its real geometry for the described v5e
    (``benchmark/aot_check.py`` builds the abstract inputs; the ring takes
    four chips). Compiled once a cell, for the tests of this file."""
    from benchmark import aot_check
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    texts = {}

    def text(cell):
        if cell not in texts:
            path = os.path.join(aot_check.HERE, "configs", cell + ".json")
            with open(path) as f:
                cfg_file = json.load(f)
            stages = int(cfg_file["deployment"]["num_stages"])
            mesh = pipeline_mesh(stages, v5e_host[:stages])
            # conftest's "highest" matmul precision is the CPU oracles'; the
            # program asks jax.default_backend() which attention to lower
            with jax.default_matmul_precision("default"), mock.patch.object(
                jax, "default_backend", lambda: "tpu"
            ):
                name, lowered = next(aot_check.programs(cfg_file, mesh))
            assert name == "serve_chunk"
            texts[cell] = lowered.compile().as_text()
        return texts[cell]

    return text


@pytest.mark.parametrize(
    "cell", sorted(_CELL_SHAPES) + [
        "gigachat31_702b_a36b", "nemotron3_super_120b_a12b",
        "keye_vl2_30b_a3b", "longcat_flash_omni", "ouro_2p6b"])
def test_a_decode_step_reads_its_weights_as_they_are_stored(
        compiled_serve_chunk, cell):
    """The compiled ``serve_chunk`` of each benchmark configuration, at its
    real geometry for the described v5e (``benchmark/aot_check.py`` builds
    the abstract inputs; the ring takes four chips), consumes every weight
    stack in the layout it is stored in: no re-laying ``copy`` of a
    parameter above 16 MiB, inside or outside the layer loop, and the k
    and v projections plain dots like q's. Before the projection's edge
    was held (``models/llama.py::attn_mlp_block``) XLA folded the head
    split into the two small dots and transposed the whole ``wk`` / ``wv``
    stacks at the top of every call: 0.25-0.27 ms of a decode step on the
    chip (``PERF.md``, PR 31). Nor does any operation but a kernel produce
    an arena (PR 46: the two scatters a layer of a step's fresh K/V went,
    and with them what XLA copied around them). Latent attention (PR 34) met the same twice
    (``wq_b``'s head split, held the same way) and once from the STORED side:
    a ``[H, 576]`` weight is not whole lane tiles, the chip keeps it
    input-minor, and the stack of ``wkv_a`` was re-laid every call until the
    leaf was padded to the arena entry's 640 columns; ``longcat_flash`` (PR
    57) runs that attention TWICE a layer over leaves with a ``_0`` / ``_1``
    suffix — the same edges, twice; ``ouro`` (PR 60), the llama block with NO
    bias and no q norm, met it on ``wq`` (nothing stood between the dot and
    the head split: 403 MB re-laid a call and a layer's slice copied before
    its dot, 3.6 ms of a 33.4 ms step on the chip, until q left the projection
    through the same edge). Nothing runs: a compile is not a time."""
    text = compiled_serve_chunk(cell)
    assert _weight_stack_relayouts(text) == []
    dots, windowed = _windowed_projections(text)
    assert len(dots) >= 3 and windowed == []
    # and writes its arena where it lies (PR 46): no operation of the
    # program but a kernel produces an arena — no scatter into the carried
    # stack, no copy or staging of it around one. Since PR 61 that kernel is
    # the attention's own (``paged_decode`` stores the step's fresh K/V from
    # its frontier cell, each arena aliased over itself): the write kernel
    # ``paged_kv_write`` is gone from every program but Keye's, whose index
    # arena it still feeds (the score call reads it before the attention)
    writes = text.count("paged_kv_write/pallas_call")
    assert writes == (1 if cell == "keye_vl2_30b_a3b" else 0)
    decodes = [
        ln for ln in text.split("\n")
        if "tpu_custom_call" in ln and "paged_decode/pallas_call" in ln]
    assert decodes and all(
        "output_to_operand_aliasing" in ln for ln in decodes), decodes
    assert _arena_ops(text) == []


def test_a_selecting_decode_step_reads_k_and_v_through_a_kernel_only(
        compiled_serve_chunk):
    """``keye_vl2_30b_a3b``'s compiled ``serve_chunk`` (PR 50): the selection
    reaches the attention as key positions, so nothing but a kernel reads the
    K or V arena — no ``gather`` has an arena, or a reshape of one, for its
    operand (the parent gathered the 2,048 chosen tokens' rows out of the
    flattened pools, 16,384 rows a layer call: 34% of its step on the chip) —
    and the decode kernel appears ONCE in the layer body, outside the
    ``cond`` that chooses the key positions (a score kernel and a top-k on
    one side, the positions as they are on the other), not once a branch."""
    text = compiled_serve_chunk("keye_vl2_30b_a3b")
    shape = {
        name: [int(x) for x in dims.split(",") if x]
        for name, dims in re.findall(r"(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", text)
    }
    gathers = re.findall(
        r"= (\w+)\[([\d,]*)\][^\n]*? gather\((%[\w.\-]+), ", text)
    assert gathers  # the embedding's rows, the experts' order
    for dtype, dims, operand in gathers:
        # an arena (or a flat view of one) holds 12 layers x 2305 blocks x 4
        # heads x 32 tokens of 128: 453 M elements; the largest operand of a
        # gather here is the embedding table's 78 M
        assert int(np.prod(shape.get(operand, [0]) or [1])) < 100 << 20, (
            dtype, dims, operand)
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]+)"', text)
    decode = [k for k in kernels if k.endswith("paged_decode/pallas_call")]
    assert len(decode) == 1 and "/cond/" not in decode[0], kernels
    scores = [k for k in kernels if k.endswith("index_scores/pallas_call")]
    assert len(scores) == 1 and "/cond/branch_1_fun/" in scores[0], kernels
    # and the score kernel takes the layer-stacked index arena WHOLE, once
    # (PR 56: it copies a block by hand; a block was an operand, the arena
    # eight times over)
    (call,) = [
        ln for ln in text.split("\n")
        if "tpu_custom_call" in ln and "index_scores/pallas_call" in ln]
    operands = call.split("operand_layout_constraints=")[1].split("}}")[0]
    assert re.findall(r"\w+\[(?:\d+,){4}\d+\]", operands) == [
        "bf16[12,2305,1,32,128]"], operands


def _called_from(text, root):
    """The instructions of computation ``root`` and of every computation it
    calls (fusions, reductions, branches, loops)."""
    comps = {
        m.group(1): m.group(2).split("\n") for m in re.finditer(
            r"\n(?:ENTRY )?(%[\w.\-]+) [^\n]*\{\n(.*?)\n\}", text, re.S)
    }
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [ref.strip() for ref in group.split(",")]
    return [line for name in seen for line in comps[name]]


def _elements(line):
    dims = re.search(r"= \(?\w+\[([\d,]*)\]", line)
    return int(np.prod([int(d) for d in dims.group(1).split(",") if d] or [1]))


def test_a_selecting_decode_step_finds_its_topk_th_score_without_a_sort(
        compiled_serve_chunk):
    """``keye_vl2_30b_a3b``'s compiled ``serve_chunk`` (PR 51): nothing of
    the selecting branch of the layer body sorts or scans — ``select_mask``
    finds the ``topk``-th score by a search, the kernel ``select_topk`` for a
    decode step's slot since PR 58 (the parent's ``lax.top_k`` over
    the slot's ``[4, 9216]`` scores was 70 us a layer call on the chip, the
    largest device operation of a step, and its tie rule's ``cumsum`` 6.5 us
    more OUTSIDE every scope: the compiler's ``reduce-window`` rewrite drops
    the metadata) — and all of the branch lies under ``indexer`` or
    ``select``, the scopes ``decode_index_pct`` and ``index_hbm_pct`` divide
    by: by name where an instruction has one, and no instruction without one
    makes an array as wide as the window."""
    text = compiled_serve_chunk("keye_vl2_30b_a3b")
    lines = text.split("\n")
    # what sorts is the router's top-k and the experts' order
    sorts = [ln for ln in lines if " sort(" in ln or "TopK" in ln]
    assert sorts and all(
        re.search(r'op_name="[^"]*/(router|moe)/', ln) for ln in sorts), sorts
    # nothing scans: the kernels of a decode step walk the slot's rows in
    # their bodies (the score kernel laid its rows end to end until PR 56)
    scans = [ln for ln in lines if " reduce-window(" in ln]
    assert all(_elements(ln) <= 4 for ln in scans), scans
    # the layer's cond: the score kernel lies in its branch 1
    (branch,) = [
        ref.split(",")[1].strip() for ln in lines
        for ref in re.findall(r"branch_computations=\{([^}]*)\}", ln)
        if "cond/branch_1_fun" not in ln
    ]
    chosen = _called_from(text, branch)
    assert any("index_scores/pallas_call" in ln for ln in chosen)
    named = [ln for ln in chosen if "/cond/branch_1_fun/" in ln]
    searched = [ln for ln in named if "/select/" in ln]
    # the search is ONE Pallas call since PR 58 (a slot's four queries): its
    # passes — candidates compared, the hits counted — are turns of a loop in
    # the kernel's body, none of them an XLA reduction of its own any more
    assert sum("select_topk/pallas_call" in ln for ln in searched) == 1
    assert not [ln for ln in searched if " reduce(" in ln]
    for ln in named:
        assert re.search(r'op_name="[^"]*/(select|indexer)/', ln), ln
    for ln in chosen:
        if "op_name=" not in ln and _elements(ln) >= 9216:
            # (the positions leave the branch's fast memory by an async copy
            # since the decode kernel takes them as a lane row a cell)
            assert re.search(
                r" (parameter|get-tuple-element|bitcast|tuple|copy"
                r"|copy-start|copy-done)\(", ln), ln


@pytest.mark.parametrize("cell", ["qwen25_7b", "olmoe_1b_7b"])
def test_a_model_without_an_indexer_traces_nothing_of_the_selection(
        compiled_serve_chunk, cell):
    """No operation of a configuration without ``sparse_attn`` lies under the
    ``select`` or the ``indexer`` scope (by the scope, not by the word
    ``sort``: a router's own ``top_k`` is not the selection's)."""
    names = re.findall(r'op_name="([^"]*)"', compiled_serve_chunk(cell))
    assert len(names) > 100
    assert not [n for n in names if re.search(r"/(select|indexer)/", n)]


def test_a_windowed_models_step_programs_compile_and_read_weights_as_stored(
        v5e_host):
    """``mimo_v25`` (a KV state per kind of attention layer, which
    ``aot_check.py`` cannot describe: ``benchmark/tests/aot_windowed.py``
    makes the state as the server does): the decode program and the chunked
    prefill compile for the described v5e — both paged kernels with a lower
    bound on their walk, a sink operand, keys of 256 lanes and values of 128
    — and the decode step re-lays no weight stack: the fused qkv projection
    leaves its dot through a barrier, and the runs take each layer out of its
    kind's stack inside the scan."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "aot_windowed", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "tests",
            "aot_windowed.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with jax.default_matmul_precision("default"):
        texts = mod.check("mimo_v25", chunks=(256,), texts=True)
    decode = texts["serve_chunk"]
    assert _weight_stack_relayouts(decode) == []
    dots, windowed = _windowed_projections(decode)
    assert len(dots) >= 3 and windowed == []
    # five runs of one kind: ONE attention kernel each (it stores the step's
    # fresh K/V itself since PR 61: the write kernel before it is gone), an
    # expert kernel in four
    assert decode.count("tpu_custom_call") == 9
    assert "paged_kv_write" not in decode
    assert decode.count("paged_decode/pallas_call") >= 5
    assert "paged_prefill" in texts["serve_prefill_chunk[256]"]
    # a decode step's fresh K/V lands inside the attention kernel: XLA scatters
    # into no arena. What is left is its own choice of memory for a SMALL
    # array the loop carries: the window layers' 31 MB value arena moves
    # into fast memory before the step's loops and back after them, once a
    # step, as it did around the scatters (40 + 3 us of a 2.9 ms step on
    # the chip: PERF.md, PR 46)
    assert _arena_ops(decode) == [
        ("copy-start", (1, 9, 53, 8, 32, 128)),
        ("copy-start", (9, 53, 8, 32, 128)),
    ]


def test_a_recurrent_models_step_programs_compile_and_read_weights_as_stored(
        v5e_host):
    """``nemotron3_super_120b_a12b`` (a recurrent state beside the arena;
    ``benchmark/tests/aot_recurrent.py`` compiles the two programs such a
    model dispatches): the decode program and the chunked
    prefill compile for the described v5e at the published widths — the
    ``relu2`` expert kernel over tiles of 896 columns, both paged kernels for
    the two attention layers, the decode step's state update as ONE kernel a
    mixer layer (``ssm_rows``) and the block-form scan in XLA — and the
    decode step re-lays no weight stack: ``w_in`` leaves its dot
    through a barrier, no ``w_in`` / ``w_out`` / expert stack is copied."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "aot_recurrent", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "tests",
            "aot_recurrent.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with jax.default_matmul_precision("default"):
        texts = mod.check("nemotron3_super_120b_a12b", texts=True)
    decode = texts["serve_chunk"]
    assert _weight_stack_relayouts(decode) == []
    dots, windowed = _windowed_projections(decode)
    assert len(dots) >= 3 and windowed == []
    # seventeen runs of one kind: an expert kernel in seven, the decode
    # kernel (it stores the step's fresh K/V itself: PR 61) in two, the
    # state kernel in eight
    assert decode.count("tpu_custom_call") == 17
    assert "paged_kv_write" not in decode
    assert "paged_decode" in decode and "moe_experts" in decode
    assert decode.count("ssm_rows/pallas_call") >= 8
    prefill = texts["serve_prefill_chunk[256]"]
    assert "paged_prefill" in prefill and "moe_experts" in prefill
    assert _weight_stack_relayouts(prefill) == []
    # the recurrent state is updated where it lies: neither program copies
    # an array of the state's size (134 MB in and out of every step, 22% of
    # it, before the carried state went through a barrier)
    for text in (decode, prefill):
        assert [line for line in text.split("\n")
                if " copy(" in line and "128,64,128]" in line] == []


def test_the_compiled_program_guard_sees_a_transposed_weight_stack():
    """The guard's own reading, on the lines the parent's compiled 7B
    program held: the transposed int8 stack and the windowed dot are
    found; a prefetch of the router stack (a move, 4 MiB) is not."""
    text = """
  %stage_layers__wk___q.1 = s8[1,28,3584,512]{3,2,1,0:T(8,128)(4,1)} parameter(9), metadata={op_name="stage_layers['wk'].q"}
  %copy.18 = s8[1,28,3584,512]{2,3,1,0:T(8,128)(4,1)S(1)} copy(%stage_layers__wk___q.1), sharding={replicated}, metadata={op_name="stage_layers['wk'].q"}
  %copy-done.9 = bf16[1,16,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.9)
  %copy.40 = bf16[1,16,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)} copy(%copy-done.9), metadata={op_name="stage_layers['router']"}
  %stage_layers__wo___q.1 = s8[1,28,3584,3584]{3,2,1,0:T(8,128)(4,1)} parameter(11), metadata={op_name="stage_layers['wo'].q"}
  %copy.50 = s8[1,28,3584,3584]{3,2,1,0:T(8,128)(4,1)S(1)} copy(%stage_layers__wo___q.1), metadata={op_name="stage_layers['wo'].q"}
  %convolution.45 = bf16[4,4,128]{2,0,1:T(4,128)(2,1)} convolution(%fusion.188, %fusion.189), window={size=4 pad=3_3 rhs_reversal=1}, dim_labels=bf0_0oi->b0f, metadata={op_name="jit(serve_chunk)/state/while/body/closed_call/qkv/dot_general"}
  %convolution.9 = bf16[4,3584]{1,0:T(4,128)(2,1)} convolution(%fusion.1, %fusion.2), dim_labels=bf_io->bf, metadata={op_name="jit(serve_chunk)/state/while/body/closed_call/qkv/dot_general"}
"""
    found = _weight_stack_relayouts(text)
    assert len(found) == 1 and found[0].startswith("%copy.18 ")
    dots, windowed = _windowed_projections(text)
    assert len(dots) == 2 and len(windowed) == 1


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, inner jaxprs walked."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in _inner_jaxprs(eqn):
                yield from _pallas_calls(sub)


def _block_shapes(eqn):
    """A ``pallas_call``'s operand and result blocks as the kernel sees
    them, from its grid mapping: one tuple per block, a squeezed dim None."""
    return [
        tuple(getattr(d, "block_size", None) for d in bm.block_shape)
        for bm in eqn.params["grid_mapping"].block_mappings
    ]


@pytest.mark.parametrize("store", ["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_decode_kernel_takes_a_blocks_heads_together(cell, store):
    """The decode kernel at the three cells' shapes, read from the traced
    ``pallas_call`` (nothing runs): ONE invocation — no grid over cells,
    heads or rows: the walk is a loop in the body —, three scalar-prefetch
    operands (layer, table, the frontier), both arenas WHOLE and in HBM —
    no operand a block: the body copies them by hand — and a double-buffered
    VMEM scratch a cell wide for each, ``(2, bps, Nkv, BS, D)``: all
    key/value heads of a block in one copy, ``bps`` the shapes'
    (``decode_blocks_per_cell``); an int8 arena's scales a cell's row in
    SCALAR memory, the blocks' ``2·Nkv`` side by side."""
    from llm_sharding_tpu.ops import paged_attention as pa

    Nh, Nkv = _CELL_SHAPES[cell]
    B, T, BS, D, Lp, NB = 4, 128, 32, 128, 3, 260
    S = jax.ShapeDtypeStruct
    dt = jnp.bfloat16 if store == "bf16" else jnp.int8
    arena = S((Lp, NB, Nkv, BS, D), dt)
    scale = S((Lp, NB, Nkv), jnp.float32) if store == "int8" else None
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, l, t, qp, kp, ks, vs: pa.paged_attention_tpu(
            q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
        )
    )(
        S((B, 1, Nh, D), jnp.bfloat16), arena, arena, S((), jnp.int32),
        S((B, T), jnp.int32), S((B, 1), jnp.int32),
        S((B, T * BS), jnp.int32), scale, scale,
    )
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    bps = pa.decode_blocks_per_cell(T, BS, Nkv, 2 * D, dt.dtype.itemsize)
    assert bps == {4: 16, 8: 8, 16: 4}[Nkv]
    assert tuple(gm.grid) == (1,)
    assert gm.num_index_operands == 3
    # the pool reaches the kernel twice, whole, where it lies
    pools = [bm.block_aval for bm in gm.block_mappings
             if len(bm.block_aval.shape) == 5]
    assert [(a.shape, str(a.memory_space)) for a in pools] == [
        ((Lp, NB, Nkv, BS, D), "hbm")] * 2
    scratch = [v.aval for v in call.params["jaxpr"].invars][
        -gm.num_scratch_operands:]
    cells = [a.shape for a in scratch if len(a.shape) == 5]
    assert cells == [(2, bps, Nkv, BS, D)] * 2
    smem = [a.shape for a in scratch if str(a.memory_space) == "smem"]
    assert smem == ([(2, 1, bps * 2 * Nkv)] if store == "int8" else [])


# ---- the score kernel of a selecting decode step (``index_scores_tpu``) ------
# Rows of 16 table entries of 8 tokens over an index arena of 128 lanes, 4 index
# heads; ``width`` is the blocks a cell (None: the shapes' own, here the whole
# table in one cell).

#: case -> (a row's written columns [B], what the trash block holds, the index
#: key's own width, the store)
_SCORE_CASES = {
    "rows of unequal frontiers": ([37, 128, 9, 70], 0.0, 128, "f32"),
    "a dead row in the middle of the slot": ([40, 0, 0, 100], 0.0, 128, "f32"),
    "a frontier that ends inside a cell": ([33, 17, 1, 95], 0.0, 128, "f32"),
    "a trash block holding inf": ([20, 0, 61, 128], np.inf, 128, "f32"),
    "a trash block holding nan": ([20, 0, 61, 128], np.nan, 128, "bf16"),
    "a 64-wide key padded to 128 lanes": ([50, 77, 0, 12], 0.0, 64, "bf16"),
}


def _score_inputs(case, T=16, BS=8, Hi=4, lanes=128):
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops import paged_attention as pa

    ctx, trash, width, store = _SCORE_CASES[case]
    dt = jnp.float32 if store == "f32" else jnp.bfloat16
    B, L = len(ctx), 2
    NB = 1 + B * T
    rng = np.random.default_rng(len(case))
    arena = np.zeros((L, NB, 1, BS, lanes), np.float32)
    arena[..., :width] = rng.standard_normal((L, NB, 1, BS, width))
    arena[:, 0] = trash
    table = np.zeros((B, T), np.int32)
    kv_pos = np.full((B, T * BS), POS_SENTINEL, np.int32)
    for b, n in enumerate(ctx):
        own = -(-n // BS)
        # a row's blocks lie in the arena in no order
        table[b, :own] = 1 + b * T + rng.permutation(T)[:own]
        kv_pos[b, :n] = np.arange(n)
    q_pos = np.asarray(
        [[n - 1 if n else POS_SENTINEL] for n in ctx], np.int32)
    select = pa.Selection(
        jnp.asarray(rng.standard_normal((B, 1, Hi, width)), dt),
        jnp.asarray(rng.uniform(0.5, 1.5, (B, 1, Hi)), jnp.float32),
        jnp.asarray(arena, dt), 16,
    )
    return select, jnp.asarray(table), jnp.asarray(q_pos), jnp.asarray(kv_pos)


@pytest.mark.parametrize("width", [None, 4, 1])
@pytest.mark.parametrize("case", sorted(_SCORE_CASES))
def test_the_score_kernel_scores_what_the_xla_branch_scores(case, width):
    """``index_scores_tpu`` (interpret mode: the body the chip runs) against
    the XLA branch of ``index_scores`` — the gathered window's einsum — on
    every attendable column, over a table of ONE cell (the shapes' own
    width: narrower than a cell's cap), of four and of sixteen: the same
    scores whatever the width, zeros (never a trash block's ``inf`` /
    ``nan``) where the walk did not go or the table names the trash block,
    and ``select_mask`` over them keeps the very set ``select_tokens``
    lists."""
    from llm_sharding_tpu.ops import paged_attention as pa

    select, table, q_pos, kv_pos = _score_inputs(case)
    BS = select.idx_arena.shape[3]
    ok = pa._attendable(table, q_pos, kv_pos, BS)
    want = pa.index_scores(select, 1, table, q_pos, kv_pos, ok)[:, 0]
    lanes = select.idx_arena.shape[-1]
    qi = jnp.pad(select.qi, [(0, 0)] * 3 + [(0, lanes - select.qi.shape[-1])])
    raw = pa.index_scores_tpu(
        qi[:, 0], select.wi[:, 0], select.idx_arena, 1, table, q_pos,
        kv_pos, interpret=True, blocks_per_cell=width,
    )
    assert np.isfinite(np.asarray(raw)).all()
    seen = np.asarray(ok[:, 0])
    assert (np.asarray(raw)[np.repeat(np.asarray(table) == 0, BS, 1)] == 0).all()
    np.testing.assert_allclose(
        np.asarray(raw)[seen], np.asarray(want)[seen], rtol=1e-5, atol=1e-5)
    # through the dispatch (the shapes' own width) the masked scores too
    if width is None:
        got = pa.index_scores(
            select, 1, table, q_pos, kv_pos, ok, "interpret")[:, 0]
        np.testing.assert_array_equal(
            np.asarray(got) == -np.inf, np.asarray(want) == -np.inf)
    score = jnp.where(ok[:, 0], raw, -jnp.inf)
    keep = np.asarray(pa.select_mask(score, select.topk))
    cols, real = (np.asarray(a) for a in pa.select_tokens(score, select.topk))
    for b in range(seen.shape[0]):
        assert sorted(np.flatnonzero(keep[b])) == sorted(cols[b][real[b]])
        assert keep[b].sum() == min(seen[b].sum(), select.topk)


def test_the_score_kernel_refuses_a_width_that_does_not_divide_the_table():
    from llm_sharding_tpu.ops import paged_attention as pa

    select, table, q_pos, kv_pos = _score_inputs("rows of unequal frontiers")
    with pytest.raises(ValueError, match="does not divide the table width"):
        pa.index_scores_tpu(
            select.qi[:, 0], select.wi[:, 0], select.idx_arena, 1, table,
            q_pos, kv_pos, interpret=True, blocks_per_cell=5,
        )


#: table widths at Keye's index arena (12 layers x 2305 blocks of 32 tokens x
#: 128 bf16 lanes, 16 index heads, 4 rows) -> the blocks a cell
_SCORE_TABLES = {288: 96, 256: 64, 33: 33}


@pytest.mark.parametrize("table", sorted(_SCORE_TABLES))
def test_the_score_kernel_walks_the_index_arena_by_hand(table):
    """The score kernel at Keye's shape, read from the traced ``pallas_call``
    (nothing runs): ONE invocation — no grid over cells: the walk is a loop
    in the body —, three scalar-prefetch operands (layer, table, the
    frontier: no walk laid end to end), the index arena WHOLE and in HBM,
    once — no operand a block —, a double-buffered VMEM scratch a cell wide
    and the whole call's scores one output block, ``[B, T·BS]`` as the search
    reads them."""
    from llm_sharding_tpu.ops import paged_attention as pa

    B, Hi, lanes, BS, L, NB = 4, 16, 128, 32, 12, 2305
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(pa.index_scores_tpu)(
        S((B, Hi, lanes), jnp.bfloat16), S((B, Hi), jnp.float32),
        S((L, NB, 1, BS, lanes), jnp.bfloat16), S((), jnp.int32),
        S((B, table), jnp.int32), S((B, 1), jnp.int32),
        S((B, table * BS), jnp.int32),
    )
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    bps = pa.index_blocks_per_cell(table, BS, lanes, 2)
    assert bps == _SCORE_TABLES[table]
    assert tuple(gm.grid) == (1,) and gm.num_index_operands == 3
    pools = [bm.block_aval for bm in gm.block_mappings
             if len(bm.block_aval.shape) == 5]
    assert [(a.shape, str(a.memory_space)) for a in pools] == [
        ((L, NB, 1, BS, lanes), "hbm")]
    assert _block_shapes(call)[-1] == (B, table * BS)
    scratch = [v.aval for v in call.params["jaxpr"].invars][
        -gm.num_scratch_operands:]
    assert [a.shape for a in scratch if len(a.shape) == 3] == [
        (2, bps * BS, lanes)]


@pytest.mark.parametrize("table", sorted(_SCORE_TABLES))
def test_the_score_kernel_compiles_for_a_described_v5e(v5e_chip, table):
    """The TPU's own compiler (Mosaic included) accepts the score kernel at
    Keye's shape — a block's ``(BS, lanes)`` tile copied by hand out of the
    5-D stacked arena into a slice of a slot, a cell's scores stored at its
    lane offset of the one ``[B, T·BS]`` output block — at a table of three
    cells of 96 blocks, of four of 64 and an odd one of ONE cell; and
    nothing re-lays the scores after the call. No chip: the compile is real,
    nothing runs."""
    from llm_sharding_tpu.ops import paged_attention as pa

    B, Hi, lanes, BS, L, NB = 4, 16, 128, 32, 12, 2305
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    with jax.default_matmul_precision("default"):
        text = jax.jit(pa.index_scores_tpu).lower(
            S((B, Hi, lanes), jnp.bfloat16), S((B, Hi), jnp.float32),
            S((L, NB, 1, BS, lanes), jnp.bfloat16), S((), jnp.int32),
            S((B, table), jnp.int32), S((B, 1), jnp.int32),
            S((B, table * BS), jnp.int32),
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "index_scores" in text
    for m in re.finditer(r"= f32\[([\d,]+)\][^ ]* (copy|reduce)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < (
            B * table * BS)
    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < (
            L * NB * BS * lanes)


@pytest.mark.parametrize("weights", ["int8", "bf16"])
@pytest.mark.parametrize("rows", [4, 1024])
def test_expert_kernel_compiles_for_a_described_v5e(v5e_chip, rows, weights):
    """Mosaic accepts the expert kernel (``ops/moe.py``) at OLMoE-1B-7B's
    published widths — 64 experts of 2048 x 1024, the layer-stacked weights
    read in place through scalar-prefetched layer and expert indices — in
    both regimes: a decode step's 4 rows (one tile per distinct expert) and a
    prefill chunk's 1,024 positions (grouped tiles of 128 rows). The stack is
    cut to 2 layers; no weight-sized copy may stand beside the custom call."""
    from unittest import mock

    from llm_sharding_tpu.ops import moe
    from llm_sharding_tpu.ops.quant import QTensor

    L, H, E, F, k = 2, 2048, 64, 1024, 8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    if weights == "int8":
        leaf = lambda *shape: QTensor(
            S((L, *shape), jnp.int8), S((L, shape[-1]), jnp.bfloat16))
    else:
        leaf = lambda *shape: S((L, *shape), jnp.bfloat16)

    def fn(x, w, ids, live, layer, wg, wu, wd):
        return moe.expert_mlp(
            x, w, ids, wg, wu, wd, E, live=live, layer=layer, backend="kernel"
        )

    with jax.default_matmul_precision("default"), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        compiled = jax.jit(fn).lower(
            S((rows, H), jnp.bfloat16), S((rows, k), jnp.float32),
            S((rows, k), jnp.int32), S((rows,), jnp.bool_), S((), jnp.int32),
            leaf(H, E * F), leaf(H, E * F), leaf(E * F, H),
        ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "moe_experts" in text
    import re

    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < H * E * F


def test_forced_backend_env_validation(monkeypatch):
    from llm_sharding_tpu.ops.paged_attention import forced_backend

    monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
    assert forced_backend() is None
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "1")
    assert forced_backend() == "kernel"
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    assert forced_backend() == "interpret"
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "maybe")
    with pytest.raises(ValueError, match="PAGED_FORCE_KERNEL"):
        forced_backend()


def test_op_level_forced_kernel_off_tpu_is_curated(monkeypatch):
    """A lingering PAGED_FORCE_KERNEL=kernel reaching backend='auto' on a
    CPU host must raise the curated op-level error, not a raw
    Pallas/Mosaic lowering failure (the serve path curates this at
    construction; the standalone op must too)."""
    from llm_sharding_tpu.ops.paged_attention import paged_attention

    k = jnp.zeros((1, 2, 1, 8, 128), jnp.float32)  # [L, NB, Nkv, BS, D]
    tbl = jnp.ones((1, 2), jnp.int32)
    q = jnp.zeros((1, 1, 1, 128), jnp.float32)
    qpos = jnp.zeros((1, 1), jnp.int32)
    kvpos = jnp.zeros((1, 16), jnp.int32)
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "kernel")
    with pytest.raises(ValueError, match="TPU backend"):
        paged_attention(q, k, k, 0, tbl, qpos, kvpos, backend="auto")
    with pytest.raises(ValueError, match="TPU backend"):
        paged_attention(q, k, k, 0, tbl, qpos, kvpos, backend="kernel")


def test_kernel_serve_path_interpret_token_identical(setup, monkeypatch):
    """The tentpole contract, pinned independently of the CI env: with the
    kernel forced into interpret mode, the serve programs decode through
    the Pallas code path — direct block-indexed writes, streamed-block
    attention, NO gathered window — and greedy output still equals dense
    serving and the solo oracle. Covers plain decode AND spec-verify's
    canonical-column scatter (rollback = position rewind)."""
    params, eng = setup
    specs = [
        (prompt(71, 5), 9, {}), (prompt(72, 3), 6, {}),
        (prompt(73, 6), 4, {}),
    ]
    dense = run_workload(eng.serve(capacity=64), specs)
    dense_spec = run_workload(eng.serve(capacity=64, speculate=2), specs)
    assert dense_spec == dense

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    srv = eng.serve(capacity=64, **paged_kw())
    assert srv.attn_impl == "interpret"
    assert run_workload(srv, specs) == dense
    check_drained(srv)
    srv_spec = eng.serve(capacity=64, speculate=2, **paged_kw())
    assert srv_spec.attn_impl == "interpret"
    assert run_workload(srv_spec, specs) == dense
    check_drained(srv_spec)
    for (p, b, _), toks in zip(specs, dense):
        assert toks == oracle_tokens(params, p, b)


def test_attn_backend_metrics(setup, monkeypatch):
    """server_attn_backend reflects each live server's resolved
    implementation and server_attn_blocks_read_total grows as paged
    decode steps attend mapped blocks (the bench's bytes-estimate feed)."""
    from llm_sharding_tpu.obs.metrics import ATTN_BACKEND, ATTN_BLOCKS_READ
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    _, eng = setup
    monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
    srv = eng.serve(capacity=64, **paged_kw())
    assert srv.attn_impl == "xla"  # CPU mesh resolves auto → gather
    _update_load_gauges()
    assert ATTN_BACKEND.labels(backend="xla").value >= 1
    before = ATTN_BLOCKS_READ.value
    r = srv.submit(prompt(74), 8)
    srv.run_until_idle()
    assert r.done and ATTN_BLOCKS_READ.value > before
    check_drained(srv)
    # a closed server must drop out of the tally even while referenced
    # (the one-hot contract across e.g. a :placement rebuild)
    xla_live = ATTN_BACKEND.labels(backend="xla").value
    srv.close()
    _update_load_gauges()
    assert ATTN_BACKEND.labels(backend="xla").value == xla_live - 1


# ------------------------------------- named scopes in the step programs

#: Which words of ``obs.stepline.SCOPES`` each step program must NOT carry
#: when lowered (paged arena, chunked prefill, the kernel code path
#: emulated). The arena-native programs (serve_chunk, serve_prefill_chunk)
#: slice no layer out of the pool, write none back and lay nothing out: the
#: kernels index the carried stack. serve_admit prefills a DENSE window
#: (the dense scan's kv_take / kv_put) and cuts it into head-major blocks
#: (kv_layout) before the scatter. serve_prefill_chunk samples nothing.
#: serve_admit_finish only embeds each row's last token.
#: A model's MLP is dense (``mlp``) or sparse experts (``router`` and
#: ``moe``, ``ops/moe.py``), never both: the model of a case says which
#: words its programs lack.
_NO_ARENA_COPY = {"kv_take", "kv_layout", "kv_put"}
_NO_HEAD = {"head", "sample"}
#: ``absorb`` is latent attention's (``models/deepseek_v3.py``): neither model here has it.
# (a Mamba mixer's and a LatentMoE's words are ``nemotron_h``'s and
# ``jamba``'s alone, a KDA mixer's ``solar_open2``'s:
# ``tests/test_nemotron_h_serve.py``, ``tests/test_jamba_serve.py`` and
# ``tests/test_solar_open2_serve.py`` hold their programs to them)
_RECURRENT_WORDS = {
    "ssm_proj", "conv", "ssm", "ssm_x", "moe_latent", "kda_proj", "kda",
}
# (``indexer`` / ``select`` are a token-selecting model's alone:
# ``tests/test_keye_vl2_serve.py`` holds its programs to them)
_SELECT_WORDS = {"indexer", "select"}
# (``zero_expert`` is ``longcat_flash``'s alone — experts without weights and
# the shortcut's join: ``tests/test_longcat_flash_serve.py`` holds its
# programs to it)
_SHORTCUT_WORDS = {"zero_expert"}
# (``pass_close`` is a looped stack's alone — the final norm that closes a
# pass and the exit gate: ``tests/test_ouro_serve.py`` holds its programs to
# it, and these one-pass models' to being without it)
_LOOP_WORDS = {"pass_close"}
_OTHERS_WORDS = (
    _RECURRENT_WORDS | _SELECT_WORDS | _SHORTCUT_WORDS | _LOOP_WORDS
)
_MLP_WORDS = {
    "dense": {"router", "moe", "absorb"} | _OTHERS_WORDS,
    "experts": {"mlp", "absorb"} | _OTHERS_WORDS,
}
PROGRAM_SCOPES = {
    # a decode step's fresh K/V is stored INSIDE ``paged_decode`` (under
    # ``attn``) on the kernel path these programs take: nothing is left under
    # ``kv_write`` there (PR 61)
    "serve_chunk": _NO_ARENA_COPY | {"kv_write"},
    "serve_prefill_chunk": _NO_ARENA_COPY | _NO_HEAD,
    "serve_admit": set(),
    "serve_admit_finish": None,  # exactly: embed, state
}


@pytest.fixture(scope="module")
def lowered_programs(setup):
    return _lower_programs(*setup, cfg=CFG)


@pytest.fixture(scope="module")
def lowered_programs_experts():
    """The same through a model with sparse experts (a ring of two)."""
    from llm_sharding_tpu.models.config import tiny_olmoe

    cfg = tiny_olmoe(max_position_embeddings=CFG.max_position_embeddings)
    params = llama.init_params(cfg, jax.random.key(12), dtype=jnp.float32)
    eng = PipelineEngine(cfg, params, num_stages=2, cache_dtype=jnp.float32,
                         devices=jax.devices()[:2])
    return _lower_programs(params, eng, cfg=cfg)


def _lower_programs(params, eng, cfg):
    """Serve a one-shot and a chunked admission through the interpreted
    kernels, lowering each step program with the very arguments the server
    dispatched it with. Returns ``(texts, served, oracle)``."""
    from llm_sharding_tpu.parallel import serve as serve_ops

    texts = {}

    def spy(mp, name):
        orig = getattr(serve_ops, name)

        def call(*a, **kw):
            if name not in texts:
                texts[name] = orig.lower(*a, **kw).as_text(debug_info=True)
            return orig(*a, **kw)

        mp.setattr(serve_ops, name, call)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PAGED_FORCE_KERNEL", "interpret")
        for name in PROGRAM_SCOPES:
            spy(mp, name)
        srv = eng.serve(
            capacity=64, batch_per_slot=2, kv_block_size=8, kv_blocks=65,
            prefill_chunk=16,
        )
        assert srv.attn_impl == "interpret"
        prompts = [prompt(301, n=5), prompt(302, n=20)]
        reqs = [srv.submit(p, 5) for p in prompts]
        srv.run_until_idle()
        srv.close()
    served = [list(r.tokens) for r in reqs]
    oracle = []
    for p in prompts:
        res = generate(cfg, params, p, 5, cache_dtype=jnp.float32)
        oracle.append(list(res.tokens[0, len(p): int(res.lengths[0])]))
    return texts, served, oracle


def _scopes_in(text):
    """The vocabulary words on any operation's name-stack path."""
    import re

    from llm_sharding_tpu.obs.stepline import SCOPES

    paths = set(re.findall(r'loc\("([^"]+)"', text))
    return {
        w for w in SCOPES
        if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)
    }, paths


@pytest.mark.parametrize("model", sorted(_MLP_WORDS))
@pytest.mark.parametrize("program", sorted(PROGRAM_SCOPES))
def test_step_programs_carry_the_scope_vocabulary(request, program, model):
    """Every step program names its device work by the closed vocabulary
    (``obs.stepline.SCOPES``) — what a profiler trace's ``tf_op`` then
    carries — and naming changes no token: the served ids equal the
    monolithic oracle's, as before the scopes. A model with sparse experts
    carries ``router`` and ``moe`` where a dense one carries ``mlp``, in all
    three programs that run layers."""
    from llm_sharding_tpu.obs.stepline import SCOPES

    texts, served, oracle = request.getfixturevalue(
        "lowered_programs" if model == "dense" else "lowered_programs_experts"
    )
    assert served == oracle
    found, paths = _scopes_in(texts[program])
    missing_ok = PROGRAM_SCOPES[program]
    want = (
        {"embed", "state"} if missing_ok is None
        else set(SCOPES) - missing_ok - _MLP_WORDS[model]
    )
    assert found == want, (sorted(want - found), sorted(found - want))
    if program in ("serve_chunk", "serve_prefill_chunk"):
        # no arena copy: no layer sliced out of the pool, no operand of a
        # kernel transposed, nothing written back around the layer — under
        # any enclosing scope (MLIR locations are relative to the traced
        # function, XLA joins them into tf_op)
        for gone in ("kv_take/", "kv_layout/", "kv_put/"):
            assert not any(gone in p + "/" for p in paths), gone
        # the read is the kernel's own block DMAs; a chunk's write is the
        # scatter into the carried stack, a decode step's (one entry a row,
        # a plain arena, the attention on its kernel) the attention
        # kernel's own, which leaves XLA no scatter into the arena and the
        # program no write kernel
        scatter = any(p.endswith("kv_write/scatter") for p in paths)
        assert not any("paged_kv_write" in p for p in paths)
        assert scatter == (program != "serve_chunk")
    if program == "serve_chunk":
        assert any(p.endswith("ring_hop/ppermute") for p in paths)


def test_the_pallas_kernels_are_named(lowered_programs):
    """``name=`` on the pallas_calls: a trace names the kernels
    ``paged_decode`` / ``paged_prefill``, not by a numbered fusion."""
    texts, _, _ = lowered_programs
    _, decode = _scopes_in(texts["serve_chunk"])
    _, prefill = _scopes_in(texts["serve_prefill_chunk"])
    assert any(p.startswith("paged_decode/") for p in decode)
    assert any(p.startswith("paged_prefill/") for p in prefill)
    assert not any(p.startswith("paged_prefill/") for p in decode)


def test_the_expert_kernel_is_named(lowered_programs_experts):
    texts, _, _ = lowered_programs_experts
    for program in ("serve_chunk", "serve_prefill_chunk", "serve_admit"):
        _, paths = _scopes_in(texts[program])
        assert any("moe_experts" in p for p in paths), program


# ----------------------- the arena stays where it lies (program structure)


def _inner_jaxprs(eqn):
    """The jaxprs an equation holds in its parameters (scan, while, cond,
    pjit, shard_map alike)."""
    from jax.extend import core as jex

    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jex.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex.Jaxpr):
                yield x


def _leaf_eqns(jaxpr):
    """Every equation of ``jaxpr`` that holds no inner jaxpr (a
    ``pallas_call`` counts as one equation), inner jaxprs walked through."""
    for eqn in jaxpr.eqns:
        subs = (
            [] if eqn.primitive.name == "pallas_call"
            else list(_inner_jaxprs(eqn))
        )
        if subs:
            for sub in subs:
                yield from _leaf_eqns(sub)
        else:
            yield eqn


def _layer_scans(jaxpr, block_shape):
    """The scans that carry a layer-stacked arena ``[L, *block_shape]``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and any(
            tuple(v.aval.shape[1:]) == block_shape and v.aval.ndim == 5
            for v in eqn.invars
        ):
            yield eqn
            continue
        for sub in _inner_jaxprs(eqn):
            yield from _layer_scans(sub, block_shape)


#: what may touch a value of a layer-arena's size: the three operations
#: that address (layer, block) INSIDE the carried stack ...
_IN_PLACE = {"gather", "scatter", "pallas_call"}
#: ... and, outside the layer scan, the relabelings of the whole state leaf
#: at a program's edge (the stage dim stripped and restored: no data moves)
_RELABEL = {"squeeze", "broadcast_in_dim", "reshape"}


def _arena_sized_offenders(eqns, stack_shape, allowed):
    """Equations with an operand or result of a LAYER-arena's size or more
    that are not ``allowed`` — or that are, but touch something other than
    the whole stack (a layer of it sliced out or put back is the copy this
    test exists to keep out)."""
    layer = int(np.prod(stack_shape[1:]))
    bad = []
    for eqn in eqns:
        big = [
            v.aval for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v.aval, "shape") and int(np.prod(v.aval.shape)) >= layer
        ]
        if not big:
            continue
        name = eqn.primitive.name
        whole = all(
            int(np.prod(a.shape)) == int(np.prod(stack_shape)) for a in big
        )
        full_slice = name == "slice" and (
            eqn.invars[0].aval.shape == eqn.outvars[0].aval.shape
        )
        if not ((name in allowed or full_slice) and whole):
            bad.append((name, [tuple(a.shape) for a in big]))
    return bad


@pytest.fixture(scope="module", params=["xla", "interpret"])
def traced_programs(request, setup):
    """The jaxprs of the three arena-native step programs as a paged server
    dispatched them — a chunked admission, decode chunks, and (second
    server) speculative verify — on one attention backend, bf16-style and
    int8 arenas. Returns ``{(program, kv_dtype): jaxpr}``, the local arena
    stack's shape, what was served against the oracle, and per ``(kv_dtype,
    speculate)`` server what its decode / verify dispatches wrote by the
    write's form: the counter's rise and the step records' sum."""
    from llm_sharding_tpu.obs.metrics import (
        DECODE_KV_ENTRIES_WRITTEN, DECODE_KV_WRITES,
    )

    def written():
        return {w: DECODE_KV_ENTRIES_WRITTEN.labels(write=w).value
                for w in DECODE_KV_WRITES}

    from llm_sharding_tpu.parallel import serve as serve_ops

    params, eng = setup
    backend = request.param
    jaxprs, writes = {}, {}
    served, oracle = [], []
    with pytest.MonkeyPatch.context() as mp:
        if backend == "interpret":
            mp.setenv("PAGED_FORCE_KERNEL", "interpret")
        kvd = {"now": None}
        for name in ("serve_chunk", "serve_prefill_chunk", "serve_verify"):
            orig = getattr(serve_ops, name)

            def call(*a, _orig=orig, _name=name, **kw):
                if (_name, kvd["now"]) not in jaxprs:
                    jaxprs[_name, kvd["now"]] = _orig.trace(*a, **kw).jaxpr
                return _orig(*a, **kw)

            mp.setattr(serve_ops, name, call)
        for kv_dtype in ("bf16", "int8"):
            for spec in (0, 2):
                kvd["now"] = kv_dtype
                w0 = written()
                srv = eng.serve(
                    capacity=64, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=65, kv_dtype=kv_dtype,
                    paged_attn="xla" if backend == "xla" else "auto",
                    # a speculative server has no chunked admission
                    **(dict(speculate=spec) if spec
                       else dict(prefill_chunk=16)),
                )
                assert srv.attn_impl == backend
                stack_shape = tuple(srv.state.k.shape[1:])
                prompts = [prompt(311 + spec, n=5), prompt(312 + spec, n=20)]
                reqs = [srv.submit(p, 5) for p in prompts]
                srv.run_until_idle()
                recs = collections.Counter()
                for r in srv.stepline.snapshot():
                    recs.update(r.get("decode_kv_entries", {}))
                srv.close()
                w1 = written()
                writes[kv_dtype, spec] = (
                    {w: w1[w] - w0[w] for w in w0}, dict(recs)
                )
                if kv_dtype == "bf16":  # exact arena: token-exact serving
                    served += [list(r.tokens) for r in reqs]
                    oracle += [oracle_tokens(params, p, 5) for p in prompts]
    return jaxprs, stack_shape, served, oracle, writes


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize(
    "program", ["serve_chunk", "serve_prefill_chunk", "serve_verify"]
)
def test_no_arena_sized_copy_in_a_step_program(
    traced_programs, program, kv_dtype
):
    """THE invariant of the head-major, layer-indexed arena: in the body of
    the paged layer scan no equation has an input or output of a
    layer-arena's size or more, except the gather, the scatter and the
    ``pallas_call`` that take the WHOLE carried stack as an operand and
    address ``(layer, block)`` inside it. Around the scan, in the rest of
    the program, the only other arena-sized equations are the relabelings
    of the state leaf at the program's edge. So a decode or prefill step
    holds no arena-sized transpose, slice or update, on either backend —
    and what it serves still equals the dense-path oracle."""
    jaxprs, stack_shape, served, oracle, _ = traced_programs
    assert served == oracle
    jaxpr = jaxprs[program, kv_dtype]
    scans = list(_layer_scans(jaxpr.jaxpr, stack_shape[1:]))
    assert scans, "no layer scan carries the stacked arena"
    for scan in scans:
        body = scan.params["jaxpr"].jaxpr
        eqns = list(_leaf_eqns(body))
        assert _arena_sized_offenders(eqns, stack_shape, _IN_PLACE) == []
        # the three in-place operations are really there
        names = {e.primitive.name for e in eqns}
        assert "scatter" in names
        assert ("pallas_call" in names) or ("gather" in names)
    assert _arena_sized_offenders(
        _leaf_eqns(jaxpr.jaxpr), stack_shape, _IN_PLACE | _RELABEL
    ) == []


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["serve_chunk", "serve_verify"])
def test_a_layer_scan_holds_one_decode_kernel_over_whole_blocks(
    traced_programs, program, kv_dtype
):
    """The decode programs as a server dispatched them: every layer scan
    that carries the arena holds exactly ONE attention ``pallas_call``,
    named ``paged_decode``, and the tiles of its two cell buffers are
    ``(Nkv, BS, D)`` wide — a block's key/value heads together in one copy.
    It is the scan's ONLY Pallas call: where a step writes one entry a row
    into a plain arena (``serve_chunk`` over bf16) the kernel stores the
    entry itself, both arenas aliased over outputs of the call and the
    entries among its operands (PR 61: no ``paged_kv_write`` before it); a
    verify's ``K + 1`` entries and an int8 arena keep the scatter, and
    their attention call aliases nothing."""
    jaxprs, stack_shape, _, _, _ = traced_programs
    jaxpr = jaxprs[program, kv_dtype]
    _, _, Nkv, BS, D = stack_shape
    scans = list(_layer_scans(jaxpr.jaxpr, stack_shape[1:]))
    assert scans
    if not list(_pallas_calls(jaxpr.jaxpr)):
        # the XLA backend: the same scans read the pool by a gather
        for scan in scans:
            names = {e.primitive.name
                     for e in _leaf_eqns(scan.params["jaxpr"].jaxpr)}
            assert "gather" in names
        return
    writes = program == "serve_chunk" and kv_dtype == "bf16"
    for scan in scans:
        (call,) = _pallas_calls(scan.params["jaxpr"].jaxpr)
        assert call.params["name"] == "paged_decode"
        scratch = call.params["jaxpr"].invars[
            -call.params["grid_mapping"].num_scratch_operands:]
        cells = [v.aval.shape for v in scratch if len(v.aval.shape) == 5]
        assert len(cells) == 2 and {c[2:] for c in cells} == {(Nkv, BS, D)}
        # the arenas, each handed in ONCE and aliased over its own output;
        # the fresh entries [rows, Nkv, D] ride in beside them
        aliased = [
            (call.invars[i].aval.shape, call.outvars[o].aval.shape)
            for i, o in call.params["input_output_aliases"]]
        assert aliased == ([(stack_shape,) * 2] * 2 if writes else [])
        arenas = [v for v in call.invars if v.aval.shape == stack_shape]
        assert len(arenas) == 2
        entries = [v for v in call.invars if v.aval.shape[1:] == (Nkv, D)]
        assert len(entries) == (2 if writes else 0)


@pytest.mark.parametrize("spec", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_the_decode_write_is_counted_by_its_form(
    traced_programs, request, kv_dtype, spec
):
    """``server_decode_kv_entries_written_total{write=}`` and the step
    record's ``decode_kv_entries`` say how a served step's fresh K/V landed:
    ``attention`` where the step program's statics let the attention kernel
    store it (one entry a row, a plain arena, the attention on its kernel) —
    the same predicate ``paged_attention_write`` asks, so the count is the
    program's — and ``scatter`` for a verify step, an int8 arena and the XLA
    path; ``kernel`` (what ``paged_kv_write`` still stores: a selecting
    model's index keys) stays 0 for a model without an indexer."""
    *_, writes = traced_programs
    backend = request.node.callspec.params["traced_programs"]
    counted, recorded = writes[kv_dtype, spec]
    form = "attention" if (
        backend == "interpret" and kv_dtype == "bf16" and not spec
    ) else "scatter"
    assert counted[form] > 0 and sum(counted.values()) == counted[form], (
        counted)
    assert recorded == {form: counted[form]}
    if spec:  # a verify writes K + 1 entries a live row
        assert counted[form] % (spec + 1) == 0


def test_the_structural_check_sees_a_sliced_out_layer():
    """The check above is not vacuous: the retired pattern — a layer
    sliced out of the stack, used, and written back — is reported."""
    stack = jnp.zeros((3, 9, 2, 8, 16), jnp.float32)

    def retired(stack, l):
        one = jax.lax.dynamic_index_in_dim(stack, l, keepdims=False)
        one = jnp.transpose(one, (0, 2, 1, 3))
        one = jnp.transpose(one + 1.0, (0, 2, 1, 3))
        return jax.lax.dynamic_update_slice(stack, one[None], (l, 0, 0, 0, 0))

    eqns = list(_leaf_eqns(jax.make_jaxpr(retired)(stack, 1).jaxpr))
    found = {n for n, _ in _arena_sized_offenders(
        eqns, stack.shape, _IN_PLACE
    )}
    assert {"dynamic_slice", "transpose", "dynamic_update_slice"} <= found
