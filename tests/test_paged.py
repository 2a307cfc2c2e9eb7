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

The op-level kernel-against-XLA tests are ``test_paged_ops.py``; the
compiled-program guards, the scope vocabulary and the structural checks
``test_paged_programs.py``.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.runtime.blocks import (
    TRASH_BLOCK, BlockAllocator, BlockExhausted,
)
from llm_sharding_tpu.runtime.faults import FaultPlan, PermanentFault

from paged_arena import CFG, oracle_tokens, prompt, tiny_engine

# CI runs this module twice: default 16, then PAGED_TEST_BLOCK_SIZE=4 to
# stress block-boundary and multi-entry-table paths (capacity 64 → T=16)
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "16"))


@pytest.fixture(scope="module")
def setup():
    return tiny_engine()


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
