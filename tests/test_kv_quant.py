"""Quantized KV arena (ISSUE 11): int8/fp8 paged blocks with
per-block-per-head scales, dequant fused into the paged-attention op.

Contracts under test:
- ops: quantize-at-insert round trip is step-bounded; running-max scale
  growth requantizes existing codes; the fused-dequant attention (XLA and
  the Pallas kernel in interpret mode) matches dequantize-then-attend.
- serve: an int8-KV server produces a valid greedy rollout whose tokens
  track the bf16-KV server's (the drift-tolerance harness — quantization
  is intentionally non-bit-exact, the FIRST such serve variant), under
  both the XLA fallback and the interpret-mode kernel.
- capacity: at equal HBM bytes the int8 arena admits >= 1.9x the blocks
  of bf16 (acceptance bar, via BlockAllocator.bytes_per_block), and the
  server_arena_bytes{dtype=} gauge reports the real allocation.
- tiering/persistence: radix host-tier demote -> restore round-trips
  int8 codes + scales byte-exactly; snapshots carry kv_dtype and the
  scale arenas and a restored int8 daemon continues identically.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.ops.paged_attention import (
    gather_block_kv, kernel_eligible, paged_attention_tpu,
    paged_attention_xla, write_block_kv,
)
from llm_sharding_tpu.ops.quant import (
    KV_DTYPES, fp8_kv_supported, is_kv_quantized, kv_dequantize, kv_qmax,
    kv_quantize, kv_storage_dtype,
)
from llm_sharding_tpu.runtime.blocks import BlockAllocator
from llm_sharding_tpu.runtime.engine import PipelineEngine

from paged_arena import others_untouched

CFG = tiny_llama(num_hidden_layers=8)
BS = 8  # serve-side kv block size in the tests
# the op units run on a head-major stack of L layers and touch layer LYR:
# the layers around it must keep their bytes, and a read of the wrong layer
# would find the zeros they still hold
L, LYR = 3, 1


# ------------------------------------------------------------- op units


def test_kv_quantize_dequantize_round_trip_int8():
    x = jax.random.normal(jax.random.key(0), (4, 16, 2, 8), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=(1, 3)) / kv_qmax(jnp.int8)  # [4, 2]
    sc = scale[:, None, :, None]
    q = kv_quantize(x, sc, jnp.int8)
    assert q.dtype == jnp.int8
    back = kv_dequantize(q, sc, jnp.float32)
    # error within half a quantization step per element
    assert bool(jnp.all(jnp.abs(back - x) <= sc * 0.5 + 1e-7))


@pytest.mark.skipif(not fp8_kv_supported(), reason="no fp8 on this backend")
def test_kv_quantize_dequantize_round_trip_fp8():
    x = jax.random.normal(jax.random.key(1), (4, 16, 2, 8), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=(1, 3)) / kv_qmax(jnp.float8_e4m3fn)
    sc = scale[:, None, :, None]
    q = kv_quantize(x, sc, jnp.float8_e4m3fn)
    assert q.dtype == jnp.float8_e4m3fn
    back = kv_dequantize(q, sc, jnp.float32)
    # e4m3 has ~2 mantissa-step relative error at these magnitudes
    assert float(jnp.max(jnp.abs(back - x))) < 0.2 * float(jnp.max(jnp.abs(x)))


def test_kv_dtype_vocabulary():
    assert KV_DTYPES == ("bf16", "int8", "fp8")
    assert kv_storage_dtype("bf16", jnp.float32) == jnp.dtype(jnp.float32)
    assert kv_storage_dtype("int8") == jnp.dtype(jnp.int8)
    assert is_kv_quantized(jnp.int8) and is_kv_quantized(jnp.float8_e4m3fn)
    assert not is_kv_quantized(jnp.bfloat16)
    with pytest.raises(ValueError, match="kv dtype"):
        kv_storage_dtype("int4")


def _empty_arena(NB=6, Nkv=2, D=8):
    z = jnp.zeros((L, NB, Nkv, BS, D), jnp.int8)
    s = jnp.zeros((L, NB, Nkv), jnp.float32)
    return z, z, s, s


def test_write_block_kv_quantized_insert_then_gather():
    """Insert-quantized entries read back (via the dequantizing gather)
    within half a quantization step; untouched blocks stay zero."""
    rng = np.random.default_rng(2)
    kq, vq, ks, vs = _empty_arena()
    tbl = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    cols = jnp.asarray([[0, 1], [0, BS + 1]], jnp.int32)
    kn = jnp.asarray(rng.normal(size=(2, 2, 2, 8)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(2, 2, 2, 8)), jnp.float32)
    empty = _empty_arena()
    kq, vq, ks, vs = write_block_kv(
        kq, vq, LYR, tbl, cols, kn, vn, k_scale=ks, v_scale=vs
    )
    for before, after in zip(empty, (kq, vq, ks, vs)):
        others_untouched(before, after, LYR)
    gk, gv = gather_block_kv(kq, vq, LYR, tbl, ks, vs, out_dtype=jnp.float32)
    step = float(jnp.max(ks)) + 1e-7
    assert float(jnp.max(jnp.abs(gk[0, 0] - kn[0, 0]))) <= 0.5 * step
    assert float(jnp.max(jnp.abs(gv[1, BS + 1] - vn[1, 1]))) <= 0.5 * step
    # trash-mapped window region (row 0, third table entry) gathers zeros
    np.testing.assert_array_equal(np.asarray(gk[0, 2 * BS:]), 0.0)


def test_write_block_kv_scale_growth_requantizes_block():
    """A fresh entry that raises a block's absmax requantizes the block's
    existing codes: old entries stay recoverable within the NEW (coarser)
    step, and the block scale is the running max."""
    rng = np.random.default_rng(3)
    kq, vq, ks, vs = _empty_arena()
    tbl = jnp.asarray([[1]], jnp.int32)
    small = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), jnp.float32)
    big = small * 50.0
    kq, vq, ks, vs = write_block_kv(
        kq, vq, LYR, tbl, jnp.asarray([[0]]), small, small,
        k_scale=ks, v_scale=vs,
    )
    s0 = np.asarray(ks[LYR, 1]).copy()
    kq, vq, ks, vs = write_block_kv(
        kq, vq, LYR, tbl, jnp.asarray([[1]]), big, big,
        k_scale=ks, v_scale=vs,
    )
    assert np.all(np.asarray(ks[LYR, 1]) >= s0 * 49)
    gk, _ = gather_block_kv(kq, vq, LYR, tbl, ks, vs, out_dtype=jnp.float32)
    new_step = np.asarray(ks[LYR, 1])  # per-head step after growth
    err_old = np.abs(np.asarray(gk[0, 0]) - np.asarray(small[0, 0]))
    assert np.all(err_old <= new_step[:, None] * 0.75 + 1e-6)
    err_new = np.abs(np.asarray(gk[0, 1]) - np.asarray(big[0, 0]))
    assert np.all(err_new <= new_step[:, None] * 0.5 + 1e-6)


def test_write_block_kv_quantized_valid_gating():
    """Invalid entries neither write nor grow the block scale (the
    ring-inactive microstep no-op contract, quantized edition)."""
    kq, vq, ks, vs = _empty_arena()
    tbl = jnp.asarray([[1]], jnp.int32)
    huge = jnp.full((1, 1, 2, 8), 100.0, jnp.float32)
    kq2, vq2, ks2, vs2 = write_block_kv(
        kq, vq, LYR, tbl, jnp.asarray([[0]]), huge, huge,
        valid=jnp.asarray(False), k_scale=ks, v_scale=vs,
    )
    np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks))
    np.testing.assert_array_equal(np.asarray(kq2), np.asarray(kq))


def _quantized_attention_setup(seed=4, B=2, T=3, Nkv=2, G=2, D=8):
    rng = np.random.default_rng(seed)
    NB = B * T + 1
    kq = vq = jnp.zeros((L, NB, Nkv, BS, D), jnp.int8)
    ks = vs = jnp.zeros((L, NB, Nkv), jnp.float32)
    tbl = jnp.asarray(
        np.concatenate([np.arange(1, B * T + 1).reshape(B, T)]), jnp.int32
    )
    # fill every mapped block through the quantizing writer
    for c in range(T * BS):
        kn = jnp.asarray(rng.normal(size=(B, 1, Nkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, 1, Nkv, D)), jnp.float32)
        kq, vq, ks, vs = write_block_kv(
            kq, vq, LYR, tbl, jnp.full((B, 1), c, jnp.int32), kn, vn,
            k_scale=ks, v_scale=vs,
        )
    q = jnp.asarray(rng.normal(size=(B, 1, Nkv * G, D)), jnp.float32)
    qpos = jnp.full((B, 1), T * BS - 1, jnp.int32)
    kvpos = jnp.tile(jnp.arange(T * BS, dtype=jnp.int32)[None], (B, 1))
    return q, kq, vq, tbl, qpos, kvpos, ks, vs


def test_quantized_xla_attention_matches_dequantized_arena():
    """Fused-dequant XLA path == dequantize-the-whole-arena-then-attend,
    BIT-exact (both dequantize into the query dtype before the same
    math)."""
    q, kq, vq, tbl, qpos, kvpos, ks, vs = _quantized_attention_setup()
    got = paged_attention_xla(q, kq, vq, LYR, tbl, qpos, kvpos,
                              k_scale=ks, v_scale=vs)
    kd = kv_dequantize(kq, ks[..., None, None], jnp.float32)
    vd = kv_dequantize(vq, vs[..., None, None], jnp.float32)
    want = paged_attention_xla(q, kd, vd, LYR, tbl, qpos, kvpos)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantized_kernel_interpret_matches_dequantized_kernel():
    """The FUSED kernel (interpret mode, CPU CI-safe) == dequantizing the
    arena first and running the plain kernel — the in-VMEM dequant must
    be exactly the gather-path dequant."""
    q, kq, vq, tbl, qpos, kvpos, ks, vs = _quantized_attention_setup()
    got = paged_attention_tpu(
        q, kq, vq, LYR, tbl, qpos, kvpos, interpret=True,
        k_scale=ks, v_scale=vs,
    )
    kd = kv_dequantize(kq, ks[..., None, None], jnp.float32)
    vd = kv_dequantize(vq, vs[..., None, None], jnp.float32)
    want = paged_attention_tpu(
        q, kd, vd, LYR, tbl, qpos, kvpos, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6
    )
    # and the fused kernel tracks the fused XLA path (online softmax vs
    # cached attention: same values modulo f32 accumulation order)
    xla = paged_attention_xla(q, kq, vq, LYR, tbl, qpos, kvpos,
                              k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(xla), rtol=2e-5, atol=2e-5
    )


def test_kernel_eligible_names_one_byte_sublane():
    """1-byte KV dtypes tile at sublane 32: block 32 is kernel-eligible,
    16 (fine for bf16) is not."""
    tbl = dict(rows=4, table_width=32)
    assert kernel_eligible(128, 32, jnp.int8, **tbl)
    assert not kernel_eligible(128, 16, jnp.int8, **tbl)
    assert kernel_eligible(128, 16, jnp.bfloat16, **tbl)
    assert kernel_eligible(128, 32, jnp.float8_e4m3fn, **tbl)


# ------------------------------------------------------- capacity math


def test_int8_arena_admits_2x_blocks_at_equal_hbm():
    """The acceptance bar: at an equal HBM byte budget the int8 arena
    admits >= 1.9x the blocks of bf16 (codes halve; the f32 scales are
    Nkv per block-layer vs BS*Nkv*Dh values — noise at serving shapes)."""
    a = BlockAllocator(2, 64)
    kw = dict(num_layers=28, num_kv_heads=8, head_dim=128)
    b16 = a.bytes_per_block(kv_dtype=jnp.bfloat16, **kw)
    b8 = a.bytes_per_block(kv_dtype=jnp.int8, **kw)
    budget = 1000 * b16
    assert (budget // b8) >= 1.9 * (budget // b16)
    # the tiny test geometry clears the bar too
    kw = dict(num_layers=8, num_kv_heads=CFG.num_key_value_heads,
              head_dim=CFG.head_dim_)
    b16 = a.bytes_per_block(kv_dtype=jnp.bfloat16, **kw)
    b8 = a.bytes_per_block(kv_dtype=jnp.int8, **kw)
    assert ((1000 * b16) // b8) >= 1.9 * 1000


# ---------------------------------------------------------- serve paths


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=2, cache_dtype=jnp.float32)
    return params, eng


def _serve(eng, **kw):
    base = dict(capacity=64, kv_block_size=BS, kv_blocks=48)
    base.update(kw)
    return eng.serve(**base)


def _rollout(srv, prompts, max_new=12):
    reqs = [srv.submit(p, max_new) for p in prompts]
    srv.run_until_idle()
    toks = [list(r.tokens) for r in reqs]
    srv.close()
    return toks


PROMPTS = [
    np.array([5, 9, 2, 14], np.int32),
    np.array([7, 3, 1], np.int32),
    np.array([11, 4, 9, 2, 6, 1, 13, 8, 3], np.int32),
]


def _match_frac(a, b):
    per = [
        sum(x == y for x, y in zip(ta, tb)) / max(len(ta), len(tb), 1)
        for ta, tb in zip(a, b)
    ]
    return sum(per) / len(per)


def test_serve_int8_kv_tracks_bf16(setup):
    """The drift-tolerance harness: greedy rollouts from an int8-KV server
    track the exact-KV server's tokens. A tiny random-init model is the
    WORST case for quantization drift (near-tied logits everywhere), so
    the floor here is deliberately below the 0.95 the bench asserts on
    the real-geometry chip workload — what this test pins down is that
    the quantized path decodes sanely and the harness measures it."""
    params, eng = setup
    base = _rollout(_serve(eng), PROMPTS)
    q8 = _rollout(_serve(eng, kv_dtype="int8"), PROMPTS)
    assert all(len(t) == 12 for t in q8)  # full rollouts, no crashes
    frac = _match_frac(base, q8)
    assert frac >= 0.5, f"int8 KV token match {frac} vs bf16"


def test_serve_int8_kv_interpret_kernel_matches_xla(setup):
    """The serve-side FUSED path: an int8 server decoding through the
    interpret-mode Pallas kernel commits the same tokens as the int8
    server on the XLA fallback (same quantized state evolution; the two
    backends read identical dequantized values)."""
    params, eng = setup
    xla = _rollout(_serve(eng, kv_dtype="int8", paged_attn="xla"), PROMPTS)
    import os

    os.environ["PAGED_FORCE_KERNEL"] = "interpret"
    try:
        interp = _rollout(_serve(eng, kv_dtype="int8"), PROMPTS)
    finally:
        del os.environ["PAGED_FORCE_KERNEL"]
    assert xla == interp


def test_serve_int8_spec_verify(setup):
    """Speculative decoding over a quantized arena: the verify traversal
    writes its K+1 entries through the quantizing scatter and rolls back
    by position rewind — the rollout completes and tracks bf16."""
    params, eng = setup
    base = _rollout(_serve(eng, speculate=4), PROMPTS)
    q8 = _rollout(_serve(eng, speculate=4, kv_dtype="int8"), PROMPTS)
    assert all(len(t) == 12 for t in q8)
    assert _match_frac(base, q8) >= 0.5


def test_serve_int8_chunked_prefill(setup):
    """Chunked admission dequantizes the already-written window between
    chunks and requantizes at each scatter — long prompts admit and
    decode sanely on a quantized arena."""
    params, eng = setup
    long_p = np.arange(1, 25, dtype=np.int32) % CFG.vocab_size
    base = _rollout(_serve(eng, prefill_chunk=8), [long_p], max_new=8)
    q8 = _rollout(
        _serve(eng, prefill_chunk=8, kv_dtype="int8"), [long_p], max_new=8
    )
    assert len(q8[0]) == 8
    assert _match_frac(base, q8) >= 0.5


def test_kv_dtype_validation(setup):
    params, eng = setup
    with pytest.raises(ValueError, match="kv_dtype"):
        eng.serve(capacity=32, kv_dtype="int8")  # dense: no blocks
    with pytest.raises(ValueError, match="kv_dtype must be one of"):
        eng.serve(
            capacity=32, kv_block_size=BS, kv_blocks=8, kv_dtype="int4"
        )
    srv = _serve(eng)  # default stays bf16 == exact storage
    assert srv.kv_dtype == "bf16" and not srv.kv_quantized
    assert srv.kv_store_dtype == jnp.dtype(jnp.float32)  # engine cache dtype
    srv.close()


def test_arena_bytes_gauge_and_helper(setup):
    """server_arena_bytes{dtype=} reports the REAL device allocation: the
    allocator helper's figure equals the state leaves' nbytes, and the
    int8 arena (same block count) is under ~52% of bf16's (codes halve,
    f32 cache dtype here makes it a quarter + scales)."""
    from llm_sharding_tpu.obs.metrics import ARENA_BYTES
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    params, eng = setup
    srv = _serve(eng)
    state_bytes = (
        srv.state.k.nbytes + srv.state.v.nbytes
        + (srv.state.k_scale.nbytes + srv.state.v_scale.nbytes
           if srv.kv_quantized else 0)
    )
    assert srv.arena_bytes_device == state_bytes
    q = _serve(eng, kv_dtype="int8")
    q_bytes = (
        q.state.k.nbytes + q.state.v.nbytes
        + q.state.k_scale.nbytes + q.state.v_scale.nbytes
    )
    assert q.arena_bytes_device == q_bytes
    assert q.arena_bytes_device < 0.52 * srv.arena_bytes_device
    _update_load_gauges()
    assert ARENA_BYTES.labels(dtype="bf16").value == srv.arena_bytes_device
    assert ARENA_BYTES.labels(dtype="int8").value == q.arena_bytes_device
    srv.close(), q.close()
    _update_load_gauges()
    assert ARENA_BYTES.labels(dtype="int8").value == 0  # closed servers out


def test_host_tier_round_trip_int8_byte_exact(setup):
    """Radix demote → restore of a QUANTIZED prefix: codes AND scales
    come back byte-identical (the 4-component host_kv tuple), and the
    host-tier hit still decodes."""
    params, eng = setup
    srv = _serve(
        eng, kv_dtype="int8", prefix_cache="host", host_pool_blocks=16
    )
    p1 = (np.arange(2, 2 + 3 * BS, dtype=np.int32)) % CFG.vocab_size
    r1 = srv.submit(p1, 5)
    srv.run_until_idle()
    assert len(r1.tokens) == 5
    node = srv._radix.root.children[int(p1[0])]
    blocks_before = [int(b) for b in node.blocks][:3]
    before = srv._read_arena_blocks(blocks_before)
    assert len(before) == 4  # k, v, k_scale, v_scale
    assert before[0].dtype == np.int8 and before[2].dtype == np.float32
    assert srv._radix.demote_all() > 0
    assert len(node.host_kv) == 4  # quantized components demote together
    # stream back WITHOUT an admission in between: take() restores the
    # demoted node into fresh device blocks — the pure demote→restore
    # round trip must be byte-exact for codes AND scales. (A radix-hit
    # ADMISSION afterwards re-scatters shared blocks through the
    # quantizing path, which may snap scales — that is the documented
    # requant drift, not a tiering bug, hence the comparison here.)
    with srv._mutex:
        ref = srv._radix.take(p1, 3 * BS)
    assert ref is not None and ref.n == 3 * BS
    after = srv._read_arena_blocks(list(ref.blocks)[:3])
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    with srv._mutex:
        srv._radix.release(ref)
    # and a host-tier hit still serves end to end
    p2 = np.concatenate([p1, np.array([3, 1], np.int32)])
    r2 = srv.submit(p2, 5)
    srv.run_until_idle()
    assert len(r2.tokens) == 5
    st = srv.prefix_cache_stats()
    assert st["host_hit_tokens"] >= 3 * BS
    srv._alloc.check(), srv._radix.check()
    srv.close()


def test_snapshot_restore_int8_continues_identically(setup):
    """kv_dtype + the scale arenas ride the checkpoint: a mid-decode int8
    snapshot restores (kv_quantized, same arena dtype) and the revived
    daemon finishes each request with EXACTLY the tokens the uninterrupted
    run produced — quantized state is still deterministic state."""
    params, eng = setup
    full = _rollout(_serve(eng, kv_dtype="int8"), PROMPTS)
    srv = _serve(eng, kv_dtype="int8")
    reqs = [srv.submit(p, 12) for p in PROMPTS]
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    assert snap["serve_kwargs"]["kv_dtype"] == "int8"
    assert snap["state"]["k"].dtype == np.int8
    assert snap["state"]["k_scale"].dtype == np.float32
    from llm_sharding_tpu.runtime.server import PipelineServer

    srv.close()
    srv2 = PipelineServer.restore(eng, snap)
    assert srv2.kv_dtype == "int8" and srv2.kv_quantized
    revived = sorted(
        (r for r in list(srv2._rows) + list(srv2._queue) if r is not None),
        key=lambda r: r.id,
    )
    assert len(revived) == len(PROMPTS)
    srv2.run_until_idle()
    got = [list(r.tokens) for r in revived]
    assert got == full
    srv2.close()


def test_radix_hit_shared_blocks_byte_stable(setup):
    """ISSUE-12 satellite (PR-9 leftover c): a quantized radix-hit
    admission SKIPS re-scattering the already-quantized shared prefix
    blocks. The old path re-quantized the dequantized (compute-dtype-
    rounded) prefix window, re-snapping each shared block's scale and
    drifting codes by ±1 ulp under concurrent readers; with the skip, the
    insert-time quantization is a one-time scale snap — the shared
    blocks' codes AND scales are byte-identical before and after any
    number of hits."""
    params, eng = setup
    srv = _serve(eng, kv_dtype="int8", prefix_cache="hbm")
    p = np.random.default_rng(90).integers(
        1, CFG.vocab_size, 2 * BS + 3
    ).astype(np.int32)
    r1 = srv.submit(p, 6)
    srv.run_until_idle()
    assert r1.error is None
    aligned = (len(p) // BS) * BS
    with srv._mutex:
        ref = srv._radix.take(p, aligned)
        assert ref is not None and ref.n == aligned
        blocks = list(ref.blocks)
        before = [
            np.asarray(a).copy() for a in srv._read_arena_blocks(blocks)
        ]
        srv._radix.release(ref)
    assert len(before) == 4  # codes + scales for K and V
    ext = np.random.default_rng(91).integers(
        1, CFG.vocab_size, 3
    ).astype(np.int32)
    r2 = srv.submit(np.concatenate([p, ext]), 6)
    srv.run_until_idle()
    assert r2.error is None
    assert srv._radix.hit_tokens >= aligned  # the hit really happened
    after = srv._read_arena_blocks(blocks)
    for i, (b, a) in enumerate(zip(before, after)):
        assert np.array_equal(b, np.asarray(a)), (
            f"shared-block component {i} drifted across a radix hit"
        )
    srv.close()
