"""Flash-style chunked prefill over the paged arena (ISSUE 14).

The contract under test: chunked admission ATTENDS THE ARENA IN PLACE
(``ops/paged_attention.paged_prefill`` — no gathered-window round trip)
and is token-identical to the monolithic oracle AND across backends
(interpret-emulated kernel vs the exact XLA gather) on plain, quantized
and radix-hit workloads; a radix hit whose leftover suffix needs chunked
prefill ADMITS through it with a prefix offset instead of falling back
cold (the old one-shot-only restriction — the regression test here);
and the decode kernel's ``blocks_per_step`` batching is bit-identical
to the single-block grid.

``PAGED_TEST_BLOCK_SIZE`` parameterizes the block size (CI reruns at 4:
block-boundary stress — chunks straddle block seams) and
``PAGED_FORCE_KERNEL=interpret`` drives the whole suite through the
chunked-prefill kernel code path on the CPU mesh.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.models.cache import POS_SENTINEL
from llm_sharding_tpu.ops import paged_attention as pa
from llm_sharding_tpu.ops.paged_attention import (
    auto_blocks_per_step, paged_attention_tpu, paged_attention_xla,
    paged_prefill, paged_prefill_tpu, prefill_walk,
)
from llm_sharding_tpu.ops.quant import fp8_kv_supported, kv_qmax, kv_quantize
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from paged_arena import counted, row_wise_chunk_write

CFG = tiny_llama(num_hidden_layers=8, max_position_embeddings=512)
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "8"))
CAP = 256
CHUNK = 16


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(11), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return [int(x) for x in res.tokens[0, len(p): int(res.lengths[0])]]


def serve(eng, **kw):
    kw.setdefault("capacity", CAP)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("kv_blocks", 4 * CAP // BS + 1)
    kw.setdefault("prefill_chunk", CHUNK)
    return eng.serve(**kw)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def drive(srv, reqs):
    while any(not r.done for r in reqs):
        srv.step()
    return [list(r.tokens) for r in reqs]


# ---------------------------------------------------------------- op level


def _op_case(seed=0, S=12, T=8, sentinel_from=20, layer=2):
    """The ops' positional arguments ``(q, k_stack, v_stack, layer, table,
    q_positions, kv_positions)`` over a head-major stack of three layers,
    every one different, attended at ``layer``."""
    rng = np.random.default_rng(seed)
    L, Nkv, G, D, NB = 3, 2, 2, 16, 24
    bs = 4
    W = T * bs
    ka = jnp.asarray(rng.normal(size=(L, NB, Nkv, bs, D)).astype(np.float32))
    va = jnp.asarray(rng.normal(size=(L, NB, Nkv, bs, D)).astype(np.float32))
    tbl = jnp.asarray(rng.integers(1, NB, (2, T)).astype(np.int32))
    tbl = tbl.at[0, T - 2:].set(0)  # trash tail on row 0
    kvpos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None], (2, W))
    kvpos = jnp.where(kvpos < sentinel_from, kvpos, jnp.int32(2**30))
    q = jnp.asarray(
        rng.normal(size=(2, S, Nkv * G, D)).astype(np.float32)
    )
    qp = jnp.broadcast_to(
        jnp.arange(8, 8 + S, dtype=jnp.int32)[None], (2, S)
    )
    return q, ka, va, layer, tbl, qp, kvpos


def test_paged_prefill_interpret_matches_xla_all_bps():
    args = _op_case()
    ref = paged_attention_xla(*args)
    for bps in (1, 2, 4):
        out = paged_prefill_tpu(*args, interpret=True, blocks_per_step=bps)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_prefill_nlive_clamp_is_inert():
    # nlive covering the written frontier (20 cols / bs=4 -> 5 blocks)
    # must not change the result: everything past it is sentinel-masked
    args = _op_case()
    ref = paged_attention_xla(*args)
    out = paged_prefill_tpu(
        *args, interpret=True,
        nlive=jnp.asarray([5, 5], jnp.int32), blocks_per_step=2,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_prefill_quantized_fused_dequant():
    q, ka, va, *rest = _op_case(seed=3)
    sk = jnp.max(jnp.abs(ka), axis=(3, 4)) / kv_qmax(jnp.int8)  # [L,NB,Nkv]
    sv = jnp.max(jnp.abs(va), axis=(3, 4)) / kv_qmax(jnp.int8)
    kq = kv_quantize(ka, sk[..., None, None], jnp.int8)
    vq = kv_quantize(va, sv[..., None, None], jnp.int8)
    ref = paged_attention_xla(q, kq, vq, *rest, k_scale=sk, v_scale=sv)
    out = paged_prefill_tpu(
        q, kq, vq, *rest, interpret=True,
        k_scale=sk, v_scale=sv, blocks_per_step=2,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_decode_blocks_per_step_matches_single_block():
    q, ka, va, lyr, tbl, qp, kvpos = _op_case(S=1, sentinel_from=32)
    args = (q[:, :1], ka, va, lyr, tbl, qp[:, :1], kvpos)
    ref = paged_attention_xla(*args)
    for bps in (1, 2, 4, 8):
        out = paged_attention_tpu(
            *args, interpret=True, blocks_per_step=bps,
        )
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_auto_blocks_per_step():
    assert auto_blocks_per_step(8, 4) == 8
    assert auto_blocks_per_step(7, 4) == 1  # must divide the table width
    assert auto_blocks_per_step(64, 64) == 8
    assert auto_blocks_per_step(64, 512) == 1  # tile cap
    assert auto_blocks_per_step(6, 8) == 2


@pytest.mark.parametrize("shape, bps", [
    # (table, block, key/value heads, key + value lanes, bytes an element)
    ((128, 32, 4, 256, 2), 16),  # Qwen2.5-7B: the score tiles' 2,048 lanes
    ((288, 32, 4, 256, 2), 16),  # Keye: 32 KiB a tile, 2 MiB the two slots
    ((128, 32, 8, 256, 2), 8),  # a Qwen2.5-14B stage
    ((128, 32, 16, 256, 2), 4),  # OLMoE-1B-7B: 128 KiB a tile
    ((256, 32, 8, 384, 2), 8),  # MiMo's window layers: keys of 256 lanes
    ((256, 32, 4, 384, 2), 16),  # MiMo's full layers
    ((128, 32, 1, 640, 2), 16),  # a latent arena: 512 tokens a cell
    ((128, 32, 4, 256, 1), 16),  # int8: the lanes still bound it
    ((128, 32, 4, 1024, 4), 4),  # float32 keys of 512 lanes: the bytes do
    ((128, 128, 32, 256, 2), 1),  # one block is already 4,096 lanes
    ((33, 16, 4, 256, 2), 1), ((24, 8, 4, 32, 4), 8),  # divides the table
])
def test_decode_blocks_per_cell(shape, bps):
    """The decode kernel's cell is as wide as its K and V tiles, double
    buffered, its score tiles and 512 tokens allow: a function of the
    shapes, no operand count in it."""
    from llm_sharding_tpu.ops.paged_attention import (
        DECODE_CELL_VMEM, decode_blocks_per_cell,
    )

    assert decode_blocks_per_cell(*shape) == bps
    T, BS, Nkv, lanes, size = shape
    assert T % bps == 0
    assert 2 * bps * Nkv * BS * lanes * size <= DECODE_CELL_VMEM or bps == 1


@pytest.mark.parametrize("shape, bps", [
    # (table, block, lanes of a stored index key, bytes an element)
    ((288, 32, 128, 2), 96),  # Keye: 8 KiB a block, three cells of 3,072
    ((256, 32, 128, 2), 64), ((128, 32, 128, 2), 64),  # 128 copies: too many
    ((64, 32, 128, 2), 64),  # one cell
    ((288, 32, 512, 2), 32),  # a wide index key: the slot's bytes bound it
    ((288, 32, 128, 1), 96),  # an fp8 index key: the copies still do
    ((512, 16, 128, 2), 64),  # 16-token blocks: eight to a lane tile
    ((33, 32, 128, 2), 33),  # no width's scores are whole lane tiles: one
    ((16, 8, 128, 4), 16),  # cell, the table
])
def test_index_blocks_per_cell(shape, bps):
    """The score kernel's cell is as wide as divides the table, stays within
    the copies and the bytes a slot of its double buffer may hold and stores
    whole 128-lane tiles of scores: a function of the shapes."""
    from llm_sharding_tpu.ops.paged_attention import (
        INDEX_CELL_BLOCKS, INDEX_CELL_VMEM, index_blocks_per_cell,
    )

    assert index_blocks_per_cell(*shape) == bps
    T, BS, lanes, size = shape
    assert T % bps == 0
    assert bps == T or (
        bps * BS % 128 == 0 and bps <= INDEX_CELL_BLOCKS
        and bps * BS * lanes * size <= INDEX_CELL_VMEM)


def test_paged_prefill_backend_validation():
    args = _op_case()
    with pytest.raises(ValueError, match="expected one of"):
        paged_prefill(*args, backend="bogus")
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="requires a TPU backend"):
            paged_prefill(*args, backend="kernel")


# ---------------------------------------------------------- the live walk


def _slot(plens, col0, S, *, T=16, bs=4, heads=(4, 2), D=16, latent_v=0,
          store=None, trash=(), seed=0, mapped=None, nan_trash=True):
    """One chunk ``[col0, col0 + S)`` of a slot's rows, row ``b`` holding a
    prompt of ``plens[b]`` tokens (0: a padded row): the ops' positional
    arguments and the extra keywords of a quantized arena. A row's table
    maps ``mapped`` columns (default: through the chunk's end) and is trash
    past them; ``trash`` names table entries made trash INSIDE the written
    part; block 0 holds NaN (what parked rows may have left there)."""
    rng = np.random.default_rng(seed)
    Nh, Nkv = heads
    B, L, W = len(plens), 3, T * bs
    Dv = 0 if latent_v else D
    NB = B * T + 1
    ka = rng.normal(size=(L, NB, Nkv, bs, D)).astype(np.float32)
    va = rng.normal(size=(L, NB, Nkv, bs, Dv)).astype(np.float32)
    if nan_trash and store is None:
        ka[:, 0] = np.nan
        va[:, 0] = np.nan
    tbl = np.zeros((B, T), np.int32)
    kvpos = np.full((B, W), POS_SENTINEL, np.int32)
    qpos = np.full((B, S), POS_SENTINEL, np.int32)
    for b, n in enumerate(plens):
        if not n:
            continue
        cols = (col0 + S) if mapped is None else mapped
        nb = -(-cols // bs)
        tbl[b, :nb] = 1 + b * T + np.arange(nb)
        tbl[b, list(trash)] = 0
        written = min(n, col0 + S)
        kvpos[b, :written] = np.arange(written)
        real = np.arange(col0, col0 + S) < n
        qpos[b, real] = col0 + np.flatnonzero(real)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)).astype(np.float32))
    ka, va = jnp.asarray(ka), jnp.asarray(va)
    kw = {}
    if store is not None:
        sk = jnp.max(jnp.abs(ka), axis=(3, 4)) / kv_qmax(store)
        sv = jnp.max(jnp.abs(va), axis=(3, 4)) / kv_qmax(store)
        ka = kv_quantize(ka, sk[..., None, None], store)
        va = kv_quantize(va, sv[..., None, None], store)
        kw = dict(k_scale=sk, v_scale=sv)
    args = (q, ka, va, 1, jnp.asarray(tbl), jnp.asarray(qpos),
            jnp.asarray(kvpos))
    return args, kw


def _kernel(args, **kw):
    """The kernel emulated, traced afresh (``BLOCK_Q_PREFILL`` is read at
    trace time and some cases change it)."""
    raw = paged_prefill_tpu.__wrapped__
    return jax.jit(lambda *a: raw(*a, interpret=True, **kw))(*args)


def _check(args, kw, latent_v=0, **kernel_kw):
    """Real queries read what the gather path gives them; every other row
    of the result is exactly zero, whatever the buffers held."""
    out = np.asarray(_kernel(args, latent_v=latent_v, **kw, **kernel_kw))
    ref = np.asarray(paged_attention_xla(*args, latent_v=latent_v, **kw))
    real = np.asarray(args[5]) < POS_SENTINEL
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[real], ref[real], rtol=2e-5, atol=2e-5)
    assert not out[~real].any()
    return out


_FP8 = pytest.param(
    "fp8", marks=pytest.mark.skipif(
        not fp8_kv_supported(), reason="no fp8 on this backend"
    ),
)
_WALKS = {
    # padded rows in a slot: 1, 2, 3 of 4 rows dead
    "one_row_dead": dict(plens=(40, 0, 33, 48), col0=32, S=16),
    "two_rows_dead": dict(plens=(0, 40, 0, 48), col0=32, S=16),
    "three_rows_dead": dict(plens=(0, 0, 37, 0), col0=32, S=16),
    # a second and a later chunk: the table maps the whole bucket, the
    # cells past the chunk's own keys are past every tile's frontier
    "first_chunk": dict(plens=(64, 0, 0, 9), col0=0, S=16, mapped=64),
    "second_chunk": dict(plens=(64, 0, 0, 20), col0=16, S=16, mapped=64),
    "fourth_chunk": dict(plens=(64, 0, 0, 50), col0=48, S=16, mapped=64),
    # a radix prefix (prefix_off 24: the suffix's first chunk starts there)
    # with trash entries inside the table
    "radix_prefix_trash_inside": dict(
        plens=(50, 44, 0, 0), col0=24, S=16, trash=(1, 4),
    ),
    "radix_prefix_a_whole_cell_trash": dict(
        plens=(50, 0, 0, 41), col0=24, S=16, trash=(0, 1, 2, 3),
    ),
    # the fold: GQA 7:1 (Qwen2.5-7B), MHA (OLMoE), 64 heads over one
    # latent head (absorbed latent attention)
    "gqa_7_to_1": dict(plens=(30, 0, 0, 0), col0=16, S=16, heads=(7, 1)),
    "mha": dict(plens=(0, 30, 22, 0), col0=16, S=16, heads=(4, 4)),
    "latent": dict(
        plens=(30, 0, 19, 0), col0=16, S=16, heads=(8, 1), D=24,
        latent_v=16,
    ),
}


@pytest.mark.parametrize("case", sorted(_WALKS))
def test_live_walk_matches_the_gather_on_real_queries(case):
    spec = dict(_WALKS[case])
    latent_v = spec.get("latent_v", 0)
    args, kw = _slot(spec.pop("plens"), spec.pop("col0"), spec.pop("S"),
                     **spec)
    _check(args, kw, latent_v=latent_v)


@pytest.mark.parametrize("bps", [1, 2, 4, 8])
def test_live_walk_at_every_blocks_per_step(bps):
    args, kw = _slot((60, 0, 35, 0), 32, 16, trash=(2,))
    _check(args, kw, blocks_per_step=bps)
    walk = prefill_walk(
        *args[4:], q_heads=4, kv_heads=2, blocks_per_step=bps
    )
    # row 0: 12 entries, row 2: 9 (35 tokens), two heads, one query tile
    assert int(walk.steps) == 2 * (-(-12 // bps) + -(-9 // bps))


@pytest.mark.parametrize("store", ["int8", _FP8])
def test_live_walk_over_a_quantized_arena(store):
    dt = jnp.int8 if store == "int8" else jnp.float8_e4m3fn
    args, kw = _slot((0, 45, 0, 38), 32, 16, store=dt, trash=(3,))
    _check(args, kw)


@pytest.mark.parametrize("tile", [8, 16])
def test_a_short_prompt_in_a_long_bucket_walks_no_dead_query_tile(
    tile, monkeypatch
):
    """MHA, a chunk of 32 with 11 real queries: at a query tile of 8 the
    chunk is four tiles of which two hold a real query, at 16 two of which
    one does; the dead ones are in no run and their rows read zero."""
    monkeypatch.setattr(pa, "BLOCK_Q_PREFILL", tile)
    args, kw = _slot((43, 0, 0, 0), 32, 32, heads=(2, 2))
    _check(args, kw)
    walk = prefill_walk(*args[4:], q_heads=2, kv_heads=2)
    live_tiles = -(-11 // tile)
    assert np.count_nonzero(np.asarray(walk.nent)) == 2 * live_tiles
    # each live run walks the 43 written tokens: 11 entries, two cells
    assert int(walk.steps) == 2 * live_tiles * 2


def test_a_chunk_whose_every_row_is_dead_walks_nothing_and_reads_zero():
    """Grid length 0: every query at the sentinel (a bucket's chunk past
    the prompt, or an empty slot). The kernel runs no step, so its output
    buffer is whatever it was: the result is zeros all the same."""
    args, kw = _slot((20, 0, 0, 9), 32, 16, mapped=48)
    assert not (np.asarray(args[5]) < POS_SENTINEL).any()
    walk = prefill_walk(*args[4:], q_heads=4, kv_heads=2)
    assert int(walk.steps) == 0 and not np.asarray(walk.nent).any()
    out = _check(args, kw)
    assert out.shape == args[0].shape and not out.any()


def test_the_walk_lists_hold_one_entry_more_than_the_most_steps():
    """The pipeline evaluates the index maps one step AHEAD of the one it
    runs: at the last step of a walk that is ALL live it reads
    ``run_of[steps]``. That entry exists and names a run of the call (the
    core halts on an index past the list; PERF.md, PR 28)."""
    B, T, bs, S = 2, 8, 4, 16
    args, kw = _slot((32, 32), 16, S, T=T, bs=bs, heads=(4, 2))
    walk = prefill_walk(*args[4:], q_heads=4, kv_heads=2, blocks_per_step=2)
    runs = B * 2 * 1
    most = runs * (T // 2)
    assert int(walk.steps) == most  # every cell of every run is live
    assert walk.run_of.shape == (most + 1,)
    run_of = np.asarray(walk.run_of)
    assert run_of[most] == runs - 1 and (np.diff(run_of) >= 0).all()
    assert (np.asarray(walk.start) + -(-np.asarray(walk.nent) // 2)
            <= most).all()
    _check(args, kw, blocks_per_step=2)
    # a walk built for another tiling is refused by name, not run
    with pytest.raises(ValueError, match="walk of .* cells was not built"):
        _kernel(args, blocks_per_step=4, walk=walk)


def test_the_walk_finds_padded_rows_whatever_nlive_says():
    """``serve_prefill_chunk`` hands every row of the slot the same
    ``nlive``; the walk is the live row's alone, and ``nlive`` still
    clamps it. Built by the caller or inside the op: one result."""
    args, kw = _slot((0, 0, 64, 0), 16, 16, mapped=64)
    same = jnp.full((4,), 8, jnp.int32)  # (16 + 16) / 4 blocks, every row
    walk = prefill_walk(*args[4:], same, q_heads=4, kv_heads=2)
    assert np.asarray(walk.nent).reshape(4, 2).tolist() == [
        [0, 0], [0, 0], [8, 8], [0, 0]
    ]
    assert int(walk.steps) == 2 * 1  # bps 8: one cell a head
    clamped = prefill_walk(
        *args[4:], jnp.full((4,), 5, jnp.int32), q_heads=4, kv_heads=2
    )
    assert np.asarray(clamped.nent).max() == 5
    out = _check(args, kw, nlive=same)
    np.testing.assert_array_equal(out, _kernel(args, walk=walk))


def test_the_walk_of_a_324_token_prompt_is_84_cells_of_3584():
    """ISSUE 36's arithmetic at Qwen2.5-7B's geometry (4 rows, 28 query /
    4 key/value heads, chunks of 256, 128 table entries of 32 tokens): one
    324-token prompt walks 1 x 4 x 7 x (1 + 2) cells over its two chunks
    where the rectangular grid walked 2 x 1,792."""
    live = walked = 0
    for col0 in (0, 256):
        args, _ = _slot(
            (324, 0, 0, 0), col0, 256, T=128, bs=32, heads=(28, 4), D=8,
            mapped=512,
        )
        nlive = jnp.full((4,), (col0 + 256) // 32, jnp.int32)
        walk = prefill_walk(*args[4:], nlive, q_heads=28, kv_heads=4)
        live += int(walk.steps)
        walked += walk.run_of.shape[0] - 1
    assert (live, walked) == (84, 3584)


# ------------------------------------------------------------- serve level


def test_chunked_prefill_offset0_matches_oracle(setup):
    """Cold chunked admission (offset == 0 equivalence) through the
    arena-native path, chunks straddling block seams at every
    PAGED_TEST_BLOCK_SIZE."""
    params, eng = setup
    srv = serve(eng)
    # 56 tokens: bucket 64 = 4 chunks; at BS=4 each chunk covers 4
    # blocks, at BS=8 a chunk spans 2 — both straddle seams
    ps = [prompt(7, 56), prompt(8, 23)]  # 23: prompt ends mid-block
    reqs = [srv.submit(p, max_new_tokens=6) for p in ps]
    toks = drive(srv, reqs)
    for p, t in zip(ps, toks):
        assert t == oracle(params, p, 6)
    srv.close()


def test_chunked_prefill_interpret_matches_xla_server(setup, monkeypatch):
    """The acceptance oracle: the SAME chunked workload through the
    interpret-emulated kernel vs the exact XLA gather backend — token
    match must be 1.0."""
    params, eng = setup
    ps = [prompt(17, 56), prompt(18, 40)]

    def run(force):
        if force:
            monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
        else:
            monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
        srv = serve(eng, paged_attn="auto" if force else "xla")
        assert srv.attn_impl == ("interpret" if force else "xla")
        toks = drive(srv, [srv.submit(p, max_new_tokens=6) for p in ps])
        srv.close()
        return toks

    assert run(True) == run(False)


def test_radix_hit_long_suffix_admits_chunked(setup, monkeypatch):
    """THE regression test: a radix hit whose leftover suffix needs
    chunked admission used to fall back cold (zero hit tokens); now it
    admits through serve_prefill_chunk at the hit's prefix offset,
    token-identically. The shared prefix deliberately ends MID-BLOCK
    (43 tokens) so the match rounds down to a block boundary."""
    params, eng = setup
    import llm_sharding_tpu.runtime.server as server_mod

    srv = serve(eng, prefix_cache="hbm")
    shared = prompt(21, 43)  # match will round down to (43 // BS) * BS
    p1 = np.concatenate([shared, prompt(22, 9)])
    r1 = srv.submit(p1, max_new_tokens=6)
    drive(srv, [r1])
    assert r1.tokens == oracle(params, p1, 6)

    offs = []
    orig = server_mod.PipelineServer._admit_chunked

    def spy(self, *a, **kw):
        offs.append(kw.get("prefix_off", 0))
        return orig(self, *a, **kw)

    monkeypatch.setattr(server_mod.PipelineServer, "_admit_chunked", spy)
    hit0 = srv._radix.hit_tokens
    # long suffix: bucket(suffix) > prefill_chunk -> needs chunked
    p2 = np.concatenate([shared, prompt(23, 60)])
    r2 = srv.submit(p2, max_new_tokens=6)
    drive(srv, [r2])
    expect_n = (43 // BS) * BS
    assert srv._radix.hit_tokens - hit0 == expect_n, (
        "radix hit with a chunked suffix fell back cold"
    )
    assert offs == [expect_n], (
        "hit did not admit through chunked prefill at its offset"
    )
    assert r2.tokens == oracle(params, p2, 6)
    # the finished chunked row's prompt blocks insert back into the tree
    # (minus the injected final token's block) and a full repeat still
    # serves correctly
    r3 = srv.submit(p2, max_new_tokens=6)
    drive(srv, [r3])
    assert r3.tokens == oracle(params, p2, 6)
    srv._alloc.check()
    srv._radix.check()
    srv.close()


def test_radix_chunked_quantized_token_match(setup):
    """Quantized (int8) chunked admission over a radix hit: the arena-
    native path quantizes fresh chunk KV at insert (no inter-chunk
    dequant round trip) and never rewrites the shared prefix blocks.
    int8 greedy may drift from the f32 oracle (the kv-quant tolerance
    harness owns that); here the contract is internal consistency:
    warm == cold int8 output."""
    params, eng = setup
    shared = prompt(31, 2 * BS)
    p = np.concatenate([shared, prompt(32, 60)])

    def run(cache):
        srv = serve(eng, prefix_cache=cache, kv_dtype="int8")
        if cache != "off":
            rw = srv.submit(np.concatenate([shared, prompt(33, 5)]), 4)
            drive(srv, [rw])  # warm the tree
            hit0 = srv._radix.hit_tokens
        r = srv.submit(p, max_new_tokens=6)
        drive(srv, [r])
        if cache != "off":
            assert srv._radix.hit_tokens - hit0 == 2 * BS
        srv.close()
        return r.tokens

    assert run("hbm") == run("off")


@pytest.mark.parametrize("attn", ["xla", "interpret"])
def test_tile_write_serves_the_tokens_of_the_row_wise_write(
    setup, monkeypatch, attn
):
    """A cold chunked admission of four chunks, then a radix-hit admission
    whose chunks start at ``prefix_off`` > 0: with the chunks' K/V written
    as whole-block tiles the served tokens are the row-wise write's (and
    the oracle's), the shared prefix's blocks hold the same bytes after
    the hit's chunks ran as before, and the counter and the step records
    say which form each run's chunks took."""
    params, eng = setup
    if attn == "interpret":
        monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    else:
        monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
    shared = prompt(61, 24)
    cold = np.concatenate([shared, prompt(62, 32)])  # bucket 64: 4 chunks
    hit = np.concatenate([shared, prompt(63, 40)])

    def run():
        srv = serve(
            eng, prefix_cache="hbm",
            paged_attn="auto" if attn == "interpret" else "xla",
        )
        assert srv.attn_impl == attn
        toks = drive(srv, [srv.submit(cold, max_new_tokens=6)])
        n = srv._radix.match_tokens(hit)
        assert n == 24 // BS * BS
        blocks = [
            b for node, used in srv._radix._walk(hit, n)
            for b in node.blocks[: used // BS]
        ]
        before = [
            np.asarray(a)[:, :, blocks] for a in (srv.state.k, srv.state.v)
        ]
        hit0 = srv._radix.hit_tokens
        toks += drive(srv, [srv.submit(hit, max_new_tokens=6)])
        assert srv._radix.hit_tokens - hit0 == n
        for b, a in zip(before, (srv.state.k, srv.state.v)):
            np.testing.assert_array_equal(np.asarray(a)[:, :, blocks], b)
        recs = [
            r["prefill_kv_blocks"] for r in srv.stepline.snapshot()
            if "prefill_kv_blocks" in r
        ]
        srv._alloc.check()
        srv.close()
        return toks, recs

    (tiles, rec_tiles), n_tiles = counted(run)
    with row_wise_chunk_write():
        (rows, rec_rows), n_rows = counted(run)
    assert tiles == rows == [oracle(params, cold, 6), oracle(params, hit, 6)]
    # 4 + 4 chunks (the hit's suffix of 40 + 24 % BS buckets to 64) of one row
    assert n_tiles == {"tile": 8 * CHUNK // BS, "rows": 0}
    assert n_rows == {"tile": 0, "rows": 8 * CHUNK // BS}
    assert sum(r.get("tile", 0) for r in rec_tiles) == n_tiles["tile"]
    assert all(set(r) == {"tile"} for r in rec_tiles)
    assert all(set(r) == {"rows"} for r in rec_rows)


def test_a_chunk_under_a_block_is_counted_as_rows(setup):
    """A chunk shorter than a block (here by ``prefill_chunk``; in a
    deployment a context-parallel radix admission whose suffix bucket is
    under a block) cannot be whole-block tiles: the row-wise write serves
    it, token for token, and the counter says ``rows``."""
    params, eng = setup
    srv = serve(eng, prefill_chunk=BS // 2)
    p = prompt(71, 2 * BS - 3)  # bucket 2 * BS: four chunks of half a block
    toks, n = counted(lambda: drive(srv, [srv.submit(p, max_new_tokens=5)]))
    assert toks == [oracle(params, p, 5)]
    assert n == {"tile": 0, "rows": 4}
    srv.close()


def test_a_tile_chunk_refuses_a_start_inside_a_block(setup):
    """What the tiles rest on, stated by the host: a chunk of whole blocks
    starts on a block boundary. The row-wise write forgave a start inside
    a block; a tile there would overwrite the head of the block before —
    a shared prefix block."""
    _, eng = setup
    srv = serve(eng)
    with pytest.raises(ValueError, match="block boundary"):
        srv._admit_chunked(
            0, np.zeros((srv.batch_per_slot, 2 * CHUNK), np.int32),
            *[None] * 8, prefix_off=BS + 1,
        )
    assert not srv._admitting_rows
    srv.close()


def test_prefill_path_metrics(setup):
    from llm_sharding_tpu.obs.metrics import (
        PREFILL_BLOCKS_READ, PREFILL_PATH,
    )

    params, eng = setup
    srv = serve(eng)
    b0 = PREFILL_BLOCKS_READ.value
    r = srv.submit(prompt(41, 56), max_new_tokens=4)
    drive(srv, [r])
    # bucket 64 in 4 chunks of 16: frontier blocks per chunk summed
    expect = sum(-(-(off + CHUNK) // BS) for off in range(0, 64, CHUNK))
    assert PREFILL_BLOCKS_READ.value - b0 == expect
    # xla resolution on the CPU mesh (or kernel under the interpret lane)
    want = (
        "kernel" if os.environ.get("PAGED_FORCE_KERNEL") == "interpret"
        else "xla"
    )
    vals = {
        p: PREFILL_PATH.labels(path=p).value
        for p in ("kernel", "xla", "gather")
    }
    assert vals[want] == 1.0
    assert sum(vals.values()) == 1.0
    srv.close()


def test_chunked_prefill_under_live_decode(setup):
    """A chunked admission landing while another slot is mid-decode:
    the interleaved decode cycles (whose parked-slot writes are now
    gated) must neither corrupt the admitting slot nor the live one."""
    params, eng = setup
    srv = serve(eng)
    bg = srv.submit(prompt(51, 6), max_new_tokens=24)
    while not bg.tokens:
        srv.step()
    long_r = srv.submit(prompt(52, 56), max_new_tokens=6)
    toks = drive(srv, [bg, long_r])
    assert toks[0] == oracle(params, prompt(51, 6), 24)
    assert toks[1] == oracle(params, prompt(52, 56), 6)
    srv.close()
