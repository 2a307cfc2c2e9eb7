"""The paged-KV ops against their XLA forms (``ops/paged_attention.py``):
the gather, the block-indexed writes, the decode and prefill kernels in
interpret mode, the score kernel of a selecting decode step. Op level only:
no server, no engine (``test_paged.py`` serves; ``test_paged_programs.py``
reads the compiled and traced step programs).
"""

import functools
from unittest import mock

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama

from paged_arena import (
    LAYER_CASES, LAYERS, _pallas_calls, int8_stack, make_stack,
    others_untouched, window,
)

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
