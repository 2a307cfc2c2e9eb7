"""Pallas flash-attention kernel == XLA cached_attention (interpret mode on
CPU; the same kernel runs compiled on TPU via attention_prefill selection)."""

import numpy as np
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models.cache import POS_SENTINEL
from llm_sharding_tpu.ops.attention import cached_attention
from llm_sharding_tpu.ops.flash_attention import flash_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


def test_flash_matches_xla_basic():
    B, S, C, Nh, Nkv, D = 2, 16, 32, 4, 2, 128
    q = _rand((B, S, Nh, D), 0)
    k = _rand((B, C, Nkv, D), 1)
    v = _rand((B, C, Nkv, D), 2)
    # prefill at offset 8: cache holds 8 old + S new keys
    q_pos = jnp.broadcast_to(jnp.arange(8, 8 + S), (B, S)).astype(jnp.int32)
    kv_pos = jnp.where(
        jnp.arange(C) < 8 + S, jnp.arange(C), POS_SENTINEL
    )[None].astype(jnp.int32)
    kv_pos = jnp.broadcast_to(kv_pos, (B, C))

    want = cached_attention(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_ragged_block_q_padding():
    """S not a multiple of the 128-token query block exercises the pad path."""
    B, S, C, Nh, Nkv, D = 1, 130, 256, 2, 2, 128
    q = _rand((B, S, Nh, D), 3)
    k = _rand((B, C, Nkv, D), 4)
    v = _rand((B, C, Nkv, D), 5)
    q_pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    kv_pos = jnp.where(jnp.arange(C) < S, jnp.arange(C), POS_SENTINEL)[None]
    kv_pos = jnp.broadcast_to(kv_pos, (B, C)).astype(jnp.int32)

    want = cached_attention(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_decode_matches_full_capacity():
    """Decode-shaped attention over only the LIVE blocks (the paged
    successor of the retired ``bucketed_decode_attention`` — block
    granularity instead of a lax.switch whose branch copies made it slower
    than full capacity) == dense attention over the whole capacity, for
    live lengths straddling block boundaries."""
    from llm_sharding_tpu.ops.paged_attention import paged_attention_xla

    B, C, BS, Nh, Nkv, D = 2, 1024, 256, 4, 2, 64
    T = C // BS
    k = _rand((B, C, Nkv, D), 10)
    v = _rand((B, C, Nkv, D), 11)
    # the dense cache reinterpreted as B*T arena blocks + trash block 0:
    # row b's logical column c lives in arena block 1 + b*T + c // BS —
    # head-major blocks, as layer 1 of a two-layer stack (layer 0 zeros)
    def as_arena(x):
        blocks = jnp.concatenate(
            [jnp.zeros((1, BS, Nkv, D), x.dtype),
             x.reshape(B * T, BS, Nkv, D)]
        )
        blocks = jnp.transpose(blocks, (0, 2, 1, 3))  # [NB, Nkv, BS, D]
        return jnp.stack([jnp.zeros_like(blocks), blocks])

    k_arena, v_arena = as_arena(k), as_arena(v)
    for live in (3, 255, 256, 257, 600, 1023):
        q = _rand((B, 1, Nh, D), 12 + live)
        q_pos = jnp.full((B, 1), live, jnp.int32)
        kv_pos = jnp.where(jnp.arange(C) <= live, jnp.arange(C), POS_SENTINEL)
        kv_pos = jnp.broadcast_to(kv_pos[None], (B, C)).astype(jnp.int32)
        want = cached_attention(q, k, v, q_pos, kv_pos)
        # map only the blocks covering the live prefix; the rest stay on
        # the trash block, masked by the sentinel kv positions
        n_live = live // BS + 1
        tbl = np.zeros((B, T), np.int32)
        for b in range(B):
            tbl[b, :n_live] = 1 + b * T + np.arange(n_live)
        got = paged_attention_xla(
            q, k_arena, v_arena, 1, jnp.asarray(tbl), q_pos, kv_pos
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_with_padded_rows():
    """Sentinel query positions (padded batch rows) stay finite and match."""
    B, S, C, Nh, Nkv, D = 2, 8, 16, 2, 2, 128
    q = _rand((B, S, Nh, D), 6)
    k = _rand((B, C, Nkv, D), 7)
    v = _rand((B, C, Nkv, D), 8)
    idx = jnp.arange(S, dtype=jnp.int32)
    plen = jnp.array([8, 5])
    q_pos = jnp.where(idx[None] < plen[:, None], idx[None], POS_SENTINEL)
    kv_idx = jnp.arange(C, dtype=jnp.int32)
    kv_pos = jnp.where(kv_idx[None] < plen[:, None], kv_idx[None], POS_SENTINEL)

    want = cached_attention(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, interpret=True)
    assert np.isfinite(np.asarray(got)[1, :5]).all()
    np.testing.assert_allclose(
        np.asarray(got)[1, :5], np.asarray(want)[1, :5], atol=2e-5
    )
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0], atol=2e-5)


def test_flash_gqa_fold_llama3_geometry():
    """G=4 (llama3-8b head geometry: 32 q heads / 8 kv heads — scaled down in
    head count, exact in G) exercises the GQA fold: query heads sharing a KV
    head ride one folded row axis, with S not a multiple of the query block."""
    B, S, C, Nh, Nkv, D = 2, 33, 128, 8, 2, 128
    q = _rand((B, S, Nh, D), 30)
    k = _rand((B, C, Nkv, D), 31)
    v = _rand((B, C, Nkv, D), 32)
    q_pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    kv_pos = jnp.where(jnp.arange(C) < S, jnp.arange(C), POS_SENTINEL)[None]
    kv_pos = jnp.broadcast_to(kv_pos, (B, C)).astype(jnp.int32)

    want = cached_attention(q, k, v, q_pos, kv_pos)
    got = flash_attention(q, k, v, q_pos, kv_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_multi_block_recurrence_interpret(monkeypatch):
    """Force multiple query AND KV blocks at tiny shapes (the production
    512/1024 blocks mean small interpret tests otherwise run a single block,
    never exercising the online-softmax cross-block recurrence, the acc/m/l
    init-correct-finish phases, or the q/kv pad paths)."""
    from llm_sharding_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "BLOCK_Q", 16)
    monkeypatch.setattr(fa, "BLOCK_K", 32)

    B, S, C, Nh, Nkv, D = 2, 37, 70, 4, 2, 8  # ragged: pads both axes
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, C, Nkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, C, Nkv, D)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32) + 33, (B, S))
    kvpos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))

    got = fa.flash_attention(q, k, v, qpos, kvpos, interpret=True)
    want = cached_attention(q, k, v, qpos, kvpos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
