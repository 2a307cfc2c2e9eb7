"""Ring attention + context parallelism: exact equivalence with the
single-device path (the long-context capability the reference lacks,
SURVEY.md §5)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import POS_SENTINEL, init_cache
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.ops.attention import cached_attention
from llm_sharding_tpu.ops.ring_attention import ring_attention
from llm_sharding_tpu.parallel.context import context_mesh, context_prefill
from llm_sharding_tpu.parallel.mesh import SEQ_AXIS

CFG = tiny_llama(num_hidden_layers=4)


def _reference_attention(q, k, v, q_pos, kv_pos):
    """Single-device oracle via cached_attention (cache == the whole seq)."""
    return cached_attention(q, k, v, q_pos, kv_pos)


def test_ring_attention_matches_dense():
    B, S, Nh, Nkv, D = 2, 32, 4, 2, 16
    n_dev = 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)

    want = _reference_attention(q, k, v, pos, pos)

    mesh = context_mesh(n_dev)
    got = jax.jit(
        shard_map(
            lambda q, k, v, qp, kp: ring_attention(q, k, v, qp, kp, SEQ_AXIS),
            mesh=mesh,
            in_specs=(P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None, SEQ_AXIS),
                      P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
            out_specs=P(None, SEQ_AXIS),
            check_vma=False,
        )
    )(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_attention_with_padding():
    """Sentinel-position pads must be excluded globally, and fully-masked
    rows (queries before any valid key) return zeros, not NaN."""
    B, S, Nh, Nkv, D = 1, 16, 2, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, S, Nh, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Nkv, D)), jnp.float32)
    idx = jnp.arange(S, dtype=jnp.int32)
    pos = jnp.where(idx < 10, idx, POS_SENTINEL)[None]  # last 6 are pads

    want = _reference_attention(q, k, v, pos, pos)
    mesh = context_mesh(4)
    got = jax.jit(
        shard_map(
            lambda q, k, v, qp, kp: ring_attention(q, k, v, qp, kp, SEQ_AXIS),
            mesh=mesh,
            in_specs=(P(None, SEQ_AXIS),) * 3 + (P(None, SEQ_AXIS),) * 2,
            out_specs=P(None, SEQ_AXIS),
            check_vma=False,
        )
    )(q, k, v, pos, pos)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:, :10], want[:, :10], atol=2e-5)


def test_context_prefill_matches_monolith():
    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    B, S = 1, 32
    rng = np.random.default_rng(3)
    ids = rng.integers(0, CFG.vocab_size, (B, S)).astype(np.int32)

    cache = init_cache(CFG, B, S, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    want, _ = llama.forward(CFG, params, jnp.asarray(ids), cache, positions)

    mesh = context_mesh(8)
    got = context_prefill(CFG, mesh, params, ids, full_logits=True)
    np.testing.assert_allclose(got, np.asarray(want), atol=3e-4, rtol=2e-3)

    # default mode: last-token logits only, psum-assembled [B, V]
    got_last = context_prefill(CFG, mesh, params, ids)
    assert got_last.shape == (B, CFG.vocab_size)
    np.testing.assert_allclose(got_last, np.asarray(want)[:, -1], atol=3e-4, rtol=2e-3)


def test_context_prefill_padded():
    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    B, S, real = 1, 32, 27
    rng = np.random.default_rng(4)
    ids = np.zeros((B, S), np.int32)
    ids[0, :real] = rng.integers(0, CFG.vocab_size, real)

    cache = init_cache(CFG, B, S, dtype=jnp.float32)
    idx = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.where(idx < real, idx, POS_SENTINEL)[None]
    want, _ = llama.forward(CFG, params, jnp.asarray(ids), cache, positions)

    mesh = context_mesh(8)
    got = context_prefill(
        CFG, mesh, params, ids, prompt_len=np.array([real]), full_logits=True
    )
    np.testing.assert_allclose(
        got[:, :real], np.asarray(want)[:, :real], atol=3e-4, rtol=2e-3
    )

    # default mode picks the LAST REAL position, not the padded tail
    got_last = context_prefill(CFG, mesh, params, ids, prompt_len=np.array([real]))
    np.testing.assert_allclose(
        got_last, np.asarray(want)[:, real - 1], atol=3e-4, rtol=2e-3
    )


def test_indivisible_length_rejected():
    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    mesh = context_mesh(8)
    with pytest.raises(ValueError, match="divisible"):
        context_prefill(CFG, mesh, params, np.zeros((1, 30), np.int32))


def test_context_prefill_to_decode_token_exact():
    """r2 next-#6 acceptance: ring-attention prefill emits a decode cache and
    greedy decode from it matches the monolithic oracle token-exact — the
    long-context path is a serving feature, not a scorer demo."""
    from llm_sharding_tpu.parallel.context import context_generate
    from llm_sharding_tpu.runtime.generate import generate

    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    # padded batch: rows shorter than the (divisible) padded width
    ids = rng.integers(0, CFG.vocab_size, (2, 32)).astype(np.int32)
    plen = np.array([29, 32], np.int32)

    mesh = context_mesh(8)
    got = context_generate(
        CFG, mesh, params, ids, 12, prompt_len=plen, cache_dtype=jnp.float32
    )
    want = generate(
        CFG, params, ids, 12, prompt_len=plen, cache_dtype=jnp.float32
    )
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.lengths, want.lengths)


def test_context_prefill_to_decode_sampled():
    """Seeded sampling through the handoff matches the monolith (same key
    chain: one split for the first token, one per decode step)."""
    from llm_sharding_tpu.parallel.context import context_generate
    from llm_sharding_tpu.runtime.generate import generate

    params = llama.init_params(CFG, jax.random.key(2), dtype=jnp.float32)
    rng = np.random.default_rng(6)
    ids = rng.integers(0, CFG.vocab_size, (1, 16)).astype(np.int32)

    mesh = context_mesh(4)
    got = context_generate(
        CFG, mesh, params, ids, 10, temperature=0.8, top_k=9, seed=3,
        cache_dtype=jnp.float32,
    )
    want = generate(
        CFG, params, ids, 10, temperature=0.8, top_k=9, seed=3,
        cache_dtype=jnp.float32,
    )
    np.testing.assert_array_equal(got.tokens, want.tokens)


def test_context_prefill_to_decode_gpt2():
    """Context parallelism for the second model family: gpt2 ring-attention
    prefill (learned positions added at embed; nothing positional inside the
    layers) → decode from the assembled cache, token-exact vs the monolith."""
    from llm_sharding_tpu.models import gpt2
    from llm_sharding_tpu.models.config import tiny_gpt2
    from llm_sharding_tpu.parallel.context import context_generate
    from llm_sharding_tpu.runtime.generate import generate

    cfg = tiny_gpt2(num_hidden_layers=4)
    params = gpt2.init_params(cfg, jax.random.key(4), dtype=jnp.float32)
    rng = np.random.default_rng(9)
    ids = rng.integers(1, cfg.vocab_size, (2, 16)).astype(np.int32)
    plen = np.array([13, 16], np.int32)

    mesh = context_mesh(4)
    got = context_generate(
        cfg, mesh, params, ids, 10, prompt_len=plen, cache_dtype=jnp.float32
    )
    want = generate(
        cfg, params, ids, 10, prompt_len=plen, cache_dtype=jnp.float32
    )
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.lengths, want.lengths)
