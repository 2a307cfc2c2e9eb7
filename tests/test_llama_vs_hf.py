"""Golden test: pure-JAX Llama == HF transformers (torch CPU) on tiny configs.

The reference's only numerical oracle is running the full HF model
(``/root/reference/inference.py``, ``utils/node_profiler.py:1238-1331``); this
test makes that comparison automated and exact at the logits level (fp32).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import torch
from transformers import LlamaConfig, LlamaForCausalLM

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import init_cache
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.utils.convert import params_from_hf

CFG = tiny_llama()


@pytest.fixture(scope="module")
def hf_model():
    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        max_position_embeddings=CFG.max_position_embeddings,
        rms_norm_eps=CFG.rms_norm_eps,
        rope_theta=CFG.rope_theta,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def params(hf_model):
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    return params_from_hf(CFG, sd, dtype=jnp.float32)


def hf_logits(hf_model, ids: np.ndarray) -> np.ndarray:
    with torch.no_grad():
        return hf_model(torch.from_numpy(ids)).logits.numpy()


def test_full_sequence_logits_match(hf_model, params):
    B, S = 2, 12
    rng = np.random.default_rng(0)
    ids = rng.integers(0, CFG.vocab_size, (B, S)).astype(np.int32)

    ref = hf_logits(hf_model, ids)

    cache = init_cache(CFG, B, capacity=S, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    logits, cache = llama.forward(CFG, params, jnp.asarray(ids), cache, positions)

    np.testing.assert_allclose(np.asarray(logits), ref, atol=2e-4, rtol=2e-3)
    assert int(cache.length) == S


def test_prefill_then_decode_matches_full(hf_model, params):
    """KV-cached incremental decode == full-sequence forward (the cache
    discipline the reference gets from DynamicCache, here explicit)."""
    B, S_total, S_prefill = 1, 10, 6
    rng = np.random.default_rng(1)
    ids = rng.integers(0, CFG.vocab_size, (B, S_total)).astype(np.int32)
    ref = hf_logits(hf_model, ids)

    cache = init_cache(CFG, B, capacity=S_total, dtype=jnp.float32)
    pre = jnp.asarray(ids[:, :S_prefill])
    positions = jnp.broadcast_to(jnp.arange(S_prefill), (B, S_prefill))
    logits, cache = llama.forward(CFG, params, pre, cache, positions)
    np.testing.assert_allclose(
        np.asarray(logits), ref[:, :S_prefill], atol=2e-4, rtol=2e-3
    )

    for t in range(S_prefill, S_total):
        tok = jnp.asarray(ids[:, t : t + 1])
        pos = jnp.full((B, 1), t, jnp.int32)
        logits, cache = llama.forward(CFG, params, tok, cache, pos)
        np.testing.assert_allclose(
            np.asarray(logits)[:, 0], ref[:, t], atol=2e-4, rtol=2e-3
        )
    assert int(cache.length) == S_total


def test_layer_mask_passthrough(params):
    """Masked-out layers must leave hidden states and cache untouched —
    the mechanism behind ragged pipeline stages."""
    B, S = 1, 5
    ids = jnp.arange(S, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    h = llama.embed(params, ids)
    cache = init_cache(CFG, B, capacity=S, dtype=jnp.float32)

    mask = jnp.array([True, False, True, False])
    h_out, cache_out, _ = llama.forward_layers(
        CFG, params["layers"], h, cache, positions, layer_mask=mask
    )
    # Layers 1 and 3 wrote nothing
    assert np.all(np.asarray(cache_out.k[1]) == 0)
    assert np.all(np.asarray(cache_out.k[3]) == 0)
    assert not np.all(np.asarray(cache_out.k[0]) == 0)

    # Equivalent to running a 2-layer model of layers {0, 2}
    sub_layers = jax.tree.map(lambda a: a[jnp.array([0, 2])], params["layers"])
    sub_cache = init_cache(CFG, B, capacity=S, num_layers=2, dtype=jnp.float32)
    h_sub, _, _ = llama.forward_layers(CFG, sub_layers, h, sub_cache, positions)
    np.testing.assert_allclose(np.asarray(h_out), np.asarray(h_sub), atol=1e-5)


def test_llama3_rope_scaling_matches_hf():
    """Llama-3.x piecewise RoPE frequency scaling parity with HF
    (BASELINE config #4 needs this; ops/rope.py:_llama3_scale_inv_freq)."""
    from llm_sharding_tpu.models.config import RopeScaling

    cfg3 = tiny_llama(
        rope_theta=500000.0,
        max_position_embeddings=128,
        rope_scaling=RopeScaling(
            factor=8.0,
            low_freq_factor=1.0,
            high_freq_factor=4.0,
            original_max_position_embeddings=64,
        ),
    )
    hf_cfg = LlamaConfig(
        vocab_size=cfg3.vocab_size,
        hidden_size=cfg3.hidden_size,
        intermediate_size=cfg3.intermediate_size,
        num_hidden_layers=cfg3.num_hidden_layers,
        num_attention_heads=cfg3.num_attention_heads,
        num_key_value_heads=cfg3.num_key_value_heads,
        max_position_embeddings=cfg3.max_position_embeddings,
        rms_norm_eps=cfg3.rms_norm_eps,
        rope_theta=cfg3.rope_theta,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
        tie_word_embeddings=False,
    )
    torch.manual_seed(42)
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params3 = params_from_hf(cfg3, sd, dtype=jnp.float32)

    B, S = 1, 96  # long enough to exercise the scaled low-frequency band
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg3.vocab_size, (B, S)).astype(np.int32)
    ref = hf_logits(model, ids)

    cache = init_cache(cfg3, B, capacity=S, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    logits, _ = llama.forward(cfg3, params3, jnp.asarray(ids), cache, positions)
    np.testing.assert_allclose(np.asarray(logits), ref, atol=3e-4, rtol=2e-3)
