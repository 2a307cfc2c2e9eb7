"""Golden test: ``deepseek_v3`` (latent attention, YaRN, leading dense layers,
sigmoid group-limited routing with a correction bias, a shared expert) ==
HF transformers' ``DeepseekV3ForCausalLM`` (torch CPU) at tiny size — the
published checkpoint layout through ``utils/convert`` into the program's
leaves (rotated columns de-interleaved, ``kv_b_proj`` split into the absorbed
factors), and the plain reference of ``benchmark/blocks/deepseek_v3.py`` on
the same converted leaves."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
if not hasattr(transformers, "DeepseekV3ForCausalLM"):
    pytest.skip("this transformers has no DeepseekV3ForCausalLM",
                allow_module_level=True)

from llm_sharding_tpu.models import deepseek_v3 as deepseek
from llm_sharding_tpu.models.cache import init_cache
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_deepseek_v3_keys,
)
from llm_sharding_tpu.utils.convert import params_from_hf

KEYS = tiny_deepseek_v3_keys()
CFG = ModelConfig.from_hf_config(KEYS)


def hf_model():
    torch.manual_seed(7)
    known = {k: v for k, v in KEYS.items() if k not in ("model_type",)}
    hf_cfg = transformers.DeepseekV3Config(
        **known, rope_interleave=True, attention_dropout=0.0,
        tie_word_embeddings=False,
    )
    model = transformers.DeepseekV3ForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            # gains off one and weights off the tiny default init, so a
            # dropped or misplaced norm, bias or factor shows
            if name.endswith("norm.weight"):
                p.add_(0.2 * torch.randn_like(p))
            elif name.endswith("mlp.gate.weight"):
                p.copy_(torch.randn_like(p) * p.shape[-1] ** -0.5)
            elif p.ndim == 2 and "embed" not in name:
                p.copy_(torch.randn_like(p) * p.shape[-1] ** -0.5)
        for name, b in model.named_buffers():
            if name.endswith("e_score_correction_bias"):
                b.copy_(0.1 * torch.randn_like(b))
    return model.eval()


def test_full_sequence_logits_match_transformers_and_the_plain_reference():
    model = hf_model()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = params_from_hf(CFG, sd, dtype=jnp.float32)
    assert set(params["layers"]) == {"dense", "moe"}
    moe = params["layers"]["moe"]
    assert moe["router_bias"].dtype == jnp.float32
    assert moe["w_uk"].shape == (
        2, CFG.num_attention_heads * CFG.qk_nope_head_dim, CFG.kv_lora_rank)
    B, S = 2, 24
    ids = np.random.default_rng(0).integers(
        0, CFG.vocab_size, (B, S)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(ids).long()).logits.numpy()
    cache = init_cache(CFG, B, capacity=S, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        logits, _ = deepseek.forward(
            CFG, params, jnp.asarray(ids), cache, positions)
    np.testing.assert_allclose(np.asarray(logits), ref, atol=3e-4, rtol=3e-4)

    # the benchmark's plain reference, on the converted leaves
    from benchmark import blocks, reference, weights

    block = blocks.load("deepseek_v3")
    kinds = blocks.kinds(block, KEYS)
    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    hidden = reference.hidden_states(
        block, KEYS, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids[0]],
    )[0][:S]
    plain = block.logits(hidden, tables, **block.head_static(KEYS))
    np.testing.assert_allclose(np.asarray(plain), ref[0], atol=3e-4, rtol=3e-4)


def test_the_share_reads_only_the_held_experts_of_the_checkpoint():
    model = hf_model()
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    cfg = ModelConfig.from_hf_config(dict(
        KEYS, n_routed_experts=2, n_routed_experts_total=8, ep_rank=3))
    params = params_from_hf(cfg, sd, dtype=jnp.float32)
    F = cfg.moe_intermediate_size
    got = np.asarray(params["layers"]["moe"]["we_gate"][0])
    assert got.shape == (cfg.hidden_size, 2 * F)
    want = sd["model.layers.1.mlp.experts.7.gate_proj.weight"].T
    np.testing.assert_array_equal(got[:, F:], want)
    assert params["layers"]["moe"]["router"].shape[-1] == 8
