"""Golden test: OLMoE (the llama block with a q/k RMSNorm and sparse experts)
== HF transformers' ``OlmoeForCausalLM`` (torch CPU) at tiny size — the
published checkpoint layout (per-expert ``gate_proj``/``up_proj``/``down_proj``,
``mlp.gate``, ``q_norm``/``k_norm``) through ``utils/convert`` into the
program's leaves, with and without ``norm_topk_prob``."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
if not hasattr(transformers, "OlmoeForCausalLM"):
    pytest.skip("this transformers has no OlmoeForCausalLM",
                allow_module_level=True)

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import init_cache
from llm_sharding_tpu.models.config import ModelConfig, tiny_olmoe
from llm_sharding_tpu.utils.convert import params_from_hf

CFG = tiny_olmoe()


def hf_model(norm_topk_prob: bool):
    torch.manual_seed(5)
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=CFG.vocab_size, hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        max_position_embeddings=CFG.max_position_embeddings,
        rms_norm_eps=CFG.rms_norm_eps, rope_theta=CFG.rope_theta,
        num_experts=CFG.num_experts,
        num_experts_per_tok=CFG.num_experts_per_tok,
        norm_topk_prob=norm_topk_prob, tie_word_embeddings=False,
        clip_qkv=None,
    )
    model = transformers.OlmoeForCausalLM(hf_cfg)
    # the norms' gains off one, so a dropped or misplaced norm shows
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("norm.weight"):
                p.add_(0.2 * torch.randn_like(p))
    return model.eval()


def test_config_maps_olmoe_to_the_llama_block_and_refuses_what_it_cannot():
    assert CFG.model_type == "llama" and CFG.qk_norm
    assert (CFG.num_experts, CFG.num_experts_per_tok) == (8, 2)
    base = dict(model_type="olmoe", vocab_size=8, hidden_size=8,
                intermediate_size=8, num_hidden_layers=1,
                num_attention_heads=1, num_experts=4, num_experts_per_tok=2)
    with pytest.raises(ValueError, match="clip_qkv"):
        ModelConfig.from_hf_config(dict(base, clip_qkv=8.0))
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        ModelConfig.from_hf_config(dict(base, num_experts_per_tok=5))
    with pytest.raises(ValueError, match="num_experts"):
        ModelConfig.from_hf_config(
            {k: v for k, v in base.items() if k != "num_experts"})
    # a dense llama keeps no expert field
    assert ModelConfig.from_hf_config(dict(
        base, model_type="llama")).num_experts == 0


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_full_sequence_logits_match(norm_topk_prob):
    model = hf_model(norm_topk_prob)
    cfg = dataclasses.replace(CFG, norm_topk_prob=norm_topk_prob)
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    params = params_from_hf(cfg, sd, dtype=jnp.float32)
    lyr = params["layers"]
    E, F, H = cfg.num_experts, cfg.intermediate_size, cfg.hidden_size
    assert lyr["we_gate"].shape == (cfg.num_hidden_layers, H, E * F)
    assert lyr["we_down"].shape == (cfg.num_hidden_layers, E * F, H)
    assert lyr["router"].shape == (cfg.num_hidden_layers, H, E)
    assert "w_gate" not in lyr and "q_norm" in lyr and "k_norm" in lyr
    B, S = 2, 12
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(ids).long()).logits.numpy()
    cache = init_cache(cfg, B, capacity=S, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.forward(cfg, params, jnp.asarray(ids), cache, positions)
    np.testing.assert_allclose(np.asarray(logits), ref, atol=2e-4, rtol=2e-4)
