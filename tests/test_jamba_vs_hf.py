"""Golden test: the WHOLE tiny ``jamba`` causal LM == HF transformers'
``JambaForCausalLM`` (torch CPU, ``use_mamba_kernels=False``: its
``slow_forward``) built from a ``JambaConfig`` of ``tiny_jamba``'s keys with
seeded weights — code this repository did not write. The weights cross
through ``utils/convert.params_from_hf`` (``JAMBA_NAMES``: the published
tensor names), so the name map, the layer order (``layers_block_type``), the
three norms inside a mixer, the conv's layout, the tied head and ONE
key/value head under several query heads are all held to it: the program's
forward (``models/jamba.forward_full``, the scan in time) and the plain
reference (``benchmark/blocks/jamba.py``) both."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import jamba
from llm_sharding_tpu.models.config import tiny_jamba, tiny_jamba_keys
from llm_sharding_tpu.utils.convert import JAMBA_NAMES, params_from_hf

KEYS = tiny_jamba_keys()
CFG = tiny_jamba()


def hf_names(cfg, params) -> dict:
    """The program's tree under the published tensor names: the inverse of
    ``utils/convert.jamba_layer_arrays`` (tests only)."""
    out = {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.final_layernorm.weight": np.asarray(params["final_norm"]),
    }
    if "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]).T
    seen = dict.fromkeys(params["layers"], 0)
    for i, kind in enumerate(cfg.layer_kinds):
        j = seen[kind]
        seen[kind] = j + 1
        stack = params["layers"][kind]
        names = {**JAMBA_NAMES["shared"], **JAMBA_NAMES[kind]}
        for leaf, (name, how) in names.items():
            a = np.asarray(stack[leaf][j])
            out[f"model.layers.{i}.{name}"] = a.T if how == "T" else a
        if kind == "mamba":
            out[f"model.layers.{i}.mamba.conv1d.weight"] = np.asarray(
                stack["conv_w"][j]
            ).T[:, None, :]
    return out


torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
try:
    from transformers import JambaConfig, JambaForCausalLM
except Exception:  # pragma: no cover
    pytest.skip("this transformers has no jamba", allow_module_level=True)


@pytest.fixture(scope="module")
def model():
    torch.manual_seed(45)
    keys = {k: v for k, v in KEYS.items() if k != "model_type"}
    cfg = JambaConfig(**dict(keys, use_mamba_kernels=False, pad_token_id=0))
    assert cfg.layers_block_type == [
        {"mamba": "mamba", "attn": "attention"}[k] for k in CFG.layer_kinds
    ]
    m = JambaForCausalLM(cfg).eval()
    with torch.no_grad():  # gains, biases and the skip off their defaults
        for name, p in m.named_parameters():
            if name.endswith("norm.weight") or name.endswith(".D"):
                p.copy_(1.0 + 0.2 * torch.randn_like(p))
            elif name.endswith("conv1d.bias") or name.endswith("conv1d.weight"):
                p.copy_(0.5 * torch.randn_like(p))
            elif name.endswith("dt_proj.bias"):
                dt = torch.exp(torch.rand_like(p) * np.log(100.0) + np.log(1e-3))
                p.copy_(dt + torch.log(-torch.expm1(-dt)))
            elif name.endswith("A_log"):
                p.copy_(p + 0.3 * torch.randn_like(p))
            elif p.ndim == 2:
                p.copy_(torch.randn_like(p) * p.shape[-1] ** -0.5)
    return m


@pytest.fixture(scope="module")
def params(model):
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    return params_from_hf(CFG, sd, jnp.float32)


IDS = np.random.default_rng(7).integers(0, 250, size=(2, 29)).astype(np.int32)


def test_the_converter_reads_every_tensor_and_ties_the_head(model, params):
    sd = model.state_dict()
    assert "lm_head" not in params
    assert sd["lm_head.weight"].data_ptr() == sd[
        "model.embed_tokens.weight"].data_ptr()
    back = hf_names(CFG, params)
    assert set(back) | {"lm_head.weight"} == set(sd)
    for name, a in back.items():
        assert np.array_equal(a, sd[name].numpy()), name


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_the_whole_model_is_transformers(model, params, backend):
    with torch.no_grad():
        want = model(torch.from_numpy(IDS.astype(np.int64))).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got, _ = jamba.forward_full(CFG, params, jnp.asarray(IDS), backend)
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < 3e-4


def test_the_plain_reference_is_transformers(model, params):
    from test_jamba import reference_logits

    with torch.no_grad():
        want = model(torch.from_numpy(IDS[:1].astype(np.int64))).logits.numpy()
    assert np.abs(reference_logits(params, IDS[0]) - want[0]).max() < 3e-4
