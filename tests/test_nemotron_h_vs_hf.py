"""Golden test: the Mamba-2 mixer of ``nemotron_h`` == HF transformers'
``Zamba2MambaMixer.torch_forward`` (torch CPU) at tiny size. transformers
4.57 has no ``nemotron_h``, but ``zamba2``'s mixer is the same Mamba-2 layer —
``[z | xBC | dt]`` projection, causal depthwise conv with bias, ``n_groups``
sharing ``B`` / ``C``, ``softplus(dt + dt_bias)``, the gated norm gate FIRST
over groups — so the mechanism is checked against code this repository did
not write: the plain reference of ``benchmark/blocks/nemotron_h.py`` (the
sequential recurrence) and the program's ``mamba_block`` (the block form), on
shared random weights."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
try:
    from transformers.models.zamba2 import modeling_zamba2 as zamba2
except Exception:  # pragma: no cover
    pytest.skip("this transformers has no zamba2", allow_module_level=True)

from llm_sharding_tpu.models import nemotron_h
from llm_sharding_tpu.models.config import tiny_nemotron_h, tiny_nemotron_h_keys

KEYS = tiny_nemotron_h_keys()
CFG = tiny_nemotron_h()


@pytest.fixture(scope="module")
def mixer():
    torch.manual_seed(11)
    cfg = transformers.Zamba2Config(
        hidden_size=CFG.hidden_size, mamba_d_state=CFG.ssm_state_size,
        mamba_d_conv=CFG.conv_kernel, mamba_expand=2,
        mamba_ngroups=CFG.ssm_groups, n_mamba_heads=CFG.mamba_num_heads,
        # ONE chunk of zamba2's for every length here: its torch path across
        # chunks disagrees with ITSELF (the same weights at chunk_size 8 and
        # 32 read 0.03 apart at 21 positions, transformers 4.57.6); inside a
        # chunk it is the plain quadratic form. The program's blocks stay 8
        chunk_size=32, use_conv_bias=True, add_bias_linear=False,
        num_hidden_layers=2, num_attention_heads=4, vocab_size=64,
    )
    m = zamba2.Zamba2MambaMixer(cfg, layer_idx=0).eval()
    # zamba2 clamps dt from below at ``time_step_min``; ``nemotron_h`` does
    # not (``time_step_limit`` is absent: (0, inf)) — the one difference
    m.time_step_min = 0.0
    with torch.no_grad():
        for name, p in m.named_parameters():
            if name == "A_log":
                p.copy_(torch.log(1.0 + 15.0 * torch.rand_like(p)))
            elif name == "dt_bias":
                dt = torch.exp(torch.rand_like(p) * np.log(100.0) + np.log(1e-3))
                p.copy_(dt + torch.log(-torch.expm1(-dt)))
            elif name in ("D", "norm.weight"):
                p.copy_(1.0 + 0.2 * torch.randn_like(p))
            else:
                p.copy_(torch.randn_like(p) * (0.5 if "conv1d" in name
                                               else p.shape[-1] ** -0.5))
    return m


def leaves(m):
    """The HF mixer's tensors in the program's layout (what
    ``utils/convert.nemotron_layer_arrays`` does with the published names)."""
    sd = {k: v.detach().numpy() for k, v in m.state_dict().items()}
    return {
        "norm": jnp.ones((CFG.hidden_size,), jnp.float32),
        "w_in": jnp.asarray(sd["in_proj.weight"].T),
        "conv_w": jnp.asarray(sd["conv1d.weight"][:, 0, :].T),
        "conv_b": jnp.asarray(sd["conv1d.bias"]),
        "dt_bias": jnp.asarray(sd["dt_bias"]), "A_log": jnp.asarray(sd["A_log"]),
        "D": jnp.asarray(sd["D"]), "gate_norm": jnp.asarray(sd["norm.weight"]),
        "w_out": jnp.asarray(sd["out_proj.weight"].T),
    }


def normed(h):
    h = np.asarray(h, np.float32)
    return h / np.sqrt((h * h).mean(-1, keepdims=True) + CFG.rms_norm_eps)


@pytest.mark.parametrize("S", [5, 8, 21])
def test_the_mixer_is_zamba2s(mixer, S):
    """Under a block of the program's, one block, and three with a ragged
    tail (each inside one chunk of zamba2's)."""
    from benchmark import blocks

    block = blocks.load("nemotron_h")
    h = np.random.default_rng(S).standard_normal((2, S, CFG.hidden_size))
    h = h.astype(np.float32)
    with torch.no_grad():
        want = mixer.torch_forward(torch.from_numpy(normed(h))).numpy()
    p = leaves(mixer)
    kw = dict(block.layer_static(KEYS)["mamba"], kind="mamba")
    for b in range(2):
        ref = np.asarray(block.layer_forward(jnp.asarray(h[b]), p, **kw)) - h[b]
        assert np.abs(ref - want[b]).max() < 2e-5
    zero = nemotron_h.zero_recurrent(CFG, 1, 2)
    with jax.default_matmul_precision("highest"):
        got, _, _ = nemotron_h.mamba_block(
            CFG, p, jnp.asarray(h), zero["ssm"][0], zero["conv"][0],
            jnp.ones((2, S), bool))
    assert np.abs(np.asarray(got) - h - want).max() < 2e-5
    assert np.abs(want).max() > 0.1
