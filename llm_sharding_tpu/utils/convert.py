"""HF checkpoint → JAX parameter pytree conversion.

TPU-native counterpart of the reference's offline ``ModelSharder``
(``/root/reference/utils/model_sharder.py:7-134``): where the reference loads
the full torch model and ``torch.save``s ``embedding.pth`` / ``block_{i}.pth``
/ ``final_norm.pth`` / ``lm_head.pth``, this module maps HF weight names to
the pytree layout of ``models/llama.py`` / ``models/gpt2.py`` (layer-stacked
arrays ready for ``lax.scan``). Both reference architectures are covered:
"llama" (``model_sharder.py:64-94``) and "gpt" (``model_sharder.py:96-132``).

Inputs are name→numpy mappings, so the source can be torch state dicts (tests)
or safetensors files streamed tensor-by-tensor (``shard_store.py``) without
ever materializing the full model in host memory at once — the reference needs
one big-memory machine for this step (``/root/reference/README.md:29``); we
don't.
"""

from __future__ import annotations

from typing import Callable, Mapping

import numpy as np
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.family import family

TensorGetter = Callable[[str], np.ndarray]


def _getter(src: Mapping[str, np.ndarray] | TensorGetter) -> TensorGetter:
    if callable(src):
        return src
    return lambda name: np.asarray(src[name])


def _refuse_unmapped(cfg: ModelConfig) -> None:
    """A family whose checkpoint names this repository does not hold is
    refused by name, never mapped by guess."""
    if cfg.sparse_attn:
        raise NotImplementedError(
            "model_type 'KeyeVL2': the names of a Keye checkpoint's tensors "
            "(its indexer's three projections and LayerNorm, its language "
            "model's prefix beside the vision tower) are in no file of this "
            "repository — the converter maps it once they are; the block runs "
            "on seeded weights (benchmark/blocks/KeyeVL2.py)"
        )
    if cfg.out_norms or cfg.passes > 1:
        raise NotImplementedError(
            "model_type 'ouro': the names of an Ouro checkpoint's tensors (a "
            "layer's four norms, the exit gate and its bias) are in no file "
            "of this repository — the converter maps it once they are, and "
            "maps nothing by guess: a llama-family read would drop the two "
            "output norms and the gate; the block runs on seeded weights "
            "(benchmark/blocks/ouro.py)"
        )
    unmapped = family(cfg).unmapped
    if unmapped:
        raise NotImplementedError(unmapped)


def llama_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One decoder layer's params (un-stacked), ≙ ``block_{i}.pth``.

    ``attention_bias`` checkpoints (the Qwen2 family: q/k/v biased, o not)
    emit ``bq``/``bk``/``bv`` — the block adds biases by key presence, so
    exactly the projections the checkpoint biases carry them. ``mlp_bias``
    has no target family yet and is still refused rather than dropped."""
    if cfg.mlp_bias:
        raise ValueError(
            "mlp_bias checkpoints are not wired through yet; refusing to "
            "silently drop bias tensors"
        )
    _refuse_unmapped(cfg)
    pre = f"model.layers.{i}."

    def lin(name):  # torch Linear stores [out, in]; we use [in, out]
        return jnp.asarray(get(pre + name + ".weight").T, dtype)

    p = {
        "input_norm": jnp.asarray(get(pre + "input_layernorm.weight"), dtype),
        "wq": lin("self_attn.q_proj"),
        "wk": lin("self_attn.k_proj"),
        "wv": lin("self_attn.v_proj"),
        "wo": lin("self_attn.o_proj"),
        "post_norm": jnp.asarray(get(pre + "post_attention_layernorm.weight"), dtype),
    }
    if cfg.num_experts:
        # OLMoE's published layout: ``mlp.gate`` (the router, [E, H]) and
        # per-expert ``mlp.experts.{e}.{gate,up,down}_proj`` → the layer's
        # experts as one block-sparse MLP of width E·F, expert e being
        # columns (rows) e·F..(e+1)·F (ops/moe.py)
        def experts(name, axis):
            return jnp.concatenate(
                [lin(f"mlp.experts.{e}.{name}") for e in range(cfg.num_experts)],
                axis=axis,
            )

        p.update(
            router=lin("mlp.gate"),
            we_gate=experts("gate_proj", 1),
            we_up=experts("up_proj", 1),
            we_down=experts("down_proj", 0),
        )
    else:
        p.update(
            w_gate=lin("mlp.gate_proj"),
            w_up=lin("mlp.up_proj"),
            w_down=lin("mlp.down_proj"),
        )
    if cfg.qk_norm:
        p["q_norm"] = jnp.asarray(get(pre + "self_attn.q_norm.weight"), dtype)
        p["k_norm"] = jnp.asarray(get(pre + "self_attn.k_norm.weight"), dtype)
    if cfg.attention_bias:
        for key, name in (
            ("bq", "self_attn.q_proj"),
            ("bk", "self_attn.k_proj"),
            ("bv", "self_attn.v_proj"),
            ("bo", "self_attn.o_proj"),  # llama attention_bias biases o too;
            # qwen2 does not ship one — probed, not assumed
        ):
            if _has(get, pre + name + ".bias"):
                p[key] = jnp.asarray(get(pre + name + ".bias"), dtype)
    return p


def deepseek_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One ``deepseek_v3`` layer (HF ``modeling_deepseek_v3.py`` names) in
    the layout of ``models/deepseek_v3.py``; the layer's kind is
    ``cfg.layer_kinds[i]``. Three re-layouts, none of them arithmetic:

    - a head's ROTATED columns of ``q_b_proj`` and of ``kv_a_proj_with_mqa``
      are de-interleaved (pairs ``(2j, 2j+1)`` → halves ``(j, j + d/2)``):
      ``transformers`` does that to the activations on every call
      (``rope_interleave``); done to the columns once, rotation is the
      rotate-half of ``ops/rope.py`` and q·k is unchanged;
      ``kv_a_proj_with_mqa`` also gets zero columns up to the arena entry's
      width (``cfg.cache_k_dim``: whole 128-lane tiles);
    - ``kv_b_proj`` ``[Nh·(nope+v), kv_lora]`` is split per head into the
      two absorbed factors ``w_uk [Nh·nope, kv_lora]`` and ``w_uv
      [Nh·v, kv_lora]`` (its own rows, no transpose);
    - of the routed experts only those this chip HOLDS are read
      (``cfg.held_experts_``), as one block-sparse MLP;
      the router and its correction bias keep all ``num_experts``."""
    pre = f"model.layers.{i}."
    Nh = cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def raw(name):  # torch Linear stores [out, in]; we use [in, out]
        return np.asarray(get(pre + name + ".weight")).T

    def arr(x):
        return jnp.asarray(x, dtype)

    def norm(name):
        return arr(get(pre + name + ".weight"))

    wq_b = raw("self_attn.q_b_proj").reshape(-1, Nh, dn + dr)
    wq_b = np.concatenate([wq_b[..., :dn], wq_b[..., dn:][..., halves]], -1)
    wkv_a = raw("self_attn.kv_a_proj_with_mqa")
    wkv_a = np.concatenate([wkv_a[:, :r], wkv_a[:, r:][:, halves]], -1)
    wkv_a = np.pad(wkv_a, ((0, 0), (0, cfg.cache_k_dim - r - dr)))
    kv_b = np.asarray(get(pre + "self_attn.kv_b_proj.weight")).reshape(
        Nh, dn + dv, r
    )
    p = {
        "input_norm": norm("input_layernorm"),
        "wq_a": arr(raw("self_attn.q_a_proj")),
        "q_a_norm": norm("self_attn.q_a_layernorm"),
        "wq_b": arr(wq_b.reshape(-1, Nh * (dn + dr))),
        "wkv_a": arr(wkv_a),
        "kv_a_norm": norm("self_attn.kv_a_layernorm"),
        "w_uk": arr(kv_b[:, :dn].reshape(Nh * dn, r)),
        "w_uv": arr(kv_b[:, dn:].reshape(Nh * dv, r)),
        "wo": arr(raw("self_attn.o_proj")),
        "post_norm": norm("post_attention_layernorm"),
    }
    if cfg.layer_kinds[i] == "dense":
        p.update(
            w_gate=arr(raw("mlp.gate_proj")), w_up=arr(raw("mlp.up_proj")),
            w_down=arr(raw("mlp.down_proj")),
        )
        return p
    first, count = cfg.held_experts_
    held = range(first, first + count)

    def experts(name, axis):
        return arr(np.concatenate(
            [raw(f"mlp.experts.{e}.{name}") for e in held], axis=axis
        ))

    p.update(
        router=arr(raw("mlp.gate")),
        router_bias=jnp.asarray(
            get(pre + "mlp.gate.e_score_correction_bias"), jnp.float32
        ),
        we_gate=experts("gate_proj", 1), we_up=experts("up_proj", 1),
        we_down=experts("down_proj", 0),
        ws_gate=arr(raw("mlp.shared_experts.gate_proj")),
        ws_up=arr(raw("mlp.shared_experts.up_proj")),
        ws_down=arr(raw("mlp.shared_experts.down_proj")),
    )
    return p


def mimo_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One ``mimo_v2`` layer in the layout of ``models/mimo_v2.py``; the
    layer's kind is ``cfg.layer_kinds[i]``. The tensor NAMES are those of the
    published ``attention_projection_layout: fused_qkv`` as this sandbox
    could read them (no network: not checked against a checkpoint) —
    ``self_attn.qkv_proj`` ``[Hq·Dk + Hkv·Dk + Hkv·Dv, H]`` (q heads, k heads,
    v heads; transposed to ``[in, out]``, nothing re-laid),
    ``self_attn.attention_sink_bias`` ``[Hq]`` where the layer's kind has a
    sink, ``self_attn.o_proj``, the two layer norms, and the feed-forward as
    ``deepseek_v3`` names it (``mlp.gate`` + ``e_score_correction_bias``,
    ``mlp.experts.{e}.*``; a dense layer's ``mlp.*_proj``). Of the routed
    experts only those this chip HOLDS are read (``cfg.held_experts_``)."""
    from ..models.mimo_v2 import attn_of, has_sink

    pre = f"model.layers.{i}."
    kind = cfg.layer_kinds[i]

    def raw(name):  # torch Linear stores [out, in]; we use [in, out]
        return np.asarray(get(pre + name + ".weight")).T

    def arr(x):
        return jnp.asarray(x, dtype)

    p = {
        "input_norm": arr(get(pre + "input_layernorm.weight")),
        "wqkv": arr(raw("self_attn.qkv_proj")),
        "wo": arr(raw("self_attn.o_proj")),
        "post_norm": arr(get(pre + "post_attention_layernorm.weight")),
    }
    if has_sink(cfg, attn_of(kind)):
        p["sink"] = jnp.asarray(
            get(pre + "self_attn.attention_sink_bias"), jnp.float32
        )
    if kind.startswith("dense"):
        p.update(
            w_gate=arr(raw("mlp.gate_proj")), w_up=arr(raw("mlp.up_proj")),
            w_down=arr(raw("mlp.down_proj")),
        )
        return p
    first, count = cfg.held_experts_
    held = range(first, first + count)

    def experts(name, axis):
        return arr(np.concatenate(
            [raw(f"mlp.experts.{e}.{name}") for e in held], axis=axis
        ))

    p.update(
        router=arr(raw("mlp.gate")),
        router_bias=jnp.asarray(
            get(pre + "mlp.gate.e_score_correction_bias"), jnp.float32
        ),
        we_gate=experts("gate_proj", 1), we_up=experts("up_proj", 1),
        we_down=experts("down_proj", 0),
    )
    return p


def nemotron_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One ``nemotron_h`` layer in the layout of ``models/nemotron_h.py``; the
    layer's kind is ``cfg.layer_kinds[i]``. The tensor NAMES are those of the
    published checkpoint as this sandbox could read them (no network: not
    checked against a checkpoint) — ``backbone.layers.{i}.norm`` and
    ``backbone.layers.{i}.mixer.*``: a mixer's ``in_proj`` (``[z | xBC | dt]``
    rows, transposed to ``[in, out]``, nothing re-laid), ``conv1d`` (``[C, 1,
    K]`` → ``[K, C]``, float32 like its bias, ``dt_bias``, ``A_log`` and
    ``D``), the gated ``norm`` and ``out_proj``; attention's ``q_proj`` ..
    ``o_proj``; an expert layer's ``gate`` + ``e_score_correction_bias``,
    ``fc1_latent_proj`` / ``fc2_latent_proj``, ``experts.{e}.up_proj`` /
    ``down_proj`` and ``shared_experts.*``. Of the routed experts only those
    this chip HOLDS are read (``cfg.held_experts_``)."""
    pre = f"backbone.layers.{i}."
    kind = cfg.layer_kinds[i]

    def raw(name):  # torch Linear stores [out, in]; we use [in, out]
        return np.asarray(get(pre + "mixer." + name + ".weight")).T

    def arr(x):
        return jnp.asarray(x, dtype)

    def flt(name):
        return jnp.asarray(get(pre + "mixer." + name), jnp.float32)

    p = {"norm": arr(get(pre + "norm.weight"))}
    if kind == "attn":
        p.update(
            wq=arr(raw("q_proj")), wk=arr(raw("k_proj")),
            wv=arr(raw("v_proj")), wo=arr(raw("o_proj")),
        )
        return p
    if kind == "mamba":
        p.update(
            w_in=arr(raw("in_proj")),
            conv_w=jnp.asarray(
                np.asarray(get(pre + "mixer.conv1d.weight"))[:, 0, :].T,
                jnp.float32,
            ),
            conv_b=flt("conv1d.bias"), dt_bias=flt("dt_bias"),
            A_log=flt("A_log"), D=flt("D"),
            gate_norm=arr(get(pre + "mixer.norm.weight")),
            w_out=arr(raw("out_proj")),
        )
        return p
    first, count = cfg.held_experts_
    held = range(first, first + count)

    def experts(name, axis):
        return arr(np.concatenate(
            [raw(f"experts.{e}.{name}") for e in held], axis=axis
        ))

    p.update(
        router=arr(raw("gate")),
        router_bias=flt("gate.e_score_correction_bias"),
        w_lat_down=arr(raw("fc1_latent_proj")),
        w_lat_up=arr(raw("fc2_latent_proj")),
        we_up=experts("up_proj", 1), we_down=experts("down_proj", 0),
        ws_up=arr(raw("shared_experts.up_proj")),
        ws_down=arr(raw("shared_experts.down_proj")),
    )
    return p


#: ``jamba``: this repo's leaves ← the published checkpoint's tensors under
#: ``model.layers.{i}.`` (``modeling_jamba.py``'s module names; no network:
#: not checked against a checkpoint). ``T``: a torch Linear's ``[out, in]``
#: weight, transposed to ``[in, out]``; ``f32``: kept in float32 whatever
#: the model dtype (the conv, ``A_log``, ``D``, the step's bias).
JAMBA_NAMES = {
    "shared": {
        "norm": ("input_layernorm.weight", ""),
        "post_norm": ("pre_ff_layernorm.weight", ""),
        "w_gate": ("feed_forward.gate_proj.weight", "T"),
        "w_up": ("feed_forward.up_proj.weight", "T"),
        "w_down": ("feed_forward.down_proj.weight", "T"),
    },
    "attn": {
        "wq": ("self_attn.q_proj.weight", "T"),
        "wk": ("self_attn.k_proj.weight", "T"),
        "wv": ("self_attn.v_proj.weight", "T"),
        "wo": ("self_attn.o_proj.weight", "T"),
    },
    "mamba": {
        "w_in": ("mamba.in_proj.weight", "T"),  # rows [x | z]
        "conv_b": ("mamba.conv1d.bias", "f32"),
        "w_x": ("mamba.x_proj.weight", "T"),  # columns [δ | B | C]
        "dt_norm": ("mamba.dt_layernorm.weight", ""),
        "b_norm": ("mamba.b_layernorm.weight", ""),
        "c_norm": ("mamba.c_layernorm.weight", ""),
        "w_dt": ("mamba.dt_proj.weight", "T"),
        "dt_bias": ("mamba.dt_proj.bias", "f32"),
        "A_log": ("mamba.A_log", "f32"),  # [d_inner, state]
        "D": ("mamba.D", "f32"),
        "w_out": ("mamba.out_proj.weight", "T"),
    },
}


def jamba_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One ``jamba`` layer — its mixer, of kind ``cfg.layer_kinds[i]``, AND
    its MLP — in the layout of ``models/jamba.py``, by ``JAMBA_NAMES``;
    ``conv1d.weight`` (``[C, 1, K]`` → ``[K, C]``, float32) is the one tensor
    re-laid."""
    pre = f"model.layers.{i}."
    kind = cfg.layer_kinds[i]
    p = {}
    for leaf, (name, how) in {**JAMBA_NAMES["shared"], **JAMBA_NAMES[kind]}.items():
        t = np.asarray(get(pre + name))
        p[leaf] = jnp.asarray(
            t.T if how == "T" else t, jnp.float32 if how == "f32" else dtype
        )
    if kind == "mamba":
        p["conv_w"] = jnp.asarray(
            np.asarray(get(pre + "mamba.conv1d.weight"))[:, 0, :].T,
            jnp.float32,
        )
    return p


def gpt2_layer_arrays(
    cfg: ModelConfig, get: TensorGetter, i: int, dtype
) -> dict[str, jnp.ndarray]:
    """One GPT-2 block (HF Conv1D stores [in, out] — no transpose),
    ≙ the reference's gpt branch bundling h.{i} into ``block_{i}.pth``
    (``/root/reference/utils/model_sharder.py:119-126``)."""
    pre = f"transformer.h.{i}." if _has(get, f"transformer.h.{i}.ln_1.weight") else f"h.{i}."

    def t(name):
        return jnp.asarray(get(pre + name), dtype)

    return {
        "ln1_w": t("ln_1.weight"),
        "ln1_b": t("ln_1.bias"),
        "w_qkv": t("attn.c_attn.weight"),
        "b_qkv": t("attn.c_attn.bias"),
        "w_proj": t("attn.c_proj.weight"),
        "b_proj": t("attn.c_proj.bias"),
        "ln2_w": t("ln_2.weight"),
        "ln2_b": t("ln_2.bias"),
        "w_fc": t("mlp.c_fc.weight"),
        "b_fc": t("mlp.c_fc.bias"),
        "w_out": t("mlp.c_proj.weight"),
        "b_out": t("mlp.c_proj.bias"),
    }


def _has(get: TensorGetter, name: str) -> bool:
    try:
        get(name)
        return True
    except KeyError:
        return False


def _stack(layer_dicts: list[dict[str, jnp.ndarray]]) -> dict[str, jnp.ndarray]:
    return {k: jnp.stack([d[k] for d in layer_dicts]) for k in layer_dicts[0]}


def gpt2_head_arrays(get: TensorGetter, dtype) -> dict[str, jnp.ndarray]:
    """What a GPT-2 checkpoint holds outside its blocks, under its optional
    ``transformer.`` prefix (the lm_head is tied to ``wte``: no buffer)."""
    pre = "transformer." if _has(get, "transformer.wte.weight") else ""
    return {
        "embed": jnp.asarray(get(pre + "wte.weight"), dtype),
        "pos_embed": jnp.asarray(get(pre + "wpe.weight"), dtype),
        "final_norm": jnp.asarray(get(pre + "ln_f.weight"), dtype),
        "final_norm_bias": jnp.asarray(get(pre + "ln_f.bias"), dtype),
    }


def params_from_hf(
    cfg: ModelConfig,
    src: Mapping[str, np.ndarray] | TensorGetter,
    dtype=jnp.bfloat16,
) -> dict:
    """Full-model params pytree from an HF name→tensor source."""
    _refuse_unmapped(cfg)
    get = _getter(src)
    fam = family(cfg)
    kinds = cfg.layer_kinds

    def stack(kind=None):
        return _stack([
            fam.layer_arrays(cfg, get, i, dtype)
            for i in range(cfg.num_hidden_layers)
            if kind is None or kinds[i] == kind
        ])

    # layers of several kinds: one stack per kind, in layer order
    layers = {k: stack(k) for k in dict.fromkeys(kinds)} if kinds else stack()
    if fam.learned_positions:
        return {**gpt2_head_arrays(get, dtype), "layers": layers}
    # a model of several kinds may hold a SLICE of the vocabulary: its tables
    # keep the rows held here (rows 0..vocab_size-1)
    V = cfg.vocab_size if kinds else None
    embed_name, norm_name = fam.head_names
    params = {
        "embed": jnp.asarray(get(embed_name)[:V], dtype),
        "layers": layers,
        "final_norm": jnp.asarray(get(norm_name), dtype),
    }
    if not cfg.tie_word_embeddings:
        # tied: no duplicate vocab×hidden buffer — final_logits contracts
        # against the embedding table (see models/llama.py:final_logits)
        params["lm_head"] = jnp.asarray(get("lm_head.weight")[:V].T, dtype)
    return params
