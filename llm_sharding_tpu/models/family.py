"""One record a model family: which code runs a ``model_type``.

The ring (``parallel/pipeline.model_fns``), the converter
(``utils/convert.py``, ``utils/shard_store.py``), the oracle
(``runtime/generate.forward_fn_for``), the tensor / context parallel paths and
the profiler ASK ``family(cfg)``; none of them compares ``cfg.model_type``. A
new family is one block file, its presets in ``config.py`` and one entry of
``_families`` below. What a CONFIGURATION says of itself (``cfg.layer_kinds``,
``cfg.sparse_attn``, ``cfg.passes``, the shapes of its cache) stays in
``config.py``: those are facts of a configuration, not choices of code.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

from .config import ModelConfig


class Family(NamedTuple):
    # the stage functions (``model_fns`` wraps them) and the work lists of a
    # chunked prefill over layers that do not all attend alike (or None)
    forward_layers: Callable
    forward_layers_paged: Callable
    init_params: Callable  # (cfg, key, dtype) -> the whole tree, random
    prefill_walks: Optional[Callable] = None
    # the whole-model oracle over a dense ``KVCache``; None: a recurrent
    # state has no place in one
    forward: Optional[Callable] = None

    # the converter: HF names -> one layer's leaves, the checkpoint's
    # ``(embedding, final norm)`` names (gpt2 finds its own, under an optional
    # prefix: ``convert.gpt2_head_arrays``), or why the family is not mapped
    layer_arrays: Optional[Callable] = None
    head_names: tuple = ("model.embed_tokens.weight", "model.norm.weight")
    unmapped: str = ""

    # tensor / context parallelism. ``tp_specs(stacked=)``: the megatron
    # specs (None: pp×tp unsupported); ``tp_permute(stage_layers, tp)``: what
    # ``pipeline_generate`` must do to the weights before the specs apply
    # (gpt2's fused qkv); ``paged_cp``: the paged stage takes ``cp_axis``;
    # ``axes_refused``: why the family takes neither axis (``refuse_axes``);
    # ``attn_mlp_block``: the layer with its attention handed in, which ring
    # attention needs (None: ``parallel/context.py`` refuses the family)
    tp_specs: Optional[Callable] = None
    tp_permute: Optional[Callable] = None
    paged_cp: bool = False
    axes_refused: str = ""
    attn_mlp_block: Optional[Callable] = None

    # gpt2's two facts: positions are a learned table added at the embedding
    # (``pos_embed``; nothing positional inside a layer), and the final norm
    # is a LayerNorm with a bias (``final_norm_bias``)
    learned_positions: bool = False
    final_layer_norm: bool = False

    @property
    def presplit(self) -> bool:
        """The specs apply to the weights as they are stored: the engine lays
        them out pre-split, and serve×tp takes the family."""
        return self.tp_specs is not None and self.tp_permute is None


@functools.cache
def _families() -> dict[str, Family]:
    # built at the first lookup, not at import: the block files import
    # ``refuse_axes`` from here, and the converter and the specs import
    # ``models.config``
    from ..parallel import tensor
    from ..utils import convert
    from . import (
        deepseek_v3, gpt2, jamba, llama, longcat_flash, mimo_v2, nemotron_h,
        solar_open2,
    )

    def stage(mod, **kw) -> Family:
        return Family(
            mod.forward_layers, mod.forward_layers_paged, mod.init_params,
            getattr(mod, "prefill_walks", None), **kw,
        )

    return {
        "llama": stage(
            llama, forward=llama.forward,
            layer_arrays=convert.llama_layer_arrays,
            tp_specs=tensor.llama_tp_specs, paged_cp=True,
            attn_mlp_block=llama.attn_mlp_block,
        ),
        "gpt2": stage(
            gpt2, forward=gpt2.forward,
            layer_arrays=convert.gpt2_layer_arrays, head_names=(),
            tp_specs=tensor.gpt2_tp_specs,
            tp_permute=tensor.permute_gpt2_tp_layers_cached,
            attn_mlp_block=gpt2.attn_mlp_block,
            learned_positions=True, final_layer_norm=True,
        ),
        "deepseek_v3": stage(
            deepseek_v3, forward=deepseek_v3.forward,
            layer_arrays=convert.deepseek_layer_arrays,
            axes_refused="latent attention, a share of the experts",
        ),
        "mimo_v2": stage(
            mimo_v2, forward=mimo_v2.forward,
            layer_arrays=convert.mimo_layer_arrays,
            axes_refused="a KV state per kind of layer, a share of the experts",
        ),
        "nemotron_h": stage(
            nemotron_h, layer_arrays=convert.nemotron_layer_arrays,
            head_names=("backbone.embeddings.weight", "backbone.norm_f.weight"),
            axes_refused=(
                "a recurrent state beside the arena, a share of the experts"
            ),
        ),
        "jamba": stage(
            jamba, layer_arrays=convert.jamba_layer_arrays,
            head_names=("model.embed_tokens.weight", "model.final_layernorm.weight"),
            axes_refused="a recurrent state beside the arena",
        ),
        "solar_open2": stage(
            solar_open2,
            unmapped=(
                "model_type 'solar_open2': the names of a Solar-Open2 "
                "checkpoint's tensors (a KDA mixer's projections, low-rank "
                "pairs, conv and norm leaves; the attention layers' gate) are "
                "in no file of this repository — the converter maps it once "
                "they are; the block runs on seeded weights "
                "(benchmark/blocks/solar_open2.py)"
            ),
            axes_refused="a recurrent matrix state beside the arena, a share of "
                         "the experts",
        ),
        "longcat_flash": stage(
            longcat_flash, forward=longcat_flash.forward,
            unmapped=(
                "model_type 'longcat_flash': the names of a LongCat-Flash "
                "checkpoint's tensors (a layer's two attentions, two dense "
                "MLPs and four norms, its router's classifier and correction "
                "bias) are in no file of this repository — the converter maps "
                "it once they are; the block runs on seeded weights "
                "(benchmark/blocks/longcat_flash.py)"
            ),
            axes_refused="two latent attentions a layer, a share of the experts",
        ),
    }


def family(cfg: ModelConfig) -> Family:
    try:
        return _families()[cfg.model_type]
    except KeyError:
        raise ValueError(f"unsupported model_type: {cfg.model_type!r}") from None


def refuse_axes(cfg: ModelConfig, tp_axis=None, cp_axis=None) -> None:
    """The ONE refusal of a tensor or context axis over a family that takes
    neither: ``model_fns`` and the family's own stage functions raise it."""
    if tp_axis is None and cp_axis is None:
        return
    why = family(cfg).axes_refused
    if why:
        raise NotImplementedError(
            f"tensor / context parallelism over {cfg.model_type} ({why}) is "
            "not implemented"
        )
