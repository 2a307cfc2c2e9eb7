"""Model configuration for the TPU-native model-chain framework.

The reference derives its model structure from HF ``config.json`` files copied
into each shard directory (``/root/reference/utils/model_sharder.py:50-61``,
``utils/shard_loader.py:35``) and supports two architectures: "llama" and "gpt"
(``utils/model_sharder.py:64-132``). Here the same information lives in one
explicit dataclass that is serialized into the shard store and used to build
pure-JAX forward functions.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency scaling: Llama-3 style (``rope_type="llama3"``) or
    YaRN (``rope_type="yarn"``: ``beta_fast`` / ``beta_slow`` bound the ramp
    between interpolated and extrapolated frequencies, ``mscale`` /
    ``mscale_all_dim`` give the cos/sin factor and, for ``deepseek_v3``, the
    softmax scale — ``ops/rope.py``)."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    rope_type: str = "llama3"
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    truncate: bool = True


#: a character of ``layer_pattern`` → the layer's kind (``nemotron_h``
#: publishes the string as ``hybrid_override_pattern``; ``jamba``'s is made
#: from its period and offset)
NEMOTRON_KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
#: ... and of ``solar_open2`` (``K`` a KDA mixer, ``G`` gated GQA; each with
#: its expert MLP)
SOLAR_KINDS = {"K": "kda", "G": "gqa"}

#: the kinds of layer (``ModelConfig.layer_kinds``) that keep a recurrent
#: state of fixed size a request, and those that keep entries in the paged
#: arena, of the models whose arena holds SOME layers only (a model with a
#: recurrent state: ``parallel/serve.init_state`` sizes both from these)
RECURRENT_KINDS = ("mamba", "kda")
ARENA_KINDS = ("attn", "gqa")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters for a causal LM.

    ``model_type`` selects the block structure the same way the reference's
    ``ModelSharder`` branches on "llama" vs "gpt"
    (``/root/reference/utils/model_sharder.py:64,96``).
    """

    model_type: str = "llama"  # a key of ``models/family.py``'s table
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    # llama-family block variants (Gemma: gelu MLP, sqrt(H)-scaled
    # embeddings, RMSNorm computing out*(offset+w) in fp32)
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    norm_offset: float = 0.0
    embed_multiplier: float = 1.0
    # Sparse experts (OLMoE): with ``num_experts`` > 0 every layer's MLP is
    # ``num_experts`` gated MLPs of width ``intermediate_size`` and a token
    # runs the ``num_experts_per_tok`` the router scores highest; the kept
    # router probabilities are renormalised only under ``norm_topk_prob``.
    # ``qk_norm``: q and k pass through an RMSNorm over the whole projected
    # width before the heads are split and rotated.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    qk_norm: bool = False
    # ``KeyeVL2`` (the llama block again, ``models/llama.py``): the q/k
    # RMSNorm is over each HEAD's ``head_dim`` (``qk_norm_per_head``; gains
    # ``[head_dim]``), and a learned indexer chooses which keys a query
    # attends (DeepSeek-V3.2-Exp's sparse attention, over GQA): ``index_heads``
    # index queries of ``index_head_dim`` against ONE index key a token (a
    # third paged arena), the ``index_topk`` best-scored positions kept — all
    # of them while the context is no longer than that.
    qk_norm_per_head: bool = False
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # ``deepseek_v3`` (models/deepseek_v3.py). Latent attention (MLA): q through
    # a rank-``q_lora_rank`` bottleneck, keys and values decompressed from ONE
    # latent of ``kv_lora_rank`` a token, plus ``qk_rope_head_dim`` rotated
    # values shared by all heads — what the cache holds. The first
    # ``first_k_dense_replace`` layers are dense MLPs of ``intermediate_size``;
    # the rest route over ``num_experts`` (ALL of the layer's routed experts)
    # by sigmoid scores with a correction bias, ``n_group`` groups of which
    # ``topk_group`` are kept, weights scaled by ``routed_scaling_factor``,
    # beside ``n_shared_experts`` always-on experts of the same width
    # ``moe_intermediate_size``. A chip's share of the experts: it holds
    # ``experts_held`` of them, ids ``ep_rank * experts_held ..``, and
    # computes only their terms (0 = holds them all).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    experts_held: int = 0
    ep_rank: int = 0
    # ``mimo_v2`` (models/mimo_v2.py): window and full attention in one stack.
    # ``layer_attn[l]`` is 1 where layer ``l`` attends a sliding window of
    # ``sliding_window`` keys (0: full causal attention), ``layer_moe[l]`` 1
    # where its feed-forward is the routed experts (0: the dense MLP). Window
    # layers have ``swa_num_key_value_heads`` key/value heads and rotate at
    # ``swa_rope_theta``; every head's keys are ``head_dim`` wide, its values
    # ``v_head_dim``; rotary covers the first ``int(head_dim *
    # partial_rotary_factor)`` dims; values are multiplied by
    # ``attention_value_scale``; ``swa_sink`` / ``full_sink``: a learned
    # per-head logit joins the softmax's denominator in that kind of layer.
    layer_attn: tuple = ()
    layer_moe: tuple = ()
    sliding_window: int = 0
    swa_num_key_value_heads: int = 0
    swa_rope_theta: float = 0.0
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0
    swa_sink: bool = False
    full_sink: bool = False
    # ``nemotron_h`` (models/nemotron_h.py): every layer is ONE sub-block,
    # ``layer_pattern[l]`` naming it — ``M`` a Mamba-2 mixer, ``E`` a LatentMoE
    # feed-forward, ``*`` attention (no rotary embedding). The mixer:
    # ``mamba_num_heads`` heads of ``mamba_head_dim``, a state of
    # ``ssm_state_size`` a head value, ``ssm_groups`` groups sharing ``B`` / ``C``,
    # a causal depthwise conv of ``conv_kernel`` taps, the block form over
    # ``ssm_chunk`` positions. The experts live in a ``moe_latent_size``-wide
    # space (one projection down, one up), not gated (``relu2``), beside one
    # shared expert of ``moe_shared_intermediate_size`` on the full width.
    # ``time_step_min`` / ``_max`` only seed ``dt_bias`` (initialisation).
    layer_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk: int = 128
    moe_latent_size: int = 0
    moe_shared_intermediate_size: int = 0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    # ``jamba`` (models/jamba.py): every layer is TWO sub-blocks, a mixer —
    # ``M`` a Mamba-1 mixer, ``*`` attention, by ``layer_pattern`` — and the
    # dense gated MLP. The mixer runs over ``mamba_d_inner`` channels with a
    # state of ``ssm_state_size`` a channel; its step comes through a
    # low-rank path of ``ssm_dt_rank`` (> 0 marks Mamba-1: a decay per
    # channel AND state value, no heads).
    mamba_d_inner: int = 0
    ssm_dt_rank: int = 0
    # ``solar_open2`` (models/solar_open2.py): every layer is TWO sub-blocks,
    # a mixer — ``K`` a KDA linear-attention mixer (``ops/kda.py``), ``G``
    # softmax GQA WITHOUT positions and with a sigmoid gate a channel on its
    # output (``attn_gate``), by ``layer_pattern`` — and ``deepseek_v3``'s
    # routed experts beside the shared one. A KDA mixer has ``kda_num_heads``
    # heads of ``kda_head_dim`` keys x ``kda_head_dim`` values, a conv of
    # ``conv_kernel`` taps over ``[q | k | v]``, a decay per key channel
    # through a low-rank pair of width ``kda_head_dim`` and a write strength
    # ``β = kda_beta_scale · sigmoid(·)`` (2 under ``kda_allow_neg_eigval``).
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_beta_scale: float = 1.0
    attn_gate: bool = False
    # ``longcat_flash`` (models/longcat_flash.py): a layer is TWO attention +
    # dense-MLP sub-layers (latent attention as ``deepseek_v3``'s, so the cache
    # holds ``arena_slots`` = 2 latent entries a token and layer) with ONE
    # shortcut-connected expert product between them. The softmax router
    # scores ``num_experts`` real experts AND ``zero_experts`` zero-compute
    # ones (ids ``num_experts …``: they return their input and have no
    # weights). ``mla_q_scale`` multiplies ``q`` after ``q_b_proj``,
    # ``mla_kv_scale`` the normed latent before ``kv_b_proj``.
    zero_experts: int = 0
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    # ``ouro`` (the llama block again, ``models/llama.py``): a LOOPED stack.
    # The same ``num_hidden_layers`` layers run ``passes`` times for every
    # token, the final norm closing EVERY pass (its result enters the next);
    # pass ``t`` attends pass ``t``'s keys, so the cache holds ``arena_slots``
    # = ``passes`` entries a token and layer (slot ``t · L + l``). An exit
    # gate over the passes' closed states (``exit_gate`` ``[H]`` and
    # ``exit_bias`` in the head's tables) chooses the pass the head reads:
    # the first at which the running exit probability reaches
    # ``exit_threshold``, else the last — every pass runs whatever it says.
    # ``out_norms``: a norm on each branch's OUTPUT before the residual add
    # (leaves ``attn_out_norm`` / ``mlp_out_norm``), beside the two on its input.
    passes: int = 1
    exit_threshold: float = 1.0
    out_norms: bool = False
    # GPT-2 specifics
    layer_norm_epsilon: float = 1e-5
    # Token ids. ``eos_token_ids`` holds ALL stop ids (Llama-3.x instruct
    # models ship several, e.g. <|end_of_text|> and <|eot_id|>); decode loops
    # must stop on any of them. ``eos_token_id`` is the primary/first one.
    bos_token_id: int = 1
    eos_token_id: int = 2
    eos_token_ids: tuple = ()

    def __post_init__(self):
        if not self.eos_token_ids:
            object.__setattr__(self, "eos_token_ids", (self.eos_token_id,))
        else:
            object.__setattr__(self, "eos_token_ids", tuple(self.eos_token_ids))
        for name in ("layer_attn", "layer_moe"):  # from_json hands lists
            object.__setattr__(self, name, tuple(getattr(self, name)))

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def num_kv_groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    # What one token of one layer is in the KV cache (dense rows and the
    # paged arena alike): ``cache_heads`` entries of ``cache_k_dim`` keys and
    # ``cache_v_dim`` values. A latent cache holds ONE entry, ``[c_kv |
    # k_pe]`` padded to whole 128-lane tiles, and no values: the value read
    # is the first ``kv_lora_rank`` lanes of the key read.
    @property
    def latent_kv(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def windowed(self) -> bool:
        """Some layers attend a sliding window: they keep a KV state of their
        own (an arena and a block table per kind of attention)."""
        return self.sliding_window > 0 and any(self.layer_attn)

    @property
    def sparse_attn(self) -> bool:
        """A query attends the ``index_topk`` keys its indexer scores highest:
        every layer keeps an index key a token beside K and V (a third arena
        under the same block table, ``ServeState.idx``)."""
        return self.index_topk > 0

    @property
    def index_cache_dim(self) -> int:
        """Lanes of one STORED index key: ``index_head_dim`` padded with zeros
        to whole 128-lane tiles, as ``mimo_v2`` stores its 192-wide key in 256
        (at 64 lanes the chip's compiler gives the arena a layout of its own
        for the gather and re-lays all of it around every write kernel)."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def recurrent(self) -> bool:
        """Some layers keep a recurrent state of FIXED size a request (a
        Mamba mixer's, of either family; a KDA mixer's matrices): indexed by
        row beside the paged arenas, not paged by token."""
        return "M" in self.layer_pattern or "K" in self.layer_pattern

    @property
    def ssm_inner(self) -> int:
        return self.mamba_d_inner or self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the mixer's conv runs over: Mamba-2's ``[x | B | C]``,
        Mamba-1's ``x`` alone, KDA's ``[q | k | v]``."""
        if self.kda_num_heads:
            return 3 * self.kda_num_heads * self.kda_head_dim
        if self.ssm_dt_rank:
            return self.ssm_inner
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def recurrent_shapes(self) -> dict:
        """What ONE request keeps in ONE mixer layer, by name (float32; {}
        for a model without such layers) — the ONE expression of the state's
        shape: ``ServeState.recurrent``, ``models/stack.zero_recurrent``,
        ``recurrent_row_bytes`` and ``ops/ssm.rows_backend`` read it.
        ``ssm``: Mamba-2's ``[heads, head_dim, state]``; Mamba-1's ``[state,
        8, d_inner / 8]`` — channel ``c`` at ``(c // (d_inner / 8), c %
        (d_inner / 8))``, so that a state value's channels fill whole (8,
        128) tiles where ``ops/ssm.scan_rows_tpu`` advances them. ``kda`` (in
        ``ssm``'s place): a KDA mixer's ``[heads, d_k, d_v]``, a MATRIX a head
        (``ops/kda.py``). ``conv``: the conv's last ``conv_kernel - 1``
        inputs."""
        if not self.recurrent:
            return {}
        conv = (self.conv_kernel - 1, self.conv_dim)
        if self.kda_num_heads:
            nh, hd = self.kda_num_heads, self.kda_head_dim
            return {"kda": (nh, hd, hd), "conv": conv}
        if self.ssm_dt_rank:
            ssm = (self.ssm_state_size, 8, self.ssm_inner // 8)
        else:
            ssm = (self.mamba_num_heads, self.mamba_head_dim,
                   self.ssm_state_size)
        return {"ssm": ssm, "conv": conv}

    @property
    def recurrent_row_bytes(self) -> int:
        """Bytes ONE request's recurrent state holds in ONE mixer layer
        (float32): the state and the conv's last ``conv_kernel - 1`` inputs."""
        return 4 * sum(
            math.prod(shape) for shape in self.recurrent_shapes.values()
        )

    @property
    def cache_heads(self) -> int:
        """Key/value heads of a DENSE cache row (a windowed model: the most
        any kind of layer has; its paged arenas have each kind's own count,
        ``kv_heads_of``)."""
        if self.latent_kv:
            return 1
        return max(self.num_key_value_heads, self.swa_num_key_value_heads)

    def kv_heads_of(self, attn: str) -> int:
        """Key/value heads of a ``"full"`` or ``"swa"`` attention layer."""
        if attn == "swa" and self.swa_num_key_value_heads:
            return self.swa_num_key_value_heads
        return self.num_key_value_heads

    @property
    def cache_k_dim(self) -> int:
        if self.model_type == "mimo_v2":
            # a key padded to whole 128-lane tiles (192 is one and a half)
            return -(-self.head_dim_ // 128) * 128
        if not self.latent_kv:
            return self.head_dim_
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def cache_v_dim(self) -> int:
        if self.model_type == "mimo_v2":
            return self.v_head_dim
        return 0 if self.latent_kv else self.head_dim_

    @property
    def rope_dim(self) -> int:
        """Width of the rotated part of a head."""
        if self.model_type == "mimo_v2":
            return int(self.head_dim_ * self.partial_rotary_factor)
        return self.qk_rope_head_dim if self.latent_kv else self.head_dim_

    @property
    def arena_slots(self) -> int:
        """Cache / arena layer slots ONE layer fills: a ``longcat_flash``
        layer runs two attentions, each over a latent entry of its own, so a
        stage of ``Lp`` layers has ``arena_slots · Lp`` slots (layer ``l``
        writes and reads slots ``2l`` and ``2l + 1``); a looped stack
        (``passes``) keeps keys and values of its own for every pass, pass
        major (pass ``t`` of layer ``l`` of ``Lp``: slot ``t · Lp + l``).
        Every place that sizes or walks a cache's layer axis multiplies by
        this."""
        return 2 if self.model_type == "longcat_flash" else self.passes

    @property
    def router_experts(self) -> int:
        """Width of the router and of the expert counters: the real experts
        and, after them, the zero-compute ones."""
        return self.num_experts + self.zero_experts

    @property
    def experts_held_(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def held_experts_(self) -> tuple:
        """``(first id, count)`` of the routed experts this chip holds."""
        return self.ep_rank * self.experts_held_, self.experts_held_

    @property
    def layer_kinds(self) -> tuple:
        """One kind name per layer, or () where the layers are all alike
        (the tree is then ``params["layers"][leaf]``, else
        ``params["layers"][kind][leaf]``, one stack per kind in layer
        order)."""
        if self.model_type == "mimo_v2":
            return tuple(
                ("moe" if m else "dense") + ("_swa" if a else "_full")
                for a, m in zip(self.layer_attn, self.layer_moe)
            )
        if self.model_type in ("nemotron_h", "jamba"):
            return tuple(NEMOTRON_KINDS[c] for c in self.layer_pattern)
        if self.model_type == "solar_open2":
            return tuple(SOLAR_KINDS[c] for c in self.layer_pattern)
        if self.model_type != "deepseek_v3":
            return ()
        k = self.first_k_dense_replace
        return ("dense",) * k + ("moe",) * (self.num_hidden_layers - k)

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        return json.dumps(d, indent=2)

    @classmethod
    def from_json(cls, text: str) -> "ModelConfig":
        d = json.loads(text)
        if d.get("rope_scaling") is not None:
            d["rope_scaling"] = RopeScaling(**d["rope_scaling"])
        return cls(**d)

    @classmethod
    def from_hf_config(cls, hf: dict[str, Any]) -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict (llama or gpt2)."""
        mt = hf.get("model_type", "llama")
        if mt == "qwen2":
            # Qwen2/2.5 is the llama block structure with q/k/v projection
            # biases (HF's Qwen2Attention hard-codes qkv bias on, o bias
            # off — the converter emits bq/bk/bv and the block adds them by
            # key presence). Sliding-window variants are out of scope.
            if hf.get("use_sliding_window", False):
                raise ValueError(
                    "qwen2 sliding-window attention is not supported; "
                    "convert a checkpoint with use_sliding_window=false"
                )
            hf = dict(hf, model_type="llama", attention_bias=True)
            mt = "llama"
        if mt == "gemma":
            # Gemma-1 is the llama block with three deltas (HF
            # modeling_gemma.py): gelu-tanh MLP activation, embeddings
            # scaled by sqrt(hidden), and RMSNorm out*(1+w) in fp32; always
            # tied embeddings, explicit head_dim (256). Gemma-2's softcaps /
            # alternating sliding window are a different block — refused.
            act = hf.get("hidden_activation") or hf.get(
                "hidden_act", "gelu_pytorch_tanh"
            )
            if act not in ("gelu_pytorch_tanh", "gelu", "gelu_tanh"):
                raise ValueError(f"gemma activation {act!r} not supported")
            # value check, not key presence: HF serializers emit null-valued
            # keys for attributes copied across config versions
            if (hf.get("final_logit_softcapping") is not None
                    or hf.get("sliding_window") is not None):
                raise ValueError(
                    "gemma-2 (softcapping / sliding window) is not "
                    "supported; this maps gemma-1 checkpoints"
                )
            hf = dict(
                hf,
                model_type="llama",
                hidden_act="gelu_tanh",
                norm_offset=1.0,
                embed_multiplier=float(hf["hidden_size"]) ** 0.5,
                tie_word_embeddings=True,
            )
            mt = "llama"
        if mt == "olmoe":
            # OLMoE is the llama block with two deltas (HF modeling_olmoe.py):
            # an RMSNorm over the full q and k projections, and the dense MLP
            # replaced by ``num_experts`` gated MLPs chosen per token. What
            # the block cannot honour is refused by name.
            if hf.get("clip_qkv") is not None:
                raise ValueError(
                    "olmoe clip_qkv is not supported; this maps checkpoints "
                    "with clip_qkv null (OLMoE-1B-7B-0125 and later)"
                )
            for key in ("num_experts", "num_experts_per_tok"):
                if key not in hf:
                    raise ValueError(f"olmoe config.json lacks {key!r}")
            if not 0 < hf["num_experts_per_tok"] <= hf["num_experts"]:
                raise ValueError(
                    f"olmoe num_experts_per_tok {hf['num_experts_per_tok']} "
                    f"is not in 1..num_experts {hf['num_experts']}"
                )
            moe = dict(
                num_experts=hf["num_experts"],
                num_experts_per_tok=hf["num_experts_per_tok"],
                norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
                qk_norm=True,
            )
            hf = dict(hf, model_type="llama")
            mt = "llama"
        elif mt == "KeyeVL2":
            hf, moe = cls._keye_vl2_keys(hf)
            mt = "llama"
        elif mt == "ouro":
            hf, moe = cls._ouro_keys(hf)
            mt = "llama"
        else:
            moe = {}
        if mt == "deepseek_v3":
            return cls._from_deepseek_v3(hf)
        if mt == "mimo_v2":
            return cls._from_mimo_v2(hf)
        if mt == "nemotron_h":
            return cls._from_nemotron_h(hf)
        if mt == "jamba":
            return cls._from_jamba(hf)
        if mt == "solar_open2":
            return cls._from_solar_open2(hf)
        if mt == "longcat_flash":
            return cls._from_longcat_flash(hf)
        if mt in ("llama",):
            act = hf.get("hidden_act", "silu")
            if act not in ("silu", "gelu_tanh"):
                raise ValueError(
                    f"unsupported llama-family hidden_act {act!r}"
                )
            rs = None
            raw_rs = hf.get("rope_scaling")
            if raw_rs:
                rt = raw_rs.get("rope_type", raw_rs.get("type"))
                if rt == "llama3":
                    rs = RopeScaling(
                        factor=raw_rs.get("factor", 8.0),
                        low_freq_factor=raw_rs.get("low_freq_factor", 1.0),
                        high_freq_factor=raw_rs.get("high_freq_factor", 4.0),
                        original_max_position_embeddings=raw_rs.get(
                            "original_max_position_embeddings", 8192
                        ),
                    )
                elif rt in ("default", None):
                    rs = None
                else:
                    raise ValueError(
                        f"unsupported rope_scaling type {rt!r}; only 'llama3' "
                        "and default RoPE are implemented"
                    )
            eos = hf.get("eos_token_id", 2)
            eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
            return cls(
                model_type="llama",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf.get(
                    "num_key_value_heads", hf["num_attention_heads"]
                ),
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get("max_position_embeddings", 4096),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                rope_theta=hf.get("rope_theta", 10000.0),
                rope_scaling=rs,
                tie_word_embeddings=hf.get("tie_word_embeddings", False),
                attention_bias=hf.get("attention_bias", False),
                mlp_bias=hf.get("mlp_bias", False),
                hidden_act=act,
                norm_offset=hf.get("norm_offset", 0.0),
                embed_multiplier=hf.get("embed_multiplier", 1.0),
                **moe,
                bos_token_id=(
                    1 if hf.get("bos_token_id") is None
                    else hf["bos_token_id"]
                ),
                eos_token_id=eos_ids[0],
                eos_token_ids=eos_ids,
            )
        elif mt == "gpt2":
            n_embd = hf.get("n_embd", 768)
            return cls(
                model_type="gpt2",
                vocab_size=hf.get("vocab_size", 50257),
                hidden_size=n_embd,
                intermediate_size=hf.get("n_inner") or 4 * n_embd,
                num_hidden_layers=hf.get("n_layer", 12),
                num_attention_heads=hf.get("n_head", 12),
                num_key_value_heads=hf.get("n_head", 12),
                max_position_embeddings=hf.get("n_positions", 1024),
                layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
                tie_word_embeddings=True,
                bos_token_id=hf.get("bos_token_id", 50256),
                eos_token_id=hf.get("eos_token_id", 50256),
            )
        raise ValueError(f"unsupported model_type: {mt!r}")

    @staticmethod
    def _ouro_keys(hf: dict[str, Any]) -> tuple:
        """Ouro (ByteDance's LoopLM family) as the llama block's keys: Qwen2's
        key names but NO projection bias (the released ``OuroAttention``), four
        norms a layer, ``total_ut_steps`` passes over the same layers and an
        exit gate at ``early_exit_threshold``. ``max_window_layers`` is
        accepted and not read (every layer attends in full). What the block
        cannot honour is refused by name."""
        for key in ("total_ut_steps", "early_exit_threshold"):
            if key not in hf:
                raise ValueError(f"ouro config.json lacks {key!r}")
        T, theta = int(hf["total_ut_steps"]), float(hf["early_exit_threshold"])
        if T < 1:
            raise ValueError(
                f"ouro total_ut_steps {T}: the layers run once at least"
            )
        if not 0.0 < theta <= 1.0:
            raise ValueError(
                f"ouro early_exit_threshold {theta} is not in (0, 1]: it is "
                "compared with a running sum of exit probabilities"
            )
        if hf.get("use_sliding_window") or hf.get("sliding_window"):
            raise ValueError(
                "ouro sliding-window attention is not supported: every pass "
                "of every layer attends its whole context"
            )
        other = sorted(set(hf.get("layer_types") or ()) - {"full_attention"})
        if other:
            raise ValueError(
                f"ouro layer_types {other}: only 'full_attention' layers are "
                "supported"
            )
        moe = dict(passes=T, exit_threshold=theta, out_norms=True)
        return dict(hf, model_type="llama", attention_bias=False), moe

    @staticmethod
    def _keye_vl2_keys(hf: dict[str, Any]) -> tuple:
        """Keye-VL-2.0's language model as the llama block's keys: the
        Qwen3-MoE shape (per-head q/k RMSNorm, ``num_experts`` softmax-routed
        experts of ``moe_intermediate_size`` in EVERY layer) with
        ``sa_config``'s indexer. ``mrope_section`` is accepted: with text
        alone the three position streams are equal and the rotation is the
        plain one. The vision tower has no keys and is not built. What the
        block cannot honour is refused by name."""
        sa = hf.get("sa_config") or {}
        for key in ("indexer_num_heads", "indexer_head_dim", "topk"):
            if not sa.get(key):
                raise ValueError(f"KeyeVL2 sa_config lacks {key!r}")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError(
                "KeyeVL2 sa_config.indexer_num_kv_heads "
                f"{sa['indexer_num_kv_heads']}: the index arena holds ONE "
                "index key a token"
            )
        if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                "KeyeVL2 with dense MLP layers (mlp_only_layers / "
                "decoder_sparse_step != 1) is not supported: every layer's "
                "MLP is the routed experts"
            )
        if hf.get("use_sliding_window") or hf.get("sliding_window"):
            raise ValueError("KeyeVL2 sliding-window attention is not supported")
        rs = hf.get("rope_scaling") or {}
        if rs.get("rope_type", rs.get("type", "default")) != "default":
            raise ValueError(
                f"KeyeVL2 rope_scaling {rs!r}: only the default rotation "
                "(with an mrope_section, read as plain rotary for text)"
            )
        moe = dict(
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            qk_norm=True,
            qk_norm_per_head=True,
            index_heads=int(sa["indexer_num_heads"]),
            index_head_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]),
        )
        # the expert width is the llama block's ``intermediate_size`` (the
        # published ``intermediate_size`` is the dense MLP's, which no layer has)
        hf = dict(
            hf, model_type="llama", rope_scaling=None,
            intermediate_size=hf["moe_intermediate_size"],
        )
        return hf, moe

    @classmethod
    def _from_deepseek_v3(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``deepseek_v3`` as published (HF ``modeling_deepseek_v3.py``):
        latent attention with a q bottleneck, YaRN or plain RoPE, leading
        dense layers, then sigmoid group-limited routing (``noaux_tc``)
        beside shared experts. Beside the published keys, two of a chip's
        share of the experts: ``n_routed_experts`` is how many are HELD,
        ``n_routed_experts_total`` (default: the same) how many the router
        scores, ``ep_rank`` which run of them this is. What is not done is
        refused by name."""
        need = (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(
                    f"deepseek_v3 config.json lacks {key!r} (a model "
                    "without the q bottleneck, q_lora_rank null, is not "
                    "supported)"
                )
        refuse = {
            "topk_method": ("noaux_tc",), "scoring_func": ("sigmoid",),
            "hidden_act": ("silu",), "moe_layer_freq": (1,),
            "attention_bias": (False,), "norm_topk_prob": (True,),
            "num_nextn_predict_layers": (0,), "tie_word_embeddings": (False,),
        }
        for key, ok in refuse.items():
            if key in hf and hf[key] is not None and hf[key] not in ok:
                raise ValueError(
                    f"deepseek_v3 {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))}"
                    + ("; the multi-token-prediction module is not served: "
                       "drop its layers at conversion"
                       if key == "num_nextn_predict_layers" else "")
                    + ("; a softmax router over latent attention (a "
                       "correction bias for the choice, weights not "
                       "renormalised) is the longcat_flash family's, "
                       "models/longcat_flash.py"
                       if key == "scoring_func" else "")
                    + ")"
                )
        if hf.get("rope_interleave") is False:
            raise ValueError(
                "deepseek_v3 rope_interleave=false is not supported: the "
                "converter stores the rotated columns de-interleaved"
            )
        held = int(hf["n_routed_experts"])
        total = int(hf.get("n_routed_experts_total", held))
        rank = int(hf.get("ep_rank", 0))
        groups = int(hf.get("n_group", 1))
        if total % held or not 0 <= rank < total // held:
            raise ValueError(
                f"deepseek_v3 share: {held} experts held of {total}, rank "
                f"{rank}: the held count must divide the total and the rank "
                f"lie in 0..{total // max(held, 1) - 1}"
            )
        if total % groups or (total // groups) < 2:
            raise ValueError(
                f"deepseek_v3 n_group {groups} does not split {total} "
                "experts into groups of at least 2"
            )
        rs = None
        raw = hf.get("rope_scaling")
        if raw:
            rt = raw.get("rope_type", raw.get("type"))
            if rt == "yarn":
                rs = RopeScaling(
                    factor=float(raw["factor"]),
                    original_max_position_embeddings=int(
                        raw.get("original_max_position_embeddings")
                        or hf.get("max_position_embeddings", 4096)
                    ),
                    rope_type="yarn",
                    beta_fast=float(raw.get("beta_fast") or 32.0),
                    beta_slow=float(raw.get("beta_slow") or 1.0),
                    mscale=float(raw.get("mscale") or 0.0),
                    mscale_all_dim=float(raw.get("mscale_all_dim") or 0.0),
                    truncate=bool(raw.get("truncate", True)),
                )
            elif rt not in ("default", None):
                raise ValueError(
                    f"deepseek_v3 rope_scaling type {rt!r} is not "
                    "supported; only 'yarn' and default RoPE are"
                )
        eos = hf.get("eos_token_id", 1)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        return cls(
            model_type="deepseek_v3",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"]),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling=rs,
            num_experts=total,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            norm_topk_prob=True,
            q_lora_rank=int(hf["q_lora_rank"]),
            kv_lora_rank=int(hf["kv_lora_rank"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
            qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            n_group=groups,
            topk_group=int(hf.get("topk_group", 1)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            first_k_dense_replace=int(hf.get("first_k_dense_replace", 0)),
            experts_held=held,
            ep_rank=rank,
            bos_token_id=(
                0 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )


    @classmethod
    def _from_mimo_v2(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``mimo_v2`` as published (MiMo-V2-Flash / MiMo-V2.5's language model):
        per-layer attention kind ``hybrid_layer_pattern`` (0 full, 1 sliding
        window) and feed-forward kind ``moe_layer_freq`` (0 dense, 1 experts),
        head counts and rotary base per attention kind, keys of ``head_dim`` and
        values of ``v_head_dim``, partial rotary, a value scale, a sink logit per
        head where ``add_*_attention_sink_bias`` says so, ``noaux_tc`` routing
        over one group with no shared expert. Beside the published keys a chip's
        share of the experts as ``deepseek_v3`` has it (``n_routed_experts`` HELD
        of ``n_routed_experts_total``, ``ep_rank``). ``attention_chunk_size`` is
        kept and NOT read. What is not done is refused by name."""
        L = int(hf["num_hidden_layers"])
        need = (
            "hybrid_layer_pattern", "moe_layer_freq", "head_dim", "v_head_dim",
            "sliding_window", "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(f"mimo_v2 config.json lacks {key!r}")
        attn = tuple(int(a) for a in hf["hybrid_layer_pattern"])[:L]
        ffn = hf["moe_layer_freq"]
        ffn = (tuple(int(m) for m in ffn)[:L] if isinstance(ffn, (list, tuple))
               else tuple(int(l % int(ffn) == 0) for l in range(L)))
        if len(attn) != L or len(ffn) != L:
            raise ValueError(
                f"mimo_v2 hybrid_layer_pattern / moe_layer_freq name "
                f"{len(attn)} / {len(ffn)} layers, the model has {L}"
            )
        refuse = {
            "topk_method": ("noaux_tc",), "scoring_func": ("sigmoid",),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "norm_topk_prob": (True,), "tie_word_embeddings": (False,),
            "n_group": (1,), "topk_group": (1,), "n_shared_experts": (0,),
            "swa_head_dim": (hf["head_dim"],), "swa_v_head_dim": (hf["v_head_dim"],),
            "swa_num_attention_heads": (hf["num_attention_heads"],),
            "sliding_window_size": (hf["sliding_window"],),
        }
        for key, ok in refuse.items():
            if hf.get(key) is not None and hf[key] not in ok:
                raise ValueError(
                    f"mimo_v2 {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))})"
                )
        raw = hf.get("rope_scaling")
        if raw and raw.get("rope_type", raw.get("type")) not in ("default", None):
            raise ValueError(
                f"mimo_v2 rope_scaling {raw!r} is not supported; only default "
                "RoPE is"
            )
        held = int(hf["n_routed_experts"])
        total = int(hf.get("n_routed_experts_total", held))
        rank = int(hf.get("ep_rank", 0))
        if total % held or not 0 <= rank < total // held:
            raise ValueError(
                f"mimo_v2 share: {held} experts held of {total}, rank {rank}: "
                f"the held count must divide the total and the rank lie in "
                f"0..{total // max(held, 1) - 1}"
            )
        eos = hf.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        scale = hf.get("routed_scaling_factor")
        return cls(
            model_type="mimo_v2",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=L,
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf["num_key_value_heads"],
            head_dim=int(hf["head_dim"]),
            v_head_dim=int(hf["v_head_dim"]),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=float(hf.get("layernorm_epsilon",
                                      hf.get("rms_norm_eps", 1e-5))),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            num_experts=total,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            norm_topk_prob=True,
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            n_group=1,
            topk_group=1,
            routed_scaling_factor=1.0 if scale is None else float(scale),
            experts_held=held,
            ep_rank=rank,
            layer_attn=attn,
            layer_moe=ffn,
            sliding_window=int(hf["sliding_window"]),
            swa_num_key_value_heads=int(
                hf.get("swa_num_key_value_heads") or hf["num_key_value_heads"]
            ),
            swa_rope_theta=float(
                hf.get("swa_rope_theta") or hf.get("rope_theta", 10000.0)
            ),
            partial_rotary_factor=float(hf.get("partial_rotary_factor", 1.0)),
            attention_value_scale=float(hf.get("attention_value_scale") or 1.0),
            swa_sink=bool(hf.get("add_swa_attention_sink_bias", False)),
            full_sink=bool(hf.get("add_full_attention_sink_bias", False)),
            bos_token_id=(
                1 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )


    @classmethod
    def _from_nemotron_h(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``nemotron_h`` as Nemotron-3-Super-120B-A12B publishes it: the
        first ``num_hidden_layers`` characters of ``hybrid_override_pattern``
        name each layer's ONE sub-block (``M`` Mamba-2 mixer, ``E`` LatentMoE,
        ``*`` attention; ``-``, a plain MLP, is refused). Read: the mixer's
        ``mamba_num_heads`` / ``mamba_head_dim`` / ``ssm_state_size`` /
        ``n_groups`` / ``conv_kernel`` / ``chunk_size`` (``expand`` is checked
        against heads x head size); the experts' ``n_routed_experts``,
        ``num_experts_per_tok``, ``moe_intermediate_size``,
        ``moe_latent_size``, ``moe_shared_expert_intermediate_size``,
        ``routed_scaling_factor``, ``n_group`` / ``topk_group``; attention's
        head counts and ``head_dim``; ``layer_norm_epsilon`` (= ``norm_eps``).
        Beside them a chip's share of the experts as ``deepseek_v3`` has it
        (``n_routed_experts`` HELD of ``n_routed_experts_total``,
        ``ep_rank``). Kept and NOT read, each for its reason: ``rope_theta``,
        ``partial_rotary_factor`` (the ``nemotron_h`` attention block applies
        no rotary embedding), ``use_mamba_kernels`` (names an implementation),
        ``moe_shared_expert_overlap`` (a schedule), ``rescale_prenorm_residual``,
        ``time_step_floor`` (initialisation; ``time_step_min`` / ``_max`` are
        kept for ``init_params``), ``num_logits_to_keep`` (a generation
        option), ``max_position_embeddings`` beyond the request check,
        ``intermediate_size`` (the ``-`` block's width: no such layer),
        ``mtp_hybrid_override_pattern`` (read only with the module it
        describes). What is not done is refused by name."""
        L = int(hf["num_hidden_layers"])
        need = (
            "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "ssm_state_size", "n_groups", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "head_dim",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(f"nemotron_h config.json lacks {key!r}")
        pattern = str(hf["hybrid_override_pattern"])[:L]
        if len(pattern) != L or set(pattern) - set(NEMOTRON_KINDS):
            raise ValueError(
                f"nemotron_h hybrid_override_pattern {pattern!r}: {L} layers "
                "need that many of 'M' (Mamba-2), 'E' (LatentMoE) and '*' "
                "(attention); '-' (a plain MLP layer) is not supported"
            )
        refuse = {
            "mamba_hidden_act": ("silu",), "mlp_hidden_act": ("relu2",),
            "attention_bias": (False,), "mlp_bias": (False,),
            "mamba_proj_bias": (False,), "use_bias": (False,),
            "use_conv_bias": (True,), "norm_topk_prob": (True,),
            "tie_word_embeddings": (False,), "n_group": (1,),
            "topk_group": (1,), "n_shared_experts": (1,),
            "residual_in_fp32": (False,), "sliding_window": (None,),
            "num_nextn_predict_layers": (0,), "time_step_limit": (None,),
            "expand": (
                int(hf["mamba_num_heads"]) * int(hf["mamba_head_dim"])
                // int(hf["hidden_size"]),
            ),
        }
        for key, ok in refuse.items():
            if key in hf and hf[key] not in ok:
                raise ValueError(
                    f"nemotron_h {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))})"
                    + (": the multi-token-prediction module is not built"
                       if key == "num_nextn_predict_layers" else "")
                )
        heads, groups = int(hf["mamba_num_heads"]), int(hf["n_groups"])
        if heads % groups or heads * int(hf["mamba_head_dim"]) % groups:
            raise ValueError(
                f"nemotron_h n_groups {groups} does not divide "
                f"mamba_num_heads {heads}"
            )
        eps = hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5))
        if hf.get("norm_eps", eps) != eps:
            raise ValueError(
                f"nemotron_h norm_eps {hf['norm_eps']!r} differs from "
                f"layer_norm_epsilon {eps!r}: one epsilon serves every norm"
            )
        held = int(hf["n_routed_experts"])
        total = int(hf.get("n_routed_experts_total", held))
        rank = int(hf.get("ep_rank", 0))
        if total % held or not 0 <= rank < total // held:
            raise ValueError(
                f"nemotron_h share: n_routed_experts {held} held of "
                f"n_routed_experts_total {total}, ep_rank {rank}: the held "
                f"count must divide the total and the rank lie in "
                f"0..{total // max(held, 1) - 1}"
            )
        eos = hf.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        scale = hf.get("routed_scaling_factor")
        return cls(
            model_type="nemotron_h",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=int(hf["moe_shared_expert_intermediate_size"]),
            num_hidden_layers=L,
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf["num_key_value_heads"],
            head_dim=int(hf["head_dim"]),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=float(eps),
            num_experts=total,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            norm_topk_prob=True,
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            n_shared_experts=1,
            n_group=1,
            topk_group=1,
            routed_scaling_factor=1.0 if scale is None else float(scale),
            experts_held=held,
            ep_rank=rank,
            layer_pattern=pattern,
            mamba_num_heads=heads,
            mamba_head_dim=int(hf["mamba_head_dim"]),
            ssm_state_size=int(hf["ssm_state_size"]),
            ssm_groups=groups,
            conv_kernel=int(hf.get("conv_kernel", 4)),
            ssm_chunk=int(hf.get("chunk_size", 128)),
            moe_latent_size=int(hf["moe_latent_size"]),
            moe_shared_intermediate_size=int(
                hf["moe_shared_expert_intermediate_size"]
            ),
            time_step_min=float(hf.get("time_step_min", 0.001)),
            time_step_max=float(hf.get("time_step_max", 0.1)),
            bos_token_id=(
                1 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )

    @classmethod
    def _from_jamba(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``jamba`` as AI21-Jamba2-3B publishes it. Layer ``l`` is an
        attention layer where ``l % attn_layer_period == attn_layer_offset``
        and a Mamba-1 mixer otherwise (the published model code's
        ``layers_block_type``); every layer's feed-forward is the dense gated
        MLP. Read: ``mamba_expand`` (x ``hidden_size`` = the mixer's
        channels), ``mamba_d_state``, ``mamba_d_conv``, ``mamba_dt_rank``
        (``"auto"``: ``ceil(hidden_size / 16)``), the head counts (``head_dim``
        where given, else ``hidden_size / num_attention_heads``),
        ``intermediate_size``, ``rms_norm_eps`` (every norm's, the three inside
        a mixer too), ``tie_word_embeddings``. Kept and NOT read, each for its
        reason (``JAMBA_KEYS_NOT_READ``): ``expert_layer_period`` /
        ``expert_layer_offset`` / ``num_experts_per_tok`` (they choose among
        layers and experts only where ``num_experts > 1``, which is refused),
        ``use_mamba_kernels`` (names an implementation), ``num_logits_to_keep``
        (a generation option), ``max_position_embeddings`` beyond the request
        check (the block has NO positional embedding). What is not done is
        refused by name."""
        need = (
            "attn_layer_period", "attn_layer_offset", "mamba_d_state",
            "mamba_expand", "mamba_dt_rank", "intermediate_size",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(f"jamba config.json lacks {key!r}")
        refuse = {
            "num_experts": (1,), "mamba_proj_bias": (False,),
            "mamba_conv_bias": (True,), "sliding_window": (None,),
            "hidden_act": ("silu",), "attention_bias": (False,),
        }
        for key, ok in refuse.items():
            if key in hf and hf[key] not in ok:
                raise ValueError(
                    f"jamba {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))})"
                    + (": the routed feed-forward (JambaSparseMoeBlock) is "
                       "not built" if key == "num_experts" else "")
                )
        L, H = int(hf["num_hidden_layers"]), int(hf["hidden_size"])
        period, offset = (
            int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
        )
        if not 0 <= offset < period:
            raise ValueError(
                f"jamba attn_layer_offset {offset} is not in 0..{period - 1} "
                f"(attn_layer_period {period})"
            )
        d_inner = int(hf["mamba_expand"]) * H
        if d_inner % 8:
            raise ValueError(
                f"jamba mamba_expand x hidden_size = {d_inner} channels do "
                "not split into 8 (the recurrent state's layout)"
            )
        rank = hf["mamba_dt_rank"]
        eos = hf.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        return cls(
            model_type="jamba",
            vocab_size=hf["vocab_size"],
            hidden_size=H,
            intermediate_size=int(hf["intermediate_size"]),
            num_hidden_layers=L,
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            layer_pattern="".join(
                "*" if l % period == offset else "M" for l in range(L)
            ),
            mamba_d_inner=d_inner,
            ssm_state_size=int(hf["mamba_d_state"]),
            ssm_dt_rank=-(-H // 16) if rank == "auto" else int(rank),
            conv_kernel=int(hf.get("mamba_d_conv", 4)),
            bos_token_id=(
                1 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )


    @classmethod
    def _from_solar_open2(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``solar_open2`` as Solar-Open2-250B publishes it. Layer ``l`` is
        softmax GQA where ``l`` is in ``gqa_layers`` — NO positions (``use_rope``
        false) and, under ``use_gqa_gate``, a sigmoid gate a channel on its
        output — and a KDA linear-attention mixer otherwise
        (``linear_attn_config``: ``num_heads`` heads of ``head_dim`` x
        ``head_dim``, a conv of ``short_conv_kernel_size``; low-rank gate
        projections of width ``head_dim``; ``kda_allow_neg_eigval``: the write
        strength ``β`` in (0, 2)). Every layer's MLP is the routed experts —
        sigmoid scores with a correction bias (``noaux_tc``), ONE group —
        beside ``n_shared_experts`` shared ones of the same width. Beside the
        published keys, a chip's share of the experts exactly as
        ``_from_deepseek_v3`` reads one: ``n_routed_experts`` is how many are
        HELD, ``n_routed_experts_total`` (default: the same) how many the
        router scores, ``ep_rank`` which run of them this is. Kept and NOT
        read, each for its reason (``SOLAR_KEYS_NOT_READ``). What is not done is
        refused by name."""
        need = (
            "gqa_layers", "linear_attn_config", "head_dim", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(f"solar_open2 config.json lacks {key!r}")
        refuse = {
            "kda_use_full_proj": (False,), "use_rope": (False,),
            "first_k_dense_replace": (0,), "norm_topk_prob": (True,),
            "tie_word_embeddings": (False,), "hidden_act": ("silu",),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "attention_bias": (False,),
        }
        why = {
            "kda_use_full_proj": "the gate projections are the low-rank pairs "
            "(W_a↓ / W_a↑, W_g↓ / W_g↑)",
            "use_rope": "the attention layers carry no positions (NoPE)",
            "first_k_dense_replace": "every layer's MLP is the routed "
            "experts; a dense layer is not built",
        }
        for key, ok in refuse.items():
            if key in hf and hf[key] not in ok:
                raise ValueError(
                    f"solar_open2 {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))})"
                    + (f": {why[key]}" if key in why else "")
                )
        lin = hf["linear_attn_config"]
        if lin.get("num_kv_heads") is not None:
            raise ValueError(
                "solar_open2 linear_attn_config.num_kv_heads "
                f"{lin['num_kv_heads']!r} is not supported (only null: a KDA "
                "layer has a key and a value head a query head)"
            )
        for key in ("num_heads", "head_dim"):
            if not lin.get(key):
                raise ValueError(
                    f"solar_open2 linear_attn_config lacks {key!r}"
                )
        L = int(hf["num_hidden_layers"])
        gqa = sorted(int(l) for l in hf["gqa_layers"])
        if not gqa or gqa[0] < 0 or gqa[-1] >= L or len(set(gqa)) != len(gqa):
            raise ValueError(
                f"solar_open2 gqa_layers {hf['gqa_layers']!r}: distinct layer "
                f"indices in 0..{L - 1}, at least one"
            )
        if len(gqa) == L:
            raise ValueError(
                f"solar_open2 gqa_layers names all {L} layers: a model with "
                "no KDA layer is the llama block's"
            )
        held = int(hf["n_routed_experts"])
        total = int(hf.get("n_routed_experts_total", held))
        rank = int(hf.get("ep_rank", 0))
        if total % held or not 0 <= rank < total // held:
            raise ValueError(
                f"solar_open2 share: {held} experts held of {total}, rank "
                f"{rank}: the held count must divide the total and the rank "
                f"lie in 0..{total // max(held, 1) - 1}"
            )
        eos = hf.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        return cls(
            model_type="solar_open2",
            vocab_size=hf["vocab_size"],
            hidden_size=int(hf["hidden_size"]),
            intermediate_size=int(hf.get("intermediate_size", 0)),
            num_hidden_layers=L,
            num_attention_heads=int(hf["num_attention_heads"]),
            num_key_value_heads=int(
                hf.get("num_key_value_heads", hf["num_attention_heads"])
            ),
            head_dim=int(hf["head_dim"]),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            num_experts=total,
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            norm_topk_prob=True,
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            n_group=1,
            topk_group=1,
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            ep_rank=rank,
            layer_pattern="".join("G" if l in gqa else "K" for l in range(L)),
            conv_kernel=int(lin.get("short_conv_kernel_size", 4)),
            kda_num_heads=int(lin["num_heads"]),
            kda_head_dim=int(lin["head_dim"]),
            kda_beta_scale=2.0 if hf.get("kda_allow_neg_eigval") else 1.0,
            attn_gate=bool(hf.get("use_gqa_gate", False)),
            bos_token_id=(
                1 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )

    @classmethod
    def _from_longcat_flash(cls, hf: dict[str, Any]) -> "ModelConfig":
        """``longcat_flash`` under the family's OWN key names (LongCat-Flash's
        decoder, which LongCat-Flash-Omni's language model is): ``num_layers``
        double layers — two latent attentions (``deepseek_v3``'s MLA;
        ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: ``q`` after ``q_b_proj``
        times ``(hidden / q_lora_rank)^½``, the normed latent before
        ``kv_b_proj`` times ``(hidden / kv_lora_rank)^½``) and two dense MLPs
        of ``ffn_hidden_size`` — around ONE shortcut-connected expert product:
        ``n_routed_experts`` gated experts of ``expert_ffn_hidden_size`` and
        ``zero_expert_num`` zero-compute experts that return their input,
        ``moe_topk`` a token by a float32 softmax over all of them (a
        correction bias for the choice only, weights not renormalised, times
        ``routed_scaling_factor``). A chip's share of the REAL experts as
        ``_from_deepseek_v3`` reads one: ``n_routed_experts`` is how many are
        HELD, ``n_routed_experts_total`` (default: the same) how many the
        router scores, ``ep_rank`` which run of them this is. What is not
        done is refused by name."""
        need = (
            "num_layers", "hidden_size", "ffn_hidden_size",
            "expert_ffn_hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "moe_topk", "vocab_size",
        )
        for key in need:
            if hf.get(key) is None:
                raise ValueError(
                    f"longcat_flash config.json lacks {key!r} (a model "
                    "without the q bottleneck, q_lora_rank null, is not "
                    "supported)"
                )
        refuse = {
            "zero_expert_type": ("identity",), "attention_method": ("MLA",),
            "norm_topk_prob": (False,), "router_bias": (False,),
            "tie_word_embeddings": (False,), "hidden_act": ("silu",),
            "attention_bias": (False,), "rope_interleave": (True,),
        }
        for key, ok in refuse.items():
            if key in hf and hf[key] is not None and hf[key] not in ok:
                raise ValueError(
                    f"longcat_flash {key}={hf[key]!r} is not supported (only "
                    f"{', '.join(map(repr, ok))})"
                )
        if hf.get("rope_scaling"):
            raise ValueError(
                "longcat_flash rope_scaling is not supported: the family "
                "publishes plain RoPE (serve a scaled checkpoint once its "
                "softmax-scale convention is in this repository)"
            )
        held = int(hf["n_routed_experts"])
        total = int(hf.get("n_routed_experts_total", held))
        rank = int(hf.get("ep_rank", 0))
        zero = int(hf.get("zero_expert_num") or 0)
        top_k = int(hf["moe_topk"])
        if total % held or not 0 <= rank < total // held:
            raise ValueError(
                f"longcat_flash share: {held} experts held of {total}, rank "
                f"{rank}: the held count must divide the total and the rank "
                f"lie in 0..{total // max(held, 1) - 1}"
            )
        if not 0 < top_k <= total + zero:
            raise ValueError(
                f"longcat_flash moe_topk {top_k} is not in 1..{total + zero} "
                "(the real and the zero-compute experts)"
            )
        H = int(hf["hidden_size"])
        eos = hf.get("eos_token_id", 2)
        eos_ids = tuple(eos) if isinstance(eos, list) else (eos,)
        return cls(
            model_type="longcat_flash",
            vocab_size=hf["vocab_size"],
            hidden_size=H,
            intermediate_size=int(hf["ffn_hidden_size"]),
            num_hidden_layers=int(hf["num_layers"]),
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf["num_attention_heads"],
            head_dim=int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"]),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            num_experts=total,
            num_experts_per_tok=top_k,
            norm_topk_prob=False,
            q_lora_rank=int(hf["q_lora_rank"]),
            kv_lora_rank=int(hf["kv_lora_rank"]),
            qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
            qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
            v_head_dim=int(hf["v_head_dim"]),
            moe_intermediate_size=int(hf["expert_ffn_hidden_size"]),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            experts_held=held,
            ep_rank=rank,
            zero_experts=zero,
            mla_q_scale=(
                (H / int(hf["q_lora_rank"])) ** 0.5
                if hf.get("mla_scale_q_lora") else 1.0
            ),
            mla_kv_scale=(
                (H / int(hf["kv_lora_rank"])) ** 0.5
                if hf.get("mla_scale_kv_lora") else 1.0
            ),
            bos_token_id=(
                1 if hf.get("bos_token_id") is None else hf["bos_token_id"]
            ),
            eos_token_id=eos_ids[0],
            eos_token_ids=eos_ids,
        )


#: ``solar_open2`` keys a published config carries that ``_from_solar_open2``
#: keeps and does NOT read, each with its reason
SOLAR_KEYS_NOT_READ = {
    "gqa_interval": "gqa_layers is the list of the attention layers",
    "rope_theta": "use_rope is false: no layer rotates",
    "partial_rotary_factor": "likewise",
    "intermediate_size": "the dense MLP's width: first_k_dense_replace is 0, "
    "no layer has one",
}


# Convenience presets (sizes mirror the models the reference targets:
# Llama-2-7B / Llama-3.2-3B / GPT-2, /root/reference/README.md + model_sharder.py)
def llama2_7b() -> ModelConfig:
    return ModelConfig()


def llama2_13b() -> ModelConfig:
    return ModelConfig(
        hidden_size=5120,
        intermediate_size=13824,
        num_hidden_layers=40,
        num_attention_heads=40,
        num_key_value_heads=40,
    )


def llama3_8b() -> ModelConfig:
    # Llama-3-8B proper: plain 500k-theta RoPE, 8k context, NO rope_scaling
    # (only the 3.1+ releases scale frequencies — see llama31_8b).
    return ModelConfig(
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        max_position_embeddings=8192,
        rope_theta=500000.0,
        bos_token_id=128000,
        eos_token_id=128001,
    )


def llama31_8b() -> ModelConfig:
    return dataclasses.replace(
        llama3_8b(),
        max_position_embeddings=131072,
        rope_scaling=RopeScaling(),
    )


def llama32_3b() -> ModelConfig:
    return ModelConfig(
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_hidden_layers=28,
        num_attention_heads=24,
        num_key_value_heads=8,
        head_dim=128,
        max_position_embeddings=8192,
        rope_theta=500000.0,
        rope_scaling=RopeScaling(factor=32.0),
        tie_word_embeddings=True,
        bos_token_id=128000,
        eos_token_id=128001,
    )


def llama2_70b() -> ModelConfig:
    return ModelConfig(
        hidden_size=8192,
        intermediate_size=28672,
        num_hidden_layers=80,
        num_attention_heads=64,
        num_key_value_heads=8,
    )


def gpt2_small() -> ModelConfig:
    return ModelConfig.from_hf_config({"model_type": "gpt2"})


def qwen25_7b() -> ModelConfig:
    """Qwen2.5-7B: llama block structure + qkv biases (third model family)."""
    return ModelConfig.from_hf_config({
        "model_type": "qwen2",
        "vocab_size": 152064,
        "hidden_size": 3584,
        "intermediate_size": 18944,
        "num_hidden_layers": 28,
        "num_attention_heads": 28,
        "num_key_value_heads": 4,
        "max_position_embeddings": 32768,
        "rms_norm_eps": 1e-6,
        "rope_theta": 1000000.0,
        "tie_word_embeddings": False,
        "bos_token_id": 151643,
        # both the Instruct eos (<|im_end|> 151645) and the base/endoftext id
        # (151643): the stop set must catch either, whichever weights load
        "eos_token_id": [151645, 151643],
    })


def gemma_2b() -> ModelConfig:
    """Gemma-2B (fourth model family): MQA (1 kv head), head_dim 256
    decoupled from hidden/heads, gelu MLP, scaled embeddings, tied head."""
    return ModelConfig.from_hf_config({
        "model_type": "gemma",
        "vocab_size": 256000,
        "hidden_size": 2048,
        "intermediate_size": 16384,
        "num_hidden_layers": 18,
        "num_attention_heads": 8,
        "num_key_value_heads": 1,
        "head_dim": 256,
        "max_position_embeddings": 8192,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "hidden_act": "gelu_pytorch_tanh",
        "bos_token_id": 2,
        "eos_token_id": 1,
    })


def gemma_7b() -> ModelConfig:
    """Gemma-7B."""
    return ModelConfig.from_hf_config({
        "model_type": "gemma",
        "vocab_size": 256000,
        "hidden_size": 3072,
        "intermediate_size": 24576,
        "num_hidden_layers": 28,
        "num_attention_heads": 16,
        "num_key_value_heads": 16,
        "head_dim": 256,
        "max_position_embeddings": 8192,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "hidden_act": "gelu_pytorch_tanh",
        "bos_token_id": 2,
        "eos_token_id": 1,
    })


def olmoe_1b_7b() -> ModelConfig:
    """OLMoE-1B-7B-0125-Instruct: the llama block with a q/k RMSNorm and 64
    experts of width 1024, 8 a token, kept probabilities not renormalised;
    plain MHA (16 key/value heads), untied head."""
    return ModelConfig.from_hf_config({
        "model_type": "olmoe",
        "vocab_size": 50304,
        "hidden_size": 2048,
        "intermediate_size": 1024,
        "num_hidden_layers": 16,
        "num_attention_heads": 16,
        "num_key_value_heads": 16,
        "max_position_embeddings": 4096,
        "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0,
        "tie_word_embeddings": False,
        "clip_qkv": None,
        "num_experts": 64,
        "num_experts_per_tok": 8,
        "norm_topk_prob": False,
        "bos_token_id": None,
        "eos_token_id": 50279,
    })


def tiny_olmoe(**kw) -> ModelConfig:
    """Tiny olmoe-layout config (q/k norm, 8 experts of width 32, 2 a token)
    for CPU tests."""
    base = dict(
        model_type="olmoe",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
        num_experts=8,
        num_experts_per_tok=2,
        norm_topk_prob=False,
    )
    base.update(kw)
    return ModelConfig.from_hf_config(base)


def tiny_keye_vl2_keys(**kw) -> dict:
    """The published-style keys of ``tiny_keye_vl2``: GQA 4 / 2 heads of 16,
    per-head q/k norm, 8 experts of width 32 (2 a token, renormalised), an
    indexer of 4 heads x 8 over one 8-wide index key, ``topk`` 16."""
    base = dict(
        model_type="KeyeVL2",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=256,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                      "type": "default"},
        num_experts=8,
        num_experts_per_tok=2,
        norm_topk_prob=True,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 16},
        tie_word_embeddings=False,
    )
    base.update(kw)
    return base


def tiny_keye_vl2(**kw) -> ModelConfig:
    """Tiny KeyeVL2-layout config (a token-selecting llama block) for CPU
    tests."""
    return ModelConfig.from_hf_config(tiny_keye_vl2_keys(**kw))


def tiny_deepseek_v3_keys(**kw) -> dict:
    """The published-style keys of ``tiny_deepseek_v3`` (what a
    ``config.json`` of it would hold)."""
    base = dict(
        model_type="deepseek_v3",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=3,
        first_k_dense_replace=1,
        num_attention_heads=4,
        num_key_value_heads=4,
        q_lora_rank=24,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=24,
        n_routed_experts=8,
        n_shared_experts=1,
        num_experts_per_tok=2,
        n_group=4,
        topk_group=2,
        routed_scaling_factor=2.5,
        norm_topk_prob=True,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "yarn", "factor": 4.0, "beta_fast": 32,
            "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 32,
        },
        eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_deepseek_v3(**kw) -> ModelConfig:
    """Tiny deepseek_v3-layout config for CPU tests: 1 dense + 2 expert
    layers, ``v_head_dim`` != ``qk_nope_head_dim``, YaRN on, 8 experts in 4
    groups of which 2 are kept, 2 a token, one shared expert."""
    return ModelConfig.from_hf_config(tiny_deepseek_v3_keys(**kw))


def tiny_mimo_v2_keys(**kw) -> dict:
    """The published-style keys of ``tiny_mimo_v2``."""
    base = dict(
        model_type="mimo_v2",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=32,
        num_hidden_layers=4,
        hybrid_layer_pattern=[0, 1, 1, 0],
        moe_layer_freq=[0, 1, 1, 1],
        num_attention_heads=4,
        num_key_value_heads=1,
        swa_num_key_value_heads=2,
        head_dim=24,
        v_head_dim=16,
        partial_rotary_factor=0.334,
        sliding_window=8,
        attention_chunk_size=8,
        attention_value_scale=0.707,
        add_swa_attention_sink_bias=True,
        add_full_attention_sink_bias=False,
        n_routed_experts=8,
        n_shared_experts=None,
        num_experts_per_tok=2,
        n_group=1,
        topk_group=1,
        routed_scaling_factor=None,
        norm_topk_prob=True,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        max_position_embeddings=256,
        layernorm_epsilon=1e-5,
        rope_theta=1e7,
        swa_rope_theta=1e4,
        eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_mimo_v2(**kw) -> ModelConfig:
    """Tiny mimo_v2-layout config for CPU tests: a dense full-attention
    layer, two expert window layers (window 8, a sink, 2 key/value heads)
    and an expert full-attention layer (1 head); keys of 24 (rotary on the
    first 8), values of 16."""
    return ModelConfig.from_hf_config(tiny_mimo_v2_keys(**kw))


def nemotron3_super_keys(**kw) -> dict:
    """Nemotron-3-Super-120B-A12B's published ``config.json`` keys (88 layers,
    512 experts; the multi-token-prediction module, which is not built, set
    to 0 of the published 1)."""
    base = dict(
        model_type="nemotron_h",
        attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
        head_dim=128, hidden_size=4096,
        hybrid_override_pattern=(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"
        ),
        intermediate_size=2688, layer_norm_epsilon=1e-5, mamba_head_dim=64,
        mamba_hidden_act="silu", mamba_num_heads=128, mamba_proj_bias=False,
        max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
        moe_intermediate_size=2688, moe_latent_size=1024,
        moe_shared_expert_intermediate_size=5376,
        moe_shared_expert_overlap=False, mtp_hybrid_override_pattern="*E",
        n_group=1, n_groups=8, n_routed_experts=512, n_shared_experts=1,
        norm_eps=1e-5, norm_topk_prob=True, num_attention_heads=32,
        num_experts_per_tok=22, num_hidden_layers=88, num_key_value_heads=2,
        num_logits_to_keep=1, num_nextn_predict_layers=0,
        partial_rotary_factor=1, rescale_prenorm_residual=True,
        residual_in_fp32=False, rope_theta=10000, routed_scaling_factor=5,
        sliding_window=None, ssm_state_size=128, tie_word_embeddings=False,
        time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
        topk_group=1, use_bias=False, use_conv_bias=True,
        use_mamba_kernels=True, vocab_size=131072,
    )
    base.update(kw)
    return base


def nemotron3_super_120b_a12b(**kw) -> ModelConfig:
    return ModelConfig.from_hf_config(nemotron3_super_keys(**kw))


def tiny_nemotron_h_keys(**kw) -> dict:
    """The published-style keys of ``tiny_nemotron_h``."""
    base = nemotron3_super_keys(
        vocab_size=256, hidden_size=64, num_hidden_layers=6,
        hybrid_override_pattern="MEM*EM", num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
        mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8,
        n_routed_experts=8, num_experts_per_tok=3, moe_intermediate_size=24,
        moe_latent_size=32, moe_shared_expert_intermediate_size=48,
        intermediate_size=24, routed_scaling_factor=2.5,
        max_position_embeddings=256, eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_nemotron_h(**kw) -> ModelConfig:
    """Tiny nemotron_h-layout config for CPU tests: three Mamba-2 mixers (8
    heads of 16, a state of 16, 2 groups, blocks of 8 positions), two
    LatentMoE layers (8 experts of 24 in a latent space of 32, 3 a token, a
    shared expert of 48) and one attention layer (4 heads, 2 key/value)."""
    return ModelConfig.from_hf_config(tiny_nemotron_h_keys(**kw))


#: published ``jamba`` keys that ``_from_jamba`` keeps and does NOT read
JAMBA_KEYS_NOT_READ = (
    "expert_layer_period", "expert_layer_offset", "num_experts_per_tok",
    "use_mamba_kernels", "num_logits_to_keep",
)


def jamba2_3b_keys(**kw) -> dict:
    """AI21-Jamba2-3B's published ``config.json`` keys (28 layers: 26 Mamba-1
    mixers, attention at layers 7 and 21; a dense gated MLP in every layer)."""
    base = dict(
        model_type="jamba",
        attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
        expert_layer_period=2, hidden_act="silu", hidden_size=2560,
        intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
        mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
        mamba_proj_bias=False, max_position_embeddings=262144,
        num_attention_heads=20, num_experts=1, num_experts_per_tok=1,
        num_hidden_layers=28, num_key_value_heads=1, num_logits_to_keep=1,
        rms_norm_eps=1e-6, sliding_window=None, tie_word_embeddings=True,
        use_mamba_kernels=True, vocab_size=65536,
    )
    base.update(kw)
    return base


def jamba2_3b(**kw) -> ModelConfig:
    return ModelConfig.from_hf_config(jamba2_3b_keys(**kw))


def tiny_jamba_keys(**kw) -> dict:
    """The published-style keys of ``tiny_jamba``."""
    base = jamba2_3b_keys(
        vocab_size=256, hidden_size=64, num_hidden_layers=6,
        attn_layer_period=4, attn_layer_offset=2, num_attention_heads=4,
        num_key_value_heads=1, intermediate_size=96, mamba_d_state=4,
        mamba_dt_rank=3, max_position_embeddings=256, eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_jamba(**kw) -> ModelConfig:
    """Tiny jamba-layout config for CPU tests: a period of 4 with the
    attention layer inside it (``MM*MMM``; 4 query heads over ONE key/value
    head of 16), five Mamba-1 mixers of 128 channels with a state of 4 a
    channel and a step rank of 3, a gated MLP of 96 in every layer, a tied
    head."""
    return ModelConfig.from_hf_config(tiny_jamba_keys(**kw))


def tiny_solar_open2_keys(**kw) -> dict:
    """The published-style keys of ``tiny_solar_open2``."""
    base = dict(
        model_type="solar_open2",
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=16, num_heads=4,
            num_kv_heads=None,
        ),
        gqa_interval=3, gqa_layers=[0, 4], use_rope=False, use_gqa_gate=True,
        kda_use_full_proj=False, kda_allow_neg_eigval=True,
        first_k_dense_replace=0, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000,
        partial_rotary_factor=1, tie_word_embeddings=False, eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_solar_open2(**kw) -> ModelConfig:
    """Tiny solar_open2-layout config for CPU tests: two periods of 4
    (``GKKKGKKK``: gated GQA without positions — 4 query / 2 key-value heads
    of 16 —, then three KDA mixers of 4 heads of 16 x 16 state, a conv of 4),
    every layer's MLP 8 routed experts, 2 a token, and one shared expert."""
    return ModelConfig.from_hf_config(tiny_solar_open2_keys(**kw))


def tiny_longcat_flash_keys(**kw) -> dict:
    """The published-style keys of ``tiny_longcat_flash`` (the family's own
    names, as LongCat-Flash's ``config.json`` holds them)."""
    base = dict(
        model_type="longcat_flash",
        vocab_size=256, hidden_size=64, ffn_hidden_size=96,
        expert_ffn_hidden_size=32, num_layers=3, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        n_routed_experts=8, zero_expert_num=4, zero_expert_type="identity",
        moe_topk=3, routed_scaling_factor=6.0, attention_method="MLA",
        attention_bias=False, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10000.0, eos_token_id=255,
    )
    base.update(kw)
    return base


def tiny_longcat_flash(**kw) -> ModelConfig:
    """Tiny longcat_flash-layout config for CPU tests: 3 double layers (6
    latent attentions of 4 heads, ``v_head_dim`` != ``qk_nope_head_dim``, both
    latent scales on), 8 real + 4 zero-compute experts, 3 a token, scale 6."""
    return ModelConfig.from_hf_config(tiny_longcat_flash_keys(**kw))


def tiny_ouro_keys(**kw) -> dict:
    """The published-style keys of ``tiny_ouro`` (Ouro's own names)."""
    base = dict(
        model_type="ouro",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        head_dim=16,
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
        layer_types=["full_attention"] * 3,
        use_sliding_window=False,
        sliding_window=None,
        total_ut_steps=3,
        early_exit_threshold=1.0,
    )
    base.update(kw)
    return base


def tiny_ouro(**kw) -> ModelConfig:
    """Tiny ouro-layout config for CPU tests: 3 sandwich-norm layers run 3
    times a token, an exit gate over the passes."""
    return ModelConfig.from_hf_config(tiny_ouro_keys(**kw))


def tiny_qwen2(**kw) -> ModelConfig:
    """Tiny qwen2-layout config (llama + qkv biases) for CPU tests."""
    base = dict(
        model_type="qwen2",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
    )
    base.update(kw)
    return ModelConfig.from_hf_config(base)


def tiny_llama(**kw) -> ModelConfig:
    """Tiny config for CPU tests (the reference has no tests; SURVEY.md §4)."""
    base = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
    )
    base.update(kw)
    return ModelConfig(**base)


def tiny_gemma(**kw) -> ModelConfig:
    """Tiny gemma-layout config (llama block + gelu MLP + scaled embeddings
    + offset RMSNorm + tied head, explicit head_dim) for CPU tests."""
    base = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=32,  # decoupled from hidden/heads like the real family
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        hidden_act="gelu_tanh",
        norm_offset=1.0,
        embed_multiplier=64.0 ** 0.5,
        tie_word_embeddings=True,
    )
    base.update(kw)
    return ModelConfig(**base)


def tiny_gpt2(**kw) -> ModelConfig:
    base = dict(
        model_type="gpt2",
        vocab_size=256,
        hidden_size=64,
        intermediate_size=256,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
        tie_word_embeddings=True,
        bos_token_id=0,
        eos_token_id=0,
    )
    base.update(kw)
    return ModelConfig(**base)
