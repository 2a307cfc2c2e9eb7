"""Pure-JAX ``longcat_flash`` causal LM (LongCat-Flash's decoder as published
in ``transformers``' ``modeling_longcat_flash.py``; LongCat-Flash-Omni's
language model is this block): a layer that is TWO layers around one
shortcut-connected expert product, with experts that cost nothing.

**One layer**, input ``h``, four norms, two latent attentions, two dense MLPs,
one expert product (``N`` = RMSNorm)::

    h1 = h  + MLA_0(N(h;  input_norm_0))
    x1 = N(h1; post_norm_0)
    m  = MoE(x1)                     # the shortcut: used four lines down
    h2 = h1 + MLP_0(x1)
    h3 = h2 + MLA_1(N(h2; input_norm_1))
    h4 = h3 + MLP_1(N(h3; post_norm_1)) + m

The expert product has no consumer for a whole attention + MLP: inside the one
scan body the scheduler may place it anywhere in between (a deployment hides
the experts' exchange there).

**Leaves.** Every layer is the same kind, so the tree is ``params["layers"]
[leaf]`` stacked ``[L, ...]``. The two sub-layers' leaves carry a ``_0`` /
``_1`` SUFFIX on ``deepseek_v3``'s names (``input_norm_0``, ``wq_a_0``,
``q_a_norm_0``, ``wq_b_0``, ``wkv_a_0``, ``kv_a_norm_0``, ``w_uk_0``,
``w_uv_0``, ``wo_0``, ``post_norm_0``, ``w_gate_0``, ``w_up_0``, ``w_down_0``,
and the same with ``_1``) — not a sub-layer axis: every matmul leaf stays a
plain ``[in, out]`` matrix for ``ops/quant``, the shard store and the
benchmark's generator. The experts' are ``router [H, E + Z]``, ``router_bias
[E + Z]`` (float32, the choice only), ``we_gate`` / ``we_up [H, held·F]``,
``we_down [held·F, H]``.

**Attention** is ``models/deepseek_v3.mla_attention`` (absorbed, one latent
entry a token and ATTENTION) with the family's two scales folded into its
norms' gains: ``q`` times ``cfg.mla_q_scale``, the normed latent times
``cfg.mla_kv_scale`` — so the arena entry holds ``[s_kv · c_kv | k_pe]``,
``k_pe`` unscaled. A layer fills TWO cache / arena layer slots
(``cfg.arena_slots``): layer ``l`` writes and reads slots ``2l`` and ``2l +
1``; the layer mask stays one entry a layer.

**Experts.** ``ops/moe.route(bias=, scale=)``: float32 softmax over all ``E +
Z`` outputs, the ``k`` largest of ``p + bias`` chosen, the UNbiased ``p`` kept
as they are, times ``routed_scaling_factor``. ``ops/moe.expert_mlp(held=,
zero_from=E)``: pairs on the real experts HELD here form tiles; pairs on real
experts held elsewhere add nothing; pairs on the ``Z`` zero-compute experts
(ids ``E …``) form no tile and add ``(Σ w) · x1``, computed where the token
lives. A dead row, a pad position and a masked layer route nowhere, add no
zero-compute term and are not counted. ``stats.expert_tokens`` is ``[E + Z]``.

Refused by name: tensor and context parallelism over this model, a quantized
(int8/fp8) latent cache. Every layer is one kind, so a ring's stages differ in
layer COUNT alone, which the placement pads and the layer mask gates (a masked
layer writes its two slots' trash block and routes nowhere).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.flash_attention import attention_step
from ..ops.norms import rms_norm
from ..ops.rope import rope_cos_sin
from .cache import KVCache
from .config import ModelConfig
from .deepseek_v3 import gated_mlp, mla_attention, softmax_scale
from .family import refuse_axes
from .llama import embed, final_logits  # noqa: F401  (the family's own)
from .stack import join_whole, masked_stats, scan_layers_paged, split_whole

Params = dict[str, Any]

#: a sub-layer's leaves, ``deepseek_v3``'s names (stored with ``_0`` / ``_1``)
SUB_LEAVES = (
    "input_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "w_uk",
    "w_uv", "wo", "post_norm", "w_gate", "w_up", "w_down",
)


def sub_layer(p: Params, i: int) -> Params:
    """Sub-layer ``i``'s leaves under ``deepseek_v3``'s plain names."""
    return {name: p[f"{name}_{i}"] for name in SUB_LEAVES}


# ---------------------------------------------------------------------------
# Initialization (random weights for tests; the benchmark draws its own)
# ---------------------------------------------------------------------------

def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16,
) -> Params:
    H, Nh = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv, I = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.intermediate_size
    L = num_layers
    ks = iter(jax.random.split(key, 32))

    def w(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            fan_in ** -0.5, dtype
        )

    def gain(n):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (L, n), dtype)

    p = {}
    for i in (0, 1):
        sub = {
            "input_norm": gain(H),
            "wq_a": w(H, rq),
            "q_a_norm": gain(rq),
            "wq_b": w(rq, Nh * (dn + dr)),
            "wkv_a": jnp.pad(
                w(H, rkv + dr),
                ((0, 0), (0, 0), (0, cfg.cache_k_dim - rkv - dr)),
            ),
            "kv_a_norm": gain(rkv),
            "w_uk": w(Nh * dn, rkv, fan_in=rkv),
            "w_uv": w(Nh * dv, rkv, fan_in=rkv),
            "wo": w(Nh * dv, H),
            "post_norm": gain(H),
            "w_gate": w(H, I), "w_up": w(H, I), "w_down": w(I, H),
        }
        p.update({f"{name}_{i}": leaf for name, leaf in sub.items()})
    F, held = cfg.moe_intermediate_size, cfg.experts_held_
    p.update(
        # (drawn wide: a unit-variance router over E + Z outputs keeps k
        # weights of ~1 / (E + Z) each, and the expert path would vanish)
        router=3.0 * w(H, cfg.router_experts),
        router_bias=0.1 * jax.random.normal(
            next(ks), (L, cfg.router_experts), jnp.float32
        ),
        we_gate=w(H, held * F), we_up=w(H, held * F),
        we_down=w(held * F, H, fan_in=F),
    )
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    return {
        "embed": (
            jax.random.normal(k_emb, (V, H), jnp.float32) * H ** -0.5
        ).astype(dtype),
        "layers": init_layer_params(
            cfg, k_layers, cfg.num_hidden_layers, dtype
        ),
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": (
            jax.random.normal(k_head, (H, V), jnp.float32) * H ** -0.5
        ).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------

def layer_block(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    cos: jnp.ndarray,  # [B, S, rope]
    sin: jnp.ndarray,
    attend,  # (i, cache, q [B,S,Nh,Dk], entry [B,S,1,Dk]) -> (o_lat, cache):
    #   attention ``i`` of the layer writes its latent entries into ITS slot
    #   of ``cache`` and attends it
    cache,
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] positions that route
    moe_backend: str = "auto",
):
    """The module docstring's six lines, with the cache mechanism injected.
    Returns ``(h, cache, stats)``."""
    B, S, H = h.shape
    eps = cfg.rms_norm_eps
    p0, p1 = sub_layer(p, 0), sub_layer(p, 1)
    scales = dict(q_scale=cfg.mla_q_scale, kv_scale=cfg.mla_kv_scale)

    h, cache = mla_attention(
        cfg, p0, h, cos, sin, functools.partial(attend, 0, cache), **scales
    )
    with jax.named_scope("norm"):
        x1 = rms_norm(h, p0["post_norm"], eps)
    x2 = x1.reshape(B * S, H)
    with jax.named_scope("router"):
        weights, ids = moe.route(
            x2, p["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob,
            bias=p["router_bias"], scale=cfg.routed_scaling_factor,
        )
    m, stats = moe.expert_mlp(
        x2, weights, ids, p["we_gate"], p["we_up"], p["we_down"],
        cfg.router_experts,
        live=None if moe_live is None else moe_live.reshape(B * S),
        layer=p.get("layer"), backend=moe_backend, held=cfg.held_experts_,
        zero_from=cfg.num_experts,
    )
    with jax.named_scope("mlp"):
        h = h + gated_mlp(x1, p0["w_gate"], p0["w_up"], p0["w_down"])
    h, cache = mla_attention(
        cfg, p1, h, cos, sin, functools.partial(attend, 1, cache), **scales
    )
    with jax.named_scope("norm"):
        x = rms_norm(h, p1["post_norm"], eps)
    with jax.named_scope("mlp"):
        h = h + gated_mlp(x, p1["w_gate"], p1["w_up"], p1["w_down"])
    with jax.named_scope("zero_expert"):  # the shortcut joins the stream
        h = h + m.reshape(B, S, H)
    return h, cache, stats


def forward_layers(
    cfg: ModelConfig,
    layers: Params,  # stacked leaves [L, ...]
    h: jnp.ndarray,
    cache: KVCache,  # k [2L, B, C, 1, Dk] latents, v [2L, B, C, 1, 0]
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Dense-cache path (the monolith, one-shot admission): ``stack.
    scan_layers``'s contract — key positions recorded once, the cache on the
    scan carry with in-place writes of the step's positions only, a masked
    layer changing nothing — over TWO cache slots a layer, which that scan's
    one row a layer cannot hand out. Returns ``(h, cache, stats)``."""
    refuse_axes(cfg, tp_axis)
    S = h.shape[1]
    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)
    scale = softmax_scale(cfg)
    r = cfg.kv_lora_rank
    length = cache.length
    L = cache.num_layers // 2 if layer_mask is None else layer_mask.shape[0]
    if layer_mask is None:
        layer_mask = jnp.ones((L,), bool)
    kv_pos = jax.lax.dynamic_update_slice(
        cache.pos, positions.astype(jnp.int32), (0, length)
    )
    layers, whole = split_whole(layers)

    def body(carry, xs):
        h, k_all = carry
        p, i, valid = xs

        def attend(j, k_all, q_full, entry):
            slot = 2 * i + j
            with jax.named_scope("kv_take"):
                k_row = jax.lax.dynamic_index_in_dim(
                    k_all, slot, keepdims=False
                )
            with jax.named_scope("kv_write"):
                entry = entry.astype(k_row.dtype)
                k_r = jax.lax.dynamic_update_slice(
                    k_row, entry, (0, length, 0, 0)
                )
            o = attention_step(
                q_full, k_r, k_r[..., :r], positions, kv_pos, length, scale
            )
            with jax.named_scope("kv_put"):
                # only positions [length, length + S) of the row changed;
                # a masked layer writes back what was there
                old = jax.lax.dynamic_slice(
                    k_row, (0, length, 0, 0), entry.shape
                )
                k_all = jax.lax.dynamic_update_slice(
                    k_all, jnp.where(valid, entry, old)[None],
                    (slot, 0, length, 0, 0),
                )
            return o, k_all

        # (a masked layer's output and stats are dropped below)
        h_new, k_all, stats = layer_block(
            cfg, join_whole(p, whole, i), h, cos, sin, attend, k_all,
            moe_live, "xla",
        )
        h = jnp.where(valid, h_new, h)
        return (h, k_all), masked_stats(stats, valid)

    (h, k_all), stats = jax.lax.scan(
        body, (h, cache.k),
        (layers, jnp.arange(L, dtype=jnp.int32), layer_mask),
    )
    new = KVCache(k=k_all, v=cache.v, pos=kv_pos, length=length + S)
    return h, new, stats


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # stacked leaves [L, ...]
    h: jnp.ndarray,
    k_arena: jnp.ndarray,  # [2L, NB, 1, BS, Dk] the latent pool
    v_arena: jnp.ndarray,  # [2L, NB, 1, BS, 0] — holds nothing
    block_table: jnp.ndarray,
    cols: jnp.ndarray,
    kv_positions: jnp.ndarray,
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    write_valid=True,
    tp_axis: Optional[str] = None,
    backend: str = "auto",
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    prefill: bool = False,
    walk=None,
    cp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Paged path (``models/deepseek_v3.forward_layers_paged``'s contract)
    over an arena with TWO layer slots a layer: layer ``l``'s first attention
    writes and reads slot ``2l``, its second ``2l + 1`` — through the latent
    kernels as they are, which take the whole stack and a slot index.
    Returns ``(h, k_arena, v_arena, None, None, stats)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) latent cache is not implemented"
        )
    col0 = cols[0, 0] if prefill else None  # (deepseek_v3's note)
    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)
    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )
    scale = softmax_scale(cfg)
    r = cfg.kv_lora_rank

    def apply(p, l, valid, h, k_all, v_all, ks_all, vs_all):
        def attend(j, k_all, q_full, entry):
            slot = 2 * l + j
            if not prefill:  # a decode step
                o, k_a, *_ = paged_attention_write(
                    q_full, entry, None, k_all, v_all, slot, block_table,
                    cols, positions, kv_positions, valid=wv & valid,
                    scale=scale, backend=backend, latent_v=r,
                )
                return o, k_a
            k_a, _ = write_chunk_kv(
                k_all, v_all, slot, block_table, col0, entry, None,
                valid=wv & valid,
            )
            return paged_prefill(
                q_full, k_a, v_all, slot, block_table, positions,
                kv_positions, scale, backend=backend, walk=walk, latent_v=r,
            ), k_a

        gate = jnp.asarray(wv) & valid
        live = jnp.broadcast_to(
            gate if moe_live is None else moe_live & gate, h.shape[:2]
        )
        h, k_a, stats = layer_block(
            cfg, p, h, cos, sin, attend, k_all, live, backend
        )
        return h, k_a, v_all, None, None, stats

    if layer_mask is None:
        layer_mask = jnp.ones((k_arena.shape[0] // 2,), bool)
    return scan_layers_paged(layers, h, k_arena, v_arena, apply, layer_mask)


def prefill_walks(cfg: ModelConfig, block_table, positions, kv_positions,
                  nlive, stage_layers):
    """The chunked-prefill kernel's work list (ONE: the attentions are
    alike, one latent head each) and what it counts over the stage's
    attention CALLS — two a layer."""
    from ..ops.paged_attention import prefill_walk

    w = prefill_walk(
        block_table, positions, kv_positions, nlive,
        q_heads=cfg.num_attention_heads, kv_heads=1,
    )
    n = 2 * jax.tree.leaves(stage_layers)[0].shape[0]
    return w, n * jnp.stack([w.steps, w.run_of.shape[0] - 1]).astype(jnp.int32)


def forward(
    cfg: ModelConfig,
    params: Params,
    token_ids: jnp.ndarray,  # [B, S]
    cache: KVCache,
    positions: jnp.ndarray,  # [B, S]
) -> tuple[jnp.ndarray, KVCache]:
    """Full-model step: embed → layers → logits (the monolithic oracle)."""
    h = embed(params, token_ids)
    h, cache, _ = forward_layers(cfg, params["layers"], h, cache, positions)
    return final_logits(cfg, params, h), cache


def forward_full(cfg: ModelConfig, params: Params, token_ids: jnp.ndarray):
    """The whole model over whole sequences from an empty dense cache: logits
    ``[B, S, V]`` (the tier-1 tests hold them to the reference,
    ``benchmark/blocks/longcat_flash.py``)."""
    from .cache import init_cache

    B, S = token_ids.shape
    dtype = params["embed"].dtype
    cache = init_cache(cfg, B, S, dtype=dtype)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return forward(cfg, params, token_ids, cache, pos)[0]
