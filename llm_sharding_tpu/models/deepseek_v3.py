"""Pure-JAX ``deepseek_v3`` causal LM (DeepSeek-V3 as published in HF
``modeling_deepseek_v3.py``; GigaChat3.1-702B-A36B is this block): latent
attention (MLA) over ONE cached latent a token, leading dense layers, then
expert layers routed by sigmoid scores in groups beside a shared expert —
with a chip's SHARE of the routed experts.

**Layers of two kinds.** ``params["layers"] = {"dense": {leaf: [Ld, ...]},
"moe": {leaf: [Lm, ...]}}``, one stack per kind in layer order
(``cfg.layer_kinds``; ``stack.kind_spans`` lays a stage's layer slots out
kind after kind). Each kind is its own ``lax.scan``; the cache / arena and
the layer mask are shared and indexed by slot.

**Attention (MLA), absorbed everywhere.** ``c_q = RMSNorm(x W_qa)``, ``q =
c_q W_qb`` → heads of ``[nope | rope]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv
= RMSNorm(c_kv)``, ``k_pe`` rotated once and shared by all heads. The cache
holds ``[c_kv | k_pe]`` (padded to whole 128-lane tiles:
``cfg.cache_k_dim``) and NO values: one entry a token and layer. Keys and
values are never decompressed: ``q_lat[h] = q_nope[h] W_uk[h]`` (nope →
``kv_lora_rank``), scores ``[q_lat | RoPE(q_pe)] · [c_kv | k_pe]`` over ONE
latent head, ``o_lat[h] = Σ p c_kv`` (the value read is the first
``kv_lora_rank`` lanes of the key read), ``o[h] = o_lat[h] W_uv[h]``. The
softmax scale is ``(nope + rope)^-0.5 · m²``, ``m = yarn_mscale(factor,
mscale_all_dim)`` under YaRN. Decode and prefill (one-shot and chunked) run
this one form; it equals the decompressed attention of the published
description (tests/test_deepseek_v3.py holds it to that).

Weight layout (what ``utils/convert.py`` emits): ``wq_a [H, q_lora]``,
``q_a_norm``, ``wq_b [q_lora, Nh·(nope+rope)]`` (a head's rope columns
DE-INTERLEAVED, so rotation is the rotate-half of ``ops/rope.py`` and equals
``transformers``' ``rope_interleave: true`` on the published layout),
``wkv_a [H, cache_k_dim]`` — ``[c_kv | k_pe]`` columns (rope columns likewise)
and then ZERO columns up to the arena entry's width: 576 columns are not
whole 128-lane tiles, the chip stores such a matrix input-minor, and XLA
re-laid the whole layer stack of it (33 MB at 8 layers of GigaChat3.1's
widths) at the top of every decode call — ``kv_a_norm``, and
``kv_b_proj`` split per head into the two absorbed factors, each a plain
``[in, out]`` matmul leaf: ``w_uk [Nh·nope, kv_lora]`` (head ``h`` = rows
``h·nope …``) and ``w_uv [Nh·v, kv_lora]`` (head ``h`` = rows ``h·v …``) —
``kv_b_proj``'s own rows, the nope rows and the value rows of each head;
``wo [Nh·v, H]``.

**Expert layers.** ``y = Σ_k w_k · MLP_{e_k}(x) + MLP_shared(x)``: the router
``ops/moe.route_noaux_tc`` over all ``cfg.num_experts``; of the chosen pairs
only those on experts HELD here (``cfg.held_experts_``: ``experts_held_`` ids
from ``ep_rank · held``) are computed (``ops/moe.expert_mlp(held=…)``); the
shared expert (``ws_*``) is computed in full. Leaves: ``router [H, E]``, ``router_bias [E]``
(float32, used for the choice only), ``we_gate`` / ``we_up [H, held·F]``,
``we_down [held·F, H]``, ``ws_gate`` / ``ws_up [H, Fs]``, ``ws_down [Fs, H]``.

``mla_block`` is ``mla_attention`` (everything up to and including
``o_proj``'s residual add) followed by ``mlp_sub_block``; other families run
the halves on their own: ``models/solar_open2.py`` the second after its own
mixers, ``models/longcat_flash.py`` the first TWICE a layer with that family's
two latent scales (``q_scale`` after ``wq_b``, ``kv_scale`` on the normed
latent: folded into the norms' gains, and in the program only where they are
not 1) around a softmax-routed expert product of its own (a correction bias
for the choice, experts without weights: ``ops/moe.route(bias=)``,
``expert_mlp(zero_from=)``) — this block's router stays sigmoid ``noaux_tc``.

Refused by name: tensor and context parallelism over this model, a
quantized (int8/fp8) latent cache.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.flash_attention import attention_step
from ..ops.norms import rms_norm
from ..ops.quant import QTensor, qmatmul
from ..ops.rope import apply_rope, rope_cos_sin, yarn_mscale
from .cache import KVCache
from .config import ModelConfig
from .family import refuse_axes
from .llama import embed, final_logits  # noqa: F401  (the family's own)
from .stack import kind_spans, scan_layers, scan_layers_paged, zero_stats

Params = dict[str, Any]

KINDS = ("dense", "moe")


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.rope_type == "yarn" and rs.mscale_all_dim:
        m = yarn_mscale(rs.factor, rs.mscale_all_dim)
        scale *= m * m
    return scale


# ---------------------------------------------------------------------------
# Initialization (random weights for tests; real ones come from convert.py)
# ---------------------------------------------------------------------------

def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16,
    kind: Optional[str] = None,
) -> Params:
    """``num_layers`` stacked layers of ``kind``; without a kind, that many
    of EACH kind the model has, as the per-kind tree ``{kind: leaves}``."""
    if kind is None:
        return {
            k: init_layer_params(
                cfg, jax.random.fold_in(key, i), num_layers, dtype, k
            )
            for i, k in enumerate(dict.fromkeys(cfg.layer_kinds))
        }
    H, Nh = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    L = num_layers
    ks = iter(jax.random.split(key, 16))

    def w(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            fan_in ** -0.5, dtype
        )

    p = {
        "input_norm": jnp.ones((L, H), dtype),
        "wq_a": w(H, rq),
        "q_a_norm": jnp.ones((L, rq), dtype),
        "wq_b": w(rq, Nh * (dn + dr)),
        "wkv_a": jnp.pad(
            w(H, rkv + dr), ((0, 0), (0, 0), (0, cfg.cache_k_dim - rkv - dr))
        ),
        "kv_a_norm": jnp.ones((L, rkv), dtype),
        "w_uk": w(Nh * dn, rkv, fan_in=rkv),
        "w_uv": w(Nh * dv, rkv, fan_in=rkv),
        "wo": w(Nh * dv, H),
        "post_norm": jnp.ones((L, H), dtype),
    }
    if kind == "dense":
        I = cfg.intermediate_size
        p.update(w_gate=w(H, I), w_up=w(H, I), w_down=w(I, H))
        return p
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    held = cfg.experts_held_
    Fs = F * cfg.n_shared_experts
    p.update(
        router=w(H, E),
        router_bias=0.1 * jax.random.normal(next(ks), (L, E), jnp.float32),
        we_gate=w(H, held * F), we_up=w(H, held * F),
        we_down=w(held * F, H, fan_in=F),
        ws_gate=w(H, Fs), ws_up=w(H, Fs), ws_down=w(Fs, H),
    )
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    k_emb, k_dense, k_moe, k_head = jax.random.split(key, 4)
    V, H = cfg.vocab_size, cfg.hidden_size
    kinds = cfg.layer_kinds
    return {
        "embed": (
            jax.random.normal(k_emb, (V, H), jnp.float32) * H ** -0.5
        ).astype(dtype),
        "layers": {
            kind: init_layer_params(cfg, k, kinds.count(kind), dtype, kind)
            for kind, k in (("dense", k_dense), ("moe", k_moe))
            if kind in kinds
        },
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": (
            jax.random.normal(k_head, (H, V), jnp.float32) * H ** -0.5
        ).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------

def _codes(w):
    return (w.q, w.scale) if isinstance(w, QTensor) else (w, None)


def absorb_q(q_nope: jnp.ndarray, w_uk) -> jnp.ndarray:
    """``q_lat[h] = q_nope[h] W_uk[h]``: ``[B, S, Nh, nope]`` → ``[B, S, Nh,
    kv_lora]``. ``w_uk [Nh·nope, kv_lora]`` raw or int8 (one scale per
    latent channel, applied to the product)."""
    B, S, Nh, dn = q_nope.shape
    codes, scale = _codes(w_uk)
    out = _per_head(
        "hni,hio->hno", q_nope, codes.reshape(Nh, dn, codes.shape[-1])
    )
    if scale is not None:
        out = out * scale.astype(jnp.float32)
    return _from_heads(out, q_nope)


def absorb_o(o_lat: jnp.ndarray, w_uv) -> jnp.ndarray:
    """``o[h] = o_lat[h] W_uv[h]ᵀ``: ``[B, S, Nh, kv_lora]`` → ``[B, S, Nh,
    v]``. ``w_uv [Nh·v, kv_lora]`` raw or int8: the latent dim is the MINOR
    one of both factors (a 192-wide minor dim pads to 256 lanes, and XLA
    re-laid the layer's whole factor, 6.3 MB, every call to avoid it). Its
    one scale per latent channel lies along the contraction, so it is
    multiplied into ``o_lat`` first."""
    B, S, Nh, r = o_lat.shape
    codes, scale = _codes(w_uv)
    if scale is not None:
        o_lat = (
            o_lat.astype(jnp.float32) * scale.astype(jnp.float32)
        ).astype(o_lat.dtype)
    out = _per_head("hni,hoi->hno", o_lat, codes.reshape(Nh, -1, r))
    return _from_heads(out, o_lat)


def _per_head(spec, x, w):
    """``x [B, S, Nh, i]`` against a per-head weight (raw or int8 codes),
    heads as the LEADING batch dim of both operands — the canonical batched
    form: the weight is read where it lies — with float32 accumulation.
    Returns ``[Nh, B·S, o]`` float32."""
    B, S, Nh, i = x.shape
    xh = jnp.transpose(x, (2, 0, 1, 3)).reshape(Nh, B * S, i)
    return jnp.einsum(
        spec, xh, w.astype(x.dtype), preferred_element_type=jnp.float32
    )


def _from_heads(out, like):
    B, S, Nh, _ = like.shape
    out = out.astype(like.dtype).reshape(Nh, B, S, -1)
    return jnp.transpose(out, (1, 2, 0, 3))


def gated_mlp(x, w_gate, w_up, w_down):
    """SiLU-gated MLP (activation in float32, as ``models/llama.py``)."""
    act = jax.nn.silu(qmatmul(x, w_gate).astype(jnp.float32))
    return qmatmul(act.astype(x.dtype) * qmatmul(x, w_up), w_down)


def mla_block(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    cos: jnp.ndarray,  # [B, S, rope]
    sin: jnp.ndarray,
    attend,  # (q [B,S,Nh,Dk], entry [B,S,1,Dk]) -> (o_lat [B,S,Nh,kv_lora],
    #   cache): writes the step's latent entries, attends the cache
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] positions that route
    moe_backend: str = "auto",
):
    """One layer, dense or expert (keyed by the presence of ``router``),
    with the cache mechanism injected: ``mla_attention`` then
    ``mlp_sub_block``. Returns ``(h, cache, stats)``: ``cache`` is what
    ``attend`` returned beside its output, ``stats`` the layer's ``MoeStats``
    (None for a dense layer; the kinds' stats are joined over the stage's
    layer slots)."""
    h, cache = mla_attention(cfg, p, h, cos, sin, attend)
    h, stats = mlp_sub_block(cfg, p, h, moe_live, moe_backend)
    return h, cache, stats


def _scaled_norm(x, g, scale: float, eps: float):
    """``RMSNorm(x; g) · scale`` with the constant folded into the gain in
    float32 (the product rounds once, where the unscaled gain rounds); the
    plain norm at 1 — the program then is what it was without a scale."""
    if scale == 1.0:
        return rms_norm(x, g, eps)
    return rms_norm(x, g.astype(jnp.float32) * scale, eps).astype(x.dtype)


def mla_attention(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    cos: jnp.ndarray,  # [B, S, rope]
    sin: jnp.ndarray,
    attend,  # as ``mla_block``'s
    q_scale: float = 1.0,  # static: ``q`` after ``wq_b`` times this
    kv_scale: float = 1.0,  # static: the normed latent times this
):
    """A layer's attention half, ``h + MLA(RMSNorm_in(h)) W_o``, with the
    cache mechanism injected (``models/longcat_flash.py`` runs it twice a
    layer). Returns ``(h, cache)``. The two scales (``longcat_flash``'s
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora``) are folded into the gains
    of the norms before ``wq_b`` and before the absorbed factors — ``q`` is
    linear in ``c_q``, and the arena entry then HOLDS the scaled latent, once
    — and are in the program only where they are not 1. The named scopes are
    words of ``obs.stepline.SCOPES``."""
    B, S, H = h.shape
    Nh = cfg.num_attention_heads
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    Dk = cfg.cache_k_dim

    with jax.named_scope("norm"):
        x = rms_norm(h, p["input_norm"], eps)
    with jax.named_scope("qkv"):
        c_q = qmatmul(x, p["wq_a"])
        kv_a = qmatmul(x, p["wkv_a"])  # [B, S, Dk]: [c_kv | k_pe | zeros]
        # the projections leave as the dots made them (models/llama.py, PR
        # 31): without this edge XLA folds the column split below into the
        # dot and re-lays the WHOLE layer stack of the weight at the top of
        # every call
        kv_a = jax.lax.optimization_barrier(kv_a)
    with jax.named_scope("norm"):
        c_q = _scaled_norm(c_q, p["q_a_norm"], q_scale, eps)
        c_kv = _scaled_norm(kv_a[..., :r], p["kv_a_norm"], kv_scale, eps)
    with jax.named_scope("qkv"):
        # likewise: the head split folded into this dot cost a copy of the
        # stack of ``wq_b`` (151 MB at 8 layers of GigaChat3.1's widths,
        # 0.6 ms) a decode step, seen in the compiled v5e program
        q = jax.lax.optimization_barrier(qmatmul(c_q, p["wq_b"]))
        q = q.reshape(B, S, Nh, dn + dr)
    with jax.named_scope("rope"):
        q_pe = apply_rope(q[..., dn:], cos, sin)
        k_pe = apply_rope(kv_a[:, :, None, r:r + dr], cos, sin)  # [B, S, 1, rope]
    with jax.named_scope("absorb"):
        q_lat = absorb_q(q[..., :dn], p["w_uk"])
        pad = Dk - r - dr
        q_full = jnp.concatenate(
            [q_lat, q_pe] + ([jnp.zeros((B, S, Nh, pad), q_lat.dtype)]
                             if pad else []), axis=-1,
        )
    with jax.named_scope("kv_write"):
        entry = jnp.concatenate(
            [c_kv[:, :, None, :], k_pe.astype(c_kv.dtype)]
            + ([jnp.zeros((B, S, 1, pad), c_kv.dtype)] if pad else []),
            axis=-1,
        )  # [B, S, 1, Dk]: what the cache holds of a token
    o_lat, cache = attend(q_full, entry)
    with jax.named_scope("absorb"):
        o = absorb_o(o_lat, p["w_uv"])
    with jax.named_scope("o_proj"):
        h = h + qmatmul(o.reshape(B, S, -1), p["wo"])
    return h, cache


def mlp_sub_block(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] positions that route
    moe_backend: str = "auto",
):
    """A layer's second sub-block, ``h + MLP(RMSNorm_post(h))``: the dense
    gated MLP or, keyed by the presence of ``router``, the routed experts held
    here beside the shared expert (``models/solar_open2.py`` runs it after its
    own mixers). Returns ``(h, stats)``, ``stats`` the layer's ``MoeStats``
    (None for a dense layer)."""
    B, S, H = h.shape
    with jax.named_scope("norm"):
        x = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
    if "router" not in p:
        with jax.named_scope("mlp"):
            mlp = gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"])
            return h + mlp, None
    x2 = x.reshape(B * S, H)
    with jax.named_scope("router"):
        weights, ids = moe.route_noaux_tc(
            x2, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor,
        )
    y, stats = moe.expert_mlp(
        x2, weights, ids, p["we_gate"], p["we_up"], p["we_down"],
        cfg.num_experts,
        live=None if moe_live is None else moe_live.reshape(B * S),
        layer=p.get("layer"), backend=moe_backend, held=cfg.held_experts_,
    )
    with jax.named_scope("mlp"):  # the shared expert: every token, in full
        shared = gated_mlp(x, p["ws_gate"], p["ws_up"], p["ws_down"])
    return h + y.reshape(B, S, H) + shared, stats


def _join_stats(cfg, parts):
    """The kinds' stacked stats, laid over the stage's layer slots."""
    parts = [
        zero_stats(cfg, count) if st is None else st for st, count in parts
    ]
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)


def forward_layers(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    cache: KVCache,  # k [L, B, C, 1, Dk] latents, v [L, B, C, 1, 0]
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Dense-cache path (the monolith, one-shot admission). Returns ``(h,
    cache, stats)``, ``stats`` stacked over the stage's layer slots."""
    refuse_axes(cfg, tp_axis)
    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)
    scale = softmax_scale(cfg)
    r = cfg.kv_lora_rank

    def apply(p, h, k_row, v_row, kv_pos, length):
        def attend(q_full, entry):
            with jax.named_scope("kv_write"):
                k_r = jax.lax.dynamic_update_slice(
                    k_row, entry.astype(k_row.dtype), (0, length, 0, 0)
                )
            return attention_step(
                q_full, k_r, k_r[..., :r], positions, kv_pos, length, scale
            ), k_r

        h, k_r, stats = mla_block(cfg, p, h, cos, sin, attend, moe_live)
        return h, k_r, v_row, stats

    if layer_mask is None:
        layer_mask = jnp.ones((cache.num_layers,), bool)
    parts = []
    k_all, v_all, new = cache.k, cache.v, cache
    for kind, first, count in kind_spans(layers, cfg.layer_kinds):
        h, new, stats = scan_layers(
            layers[kind], h, cache._replace(k=k_all, v=v_all), positions,
            apply, layer_mask[first:first + count], first_layer=first,
        )
        k_all, v_all = new.k, new.v
        parts.append((stats, count))
    return h, new, _join_stats(cfg, parts)


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, 1, BS, Dk] the latent pool
    v_arena: jnp.ndarray,  # [L, NB, 1, BS, 0] — holds nothing
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
    """Paged path (``models/llama.forward_layers_paged``'s contract): the
    step's latent entries land through ``paged_attention_write`` (keys
    only: a latent arena holds no values) and attention streams the
    table's blocks of the latent pool, each read ONCE (``latent_v``: the
    value is the first ``kv_lora_rank`` lanes of the key). Returns ``(h,
    k_arena, v_arena, None, None, stats)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) latent cache is not implemented"
        )
    # a chunk's rows share their columns: it writes whole blocks from its
    # first column on (llama's note)
    col0 = cols[0, 0] if prefill else None
    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)
    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )
    scale = softmax_scale(cfg)
    r = cfg.kv_lora_rank

    def apply(p, l, valid, h, k_all, v_all, ks_all, vs_all):
        def attend(q_full, entry):
            if not prefill:  # a decode step (llama's note)
                o, k_a, *_ = paged_attention_write(
                    q_full, entry, None, k_all, v_all, l, block_table, cols,
                    positions, kv_positions, valid=wv & valid, scale=scale,
                    backend=backend, latent_v=r,
                )
                return o, k_a
            k_a, _ = write_chunk_kv(
                k_all, v_all, l, block_table, col0, entry, None,
                valid=wv & valid,
            )
            return paged_prefill(
                q_full, k_a, v_all, l, block_table, positions,
                kv_positions, scale, backend=backend, walk=walk,
                latent_v=r,
            ), k_a

        live = moe_live
        if "router" in p:
            gate = jnp.asarray(wv) & valid
            live = jnp.broadcast_to(
                gate if moe_live is None else moe_live & gate, h.shape[:2]
            )
        h, k_a, stats = mla_block(cfg, p, h, cos, sin, attend, live, backend)
        return h, k_a, v_all, None, None, stats

    if layer_mask is None:
        layer_mask = jnp.ones((k_arena.shape[0],), bool)
    parts = []
    for kind, first, count in kind_spans(layers, cfg.layer_kinds):
        h, k_arena, v_arena, _, _, stats = scan_layers_paged(
            layers[kind], h, k_arena, v_arena, apply,
            layer_mask[first:first + count], first_layer=first,
        )
        parts.append((stats, count))
    return h, k_arena, v_arena, None, None, _join_stats(cfg, parts)


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
