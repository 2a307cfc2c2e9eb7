"""Pure-JAX ``mimo_v2`` causal LM (the language model of MiMo-V2-Flash /
MiMo-V2.5): WINDOW and FULL attention in one stack, each kind of layer with
its own KV state.

**Layers of several kinds, interleaved.** Layer ``l`` attends a sliding
window (``cfg.layer_attn[l]`` 1) or the whole context (0), and its
feed-forward is the routed experts (``cfg.layer_moe[l]`` 1) or a dense MLP
(0): the kinds ``dense_full``, ``dense_swa``, ``moe_full``, ``moe_swa``
(``cfg.layer_kinds``). ``params["layers"] = {kind: {leaf: [L_kind, ...]}}``,
one stack per kind in layer order, as ``deepseek_v3`` has it — but here the
kinds ALTERNATE down the model (five window layers, then a full one), so a
stage runs its layers as RUNS of one kind in model order (``stage_runs``):
each run one ``lax.scan`` over a range of its kind's stack, the weights read
where they lie. Every stage of a ring must hold the same sequence of kinds.

**Attention.** One fused projection ``wqkv [H, Hq·Dk + Hkv·Dk + Hkv·Dv]``
(``Dk = cfg.head_dim`` 192, ``Dv = cfg.v_head_dim`` 128; ``Hkv`` 4 in full
layers, ``cfg.swa_num_key_value_heads`` 8 in window layers). Rotary on the
first ``cfg.rope_dim`` dims of q and k, by halves, at ``cfg.rope_theta``
(full) or ``cfg.swa_rope_theta`` (window); the rest pass. ``v`` is
multiplied by ``cfg.attention_value_scale`` as it leaves the projection
(what the cache holds is the scaled value). Scores ``q·k / sqrt(Dk)``,
causal; a window layer keeps keys ``i - window < j <= i``. Where the
configuration says so (``cfg.swa_sink`` / ``cfg.full_sink``) a learned scalar
per query head (``sink [Hq]``, float32) joins the row's logits before the
softmax and its column is dropped after: ``exp(s_h)`` in the denominator,
nothing in the output. Output ``[Hq, Dv]`` through ``wo [Hq·Dv, H]``.

**The KV state.** A key is stored padded to whole 128-lane tiles
(``cfg.cache_k_dim`` 256: q is padded with zeros alike, so the padded lanes
add nothing to a score), a value as it is. Dense rows hold
``cfg.cache_heads`` heads (the most any kind has; a full layer uses the
first ``Hkv``). The PAGED state is one arena and one block table per kind of
attention: ``k_arena = (full [L_full, NB, 4, BS, 256], swa [L_swa, NB', 8,
BS, 256])``, likewise ``v_arena`` (128 wide), ``block_table`` and the
prefill ``walk``; a layer's index in its arena is its order among the
stage's layers of that attention kind. A window layer's table names only
the blocks the window can still reach (``runtime/server.py`` gives the ones
behind it back to the pool); both paged kernels take the window as a lower
bound on their walk.

**Feed-forward.** Dense: SwiGLU of ``intermediate_size``. Experts:
``ops/moe.route_noaux_tc`` over all ``cfg.num_experts`` in ONE group (no
group step), the chosen weights normalised, scale 1; the HELD experts' terms
only (``ops/moe.expert_mlp(held=)``); no shared expert.

Refused by name: tensor and context parallelism, a quantized (int8/fp8)
arena, a stage whose kinds differ from the model's first stage's.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.attention import cached_attention
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from ..ops.rope import apply_rope, rope_cos_sin
from .cache import KVCache
from .config import ModelConfig
from . import stack
from .deepseek_v3 import gated_mlp
from .family import refuse_axes
from .llama import embed, final_logits  # noqa: F401  (the family's own)
from .stack import place_stats, scan_layers, scan_run

Params = dict[str, Any]


def attn_of(kind: str) -> str:
    """``"full"`` or ``"swa"``: the attention of a layer kind."""
    return kind.rsplit("_", 1)[1]


def has_sink(cfg: ModelConfig, attn: str) -> bool:
    return cfg.swa_sink if attn == "swa" else cfg.full_sink


#: ``stack.stage_runs``, each run with its kind's attention and its first
#: layer in that attention's arena
stage_runs = functools.partial(stack.stage_runs, attn_of=attn_of)


def attn_layer_counts(cfg: ModelConfig, layers: Params, axis: int = 1) -> dict:
    """``{"full": n, "swa": n}``: a stage's layers of each attention kind —
    the layer dims of the two arenas — from its tree: ``axis`` 1 of the
    stage-stacked ``[S, P_kind, ...]`` leaves, 0 inside a stage program."""
    out = {"full": 0, "swa": 0}
    for kind in dict.fromkeys(cfg.layer_kinds):
        out[attn_of(kind)] += jax.tree.leaves(layers[kind])[0].shape[axis]
    return out


# ---------------------------------------------------------------------------
# Initialization (random weights for tests; real ones come from convert.py)
# ---------------------------------------------------------------------------

def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16,
    kind: Optional[str] = None,
) -> Params:
    """``num_layers`` stacked layers of ``kind``; without a kind, that many
    of EACH kind the model has, as the per-kind tree."""
    if kind is None:
        return {
            k: init_layer_params(
                cfg, jax.random.fold_in(key, i), num_layers, dtype, k
            )
            for i, k in enumerate(dict.fromkeys(cfg.layer_kinds))
        }
    H, Hq = cfg.hidden_size, cfg.num_attention_heads
    Dk, Dv = cfg.head_dim_, cfg.v_head_dim
    attn = attn_of(kind)
    Hkv = cfg.kv_heads_of(attn)
    L = num_layers
    ks = iter(jax.random.split(key, 12))

    def w(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            fan_in ** -0.5, dtype
        )

    p = {
        "input_norm": jnp.ones((L, H), dtype),
        "wqkv": w(H, Hq * Dk + Hkv * Dk + Hkv * Dv),
        "wo": w(Hq * Dv, H),
        "post_norm": jnp.ones((L, H), dtype),
    }
    if has_sink(cfg, attn):
        p["sink"] = jax.random.normal(next(ks), (L, Hq), jnp.float32)
    if kind.startswith("dense"):
        I = cfg.intermediate_size
        p.update(w_gate=w(H, I), w_up=w(H, I), w_down=w(I, H))
        return p
    E, F, held = cfg.num_experts, cfg.moe_intermediate_size, cfg.experts_held_
    p.update(
        router=w(H, E),
        router_bias=0.1 * jax.random.normal(next(ks), (L, E), jnp.float32),
        we_gate=w(H, held * F), we_up=w(H, held * F),
        we_down=w(held * F, H, fan_in=F),
    )
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    kinds = cfg.layer_kinds
    return {
        "embed": (
            jax.random.normal(k_emb, (V, H), jnp.float32) * H ** -0.5
        ).astype(dtype),
        "layers": {
            kind: init_layer_params(
                cfg, jax.random.fold_in(k_layers, i), kinds.count(kind),
                dtype, kind,
            )
            for i, kind in enumerate(dict.fromkeys(kinds))
        },
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": (
            jax.random.normal(k_head, (H, V), jnp.float32) * H ** -0.5
        ).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------

def _pad_lanes(x, width):
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((*x.shape[:-1], pad), x.dtype)], axis=-1
    )


def layer_block(
    cfg: ModelConfig,
    p: Params,
    attn: str,  # "full" | "swa"
    h: jnp.ndarray,  # [B, S, H]
    cos: jnp.ndarray,  # [B, S, rope_dim] at this kind's base
    sin: jnp.ndarray,
    attend,  # (q [B,S,Hq,Dkp], k [B,S,Hkv,Dkp], v [B,S,Hkv,Dv]) -> (o
    #   [B,S,Hq,Dv], cache): writes the step's entries, attends the cache
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] positions that route
    moe_backend: str = "auto",
):
    """One layer of attention kind ``attn``, dense or expert (keyed by the
    presence of ``router``), with the cache mechanism injected. Returns
    ``(h, cache, stats)`` (``stats`` None for a dense layer). The named
    scopes are words of ``obs.stepline.SCOPES``; window and full attention
    both run under ``attn``, the sink inside it."""
    B, S, H = h.shape
    Hq, Hkv = cfg.num_attention_heads, cfg.kv_heads_of(attn)
    Dk, Dv, Dkp = cfg.head_dim_, cfg.v_head_dim, cfg.cache_k_dim
    eps = cfg.rms_norm_eps

    with jax.named_scope("norm"):
        x = rms_norm(h, p["input_norm"], eps)
    with jax.named_scope("qkv"):
        # the projection leaves as the dot made it (models/llama.py, PR 31;
        # models/deepseek_v3.py): without this edge XLA folds the column
        # split below into the dot and re-lays the WHOLE layer stack of the
        # fused weight at the top of every call
        qkv = jax.lax.optimization_barrier(qmatmul(x, p["wqkv"]))
        nq, nk = Hq * Dk, Hkv * Dk
        q = qkv[..., :nq].reshape(B, S, Hq, Dk)
        k = qkv[..., nq:nq + nk].reshape(B, S, Hkv, Dk)
        v = qkv[..., nq + nk:].reshape(B, S, Hkv, Dv)
        if cfg.attention_value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(
                v.dtype
            )
    with jax.named_scope("rope"):
        q = _pad_lanes(apply_rope(q, cos, sin), Dkp)
        k = _pad_lanes(apply_rope(k, cos, sin), Dkp)
    o, cache = attend(q, k, v)
    with jax.named_scope("o_proj"):
        h = h + qmatmul(o.reshape(B, S, Hq * Dv), p["wo"])

    with jax.named_scope("norm"):
        x = rms_norm(h, p["post_norm"], eps)
    if "router" not in p:
        with jax.named_scope("mlp"):
            return h + gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"]), cache, None
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
    return h + y.reshape(B, S, H), cache, stats


def _rope_tables(cfg: ModelConfig, positions):
    """``{attn: (cos, sin)}``: one pair per attention kind's base."""
    with jax.named_scope("rope"):
        return {
            "full": rope_cos_sin(positions, cfg, dtype=jnp.float32),
            "swa": rope_cos_sin(
                positions, cfg, dtype=jnp.float32,
                theta=cfg.swa_rope_theta or cfg.rope_theta,
            ),
        }


def _window(cfg: ModelConfig, attn: str) -> int:
    return cfg.sliding_window if attn == "swa" else 0


def forward_layers(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    cache: KVCache,  # k [L, B, C, cache_heads, 256], v [..., 128]; layer
    #   slot = the stage's kind-major slot (``Run.slot_first``)
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Dense-cache path (the monolith). A window layer masks its window over
    the whole row (nothing is freed in a dense cache); attention is the XLA
    form (``ops/attention.cached_attention``). Returns ``(h, cache,
    stats)``."""
    refuse_axes(cfg, tp_axis)
    rope = _rope_tables(cfg, positions)
    scale = cfg.head_dim_ ** -0.5
    if layer_mask is None:
        layer_mask = jnp.ones((cache.num_layers,), bool)
    k_all, v_all, new, parts = cache.k, cache.v, cache, []
    for run in stage_runs(cfg, layers):
        Hkv = cfg.kv_heads_of(run.attn)
        cos, sin = rope[run.attn]

        def apply(p, h, k_row, v_row, kv_pos, length, run=run, Hkv=Hkv,
                  cos=cos, sin=sin):
            def attend(q, k, v):
                with jax.named_scope("kv_write"):
                    k_r = jax.lax.dynamic_update_slice(
                        k_row, k.astype(k_row.dtype), (0, length, 0, 0)
                    )
                    v_r = jax.lax.dynamic_update_slice(
                        v_row, v.astype(v_row.dtype), (0, length, 0, 0)
                    )
                with jax.named_scope("attn"):
                    o = cached_attention(
                        q, k_r[:, :, :Hkv], v_r[:, :, :Hkv], positions,
                        kv_pos, scale, window=_window(cfg, run.attn),
                        sink=p.get("sink"),
                    )
                return o, (k_r, v_r)

            h, (k_r, v_r), stats = layer_block(
                cfg, p, run.attn, h, cos, sin, attend, moe_live
            )
            return h, k_r, v_r, stats

        sub = jax.tree.map(
            lambda a: a[run.stack_first:run.stack_first + run.count],
            layers[run.kind],
        )
        h, new, stats = scan_layers(
            sub, h, cache._replace(k=k_all, v=v_all), positions, apply,
            layer_mask[run.slot_first:run.slot_first + run.count],
            first_layer=run.slot_first,
        )
        k_all, v_all = new.k, new.v
        parts.append((run, stats))
    return h, new, place_stats(cfg, layer_mask.shape[0], parts)


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    k_arena,  # (full [L_full, NB, Hkv, BS, Dkp], swa [L_swa, NB', Hkv', BS, Dkp])
    v_arena,  # likewise, Dv wide
    block_table,  # (full [B, T], swa [B, T])
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
    walk=None,  # (full PrefillWalk, swa PrefillWalk) or None
    cp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Paged path (``models/llama.forward_layers_paged``'s contract, the
    arena, the table and the walk each a PAIR, full then window): a layer
    writes the step's entries into ITS kind's arena through ITS kind's table
    and attends it; a window layer hands the kernels its window (a lower
    bound on the walk, a mask at the edge) and its sink. Returns ``(h,
    k_arena, v_arena, None, None, stats)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) arena under mimo_v2 is not implemented"
        )
    # a chunk's rows share their columns: it writes whole blocks from its
    # first column on (llama's note)
    col0 = cols[0, 0] if prefill else None
    rope = _rope_tables(cfg, positions)
    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )
    scale = cfg.head_dim_ ** -0.5
    which = {"full": 0, "swa": 1}
    walks = (None, None) if walk is None else walk
    n_slots = sum(attn_layer_counts(cfg, layers, axis=0).values())
    if layer_mask is None:
        layer_mask = jnp.ones((n_slots,), bool)
    arenas = [(k_arena[0], v_arena[0]), (k_arena[1], v_arena[1])]
    parts = []
    for run in stage_runs(cfg, layers):
        a = which[run.attn]
        tbl, wk = block_table[a], walks[a]
        cos, sin = rope[run.attn]
        win = _window(cfg, run.attn)

        def apply(p, i, valid, carry, run=run, tbl=tbl, wk=wk, cos=cos,
                  sin=sin, win=win):
            h, k_all, v_all = carry
            l = i + run.arena_first  # the layer's slot in its kind's arena

            def attend(q, k, v):
                kw = {"window": win, "sink": p.get("sink")}
                if not prefill:  # a decode step (llama's note)
                    o, k_a, v_a, *_ = paged_attention_write(
                        q, k, v, k_all, v_all, l, tbl, cols, positions,
                        kv_positions, valid=wv & valid, scale=scale,
                        backend=backend, **kw,
                    )
                    return o, (k_a, v_a)
                k_a, v_a = write_chunk_kv(
                    k_all, v_all, l, tbl, col0, k, v, valid=wv & valid,
                )
                o = paged_prefill(
                    q, k_a, v_a, l, tbl, positions, kv_positions,
                    scale, backend=backend, walk=wk, **kw,
                )
                return o, (k_a, v_a)

            live = moe_live
            if "router" in p:
                gate = jnp.asarray(wv) & valid
                live = jnp.broadcast_to(
                    gate if moe_live is None else moe_live & gate, h.shape[:2]
                )
            h_new, (k_a, v_a), stats = layer_block(
                cfg, p, run.attn, h, cos, sin, attend, live, backend
            )
            return (jnp.where(valid, h_new, h), k_a, v_a), stats

        (h, k_a, v_a), stats = scan_run(
            run, layers[run.kind],
            layer_mask[run.slot_first:run.slot_first + run.count],
            (h, *arenas[a]), apply,
        )
        arenas[a] = (k_a, v_a)
        parts.append((run, stats))
    return (
        h, (arenas[0][0], arenas[1][0]), (arenas[0][1], arenas[1][1]),
        None, None, place_stats(cfg, n_slots, parts),
    )


def prefill_walks(cfg: ModelConfig, block_table, positions, kv_positions,
                  nlive, stage_layers):
    """The chunked-prefill kernel's work lists, one per kind of attention
    (the window's has a lower bound), and what they count over the stage's
    layer calls: ``[cells walked, cells of the tables' whole width]``."""
    from ..ops.paged_attention import prefill_walk

    counts = attn_layer_counts(cfg, stage_layers, axis=0)
    walks, total = [], jnp.zeros((2,), jnp.int32)
    for a, attn in enumerate(("full", "swa")):
        w = prefill_walk(
            block_table[a], positions, kv_positions, nlive,
            q_heads=cfg.num_attention_heads,
            kv_heads=cfg.kv_heads_of(attn), window=_window(cfg, attn),
        )
        walks.append(w)
        total = total + counts[attn] * jnp.stack(
            [w.steps, w.run_of.shape[0] - 1]
        ).astype(jnp.int32)
    return tuple(walks), total


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
