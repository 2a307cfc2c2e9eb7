"""Pure-JAX Llama-family causal LM (Llama-2 / Llama-3 / Llama-3.2, GQA).

Replaces the reference's use of HF ``LlamaDecoderLayer`` / ``LlamaRMSNorm``
modules (``/root/reference/utils/shard_loader.py:5, 36-55``) with functional
blocks over explicit parameter pytrees. A stage's layer stack is a ``lax.scan``
over layer-stacked parameters — one compiled loop body regardless of how many
layers a pipeline stage holds — with an optional per-layer validity mask so
ragged layer splits (e.g. the reference's 6/1/25 split in
``/root/reference/send_config.py:10-34``) run under one SPMD program.

Parameter pytree (all leaves ``jnp`` arrays):

``params = {"embed": [V,H], "layers": {...each leaf stacked [L, ...]},
"final_norm": [H], "lm_head": [H,V]}``

This mirrors the reference's shard-store split — ``embedding.pth`` /
``block_{i}.pth`` / ``final_norm.pth`` / ``lm_head.pth``
(``/root/reference/utils/model_sharder.py:64-94``) — as pytree keys.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import attention_step
from ..ops.norms import layer_norm, rms_norm
from ..ops.quant import embed_rows, head_logits, out_dim, qmatmul, tied_logits
from ..ops.rope import apply_rope, rope_cos_sin
from .cache import KVCache
from .config import ModelConfig
from .stack import close_tables, run_passes, scan_layers

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization (random weights for tests/benchmarks; real weights come from
# the checkpoint converter in utils/convert.py)
# ---------------------------------------------------------------------------

def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16
) -> Params:
    H, I = cfg.hidden_size, cfg.intermediate_size
    D = cfg.head_dim_
    Nh, Nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    ks = jax.random.split(key, 7)
    L = num_layers

    def w(k, *shape):
        # Sample directly in the target dtype: a stacked fp32 intermediate for
        # a 7B-class leaf ([32, 4096, 11008] = 5.8 GB) would not fit HBM on
        # top of the already-materialized bf16 leaves.
        fan_in = shape[-2]
        return jax.random.normal(k, (L, *shape), dtype) * jnp.asarray(
            fan_in**-0.5, dtype
        )

    p = {
        "input_norm": jnp.ones((L, H), dtype),
        "wq": w(ks[0], H, Nh * D),
        "wk": w(ks[1], H, Nkv * D),
        "wv": w(ks[2], H, Nkv * D),
        "wo": w(ks[3], Nh * D, H),
        "post_norm": jnp.ones((L, H), dtype),
    }
    if cfg.num_experts:
        # sparse experts: the layer's E MLPs of width I as ONE block-sparse
        # MLP of width E·I (expert e = columns, resp. rows, e·I..(e+1)·I) and
        # the router that picks among them — see ops/moe.py
        E = cfg.num_experts
        p.update(
            router=w(jax.random.fold_in(key, 7), H, E),
            we_gate=w(ks[4], H, E * I),
            we_up=w(ks[5], H, E * I),
            # an expert's own fan-in is I, not the leaf's E·I rows
            we_down=w(ks[6], E * I, H) * jnp.asarray(E**0.5, dtype),
        )
    else:
        p.update(
            w_gate=w(ks[4], H, I), w_up=w(ks[5], H, I), w_down=w(ks[6], I, H)
        )
    if cfg.qk_norm:
        # RMSNorm gains over the whole projected q / k width (OLMoE), or
        # over one head's (``qk_norm_per_head``: KeyeVL2)
        per_head = cfg.qk_norm_per_head
        p["q_norm"] = jnp.ones((L, D if per_head else Nh * D), dtype)
        p["k_norm"] = jnp.ones((L, D if per_head else Nkv * D), dtype)
    if cfg.sparse_attn:
        # the indexer that chooses a query's keys: ``index_heads`` index
        # queries, ONE index key a token (LayerNorm with bias) and a weight
        # an index head; keyed by presence like every other leaf
        HI, DI = cfg.index_heads, cfg.index_head_dim
        ki = jax.random.split(jax.random.fold_in(key, 8), 3)
        p.update(
            wq_idx=w(ki[0], H, HI * DI), wk_idx=w(ki[1], H, DI),
            w_idx=w(ki[2], H, HI),
            k_idx_norm=jnp.ones((L, DI), dtype),
            k_idx_bias=jnp.zeros((L, DI), dtype),
        )
    if cfg.out_norms:
        # a norm on each branch's OUTPUT too (Ouro's sandwich); keyed by
        # presence like the biases below
        p["attn_out_norm"] = jnp.ones((L, H), dtype)
        p["mlp_out_norm"] = jnp.ones((L, H), dtype)
    if cfg.attention_bias:
        # qkv biases (the Qwen2-family layout: q/k/v biased, o not); presence
        # of the keys — not the flag — drives the forward path, so converted
        # checkpoints control exactly which projections carry bias
        p["bq"] = jnp.zeros((L, Nh * D), dtype)
        p["bk"] = jnp.zeros((L, Nkv * D), dtype)
        p["bv"] = jnp.zeros((L, Nkv * D), dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    embed = (jax.random.normal(k_emb, (V, H), jnp.float32) * H**-0.5).astype(dtype)
    params = {
        "embed": embed,
        "layers": init_layer_params(cfg, k_layers, cfg.num_hidden_layers, dtype),
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (H, V), jnp.float32) * H**-0.5
        ).astype(dtype)
    if cfg.passes > 1:
        # a looped stack's exit gate over the passes' closed states
        # (``stack.run_passes``): a linear map H -> 1 with a bias
        k_gate, k_bias = jax.random.split(jax.random.fold_in(key, 3))
        params["exit_gate"] = (
            jax.random.normal(k_gate, (H,), jnp.float32) * H**-0.5
        ).astype(dtype)
        params["exit_bias"] = (
            0.1 * jax.random.normal(k_bias, (1,), jnp.float32)
        ).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------

def embed(params: Params, token_ids: jnp.ndarray) -> jnp.ndarray:
    """Token embedding — the privacy boundary: requests enter the chain as
    embeddings, never raw token ids (≙ ``/root/reference/utils/node_worker.py:
    215-223`` and README privacy note). The table may be int8 row-quantized
    (``ops/quant.embed_rows``)."""
    return embed_rows(params["embed"], token_ids)


def gated_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """``(act(x W_g) ⊙ x W_u) W_d``, the activation in float32 (a deliberate
    local deviation from HF, which runs it in the model dtype: exact in the
    f32 parity tests, slightly more accurate than HF in bf16). Activation per
    family: llama / qwen2 / jamba silu, gemma gelu-tanh."""
    gate = qmatmul(x, p["w_gate"]).astype(jnp.float32)
    if cfg.hidden_act == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True)
    elif cfg.hidden_act == "silu":
        act = jax.nn.silu(gate)
    else:  # catch raw HF spellings on hand-built configs, not silently silu
        raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")
    return qmatmul(act.astype(x.dtype) * qmatmul(x, p["w_up"]), p["w_down"])


def attn_mlp_block(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attn_fn,  # (q[B,S,Nh,D], k[B,S,Nkv,D], v[B,S,Nkv,D]) -> [B,S,Nh,D];
    #   a token-selecting model's takes ``index=`` too (below)
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] bool: positions that
    #   route (a model with experts only; None = all of them)
    moe_backend: str = "auto",
    index_rope=None,  # (cos, sin) [B, S, index_head_dim] — the indexer's
    #   own rotation (a token-selecting model only)
):
    """One llama block with the attention mechanism injected — the single
    implementation behind the cached (pipeline/decode) path and the
    ring-attention (context-parallel) path.

    Head counts come from the WEIGHT shapes, not the config: under explicit
    tensor parallelism (``tp_axis`` set, megatron layout — wq/wk/wv/w_gate/
    w_up column-sharded, wo/w_down row-sharded) each device sees its local
    head slice, and the two row-parallel matmuls are completed with a psum
    over ``tp_axis``. With ``tp_axis=None`` and full weights this reduces to
    the plain single-device block.

    Returns ``(h, stats)``: ``stats`` is the layer's ``MoeStats`` for a
    model with experts and None (an empty pytree) for a dense one — the one
    convention every layer, scan, stage and ring function above this keeps,
    always as the LAST result.
    """
    B, S, H = h.shape
    D = cfg.head_dim_
    # local (possibly TP-sharded) head counts from the weight shapes, raw or
    # int8-quantized (ops/quant.py)
    Nh = out_dim(p["wq"]) // D
    Nkv = out_dim(p["wk"]) // D

    # The named scopes are words of ``obs.stepline.SCOPES``: a profiler
    # trace names every device operation of the block by them.
    with jax.named_scope("norm"):
        x = rms_norm(h, p["input_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    # Optional projection biases, keyed by PRESENCE (the Qwen2-family layout
    # biases q/k/v only — ``bq``/``bk``/``bv`` from the converter; column-
    # parallel under TP so each shard adds its slice before rope/attention)
    with jax.named_scope("qkv"):
        qx, kx, vx = (
            qmatmul(x, p["wq"]), qmatmul(x, p["wk"]), qmatmul(x, p["wv"])
        )
        if "bq" in p:
            qx = qx + p["bq"]
        if "bk" in p:
            kx = kx + p["bk"]
        if "bv" in p:
            vx = vx + p["bv"]
        if cfg.qk_norm_per_head or ("bq" not in p and "q_norm" not in p):
            # (q too where the heads are split before the norm, or where
            # nothing — no bias, no norm over the whole width — stands
            # between the dot and the head split: see below. At 16 heads of
            # 128 the whole stack of ``wq``, 403 MB, was re-laid every call
            # and a layer's slice copied again before its dot: 3.6 ms of a
            # 33.4 ms step, PERF.md PR 60)
            qx = jax.lax.optimization_barrier(qx)
        # k and v leave the projection as the dot made them. Without this
        # edge XLA folds the head split below into the two small dots: a
        # decode step's ``[B, H] @ [H, Nkv*D]`` becomes a convolution
        # windowed over the heads that wants ``wk``/``wv`` with the input
        # dim minor, and the compiled v5e program re-lays the WHOLE layer
        # stack of both (parameters: they cannot stay transposed) at the
        # top of every call (tests/test_paged_programs.py holds the compiled
        # program to it).
        kx, vx = jax.lax.optimization_barrier((kx, vx))
    if "q_norm" in p:
        # OLMoE: an RMSNorm over the WHOLE projected width of q and of k,
        # before the heads are split and rotated; KeyeVL2: over each HEAD's
        # width (the gains are one head wide); keyed by presence
        with jax.named_scope("norm"):
            if cfg.qk_norm_per_head:
                qx, kx = qx.reshape(B, S, Nh, D), kx.reshape(B, S, Nkv, D)
            qx = rms_norm(qx, p["q_norm"], cfg.rms_norm_eps, cfg.norm_offset)
            kx = rms_norm(kx, p["k_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    with jax.named_scope("rope"):
        q = apply_rope(qx.reshape(B, S, Nh, D), cos, sin)
        k = apply_rope(kx.reshape(B, S, Nkv, D), cos, sin)
        v = vx.reshape(B, S, Nkv, D)

    if "wq_idx" in p:
        # the indexer (DeepSeek-V3.2-Exp, eq. 1), off the same normed input:
        # index queries and the token's ONE index key, both rotated over
        # their whole width, and a weight an index head. The attention
        # mechanism scores, selects and attends (``ops/paged_attention``)
        HI, DI = cfg.index_heads, cfg.index_head_dim
        with jax.named_scope("indexer"):
            # (held as the dots made them, like k and v above: a head split
            # folded into the dot re-lays the weight's whole layer stack)
            qi, ki, wi = jax.lax.optimization_barrier((
                qmatmul(x, p["wq_idx"]), qmatmul(x, p["wk_idx"]),
                qmatmul(x, p["w_idx"]),
            ))
            ki = layer_norm(
                ki, p["k_idx_norm"], p["k_idx_bias"], cfg.rms_norm_eps
            )
            qi = apply_rope(qi.reshape(B, S, HI, DI), *index_rope)
            ki = apply_rope(ki.reshape(B, S, 1, DI), *index_rope)
            wi = wi.astype(jnp.float32) * (HI * DI) ** -0.5
        attn = attn_fn(q, k, v, index=(qi, ki, wi))
    else:
        attn = attn_fn(q, k, v)
    with jax.named_scope("o_proj"):
        attn_out = qmatmul(attn.reshape(B, S, Nh * D), p["wo"])
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        if "bo" in p:  # row-parallel bias: added ONCE, after the psum
            attn_out = attn_out + p["bo"]
        if "attn_out_norm" in p:
            # a norm on the branch's OUTPUT (Ouro), keyed by presence: over
            # the whole width, so after the psum
            with jax.named_scope("norm"):
                attn_out = rms_norm(
                    attn_out, p["attn_out_norm"], cfg.rms_norm_eps,
                    cfg.norm_offset,
                )
        h = h + attn_out

    with jax.named_scope("norm"):
        x = rms_norm(h, p["post_norm"], cfg.rms_norm_eps, cfg.norm_offset)
    if "router" in p:
        # sparse experts, keyed by the presence of the leaves: the router
        # picks ``num_experts_per_tok`` of the layer's experts per position
        # and only those are read and computed (ops/moe.py). ``layer`` is
        # there when the scan handed the expert leaves over as whole
        # layer-stacked arrays (models/stack.py) for the kernel to index.
        from ..ops import moe

        if tp_axis is not None:
            raise NotImplementedError(
                "tensor parallelism over a model with experts is not "
                "implemented (an expert axis on the mesh is a later step)"
            )
        x2 = x.reshape(B * S, H)
        with jax.named_scope("router"):
            weights, ids = moe.route(
                x2, p["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob
            )
        y, stats = moe.expert_mlp(
            x2, weights, ids, p["we_gate"], p["we_up"], p["we_down"],
            cfg.num_experts,
            live=None if moe_live is None else moe_live.reshape(B * S),
            layer=p.get("layer"), backend=moe_backend,
        )
        return h + y.reshape(B, S, H), stats
    with jax.named_scope("mlp"):
        mlp = gated_mlp(cfg, p, x)
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
        if "mlp_out_norm" in p:
            with jax.named_scope("norm"):
                mlp = rms_norm(
                    mlp, p["mlp_out_norm"], cfg.rms_norm_eps, cfg.norm_offset
                )
        return h + mlp, None


def decoder_layer(
    cfg: ModelConfig,
    p: Params,  # un-stacked single-layer params
    h: jnp.ndarray,  # [B, S, H]
    k_row: jnp.ndarray,  # [B, C, Nkv, D] cache row for this layer
    v_row: jnp.ndarray,
    cos: jnp.ndarray,  # [B, S, D]
    sin: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S] absolute query positions
    kv_positions: jnp.ndarray,  # [B, C] per-slot key positions (post-write)
    length: jnp.ndarray,  # scalar int32: shared write offset for this step
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,
):
    """Returns ``(h, k_row, v_row, stats)`` (``attn_mlp_block``)."""
    if cfg.sparse_attn:
        raise NotImplementedError(
            "a token-selecting model (an indexer beside the attention) runs "
            "over the paged arenas only: a dense cache row holds no index keys"
        )
    rows = {}

    def attn_fn(q, k, v):
        with jax.named_scope("kv_write"):
            k_r = jax.lax.dynamic_update_slice(
                k_row, k.astype(k_row.dtype), (0, length, 0, 0)
            )
            v_r = jax.lax.dynamic_update_slice(
                v_row, v.astype(v_row.dtype), (0, length, 0, 0)
            )
        rows["k"], rows["v"] = k_r, v_r
        return attention_step(q, k_r, v_r, positions, kv_positions, length)

    h, stats = attn_mlp_block(
        cfg, p, h, cos, sin, attn_fn, tp_axis, moe_live=moe_live
    )
    return h, rows["k"], rows["v"], stats


def paged_decoder_layer(
    cfg: ModelConfig,
    p: Params,  # un-stacked single-layer params
    layer: jnp.ndarray,  # scalar int32 — this layer's index in the stacks
    valid: jnp.ndarray,  # scalar bool — masked (padding) layer gate
    h: jnp.ndarray,  # [B, S, H]
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] the WHOLE stacked pool; a
    #   token-selecting model's (``cfg.sparse_attn``): the pair ``(k_arena,
    #   idx_arena [L, NB, 1, BS, cfg.index_cache_dim])``, and so it comes back
    v_arena: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, T]
    cols: jnp.ndarray,  # [B, S] logical columns of this step's entries
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S] absolute query positions
    kv_positions: jnp.ndarray,  # [B, T*BS] logical-window key positions
    write_valid,  # scalar bool — ring-inactive microsteps gate writes
    tp_axis: Optional[str] = None,
    backend: str = "auto",
    k_scale: Optional[jnp.ndarray] = None,  # [L, NB, Nkv] — quantized arena
    v_scale: Optional[jnp.ndarray] = None,
    prefill: bool = False,  # static: chunk-shaped queries — attend via
    #   the query-tiled paged_prefill kernel instead of the decode one
    walk=None,  # the prefill kernel's work list (``prefill_walk``)
    cp_axis: Optional[str] = None,  # context-parallel combine axis
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] positions that route
    index_rope=None,  # a token-selecting model: the indexer's (cos, sin)
):
    """Decode-path layer over the pooled arena: the step's fresh KV lands
    in the blocks the table names of layer ``layer`` of the stacked pool
    (``paged_attention_write``: stored by the attention kernel itself, the
    arena left in place, where the step's statics allow, the block-indexed
    scatter otherwise) and attention streams exactly those blocks out of that
    layer (``ops/paged_attention``) — the logical window is never
    materialized and the layer is never sliced out of the stack. A quantized arena (``k_scale``/``v_scale``)
    quantizes the fresh entries at insert and dequantizes inside the
    attention op (fused into the kernel's per-block DMA loop). With
    ``prefill`` the attention dispatch is ``paged_prefill`` — the
    flash-style chunked-prefill kernel whose query axis is the whole
    chunk (``walk``: what the chunk's real queries have to walk, built
    once for all layers) and the write is ``write_chunk_kv`` (whole-block
    tiles from the chunk's first column on, where the chunk is whole blocks);
    write-then-attend order is identical, so intra-chunk causality falls out
    of the position masking either way.

    ``cp_axis`` (context-parallel serving, ``serve(cp=N)``): the arena
    this layer sees is ONE SHARD of the pooled blocks and the table maps
    only locally-owned columns (unowned → the shard's trash block, which
    absorbs this step's unowned writes). Attention then emits partial
    ``(acc, m, l)`` softmax statistics over the local blocks and
    ``combine_attn_stats`` reduces them across ``cp_axis`` with the
    flash recurrence — the combined output equals attention over the
    full window, so everything downstream stays shard-replicated.

    A token-selecting model (``cfg.sparse_attn``): the layer's index key
    lands in the index arena beside K and V, under the same table, and the
    attention reads the ``cfg.index_topk`` keys the indexer scores highest
    (``select=``: a decode step masks the others out of the decode kernel's
    walk by their key positions; a chunk out of the dense product)."""
    from ..ops.paged_attention import (
        Selection, combine_attn_stats, paged_attention_write, paged_prefill,
        write_chunk_kv, write_index_keys,
    )

    out = {}
    cp = cp_axis is not None
    idx_arena = None
    if cfg.sparse_attn:
        if cp or tp_axis is not None or k_scale is not None:
            raise NotImplementedError(
                "a token-selecting model under tp / cp or over a quantized "
                "arena is not implemented (the index arena is not carried)"
            )
        k_arena, idx_arena = k_arena
    # a chunk's rows share their columns: it writes whole blocks from its
    # first column on
    col0 = cols[0, 0] if prefill else None

    def attn_fn(q, k, v, index=None):
        gate = write_valid & valid
        select = None
        if index is not None:
            qi, ki, wi = index
            out["idx"] = write_index_keys(
                idx_arena, layer, block_table, col0 if prefill else cols, ki,
                valid=gate, backend=backend,
            )
            select = Selection(qi, wi, out["idx"], cfg.index_topk)
        if prefill:
            kv = write_chunk_kv(
                k_arena, v_arena, layer, block_table, col0, k, v,
                valid=gate, k_scale=k_scale, v_scale=v_scale,
            )
            out["kv"] = kv if k_scale is not None else (*kv, None, None)
            k_a, v_a, ks, vs = out["kv"]
            o = paged_prefill(
                q, k_a, v_a, layer, block_table, positions, kv_positions,
                backend=backend, k_scale=ks, v_scale=vs, stats=cp,
                walk=walk, select=select,
            )
        else:
            # a decode step: a row's entries at its own columns, written
            # and attended by one op (which picks the write's form)
            o, *out["kv"] = paged_attention_write(
                q, k, v, k_arena, v_arena, layer, block_table, cols,
                positions, kv_positions, valid=gate, backend=backend,
                k_scale=k_scale, v_scale=v_scale, stats=cp, select=select,
            )
        if cp:
            return combine_attn_stats(*o, cp_axis).astype(q.dtype)
        return o

    if "router" in p:
        # a masked layer and a ring-inactive microstep route nowhere: their
        # result is discarded, so no expert is read or counted for them
        gate = jnp.asarray(write_valid) & valid
        moe_live = jnp.broadcast_to(
            gate if moe_live is None else moe_live & gate, h.shape[:2]
        )
    h, stats = attn_mlp_block(
        cfg, p, h, cos, sin, attn_fn, tp_axis, moe_live=moe_live,
        moe_backend=backend, index_rope=index_rope,
    )
    k_new, *rest = out["kv"]
    if idx_arena is not None:
        k_new = (k_new, out["idx"])
    return (h, k_new, *rest, stats)


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # stacked [L, ...]
    h: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D]
    v_arena: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, T]
    cols: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    positions: jnp.ndarray,  # [B, S]
    layer_mask: Optional[jnp.ndarray] = None,
    write_valid=True,
    tp_axis: Optional[str] = None,
    backend: str = "auto",
    k_scale: Optional[jnp.ndarray] = None,  # [L, NB, Nkv] (quantized)
    v_scale: Optional[jnp.ndarray] = None,
    prefill: bool = False,  # static: chunked-prefill traversal (see
    #   paged_decoder_layer) — queries are a whole prompt chunk
    walk=None,  # the prefill kernel's work list (``prefill_walk``)
    cp_axis: Optional[str] = None,  # context-parallel combine axis (the
    #   arena/table are per-shard; see paged_decoder_layer)
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] bool — a model with
    #   experts: the positions that route (dead rows and pads route nowhere)
    close=None,  # a looped stack: what closes a pass (``stack.close_tables``)
):
    """Paged counterpart of ``forward_layers`` for the serve decode path:
    scans the layer stack over the pooled arena (``stack.scan_layers_paged``)
    instead of a materialized per-row window. Returns ``(h, k_arena,
    v_arena, k_scale, v_scale, stats)`` — scale outputs are None
    unquantized; kpos bookkeeping stays with the caller. ``stats`` is None
    for a dense model, else its ``MoeStats`` stacked over layers (``[L, E]``
    tokens per expert, ``[L]`` distinct experts read; zero at masked
    layers). A looped stack (``cfg.passes`` > 1) runs the scan once a pass
    over that pass's arena slots (``stack.run_passes``): ``h`` is then the
    closed state the exit gate chose and ``stats`` the pass it came from,
    ``[B, S]`` int32."""
    from .stack import scan_layers_paged

    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)
        index_rope = None
        if cfg.sparse_attn:
            # the indexer rotates its 64-wide vectors whole, at the same base
            index_rope = rope_cos_sin(
                positions, cfg, dtype=jnp.float32, dim=cfg.index_head_dim
            )
    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )

    def apply(p, l, valid, h, k_all, v_all, ks_all, vs_all):
        return paged_decoder_layer(
            cfg, p, l, valid, h, k_all, v_all, block_table, cols, cos, sin,
            positions, kv_positions, wv, tp_axis, backend,
            k_scale=ks_all, v_scale=vs_all, prefill=prefill, walk=walk,
            cp_axis=cp_axis, moe_live=moe_live, index_rope=index_rope,
        )

    if cfg.passes == 1:
        return scan_layers_paged(
            layers, h, k_arena, v_arena, apply, layer_mask,
            k_scale=k_scale, v_scale=v_scale,
        )
    layer_mask, L = _stack_mask(layers, layer_mask)

    def one_pass(first, h, arenas):
        h, *arenas, _ = scan_layers_paged(
            layers, h, *arenas[:2], apply, layer_mask,
            k_scale=arenas[2], v_scale=arenas[3], first_layer=first,
        )
        return h, tuple(arenas)

    h, arenas, exit_pass = run_passes(
        cfg, close, h, (k_arena, v_arena, k_scale, v_scale), one_pass, L
    )
    return (h, *arenas, exit_pass)


def _stack_mask(layers: Params, layer_mask):
    """``(layer_mask, L)`` of a stack of ``L`` layers (all of them real where
    no mask came)."""
    if layer_mask is None:
        L = jax.tree.leaves(layers)[0].shape[0]
        return jnp.ones((L,), bool), L
    return layer_mask, layer_mask.shape[0]


def forward_layers(
    cfg: ModelConfig,
    layers: Params,  # stacked [L, ...]
    h: jnp.ndarray,
    cache: KVCache,
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,  # [L] bool — False = pass-through
    tp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] bool (experts only):
    #   the positions that route; None = all of them
    close=None,  # a looped stack: what closes a pass (``stack.close_tables``)
):
    """Run ``h`` through a stack of decoder layers via ``lax.scan``.

    ``layer_mask`` enables ragged pipeline stages: masked-out layers leave the
    hidden state and their cache rows untouched, so every stage can scan the
    same (padded) layer count in one SPMD program (SURVEY.md §7 "uneven layer
    splits"). ``tp_axis`` turns on explicit megatron TP inside every layer
    (weights and KV cache must carry the matching local head slices).
    Returns ``(h, cache, stats)``; ``stats`` is None for a dense model,
    else the ``MoeStats`` stacked over layers. A looped stack (``cfg.passes``
    > 1) runs the scan once a pass over that pass's cache slots, every pass
    at the step's one write offset (``stack.run_passes``): ``h`` is then the
    closed state the exit gate chose and ``stats`` the pass it came from,
    ``[B, S]`` int32.
    """
    with jax.named_scope("rope"):
        cos, sin = rope_cos_sin(positions, cfg, dtype=jnp.float32)

    def apply(p, h, k_row, v_row, kv_pos, length):
        return decoder_layer(
            cfg, p, h, k_row, v_row, cos, sin, positions, kv_pos, length,
            tp_axis, moe_live=moe_live,
        )

    if cfg.passes == 1:
        return scan_layers(layers, h, cache, positions, apply, layer_mask)
    layer_mask, L = _stack_mask(layers, layer_mask)

    def one_pass(first, h, kv):
        # every pass writes at the step's offset: the cache as it came in
        h, new, _ = scan_layers(
            layers, h, cache._replace(k=kv[0], v=kv[1]), positions, apply,
            layer_mask, first_layer=first,
        )
        return h, (new.k, new.v, new.pos, new.length)

    h, (k, v, pos, length), exit_pass = run_passes(
        cfg, close, h,
        (cache.k, cache.v, cache.pos, cache.length + h.shape[1]), one_pass, L,
    )
    return h, KVCache(k=k, v=v, pos=pos, length=length), exit_pass


def final_logits(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    """Final norm + lm_head (≙ the reference's last-node role,
    ``/root/reference/utils/node_worker.py:155-164, 260-265``).

    Tied checkpoints carry no ``lm_head`` array — the projection contracts
    against the embedding table directly (XLA folds the transpose into the
    matmul; no duplicate vocab×hidden buffer in HBM)."""
    if cfg.passes == 1:  # (a looped stack's last pass is closed already)
        h = rms_norm(
            h, params["final_norm"], cfg.rms_norm_eps, cfg.norm_offset
        )
    if "lm_head" in params:
        return head_logits(h, params["lm_head"])
    return tied_logits(h, params["embed"])


def forward(
    cfg: ModelConfig,
    params: Params,
    token_ids: jnp.ndarray,  # [B, S]
    cache: KVCache,
    positions: jnp.ndarray,  # [B, S]
) -> tuple[jnp.ndarray, KVCache]:
    """Full-model step: embed → layers → logits. The monolithic oracle path
    (≙ ``/root/reference/inference.py`` and
    ``utils/node_profiler.py:1238-1331``)."""
    h = embed(params, token_ids)
    if cfg.embed_multiplier != 1.0:  # gemma: hidden scaled by sqrt(H)
        h = h * jnp.asarray(cfg.embed_multiplier, h.dtype)
    h, cache, _ = forward_layers(
        cfg, params["layers"], h, cache, positions,
        close=close_tables(cfg, params),
    )
    return final_logits(cfg, params, h), cache
